"""Process start-up: seconds JAX spent inside ``backend_compile`` during
set-up (a persistent-cache hit costs only its retrieval). Source: JAX's own
``backend_compile_duration`` events (a program counter)."""


def read(ctx):
    return ctx["setup_compile_s"]
