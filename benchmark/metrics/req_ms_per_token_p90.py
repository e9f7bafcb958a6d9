"""Serve entry: the tail of the end-to-end quantity: (completion - due) /
tokens generated, 90th percentile over every request due in the window."""
from benchmark.harness import window


def read(ctx):
    values = ctx["result"].get("per_token_ms")
    return window.percentile(values, 90) if values else None
