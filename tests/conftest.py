"""Test configuration: run the suite on a virtual 8-device CPU mesh.

Mirrors the reference's multi-device-without-hardware strategy (SURVEY.md §4:
cpu(0)/cpu(1) contexts, faked device lists) using
--xla_force_host_platform_device_count=8, with JAX_PLATFORMS=cpu set
before jax is imported (jax reads both from the environment).
"""
import os
import time

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

# flight-recorder post-mortems (docs/observability.md) default to the
# CWD in production; a test run triggers dozens of deliberate failure
# paths and must not litter the repo root with mxtpu_flight.json
if "MXTPU_FLIGHT_RECORDER_PATH" not in os.environ:
    import tempfile
    os.environ["MXTPU_FLIGHT_RECORDER_PATH"] = os.path.join(
        tempfile.mkdtemp(prefix="mxtpu_flight_"), "mxtpu_flight.json")

import jax  # noqa: E402
import pytest  # noqa: E402


# -- tier-1 wall-clock budget (docs/perf.md "Host off the critical path") ---
#
# The tier-1 suite runs under a hard 870s timeout (ROADMAP.md) and has
# already crept into it once. The pipelined-dispatch / async-checkpoint
# tests are contractually SLEEP-FREE (event-paced, fault-injected — never
# time.sleep waits); a regression that reintroduces real waiting fails at
# the offending test instead of silently re-inflating the suite.

_PIPELINE_TEST_CAP = float(os.environ.get("MXTPU_PIPELINE_TEST_CAP", "90"))
_T1_BUDGET = float(os.environ.get("MXTPU_T1_BUDGET", "870"))


@pytest.fixture(autouse=True)
def _tracecheck_transfer_guard(request):
    """``tracecheck``-marked tests run under ``jax.transfer_guard
    ("disallow")`` (docs/static_analysis.md "Transfer-guard interplay"):
    the runtime complement of the static host-sync lint. Explicit
    transfers (``jnp.asarray``, ``device_put``, the packed StepMetrics
    readback) stay legal; an IMPLICIT transfer inside the fused-dispatch
    hot loop — a numpy array leaking into a jit call, a Python scalar
    index forcing an H2D — raises immediately, naming the callsite,
    instead of silently serializing every dispatch."""
    if request.node.get_closest_marker("tracecheck") is None:
        yield
        return
    with jax.transfer_guard("disallow"):
        yield


@pytest.fixture(autouse=True)
def _pipeline_wall_clock_cap(request):
    """Per-test wall-clock ceiling for ``pipeline``-marked tests."""
    if request.node.get_closest_marker("pipeline") is None:
        yield
        return
    t0 = time.perf_counter()
    yield
    dt = time.perf_counter() - t0
    if dt >= _PIPELINE_TEST_CAP:
        pytest.fail(
            "pipeline-marked test took %.1fs (cap %.0fs, "
            "MXTPU_PIPELINE_TEST_CAP): these tests are contractually "
            "sleep-free — something is waiting on wall-clock instead of "
            "an event/fault hook" % (dt, _PIPELINE_TEST_CAP),
            pytrace=False)


def pytest_sessionstart(session):
    session.config._mxtpu_wall_t0 = time.time()


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    t0 = getattr(config, "_mxtpu_wall_t0", None)
    if t0 is None:
        return
    wall = time.time() - t0
    line = ("tier-1 wall clock: %.1fs of the %ds budget (%.0f%%)"
            % (wall, int(_T1_BUDGET), 100.0 * wall / _T1_BUDGET))
    if wall > 0.9 * _T1_BUDGET:
        line += " — WARNING: within 10% of the timeout, trim before adding"
    terminalreporter.write_line(line)
