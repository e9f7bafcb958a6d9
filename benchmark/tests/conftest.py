"""The harness's own CPU tests (``python -m pytest benchmark/tests -q``
under ``JAX_PLATFORMS=cpu``). They live under the benchmark's ``paths``
because a later PR may not change the yardstick, and are not part of
tier-1 (``tests/``), which this PR's contract did not let it touch."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for p in (REPO, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture()
def tiny_root(tmp_path):
    import helpers
    return helpers.make_root(tmp_path)
