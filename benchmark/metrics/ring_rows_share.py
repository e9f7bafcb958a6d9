"""Window attention: the rows of the ring that the traced window's steps
covered in a window layer (the spans' ``ring_rows``) over the rows the
same steps covered in a full layer (their ``rows``), which is what a
window layer kept as a per-position array would have read:
``ServingHealth`` sums the two as ``ring_rows_read`` and
``cache_rows_read``. A program whose spans carry no ``ring_rows`` keeps no
ring: nothing to read."""
from benchmark.harness import stepgaps


def read(ctx):
    args = [a for a in stepgaps.step_args(ctx) if "ring_rows" in a]
    rows = sum(a.get("rows", 0) for a in args)
    if not rows:
        return None
    return 100.0 * sum(a["ring_rows"] for a in args) / rows
