"""Window attention: slot-steps of the traced window dispatched at a
position at or past ``sliding_window`` (where the ring has wrapped and a
window layer reads fewer rows than a full one would), over all its
slot-steps, from the ``decode_step`` spans' own ``pos`` and ``n``: how much
of the traffic the window mechanism works on. ``ServingHealth`` counts the
numerator as ``ring_wrapped_slot_steps``. A program whose spans carry no
``ring_rows`` keeps no ring: nothing to read."""
from benchmark.harness import stepgaps


def read(ctx):
    window = ctx["cfg"].get("sliding_window")
    args = [a for a in stepgaps.step_args(ctx) if "ring_rows" in a]
    if not args or not window:
        return None
    stood = [p for a in args for p, n in zip(a["pos"], a["n"]) if n]
    if not stood:
        return None
    return 100.0 * sum(p >= int(window) for p in stood) / len(stood)
