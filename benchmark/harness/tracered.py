"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
per-layer metrics read: per-device operation intervals, busy seconds, the
device time of a program found by its NAME, idle gaps with the host span
that covers each, and exposed collective time.

The trace's own clock (ns since the trace began) is the clock of everything
here. Host spans are brought onto it through one ``bench_sync`` annotation
whose start is known on both clocks.
"""
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC_NAME = "bench_sync"
COLLECTIVE_WORDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")
_OP_NAME = re.compile(r"^%?([A-Za-z0-9_.\-]+)")


def op_name(text):
    """The operation's own name: the device line carries the whole HLO
    instruction (``%fusion.2 = f32[...] fusion(...)``)."""
    m = _OP_NAME.match(text)
    return m.group(1) if m else text


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def union_seconds(intervals):
    """Total length of the union of ``(start_ns, end_ns)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def gaps(intervals, t0, t1):
    """Idle gaps ``(start_ns, end_ns)`` inside ``[t0, t1]`` not covered by
    any interval."""
    out, cur = [], t0
    for s, e in sorted(intervals):
        if e <= t0 or s >= t1:
            continue
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


class Trace(object):
    """``devices``: {plane name: {"ops": [(name, start, end)], "modules":
    [(name, start, end)]}} with times in ns on the trace's clock;
    ``sync_ns``: where ``bench_sync`` began on that clock (None if absent).
    """

    def __init__(self, devices, sync_ns=None):
        self.devices = devices
        self.sync_ns = sync_ns

    @classmethod
    def from_file(cls, path):
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        devices, sync_ns = {}, None
        for plane in data.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                dev = {"ops": [], "modules": []}
                for line in plane.lines:
                    key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                        line.name)
                    if key is None:
                        continue
                    for ev in line.events:
                        s = int(ev.start_ns)
                        name = op_name(ev.name) if key == "ops" else ev.name
                        dev[key].append((name, s, s + int(ev.duration_ns)))
                devices[plane.name] = dev
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name == SYNC_NAME and sync_ns is None:
                            sync_ns = int(ev.start_ns)
        return cls(devices, sync_ns)

    # -- whole-device numbers ---------------------------------------------
    def span_ns(self):
        """(first op start, last op end) over all devices."""
        starts = [s for d in self.devices.values() for _, s, _ in d["ops"]]
        ends = [e for d in self.devices.values() for _, _, e in d["ops"]]
        return (min(starts), max(ends)) if starts else (0, 0)

    def busy_seconds(self, t0=None, t1=None):
        """Seconds in which an operation ran, averaged over the devices
        that ran any."""
        per_dev = []
        for dev in self.devices.values():
            iv = [(max(s, t0) if t0 is not None else s,
                   min(e, t1) if t1 is not None else e)
                  for _, s, e in dev["ops"]
                  if (t0 is None or e > t0) and (t1 is None or s < t1)]
            if iv:
                per_dev.append(union_seconds(iv))
        return sum(per_dev) / len(per_dev) if per_dev else 0.0

    def top_ops(self, n=10):
        """Device operations that took most time, summed by name over the
        first device (the others run the same program under SPMD). Loop
        operations are left out: their events cover their bodies'."""
        total = {}
        for dev in list(self.devices.values())[:1]:
            for name, s, e in dev["ops"]:
                if name.startswith("while"):
                    continue     # a loop's event spans its body's own events
                total[name] = total.get(name, 0) + (e - s)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in top]

    # -- programs by name --------------------------------------------------
    def program_runs(self, prefix, device=None):
        """``[(start, end)]`` of every execution of the program whose
        module name starts with ``prefix`` (XLA names a jitted function's
        module ``jit_<function>``), on one device."""
        dev = self._device(device)
        return sorted((s, e) for name, s, e in dev["modules"]
                      if name.startswith(prefix))

    def whole_runs(self, prefix, t0, t1, device=None):
        """The program's executions that lie inside ``[t0, t1]``, without a
        last one that the end of the trace cut short (its event ends where
        the trace stopped, well under the others' length)."""
        runs = [(s, e) for s, e in self.program_runs(prefix, device)
                if s >= t0 and e <= t1]
        if len(runs) > 2:
            lengths = sorted(e - s for s, e in runs[:-1])
            if runs[-1][1] - runs[-1][0] < 0.9 * lengths[len(lengths) // 2]:
                runs = runs[:-1]
        return runs

    def _device(self, device=None):
        if not self.devices:
            return {"ops": [], "modules": []}
        if device is None:
            device = sorted(self.devices)[0]
        return self.devices[device]

    # -- idle gaps and the spans that cover them ---------------------------
    def idle_gaps(self, t0, t1, device=None):
        dev = self._device(device)
        return gaps([(s, e) for _, s, e in dev["ops"]], t0, t1)

    def attribute_gaps(self, spans, t0, t1, device=None):
        """Each idle gap named by the host span that overlaps it most.
        ``spans`` is ``[(name, start_ns, end_ns)]`` on the trace's clock.
        Returns ``[(label, seconds)]``, unattributed gaps as ``none``."""
        out = []
        for gs, ge in self.idle_gaps(t0, t1, device):
            best, best_ov = "none", 0
            for name, s, e in spans:
                ov = min(ge, e) - max(gs, s)
                if ov > best_ov:
                    best, best_ov = name, ov
            out.append((best, (ge - gs) / 1e9))
        return out

    # -- collectives -------------------------------------------------------
    def exposed_collective_seconds(self, t0, t1, device=None):
        """Seconds inside ``[t0, t1]`` in which a collective operation was
        running on the device and no other operation was."""
        dev = self._device(device)
        coll, other = [], []
        for name, s, e in dev["ops"]:
            if e <= t0 or s >= t1:
                continue
            iv = (max(s, t0), min(e, t1))
            if any(w in name for w in COLLECTIVE_WORDS):
                coll.append(iv)
            else:
                other.append(iv)
        if not coll:
            return None
        return union_seconds(coll) - _overlap_seconds(coll, other)


def _overlap_seconds(a, b):
    """Length of (union a) intersected with (union b)."""
    return union_seconds(a) + union_seconds(b) - union_seconds(a + b)


def breakdown(trace, spans, t0, t1, n=10):
    """The ``breakdown`` of a traced run's last line: the device operations
    that took most time and the longest idle gaps by covering span, then
    the total idle per span name."""
    attributed = trace.attribute_gaps(spans, t0, t1)
    longest = sorted(attributed, key=lambda g: -g[1])[:5]
    sums = {}
    for name, sec in attributed:
        sums[name] = sums.get(name, 0.0) + sec
    idle = [["gap:" + name, sec] for name, sec in longest]
    idle += [["sum:" + name, sec] for name, sec in
             sorted(sums.items(), key=lambda kv: -kv[1])[:n - len(idle)]]
    return {"device_ops": trace.top_ops(n), "idle_gaps": idle[:n]}
