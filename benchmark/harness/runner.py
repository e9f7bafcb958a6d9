"""What every entry shares: the look for a chip, the compile cache, tracing
of a short window, the result's line, and the printing of each number
compared beside its limit."""
import json
import os
import shutil
import sys
import time

from . import tracered
from .compile_meter import CompileMeter
from .peaks import UnknownDevice, peaks_for

#: seconds of the measured window that a --trace 1 run puts under the
#: profiler: a trace of the whole window is large and slow to read
TRACE_SECONDS = 4.0


class NoChip(Exception):
    """JAX found no accelerator the table of peaks knows, or too few."""


def device_facts():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def place_compile_cache():
    """The program's own placement (``JAX_COMPILATION_CACHE_DIR`` if set,
    else ``<checkout>/.jax_cache``, a fixed path), with JAX's persistence
    thresholds lowered in THIS process so that the many sub-second programs
    of a warm start are served from the cache too."""
    import jax
    from mxnet_tpu import engine
    where = engine.setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


class Run(object):
    """One run of one cell: its arguments, the device, the meter, and (in a
    traced run) the trace and the spans on one clock."""

    def __init__(self, cell, seed, seconds, trace, control="",
                 t_process=None, require_chip=True, compile_cache=True):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace_on = bool(trace)
        #: "" | "all" | the name of one control or planted fault
        self.control = str(control or "")
        self.t_process = (time.perf_counter() if t_process is None
                          else t_process)
        self.device = device_facts()
        try:
            self.peaks = peaks_for(self.device["kind"])
        except UnknownDevice as e:
            if require_chip:
                raise NoChip(str(e))
            self.peaks = None        # a CPU rehearsal: counts only
        if require_chip and self.device["count"] < cell.chips:
            raise NoChip("cell %s asks for %d chip(s), JAX found %d"
                         % (cell.name, cell.chips, self.device["count"]))
        self.cache_dir = place_compile_cache() if compile_cache else None
        self.meter = CompileMeter()
        self.tmp = os.path.join(cell.root, ".bench_tmp",
                                cell.name.replace("/", "_"))
        self.trace = None
        self.trace_window_ns = None      # (t0, t1) on the trace's clock
        self._perf_to_trace = None       # trace_ns = perf_ns + this
        self._obs_epoch_ns = None
        self.notes = {}                  # free-form facts for stderr

    # -- tracing -----------------------------------------------------------
    def arm_spans(self):
        """Arm the program's host spans and learn their epoch on the
        perf_counter clock from one instant of our own."""
        from mxnet_tpu.obs import trace as obs
        obs.clear()
        obs.start()
        t = time.perf_counter_ns()
        obs.instant("bench_sync")
        ev = [e for e in obs.events() if e.get("name") == "bench_sync"][-1]
        self._obs_epoch_ns = t - int(ev["ts"]) * 1000

    def start_trace(self):
        import jax
        shutil.rmtree(self.tmp, ignore_errors=True)
        os.makedirs(self.tmp, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.tmp, profiler_options=opts)
        self._sync_perf_ns = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(tracered.SYNC_NAME):
            time.sleep(0.001)
        self._trace_t0_perf = time.perf_counter_ns()

    def stop_trace(self):
        import jax
        t1_perf = time.perf_counter_ns()
        jax.profiler.stop_trace()
        path = tracered.find_xplane(self.tmp)
        if path is None:
            raise RuntimeError("the profiler wrote no trace under %s"
                               % self.tmp)
        self.trace = tracered.Trace.from_file(path)
        shutil.rmtree(self.tmp, ignore_errors=True)
        if self.trace.sync_ns is None:
            raise RuntimeError("trace has no %s annotation"
                               % tracered.SYNC_NAME)
        self._perf_to_trace = self.trace.sync_ns - self._sync_perf_ns
        self.trace_window_ns = (self._trace_t0_perf + self._perf_to_trace,
                                t1_perf + self._perf_to_trace)

    def spans(self):
        """The program's complete spans as ``(name, start_ns, end_ns,
        args)`` on the trace's clock (the perf_counter clock if no trace
        was taken)."""
        from mxnet_tpu.obs import trace as obs
        shift = self._obs_epoch_ns + (self._perf_to_trace or 0)
        out = []
        for e in obs.events():
            if e.get("ph") != "X":
                continue
            s = int(e["ts"]) * 1000 + shift
            out.append((e["name"], s, s + int(e["dur"]) * 1000,
                        e.get("args") or {}))
        return out

    def perf_to_trace_ns(self, t_perf_s):
        return int(t_perf_s * 1e9) + (self._perf_to_trace or 0)

    # -- the device object of the last line --------------------------------
    def memory_peak_bytes(self):
        import jax
        peak = 0
        for d in jax.devices():
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return peak

    def device_object(self, memory_peak):
        dev = dict(self.device)
        dev["memory_peak_bytes"] = int(memory_peak)
        if self.trace is not None:
            t0, t1 = self.trace_window_ns
            dev["busy_s"] = self.trace.busy_seconds(t0, t1)
            dev["window_s"] = (t1 - t0) / 1e9
        return dev


def compare(readings, limits):
    """``readings``: name -> number. Each is held to ``limits[name]``; a
    reading with no limit is printed and not held (``limit: null``).
    Returns ``(correct, compared)`` with ``compared`` ready for the line."""
    compared, ok = {}, True
    for name, value in readings.items():
        limit = limits.get(name)
        value = float(value)
        good = limit is None or (value == value and value <= float(limit))
        ok = ok and good
        compared[name] = {"value": value, "limit": limit, "ok": good}
    missing = [n for n in limits if n not in readings]
    for name in missing:
        compared[name] = {"value": None, "limit": limits[name], "ok": False}
        ok = False
    return ok, compared


def emit(run, correct, attempted, failed, metrics, memory_peak, compared,
         breakdown=None, extra=None, out=None, err=None):
    """Print the numbers compared (stderr, last lines) and the one result
    line (stdout, last line). Off the chip the line carries counts and no
    metric: a CPU number never stands under a device metric's name."""
    out = out or sys.stdout
    err = err or sys.stderr
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed)}
    if run.peaks is None:
        line["metrics"] = {}
        line["counts"] = {k: v for k, v in (extra or {}).items()}
        line["rehearsal"] = "not a chip: counts only, no device metric"
    else:
        units = {m["name"]: m["unit"] for m in
                 run.cell.bench["end_to_end"] + run.cell.bench["per_layer"]}
        line["metrics"] = {k: {"value": v, "unit": units[k]}
                           for k, v in metrics.items() if v is not None}
        if extra:
            line["counts"] = extra
    line["device"] = run.device_object(memory_peak)
    if breakdown is not None and run.peaks is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    for k, v in sorted(run.notes.items()):
        print("note %s %s" % (k, json.dumps(v)), file=err)
    for name, c in compared.items():
        print("compared %s value=%r limit=%r ok=%s"
              % (name, c["value"], c["limit"], c["ok"]), file=err)
    err.flush()
    print(json.dumps(line), file=out)
    out.flush()
    return line


def finish(run, correct, attempted, failed, end_to_end, memory_peak, compared,
           ctx, counts, out=None, err=None):
    """The tail every entry shares: with ``--trace 0`` the line carries the
    end-to-end metrics; with ``--trace 1`` the per-layer readers are run on
    ``ctx`` (which holds ``spans`` on the trace's clock) and the breakdown
    is added."""
    metrics, breakdown = end_to_end, None
    if run.trace_on:
        ctx = dict(ctx, run=run, trace=run.trace,
                   window_ns=run.trace_window_ns)
        metrics = per_layer_metrics(run, ctx)
        t0, t1 = run.trace_window_ns
        breakdown = tracered.breakdown(
            run.trace, [s[:3] for s in ctx["spans"]], t0, t1)
    return emit(run, correct, attempted, failed, metrics, memory_peak,
                compared, breakdown, extra=counts, out=out, err=err)


def per_layer_metrics(run, ctx):
    """Run each per-layer metric's reader; a reader that finds nothing to
    read returns None and the metric is left out of the line."""
    out = {}
    for m in run.cell.per_layer:
        reader = run.cell.module("metrics", m["name"])
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = float(value)
    return out
