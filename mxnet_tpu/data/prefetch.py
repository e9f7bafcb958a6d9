"""Prefetch-to-device: the last stage of the device-fed input tier.

:class:`DevicePrefetcher` is :class:`~mxnet_tpu.io.SuperBatchIter` — the
producer-thread superbatch assembler whose single (optionally per-chip
sharded) H2D lands each stacked (k, batch, ...) dispatch input — plus the
two things the input tier adds on top:

- **Depth matched to the dispatch pipeline.** ``depth=D`` sizes the
  device-side queue at D+1 superbatches, one per in-flight dispatch of
  fit's depth-D deferred-readback window (docs/perf.md "Host off the
  critical path") plus the one being trained — so the H2D of superbatch
  N+D overlaps the scan of superbatch N end-to-end and the training loop
  never blocks on a transfer it could have hidden.
- **Per-stage accounting.** Stack time, H2D time, consumer stall and
  queue-depth samples land in the pipeline's shared
  :class:`~mxnet_tpu.data.stats.PipelineStats` (the same object the
  decode pool and reader charge), so one ``report()`` covers the whole
  tier: read -> decode -> stack -> H2D -> stall.

Sharding rides the base class: pass
``sharding=parallel.mesh.superbatch_sharding(mesh)`` and the producer's
device_put IS the per-chip scatter (docs/perf.md "Data-parallel scaling").
``Module.fit`` constructs one of these automatically for every fused
K-step run.
"""
from __future__ import annotations

import time

from .. import io as mxio
from .stats import PipelineStats, PIPELINE_STATS


class DevicePrefetcher(mxio.SuperBatchIter):
    """SuperBatchIter with dispatch-pipeline-aware depth, PipelineStats
    instrumentation, and epoch pinning (``set_epoch``) for deterministic
    resume through shuffling base iterators."""

    def __init__(self, base, k, depth=None, stats=None, **kwargs):
        # one stats object for the whole tier: reuse the base iterator's
        # (the decode pool already charges read/decode there), else make a
        # fresh one mirroring into the process-global aggregate
        self.stats = (stats if stats is not None
                      else getattr(base, "data_stats", None))
        if self.stats is None:
            self.stats = PipelineStats(parent=PIPELINE_STATS)
        if depth is not None and "queue_depth" not in kwargs:
            kwargs["queue_depth"] = max(2, int(depth) + 1)
        self._emitted = 0
        super().__init__(base, k, **kwargs)

    # SuperBatchIter calls this around its stack/device-put phases
    def _note_stage(self, stage, seconds, n=1):
        self.stats.add(stage, seconds, n)

    def _queue_get_checked(self):
        """The training loop's wait for the next superbatch: queue-depth
        sample plus the stall charge — when this time is a large fraction
        of wall clock the run is input-bound, and ``stall_frac`` in the
        Speedometer suffix says so directly. The wait also
        lands as a ``data_wait`` host span carrying the superbatch's
        correlation index (docs/observability.md)."""
        from ..obs import trace as _obs
        self.stats.note_queue_depth(self._queue.qsize())
        t0 = time.perf_counter()
        item = None
        try:
            item = super()._queue_get_checked()
            return item
        finally:
            dt = time.perf_counter() - t0
            self.stats.add("stall", dt)
            # emitted after the fact (the index rides ON the item): the
            # complete event backdates ts by its duration, so Perfetto
            # renders it exactly where the wait happened
            _obs.complete("data_wait", dt,
                          dispatch=getattr(item, "sb_seq", None))

    def set_epoch(self, epoch):
        """Pin the BASE iterator to ``epoch``'s deterministic order and
        restart the producer on it. fit calls this before the first epoch
        (resume lands mid-schedule: a fresh process must re-derive epoch
        E's shuffle, not epoch 0's) and after a divergence rollback.
        No-op when the base has no epoch-addressable order (e.g.
        NDArrayIter)."""
        base_set = getattr(self.base, "set_epoch", None)
        if base_set is None:
            return
        if (self._emitted == 0
                and getattr(self.base, "data_epoch", None) == int(epoch)):
            # nothing consumed and the base already sits on this epoch's
            # deterministic order: the producer's decoded-ahead work is
            # valid — keep it (the common fit-start case)
            return
        if self._prefetch:
            self._shutdown_producer()
        base_set(epoch)
        self._done = False
        self._emitted = 0
        if self._prefetch:
            self._start_producer()

    def next(self):
        out = super().next()
        self._emitted += 1
        return out

    def reset(self):
        super().reset()
        self._emitted = 0
