"""mxnet_tpu.autotune — closes the loop between measurement and config
(docs/perf.md "Autotuning"; the TVM measured-search discipline,
arXiv:1802.04799, applied to this system's own knobs).

Three pieces:

* a **search driver** (:mod:`.search`) — exhaustive grid for small
  spaces, greedy per-knob hill climb for larger ones, deterministic
  order, bounded budget, per-trial timeout + crash isolation;
* a **static pruner** — every candidate's compiled program set passes a
  :mod:`mxnet_tpu.memcheck` budget check BEFORE execution (one compile,
  never a run for an over-budget config);
* a **committed tuning DB** (:mod:`.db`, ``AUTOTUNE_db.json``) keyed
  ``(model, device_kind, global_batch, objective)`` that ``Module.fit``
  and ``ServingEngine`` resolve unset knobs from by default, with
  precedence **explicit arg > env > tuning DB > built-in default** —
  resolution is logged once per run via the obs registry.

``python -m mxnet_tpu.autotune --model mlp --objective img_per_sec
--write-db`` runs a sweep and persists the winner.
"""
from __future__ import annotations

import logging

from ..base import MXNetError, env_str
from . import db as _db
from .db import (TuningDB, default_db_path, load_cached, param_signature,
                 parse_buckets, symbol_signature)
from .search import Knob, SearchDriver, Trial, NEG_INF
from .space import decode_space, serve_space, space_for, train_space

__all__ = [
    "TuningDB", "SearchDriver", "Trial", "Knob", "NEG_INF",
    "default_db_path", "symbol_signature", "param_signature",
    "parse_buckets",
    "train_space", "serve_space", "decode_space", "space_for",
    "enabled", "tune", "resolve_train_knobs", "resolve_serve_knobs",
    "resolve_decode_knobs", "resolve_fit_knobs", "note_db_resolution",
    "hotspot_report",
    "TRAIN_OBJECTIVES", "SERVE_OBJECTIVES", "DECODE_OBJECTIVES",
]


def hotspot_report(fn, args=(), kwargs=None, name=None, mesh=None,
                   loop_trips=1, top=10, memory_only=True):
    """The Pallas tier's shopping list for ONE program: the flopcheck
    roofline's ranked hotspot entries (docs/static_analysis.md
    "Roofline lints") — exposed here because the hand-kernel search
    starts where the measured-search driver stops: the memory-bound
    kernels the compiler cannot fuse its way out of. Delegates to
    :func:`mxnet_tpu.flopcheck.hotspot_report`."""
    from .. import flopcheck
    return flopcheck.hotspot_report(
        fn, args, kwargs=kwargs, name=name, mesh=mesh,
        loop_trips=loop_trips, top=top, memory_only=memory_only)

TRAIN_OBJECTIVES = ("img_per_sec", "tokens_per_sec")
SERVE_OBJECTIVES = ("serve_p99", "serve_p50")
DECODE_OBJECTIVES = ("decode_tokens_per_sec",)


def enabled():
    """Whether tuning-DB knob resolution is armed (default ON;
    ``MXTPU_AUTOTUNE=0`` disarms — explicit args and env knobs always
    win regardless)."""
    return env_str("MXTPU_AUTOTUNE").lower() \
        not in ("0", "false", "off", "no")


# ---------------------------------------------------------------------------
# resolution (Module.fit / ServingEngine / DecodeLoop consumers)
# ---------------------------------------------------------------------------

def note_db_resolution(logger, who, entry_key, applied):
    """The once-per-run resolution log + obs-registry count
    (docs/observability.md): every run that takes knob values from the
    tuning DB says so exactly once, with the entry key, so a serving or
    training log always reveals where its configuration came from."""
    from ..obs import REGISTRY
    REGISTRY.counter(
        "autotune.db_resolutions",
        "knob values resolved from the tuning DB").inc()
    (logger or logging).info(
        "autotune: %s resolved %s from tuning DB entry %s (%s)",
        who, ", ".join("%s=%r" % kv for kv in sorted(applied.items())),
        entry_key, default_db_path())


def _note_mismatch(logger, note):
    from ..obs import REGISTRY
    REGISTRY.counter(
        "autotune.db_mismatches",
        "tuning-DB entries skipped for platform/device mismatch").inc()
    (logger or logging).info("autotune: %s", note)


def resolve_train_knobs(symbol, global_batch, logger=None):
    """Tuning-DB knobs for a training run over ``symbol`` at
    ``global_batch`` on this device kind. Returns ``(entry_key, knobs)``
    or ``(None, None)`` — a miss, a device/platform mismatch (noted) or a
    stale DB all resolve to None, never an error: resolution must not be
    able to break the run it is configuring."""
    if not enabled():
        return None, None
    try:
        sig = symbol_signature(symbol)
        tdb = load_cached(logger=logger)
        # DETERMINISTIC objective preference (img/s first): with entries
        # for more than one training objective on the same symbol/batch/
        # device, the choice must be this documented order — never the
        # accident of key sort order
        note = None
        for objective in TRAIN_OBJECTIVES:
            key, entry, obj_note = tdb.lookup(
                "train", symbol_sig=sig, global_batch=int(global_batch),
                objective=objective)
            note = note or obj_note  # a mismatch seen for ANY objective
            if entry is not None:
                return key, dict(entry.get("knobs") or {})
        if note:
            _note_mismatch(logger, note)
    except Exception as e:
        (logger or logging).warning(
            "autotune: tuning-DB resolution failed (%r) — knobs fall "
            "back to built-in defaults", e)
    return None, None


def resolve_serve_knobs(symbol, logger=None):
    """Tuning-DB knobs for a :class:`~mxnet_tpu.serving.ServingEngine`
    over the (stripped) ``symbol`` on this device kind; same
    never-raises contract as :func:`resolve_train_knobs`."""
    if not enabled():
        return None, None
    try:
        sig = symbol_signature(symbol)
        tdb = load_cached(logger=logger)
        # deterministic objective preference: p99 entries win over p50
        # when both exist for the same symbol/device — the tail is what
        # the serving tier's deadlines gate on (documented order, not
        # key-sort accident)
        note = None
        for objective in SERVE_OBJECTIVES:
            key, entry, obj_note = tdb.lookup("serve", symbol_sig=sig,
                                              global_batch=0,
                                              objective=objective)
            note = note or obj_note
            if entry is not None:
                return key, dict(entry.get("knobs") or {})
        if note:
            _note_mismatch(logger, note)
    except Exception as e:
        (logger or logging).warning(
            "autotune: tuning-DB resolution failed (%r) — serving knobs "
            "fall back to built-in defaults", e)
    return None, None


def resolve_decode_knobs(params, logger=None):
    """Tuning-DB knobs for a :class:`~mxnet_tpu.serving.DecodeLoop` over
    ``params`` (a flat ``name -> array`` dict — the decode loop has no
    Symbol, so entries match on :func:`param_signature`); returns the
    knobs dict or ``None``, never raises, and logs the resolution once
    on a hit (the loop's own arg/env precedence has already been
    applied by the caller)."""
    if not enabled():
        return None
    try:
        sig = param_signature(params)
        tdb = load_cached(logger=logger)
        note = None
        for objective in DECODE_OBJECTIVES:
            key, entry, obj_note = tdb.lookup("decode", symbol_sig=sig,
                                              global_batch=0,
                                              objective=objective)
            note = note or obj_note
            if entry is not None:
                knobs = dict(entry.get("knobs") or {})
                if knobs:
                    note_db_resolution(logger, "DecodeLoop", key, knobs)
                return knobs
        if note:
            _note_mismatch(logger, note)
    except Exception as e:
        (logger or logging).warning(
            "autotune: tuning-DB resolution failed (%r) — decode knobs "
            "fall back to built-in defaults", e)
    return None


def resolve_fit_knobs(module, train_data, steps_per_dispatch,
                      dispatch_pipeline, logger=None):
    """``Module.fit``'s knob resolution (docs/perf.md "Autotuning"):
    precedence **explicit arg > env > tuning DB > built-in default**,
    applied per knob. Returns ``(steps_per_dispatch, dispatch_pipeline,
    {knob: source})`` with sources in ``{"arg", "env", "db",
    "default"}``; a DB hit is logged once via the obs registry."""
    from .. import engine as _engine
    logger = logger or logging
    src = {}
    k = depth = None
    if steps_per_dispatch is not None:
        k = max(1, int(steps_per_dispatch))
        src["steps_per_dispatch"] = "arg"
    elif _engine.bulk_configured():
        k = max(1, int(_engine.bulk_size()))
        src["steps_per_dispatch"] = "env"
    if dispatch_pipeline is not None:
        depth = max(0, int(dispatch_pipeline))
        src["dispatch_pipeline"] = "arg"
    elif _engine.dispatch_pipeline_configured():
        depth = max(0, int(_engine.dispatch_pipeline()))
        src["dispatch_pipeline"] = "env"
    if k is None or depth is None:
        entry_key = knobs = None
        try:
            symbol = getattr(module, "symbol", None)
            first = (train_data.provide_data or [None])[0]
            shape = (first.shape if hasattr(first, "shape") else first[1])
            global_batch = int(shape[0])
        except Exception:
            symbol, global_batch = None, None
        if symbol is not None and global_batch is not None:
            entry_key, knobs = resolve_train_knobs(symbol, global_batch,
                                                   logger=logger)
        if knobs:
            applied = {}
            if k is None and "steps_per_dispatch" in knobs:
                k = max(1, int(knobs["steps_per_dispatch"]))
                src["steps_per_dispatch"] = "db"
                applied["steps_per_dispatch"] = k
            if depth is None and "dispatch_pipeline" in knobs:
                depth = max(0, int(knobs["dispatch_pipeline"]))
                src["dispatch_pipeline"] = "db"
                applied["dispatch_pipeline"] = depth
            if applied:
                note_db_resolution(logger, "Module.fit", entry_key,
                                   applied)
    if k is None:
        k = max(1, int(_engine.bulk_size()))
        src["steps_per_dispatch"] = "default"
    if depth is None:
        depth = max(0, int(_engine.dispatch_pipeline()))
        src["dispatch_pipeline"] = "default"
    return k, depth, src


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------

def tune(model="mlp", objective="img_per_sec", budget=24, batch=None,
         db_path=None, write_db=False, space=None, trial_timeout=None,
         qps=None, nreq=None, rounds=2, logger=None, log=None):
    """Run one autotuning sweep and (optionally) persist the winner.

    Builds the harness for ``objective`` (training objectives measure the
    fused K-step scan with fit's pipelined readback discipline; serving
    objectives drive the batcher with open-loop arrivals), prunes each
    candidate statically through memcheck, searches the space under
    ``budget`` trials, and returns a JSON-able result dict. With
    ``write_db`` the best trial lands in the tuning DB (atomic write),
    keyed ``(model, device_kind, global_batch, objective)``.
    """
    from .harness import DecodeHarness, ServeHarness, TrainHarness
    logger = logger or logging
    if objective in TRAIN_OBJECTIVES:
        h = TrainHarness(model=model, batch=batch, objective=objective,
                         rounds=rounds, logger=logger)
        sp = space or train_space()
        global_batch = h.batch
    elif objective in SERVE_OBJECTIVES:
        kw = {}
        if qps is not None:
            kw["qps"] = qps
        if nreq is not None:
            kw["nreq"] = nreq
        h = ServeHarness(model=model, objective=objective, logger=logger,
                         **kw)
        sp = space or serve_space()
        global_batch = 0
    elif objective in DECODE_OBJECTIVES:
        kw = {}
        if nreq is not None:
            kw["nreq"] = nreq
        h = DecodeHarness(model=model, objective=objective, logger=logger,
                          **kw)
        sp = space or decode_space()
        global_batch = 0
    else:
        raise MXNetError(
            "autotune: unknown objective %r (training: %s; serving: %s; "
            "decode: %s)"
            % (objective, "|".join(TRAIN_OBJECTIVES),
               "|".join(SERVE_OBJECTIVES), "|".join(DECODE_OBJECTIVES)))
    driver = SearchDriver(sp, h.evaluate, prune=h.prune,
                          program_knobs=h.program_knobs, budget=budget,
                          trial_timeout=trial_timeout, logger=logger,
                          log=log)
    try:
        best, trials = driver.run()
    finally:
        if hasattr(h, "close"):
            h.close()   # decode trials hold live loop threads
    default = driver.default_trial
    result = {
        "model": model,
        "objective": objective,
        "kind": h.kind,
        "global_batch": global_batch,
        "unit": h.unit,
        "symbol_sig": h.symbol_sig(),
        "counts": driver.counts(),
        "trials": [t.to_dict() for t in trials],
        "default": default.to_dict() if default is not None else None,
        "best": best.to_dict() if best is not None else None,
    }
    if best is not None and default is not None and default.ok:
        result["speedup_vs_default"] = (
            round(best.score / default.score, 4)
            if default.score > 0 else None)
    if best is not None and write_db:
        tdb = TuningDB.load(db_path, logger=logger)
        if tdb.stale:
            # a stale file must not survive a deliberate --write-db: the
            # refresh REPLACES it (that is the baseline-update workflow)
            tdb = TuningDB(db_path)
        key = tdb.put(
            model, objective, global_batch, best.knobs, best.score,
            h.unit, kind=h.kind, symbol=h.symbol.name,
            symbol_sig=h.symbol_sig(),
            extra={"default_score": (default.score
                                     if default is not None and default.ok
                                     else None),
                   "trials": len(trials),
                   "pruned": driver.counts().get("pruned", 0)})
        tdb.save()
        result["db"] = {"path": tdb.path, "entry": key}
        logger.info("autotune: wrote winner %r (score %.6g %s) to %s",
                    best.knobs, best.score, h.unit, tdb.path)
    return result
