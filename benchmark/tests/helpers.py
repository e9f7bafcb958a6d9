"""Builds a throw-away root for the harness's CPU tests: a copy of
``benchmark/`` with the tiny cells of ``tests/tiny/`` ADDED as new files
(a configuration, its reference, traffic mixes) and a tiny
``BENCHMARK.json`` (``tiny/cells.json``): no file of the copy is edited,
which is how a later PR adds a cell."""
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)


def make_root(tmp):
    root = os.path.join(str(tmp), "root")
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    tiny = os.path.join(HERE, "tiny")
    dst = os.path.join(root, "benchmark")
    shutil.copy(os.path.join(tiny, "cells.json"),
                os.path.join(root, "BENCHMARK.json"))
    for cfg, ref in (("tiny-lm", "opt-1.3b"), ("tiny-resnet", "resnet50")):
        shutil.copy(os.path.join(tiny, cfg + ".json"),
                    os.path.join(dst, "configs", cfg + ".json"))
        shutil.copy(os.path.join(dst, "reference", ref + ".py"),
                    os.path.join(dst, "reference", cfg + ".py"))
    for mix in ("tiny_closed", "tiny_open", "tiny_fit", "tiny_fit_dp"):
        shutil.copy(os.path.join(tiny, mix + ".json"),
                    os.path.join(dst, "traffic", mix + ".json"))
    return root
