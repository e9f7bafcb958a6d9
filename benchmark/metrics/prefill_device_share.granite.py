"""Ops: share of the device's busy time, in the traced window, that the
prefill passes took, in the ``granite-4.0-h-micro`` cell: the reader of
``prefill_device_share`` itself (one reader per configuration, as the
rooflines have). Nothing to read where the loop has no prefill program."""
import os

from benchmark.harness import cells

_SHARE = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "prefill_device_share.py"))


def read(ctx):
    return _SHARE.read(ctx)
