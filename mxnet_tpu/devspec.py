"""devspec: ONE per-device-kind capability table for every roofline.

commscheck's wire model (peak FLOP/s, ICI link bandwidth) and
flopcheck's roofline (peak FLOP/s, HBM bandwidth) price programs from the
same spec sheet. A spec number that lives in two places drifts — one
table gets a new chip generation, the other silently keeps pricing it as
unknown — so the three columns live HERE and both read them through the
same prefix-matched lookup:

==============  ===========  ===========  ===========
device kind     peak bf16    HBM          ICI link
                FLOP/s       bytes/s      bytes/s
==============  ===========  ===========  ===========
TPU v2          46e12        7.0e11       6.2e10
TPU v3          123e12       9.0e11      8.1e10
TPU v4          275e12       1.2e12       1.2e11
TPU v5e/lite    197e12       8.1e11       4.5e10
TPU v5p         459e12       2.765e12     9.0e10
TPU v6e/lite    918e12       1.64e12      9.0e10
==============  ===========  ===========  ===========

(public spec-sheet figures, order-of-magnitude — every consumer's
roofline is a MODEL). CPU / unknown kinds fall back to nominal figures
so the forced-host CI mesh stays finite and deterministic; the
``peak_source`` field says which case you got (``"spec"`` vs
``"nominal-fallback"``) so an MFU/roofline number is never silently a
guess.
"""
from __future__ import annotations

from collections import namedtuple

__all__ = [
    "DeviceSpec", "DEVICE_SPECS", "DEFAULT_SPEC", "device_kind", "lookup",
    "peak_flops", "hbm_bandwidth", "link_bandwidth", "ridge_intensity",
    "peak_source",
]

#: one device kind's capability row (all rates are per-chip):
#: ``peak_flops_per_s`` dense bf16, ``hbm_bytes_per_s`` main-memory
#: bandwidth, ``link_bytes_per_s`` one-directional inter-chip ICI
DeviceSpec = namedtuple("DeviceSpec", ["peak_flops_per_s",
                                       "hbm_bytes_per_s",
                                       "link_bytes_per_s"])

#: per-device-kind table, matched by ``device_kind`` PREFIX (a v5e
#: reports "TPU v5 lite" or "TPU v5e" depending on runtime version)
DEVICE_SPECS = {
    "TPU v2": DeviceSpec(46e12, 7.0e11, 6.2e10),
    "TPU v3": DeviceSpec(123e12, 9.0e11, 8.1e10),
    "TPU v4": DeviceSpec(275e12, 1.2e12, 1.2e11),
    "TPU v5 lite": DeviceSpec(197e12, 8.1e11, 4.5e10),
    "TPU v5e": DeviceSpec(197e12, 8.1e11, 4.5e10),
    "TPU v5p": DeviceSpec(459e12, 2.765e12, 9.0e10),
    "TPU v6 lite": DeviceSpec(918e12, 1.64e12, 9.0e10),
    "TPU v6e": DeviceSpec(918e12, 1.64e12, 9.0e10),
}

#: CPU / unknown backends: nominal few-core figures. The ratio matters
#: as much as the magnitudes — peak/hbm here puts the ridge point at 10
#: FLOP/byte, so low-intensity kernels (attention score x V, optimizer
#: sweeps) classify memory-bound on the CI host the way they do on real
#: chips, instead of everything degenerating to one side of the ridge.
DEFAULT_SPEC = DeviceSpec(5.0e10, 5.0e9, 1.0e10)


def device_kind(device=None):
    """The backend's device-kind string ("" when it reports none)."""
    import jax
    device = device or jax.devices()[0]
    return getattr(device, "device_kind", "")


def lookup(device=None):
    """``(DeviceSpec, peak_source)`` for a device: the spec-sheet row
    matched by device-kind prefix (``peak_source="spec"``), or the
    nominal fallback (``peak_source="nominal-fallback"``)."""
    kind = device_kind(device)
    for k, spec in DEVICE_SPECS.items():
        if kind.startswith(k):
            return spec, "spec"
    return DEFAULT_SPEC, "nominal-fallback"


def peak_flops(device=None):
    """Peak dense bf16 FLOP/s by device kind (nominal fallback for
    CPU/unknown — check :func:`peak_source` before headlining it)."""
    return lookup(device)[0].peak_flops_per_s


def hbm_bandwidth(device=None):
    """Main-memory (HBM) bandwidth in bytes/s by device kind."""
    return lookup(device)[0].hbm_bytes_per_s


def link_bandwidth(device=None):
    """One-directional inter-chip link bandwidth in bytes/s by device
    kind (the commscheck wire-time model's denominator)."""
    return lookup(device)[0].link_bytes_per_s


def ridge_intensity(device=None):
    """The roofline ridge point in FLOP/byte: kernels whose arithmetic
    intensity sits below it are memory-bound at any utilization."""
    spec, _ = lookup(device)
    return spec.peak_flops_per_s / spec.hbm_bytes_per_s


def peak_source(device=None):
    """``"spec"`` when the device kind matched a spec-sheet row,
    ``"nominal-fallback"`` otherwise."""
    return lookup(device)[1]
