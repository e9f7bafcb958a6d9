"""Device context for mxnet_tpu.

TPU is a first-class device type (ref: include/mxnet/base.h:101-307 defines
Context{kCPU,kGPU,kCPUPinned}+dev_id; here the accelerator type is ``tpu`` and
``gpu`` is accepted as a compatibility alias so reference-era scripts run
unchanged). A Context maps onto a ``jax.Device``; multi-device placement and
communication use ``jax.sharding.Mesh`` (see mxnet_tpu.parallel) rather than
per-device streams.
"""
from __future__ import annotations

import threading

from .base import MXNetError


class Context(object):
    """A device context.

    Parameters
    ----------
    device_type : {'cpu', 'tpu', 'gpu', 'cpu_pinned'} or Context
        'gpu' is an alias for the accelerator ('tpu') so that reference
        training scripts (e.g. train_mnist.py --gpus 0) work verbatim.
    device_id : int
    """

    # parity: base.h devtype ids (1 cpu, 2 gpu, 3 cpu_pinned); tpu gets 4.
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in self.devstr2type:
                raise MXNetError("unknown device type %r" % (device_type,))
            self.device_typeid = self.devstr2type[device_type]
            self.device_id = device_id

    @property
    def device_type(self):
        return self.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # ------------------------------------------------------------------
    # JAX device resolution
    # ------------------------------------------------------------------
    def to_device(self):
        """Resolve this context to a concrete jax.Device.

        Contexts name devices of THIS process (jax.local_devices): in a
        multi-process (dist_sync) job, each worker's cpu(0)/tpu(0) is its own
        chip — the reference semantics, where device ids are per-worker
        (ref: kvstore_dist.h worker-local device lists)."""
        import jax
        dt = self.device_type
        if dt == "cpu" or dt == "cpu_pinned":
            devs = (_local_platform_devices("cpu")
                    or jax.local_devices())
            # context ids beyond physical devices are legal for CPU in the
            # reference (SURVEY.md section 4 multi-device trick); clamp by modulo.
            return devs[self.device_id % len(devs)]
        # tpu / gpu alias -> this process's accelerator devices. No CPU
        # stand-in and no id wrap-around: a run that names a chip it does
        # not have must fail, not train somewhere else
        devs = _accelerator_devices()
        if not devs:
            raise MXNetError(
                "%s: no accelerator device is visible to this process "
                "(jax platform %r) — use mx.cpu() to run on the host"
                % (self, jax.default_backend()))
        if not 0 <= self.device_id < len(devs):
            raise MXNetError(
                "%s: device id out of range, this process has %d %s "
                "device(s)" % (self, len(devs), devs[0].platform))
        return devs[self.device_id]

    @property
    def sharding(self):
        import jax
        return jax.sharding.SingleDeviceSharding(self.to_device())


def _local_platform_devices(name):
    import jax
    try:
        return [d for d in jax.local_devices() if d.platform == name]
    except RuntimeError:
        return []


def _accelerator_devices():
    """This process's non-cpu devices (empty on a CPU-only host)."""
    import jax
    return [d for d in jax.local_devices() if d.platform != "cpu"]


def cpu(device_id=0):
    """Return a CPU context."""
    return Context("cpu", device_id)


def tpu(device_id=0):
    """Return a TPU context."""
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Alias for :func:`tpu` — keeps reference scripts with --gpus flags working."""
    return Context("gpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def num_devices():
    """Number of accelerator devices visible, 0 on a CPU-only host
    (parity: mx.context device count)."""
    return len(_accelerator_devices())


def current_context():
    """The thread-local default context (default: first accelerator, else cpu)."""
    if not hasattr(Context._default_ctx, "value"):
        Context._default_ctx.value = (Context("tpu", 0)
                                      if _accelerator_devices()
                                      else Context("cpu", 0))
    return Context._default_ctx.value


def default_context():
    return current_context()
