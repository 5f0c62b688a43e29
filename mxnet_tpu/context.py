"""Device context.

TPU-native equivalent of the reference's ``Context`` (python/mxnet/context.py,
include/mxnet/base.h:129-210).  A ``Context`` names a logical device; it maps
onto a PJRT :class:`jax.Device`.  ``mx.tpu(i)`` is the first-class accelerator
context (the reference's ``mx.gpu(i)``); ``mx.gpu`` is kept as an alias so
reference user code runs unchanged.  Only when the process was explicitly
pinned to the CPU platform (``JAX_PLATFORMS=cpu`` — the unit tests, with a
virtual 8-device CPU mesh) does ``tpu(i)`` resolve to host device *i*,
mirroring how the reference unit-tests multi-device logic with multiple CPU
contexts (SURVEY.md §4 "Multi-device (fake cluster)").  Anywhere else
``tpu(i)`` is chip *i* or an error: it never lands on the host unasked.
"""
from __future__ import annotations

import threading
from typing import Optional

from .base import MXNetError


def platform_pinned_to_cpu() -> bool:
    """True when the process selected the CPU platform on purpose
    (``JAX_PLATFORMS=cpu`` or ``jax.config.update("jax_platforms",
    "cpu")``, as tests/conftest.py and cpu_pin.pin_cpu do).  THE rule for
    every place that may stand in for the chip — ``tpu(i)`` resolving to
    a virtual CPU device, the Pallas kernels running interpreted: a
    machine that merely lacks a TPU does not qualify."""
    import jax
    first = (jax.config.jax_platforms or "").split(",")[0]
    return first.strip().lower() == "cpu"


class Context:
    """A logical device (cpu/tpu/gpu-alias) backed by a PJRT jax.Device."""

    # reference devtype ids (base.h:137-146) + tpu extension
    devtype2id = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
    devid2type = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_type, self.device_id = device_type.device_type, device_type.device_id
        else:
            if device_type not in self.devtype2id:
                raise MXNetError(f"unknown device type {device_type!r}")
            self.device_type = device_type
            self.device_id = device_id
        self._old_ctx: Optional[Context] = None

    @property
    def device_typeid(self) -> int:
        return self.devtype2id[self.device_type]

    def jax_device(self):
        """Resolve to the PJRT device backing this context.

        Process-LOCAL devices only: under jax.distributed the global device
        list includes other hosts' devices, which this process cannot
        address (multi-host placement is expressed with meshes/shardings,
        never by binding a Context to a remote device)."""
        import jax
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            try:
                devs = jax.local_devices(backend="cpu")
            except RuntimeError:
                devs = jax.local_devices()
            return devs[self.device_id % len(devs)]
        # tpu / gpu-alias: a real chip, or (CPU pin only) virtual device i
        if platform_pinned_to_cpu():
            devs = jax.local_devices(backend="cpu")
        else:
            try:
                devs = jax.local_devices(backend="tpu")
            except RuntimeError as e:
                raise MXNetError(
                    f"{self} needs a TPU but JAX found none (default "
                    f"backend: {jax.default_backend()!r}); set "
                    f"JAX_PLATFORMS=cpu to map tpu(i) onto virtual CPU "
                    f"devices") from e
        if not 0 <= self.device_id < len(devs):
            raise MXNetError(
                f"{self} is out of range: this process addresses "
                f"{len(devs)} {devs[0].platform} device(s)")
        return devs[self.device_id]

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __str__(self):
        return f"{self.device_type}({self.device_id})"

    __repr__ = __str__

    def __enter__(self):
        if not hasattr(self._default_ctx, "value"):
            self._default_ctx.value = Context("cpu", 0)
        self._old_ctx = self._default_ctx.value
        self._default_ctx.value = self
        return self

    def __exit__(self, *args):
        self._default_ctx.value = self._old_ctx

    def empty_cache(self):
        """Release cached device memory (reference: storage pool ReleaseAll).

        PJRT owns HBM; this asks JAX to drop live-but-unreferenced buffers.
        """
        import gc
        gc.collect()


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias for :func:`tpu` so reference scripts run unchanged on TPU pods."""
    return Context("tpu", device_id)


def num_gpus() -> int:
    """Number of accelerator devices visible (reference: mx.context.num_gpus)."""
    import jax
    try:
        return len([d for d in jax.devices() if d.platform != "cpu"])
    except RuntimeError:
        return 0


def num_tpus() -> int:
    return num_gpus()


def current_context() -> Context:
    if not hasattr(Context._default_ctx, "value"):
        Context._default_ctx.value = Context("cpu", 0)
    return Context._default_ctx.value
