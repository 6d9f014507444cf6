"""Device contexts: ``mx.cpu()``, ``mx.tpu()`` (and a ``gpu`` alias).

Ref: python/mxnet/context.py :: class Context, with-scope default context
stack. The north-star (BASELINE.json:5) adds ``mx.tpu(i)`` beside cpu/gpu;
here TPU is the first-class accelerator and a Context resolves lazily to a
``jax.Device``. Data placement is committed via ``jax.device_put`` so XLA
compiles per-device executables exactly like the reference's per-ctx
operator dispatch.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
           "num_gpus", "num_tpus", "device"]


class Context:
    """A device context. devtype in {'cpu', 'tpu', 'gpu', 'cpu_pinned'}.

    ``gpu`` is accepted for script compatibility and resolves to the
    platform accelerator (TPU here) — the reference treats devtype as the
    accelerator namespace, and on this stack that accelerator is TPU.
    """

    _default = threading.local()
    devtype2id = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 7}
    devid2type = {v: k for k, v in devtype2id.items()}

    def __init__(self, device_type: str = "cpu", device_id: int = 0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in self.devtype2id:
            raise MXNetError("unknown device type %r" % (device_type,))
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- identity ----------------------------------------------------------
    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __str__ = __repr__

    # -- jax resolution ----------------------------------------------------
    @property
    def jax_device(self) -> jax.Device:
        """Resolve to the concrete jax.Device (lazily; may raise)."""
        return _resolve(self.device_type, self.device_id)

    def empty_cache(self):  # ref: Context.empty_cache (GPU pool release)
        # XLA/PJRT owns the HBM pool; nothing to do but keep the API.
        return None

    def memory_info(self) -> dict:
        """Live tracked-NDArray footprint on this context:
        ``{"bytes", "count"}`` (populated while MXNET_TELEMETRY is on;
        see telemetry.memory_snapshot for the full picture)."""
        from . import telemetry
        return telemetry.ndarray_live(str(self))

    # -- scope -------------------------------------------------------------
    def __enter__(self):
        stack = getattr(Context._default, "stack", None)
        if stack is None:
            stack = Context._default.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._default.stack.pop()
        return False


def _accelerators():
    # local_devices: in a multi-process job each worker addresses its
    # own chips by local id, matching the reference's per-worker
    # mx.gpu(i) semantics (global devices are not addressable)
    devs = [d for d in jax.local_devices() if d.platform != "cpu"]
    return devs


def _resolve(devtype: str, devid: int) -> jax.Device:
    if devtype in ("cpu", "cpu_pinned"):
        devs = []
        if _has_cpu():
            try:
                # local cpu-backend devices (multi-process safe)
                devs = jax.local_devices(backend="cpu")
            except RuntimeError:
                devs = []
        if not devs:
            devs = jax.local_devices()
        return devs[devid % len(devs)]
    accs = _accelerators()
    if not accs:
        # CPU fallback keeps the tpu-context test-suite runnable on the
        # 8-virtual-device CPU mesh (SURVEY.md §4 pattern 4). It also
        # means tpu(i) proves nothing about the platform: a program
        # that reports device numbers calls
        # runtime.require_accelerator() first (chip_smoke.py, bench.py).
        accs = jax.local_devices()
    if devid >= len(accs):
        raise MXNetError(
            "context %s(%d) out of range: %d device(s) visible"
            % (devtype, devid, len(accs)))
    return accs[devid]


def _has_cpu() -> bool:
    try:
        jax.devices("cpu")
        return True
    except RuntimeError:
        return False


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Compat alias — resolves to the platform accelerator (TPU)."""
    return Context("gpu", device_id)


def num_gpus() -> int:
    return len(_accelerators())


def num_tpus() -> int:
    return len(_accelerators())


def device(dev: Optional[Context] = None) -> Context:
    return dev if dev is not None else current_context()


def current_context() -> Context:
    stack = getattr(Context._default, "stack", None)
    if stack:
        return stack[-1]
    return _default_context()


_DEFAULT = None


def _default_context() -> Context:
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = tpu(0) if _accelerators() else cpu(0)
    return _DEFAULT
