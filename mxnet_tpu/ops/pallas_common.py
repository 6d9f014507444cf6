"""Shared helpers for the Pallas kernel modules (pallas_attention,
pallas_norm, pallas_dropout, pallas_epilogue) — one
platform probe and one partitioning gate, so the decisions can never
diverge between kernels."""
from __future__ import annotations

import contextlib
import threading

import jax

__all__ = ["interpret_mode", "interpret_asked", "auto_partitioned",
           "kernels_allowed"]

_TRACING = threading.local()


def interpret_asked() -> bool:
    """True when MXNET_PALLAS_INTERPRET asks for interpreter mode."""
    from ..config import get as _cfg
    return bool(_cfg("MXNET_PALLAS_INTERPRET"))


def interpret_mode() -> bool:
    """True when Pallas kernels must run in interpreter mode: forced by
    MXNET_PALLAS_INTERPRET, or no TPU backend is attached."""
    return interpret_asked() or jax.devices()[0].platform != "tpu"


@contextlib.contextmanager
def auto_partitioned(mesh):
    """Scope in which a program that GSPMD will partition over ``mesh``
    is traced. The TPU compiler refuses a Mosaic kernel there ("Mosaic
    kernels cannot be automatically partitioned. Please wrap the call
    in a shard_map" — interpret mode lowers to plain XLA ops and never
    shows it), so with more than one device in the mesh
    :func:`kernels_allowed` is False inside the scope and every op
    takes its XLA composition, which GSPMD can partition."""
    prev = kernels_allowed()
    _TRACING.allowed = prev and mesh.size == 1
    try:
        yield
    finally:
        _TRACING.allowed = prev


def kernels_allowed() -> bool:
    """False while tracing a program GSPMD partitions over several
    devices; every ``*_available`` / plan function asks here."""
    return getattr(_TRACING, "allowed", True)
