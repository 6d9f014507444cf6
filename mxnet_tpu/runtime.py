"""Runtime feature introspection (ref: python/mxnet/runtime.py ::
Features over src/libinfo.cc). Features reflect the TPU build."""
from __future__ import annotations

import collections
import os

import jax

from .base import MXNetError

__all__ = ["Feature", "Features", "feature_list", "require_accelerator",
           "enable_compile_cache"]

Feature = collections.namedtuple("Feature", ["name", "enabled"])


def _detect():
    devs = jax.devices()
    has_acc = any(d.platform != "cpu" for d in devs)
    feats = {
        "TPU": has_acc,
        "XLA": True,
        "JAX": True,
        "CUDA": False,
        "CUDNN": False,
        "NCCL": False,
        "MKLDNN": False,
        "OPENCV": False,
        "BLAS_OPEN": True,
        "DIST_KVSTORE": False,
        "INT64_TENSOR_SIZE": True,
        "SIGNAL_HANDLER": True,
        "DEBUG": False,
    }
    return {k: Feature(k, v) for k, v in feats.items()}


class Features(dict):
    def __init__(self):
        super().__init__(_detect())

    def __repr__(self):
        return "[%s]" % ", ".join(
            "✔ %s" % k if v.enabled else "✖ %s" % k for k, v in self.items())

    def is_enabled(self, name: str) -> bool:
        feat = self.get(name.upper())
        return bool(feat and feat.enabled)


def feature_list():
    return list(Features().values())


def require_accelerator():
    """The device a measurement ran on, as JAX reports it — or
    MXNetError when JAX found only the CPU. ``mx.tpu(i)`` resolves to a
    CPU device when no accelerator is visible (the CPU test mesh needs
    that), so a program whose numbers are device numbers asks here
    first instead of trusting the context."""
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise MXNetError(
            "no accelerator: JAX reports platform 'cpu' (%d device(s)); "
            "this command runs on the chip only" % len(devs))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache and return its
    directory. A directory given from outside wins (JAX fills
    ``jax_compilation_cache_dir`` from JAX_COMPILATION_CACHE_DIR);
    otherwise ``<checkout>/.jax_cache`` — fixed, because the path is
    part of the cache key. Call before the first compile."""
    path = jax.config.jax_compilation_cache_dir
    if not path:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however quick its compile was
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
