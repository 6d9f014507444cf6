"""Global PRNG state and `mx.random` namespace.

Ref: src/resource.cc :: kRandom/kParallelRandom resources and
python/mxnet/random.py (mx.random.seed). TPU-first: randomness is JAX's
counter-based PRNG. One root key per device context, advanced by
splitting on every sampling op; ``seed()`` resets all of them
(mx.random.seed(s, ctx=...) resets one). Device id is folded into the
key so replicas draw independent streams, mirroring the reference's
per-GPU random resources.
"""
from __future__ import annotations

import os
import threading
from typing import Dict, Optional, Tuple

import jax

from .context import Context, current_context

__all__ = ["seed", "take_key", "uniform", "normal", "randint", "randn",
           "exponential", "poisson", "gamma", "shuffle", "multinomial"]

_lock = threading.Lock()
_seed = 0
# Default key impl: 'rbg' maps to the TPU hardware PRNG (fast path).
# Scoped to keys THIS library creates — the process-global
# jax_default_prng_impl is deliberately left untouched so importing
# mxnet_tpu does not change unrelated JAX code's random streams.
from .config import get as _cfg
_IMPL = _cfg("MXNET_PRNG_IMPL")
# one independent stream per (ctx, impl): some samplers (poisson family)
# are only implemented for threefry2x32 in JAX, so ops may request a
# specific impl via Operator.rng_impl
_keys: Dict[Tuple[Context, str], jax.Array] = {}
_ctx_seed: Dict[Context, int] = {}


def _root(seed_state: int, ctx: Context, impl: str) -> jax.Array:
    return jax.random.fold_in(jax.random.key(int(seed_state), impl=impl),
                              ctx.device_id)


def seed(seed_state: int, ctx: Optional[Context] = None):
    """Reset the PRNG (ref: mx.random.seed; MXNET seed-all behavior)."""
    global _seed
    with _lock:
        if ctx is None:
            _seed = int(seed_state)
            _keys.clear()
            _ctx_seed.clear()
        else:
            ctx = Context(ctx)
            _ctx_seed[ctx] = int(seed_state)
            for k in [k for k in _keys if k[0] == ctx]:
                del _keys[k]


def take_key(ctx: Optional[Context] = None,
             impl: Optional[str] = None) -> jax.Array:
    """Split off a fresh subkey for one sampling op on ``ctx``."""
    ctx = ctx or current_context()
    impl = impl or _IMPL
    with _lock:
        key = _keys.get((ctx, impl))
        if key is None:
            key = _root(_ctx_seed.get(ctx, _seed), ctx, impl)
        key, sub = jax.random.split(key)
        _keys[(ctx, impl)] = key
    return sub


# The user-facing sampling functions are populated by ndarray.register
# (generated from the op registry) — see mxnet_tpu/ndarray/__init__.py.
def _bind_namespace(nd):
    g = globals()
    g["uniform"] = nd.random_uniform
    g["normal"] = nd.random_normal
    g["randint"] = nd.random_randint
    g["exponential"] = nd.random_exponential
    g["poisson"] = nd.random_poisson
    g["gamma"] = nd.random_gamma
    g["shuffle"] = nd.shuffle
    g["multinomial"] = nd.sample_multinomial

    def randn(*shape, loc=0.0, scale=1.0, dtype="float32", ctx=None):
        return nd.random_normal(loc=loc, scale=scale, shape=shape,
                                dtype=dtype, ctx=ctx)
    g["randn"] = randn
