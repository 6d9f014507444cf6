"""Pallas dropout — mask RNG folded into the elementwise kernel (ref:
src/operator/nn/dropout.cc, whose CUDA path likewise fuses curand mask
generation into the scale kernel).

Why this exists (round-6 perf work; round-5 builder figures): the
BERT-base step spends 0.36 ms in standalone `rng-bit-generator` programs
producing dropout masks, plus the HBM round-trip of the masks themselves. Here
the TPU hardware PRNG (pltpu.prng_seed / prng_random_bits — the same
mechanism ops/pallas_attention.py uses for in-kernel attention dropout)
generates the keep-mask INSIDE the multiply kernel: forward reads x and
writes out, nothing else touches HBM. The backward re-seeds the same
per-block PRNG streams and regenerates the identical mask, so masks are
never stored — dy in, dx out.

Only the per-block int32 seeds (a few words) are derived from the op's
JAX PRNG key outside the kernel. pltpu's PRNG has no interpreter
implementation, so this path is TPU-only: CPU runs and ineligible
shapes fall back to the jax.random.bernoulli composition in ops/nn.py
(MXNET_PALLAS_DROPOUT gates the whole path; docs/KERNELS.md).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["pallas_dropout", "pallas_dropout_available"]


def _interpret():
    from .pallas_common import interpret_mode
    return interpret_mode()


def _pick_rows(M, C, esize):
    """Row-block fitting double-buffered in/out streams + the uint32
    mask bits in ~10 MB of VMEM."""
    per_row = C * (2 * esize + 4 + 8)
    for bm in (1024, 512, 256, 128, 64, 32, 16):
        if M % bm:
            continue
        if bm * per_row * 2 <= 10 * 1024 * 1024:
            return bm
    return None


def pallas_dropout_available(shape, dtype, p):
    """True when the in-kernel-PRNG dropout can serve this call."""
    from ..config import get as _cfg
    from .pallas_common import split_of
    if not _cfg("MXNET_PALLAS_DROPOUT"):
        return False
    if _interpret():
        return False          # pltpu PRNG has no interpreter impl
    if not (0.0 < p < 1.0):
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float16)):
        return False
    if len(shape) < 2:
        return False
    split = split_of("pallas_dropout", shape)
    if not split:
        return False
    shape = split.local(shape)      # a shard's rows decide the tiling
    C = shape[-1]
    M = 1
    for s in shape[:-1]:
        M *= s
    if M < 16 or C % 128:
        return False
    return _pick_rows(M, C, jnp.dtype(dtype).itemsize) is not None


@functools.lru_cache(maxsize=None)
def _drop_call(M, C, bm, p, dtype_name, backward, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dtype = jnp.dtype(dtype_name)
    keep = 1.0 - p
    # keep iff bits >= thresh, matching the attention kernel's contract
    thresh = min(int(p * 2 ** 32), 2 ** 32 - 1)
    inv_keep = 1.0 / keep

    def pallas_dropout_kernel(seed_ref, x_ref, o_ref):
        i = pl.program_id(0)
        pltpu.prng_seed(seed_ref[i])
        bits = pltpu.prng_random_bits((bm, C))
        keep_mask = bits.astype(jnp.uint32) >= jnp.uint32(thresh)
        xv = x_ref[:].astype(jnp.float32)
        o_ref[:] = jnp.where(keep_mask, xv * inv_keep, 0.0) \
            .astype(o_ref.dtype)

    pallas_dropout_kernel.__name__ = (
        "pallas_dropout_bwd" if backward else "pallas_dropout_fwd")
    return pl.pallas_call(
        pallas_dropout_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(M // bm,),
            in_specs=[pl.BlockSpec((bm, C), lambda i, seeds: (i, 0))],
            out_specs=pl.BlockSpec((bm, C), lambda i, seeds: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, C), dtype),
        interpret=interpret,
        name=pallas_dropout_kernel.__name__,
    )


@functools.lru_cache(maxsize=None)
def _make_op(M, C, bm, p, dtype_name):
    @jax.custom_vjp
    def f(x2, seeds):
        call = _drop_call(M, C, bm, p, dtype_name, False, _interpret())
        return call(seeds, x2)

    def fwd(x2, seeds):
        return f(x2, seeds), seeds

    def bwd(seeds, dy):
        # same seeds -> the re-generated mask is bit-identical to the
        # forward's; dropout backward IS the forward applied to dy
        call = _drop_call(M, C, bm, p, dtype_name, True, _interpret())
        return (call(seeds, dy),
                jnp.zeros(seeds.shape, jax.dtypes.float0))

    f.defvjp(fwd, bwd)
    return f


def _tuned_rows(M, C, esize, default):
    """Consult the autotune table for the dropout row-block size via
    the shared row-block helper (MXNET_AUTOTUNE; off mode returns the
    _pick_rows default untouched). Probe programs need the TPU
    hardware PRNG, so candidates carry no build — they score on their
    analytic roofline only."""
    from .. import autotune
    return autotune.tuned_rows(
        "pallas_dropout", M, C, esize, default,
        C * (2 * esize + 4 + 8), floor=16,
        flops=2.0 * M * C, hbm_bytes=2.0 * M * C * esize)


def pallas_dropout(rng, data, p):
    """Inverted dropout with in-kernel mask generation.

    rng: JAX PRNG key (only used to derive per-block int32 seeds);
    data: (..., C) with the availability rules already checked;
    p: drop probability. Returns data-shaped output in data.dtype.

    In a program partitioned over a mesh the kernel runs once a shard
    (pallas_common.per_shard) on the shard's own slice of the seeds:
    one seed a row block of every shard is drawn here, outside, so no
    two shards draw one mask, and the backward (the transpose of the
    forward's ``shard_map``) regenerates a shard's mask from the slice
    the forward saved there."""
    from .pallas_common import per_shard, split_of
    split = split_of("pallas_dropout", data.shape)
    local = split.local(data.shape)
    C = local[-1]
    M = math.prod(local[:-1])
    esize = jnp.dtype(data.dtype).itemsize
    bm = _tuned_rows(M, C, esize, _pick_rows(M, C, esize))
    seeds = jax.random.randint(rng, (split.shards * (M // bm),), 0,
                               2 ** 31 - 1, dtype=jnp.int32)
    f = _make_op(M, C, bm, float(p), jnp.dtype(data.dtype).name)

    def block(x, seeds):
        return f(x.reshape(M, C), seeds).reshape(x.shape)

    return per_shard("pallas_dropout", split, block, data, seeds)
