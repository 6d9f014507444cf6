"""Contrib operators — transformer attention kernels, LM-head losses
and helpers.

Ref: src/operator/contrib/transformer.cc — the interleaved_matmul_* family
BERT uses for self-attention (one packed QKV projection, head-interleaved),
plus div_sqrt_dim, arange_like, boolean-mask helpers. On TPU these are
exactly the batched matmuls the MXU wants; XLA fuses the scaling and
softmax around them, so no Pallas is needed for the BERT sizes.

Packed QKV layout (matches the reference): (seq_len, batch,
num_heads * 3 * head_dim), per-head interleaved [q | k | v].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import telemetry
from . import pallas_attention, register


def _split_qkv(qkv, heads):
    L, N, three_hd = qkv.shape
    hd = three_hd // (3 * heads)
    x = qkv.reshape(L, N, heads, 3, hd)
    # -> (N*heads, L, hd)
    def pick(i):
        return x[:, :, :, i, :].transpose(1, 2, 0, 3).reshape(N * heads, L, hd)
    return pick(0), pick(1), pick(2), hd


@register("_contrib_interleaved_matmul_selfatt_qk")
def interleaved_matmul_selfatt_qk(queries_keys_values, *, heads):
    """scores = (Q/√d)·Kᵀ over interleaved packed QKV
    (ref: transformer.cc :: interleaved_matmul_selfatt_qk)."""
    q, k, _, hd = _split_qkv(queries_keys_values, int(heads))
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, q.dtype))
    return jnp.matmul(q * scale, jnp.swapaxes(k, -1, -2))


@register("_contrib_interleaved_matmul_selfatt_valatt")
def interleaved_matmul_selfatt_valatt(queries_keys_values, attention, *, heads):
    """out = att·V, re-packed to (L, N, heads*hd)."""
    _, _, v, hd = _split_qkv(queries_keys_values, int(heads))
    NH, L, _ = v.shape
    heads = int(heads)
    N = NH // heads
    out = jnp.matmul(attention, v)  # (N*heads, Lq, hd)
    Lq = out.shape[1]
    return out.reshape(N, heads, Lq, hd).transpose(2, 0, 1, 3).reshape(Lq, N, heads * hd)


@register("_contrib_interleaved_matmul_encdec_qk")
def interleaved_matmul_encdec_qk(queries, keys_values, *, heads):
    Lq, N, hdim = queries.shape
    heads = int(heads)
    hd = hdim // heads
    q = queries.reshape(Lq, N, heads, hd).transpose(1, 2, 0, 3).reshape(N * heads, Lq, hd)
    Lk = keys_values.shape[0]
    kv = keys_values.reshape(Lk, N, heads, 2, hd)
    k = kv[:, :, :, 0, :].transpose(1, 2, 0, 3).reshape(N * heads, Lk, hd)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, q.dtype))
    return jnp.matmul(q * scale, jnp.swapaxes(k, -1, -2))


@register("_contrib_interleaved_matmul_encdec_valatt")
def interleaved_matmul_encdec_valatt(keys_values, attention, *, heads):
    Lk, N, two_hdim = keys_values.shape
    heads = int(heads)
    hd = two_hdim // (2 * heads)
    kv = keys_values.reshape(Lk, N, heads, 2, hd)
    v = kv[:, :, :, 1, :].transpose(1, 2, 0, 3).reshape(N * heads, Lk, hd)
    out = jnp.matmul(attention, v)
    Lq = out.shape[1]
    return out.reshape(N, heads, Lq, hd).transpose(2, 0, 1, 3).reshape(Lq, N, heads * hd)


@register("_contrib_div_sqrt_dim")
def div_sqrt_dim(data):
    return data / jnp.sqrt(jnp.asarray(data.shape[-1], data.dtype))


@register("_contrib_arange_like")
def arange_like(data, *, start=0.0, step=1.0, repeat=1, axis=None):
    if axis is None:
        n = data.size
        out = jnp.arange(n, dtype=data.dtype) * step + start
        return out.reshape(data.shape)
    n = data.shape[int(axis)]
    return jnp.arange(n, dtype=data.dtype) * step + start


@register("_contrib_boolean_mask")
def boolean_mask(data, index, *, axis=0):
    # dynamic-shape op: not jittable; eager-only convenience (XLA needs
    # static shapes — prefer SequenceMask/where in compiled graphs).
    idx = jnp.nonzero(index.astype(bool))[0]
    return jnp.take(data, idx, axis=int(axis))


@register("_contrib_sdp_selfatt", needs_rng=True, needs_train_flag=True)
def sdp_selfatt(rng, queries_keys_values, *, heads, dropout=0.0,
                _train=False):
    """Fused scaled-dot-product self-attention over reference-packed
    QKV: scores -> softmax -> (train-mode) dropout -> context in one
    Pallas kernel (ops/pallas_attention.py) that consumes AND produces
    the packed layout directly — no reshape+transpose chain sits
    between the QKV projection and the kernel (the r6 transpose_jvp
    residual; the packed tests assert this on the jaxpr). The unfused
    interleaved_matmul composition is the fallback. The [L,L]
    probabilities and dropout masks never hit HBM; the backward
    recomputes them flash-style from per-block hardware-PRNG seeds.
    Counted once a traced call in
    ``mx_attn_selfatt_path_total{path="pallas"|"xla"}``."""
    L, N, thd = queries_keys_values.shape
    p = float(dropout) if _train else 0.0
    heads_i = int(heads)
    plan = pallas_attention.selfatt_plan(
        L, heads_i, N, p, dtype=queries_keys_values.dtype,
        head_dim=thd // (3 * heads_i))
    telemetry.count_event("mx_attn_selfatt_path_total",
                          path="xla" if plan is None else "pallas")
    if plan is not None:
        n_blk = plan["n_blocks"]
        if p > 0.0:
            seeds = jax.random.randint(rng, (n_blk,), 0, 2 ** 31 - 1,
                                       dtype=jnp.int32)
        else:
            seeds = jnp.zeros((n_blk,), jnp.int32)
        return pallas_attention.flash_selfatt(
            queries_keys_values, seeds, heads=heads_i, dropout=p,
            block_heads=plan["bbh"])
    scores = interleaved_matmul_selfatt_qk(queries_keys_values,
                                           heads=heads_i)
    att = jax.nn.softmax(scores, axis=-1)
    if p > 0.0:
        keep = jax.random.bernoulli(rng, 1.0 - p, att.shape)
        att = jnp.where(keep, att / (1.0 - p), 0.0).astype(att.dtype)
    return interleaved_matmul_selfatt_valatt(queries_keys_values, att,
                                             heads=heads_i)


# ---------------------------------------------------------------------------
# Dense epilogues: bias+GeLU and bias+residual as ops of their own, so
# a model names the epilogue once (gluon.nn.Dense(epilogue=...), the zoo
# BERT, the AMP lists, saved symbols). Their bodies are the plain
# compositions: XLA fuses them into the fusions of the products beside
# them, which beat the Pallas kernels that served them until PR 48 on a
# mesh and on one chip alike (docs/KERNELS.md "Fused epilogues"). GeLU's
# backward is autodiff's: a rule of its own that keeps the product's
# output alone and recomputes erf and exp saves the 512-position BERT
# step 2.2 GB and costs both one-chip cells 2 to 2.6% (PERF.md
# section 6, PR 48)
# ---------------------------------------------------------------------------
@register("_contrib_bias_gelu")
def bias_gelu(data, bias):
    """GeLU(data + bias), exact erf form: the Dense->GeLU FFN
    epilogue."""
    return jax.nn.gelu(data + bias, approximate=False)


@register("_contrib_bias_add_residual")
def bias_add_residual(data, bias, residual):
    """data + bias + residual: the projection/FFN output epilogue
    feeding the post-attention LayerNorm."""
    return data + bias + residual


# ---------------------------------------------------------------------------
# fused LM-head cross entropy (dense-vocab MLM loss)
# ---------------------------------------------------------------------------
@jax.custom_vjp
def _lm_head_ce(h2, w, b, labels):
    loss, _ = _lm_head_ce_fwd(h2, w, b, labels)
    return loss


def _lm_head_ce_fwd(h2, w, b, labels):
    # z: (T, V). f32 accumulation on the MXU; the max/LSE reductions are
    # the only consumers, so XLA keeps the logits tensor transient
    z = jax.lax.dot_general(
        h2, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) + b.astype(jnp.float32)
    m = jnp.max(z, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(z - m[:, None]), axis=-1))
    picked = jnp.take_along_axis(z, labels[:, None], 1)[:, 0]
    loss = lse - picked
    # residuals: activations + stats only — the (T, V) logits are
    # RECOMPUTED in the backward (flash-CE), never stored
    return loss, (h2, w, b, labels, lse)


def _lm_head_ce_bwd(res, dy):
    h2, w, b, labels, lse = res
    z = jax.lax.dot_general(
        h2, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) + b.astype(jnp.float32)
    p = jnp.exp(z - lse[:, None])
    onehot = jax.nn.one_hot(labels, w.shape[0], dtype=p.dtype)
    dz = ((p - onehot) * dy[:, None]).astype(h2.dtype)
    dh = jax.lax.dot_general(dz, w, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) \
        .astype(h2.dtype)
    dw = jax.lax.dot_general(dz, h2, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) \
        .astype(w.dtype)
    db = jnp.sum(dz.astype(jnp.float32), axis=0).astype(b.dtype)
    return dh, dw, db, None


_lm_head_ce.defvjp(_lm_head_ce_fwd, _lm_head_ce_bwd)


@register("_contrib_fused_lm_head_ce")
def fused_lm_head_ce(hidden, weight, bias, labels):
    """Decoder matmul + softmax cross entropy in ONE op with
    flash-style logits recomputation (TPU-native; the reference
    composes Dense + log_softmax + pick, materializing the (T, vocab)
    logits several times — at BERT's 30522 vocab that is >1 GB of HBM
    traffic per step). Forward keeps only the per-position LSE; the
    backward recomputes logits from the saved activations.

    hidden: (..., units); weight: (vocab, units) — MXNet Dense layout;
    bias: (vocab,); labels: (...) int ids with the same leading shape.
    Returns per-position loss (...,), float32.
    """
    lead = hidden.shape[:-1]
    if tuple(labels.shape) != tuple(lead):
        # a transposed-but-same-size labels array would flatten cleanly
        # into a silently wrong loss — refuse loudly (review r5)
        raise ValueError(
            "_contrib_fused_lm_head_ce: labels shape %s must equal "
            "hidden's leading shape %s" %
            (tuple(labels.shape), tuple(lead)))
    units = hidden.shape[-1]
    h2 = hidden.reshape(-1, units)
    lab = labels.reshape(-1).astype(jnp.int32)
    loss = _lm_head_ce(h2, weight, bias, lab)
    return loss.reshape(lead)


# ---------------------------------------------------------------------------
# streaming chunked LM-head cross entropy (round-6 kernel work)
#
# The r5 `--fusedce` experiment (round-5 builder figures) showed
# that recomputing the FULL-vocab logits in the backward costs more MXU
# time (~2.9 ms) than the saved logits traffic at seq 128. This op keeps
# the fused op's memory win without that loss: an online softmax over
# VOCAB CHUNKS. Forward: one (T, chunk) logits tile at a time — chunk
# matmul, running max / rescaled exp-sum, label gather — so the
# bf16[T, 30522] logits (>1 GB of HBM traffic per step across the dense
# path's four softmax passes) never fully materialize. The per-position
# LSE is carried to the backward, so the backward needs NO full-vocab
# statistics pass: each chunk's probabilities are reconstructed from its
# own (recomputed) logits tile and the saved LSE, and immediately
# consumed by that chunk's dh/dw matmuls while the tile is still
# on-chip. Total matmul FLOPs match the dense path (z, dh, dw each
# computed once); what disappears is the logits round-trips.
# ---------------------------------------------------------------------------
_NEG_BIG = -1.0e30    # pad bias: exp(_NEG_BIG - lse) underflows to 0 in f32
# device-side scope of the streaming head, forward and backward
# (docs/OBSERVABILITY.md "Device-side scopes")
HEAD_SCOPE = "mx.head.ce"


def _ce_pad(w, b, chunk):
    V, U = w.shape
    n = -(-V // chunk)
    pad = n * chunk - V
    if pad:
        w = jnp.pad(w, ((0, pad), (0, 0)))
        b = jnp.pad(b.astype(jnp.float32), (0, pad),
                    constant_values=_NEG_BIG)
    else:
        b = b.astype(jnp.float32)
    return w.reshape(n, chunk, U), b.reshape(n, chunk), n


def _ce_logits(h2, wc, bc):
    return jax.lax.dot_general(
        h2, wc, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) + bc


@functools.lru_cache(maxsize=None)
def _make_chunked_ce(chunk):
    @jax.custom_vjp
    def f(h2, w, b, labels):
        loss, _ = fwd(h2, w, b, labels)
        return loss

    def fwd(h2, w, b, labels):
        w3, b2, n = _ce_pad(w, b, chunk)
        T = h2.shape[0]
        # out-of-range ids clamp into the vocab (the reference pick's
        # default mode='clip', which the dense BERTMLMLoss path uses) —
        # fwd and bwd agree on the clamped class
        labels = jnp.clip(labels, 0, w.shape[0] - 1)

        def body(picked, xs):
            wc, bc, ci = xs
            z = _ce_logits(h2, wc, bc)                    # (T, chunk) f32
            mc = jnp.max(z, axis=1)
            sc = jnp.sum(jnp.exp(z - mc[:, None]), axis=1)
            local = labels - ci * chunk
            inchunk = (local >= 0) & (local < chunk)
            pz = jnp.take_along_axis(
                z, jnp.clip(local, 0, chunk - 1)[:, None], 1)[:, 0]
            picked = jnp.where(inchunk, pz, picked)
            return picked, (mc, sc)

        picked, (ms, ss) = jax.lax.scan(
            body, jnp.zeros((T,), jnp.float32),
            (w3, b2, jnp.arange(n, dtype=jnp.int32)))
        m = jnp.max(ms, axis=0)
        s = jnp.sum(ss * jnp.exp(ms - m), axis=0)
        lse = m + jnp.log(s)
        loss = lse - picked
        # residuals: activations + per-position LSE only — no logits,
        # and (unlike _lm_head_ce) no full-vocab pass in the backward
        return loss, (h2, w, b, labels, lse)

    def bwd(res, dy):
        # a backward rule is traced outside the scope its call was
        # made in: it opens the head's scope again
        with jax.named_scope(HEAD_SCOPE):
            return _bwd(res, dy)

    def _bwd(res, dy):
        h2, w, b, labels, lse = res
        w3, b2, n = _ce_pad(w, b, chunk)
        T, U = h2.shape
        labels = jnp.clip(labels, 0, w.shape[0] - 1)

        def body(dh, xs):
            wc, bc, ci = xs
            z = _ce_logits(h2, wc, bc)
            p = jnp.exp(z - lse[:, None])
            local = labels - ci * chunk
            inchunk = (local >= 0) & (local < chunk)
            onehot = (jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
                      == local[:, None]) & inchunk[:, None]
            # same rounding contract as the dense op: dz drops to the
            # activation dtype before feeding the MXU
            dz = ((p - onehot.astype(p.dtype)) * dy[:, None]) \
                .astype(h2.dtype)
            dh = dh + jax.lax.dot_general(
                dz, wc, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dwc = jax.lax.dot_general(
                dz, h2, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dbc = jnp.sum(dz.astype(jnp.float32), axis=0)
            return dh, (dwc, dbc)

        dh, (dws, dbs) = jax.lax.scan(
            body, jnp.zeros((T, U), jnp.float32),
            (w3, b2, jnp.arange(n, dtype=jnp.int32)))
        V = w.shape[0]
        dw = dws.reshape(n * chunk, U)[:V].astype(w.dtype)
        db = dbs.reshape(n * chunk)[:V].astype(b.dtype)
        return dh.astype(h2.dtype), dw, db, None

    f.defvjp(fwd, bwd)
    return f


def _tuned_ce_chunk(T, U, V, esize, default):
    """Consult the autotune table for the CE vocab-chunk size
    (MXNET_AUTOTUNE; off mode returns the MXNET_CHUNKED_CE_CHUNK
    default untouched). The chunk trades h2 re-reads (one per chunk,
    fwd and bwd) against the live (T, chunk) logits-tile footprint —
    total matmul FLOPs are chunk-independent."""
    from .. import autotune

    def _ce_probe(chunk):
        def build():
            h = jnp.zeros((T, U), jnp.float32)
            w = jnp.zeros((V, U), jnp.float32)
            b = jnp.zeros((V,), jnp.float32)
            lab = jnp.zeros((T,), jnp.int32)

            def fn(h, w, b):
                return jnp.sum(_make_chunked_ce(chunk)(h, w, b, lab))
            return fn, (h, w, b)
        return build

    def _candidates():
        cands = []
        # the incumbent default is ALWAYS in the grid — measure mode's
        # gate needs it as the bar (an unvetted candidate never
        # replaces an unmeasured default)
        dflt = max(1, min(int(default), V))
        grid = sorted({1024, 2048, 4096, 8192, dflt}, reverse=True)
        for chunk in grid:
            if chunk != dflt and chunk > max(V, 1024):
                continue
            n = -(-V // chunk)
            flops = 3.0 * 2.0 * T * U * V      # z, dh, dw — once each
            hbm = (3.0 * n * T * U + 2.0 * V * U) * esize
            cands.append(autotune.Candidate(
                {"chunk": chunk}, flops=flops, hbm_bytes=hbm,
                vmem_bytes=0.0,      # XLA tiles the scan body itself
                build=_ce_probe(chunk)))
        return cands

    def _valid(params):
        c = params.get("chunk")
        return isinstance(c, int) and c >= 1

    out = autotune.lookup("chunked_lm_head_ce",
                          {"T": T, "U": U, "V": V, "esize": esize},
                          {"chunk": default}, candidates=_candidates,
                          validate=_valid)
    c = out.get("chunk", default)
    return c if isinstance(c, int) and c >= 1 else default


@register("_contrib_chunked_lm_head_ce")
def chunked_lm_head_ce(hidden, weight, bias, labels, *, chunk_size=0):
    """Decoder matmul + softmax cross entropy with an ONLINE softmax
    over vocab chunks: the (positions, vocab) logits never fully
    materialize, and the backward reuses the carried per-position LSE
    instead of re-deriving full-vocab statistics (see the design note
    above; docs/KERNELS.md "Streaming chunked LM-head CE").

    hidden: (..., units); weight: (vocab, units) — MXNet Dense layout;
    bias: (vocab,); labels: (...) int ids matching hidden's leading
    shape — out-of-range ids clamp into the vocab (the reference
    pick's default mode='clip', matching the dense BERTMLMLoss path in
    both loss and gradient). chunk_size 0 reads MXNET_CHUNKED_CE_CHUNK
    (vocab is padded up to a whole number of chunks; the padding rides
    as -1e30 bias logits and contributes exact zeros). Returns
    per-position loss (...,), float32."""
    lead = hidden.shape[:-1]
    if tuple(labels.shape) != tuple(lead):
        raise ValueError(
            "_contrib_chunked_lm_head_ce: labels shape %s must equal "
            "hidden's leading shape %s" %
            (tuple(labels.shape), tuple(lead)))
    chunk = int(chunk_size)
    if chunk <= 0:
        from ..config import get as _cfg
        chunk = int(_cfg("MXNET_CHUNKED_CE_CHUNK"))
        lead_n = 1
        for s in lead:
            lead_n *= s
        chunk = _tuned_ce_chunk(lead_n, hidden.shape[-1],
                                weight.shape[0],
                                jnp.dtype(hidden.dtype).itemsize, chunk)
    chunk = max(1, min(chunk, weight.shape[0]))
    units = hidden.shape[-1]
    h2 = hidden.reshape(-1, units)
    lab = labels.reshape(-1).astype(jnp.int32)
    with jax.named_scope(HEAD_SCOPE):
        loss = _make_chunked_ce(chunk)(h2, weight, bias, lab)
    return loss.reshape(lead)


@register("_contrib_chunked_lm_head_ce_nobias")
def chunked_lm_head_ce_nobias(hidden, weight, labels, *, chunk_size=0):
    """:func:`chunked_lm_head_ce` for a head with no bias (an untied
    decoder head): the same streaming op with a zero bias."""
    return chunked_lm_head_ce(
        hidden, weight, jnp.zeros((weight.shape[0],), jnp.float32), labels,
        chunk_size=chunk_size)
