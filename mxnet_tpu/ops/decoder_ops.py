"""Ops of a decoder layer stack: RMSNorm (plain, and gated over
groups), the causal depthwise conv and the chunked state-space (SSD)
scan of a Mamba-2 mixer, a gated short convolution (two element-wise
gates around that conv, with no bias and any kernel length, as the
whole mixer), rotary positions (one axis, or sectioned over
several; plain, or slowed pair by pair by YaRN; over a whole head or
its first lanes), blocked causal
grouped-query attention over every earlier key or over a sliding window
of them, the same attention over the keys a learned selector keeps (an
index score for every causal pair, the exact ``top_k`` largest a query,
and a second loss that trains the selector), latent attention (queries
and keys / values through low-rank bottlenecks, one rotary key head
shared by every query head), a dropless expert layer that is told which
experts of the router's range it holds, a dense gated MLP, and the two
pieces a multi-token-prediction module adds to a stack (the product that
combines the next token's embedding with the stack's hidden state, and
the sum of the two losses). (Dao & Gu, arXiv:2405.21060 sec. 6-7 for
the scan; DeepSeek-V3.2-Exp's sparse attention for the selector;
DeepSeek-V2, arXiv:2405.04434 sec. 2.1 for latent attention and
DeepSeek-V3, arXiv:2412.19437 sec. 2.2 for multi-token prediction; the
layer equations are those of docs/KERNELS.md "Hybrid decoder ops".)

All but four are XLA compositions, which a GSPMD mesh partitions like
any other op. The Mamba-2 scan has two forms of one algorithm
(:func:`_scan`): a pair of Pallas kernels that keep a chunk's decays
and the carried state in VMEM (``ops/pallas_ssd.py``) where the call is
one they can serve, the chunked composition here everywhere else
(:func:`_ssd`). Causal attention has two schedules of one algorithm: a
Pallas flash kernel (``ops/pallas_causal_gqa.py``) where the call is
one it can serve, the blocked composition here everywhere else
(:func:`_attend`). Attention over a selector's keys likewise
(:func:`_sparse_attend`): thresholds, ties and mask are always the XLA
code here; the attention over the selected set runs in flash kernels
that take the set as a mask (``ops/pallas_sparse_gqa.py``), fed where
they can serve by kernels that sum the index scores over their heads in
VMEM (``ops/pallas_index_scores.py``), or as the masked composition.
The expert buffer's products likewise
(:func:`_blocks_product`): grouped kernels that read each block's
weight tiles from the experts' own arrays
(``ops/pallas_grouped_mlp.py``), or the batched product over gathered
copies of them; and the sum of a token's rows back out of the buffer
(:func:`_sum_slots`): a kernel that reads the sorted buffer in short
windows (``ops/pallas_moe_rows.py``), or XLA's gather of every slot's
row. Matrix products take their inputs in the dtype they
are given (bf16 inside ``ShardedTrainStep``) and accumulate in float32;
decays, softmax, norms and the router are computed in float32.

Eight *mixer* ops (``_contrib_mamba2_mixer``,
``_contrib_short_conv_mixer``, ``_contrib_moe_mixer``,
``_contrib_glu_mlp_mixer``, ``_contrib_gqa_mixer``,
``_contrib_rotary_gqa_mixer``, ``_contrib_mla_mixer``,
``_contrib_sparse_gqa_mixer``) hold a whole pre-norm mixer each,
``mixer(RMSNorm(x))``, and are where recomputation lives: the Mamba-2,
short-convolution, expert, dense gated, rotary, latent and
sparse-attention mixers are
``jax.checkpoint``-ed, so a training step keeps their input and
recomputes their inside in the backward. One rule says what else is
kept: *a recomputed mixer keeps, beside its input, the output of a
product that reads its normed input, where the mixer's next stage reads
that output as it lies* (the backward needs it anyway, so running the
product again is one whole product a mixer a step for an array small
beside what the steps leave free); element-wise work, norms, rotary,
gates, the convolution's taps and the scan are run again. It holds for
three mixers, counted once a traced call in
``mx_mixer_kept_total{mixer=}``: the short-convolution one keeps
``W_in``'s output (its gates read it in its own dtype), the Mamba-2 one
``in_proj``'s, the rotary one its v projection (the attention kernel's
operand as it is). The rotary mixer's q and k are not kept: they are
normed and turned from the product's float32 accumulator, which XLA
carries through that element-wise work, so a kept rounded copy is a
second array for the forward to write and one the compiler lays out
against the rest; before the norms and the turn, or after the turn, it
measured slower than running the two products again (PERF.md section
6, PR 52).
The others keep what they kept, each for a reason in numbers (PERF.md
section 7): the latent mixer's products on its normed input are the two
small down-projections and its expansions read the latents (1.3 GB in a
cell 2.3 GB under the chip); the sparse mixer's cell stands 16 MB under
the chip; the dense gated mixer's ``gate_up`` output is 1.54 GB in the
LFM2 cell, whose step the rule's own 1.8 GB already brings to 14.6 GB;
the expert mixer's large products read the routed buffer, whose ``pre``
a chunk already keeps (``_HIDDEN``); of its routing it keeps the
integers and a weight a row (``_ROUTED``). The rotary and the latent mixer
also keep their context and, on the kernel path, the rows'
log-sum-exp; the sparse one those and each row's selection threshold,
so neither the search nor a second pass of the attention is repeated;
the NoPE attention mixer keeps its q/k/v/context (and, on the kernel
path, the rows' log-sum-exp) and recomputes each query block's scores.
The device-side scopes ``mx.mamba2``, ``mx.mamba2.ssd``, ``mx.conv``
(the short-convolution mixer; inside it ``mx.conv.gate``, both gates
and the taps between its two projections), ``mx.moe``,
``mx.moe.experts``, ``mx.mlp``, ``mx.attn.causal``, ``mx.attn.window``,
``mx.attn.rotary`` and ``mx.attn.mla`` (the rotary and the latent
mixer, around the attention's own scope; inside the rotary one
``mx.attn.gate``, a gate a head on the context, where a model has
one), ``mx.attn.dsa`` (inside it
``mx.attn.index``, ``mx.attn.select``, ``mx.attn.sparse``) and
``mx.mtp`` (what a multi-token-prediction module adds outside its
block) name their instructions in the compiled program (forward,
recomputation and backward alike).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .. import telemetry
from . import (pallas_causal_gqa, pallas_grouped_mlp, pallas_index_scores,
               pallas_moe_rows, pallas_sparse_gqa, pallas_ssd, register)

F32 = jnp.float32
_HI = lax.Precision.HIGHEST

# Sizes that change speed and memory, never a result (constants, not op
# attributes: no caller has a reason to set them; mxbench's counts of
# what the scopes execute import them)
QUERY_BLOCK = 512       # attention: queries a block
BLOCK_ROWS = 512        # experts: rows a block of the sorted buffer
CAPACITY_FACTOR = 2.0   # experts: the buffer over the held experts' even share
BLOCKS_AT_ONCE = 48     # experts: the most blocks one batched product takes
BLOCKS_A_CHUNK = 24     # experts: blocks a chunk of a larger buffer


def _mm(spec, a, b):
    """einsum on the MXU: inputs as given, float32 accumulation."""
    return jnp.einsum(spec, a, b, preferred_element_type=F32)


def _dense(x, w):
    """x (..., in) @ w (out, in)^T in x's dtype (MXNet Dense layout)."""
    return _mm("...i,oi->...o", x, w).astype(x.dtype)


# ---------------------------------------------------------------------------
# what a recomputed mixer keeps
# ---------------------------------------------------------------------------
_IN_KEPT = "mx.mixer.in.kept"   # the product that reads a mixer's normed input


def _in_product(x, w):
    """:func:`_dense` of a mixer's normed input, its output named for
    :func:`_recomputed_but_in_product`."""
    return checkpoint_name(_dense(x, w), _IN_KEPT)


def _recomputed_but_in_product(body, mixer, *also):
    """``jax.checkpoint`` of a mixer's ``body`` by the module's rule: a
    step keeps the mixer's arguments, what the body names ``_IN_KEPT``
    (:func:`_in_product`) and the names ``also``; the backward runs
    everything else again. Counted once a traced call in
    ``mx_mixer_kept_total{mixer=}``."""
    telemetry.count_event("mx_mixer_kept_total", mixer=mixer)
    return jax.checkpoint(
        body, policy=jax.checkpoint_policies.save_only_these_names(
            _IN_KEPT, *also))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def _rms(x, w, eps):
    xf = x.astype(F32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(F32)).astype(x.dtype)


def _gated_rms(y, z, w, group, eps):
    g = y.astype(F32) * jax.nn.silu(z.astype(F32))
    gg = g.reshape(g.shape[:-1] + (g.shape[-1] // group, group))
    gg = gg * lax.rsqrt(jnp.mean(gg * gg, -1, keepdims=True) + eps)
    return (gg.reshape(g.shape) * w.astype(F32)).astype(y.dtype)


@register("_contrib_rms_norm")
def rms_norm(data, gamma, *, eps=1e-5):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis, in
    float32, returned in ``data``'s dtype."""
    return _rms(data, gamma, float(eps))


@register("_contrib_gated_rms_norm")
def gated_rms_norm(data, gate, gamma, *, group_size, eps=1e-5):
    """``RMSNorm_grouped(data * silu(gate)) * gamma``: the mean square
    is taken over each run of ``group_size`` channels of the last
    axis (Mamba-2's gated norm with the gate applied first)."""
    return _gated_rms(data, gate, gamma, int(group_size), float(eps))


# ---------------------------------------------------------------------------
# Mamba-2
# ---------------------------------------------------------------------------
def _causal_conv1d(x, w, b=None, dtype=F32, segments=None):
    """The taps' products and their sum in ``dtype``, the result in
    ``x``'s. With ``segments`` (batch, length) a tap whose source token
    carries another id than the token it is summed into adds 0."""
    k, length = w.shape[1], x.shape[1]
    xp = jnp.pad(x.astype(dtype), ((0, 0), (k - 1, 0), (0, 0)))
    wf = w.astype(dtype)
    y = None if b is None else b.astype(dtype)
    if segments is not None:
        sp = jnp.pad(segments, ((0, 0), (k - 1, 0)))
    for j in range(k):
        tap = xp[:, j:j + length, :] * wf[:, j]
        if segments is not None and j < k - 1:
            tap = jnp.where((sp[:, j:j + length] == segments)[..., None],
                            tap, 0)
        y = tap if y is None else y + tap
    return y.astype(x.dtype)


@register("_contrib_causal_conv1d")
def causal_conv1d(data, weight, bias=None, segment_ids=None):
    """Causal depthwise conv over time: data (batch, length, channels),
    weight (channels, k) for any kernel length ``k``, bias (channels,)
    or none; ``y[t] = bias + sum_j weight[:, j] * data[t - (k-1) + j]``
    with zeros before the start, summed in float32. With
    ``segment_ids`` (batch, length; integers, one id a document of a
    packed row) the taps also read zeros before the start of ``t``'s
    document: a term whose source token has another id is left out."""
    return _causal_conv1d(data, weight, bias, segments=segment_ids)


def _document_starts(segments):
    """(batch, length) bool: the tokens whose id is not the one before
    theirs (a row's first token is no start: nothing precedes it)."""
    return jnp.pad(segments[:, 1:] != segments[:, :-1], ((0, 0), (1, 0)))


def _ssd(x, dt, a_neg, bm, cm, d_skip, chunk, reset=None):
    b, length, heads, p = x.shape
    groups, n = bm.shape[2], bm.shape[3]
    rep = heads // groups
    q = int(chunk)
    pad = (-length) % q
    if pad:     # dt 0 there: the state is carried unchanged, y is cut
        x, dt, bm, cm = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) *
                                 (v.ndim - 2)) for v in (x, dt, bm, cm))
        if reset is not None:
            reset = jnp.pad(reset, ((0, 0), (0, pad)))
    nc = (length + pad) // q
    dtf = dt.astype(F32)
    # log-decays, cumulative inside each chunk: (b, nc, heads, q)
    acs = jnp.cumsum(pallas_ssd.log_decays(dtf, a_neg, reset)
                     .reshape(b, nc, q, heads).transpose(0, 1, 3, 2), axis=-1)
    xdt = (x.astype(F32) * dtf[..., None]).astype(x.dtype) \
        .reshape(b, nc, q, groups, rep, p)
    bc = bm.reshape(b, nc, q, groups, n)
    cc = cm.reshape(b, nc, q, groups, n)

    # inside a chunk: y_i += sum_{j<=i} exp(acs_i - acs_j) (C_i.B_j) dt_j x_j
    seg = acs[..., :, None] - acs[..., None, :]
    tri = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf)) \
        .reshape(b, nc, groups, rep, q, q)
    scores = _mm("bcign,bcjgn->bcgij", cc, bc)
    mix = (scores[:, :, :, None] * decay).astype(x.dtype)
    y = _mm("bcgrij,bcjgrp->bcigrp", mix, xdt)

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(acs[..., -1:] - acs).transpose(0, 1, 3, 2) \
        .reshape(b, nc, q, groups, rep)
    xw = (xdt.astype(F32) * to_end[..., None]).astype(x.dtype)
    states = _mm("bcjgn,bcjgrp->bcgrpn", bc, xw)

    # the state entering chunk c: sum_{c'<c} exp(sum_{c'<k<c} a_k) states_c'
    total = acs[..., -1].reshape(b, nc, groups, rep)
    if reset is not None:
        # a chunk that holds a start lets nothing through, however many
        # it holds: one start's worth keeps the sums over chunks small
        total = jnp.maximum(total, -pallas_ssd.RESET)
    cs = jnp.cumsum(total, axis=1)
    before = jnp.concatenate([jnp.zeros_like(cs[:, :1]), cs[:, :-1]], 1)
    gap = before[:, :, None] - cs[:, None, :]         # (b, c, c', g, r)
    low = jnp.tril(jnp.ones((nc, nc), bool), -1)[None, :, :, None, None]
    carry = jnp.exp(jnp.where(low, gap, -jnp.inf))
    entering = jnp.einsum("bcdgr,bdgrpn->bcgrpn", carry, states,
                          precision=_HI)
    into = jnp.exp(acs).transpose(0, 1, 3, 2).reshape(b, nc, q, groups, rep)
    y = y + _mm("bcign,bcgrpn->bcigrp", cc, entering.astype(x.dtype)) \
        * into[..., None]

    y = y.reshape(b, nc * q, heads, p)[:, :length]
    y = y + x[:, :length].astype(F32) * d_skip.astype(F32)[:, None]
    return y.astype(x.dtype)


def _scan(x, dt, a_neg, bm, cm, d_skip, chunk, segments=None):
    """The scan by whichever form the call allows, chosen from what can
    be observed here and nothing else: the kernels of
    ``ops/pallas_ssd.py`` (a chunk's decays and mix and the carried
    state in VMEM only) for bf16 x / B / C whose group of heads, state
    and chunk are whole lane tiles, traced for one device
    (``pallas_ssd.ssd_available``); the composition :func:`_ssd` for
    everything else. Counted once a traced call in
    ``mx_mamba2_ssd_path_total{path="pallas"|"xla"}``; the device-side
    scope is ``mx.mamba2.ssd`` either way. With ``segments`` (batch,
    length) the state is reset where the id changes: both forms take
    their log-decays from one function (``pallas_ssd.log_decays``),
    which puts ``-RESET`` in a document's first step's place, so
    neither the kernels nor the composition's products know of
    documents."""
    kernel = pallas_ssd.ssd_available(x, bm, cm, chunk)
    telemetry.count_event("mx_mamba2_ssd_path_total",
                          path="pallas" if kernel else "xla")
    with jax.named_scope(pallas_ssd.SCOPE):
        form = pallas_ssd.ssd_scan if kernel else _ssd
        return form(x, dt, a_neg, bm, cm, d_skip, chunk,
                    None if segments is None else _document_starts(segments))


@register("_contrib_ssd_scan")
def ssd_scan(data, dt, a, b, c, d, segment_ids=None, *, chunk_size=128):
    """The selective state-space recurrence of Mamba-2 in its chunked
    matrix form. data (batch, length, heads, head_dim); dt (batch,
    length, heads), already positive; a (heads,), negative; b, c
    (batch, length, groups, state), head h reading group
    ``h // (heads // groups)``; d (heads,). Per head, from a zero state,

        S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T
        y_t = S_t C_t + d x_t

    computed chunk by chunk (``chunk_size`` steps: products inside a
    chunk, one carried state between chunks); any length (the tail is
    padded with dt = 0). Two forms of one algorithm (:func:`_scan`).
    With ``segment_ids`` (batch, length; integers, non-decreasing along
    a row) ``S_{t-1}`` is taken as 0 at every ``t`` whose id differs
    from ``t - 1``'s: each document of a packed row starts from the
    zero state. The reset is a decay of ``exp(-RESET)`` = 4e-18 in that
    step's place (``pallas_ssd.log_decays``), not a mask: what crosses
    a boundary is under float32's resolution of anything it meets."""
    return _scan(data, dt, a, b, c, d, chunk_size, segment_ids)


def _mamba2(u, norm_w, in_w, conv_w, conv_b, dt_bias, a_log, d_skip,
            gate_norm_w, out_w, segments=None, *, heads, head_dim, groups,
            state, chunk, eps):
    b, length, _ = u.shape
    inner, gn = heads * head_dim, groups * state
    zxbcdt = _in_product(_rms(u, norm_w, eps), in_w)
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:2 * inner + 2 * gn]
    dt = zxbcdt[..., 2 * inner + 2 * gn:]
    xbc = jax.nn.silu(_causal_conv1d(xbc, conv_w, conv_b, segments=segments)
                      .astype(F32)).astype(u.dtype)
    x = xbc[..., :inner].reshape(b, length, heads, head_dim)
    bm = xbc[..., inner:inner + gn].reshape(b, length, groups, state)
    cm = xbc[..., inner + gn:].reshape(b, length, groups, state)
    dt = jax.nn.softplus(dt.astype(F32) + dt_bias.astype(F32))
    y = _scan(x, dt, -jnp.exp(a_log.astype(F32)), bm, cm, d_skip, chunk,
              segments)
    y = _gated_rms(y.reshape(b, length, inner), z, gate_norm_w,
                   inner // groups, eps)
    return _dense(y, out_w)


@register("_contrib_mamba2_mixer")
def mamba2_mixer(data, norm_gamma, in_proj_weight, conv_weight, conv_bias,
                 dt_bias, a_log, d, gate_norm_gamma, out_proj_weight,
                 segment_ids=None, *, num_heads, head_dim, n_groups,
                 state_size, chunk_size=128, eps=1e-5):
    """A pre-norm Mamba-2 mixer, ``mixer(RMSNorm(data))``: in_proj to
    ``[z | xBC | dt]``, causal depthwise conv + SiLU over xBC, softplus
    dt, the SSD scan (:func:`ssd_scan`), the gated grouped RMSNorm and
    out_proj. data (batch, length, hidden). Recomputed in the backward
    (``jax.checkpoint``) but for in_proj's output, which a step keeps
    beside ``data`` (``2 x inner + 2 x groups x state + heads`` values a
    token, by the module's rule): the backward runs the norm, the conv,
    SiLU, softplus, the scan's forward and the gated norm again, never
    in_proj (out_proj's product is dead in the recomputation). With
    ``segment_ids`` (batch, length; integers, non-decreasing along a
    row: the documents of a packed row) the conv's taps and the scan's
    state stop at a document's first token (:func:`causal_conv1d`,
    :func:`ssd_scan`); the projections, the norms and the gate are a
    token's own and need no ids."""
    fn = _recomputed_but_in_product(lambda *arrays: _mamba2(
        *arrays, heads=int(num_heads), head_dim=int(head_dim),
        groups=int(n_groups), state=int(state_size), chunk=int(chunk_size),
        eps=float(eps)), "mamba2")
    ids = () if segment_ids is None else (segment_ids,)
    with jax.named_scope("mx.mamba2"):
        return fn(data, norm_gamma, in_proj_weight, conv_weight, conv_bias,
                  dt_bias, a_log, d, gate_norm_gamma, out_proj_weight, *ids)


@register("_contrib_count_documents", num_outputs=1, mutate_aux={1: 1})
def count_documents(segment_ids, seq_documents):
    """``segment_ids`` (batch, length; integers, one id a document of a
    packed row) as they are, for the mixers that take them; into the
    auxiliary state ``seq_documents`` (1,) float32 (written, never
    differentiated) goes the number of documents a row holds, one more
    than the places where the id changes, mean over the batch."""
    starts = jnp.sum(_document_starts(segment_ids).astype(F32), axis=1)
    return segment_ids, (1.0 + jnp.mean(starts)).reshape(1)


# ---------------------------------------------------------------------------
# a gated short convolution
# ---------------------------------------------------------------------------
def _short_conv(data, norm_w, in_w, conv_w, out_w, *, eps):
    bcu = _in_product(_rms(data, norm_w, eps), in_w)
    # between the two products nothing but element-wise work, in the
    # products' own dtype: in float32 with one rounding at the end the
    # mixer measured 33.7 ms forward + backward where this takes 30.4
    # (4 x 8,192 tokens on a v5e), and the cell's step 545 ms for 530
    # (PERF.md section 6, PR 47)
    with jax.named_scope("mx.conv.gate"):
        b, c, u = jnp.split(bcu, 3, axis=-1)
        y = c * _causal_conv1d(b * u, conv_w, dtype=bcu.dtype)
    return _dense(y, out_w)


@register("_contrib_short_conv_mixer")
def short_conv_mixer(data, norm_gamma, in_weight, conv_weight, out_weight, *,
                     eps=1e-5):
    """A pre-norm gated short-convolution mixer (LFM2's ``conv`` layer),
    ``mixer(h)``, ``h = RMSNorm(data)``: ``[B ; C ; u] = W_in h`` (three
    runs of ``channels`` values, in that order; in_weight (3 x channels,
    hidden)), ``z = B * u``, ``c_t = sum_j w_j z_{t-(k-1)+j}`` (depthwise
    and causal: conv_weight (channels, k), one filter a channel, zeros
    before the sequence's start, no bias; :func:`causal_conv1d`), ``y_t
    = W_out (C_t * c_t)`` (out_weight (hidden, channels)). No bias and no
    activation function: every product but the two projections is
    element-wise. data (batch, length, hidden). The two gates and the
    taps are computed in ``data``'s dtype (bf16 inside
    ``ShardedTrainStep``: each product and the taps' sum rounded, as
    the projections' outputs are). Recomputed in the backward
    (``jax.checkpoint``) but for ``W_in``'s output ``[B ; C ; u]``,
    which a step keeps beside ``data`` (by the module's rule): the
    backward runs the norm, both gates and the taps again (element-wise
    work that is not worth three more arrays of ``channels`` a token),
    never ``W_in`` (``W_out``'s product is dead in the recomputation).
    The device scope is ``mx.conv``; the part between the two
    projections (both gates and the taps) stands under ``mx.conv.gate``
    inside it, forward, recomputation and backward alike."""
    fn = _recomputed_but_in_product(
        functools.partial(_short_conv, eps=float(eps)), "conv")
    with jax.named_scope("mx.conv"):
        return fn(data, norm_gamma, in_weight, conv_weight, out_weight)


# ---------------------------------------------------------------------------
# causal grouped-query attention
# ---------------------------------------------------------------------------
def _causal_gqa(q, k, v, block, window=None, segments=None):
    b, length, heads, d = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, length, kv, heads // kv, d)
    scale = 1.0 / math.sqrt(d)

    @jax.checkpoint
    def rows(qb, kb, vb, first, *ids):
        # one block of queries against its prefix of keys (``first``:
        # the first query's position among the keys handed in; ``ids``:
        # the queries' and the keys' document ids, or nothing)
        s = _mm("bqgrd,bkgd->bgrqk", qb, kb) * scale
        qi = first + lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
        ki = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
        seen = ki <= qi
        if window is not None:
            seen = seen & (qi - ki < window)
        if ids:
            sq, sk = ids
            seen = seen & (sq[:, None, None, :, None]
                           == sk[:, None, None, None, :])
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _mm("bgrqk,bkgd->bqgrd", p.astype(vb.dtype), vb) \
            .astype(qb.dtype)

    out = []
    for lo in range(0, length, block):
        hi = min(lo + block, length)
        # with a window, only the keys of the block's band
        start = 0 if window is None else max(lo - window + 1, 0)
        ids = () if segments is None else (segments[:, lo:hi],
                                           segments[:, start:hi])
        out.append(rows(qg[:, lo:hi], k[:, start:hi], v[:, start:hi],
                        lo - start, *ids))
    return jnp.concatenate(out, axis=1).reshape(b, length, heads, d)


def _attend(q, k, v, window=None, keep=None, segments=None, scale=None):
    """Causal GQA by whichever schedule the call allows, chosen from
    what can be observed here and nothing else: the flash kernel for
    bf16 q / k / v with a head width of whole lane tiles (or of half a
    tile, 64 lanes, two heads of an even group a step), whole groups
    of query heads and a length of whole ``QUERY_BLOCK`` tiles, traced
    for one device (``pallas_causal_gqa.causal_gqa_available``); the
    blocked composition for everything else. With ``window`` query
    ``t`` sees the keys ``t - window < s <= t``, and both schedules
    leave the keys before a query block's band alone. Counted once a
    traced call in ``mx_attn_causal_path_total{path="pallas"|"xla"}``,
    a windowed call in ``mx_attn_window_path_total`` instead; the
    device-side scope is ``mx.attn.causal`` or ``mx.attn.window``
    likewise. ``keep`` names the context (and the kernel's log-sum-exp)
    for a caller's ``jax.checkpoint`` policy. With ``segments`` (batch,
    length; integers) a query sees only the keys whose id is its own,
    by a third mask in either schedule; such a call is counted in
    ``mx_attn_segments_path_total{path=}`` as well. ``scale`` is the
    scores' factor where it is not ``1 / sqrt(d)``: q is multiplied by
    ``scale * sqrt(d)`` here, in its own dtype, and both schedules keep
    their ``1 / sqrt(d)`` (a power of two is exact; any other factor
    rounds q once)."""
    kernel = pallas_causal_gqa.causal_gqa_available(q, k, v, QUERY_BLOCK,
                                                    segments)
    windowed = window is not None
    path = "pallas" if kernel else "xla"
    telemetry.count_event("mx_attn_window_path_total" if windowed
                          else "mx_attn_causal_path_total", path=path)
    if segments is not None:
        telemetry.count_event("mx_attn_segments_path_total", path=path)
    with jax.named_scope(pallas_causal_gqa.WINDOW_SCOPE if windowed
                         else pallas_causal_gqa.SCOPE):
        if scale is not None:
            q = q * jnp.asarray(float(scale) * math.sqrt(q.shape[-1]),
                                q.dtype)
        if kernel:
            return pallas_causal_gqa.flash_causal_gqa(
                q, k, v, QUERY_BLOCK, window, keep, segments)
        ctx = _causal_gqa(q, k, v, QUERY_BLOCK, window, segments)
        return ctx if keep is None else checkpoint_name(ctx, keep)


@register("_contrib_causal_gqa_attention")
def causal_gqa_attention(query, key, value, segment_ids=None, *, scale=None):
    """Causal ``softmax(Q K^T / sqrt(d)) V`` with grouped keys and
    values and no positional term: query (batch, length, heads, d),
    key / value (batch, length, kv_heads, d), query head h reading
    key-value head ``h // (heads // kv_heads)``. Queries are taken
    ``QUERY_BLOCK`` at a time, each block against the keys up to its own
    end only (the masked upper triangle is not computed beyond the
    diagonal block), so no length x length array exists; each block's
    scores are recomputed in the backward (:func:`_attend`: in VMEM by
    the flash kernel, through HBM by the composition). With
    ``segment_ids`` (batch, length; integers, one id a document of a
    packed row) query ``t`` sees the keys ``s <= t`` of its own
    document only. ``scale`` replaces ``1 / sqrt(d)`` (a model whose
    ``attention_multiplier`` is its own; :func:`_attend` says how)."""
    return _attend(query, key, value, segments=segment_ids, scale=scale)


@register("_contrib_gqa_mixer")
def gqa_mixer(data, norm_gamma, q_weight, k_weight, v_weight, o_weight,
              segment_ids=None, *, num_heads, num_kv_heads, head_dim,
              scale=None, eps=1e-5):
    """A pre-norm attention mixer, ``mixer(RMSNorm(data))``: bias-free
    q/k/v projections, :func:`causal_gqa_attention` (its
    ``segment_ids`` and ``scale`` likewise), bias-free output
    projection. data (batch, length, hidden). The score blocks are
    recomputed in the backward; q, k, v and the context are kept."""
    b, length, _ = data.shape
    h, kv, d = int(num_heads), int(num_kv_heads), int(head_dim)
    x = _rms(data, norm_gamma, float(eps))
    q = _dense(x, q_weight).reshape(b, length, h, d)
    k = _dense(x, k_weight).reshape(b, length, kv, d)
    v = _dense(x, v_weight).reshape(b, length, kv, d)
    ctx = _attend(q, k, v, segments=segment_ids, scale=scale)
    return _dense(ctx.reshape(b, length, h * d), o_weight)


# ---------------------------------------------------------------------------
# rotary positions
# ---------------------------------------------------------------------------
def _yarn_ramp(pairs, theta, factor, original_length, beta_fast, beta_slow):
    """(pairs,) float32 in [0, 1]: how far frequency pair ``i`` is
    slowed (0: turns as trained, 1: ``factor`` times slower). A pair
    that makes ``r`` turns over the original length sits at ``c(r) =
    pairs ln(original_length / (2 pi r)) / ln(theta)``; the ramp rises
    linearly from ``floor(c(beta_fast))`` to ``ceil(c(beta_slow))``
    (Peng et al., arXiv:2309.00071 sec. 3.2, as the public ``rope_type:
    yarn`` rule truncates them)."""
    def pair_of(turns):
        return pairs * math.log(original_length / (turns * 2 * math.pi)) \
            / math.log(theta)

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), 2 * pairs - 1)
    if low == high:
        high += 0.001
    return jnp.clip((jnp.arange(pairs, dtype=F32) - low) / (high - low), 0, 1)


def _rotary_angles(positions, pairs, theta, sections=(), yarn=()):
    """(batch, length, pairs) float32: frequency pair ``i`` turns by
    ``position * f_i``, ``f_i = theta^(-i / pairs)``. positions (batch,
    length), or (axes, batch, length) with ``sections`` (pairs an axis,
    in order, summing to ``pairs``): pair ``i`` then reads the axis
    whose run holds it (M-RoPE: time, height, width). With ``yarn``
    (factor, original length, beta_fast, beta_slow) pair ``i`` turns by
    ``f_i (1 - g_i) + f_i / factor * g_i`` instead, ``g`` the ramp of
    :func:`_yarn_ramp`."""
    inv = float(theta) ** (-jnp.arange(pairs, dtype=F32) / pairs)
    if yarn:
        factor, original_length, beta_fast, beta_slow = yarn
        ramp = _yarn_ramp(pairs, float(theta), float(factor),
                          float(original_length), float(beta_fast),
                          float(beta_slow))
        inv = inv * (1 - ramp) + inv / float(factor) * ramp
    pos = positions.astype(F32)
    if sections:
        if sum(sections) != pairs or pos.shape[0] != len(sections):
            raise ValueError("rotary sections %s over %d position axes do "
                             "not cover %d frequency pairs"
                             % (tuple(sections), pos.shape[0], pairs))
        axis_of_pair = [a for a, n in enumerate(sections) for _ in range(n)]
        pos = jnp.moveaxis(pos, 0, -1)[..., jnp.array(axis_of_pair)]
    else:
        pos = pos[..., None]
    return pos * inv


def _rotate(x, angles, attention_factor=1.0):
    """x (batch, length, heads, d), angles (batch, length, pairs): the
    first ``2 x pairs`` lanes are turned, lane ``i`` paired with ``i +
    pairs``, each pair by its angle, in float32; cos and sin times
    ``attention_factor`` (YaRN's: q and k both carry it, so a score
    carries its square). Lanes beyond them (a head turned in part) pass
    unchanged and carry no factor."""
    half = angles.shape[-1]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if attention_factor != 1.0:
        cos, sin = cos * attention_factor, sin * attention_factor
    xf = x.astype(F32)
    a, b = xf[..., :half], xf[..., half:2 * half]
    out = [a * cos - b * sin, b * cos + a * sin]
    if 2 * half < x.shape[-1]:
        out.append(xf[..., 2 * half:])
    return jnp.concatenate(out, -1).astype(x.dtype)


def _text_positions(batch, length, sections=()):
    """Text's position ids: the token's index, on every axis where
    ``sections`` asks for several."""
    shape = (len(sections), batch, length) if sections else (batch, length)
    return jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32), shape)


@register("_contrib_rotary")
def rotary(data, positions=None, *, theta=10000.0, sections=(), yarn=(),
           attention_factor=1.0):
    """Rotary position embedding over the last axis of data (batch,
    length, heads, d): lane ``i`` and lane ``i + d/2`` are a pair,
    turned by ``position * theta^(-i / (d/2))``. ``positions`` are
    integers (batch, length); with ``sections`` (frequency pairs an
    axis, summing to d/2) they are (axes, batch, length) and pair ``i``
    reads the axis whose section holds it (multi-axis M-RoPE; for text
    every axis holds the token's index). Without ``positions`` a
    token's position is its index on every axis. ``yarn`` (factor,
    original length, beta_fast, beta_slow) slows the pairs that turn
    less than ``beta_slow`` times over the original length ``factor``
    times, leaves those that turn more than ``beta_fast`` times alone
    and blends linearly between (:func:`_yarn_ramp`);
    ``attention_factor`` multiplies cos and sin."""
    sections = tuple(int(n) for n in sections)
    if positions is None:
        positions = _text_positions(*data.shape[:2], sections)
    return _rotate(data, _rotary_angles(positions, data.shape[-1] // 2,
                                        theta, sections, tuple(yarn)),
                   float(attention_factor))


def _normed_rotary_qkv(x, q_weight, k_weight, v_weight, q_norm_gamma,
                       k_norm_gamma, turn, h, kv, d, eps,
                       attention_factor=1.0, v_product=_dense):
    """q (batch, length, h, d), k and v (batch, length, kv, d) of the
    normed input x: bias-free projections (v's by ``v_product``:
    :func:`_dense`, or :func:`_in_product` for a mixer that keeps it),
    RMSNorm over each head of q and k where a weight for it is given,
    both turned by the angles ``turn`` (over the lanes the angles
    cover: :func:`_rotate`)."""
    b, length, _ = x.shape

    def heads(weight, n, gamma):
        y = _dense(x, weight).reshape(b, length, n, d)
        return y if gamma is None else _rms(y, gamma, eps)

    q = _rotate(heads(q_weight, h, q_norm_gamma), turn, attention_factor)
    k = _rotate(heads(k_weight, kv, k_norm_gamma), turn, attention_factor)
    return q, k, v_product(x, v_weight).reshape(b, length, kv, d)


_CTX_KEPT = "mx.attn.rotary.kept"   # what the mixer's checkpoint policy saves


def _rotary_mixer(data, norm_gamma, q_weight, k_weight, v_weight, o_weight,
                  q_norm_gamma, k_norm_gamma, positions, gate_weight, *, h,
                  kv, d, rotary_dim, theta, yarn, attention_factor, window,
                  eps):
    b, length, _ = data.shape
    if positions is None:
        positions = _text_positions(b, length)
    x = _rms(data, norm_gamma, eps)
    q, k, v = _normed_rotary_qkv(
        x, q_weight, k_weight, v_weight, q_norm_gamma, k_norm_gamma,
        _rotary_angles(positions, rotary_dim // 2, theta, yarn=yarn), h, kv,
        d, eps, attention_factor, v_product=_in_product)
    ctx = _attend(q, k, v, window, keep=_CTX_KEPT)
    if gate_weight is not None:
        # in the (.., heads, d) view the kernel's context is copied into
        # that tiling and back (0.8 ms a copy at 8,192 tokens of 64
        # heads on a v5e); spreading each head's gate over its lanes
        # by a 0/1 product instead measured slower (PERF.md section 6,
        # PR 42)
        with jax.named_scope("mx.attn.gate"):
            gate = jax.nn.sigmoid(_mm("...i,hi->...h", x, gate_weight))
            ctx = (ctx.astype(F32) * gate[..., None]).astype(ctx.dtype)
    return _dense(ctx.reshape(b, length, h * d), o_weight)


@register("_contrib_rotary_gqa_mixer")
def rotary_gqa_mixer(data, norm_gamma, q_weight, k_weight, v_weight, o_weight,
                     q_norm_gamma=None, k_norm_gamma=None, positions=None,
                     gate_weight=None, *, num_heads, num_kv_heads, head_dim,
                     rotary_dim=0, rope_theta=10000.0, rope_yarn=(),
                     attention_factor=1.0, window=0, eps=1e-6):
    """A pre-norm rotary attention mixer, ``mixer(h)``, ``h =
    RMSNorm(data)``: bias-free q/k/v projections, rotary positions
    (:func:`rotary`'s rule: ``rope_theta``, and YaRN's ``rope_yarn``
    and ``attention_factor`` where given; ``positions`` (batch,
    length), the token's index where none are given), causal
    grouped-query attention (:func:`_attend`) over every earlier key,
    or with ``window`` > 0 over the keys ``t - window < s <= t``,
    bias-free output projection. data (batch, length, hidden). One
    class of layer, several parameterisations: a model that mixes
    sliding-window and full layers gives each its own attributes, and
    its own ``num_heads`` where the kinds differ in their query heads
    (q_weight (num_heads d, hidden), o_weight (hidden, num_heads d)).

    Three terms are a model's to have or not (attributes and inputs
    that describe it, as ``window`` and ``rope_yarn`` do). With ``d`` =
    ``head_dim``, heads ``i`` and a token at position ``p``:

    - ``q_norm_gamma`` / ``k_norm_gamma`` (d,): ``q_i = Rot(RMSNorm(W_q
      h)_i)``, ``k_j = Rot(RMSNorm(W_k h)_j)``, the norm over each
      head's ``d`` lanes with ``eps``; left out, ``q_i = Rot((W_q
      h)_i)``, ``k_j = Rot((W_k h)_j)``.
    - ``rotary_dim`` = ``r`` (even, at most ``d``; 0: the whole head):
      ``Rot`` turns lanes ``0..r-1`` of a head, lane ``j`` paired with
      ``j + r/2``, pair ``j`` by ``p f_j``, ``f_j = rope_theta^(-2j /
      r)`` (under YaRN slowed by :func:`_yarn_ramp` over the ``r / 2``
      pairs), cos and sin times ``attention_factor``; lanes ``r..d-1``
      pass unchanged, without position and without the factor.
    - ``gate_weight`` (num_heads, hidden): ``g = sigmoid(W_g h)``, one
      value a query head a token, in float32; head ``i``'s context
      ``c_i`` is multiplied by ``g_i`` before the output projection,
      ``o = W_o concat_i (g_i c_i)``. The product, the sigmoid and the
      multiply stand under the device scope ``mx.attn.gate``.

    Recomputed in the backward (``jax.checkpoint``) but for the v
    projection's output (the attention kernel's operand as it lies; by
    the module's rule, which also says why q and k are not kept) and
    the attention's context (before the gate; and the kernel's
    log-sum-exp), which a step keeps beside ``data``: the backward runs
    the q and k projections, the norms, the turn and the gate again,
    never v's projection (``o_weight``'s product is dead in the
    recomputation) and never the attention's forward."""
    d = int(head_dim)
    turned = int(rotary_dim) or d
    if turned % 2 or not 0 < turned <= d:
        raise ValueError("rotary_dim %r is not an even number of a head's "
                         "%d lanes" % (rotary_dim, d))
    if (q_norm_gamma is None) != (k_norm_gamma is None):
        raise ValueError("q and k are normed together or not at all")
    fn = _recomputed_but_in_product(
        lambda *arrays: _rotary_mixer(
            *arrays, h=int(num_heads), kv=int(num_kv_heads), d=d,
            rotary_dim=turned, theta=float(rope_theta),
            yarn=tuple(float(n) for n in rope_yarn),
            attention_factor=float(attention_factor),
            window=int(window) or None, eps=float(eps)),
        "rotary", _CTX_KEPT)
    with jax.named_scope("mx.attn.rotary"):
        return fn(data, norm_gamma, q_weight, k_weight, v_weight, o_weight,
                  q_norm_gamma, k_norm_gamma, positions, gate_weight)


def _mla_mixer(data, norm_gamma, q_a_weight, q_a_norm_gamma, q_b_weight,
               kv_a_weight, kv_a_norm_gamma, kv_b_weight, o_weight,
               positions, *, h, nope, rope, vd, theta, eps):
    b, length, _ = data.shape
    if positions is None:
        positions = _text_positions(b, length)
    turn = _rotary_angles(positions, rope // 2, theta)
    x = _rms(data, norm_gamma, eps)
    # queries: down to the latent, its norm, up to every head's
    # [no-position | rotary] lanes
    q = _dense(_rms(_dense(x, q_a_weight), q_a_norm_gamma, eps), q_b_weight) \
        .reshape(b, length, h, nope + rope)
    # keys and values: the latent and ONE rotary key head a token; the
    # latent's norm, up to every head's [no-position key | value] lanes
    kv_a = _dense(x, kv_a_weight)
    rank = kv_a.shape[-1] - rope
    kv = _dense(_rms(kv_a[..., :rank], kv_a_norm_gamma, eps), kv_b_weight) \
        .reshape(b, length, h, nope + vd)
    k_rope = _rotate(kv_a[..., None, rank:], turn)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], turn)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope, (b, length, h, rope))], -1)
    ctx = _attend(q, k, kv[..., nope:], keep=_CTX_KEPT)
    return _dense(ctx.reshape(b, length, h * vd), o_weight)


@register("_contrib_mla_mixer")
def mla_mixer(data, norm_gamma, q_a_weight, q_a_norm_gamma, q_b_weight,
              kv_a_weight, kv_a_norm_gamma, kv_b_weight, o_weight,
              positions=None, *, num_heads, qk_nope_head_dim,
              qk_rope_head_dim, v_head_dim, rope_theta=10000.0, eps=1e-5):
    """A pre-norm latent-attention mixer (MLA), ``mixer(h)``, ``h =
    RMSNorm(data)``, no bias anywhere. Per token, with ``n`` =
    ``qk_nope_head_dim``, ``r`` = ``qk_rope_head_dim``, ``v`` =
    ``v_head_dim`` and heads ``i = 1..num_heads``:

        c_q = RMSNorm(W_qa h)                  q_a_weight (q_rank, hidden)
        [q_nope ; q_rope]_i = W_qb c_q         q_b_weight (heads (n + r), q_rank)
        [c ; k_r] = W_kva h                    kv_a_weight (kv_rank + r, hidden)
        c_kv = RMSNorm(c);  k_rope = Rot(k_r)  one head, read by every query head
        [k_nope ; v]_i = W_kvb c_kv            kv_b_weight (heads (n + v), kv_rank)
        q_i = [q_nope_i ; Rot(q_rope_i)],  k_i = [k_nope_i ; k_rope]
        o_t = W_o concat_i sum_{s <= t} softmax_s(q_it . k_is / sqrt(n + r)) v_is

    ``Rot`` is :func:`rotary`'s rule over the ``r`` rotary lanes (lane
    ``j`` paired with ``j + r/2``, ``rope_theta``; ``positions`` (batch,
    length), the token's index where none are given). data (batch,
    length, hidden). The expanded q, k and v, ``num_heads`` heads each
    (no grouping), go to :func:`_attend`, the one causal attention of
    this file, which needs q . k and v of one width: ``n + r == v``.
    Recomputed whole in the backward (``jax.checkpoint``) but for the
    context (and the kernel's log-sum-exp), which a step keeps beside
    ``data``: the backward expands q, k and v again from ``data``
    (``heads (2 (n + r) + v)`` values a token that are never kept),
    never runs the attention's forward. The module's rule keeps nothing
    more here: the products that read the normed input are the two
    small down-projections (45 GFLOP a block in the GLM-4.7-Flash
    cell), the expansions read the latents, and keeping them is 1.3 GB
    in a cell that stands at 14.63 GB (PERF.md section 7)."""
    nope, rope, vd = (int(qk_nope_head_dim), int(qk_rope_head_dim),
                      int(v_head_dim))
    if nope + rope != vd:
        raise ValueError(
            "q . k over %d + %d lanes and values of %d: the causal "
            "attention here takes one head width" % (nope, rope, vd))
    fn = jax.checkpoint(
        lambda *arrays: _mla_mixer(
            *arrays, h=int(num_heads), nope=nope, rope=rope, vd=vd,
            theta=float(rope_theta), eps=float(eps)),
        policy=jax.checkpoint_policies.save_only_these_names(_CTX_KEPT))
    with jax.named_scope("mx.attn.mla"):
        return fn(data, norm_gamma, q_a_weight, q_a_norm_gamma, q_b_weight,
                  kv_a_weight, kv_a_norm_gamma, kv_b_weight, o_weight,
                  positions)


# ---------------------------------------------------------------------------
# attention over the keys a learned selector keeps
# ---------------------------------------------------------------------------
_KEPT = "mx.attn.dsa.kept"      # what the mixer's checkpoint policy saves


def _order_keys(x):
    """float32 -> uint32 whose unsigned order is the floats' (both
    zeros alike, above every negative; 0 is below every number)."""
    u = lax.bitcast_convert_type(x, jnp.uint32)
    top = jnp.uint32(0x80000000)
    return jnp.where(x == 0, top, jnp.where(u >= top, ~u, u | top))


def _kth_largest(keys, k):
    """The ``k``-th largest of each row of uint32 ``keys`` (rows, n), 0
    for a row with fewer than ``k`` entries above 0, without sorting:
    the largest value that at least ``k`` entries reach, found a bit at
    a time from the top (32 passes of compare-and-count)."""
    def bit(i, found):
        trial = found | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
            jnp.uint32)))
        reach = jnp.sum(keys >= trial[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(reach >= k, trial, found)

    return lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[:1], jnp.uint32))


def _seen_keys(scores, seen):
    return jnp.where(seen, _order_keys(scores), jnp.uint32(0))


def _thresholds(scores, seen, k):
    """For each query row of index scores (rows, keys) float32 under the
    causal mask ``seen``: (the order key of its ``k``-th largest seen
    score, how many keys that tie with it are kept). A row that sees at
    most ``k`` keys keeps them all (threshold 0)."""
    keys = _seen_keys(scores, seen)
    tau = _kth_largest(keys, k)
    above = jnp.sum(keys > tau[:, None], axis=-1, dtype=jnp.int32)
    return tau, k - above


def _selected(scores, seen, tau, ties):
    """The selected set as a mask: the seen keys above a row's
    threshold and, of those equal to it, the first ``ties`` by index
    (``lax.top_k``'s rule: ties to the lower index)."""
    keys = _seen_keys(scores, seen)
    level = seen & (keys == tau[:, None])
    first = jnp.cumsum(level, axis=-1, dtype=jnp.int32) <= ties[:, None]
    return (keys > tau[:, None]) | (level & first)


def _index_scores(iq, ik, iw):
    """``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``, float32: iq
    (b, q, heads, d), ik (b, k, d), iw (b, q, heads) float32."""
    s = _mm("bqjd,bkd->bjqk", iq, ik)
    return jnp.sum(jax.nn.relu(s) * jnp.moveaxis(iw, -1, 1)[..., None],
                   axis=1)


def _seen_block(first, shape):
    """(queries, keys) bool of a block whose first query is at position
    ``first``: key position <= query position."""
    qi = first + lax.broadcasted_iota(jnp.int32, shape, 0)
    return lax.broadcasted_iota(jnp.int32, shape, 1) <= qi


def _block_thresholds(scores, first, k):
    """:func:`_thresholds` of each row of a query block's index scores
    (batch, queries, keys)."""
    seen = _seen_block(first, scores.shape[-2:])
    return jax.vmap(lambda s: _thresholds(s, seen, k))(scores)


def _block_selected(scores, tau, ties, first):
    """:func:`_selected` of a query block: bool (batch, queries, keys)."""
    seen = _seen_block(first, scores.shape[-2:])
    return jax.vmap(lambda s, t, n: _selected(s, seen, t, n))(
        lax.stop_gradient(scores), tau, ties)


def _index_kl(scores, keep, target):
    """A block's sum of ``KL(target || softmax over the kept keys of the
    index scores)``."""
    logq = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
    return jnp.sum(jax.scipy.special.xlogy(target, target)
                   - jnp.where(keep, target * logq, 0.0))


def _sparse_gqa(q, k, v, iq, ik, iw, top_k):
    """(context, index loss, mean keys a query attended). The blocked
    composition of :func:`_causal_gqa` with a second mask: a block of
    queries against the keys up to its end computes every pair's index
    score and attention score and masks those not selected, so its
    products are dense attention's. Per block, outside what is
    differentiated: the rows' thresholds (kept for the backward, like
    the context: the mixer's ``jax.checkpoint`` policy saves what is
    named ``_KEPT``);
    inside a ``jax.checkpoint``: index scores again (they carry the
    index loss's gradient), the mask from the thresholds, the masked
    softmax, the context, and the block's share of the index loss."""
    block = QUERY_BLOCK
    b, length, heads, d = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, length, kv, heads // kv, d)
    scale = 1.0 / math.sqrt(d)

    def select(iqb, ikb, iwb, first):
        with jax.named_scope("mx.attn.index"):
            scores = _index_scores(iqb, ikb, iwb)
        with jax.named_scope("mx.attn.select"):
            return _block_thresholds(scores, first, top_k)

    @jax.checkpoint
    def rows(qb, kb, vb, iqb, ikb, iwb, tau, ties, first):
        with jax.named_scope("mx.attn.index"):
            scores = _index_scores(iqb, ikb, iwb)
        with jax.named_scope("mx.attn.select"):
            keep = _block_selected(scores, tau, ties, first)
        with jax.named_scope(pallas_sparse_gqa.SCOPE):
            s = _mm("bqgrd,bkgd->bgrqk", qb, kb) * scale
            p = jax.nn.softmax(jnp.where(keep[:, None, None], s, -jnp.inf),
                               axis=-1)
            ctx = _mm("bgrqk,bkgd->bqgrd", p.astype(vb.dtype), vb) \
                .astype(qb.dtype)
            target = lax.stop_gradient(jnp.mean(p, axis=(1, 2)))
        with jax.named_scope("mx.attn.index"):
            kl = _index_kl(scores, keep, target)
        return ctx, kl, jnp.sum(keep, dtype=F32)

    out, loss, kept = [], 0.0, 0.0
    for lo in range(0, length, block):
        hi = min(lo + block, length)
        index_in = (iq[:, lo:hi], ik[:, :hi], iw[:, lo:hi])
        tau, ties = select(*(lax.stop_gradient(a) for a in index_in), lo)
        tau, ties = (checkpoint_name(a, _KEPT) for a in (tau, ties))
        ctx, kl, n = rows(qg[:, lo:hi], k[:, :hi], v[:, :hi], *index_in,
                          tau, ties, lo)
        out.append(ctx)
        loss, kept = loss + kl, kept + n
    ctx = jnp.concatenate(out, axis=1).reshape(b, length, heads, d)
    return checkpoint_name(ctx, _KEPT), loss / (b * length), \
        kept / (b * length)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _sparse_gqa_flash(q, k, v, iq, ik, iw, top_k):
    """:func:`_sparse_gqa`'s three results, the attention over the
    selected keys in the flash kernels of ``ops/pallas_sparse_gqa.py``:
    no score block exists outside VMEM. The selection is the
    composition's own code (thresholds, ties, mask), handed to the
    kernels as a mask: ``lax.top_k``'s set of the index scores this
    path computed, which are :func:`_index_scores` a block or, where
    ``ops/pallas_index_scores.py`` can serve, its kernels' (the heads
    summed in VMEM, another order of one sum: a score may differ from
    the composition's in its last bit, and a tie at the ``top_k``-th
    place with it). The index loss reads the kernels' head-averaged
    probabilities. Differentiated by hand (:func:`_flash_bwd`), a block
    at a time like the composition's recomputed ``rows``: kept from the
    forward are each row's threshold and tie count, the context and the
    rows' log-sum-exp (all named for the mixer's ``jax.checkpoint``), so
    the backward runs the index scores (by the forward's own code: its
    bits, so the forward's mask), the mask and the probabilities again,
    never the forward kernel."""
    return _flash_fwd(q, k, v, iq, ik, iw, top_k)[0]


def _keys_by_queries(keep):
    """A block's selected set as the kernels read it: int8 (batch,
    keys, queries)."""
    return jnp.swapaxes(keep, 1, 2).astype(jnp.int8)


def _head_mean_probs(q, k, mask, lse, block):
    """A query block's attention probabilities averaged over the heads,
    (batch, queries, keys) float32, from the kernel that rebuilds them
    (keys x queries: the swap is a change of view, the compiler keeps a
    block's index scores laid out that way too)."""
    with jax.named_scope(pallas_sparse_gqa.SCOPE):
        return jnp.swapaxes(pallas_sparse_gqa.head_mean_probs(
            q, k, mask, lse, block, QUERY_BLOCK), 1, 2)


def _index_blocks(iq, ik, iw):
    """(block number, its first query, its index inputs) a query block:
    the block's index queries and weights, the index keys up to its
    end."""
    for i, lo in enumerate(range(0, iq.shape[1], QUERY_BLOCK)):
        hi = lo + QUERY_BLOCK
        yield i, lo, (iq[:, lo:hi], ik[:, :hi], iw[:, lo:hi])


def _flash_fwd(q, k, v, iq, ik, iw, top_k):
    tile = QUERY_BLOCK
    b, length = q.shape[:2]
    scores, taus, ties, keeps, masks = [], [], [], [], []
    fused = pallas_index_scores.index_scores_available(iq, ik, iw, tile)
    for i, lo, index_in in _index_blocks(iq, ik, iw):
        with jax.named_scope("mx.attn.index"):
            if fused and i == 0:    # one kernel, every block's scores
                summed = pallas_index_scores.index_score_blocks(iq, ik, iw,
                                                                tile)
            scores.append(summed[i] if fused else _index_scores(*index_in))
        with jax.named_scope("mx.attn.select"):
            tau, tie = _block_thresholds(scores[-1], lo, top_k)
            keeps.append(_block_selected(scores[-1], tau, tie, lo))
            masks.append(_keys_by_queries(keeps[-1]))
            taus.append(tau)
            ties.append(tie)
    with jax.named_scope("mx.attn.select"):
        mask = pallas_sparse_gqa.mask_blocks(masks, length)
    with jax.named_scope(pallas_sparse_gqa.SCOPE):
        ctx, lse = pallas_sparse_gqa.attend(q, k, v, mask, tile)
    loss = kept = 0.0
    for i, (block_scores, keep) in enumerate(zip(scores, keeps)):
        target = _head_mean_probs(q, k, masks[i], lse, i)
        with jax.named_scope("mx.attn.index"):
            loss = loss + _index_kl(block_scores, keep, target)
        kept = kept + jnp.sum(keep, dtype=F32)
    tau, tie, ctx, lse = (checkpoint_name(a, _KEPT) for a in (
        jnp.concatenate(taus, axis=1), jnp.concatenate(ties, axis=1), ctx,
        lse))
    return (ctx, loss / (b * length), kept / (b * length)), \
        (q, k, v, iq, ik, iw, tau, tie, ctx, lse)


def _flash_bwd(top_k, res, cotangents):
    """A block at a time: its index scores again (with the pullback to
    the index inputs), its mask from the kept thresholds, its
    probabilities from the kept log-sum-exp, the index loss's gradient;
    then one backward kernel under the blocks' masks together. Where
    the index scores' kernels serve, every block's scores come from
    one call of the forward's kernel and the blocks' cotangents go back
    through one backward kernel. (The rule is traced after the caller's
    scopes have closed: it opens them again.)"""
    q, k, v, iq, ik, iw, tau, ties, ctx, lse = res
    dctx, dloss, _ = cotangents
    tile = QUERY_BLOCK
    b, length = q.shape[:2]
    share = dloss / (b * length)
    diq, diw, masks, dscores = [], [], [], []
    dik = jnp.zeros(ik.shape, F32)
    fused = pallas_index_scores.index_scores_available(iq, ik, iw, tile)
    with jax.named_scope("mx.attn.dsa"):
        for i, lo, index_in in _index_blocks(iq, ik, iw):
            hi = lo + tile
            with jax.named_scope("mx.attn.index"):
                if fused and i == 0:    # the forward's kernel: its bits
                    summed, pull = pallas_index_scores.index_score_blocks_vjp(
                        iq, ik, iw, tile)
                if fused:
                    scores = summed[i]
                else:
                    scores, pull = jax.vjp(_index_scores, *index_in)
            with jax.named_scope("mx.attn.select"):
                keep = _block_selected(scores, tau[:, lo:hi], ties[:, lo:hi],
                                       lo)
                masks.append(_keys_by_queries(keep))
            target = _head_mean_probs(q, k, masks[-1], lse, i)
            with jax.named_scope("mx.attn.index"):
                dscore = jax.grad(_index_kl)(scores, keep, target) * share
                if fused:
                    dscores.append(dscore)
                else:
                    dq_i, dk_i, dw_i = pull(dscore)
                    dik = dik.at[:, :hi].add(dk_i.astype(F32))
                    diq.append(dq_i)
                    diw.append(dw_i)
        with jax.named_scope("mx.attn.index"):
            if fused:       # one kernel under the blocks' cotangents
                diq, dik, diw = pull(tuple(dscores))
            else:
                diq, diw = (jnp.concatenate(t, axis=1) for t in (diq, diw))
        with jax.named_scope("mx.attn.select"):
            mask = pallas_sparse_gqa.mask_blocks(masks, length)
        with jax.named_scope(pallas_sparse_gqa.SCOPE):
            dq, dk, dv = pallas_sparse_gqa.attend_bwd(q, k, v, mask, ctx, lse,
                                                      dctx, tile)
    return dq, dk, dv, diq, dik.astype(ik.dtype), diw


_sparse_gqa_flash.defvjp(_flash_fwd, _flash_bwd)


def _sparse_attend(q, k, v, iq, ik, iw, top_k):
    """(context, index loss (1,), the auxiliary state) by whichever form
    the call allows, chosen from what can be observed here and nothing
    else (``pallas_sparse_gqa.sparse_gqa_available``: what
    :func:`_attend` asks, and kernels that will be compiled or whose
    interpretation was asked for); counted once a traced call in
    ``mx_attn_sparse_path_total{path="pallas"|"masked"}``, and beside it
    how the index scores are computed, in
    ``mx_attn_index_path_total{path="pallas"|"xla"}`` (``pallas`` only
    inside the flash form: ``index_scores_available``). The caller opens
    ``mx.attn.dsa``."""
    kernel = pallas_sparse_gqa.sparse_gqa_available(q, k, v, QUERY_BLOCK)
    telemetry.count_event("mx_attn_sparse_path_total",
                          path="pallas" if kernel else "masked")
    form, iw = _sparse_gqa_flash if kernel else _sparse_gqa, iw.astype(F32)
    telemetry.count_event(
        "mx_attn_index_path_total", path="pallas"
        if kernel and pallas_index_scores.index_scores_available(
            iq, ik, iw, QUERY_BLOCK) else "xla")
    ctx, loss, kept = form(q, k, v, iq, ik, iw, int(top_k))
    return ctx, loss.reshape(1), lax.stop_gradient(jnp.stack([kept, loss]))


_DSA_DOC = """

    The selector (DeepSeek-V3.2-Exp's lightning indexer): index_query
    (batch, length, index_heads, index_dim), index_key (batch, length,
    index_dim), index_weight (batch, length, index_heads) give every
    causal pair the score ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
    kI[s])`` (float32 accumulation); query ``t`` attends the ``top_k``
    keys of largest ``I[t, :t+1]``, all of them while ``t < top_k``,
    ties to the lower index (exactly ``lax.top_k``'s set); the context
    is ``softmax`` over that set of ``q . k / sqrt(d)`` times v, query
    head h reading key-value head ``h // (heads // kv_heads)``. The
    second output is the index loss, shape (1,): the mean over
    positions of ``KL(p_t || softmax_{s in S_t} I[t, s])`` with ``p_t``
    the attention probabilities averaged over the heads, gradient
    stopped. The selection is not differentiated: the index inputs get
    gradient from the index loss alone and the context gives them none.
    ``dsa_state`` (2,) float32 is an auxiliary state (written, never
    differentiated): the mean number of keys a query attended in this
    call, and the index loss. Queries are taken ``QUERY_BLOCK`` at a
    time against the keys up to the block's end; a block computes every
    pair's two scores and masks the pairs not selected (no length x
    length array; no key is gathered), finds each row's ``top_k``-th
    largest score without sorting it (a search over the float's bit
    pattern: 32 passes of compare-and-count) and recomputes its scores
    in the backward from the kept thresholds. Where the call allows it
    (:func:`_sparse_attend`) the attention scores live in the VMEM of
    flash kernels that read the selected set as a mask, and the index
    scores' product a head in the VMEM of kernels that write only its
    sum over the heads. Exact in every form: the set is ``lax.top_k``'s
    of the index scores the form computed, and the backward's mask is
    the forward's. Not exact between forms: the heads are summed in
    another order, so a score may differ in its last bit and two keys an
    ulp apart at the ``top_k``-th place may change places."""


@register("_contrib_sparse_gqa_attention", num_outputs=2, mutate_aux={2: 6})
def sparse_gqa_attention(query, key, value, index_query, index_key,
                         index_weight, dsa_state, *, top_k):
    """Causal grouped-query attention over the keys a learned selector
    keeps: (context (batch, length, heads, d), index loss)."""
    with jax.named_scope("mx.attn.dsa"):
        return _sparse_attend(query, key, value, index_query, index_key,
                              index_weight, top_k)


sparse_gqa_attention.__doc__ += _DSA_DOC


def _layer_norm(x, gamma, beta, eps):
    xf = x.astype(F32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + eps) * gamma.astype(F32)
            + beta.astype(F32)).astype(x.dtype)


def _sparse_mixer(data, norm_gamma, q_weight, k_weight, v_weight, o_weight,
                  q_norm_gamma, k_norm_gamma, index_q_weight, index_k_weight,
                  index_weight_weight, index_k_norm_gamma, index_k_norm_beta,
                  positions, *, h, kv, d, ih, idim, top_k, theta, sections,
                  eps):
    b, length, _ = data.shape
    if positions is None:
        positions = _text_positions(b, length, sections)
    x = _rms(data, norm_gamma, eps)
    q, k, v = _normed_rotary_qkv(
        x, q_weight, k_weight, v_weight, q_norm_gamma, k_norm_gamma,
        _rotary_angles(positions, d // 2, theta, sections), h, kv, d, eps)
    # the selector reads the normed input and hands it no gradient
    xi = lax.stop_gradient(x)
    turn = _rotary_angles(positions[0] if sections else positions, idim // 2,
                          theta)
    iq = _rotate(_dense(xi, index_q_weight).reshape(b, length, ih, idim),
                 turn)
    ik = _rotate(_layer_norm(_dense(xi, index_k_weight), index_k_norm_gamma,
                             index_k_norm_beta, eps)[:, :, None],
                 turn)[:, :, 0]
    iw = _mm("bti,ji->btj", xi, index_weight_weight) / math.sqrt(ih * idim)
    ctx, loss, state = _sparse_attend(q, k, v, iq, ik, iw, top_k)
    return _dense(ctx.reshape(b, length, h * d), o_weight), loss, state


@register("_contrib_sparse_gqa_mixer", num_outputs=2, mutate_aux={2: 13})
def sparse_gqa_mixer(data, norm_gamma, q_weight, k_weight, v_weight, o_weight,
                     q_norm_gamma, k_norm_gamma, index_q_weight,
                     index_k_weight, index_weight_weight, index_k_norm_gamma,
                     index_k_norm_beta, dsa_state, positions=None, *,
                     num_heads, num_kv_heads, head_dim, index_heads,
                     index_head_dim, top_k, rope_theta=10000.0,
                     rope_sections=(), eps=1e-6):
    """A pre-norm sparse-attention mixer, ``mixer(h)``, ``h =
    RMSNorm(data)``: bias-free q/k/v projections, RMSNorm over each
    head of q and k, rotary positions (:func:`rotary`: ``rope_sections``
    over (axes, batch, length) ``positions``, every axis the token's
    index where none are given), the selector on ``h`` with its gradient
    stopped there (index queries ``index_heads`` x ``index_head_dim``,
    one index key head under a LayerNorm, both turned whole by the
    first position axis; index weights ``W_w h / sqrt(index_heads x
    index_head_dim)``), :func:`sparse_gqa_attention`, bias-free output
    projection: (mixer output (batch, length, hidden), index loss).
    Recomputed whole in the backward (``jax.checkpoint``), but for each
    row's selection threshold and the context (and the rows'
    log-sum-exp on the kernel path), which a step keeps beside
    ``data``. Its v projection is run again in the backward with q's
    and k's, unlike the rotary mixer's: the one cell that calls this
    mixer (Keye-VL) stands 16 MB under the chip (PERF.md section 7)."""
    fn = jax.checkpoint(
        lambda *arrays: _sparse_mixer(
            *arrays, h=int(num_heads), kv=int(num_kv_heads), d=int(head_dim),
            ih=int(index_heads), idim=int(index_head_dim), top_k=int(top_k),
            theta=float(rope_theta),
            sections=tuple(int(n) for n in rope_sections), eps=float(eps)),
        policy=jax.checkpoint_policies.save_only_these_names(_KEPT))
    with jax.named_scope("mx.attn.dsa"):
        return fn(data, norm_gamma, q_weight, k_weight, v_weight, o_weight,
                  q_norm_gamma, k_norm_gamma, index_q_weight, index_k_weight,
                  index_weight_weight, index_k_norm_gamma, index_k_norm_beta,
                  positions)


sparse_gqa_mixer.__doc__ += _DSA_DOC


# ---------------------------------------------------------------------------
# routed experts
# ---------------------------------------------------------------------------
_ROUTED = "mx.moe.routed"   # what a recomputed expert mixer keeps


def _routed(tree):
    """``tree``'s arrays named for :func:`moe_mixer`'s checkpoint: the
    chosen experts and their scores, each slot's row, the buffer's maps
    and counts cross it (under 2 MB a layer in the LFM2 cell), so the
    backward chooses, counts, sorts and scatters nothing again."""
    return jax.tree.map(lambda a: checkpoint_name(a, _ROUTED), tree)


def _route(x, router_w, bias, top_k, scale, norm_topk, score_func="sigmoid"):
    """(chosen expert ids (T, k), their weights (T, k) float32). Every
    expert's score is ``score_func`` of its float32 logit: ``sigmoid``
    (each expert alone) or ``softmax`` (over all the router's experts).
    The ``top_k`` largest scores are chosen, after ``bias`` is added
    where one is given (it moves the choice, never the weight); the
    weights are the chosen scores, normalised to sum 1 where
    ``norm_topk``, times ``scale``."""
    logits = jnp.einsum("td,ed->te", x.astype(F32), router_w.astype(F32),
                        precision=_HI)
    s = _SCORES[score_func](logits)
    _, idx = lax.top_k(s if bias is None else s + bias.astype(F32), top_k)
    idx = _routed(idx)
    # the chosen scores by a one-hot sum (exact: one term is not 0) with
    # an element-wise pullback: 0.2 ms each way in the LFM2 cell where a
    # gather of T x k scalars took 1.4 and its scatter-add back 1.0
    chosen = idx[:, :, None] == jnp.arange(s.shape[1])
    w = _routed(jnp.sum(jnp.where(chosen, s[:, None, :], 0.0), axis=-1))
    if norm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * scale


_SCORES = {"sigmoid": jax.nn.sigmoid,
           "softmax": lambda logits: jax.nn.softmax(logits, axis=-1)}


def _relu2(h):
    return jnp.square(jax.nn.relu(h))


def _swiglu(h):
    """h holds ``[gate | up]`` along its last axis: ``silu(gate) * up``."""
    gate, up = jnp.split(h, 2, axis=-1)
    return jax.nn.silu(gate) * up


# what an expert does between its two products, on the first one's
# float32 output: w1 (width, hidden) for ``relu2``; for ``swiglu`` the
# gate's and the up projection's rows stacked, (2 x width, hidden)
_ACTIVATIONS = {"relu2": _relu2, "swiglu": _swiglu}


def _mlp(x, w1, w2, act=_relu2):
    h = act(_mm("...d,fd->...f", x, w1)).astype(x.dtype)
    return _mm("...f,df->...d", h, w2)


def _slots_to_rows(held, local, n_held, cap, block):
    """Where each (token, choice) slot sits in a buffer of ``cap`` rows
    sorted by expert, each expert's run padded to whole blocks of
    ``block`` rows: the slot's row number, the (padded) rows before its
    expert plus its rank among the expert's (``cap``, one past the end,
    for a slot that is not held here or falls beyond the buffer); the
    routed count of each held expert and how many of its slots have a
    row inside the buffer; the expert of each block; how many blocks
    hold a routed row (the runs are packed, so they are the buffer's
    first and the rest is an empty tail); and whether the padded runs
    fit the buffer."""
    t, k = held.shape
    onehot = (local.reshape(-1, 1) == jnp.arange(n_held)) \
        & held.reshape(-1, 1)
    rank = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
    counts = jnp.sum(onehot, axis=0, dtype=jnp.int32)
    ends = jnp.cumsum(-(-counts // block) * block)
    first = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    row = jnp.sum(jnp.where(onehot, rank + first, 0), axis=1).reshape(t, k)
    starts = jnp.arange(0, cap, block)
    expert_of_block = jnp.minimum(
        jnp.sum(starts[:, None] >= ends, axis=1), n_held - 1)
    placed = held & (row < cap)
    done = jnp.sum(onehot & placed.reshape(-1, 1), axis=0, dtype=jnp.int32)
    return (jnp.where(placed, row, cap), (counts, done), expert_of_block,
            jnp.sum(starts < ends[-1], dtype=jnp.int32), ends[-1] <= cap)


def _placed(values, at, size, fill):
    """``out[at[i]] = values[i]`` over ``size`` places, ``fill`` where
    none lands, in one scatter in which no two updates share a place
    (XLA is told so and neither sorts nor compares them): an update for
    a place past the end (``at[i] >= size``) is sent to one of its own
    beyond it and dropped. On the chip a scatter is a loop over its
    updates, ~5 ns each, so what counts is how many there are and how
    often it runs (PERF.md section 6, PR 60)."""
    own = size + jnp.arange(at.shape[0], dtype=jnp.int32)
    return jnp.full((size,), fill, values.dtype) \
        .at[jnp.where(at < size, at, own)] \
        .set(values, mode="drop", unique_indices=True)


def _rows_to_slots(row, cap):
    """:func:`_slots_to_rows`' ``row`` (T, k) the other way round: the
    slot that fills each of the buffer's ``cap`` rows, as ``token * k +
    choice`` (``T * k`` where none does), and the row's token (``T``
    where none)."""
    t, k = row.shape
    slot_of_row = _placed(jnp.arange(t * k, dtype=jnp.int32),
                          row.reshape(-1), cap, t * k)
    return slot_of_row, jnp.where(slot_of_row < t * k, slot_of_row // k, t)


@jax.custom_vjp
def _weight_of_row(w_slot, slot_of_row):
    """Each buffer row's slot weight, float32 (0 where no slot fills
    it): a gather of ``w_slot`` (T, k) at :func:`_rows_to_slots`' slots.
    Its pullback sends each row's cotangent to its slot (:func:`_placed`,
    ``cap`` updates: a fifth of the time of a gather at every slot's
    row, PERF.md section 6, PR 60)."""
    return w_slot.reshape(-1).at[slot_of_row].get(mode="fill", fill_value=0)


_weight_of_row.defvjp(
    lambda w_slot, slot_of_row: (_weight_of_row(w_slot, slot_of_row),
                                 (slot_of_row, w_slot.shape)),
    lambda res, g: (_placed(g, res[0], math.prod(res[1]), 0.0)
                    .reshape(res[1]), None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather_rows(x, token_of_row, row_of_slot, sums):
    """Rows of ``x`` (T, D) into a buffer: ``out[r] = x[token_of_row[r]]``
    (a zero row where ``token_of_row[r] == T``). XLA's gather, at the
    memory's pace where the tokens fit on chip (the indices rise within
    an expert's run: PERF.md section 6, PRs 43, 60). The zero row costs
    a copy of ``x`` and stays: a row no slot fills must be a true zero
    for the grouped kernels' ``dW`` and the window kernel's 0/1 product
    wherever a token no held expert is routed holds a NaN (a clamped
    index reads the last token's row), and XLA's own fill, a select over
    the buffer, costs more than the copy. ``sums`` is its transpose's."""
    return jnp.concatenate([x, jnp.zeros_like(x[:1])])[token_of_row]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _sum_slots(rows, token_of_row, row_of_slot, sums):
    """Each token's slots summed back: ``out[t] = sum_j rows[row_of_slot
    [t, j]]`` (a zero row where the slot is one past the end), float32
    accumulation, cast once: where ``sums`` (what
    ``pallas_moe_rows.sum_available`` said of the call) by the window
    kernel of ``ops/pallas_moe_rows.py`` (the buffer is sorted, so a
    block of tokens finds its rows in a few short stretches), else by
    XLA's gather of every slot's row."""
    if sums:
        return pallas_moe_rows.sum_slots(rows, token_of_row, row_of_slot)
    ext = jnp.concatenate([rows, jnp.zeros_like(rows[:1])])
    return jnp.sum(ext[row_of_slot].astype(F32), axis=1).astype(rows.dtype)


# the two are each other's transposes (slots <-> rows is one-to-one on
# what is held), so both backwards are the other and no scatter runs
_gather_rows.defvjp(
    lambda x, tr, rs, sums: (_gather_rows(x, tr, rs, sums), (tr, rs)),
    lambda sums, res, g: (_sum_slots(g, *res, sums), None, None))
_sum_slots.defvjp(
    lambda rows, tr, rs, sums: (_sum_slots(rows, tr, rs, sums), (tr, rs)),
    lambda sums, res, g: (_gather_rows(g, *res, sums), None, None))


_HIDDEN = "mx.moe.experts.hidden"   # what a chunk of blocks keeps


def _blocks_product(xr, expert_of_block, used, weight_of_row, up, down, act,
                    kernel):
    """Each block of ``xr`` (blocks, rows, hidden) through its expert's
    two products, each row times its slot's weight: (blocks x rows,
    hidden) in ``xr``'s dtype, by the schedule :func:`_moe_experts`
    picked (``kernel``). The grouped kernels take the whole buffer in
    one call, read each block's weight tiles where the experts' arrays
    hold them and compute the first ``used`` blocks, those that hold a
    routed row (the empty tail past them is written as the zeros it
    would come to, no product run); their backward sums the weight
    gradients by expert in VMEM. The composition computes every block,
    whatever ``used``: every block reads a copy of its expert's
    weights, and the backward writes a float32 gradient a block before
    summing by expert; so up to ``BLOCKS_AT_ONCE`` blocks that is one
    batched product, and beyond it a loop over chunks of blocks, each
    chunk keeping its first product's output and gathering its weights
    again in the backward: the copies that exist at once are a chunk's,
    not the buffer's, and the float32 rows a chunk's (144 blocks of
    8.3 M weights at 16,384 tokens over 16 experts of width 896: 9.5 GB
    of temporaries as one product)."""
    def product(xb, eb, kept=lambda pre: pre):
        pre = kept(_mm("bmd,bfd->bmf", xb, up[eb]))
        return _mm("bmf,bdf->bmd", act(pre).astype(xb.dtype), down[eb])

    n, block = xr.shape[:2]
    if kernel:
        return pallas_grouped_mlp.grouped_mlp(
            xr.reshape(n * block, -1), expert_of_block, used, weight_of_row,
            up, down, act)
    if n <= BLOCKS_AT_ONCE:
        yr = product(xr, expert_of_block).reshape(n * block, -1)
        return (yr * weight_of_row[:, None]).astype(xr.dtype)
    chunk = max(c for c in range(1, BLOCKS_A_CHUNK + 1) if n % c == 0)

    @functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(_HIDDEN))
    def of_chunk(xb, eb, wb):
        y = product(xb, eb, lambda pre: checkpoint_name(pre, _HIDDEN))
        return (y * wb[..., None]).astype(xb.dtype)

    def chunks(a):
        return a.reshape((n // chunk, chunk) + a.shape[1:])

    return lax.map(lambda a: of_chunk(*a),
                   (chunks(xr), chunks(expert_of_block),
                    chunks(weight_of_row.reshape(n, block)))) \
        .reshape(n * block, -1)


def _experts_sorted(x, row, token_of_row, weight_of_row, expert_of_block,
                    used, up, down, block, act, kernel, sums):
    """Rows gathered into one buffer sorted by expert, whole blocks an
    expert (:func:`_gather_rows`: XLA's gather); the blocks' products,
    each against its expert's weights (:func:`_blocks_product`: a block
    that holds a routed row is computed whole, the rows of it that no
    slot fills being zeros; the buffer is packed, so the blocks from
    ``used`` on are an empty tail, which the grouped kernels skip and
    the composition computes as zeros); summed back by token
    (:func:`_sum_slots`: the window kernel of
    ``ops/pallas_moe_rows.py`` where ``sums``, which reads the sorted
    buffer in short contiguous stretches, else XLA's gather of every
    slot's row). The buffer's maps (:func:`_rows_to_slots`,
    :func:`_weight_of_row`) come with the routing, made once a layer
    call by :func:`_moe_experts`, never here."""
    xr = _gather_rows(x, token_of_row, row, sums) \
        .reshape(-1, block, x.shape[1])
    yr = _blocks_product(xr, expert_of_block, used, weight_of_row, up, down,
                         act, kernel)
    return _sum_slots(yr, token_of_row, row, sums)


def _experts_dense(x, held, local, w_slot, counts, up, down, act):
    """Every held expert that is routed a row, over every token,
    weighted by the slot that chose it (0 where none did): exact
    whatever the routing; an expert that is routed none is skipped. A
    term, its condition included, is recomputed in the backward, so the
    loop keeps nothing between the experts (a `cond` differentiated
    outside a checkpoint hands on a copy of x and a hidden layer an
    expert)."""
    @jax.checkpoint
    def term(e, a, b):
        def routed():
            we = jnp.sum(jnp.where(held & (local == e), w_slot, 0.0), axis=1)
            return _mlp(x, a, b, act) * we[:, None]

        return lax.cond(counts[e] > 0, routed,
                        lambda: jnp.zeros(x.shape, F32))

    acc, _ = lax.scan(lambda acc, ew: (acc + term(*ew), None),
                      jnp.zeros(x.shape, F32),
                      (jnp.arange(up.shape[0]), up, down))
    return acc.astype(x.dtype)


def _held_terms(x, w_slot, weight_of_row, w1, w2, routing, block, act, kernel,
                sums):
    """The held experts' terms summed by token: the sorted buffer where
    the routing fits it. No row is dropped: routing that overfills the
    buffer takes the dense
    product over the held experts instead (whole matrices at the MXU's
    pace: where routing piles the tokens on a few experts, cheaper than
    more passes of the gathered product, which PR 28 measured at 5x its
    cost). ``routing``: :func:`_slots_to_rows`' row of each slot, the
    token of each row, routed counts, expert of each block, blocks that
    hold a row and whether the runs fit, then ``held`` and ``local``;
    the slots' weights by slot (the dense product's) and by row (the
    buffer's); ``kernel`` / ``sums``: whether the buffer's products /
    its slot sum are Pallas kernels."""
    row, token_of_row, counts, expert_of_block, used, fits, held, local = \
        routing
    return lax.cond(
        fits,
        lambda: _experts_sorted(x, row, token_of_row, weight_of_row,
                                expert_of_block, used, w1, w2, block, act,
                                kernel, sums),
        lambda: _experts_dense(x, held, local, w_slot, counts, w1, w2, act))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _held_terms_kept_by_inputs(x, w_slot, weight_of_row, w1, w2, routing,
                               block, act, sums):
    """:func:`_held_terms` on the kernel path, differentiated by hand so
    that nothing but its inputs crosses from the forward to the
    backward. Differentiated as it stands, the ``cond`` hands on each
    branch's residuals and fills the other branch's with zeros; the
    kernels' residuals include the experts' weights as they lie, so
    every layer's ``cond`` would return a copy of them and the program
    carry a zero array of their size (144 MiB in the Keye-VL cell, whose
    step then no longer fits the chip: PERF.md section 6, PR 35). Here
    the backward is a ``cond`` of its own over the same ``fits``, each
    branch the pullback of the forward's branch (the buffer's maps are
    among the inputs: it builds none); the caller's ``jax.checkpoint``
    recomputes nothing for it."""
    return _held_terms(x, w_slot, weight_of_row, w1, w2, routing, block, act,
                       True, sums)


def _kept_by_inputs_fwd(x, w_slot, weight_of_row, w1, w2, routing, block, act,
                        sums):
    return _held_terms(x, w_slot, weight_of_row, w1, w2, routing, block, act,
                       True, sums), (x, w_slot, weight_of_row, w1, w2, routing)


def _kept_by_inputs_bwd(block, act, sums, res, cotangent):
    *inputs, routing = res
    row, token_of_row, counts, expert_of_block, used, fits, held, local = \
        routing

    def pullback(branch):
        return lambda: jax.vjp(branch, *inputs)[1](cotangent)

    # (the rule is traced after the caller's scopes have closed)
    with jax.named_scope("mx.moe"), jax.named_scope("mx.moe.experts"):
        grads = lax.cond(
            fits,
            pullback(lambda x, w_slot, weight_of_row, w1, w2: _experts_sorted(
                x, row, token_of_row, weight_of_row, expert_of_block, used,
                w1, w2, block, act, True, sums)),
            pullback(lambda x, w_slot, weight_of_row, w1, w2: _experts_dense(
                x, held, local, w_slot, counts, w1, w2, act)))
    # the gradients leave together: without the barrier the TPU
    # compiler's buffer assignment for the Keye-VL cell's step needs
    # 31 MB more than the chip has (PERF.md section 6, PR 35)
    return tuple(lax.optimization_barrier(grads)) + (None,)


_held_terms_kept_by_inputs.defvjp(_kept_by_inputs_fwd, _kept_by_inputs_bwd)


def _buffer(t, top_k, n_held, n_routed, capacity_factor=CAPACITY_FACTOR,
            block_rows=BLOCK_ROWS):
    """(rows a block, blocks, the most blocks any routing fills) of the
    one buffer for the held experts' rows together: ``capacity_factor``
    times their even share and a block of padding an expert. A token
    chooses an expert at most once, so t x min(top_k, held) rows (and
    their padding) always do."""
    even = t * top_k / n_routed
    block = min(int(block_rows), -(-math.ceil(capacity_factor * even) // 8) * 8)
    most = -(-t * min(top_k, n_held) // block) + n_held
    return block, min(most, math.ceil(capacity_factor * even * n_held / block)
                      + n_held), most


def _moe_experts(x, router_w, bias, w1, w2, *, top_k, offset, scale,
                 norm_topk, score_func="sigmoid", activation="relu2",
                 capacity_factor=CAPACITY_FACTOR, block_rows=BLOCK_ROWS):
    t = x.shape[0]
    n_held, n_routed = w1.shape[0], router_w.shape[0]
    act = _ACTIVATIONS[activation]
    idx, w_slot = _route(x, router_w, bias, top_k, scale, norm_topk,
                         score_func)
    local = idx - offset
    held = (local >= 0) & (local < n_held)
    block, blocks, most = _buffer(t, top_k, n_held, n_routed,
                                  capacity_factor, block_rows)
    # (named, as the buffer's maps below: a recomputed mixer keeps them)
    row, (counts, placed), expert_of_block, used, fits = _routed(
        _slots_to_rows(held, local, n_held, blocks * block, block))
    # the buffer's products by whichever schedule the call allows,
    # chosen from what can be observed here and nothing else (bf16 rows
    # and weights of sizes the kernels' tiles take, traced for one
    # device, kernels that will be compiled or whose interpretation was
    # asked for), counted once a traced call
    kernel = pallas_grouped_mlp.grouped_mlp_available(
        jax.ShapeDtypeStruct((blocks, block, x.shape[1]), x.dtype), w1, w2)
    telemetry.count_event("mx_moe_experts_path_total",
                          path="pallas" if kernel else "xla")
    # the buffer's shape beside it, under the one fact of the layer that
    # its ``expert_rows`` state shows too (the experts held): what the
    # state's publisher turns routed rows into blocks with. Another
    # shape under the same count cannot be told from this one there:
    # -1, and the publisher leaves such layers out
    if telemetry.enabled():
        shape = [telemetry.gauge("mx_moe_buffer_shape", held=str(n_held),
                                 dim=dim) for dim in ("block_rows", "blocks")]
        one = [g.get() for g in shape] in ([0, 0], [block, blocks])
        for g, size in zip(shape, (block, blocks)):
            g.set(size if one else -1)
    # and the sum of a token's rows back out of the buffer likewise
    # (bf16 rows of whole lane tiles, a buffer of whole windows)
    sums = pallas_moe_rows.sum_available(
        jax.ShapeDtypeStruct((blocks * block, x.shape[1]), x.dtype), top_k, t)
    telemetry.count_event("mx_moe_rows_path_total",
                          path="pallas" if sums else "xla")
    with jax.named_scope("mx.moe.experts"):
        slot_of_row, token_of_row = _routed(
            _rows_to_slots(row, blocks * block))
        weight_of_row = _routed(_weight_of_row(w_slot, slot_of_row))
        routing = (row, token_of_row, counts, expert_of_block, used, fits,
                   held, local)
        if blocks >= most:
            y = _experts_sorted(x, row, token_of_row, weight_of_row,
                                expert_of_block, used, w1, w2, block, act,
                                kernel, sums)
        elif kernel:
            y = _held_terms_kept_by_inputs(x, w_slot, weight_of_row, w1, w2,
                                           routing, block, act, sums)
        else:
            y = _held_terms(x, w_slot, weight_of_row, w1, w2, routing, block,
                            act, False, sums)
    # the rows of each expert that were computed: in the buffer those of
    # its slots that have a row there, in the dense product all of them
    done = jnp.where(fits, placed, counts)
    return y, jnp.stack([counts, done]).astype(F32)


_MOE_DOC = """

    The router scores all ``n_routed`` experts (router_weight
    (n_routed, hidden), float32 product): ``s = score_func(x W_r^T)``,
    ``sigmoid`` (each expert alone) or ``softmax`` (over the router's
    experts); the ``top_k`` largest of ``s`` are chosen, of
    ``s + score_bias`` where that input is given; their weights are
    ``s[chosen]``, normalised to sum 1 (``norm_topk_prob``) and times
    ``routed_scaling_factor``. This chip holds the ``w1.shape[0]``
    experts from ``expert_offset`` on; only their terms are computed.
    ``activation`` names the expert: ``relu2``, w1 (held, width, hidden),
    w2 (held, hidden, width), ``f_e(x) = W2_e relu(W1_e x)^2``; or
    ``swiglu``, w1 (held, 2 x width, hidden) holding the gate's rows and
    then the up projection's, ``f_e(x) = W2_e (silu(W_gate,e x) *
    W_up,e x)``. Both attributes describe the model; neither changes how
    the rows are moved. Rows are gathered (XLA's gather), sorted by
    expert and padded to whole blocks of ``BLOCK_ROWS`` an expert, into
    one buffer of ``CAPACITY_FACTOR`` times the held experts' even
    share (plus a block an expert); which slot, token and weight each
    row holds is worked out once a call (one scatter in which no two
    updates share a place, one gather) and handed with the routing to
    the backward; and the blocks are multiplied by
    their experts' weights (:func:`_blocks_product`): by grouped Pallas
    kernels where the call allows them, which compute the blocks that
    hold a routed row and skip the buffer's empty tail, so their work
    follows the routing; else by one batched product over every block,
    the same work whatever the routing fills the buffer with; and
    each token's rows summed back (the window kernel of
    ``ops/pallas_moe_rows.py`` where the call allows it, else XLA's
    gather of every slot's row).
    Routing that overfills the buffer takes a dense product over the
    held experts that are routed a row instead, so no row is ever
    dropped. ``expert_rows`` (2, held) float32 is an auxiliary state
    (written, never differentiated): rows routed to each held expert in
    this call, and rows its product computed."""


@register("_contrib_moe_experts", num_outputs=1, mutate_aux={1: 3})
def moe_experts(data, router_weight, score_bias, expert_rows, w1, w2, *,
                top_k, expert_offset=0, routed_scaling_factor=1.0,
                norm_topk_prob=True, score_func="sigmoid",
                activation="relu2"):
    """The routed part of an expert layer over data (..., hidden):
    ``sum_{k: chosen_k held here} w_k f_{chosen_k}(x)``. ``score_bias``
    may be None (no bias on the choice)."""
    with jax.named_scope("mx.moe"):
        y, rows = _moe_experts(
            data.reshape(-1, data.shape[-1]), router_weight, score_bias,
            w1, w2, top_k=int(top_k), offset=int(expert_offset),
            scale=float(routed_scaling_factor),
            norm_topk=bool(norm_topk_prob), score_func=str(score_func),
            activation=str(activation))
    return y.reshape(data.shape), lax.stop_gradient(rows)


moe_experts.__doc__ += _MOE_DOC


@register("_contrib_moe_mixer", num_outputs=1, mutate_aux={1: 3})
def moe_mixer(data, norm_gamma, router_weight, expert_rows, w1, w2,
              score_bias=None, shared_w1=None, shared_w2=None, *, top_k,
              expert_offset=0, routed_scaling_factor=1.0, norm_topk_prob=True,
              score_func="sigmoid", activation="relu2", eps=1e-5):
    """A pre-norm expert mixer, ``mixer(RMSNorm(data))``: the routed
    part of :func:`moe_experts` plus, where ``shared_w1`` / ``shared_w2``
    are given, a shared expert of the same ``activation`` (shared_w1
    (width_s, hidden), or (2 x width_s, hidden) for ``swiglu``;
    shared_w2 (hidden, width_s)). The optional inputs come last: a
    model without a score bias or a shared expert leaves them out. data
    (batch, length, hidden). Recomputed in the backward
    (``jax.checkpoint``) but for the routing: a step keeps ``data`` and
    :func:`_routed`'s integers and row weights. What reads the
    normed input here is the router and the shared expert; the large
    products read the routed buffer, whose ``pre`` a chunk of blocks
    keeps already (``_HIDDEN``), so the module's rule adds nothing."""
    act = _ACTIVATIONS[str(activation)]

    # (the argument order PR 28 gave it: a compiled Nemotron step is
    # found again in the persistent cache)
    def mixer(data, norm_gamma, router_weight, score_bias, shared_w1,
              shared_w2, w1, w2):
        x = _rms(data, norm_gamma, float(eps)).reshape(-1, data.shape[-1])
        y, rows = _moe_experts(
            x, router_weight, score_bias, w1, w2, top_k=int(top_k),
            offset=int(expert_offset), scale=float(routed_scaling_factor),
            norm_topk=bool(norm_topk_prob), score_func=str(score_func),
            activation=str(activation))
        if shared_w1 is not None:
            y = y.astype(F32) + _mlp(x, shared_w1, shared_w2, act)
        return y.astype(data.dtype).reshape(data.shape), \
            lax.stop_gradient(rows)

    with jax.named_scope("mx.moe"):
        return jax.checkpoint(
            mixer, policy=jax.checkpoint_policies.save_only_these_names(
                _ROUTED))(data, norm_gamma, router_weight, score_bias,
                          shared_w1, shared_w2, w1, w2)


moe_mixer.__doc__ += _MOE_DOC


# ---------------------------------------------------------------------------
# a dense gated MLP; what a multi-token-prediction module adds
# ---------------------------------------------------------------------------
@register("_contrib_glu_mlp_mixer")
def glu_mlp_mixer(data, norm_gamma, gate_up_weight, down_weight, *,
                  eps=1e-5):
    """A pre-norm dense gated MLP (SwiGLU), ``mixer(h)``, ``h =
    RMSNorm(data)``: ``W_down (silu(W_gate h) * W_up h)``, no bias.
    gate_up_weight (2 x width, hidden) holds the gate's rows and then
    the up projection's (an expert's layout: one product for both),
    down_weight (hidden, width). data (batch, length, hidden).
    Recomputed whole in the backward (``jax.checkpoint``): a step keeps
    ``data`` only. The module's rule would keep ``gate_up``'s output,
    ``2 x width`` values a token: 1.54 GB in the LFM2 cell, whose step
    stands at 14.6 GB with the 1.8 GB the rule keeps there already, so
    it is left to a later change (PERF.md section 7)."""
    def mixer(data, norm_gamma, gate_up_weight, down_weight):
        x = _rms(data, norm_gamma, float(eps))
        return _mlp(x, gate_up_weight, down_weight, _swiglu) \
            .astype(data.dtype)

    with jax.named_scope("mx.mlp"):
        return jax.checkpoint(mixer)(data, norm_gamma, gate_up_weight,
                                     down_weight)


@register("_contrib_mtp_combine")
def mtp_combine(embedding, hidden, embedding_norm_gamma, hidden_norm_gamma,
                weight, *, eps=1e-5):
    """The input of a multi-token-prediction module's block
    (DeepSeek-V3, arXiv:2412.19437 eq. 21): ``u_t = W [RMSNorm_e(e_t) ;
    RMSNorm_h(g_t)]``, ``e_t`` the embedding of the token after ``t``'s
    (batch, length, hidden), ``g_t`` the stack's hidden state at ``t``
    before its final norm, weight (hidden, 2 x hidden) reading the
    embedding's lanes first, no bias. Recomputed in the backward: a
    step keeps the two inputs."""
    def combine(embedding, hidden, embedding_norm_gamma, hidden_norm_gamma,
                weight):
        return _dense(jnp.concatenate(
            [_rms(embedding, embedding_norm_gamma, float(eps)),
             _rms(hidden, hidden_norm_gamma, float(eps))], -1), weight)

    with jax.named_scope("mx.mtp"):
        return jax.checkpoint(combine)(
            embedding, hidden, embedding_norm_gamma, hidden_norm_gamma,
            weight)


@register("_contrib_mtp_loss", num_outputs=1, mutate_aux={1: 2})
def mtp_lm_loss(lm_loss, mtp_loss, loss_terms, *, mtp_weight):
    """``mean(lm_loss) + mtp_weight * mean(mtp_loss[..., :-1])``, shape
    (1,) float32: the next-token loss of every position plus the
    weighted loss of a depth-1 multi-token-prediction module, both
    per position (batch, length). The module's position ``t`` predicts
    the token two after ``t``'s, which the last position of a row does
    not have: it is left out of the second mean (whatever was fed
    there gets no gradient). ``loss_terms`` (2,) float32 is an
    auxiliary state (written, never differentiated): the two means of
    this call."""
    lm = jnp.mean(lm_loss.astype(F32))
    mtp = jnp.mean(mtp_loss[..., :-1].astype(F32))
    return (lm + float(mtp_weight) * mtp).reshape(1), \
        lax.stop_gradient(jnp.stack([lm, mtp]))
