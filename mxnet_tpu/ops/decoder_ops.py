"""Ops of a hybrid decoder layer stack: RMSNorm (plain, and gated over
groups), the causal depthwise conv and the chunked state-space (SSD)
scan of a Mamba-2 mixer, blocked causal grouped-query attention, and a
dropless expert layer that is told which experts of the router's range
it holds. (Dao & Gu, arXiv:2405.21060 sec. 6-7 for the scan; the layer
equations are those of docs/KERNELS.md "Hybrid decoder ops".)

All but one are XLA compositions, which a GSPMD mesh partitions like
any other op. Causal attention has two schedules of one algorithm: a
Pallas flash kernel (``ops/pallas_causal_gqa.py``) where the call is
one it can serve, the blocked composition here everywhere else
(:func:`_attend`). Matrix products take their inputs in the dtype they
are given (bf16 inside ``ShardedTrainStep``) and accumulate in float32;
decays, softmax, norms and the router are computed in float32.

Three *mixer* ops (``_contrib_mamba2_mixer``, ``_contrib_moe_mixer``,
``_contrib_gqa_mixer``) hold a whole pre-norm mixer each,
``mixer(RMSNorm(x))``, and are where recomputation lives: the Mamba-2
and expert mixers are ``jax.checkpoint``-ed whole, so a training step
keeps their input and recomputes their inside in the backward; the
attention mixer keeps its q/k/v/context (and, on the kernel path, the
rows' log-sum-exp) and recomputes each query block's scores. The
device-side scopes ``mx.mamba2``, ``mx.mamba2.ssd``, ``mx.moe``,
``mx.moe.experts`` and ``mx.attn.causal`` name their instructions in
the compiled program (forward, recomputation and backward alike).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry
from . import pallas_causal_gqa, register

F32 = jnp.float32
_HI = lax.Precision.HIGHEST

# Sizes that change speed and memory, never a result (constants, not op
# attributes: no caller has a reason to set them; mxbench's counts of
# what the scopes execute import them)
QUERY_BLOCK = 512       # attention: queries a block
BLOCK_ROWS = 512        # experts: rows a block of the sorted buffer
CAPACITY_FACTOR = 2.0   # experts: the buffer over the held experts' even share


def _mm(spec, a, b):
    """einsum on the MXU: inputs as given, float32 accumulation."""
    return jnp.einsum(spec, a, b, preferred_element_type=F32)


def _dense(x, w):
    """x (..., in) @ w (out, in)^T in x's dtype (MXNet Dense layout)."""
    return _mm("...i,oi->...o", x, w).astype(x.dtype)


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
def _causal_conv1d(x, w, b):
    k, length = w.shape[1], x.shape[1]
    xp = jnp.pad(x.astype(F32), ((0, 0), (k - 1, 0), (0, 0)))
    wf = w.astype(F32)
    y = b.astype(F32)
    for j in range(k):
        y = y + xp[:, j:j + length, :] * wf[:, j]
    return y.astype(x.dtype)


@register("_contrib_causal_conv1d")
def causal_conv1d(data, weight, bias):
    """Causal depthwise conv over time: data (batch, length, channels),
    weight (channels, k), bias (channels,);
    ``y[t] = bias + sum_j weight[:, j] * data[t - (k-1) + j]`` with
    zeros before the start."""
    return _causal_conv1d(data, weight, bias)


def _ssd(x, dt, a_neg, bm, cm, d_skip, chunk):
    b, length, heads, p = x.shape
    groups, n = bm.shape[2], bm.shape[3]
    rep = heads // groups
    q = int(chunk)
    pad = (-length) % q
    if pad:     # dt 0 there: the state is carried unchanged, y is cut
        x, dt, bm, cm = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) *
                                 (v.ndim - 2)) for v in (x, dt, bm, cm))
    nc = (length + pad) // q
    dtf = dt.astype(F32)
    # log-decays, cumulative inside each chunk: (b, nc, heads, q)
    acs = jnp.cumsum((dtf * a_neg.astype(F32)).reshape(b, nc, q, heads)
                     .transpose(0, 1, 3, 2), axis=-1)
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


@register("_contrib_ssd_scan")
def ssd_scan(data, dt, a, b, c, d, *, chunk_size=128):
    """The selective state-space recurrence of Mamba-2 in its chunked
    matrix form. data (batch, length, heads, head_dim); dt (batch,
    length, heads), already positive; a (heads,), negative; b, c
    (batch, length, groups, state), head h reading group
    ``h // (heads // groups)``; d (heads,). Per head, from a zero state,

        S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T
        y_t = S_t C_t + d x_t

    computed chunk by chunk (``chunk_size`` steps: products inside a
    chunk, one carried state between chunks); any length (the tail is
    padded with dt = 0)."""
    with jax.named_scope("mx.mamba2.ssd"):
        return _ssd(data, dt, a, b, c, d, chunk_size)


def _mamba2(u, norm_w, in_w, conv_w, conv_b, dt_bias, a_log, d_skip,
            gate_norm_w, out_w, *, heads, head_dim, groups, state, chunk,
            eps):
    b, length, _ = u.shape
    inner, gn = heads * head_dim, groups * state
    zxbcdt = _dense(_rms(u, norm_w, eps), in_w)
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:2 * inner + 2 * gn]
    dt = zxbcdt[..., 2 * inner + 2 * gn:]
    xbc = jax.nn.silu(_causal_conv1d(xbc, conv_w, conv_b).astype(F32)) \
        .astype(u.dtype)
    x = xbc[..., :inner].reshape(b, length, heads, head_dim)
    bm = xbc[..., inner:inner + gn].reshape(b, length, groups, state)
    cm = xbc[..., inner + gn:].reshape(b, length, groups, state)
    dt = jax.nn.softplus(dt.astype(F32) + dt_bias.astype(F32))
    with jax.named_scope("mx.mamba2.ssd"):
        y = _ssd(x, dt, -jnp.exp(a_log.astype(F32)), bm, cm, d_skip, chunk)
    y = _gated_rms(y.reshape(b, length, inner), z, gate_norm_w,
                   inner // groups, eps)
    return _dense(y, out_w)


@register("_contrib_mamba2_mixer")
def mamba2_mixer(data, norm_gamma, in_proj_weight, conv_weight, conv_bias,
                 dt_bias, a_log, d, gate_norm_gamma, out_proj_weight, *,
                 num_heads, head_dim, n_groups, state_size, chunk_size=128,
                 eps=1e-5):
    """A pre-norm Mamba-2 mixer, ``mixer(RMSNorm(data))``: in_proj to
    ``[z | xBC | dt]``, causal depthwise conv + SiLU over xBC, softplus
    dt, the SSD scan (:func:`ssd_scan`), the gated grouped RMSNorm and
    out_proj. data (batch, length, hidden). Recomputed whole in the
    backward (``jax.checkpoint``): a step keeps ``data`` only."""
    fn = jax.checkpoint(lambda *arrays: _mamba2(
        *arrays, heads=int(num_heads), head_dim=int(head_dim),
        groups=int(n_groups), state=int(state_size), chunk=int(chunk_size),
        eps=float(eps)))
    with jax.named_scope("mx.mamba2"):
        return fn(data, norm_gamma, in_proj_weight, conv_weight, conv_bias,
                  dt_bias, a_log, d, gate_norm_gamma, out_proj_weight)


# ---------------------------------------------------------------------------
# causal grouped-query attention
# ---------------------------------------------------------------------------
def _causal_gqa(q, k, v, block):
    b, length, heads, d = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, length, kv, heads // kv, d)
    scale = 1.0 / math.sqrt(d)

    @jax.checkpoint
    def rows(qb, kb, vb, first):
        # one block of queries against its prefix of keys
        s = _mm("bqgrd,bkgd->bgrqk", qb, kb) * scale
        qi = first + lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
        ki = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
        p = jax.nn.softmax(jnp.where(ki <= qi, s, -jnp.inf), axis=-1)
        return _mm("bgrqk,bkgd->bqgrd", p.astype(vb.dtype), vb) \
            .astype(qb.dtype)

    out = []
    for lo in range(0, length, block):
        hi = min(lo + block, length)
        out.append(rows(qg[:, lo:hi], k[:, :hi], v[:, :hi], lo))
    return jnp.concatenate(out, axis=1).reshape(b, length, heads, d)


def _attend(q, k, v):
    """Causal GQA by whichever schedule the call allows, chosen from
    what can be observed here and nothing else: the flash kernel for
    bf16 q / k / v with a head width of whole lane tiles, whole groups
    of query heads and a length of whole ``QUERY_BLOCK`` tiles, traced
    for one device (``pallas_causal_gqa.causal_gqa_available``); the
    blocked composition for everything else. Counted once a traced call
    in ``mx_attn_causal_path_total{path="pallas"|"xla"}``."""
    kernel = pallas_causal_gqa.causal_gqa_available(q, k, v, QUERY_BLOCK)
    telemetry.count_event("mx_attn_causal_path_total",
                          path="pallas" if kernel else "xla")
    with jax.named_scope(pallas_causal_gqa.SCOPE):
        if kernel:
            return pallas_causal_gqa.flash_causal_gqa(q, k, v, QUERY_BLOCK)
        return _causal_gqa(q, k, v, QUERY_BLOCK)


@register("_contrib_causal_gqa_attention")
def causal_gqa_attention(query, key, value):
    """Causal ``softmax(Q K^T / sqrt(d)) V`` with grouped keys and
    values and no positional term: query (batch, length, heads, d),
    key / value (batch, length, kv_heads, d), query head h reading
    key-value head ``h // (heads // kv_heads)``. Queries are taken
    ``QUERY_BLOCK`` at a time, each block against the keys up to its own
    end only (the masked upper triangle is not computed beyond the
    diagonal block), so no length x length array exists; each block's
    scores are recomputed in the backward (:func:`_attend`: in VMEM by
    the flash kernel, through HBM by the composition)."""
    return _attend(query, key, value)


@register("_contrib_gqa_mixer")
def gqa_mixer(data, norm_gamma, q_weight, k_weight, v_weight, o_weight, *,
              num_heads, num_kv_heads, head_dim, eps=1e-5):
    """A pre-norm attention mixer, ``mixer(RMSNorm(data))``: bias-free
    q/k/v projections, :func:`causal_gqa_attention`, bias-free output
    projection. data (batch, length, hidden). The score blocks are
    recomputed in the backward; q, k, v and the context are kept."""
    b, length, _ = data.shape
    h, kv, d = int(num_heads), int(num_kv_heads), int(head_dim)
    x = _rms(data, norm_gamma, float(eps))
    q = _dense(x, q_weight).reshape(b, length, h, d)
    k = _dense(x, k_weight).reshape(b, length, kv, d)
    v = _dense(x, v_weight).reshape(b, length, kv, d)
    ctx = _attend(q, k, v)
    return _dense(ctx.reshape(b, length, h * d), o_weight)


# ---------------------------------------------------------------------------
# routed experts
# ---------------------------------------------------------------------------
def _route(x, router_w, bias, top_k, scale, norm_topk):
    """(chosen expert ids (T, k), their weights (T, k) float32)."""
    logits = jnp.einsum("td,ed->te", x.astype(F32), router_w.astype(F32),
                        precision=_HI)
    s = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(s + bias.astype(F32), top_k)
    w = jnp.take_along_axis(s, idx, axis=1)
    if norm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * scale


def _relu2_mlp(x, w1, w2):
    h = jnp.square(jax.nn.relu(_mm("...d,fd->...f", x, w1))).astype(x.dtype)
    return _mm("...f,df->...d", h, w2)


def _slots_to_rows(held, local, n_held, cap, block):
    """Where each (token, choice) slot sits in a buffer of ``cap`` rows
    sorted by expert, each expert's run padded to whole blocks of
    ``block`` rows: the slot's row number, the (padded) rows before its
    expert plus its rank among the expert's (``cap``, one past the end,
    for a slot that is not held here or falls beyond the buffer); the
    routed count of each held expert; the expert of each block; and
    whether the padded runs fit the buffer."""
    t, k = held.shape
    onehot = (local.reshape(-1, 1) == jnp.arange(n_held)) \
        & held.reshape(-1, 1)
    rank = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
    counts = jnp.sum(onehot, axis=0, dtype=jnp.int32)
    ends = jnp.cumsum(-(-counts // block) * block)
    first = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    row = jnp.sum(jnp.where(onehot, rank + first, 0), axis=1).reshape(t, k)
    expert_of_block = jnp.minimum(
        jnp.sum(jnp.arange(0, cap, block)[:, None] >= ends, axis=1),
        n_held - 1)
    return (jnp.where(held & (row < cap), row, cap), counts,
            expert_of_block, ends[-1] <= cap)


@jax.custom_vjp
def _gather_rows(x, token_of_row, row_of_slot):
    """Rows of ``x`` (T, D) into a buffer: ``out[r] = x[token_of_row[r]]``
    (a zero row where ``token_of_row[r] == T``)."""
    return jnp.concatenate([x, jnp.zeros_like(x[:1])])[token_of_row]


@jax.custom_vjp
def _sum_slots(rows, token_of_row, row_of_slot):
    """Each token's slots summed back: ``out[t] = sum_j rows[row_of_slot
    [t, j]]`` (a zero row where the slot is one past the end)."""
    ext = jnp.concatenate([rows, jnp.zeros_like(rows[:1])])
    return jnp.sum(ext[row_of_slot].astype(F32), axis=1).astype(rows.dtype)


# the two are each other's transposes (slots <-> rows is one-to-one on
# what is held), so both backwards are gathers and no scatter runs
_gather_rows.defvjp(
    lambda x, tr, rs: (_gather_rows(x, tr, rs), (tr, rs)),
    lambda res, g: (_sum_slots(g, *res), None, None))
_sum_slots.defvjp(
    lambda rows, tr, rs: (_sum_slots(rows, tr, rs), (tr, rs)),
    lambda res, g: (_gather_rows(g, *res), None, None))


def _experts_sorted(x, row, w_slot, expert_of_block, up, down, block):
    """Rows gathered into one buffer sorted by expert, whole blocks an
    expert; one batched product over the blocks, each against its
    expert's weights (the same work whatever the routing: a block is
    computed whole, rows that no slot fills are zeros); summed back by
    token. Also the rows of each expert that were computed."""
    t, n_held = x.shape[0], up.shape[0]
    cap = expert_of_block.shape[0] * block
    slots = jnp.broadcast_to(jnp.arange(t)[:, None], row.shape)
    token_of_row = jnp.full((cap + 1,), t, jnp.int32) \
        .at[row.reshape(-1)].set(slots.reshape(-1))[:-1]
    weight_of_row = jnp.zeros((cap + 1,), F32) \
        .at[row.reshape(-1)].set(w_slot.reshape(-1))[:-1]
    xr = _gather_rows(x, token_of_row, row).reshape(-1, block, x.shape[1])
    h = jnp.square(jax.nn.relu(
        _mm("bmd,bfd->bmf", xr, up[expert_of_block]))).astype(x.dtype)
    yr = _mm("bmf,bdf->bmd", h, down[expert_of_block]).reshape(cap, -1)
    yr = (yr * weight_of_row[:, None]).astype(x.dtype)
    filled = (token_of_row < t).reshape(-1, block)
    done = jnp.sum(jnp.where(
        expert_of_block[:, None] == jnp.arange(n_held),
        jnp.sum(filled, axis=1, dtype=jnp.int32)[:, None], 0), axis=0)
    return _sum_slots(yr, token_of_row, row), done


def _experts_dense(x, held, local, w_slot, counts, up, down):
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
            return _relu2_mlp(x, a, b) * we[:, None]

        return lax.cond(counts[e] > 0, routed,
                        lambda: jnp.zeros(x.shape, F32))

    acc, _ = lax.scan(lambda acc, ew: (acc + term(*ew), None),
                      jnp.zeros(x.shape, F32),
                      (jnp.arange(up.shape[0]), up, down))
    return acc.astype(x.dtype)


def _moe_experts(x, router_w, bias, w1, w2, *, top_k, offset, scale,
                 norm_topk, capacity_factor=CAPACITY_FACTOR,
                 block_rows=BLOCK_ROWS):
    t = x.shape[0]
    n_held, n_routed = w1.shape[0], router_w.shape[0]
    idx, w_slot = _route(x, router_w, bias, top_k, scale, norm_topk)
    local = idx - offset
    held = (local >= 0) & (local < n_held)
    # one buffer for the held experts' rows together: `capacity_factor`
    # times their even share and a block of padding an expert. A token
    # chooses an expert at most once, so t x min(top_k, held) rows (and
    # their padding) always do
    even = t * top_k / n_routed
    block = min(int(block_rows), -(-math.ceil(capacity_factor * even) // 8) * 8)
    most = -(-t * min(top_k, n_held) // block) + n_held
    blocks = min(most, math.ceil(capacity_factor * even * n_held / block)
                 + n_held)
    row, counts, expert_of_block, fits = _slots_to_rows(
        held, local, n_held, blocks * block, block)
    with jax.named_scope("mx.moe.experts"):
        def sorted_rows():
            return _experts_sorted(x, row, w_slot, expert_of_block, w1, w2,
                                   block)

        if blocks >= most:
            y, done = sorted_rows()
        else:
            # no row is dropped: routing that overfills the buffer takes
            # the dense product over the held experts instead (whole
            # matrices at the MXU's pace: where routing piles the tokens
            # on a few experts, cheaper than more passes of the gathered
            # product, which PR 28 measured at 5x its cost)
            y, done = lax.cond(
                fits, sorted_rows,
                lambda: (_experts_dense(x, held, local, w_slot, counts, w1,
                                        w2), counts))
    return y, jnp.stack([counts, done]).astype(F32)


_MOE_DOC = """

    The router scores all ``n_routed`` experts (router_weight
    (n_routed, hidden), float32 product): ``s = sigmoid(x W_r^T)``, the
    ``top_k`` largest of ``s + score_bias`` are chosen, their weights
    are ``s[chosen]``, normalised to sum 1 (``norm_topk_prob``) and
    times ``routed_scaling_factor``. This chip holds the
    ``w1.shape[0]`` experts from ``expert_offset`` on: w1 (held, width,
    hidden), w2 (held, hidden, width), ``f_e(x) = W2_e relu(W1_e x)^2``;
    only their terms are computed. Rows are gathered, sorted by expert
    and padded to whole blocks of ``BLOCK_ROWS`` an expert, into one
    buffer of ``CAPACITY_FACTOR`` times the held experts' even share
    (plus a block an expert), and multiplied in one batched product
    over the blocks: the same work whatever the routing fills it with.
    Routing that overfills the buffer takes a dense product over the
    held experts that are routed a row instead, so no row is ever
    dropped. ``expert_rows`` (2, held) float32 is an auxiliary state
    (written, never differentiated): rows routed to each held expert in
    this call, and rows its product computed."""


@register("_contrib_moe_experts", num_outputs=1, mutate_aux={1: 3})
def moe_experts(data, router_weight, score_bias, expert_rows, w1, w2, *,
                top_k, expert_offset=0, routed_scaling_factor=1.0,
                norm_topk_prob=True):
    """The routed part of an expert layer over data (..., hidden):
    ``sum_{k: chosen_k held here} w_k f_{chosen_k}(x)``."""
    with jax.named_scope("mx.moe"):
        y, rows = _moe_experts(
            data.reshape(-1, data.shape[-1]), router_weight, score_bias,
            w1, w2, top_k=int(top_k), offset=int(expert_offset),
            scale=float(routed_scaling_factor),
            norm_topk=bool(norm_topk_prob))
    return y.reshape(data.shape), lax.stop_gradient(rows)


moe_experts.__doc__ += _MOE_DOC


@register("_contrib_moe_mixer", num_outputs=1, mutate_aux={1: 4})
def moe_mixer(data, norm_gamma, router_weight, score_bias, expert_rows,
              shared_w1, shared_w2, w1, w2, *, top_k, expert_offset=0,
              routed_scaling_factor=1.0, norm_topk_prob=True, eps=1e-5):
    """A pre-norm expert mixer, ``mixer(RMSNorm(data))``: the shared
    expert (the same squared-ReLU MLP, shared_w1 (width_s, hidden),
    shared_w2 (hidden, width_s)) plus the routed part of
    :func:`moe_experts`. data (batch, length, hidden). Recomputed whole
    in the backward (``jax.checkpoint``): a step keeps ``data`` only."""
    def mixer(data, norm_gamma, router_weight, score_bias, shared_w1,
              shared_w2, w1, w2):
        x = _rms(data, norm_gamma, float(eps)).reshape(-1, data.shape[-1])
        y, rows = _moe_experts(
            x, router_weight, score_bias, w1, w2, top_k=int(top_k),
            offset=int(expert_offset), scale=float(routed_scaling_factor),
            norm_topk=bool(norm_topk_prob))
        y = y.astype(F32) + _relu2_mlp(x, shared_w1, shared_w2)
        return y.astype(data.dtype).reshape(data.shape), \
            lax.stop_gradient(rows)

    with jax.named_scope("mx.moe"):
        return jax.checkpoint(mixer)(data, norm_gamma, router_weight,
                                     score_bias, shared_w1, shared_w2, w1, w2)


moe_mixer.__doc__ += _MOE_DOC
