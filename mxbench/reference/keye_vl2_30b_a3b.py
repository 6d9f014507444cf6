"""Plain float32 jax.numpy reference of Keye-VL-2.0-30B-A3B's language
model: every layer ``x <- x + Attn(RMSNorm(x))``, ``x <- x +
MoE(RMSNorm(x))``; grouped-query attention with RMSNorm over each head
of q and k and multi-axis rotary positions, over the keys a learned
selector keeps (whole rows of index scores, ``lax.top_k``'s indices, a
softmax over the set they name); a softmax top-k router over SwiGLU
experts as a dense loop over the experts held here; the next-token loss
plus the selector's own loss; AdamW steps through ``jax.grad``. No
kernels, no bf16, no threshold search, no sorting of rows by expert;
callers run it under ``jax.default_matmul_precision("highest")``.

Layer equations: configs/keye_vl2_30b_a3b.json ``equations``; what the
published ``config.json`` does not settle is in that file under
``assumed``, what was cut under ``reduced``. Weights come by name from
the Gluon parameters (configs/keye_vl2_30b_a3b.py::named_weights). It
imports nothing of the program's.

So that it fits beside its own optimizer state at sequence 8,192 it
recomputes layer by layer (``jax.checkpoint`` around each layer) and
takes attention's queries ``QUERY_BLOCK`` at a time (``lax.map``, each
block recomputed in the backward); neither changes what is computed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 512       # queries a block of whole score rows (memory only)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def rope(x, positions, theta, sections=None):
    """x (batch, length, heads, d): lane i and lane i + d/2 turn
    together by ``position * theta^(-i / (d/2))``. positions (batch,
    length); with ``sections`` (frequency pairs an axis) they are
    (axes, batch, length) and pair i reads the axis whose section holds
    it (M-RoPE: the first ``sections[0]`` pairs time, the next height,
    the rest width)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    pos = positions.astype(jnp.float32)
    if sections is None:
        angle = pos[..., None] * inv
    else:
        bounds = [sum(sections[:a]) for a in range(len(sections) + 1)]
        angle = jnp.concatenate(
            [pos[a][..., None] * inv[bounds[a]:bounds[a + 1]]
             for a in range(len(sections))], -1)
    cos, sin = jnp.cos(angle)[:, :, None], jnp.sin(angle)[:, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def index_scores(iq, ik, iw):
    """I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]): iq (b, q, j, d),
    ik (b, s, d), iw (b, q, j) -> (b, q, s). Zeros of either sign are
    one value (+0), as ``lax.top_k``'s ties want them."""
    s = jnp.einsum("bqjd,bsd->bqjs", iq, ik)
    out = jnp.einsum("bqjs,bqj->bqs", jax.nn.relu(s), iw)
    return jnp.where(out == 0, 0.0, out)


def selected(scores, first, top_k):
    """The set S_t as a mask (b, q, keys): the ``top_k`` keys of
    largest score among those a query sees (s <= t), all of them while
    it sees no more than ``top_k``; ``lax.top_k`` breaks ties to the
    lower index."""
    b, q, n = scores.shape
    seen = jnp.arange(n)[None, :] <= (first + jnp.arange(q))[:, None]
    _, idx = lax.top_k(jnp.where(seen, scores, -jnp.inf), min(top_k, n))
    named = jnp.zeros((b, q, n), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(q)[None, :, None], idx] \
        .set(True)
    return named & seen


def attention(w, p, x, positions, cfg):
    """(the attention branch's output, this layer's index loss)."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps, theta = cfg["head_dim"], cfg["rms_norm_eps"], cfg["rope_theta"]
    sa = cfg["sa_config"]
    ih, idim, top_k = (sa["indexer_num_heads"], sa["indexer_head_dim"],
                       sa["topk"])
    sections = cfg["rope_scaling"]["mrope_section"]
    b, length, _ = x.shape
    q = _rms((x @ w[p + "q_weight"].T).reshape(b, length, heads, d),
             w[p + "q_norm_weight"], eps)
    k = _rms((x @ w[p + "k_weight"].T).reshape(b, length, kv, d),
             w[p + "k_norm_weight"], eps)
    v = (x @ w[p + "v_weight"].T).reshape(b, length, kv, d)
    q, k = rope(q, positions, theta, sections), \
        rope(k, positions, theta, sections)
    k, v = (jnp.repeat(t, heads // kv, axis=2) for t in (k, v))

    # the selector reads the normed input and hands it no gradient
    xi = lax.stop_gradient(x)
    iq = rope((xi @ w[p + "index_q_weight"].T).reshape(b, length, ih, idim),
              positions[0], theta)
    ik = _layer_norm(xi @ w[p + "index_k_weight"].T,
                     w[p + "index_k_norm_weight"],
                     w[p + "index_k_norm_bias"], eps)
    ik = rope(ik[:, :, None], positions[0], theta)[:, :, 0]
    iw = xi @ w[p + "index_w_weight"].T / jnp.sqrt(float(ih * idim))

    @jax.checkpoint
    def block(xs):
        qb, iqb, iwb, first = xs
        scores = index_scores(iqb, ik, iwb)
        keep = selected(lax.stop_gradient(scores), first, top_k)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(float(d))
        att = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), -1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", att, v)
        # KL(mean over heads of att || softmax over S_t of the scores)
        target = lax.stop_gradient(att.mean(1))
        logq = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
        kl = jnp.where(keep & (target > 0),
                       target * (jnp.log(jnp.where(target > 0, target, 1.0))
                                 - jnp.where(keep, logq, 0.0)), 0.0)
        real = first + jnp.arange(qb.shape[1]) < length    # not padding
        return ctx, jnp.where(real[:, None], kl, 0.0).sum((-1, -2))

    # one block after another (lax.map): unrolled, the compiler runs the
    # blocks' backwards side by side and the scores do not fit
    size = min(QUERY_BLOCK, length)
    blocks = -(-length // size)
    pad = blocks * size - length

    def cut(t):     # (b, length, ...) -> (blocks, b, size, ...)
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(t.reshape((b, blocks, size) + t.shape[2:]), 1, 0)

    ctx, kl = lax.map(block, (cut(q), cut(iq), cut(iw),
                              jnp.arange(blocks) * size))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, blocks * size, heads, d)
    ctx = ctx[:, :length]
    out = ctx.reshape(b, length, heads * d) @ w[p + "o_weight"].T
    return out, kl.sum() / (b * length)


def route(w, p, x, cfg):
    """(chosen experts (..., k), their weights (..., k))."""
    pr = jax.nn.softmax(x @ w[p + "router_weight"].T, -1)
    wk, chosen = lax.top_k(pr, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        wk = wk / wk.sum(-1, keepdims=True)
    return chosen, wk


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def experts(w, p, x, cfg):
    """The terms of the experts held here, ``expert_offset`` on; the
    other chosen experts' terms are left out, as on the chip that lacks
    them. ``experts_gate_up_weight`` holds an expert's gate rows, then
    its up projection's."""
    chosen, wk = route(w, p, x, cfg)
    gate_up, down = w[p + "experts_gate_up_weight"], \
        w[p + "experts_down_weight"]
    width = gate_up.shape[1] // 2
    y = jnp.zeros_like(x)
    for e in range(gate_up.shape[0]):
        we = jnp.sum(jnp.where(chosen == cfg.get("expert_offset", 0) + e,
                               wk, 0.0), -1)
        y = y + we[..., None] * swiglu(x, gate_up[e, :width],
                                       gate_up[e, width:], down[e])
    return y


def forward(w, ids, cfg, positions=None):
    """ids (batch, length) [, position ids (3, batch, length)] ->
    (hidden states after norm_f, the layers' summed index loss)."""
    eps = cfg["rms_norm_eps"]
    b, length = ids.shape
    if positions is None:       # text: every axis the token's index
        positions = jnp.broadcast_to(jnp.arange(length), (3, b, length))
    x = w["embed_weight"][ids]
    total = 0.0
    for i in range(cfg["num_hidden_layers"]):
        p = "layers%d_" % i

        @jax.checkpoint
        def layer(x, lw, p=p):
            y, loss = attention(lw, p, _rms(x, lw[p + "attn_norm_weight"],
                                            eps), positions, cfg)
            x = x + y
            return x + experts(lw, p, _rms(x, lw[p + "moe_norm_weight"], eps),
                               cfg), loss

        x, loss = layer(x, {k: v for k, v in w.items() if k.startswith(p)})
        total = total + loss
    return _rms(x, w["norm_f_weight"], eps), total


def lm_loss(w, ids, labels, cfg, positions=None):
    """Mean cross-entropy over every position against ``labels`` (the
    feed's next tokens) plus the index loss, weighted 1."""
    hidden, index_loss = forward(w, ids, cfg, positions)
    logp = jax.nn.log_softmax(hidden @ w["head_weight"].T, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1).mean() \
        + index_loss


def _adamw(w, g, m, v, t, o):
    """MXNet's AdamW: the bias corrections folded into the rate (so
    epsilon is added to the uncorrected sqrt(v), Kingma & Ba sec. 2's
    efficient form), and a decoupled decay ``wd * w`` that the rate
    does not scale."""
    m = o["beta1"] * m + (1 - o["beta1"]) * g
    v = o["beta2"] * v + (1 - o["beta2"]) * g * g
    lr_t = o["lr"] * jnp.sqrt(1 - o["beta2"] ** t) / (1 - o["beta1"] ** t)
    return (w - lr_t * m / (jnp.sqrt(v) + o["epsilon"])
            - o["wd"] * w, m, v)


FROZEN = ("expert_rows", "dsa_state")       # counts, never read


def model_cfg(sizes):
    """The configuration file's keys as this file reads them."""
    cfg = {k: x for k, x in sizes.items()
           if isinstance(x, (int, float, str, bool))}
    cfg["sa_config"] = sizes["sa_config"]
    cfg["rope_scaling"] = sizes["rope_scaling"]
    cfg["expert_offset"] = sizes["deployment"]["expert_offset"]
    return cfg


def train_losses(weights, batch, sizes, optimizer, steps, lower=False):
    """The losses of ``steps`` AdamW steps on one batch (ids, labels as
    integer arrays), each loss taken before its update.

    ``lower`` is the check's control, not a reference: the same steps
    in the nearest precision below the one the configuration states,
    bf16 masters (the weights rounded to bf16 at the start and after
    every update) and products at the device's default precision (one
    bf16 pass on the chip). The cell's check has to call it wrong."""
    if optimizer["name"] != "adamw":
        raise ValueError("reference/keye_vl2_30b_a3b.py implements AdamW, "
                         "not %r" % optimizer["name"])
    ids, labels = (jnp.asarray(a, jnp.int32) for a in batch)
    held = (lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)) if lower \
        else (lambda a: a)
    w = {k: held(jnp.asarray(a, jnp.float32)) for k, a in weights.items()
         if not k.endswith(FROZEN)}
    m = {k: jnp.zeros_like(a) for k, a in w.items()}
    v = {k: jnp.zeros_like(a) for k, a in w.items()}
    cfg = model_cfg(sizes)

    def step(w, m, v, t, ids, labels):
        loss, g = jax.value_and_grad(
            lambda w: lm_loss(w, ids, labels, cfg))(w)
        new = {k: _adamw(w[k], g[k], m[k], v[k], t, optimizer) for k in w}
        return (loss, {k: held(n[0]) for k, n in new.items()},
                {k: n[1] for k, n in new.items()},
                {k: n[2] for k, n in new.items()})

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    losses = []
    with jax.default_matmul_precision("default" if lower else "highest"):
        for t in range(1, steps + 1):
            loss, w, m, v = step(w, m, v, float(t), ids, labels)
            losses.append(float(loss))
    return losses
