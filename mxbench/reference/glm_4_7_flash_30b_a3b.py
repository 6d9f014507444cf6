"""Plain float32 jax.numpy reference of GLM-4.7-Flash: every layer
``x <- x + MLA(RMSNorm(x))``, ``x <- x + F_l(RMSNorm(x))``; latent
attention written out from the configuration file's equations (two
low-rank bottlenecks with a norm inside each, one rotary key head a
token used by every query head, 256-lane heads, the causal mask by
``jnp.where`` on index arithmetic); a dense SwiGLU MLP in the leading
layers, then a sigmoid top-k router with a selection bias over SwiGLU
experts as a dense loop over the experts held here, and a shared
expert; the multi-token-prediction module (the next token's embedding
and the stack's last hidden state combined, one more block, the main
model's head) on the positions that have a token two ahead, sliced,
never padded; both losses; AdamW steps through ``jax.grad``. No
kernels, no bf16, no tile is skipped, no sorting of rows by expert;
callers run it under ``jax.default_matmul_precision("highest")``.

Layer equations: configs/glm_4_7_flash_30b_a3b.json ``equations``; what
the published ``config.json`` does not settle is in that file under
``assumed``, what was cut under ``reduced``. Weights come by name from
the Gluon parameters (configs/glm_4_7_flash_30b_a3b.py::named_weights).
It imports nothing of the program's.

For memory only, so that it fits the chip beside its own optimizer
state at 8,192 tokens (16 bytes a parameter with the gradients leave
4.4 GB): each layer is recomputed in the backward (``jax.checkpoint``),
attention takes its queries ``QUERY_BLOCK`` at a time against all the
keys (``lax.map``, each block recomputed), the held experts are taken
one after another (``lax.scan``, an expert's hidden layer recomputed),
and the cross-entropy takes the positions ``CE_BLOCK`` at a time. None
of these changes what is computed: a block of queries still scores
every key and masks by position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 256       # queries a block of whole score rows (memory only)
CE_BLOCK = 2048         # positions a block of logits (memory only)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope_table(cfg, length):
    """(cos, sin), each (length, pairs): pair j of the rotary lanes
    turns by ``p * theta^(-j / pairs)``. ``partial_rotary_factor`` (1
    as published) says what share of the ``qk_rope_head_dim`` lanes
    turn; ``rope_scaling`` is null, so there is no factor on the scale."""
    pairs = int(cfg["qk_rope_head_dim"] * cfg["partial_rotary_factor"]) // 2
    j = jnp.arange(pairs, dtype=jnp.float32)
    freq = float(cfg["rope_theta"]) ** (-j / max(pairs, 1))
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * freq
    return jnp.cos(angle), jnp.sin(angle)


def rotate(x, table):
    """x (batch, length, heads, r): of the lanes that turn (the first
    ``2 x pairs``), lane j and lane j + pairs turn together."""
    cos, sin = (t[None, :, None, :] for t in table)
    pairs = cos.shape[-1]
    a, b, rest = x[..., :pairs], x[..., pairs:2 * pairs], x[..., 2 * pairs:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def attention(w, p, h, cfg):
    """The latent-attention branch's output for the normed input h. A
    ``kv_a_weight`` that gives more than ``kv_lora_rank + r`` rows
    holds a rotary key for every head (a wrong model the tests hold
    the program against); the published one gives one."""
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, r, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                   cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    b, length, _ = h.shape
    table = rope_table(cfg, length)
    c_q = _rms(h @ w[p + "q_a_weight"].T, w[p + "q_a_norm_weight"], eps)
    q = (c_q @ w[p + "q_b_weight"].T).reshape(b, length, heads, nope + r)
    down = h @ w[p + "kv_a_weight"].T
    c_kv = _rms(down[..., :rank], w[p + "kv_a_norm_weight"], eps)
    k_rope = rotate(down[..., rank:].reshape(b, length, -1, r), table)
    up = (c_kv @ w[p + "kv_b_weight"].T).reshape(b, length, heads, nope + vd)
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], table)], -1)
    k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(
        k_rope, (b, length, heads, r))], -1)
    v = up[..., nope:]

    @jax.checkpoint
    def block(xs):
        qb, first = xs
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(float(nope + r))
        t = first + jnp.arange(qb.shape[1])[:, None]
        seen = jnp.arange(length)[None, :] <= t
        att = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", att, v)

    size = min(QUERY_BLOCK, length)
    blocks = -(-length // size)
    pad = blocks * size - length
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qp = jnp.moveaxis(qp.reshape((b, blocks, size) + q.shape[2:]), 1, 0)
    ctx = lax.map(block, (qp, jnp.arange(blocks) * size))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, blocks * size, heads * vd)
    return ctx[:, :length] @ w[p + "o_weight"].T


def swiglu(x, gate_up, down):
    """``gate_up`` holds the gate's rows, then the up projection's."""
    width = gate_up.shape[0] // 2
    return (jax.nn.silu(x @ gate_up[:width].T) * (x @ gate_up[width:].T)) \
        @ down.T


def route(w, p, x, cfg):
    """(chosen experts (..., k), their weights (..., k)): sigmoid
    scores, the top k of score + bias, the chosen scores renormalised
    and scaled."""
    s = jax.nn.sigmoid(x @ w[p + "router_weight"].T)
    _, chosen = lax.top_k(s + w[p + "e_score_correction_bias"],
                          cfg["num_experts_per_tok"])
    wk = jnp.take_along_axis(s, chosen, -1)
    if cfg["norm_topk_prob"]:
        wk = wk / wk.sum(-1, keepdims=True)
    return chosen, wk * cfg["routed_scaling_factor"]


def experts(w, p, x, cfg):
    """The terms of the experts held here, ``expert_offset`` on (the
    other chosen experts' terms are left out, as on the chip that lacks
    them), and the shared expert's."""
    chosen, wk = route(w, p, x, cfg)
    gate_up, down = w[p + "experts_gate_up_weight"], \
        w[p + "experts_down_weight"]
    first = cfg.get("expert_offset", 0)

    # memory only: one expert after another, its hidden layer recomputed
    @jax.checkpoint
    def add(y, held):
        e, gu, dn = held
        we = jnp.sum(jnp.where(chosen == first + e, wk, 0.0), -1)
        return y + we[..., None] * swiglu(x, gu, dn), None

    y = lax.scan(add, jnp.zeros_like(x),
                 (jnp.arange(gate_up.shape[0]), gate_up, down))[0]
    if cfg["n_shared_experts"]:
        y = y + swiglu(x, w[p + "shared_gate_up_weight"],
                       w[p + "shared_down_weight"])
    return y


def layer(w, p, x, cfg, dense):
    """One layer: the dense MLP where ``dense``, else the experts."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(w, p, _rms(x, w[p + "attn_norm_weight"], eps), cfg)
    h = _rms(x, w[p + "mlp_norm_weight"], eps)
    if dense:
        return x + swiglu(h, w[p + "gate_up_weight"], w[p + "down_weight"])
    return x + experts(w, p, h, cfg)


def _checkpointed_layer(w, p, x, cfg, dense=False):
    return jax.checkpoint(lambda x, lw: layer(lw, p, x, cfg, dense))(
        x, {k: v for k, v in w.items() if k.startswith(p)})


def forward(w, ids, cfg):
    """ids (batch, length) -> (hidden states after norm_f (batch,
    length, hidden), the multi-token-prediction module's after its last
    norm (batch, length - 1, hidden): position t there read token t + 1
    and predicts token t + 2)."""
    eps = cfg["rms_norm_eps"]
    x = w["embed_weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = _checkpointed_layer(w, "layers%d_" % i, x, cfg,
                                dense=i < cfg["first_k_dense_replace"])
    hidden = _rms(x, w["norm_f_weight"], eps)
    u = jnp.concatenate(
        [_rms(w["embed_weight"][ids[:, 1:]], w["mtp_embed_norm_weight"], eps),
         _rms(x[:, :-1], w["mtp_hidden_norm_weight"], eps)], -1) \
        @ w["mtp_combine_weight"].T
    y = _checkpointed_layer(w, "mtp_block_", u, cfg)
    return hidden, _rms(y, w["mtp_norm_weight"], eps)


def _mean_ce(hidden, head, labels):
    """Mean cross-entropy of ``hidden @ head^T`` against ``labels``."""
    hidden = hidden.reshape(-1, head.shape[1])
    flat = labels.reshape(-1)
    size = min(CE_BLOCK, flat.shape[0])
    blocks = -(-flat.shape[0] // size)
    pad = blocks * size - flat.shape[0]

    @jax.checkpoint
    def block(xs):
        h, y, real = xs
        logp = jax.nn.log_softmax(h @ head.T, -1)
        nll = -jnp.take_along_axis(logp, y[:, None], -1)[:, 0]
        return jnp.where(real, nll, 0.0).sum()

    real = jnp.arange(blocks * size) < flat.shape[0]
    total = lax.map(block, (
        jnp.pad(hidden, ((0, pad), (0, 0))).reshape(blocks, size, -1),
        jnp.pad(flat, (0, pad)).reshape(blocks, size),
        real.reshape(blocks, size)))
    return total.sum() / flat.shape[0]


def loss_terms(w, ids, labels, cfg):
    """(the mean next-token loss over every position, the module's mean
    loss over the positions that have a token two ahead): ``labels``
    are the feed's next tokens, so the module's targets are
    ``labels[:, 1:]``."""
    hidden, mtp_hidden = forward(w, ids, cfg)
    return (_mean_ce(hidden, w["head_weight"], labels),
            _mean_ce(mtp_hidden, w["head_weight"], labels[:, 1:]))


def lm_loss(w, ids, labels, cfg):
    lm, mtp = loss_terms(w, ids, labels, cfg)
    return lm + cfg["mtp_loss_weight"] * mtp


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


# counts and the last step's loss terms, never read; the selection
# bias is read, seeded, and never updated
STATES = ("expert_rows", "loss_terms")
FROZEN = ("e_score_correction_bias",)


def model_cfg(sizes):
    """The configuration file's keys as this file reads them."""
    cfg = {k: x for k, x in sizes.items()
           if isinstance(x, (int, float, str, bool))}
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
        raise ValueError("reference/glm_4_7_flash_30b_a3b.py implements "
                         "AdamW, not %r" % optimizer["name"])
    ids, labels = (jnp.asarray(a, jnp.int32) for a in batch)
    held = (lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)) if lower \
        else (lambda a: a)
    fixed = {k: jnp.asarray(a, jnp.float32) for k, a in weights.items()
             if k.endswith(FROZEN)}
    w = {k: held(jnp.asarray(a, jnp.float32)) for k, a in weights.items()
         if not k.endswith(STATES + FROZEN)}
    m = {k: jnp.zeros_like(a) for k, a in w.items()}
    v = {k: jnp.zeros_like(a) for k, a in w.items()}
    cfg = model_cfg(sizes)

    def step(w, m, v, t, ids, labels):
        loss, g = jax.value_and_grad(
            lambda w: lm_loss(dict(w, **fixed), ids, labels, cfg))(w)
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
