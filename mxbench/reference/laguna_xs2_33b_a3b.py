"""Plain float32 jax.numpy reference of Laguna-XS.2: every layer ``x <-
x + Attn_l(RMSNorm(x))``, ``x <- x + F_l(RMSNorm(x))``; grouped-query
attention without a norm on q or k, layer ``l``'s query heads from
``num_attention_heads_per_layer``, over every earlier key
(``full_attention``) or over the last ``sliding_window`` keys
(``sliding_attention``), the mask written out by ``jnp.where`` on index
arithmetic; a rotary table per layer type over the lanes its
``partial_rotary_factor`` gives (the rest of a head passes unchanged),
YaRN's written out from the configuration file's equations over those
lanes' pairs; a sigmoid gate a head, ``sigmoid(W_g h)``, on the context
before the output projection; ``F_l`` a dense SwiGLU MLP or a softmax
top-k router (renormalised, scaled) over SwiGLU experts as a dense loop
over the experts held here, plus the shared expert; the next-token
loss; AdamW steps through ``jax.grad``. No kernels, no bf16, no tile is
skipped, no sorting of rows by expert; callers run it under
``jax.default_matmul_precision("highest")``.

Layer equations: configs/laguna_xs2_33b_a3b.json ``equations``; what the
published ``config.json`` does not settle is in that file under
``assumed``, what was cut under ``reduced``. Weights come by name from
the Gluon parameters (configs/laguna_xs2_33b_a3b.py::named_weights). It
imports nothing of the program's.

For memory only, so that it fits the chip beside its own optimizer
state at 8,192 tokens: each layer is recomputed in the backward
(``jax.checkpoint``), attention takes its queries ``QUERY_BLOCK`` at a
time against all the keys (``lax.map``, each block recomputed), the
held experts are taken one after another (``lax.scan``, an expert's
hidden layer recomputed), and the cross-entropy takes the positions
``CE_BLOCK`` at a time. None of these changes what is computed: a block
of queries still scores every key and masks by position.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 256       # queries a block of whole score rows (memory only)
CE_BLOCK = 2048         # positions a block of logits (memory only)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotary_lanes(rope, head_dim):
    """The lanes of a head that a layer type's table turns."""
    return int(head_dim * float(rope.get("partial_rotary_factor", 1)))


def rope_table(rope, head_dim, length):
    """(cos, sin), each (length, r / 2), of one layer type's
    ``rope_parameters`` entry, ``r = head_dim x partial_rotary_factor``
    the lanes turned. ``default``: pair j turns by ``p * theta^(-2j /
    r)``. ``yarn``: ``f_j = theta^(-2j / r)``; ``c(t) = r ln(L0 / (2 pi
    t)) / (2 ln theta)``; ``low = floor(c(beta_fast))``, ``high =
    ceil(c(beta_slow))``, clipped to [0, r - 1]; ``g_j = clip((j - low)
    / (high - low), 0, 1)``; pair j turns by ``p (f_j (1 - g_j) + f_j /
    factor g_j)``; cos and sin times ``attention_factor``."""
    r = rotary_lanes(rope, head_dim)
    pairs = r // 2
    theta = float(rope["rope_theta"])
    j = jnp.arange(pairs, dtype=jnp.float32)
    freq = theta ** (-j / pairs)
    scale = 1.0
    if rope.get("rope_type", "default") == "yarn":
        factor = float(rope["factor"])
        orig = float(rope["original_max_position_embeddings"])

        def c(turns):
            return r * math.log(orig / (2 * math.pi * turns)) \
                / (2 * math.log(theta))

        low = max(math.floor(c(float(rope["beta_fast"]))), 0)
        high = min(math.ceil(c(float(rope["beta_slow"]))), r - 1)
        g = jnp.clip((j - low) / (high - low), 0.0, 1.0)
        freq = freq * (1 - g) + freq / factor * g
        scale = float(rope["attention_factor"])
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * freq
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def rotate(x, table):
    """x (batch, length, heads, d): with ``r / 2`` pairs in the table,
    lane j < r / 2 and lane j + r / 2 turn together; lanes r.. pass
    unchanged."""
    cos, sin = (t[None, :, None, :] for t in table)
    half = cos.shape[-1]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def visible(first, queries, keys, window):
    """(queries, keys) bool: key s is seen by query t = first + row iff
    ``s <= t`` and, with a window, ``t - s < window``."""
    t = first + jnp.arange(queries)[:, None]
    s = jnp.arange(keys)[None, :]
    return (s <= t) if window is None else (s <= t) & (t - s < window)


def attention(w, p, x, index, cfg):
    """The attention branch's output for layer ``index``."""
    kind = cfg["layer_types"][index]
    heads = cfg["num_attention_heads_per_layer"][index]
    kv, d = cfg["num_key_value_heads"], cfg["head_dim"]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    b, length, _ = x.shape
    table = rope_table(cfg["rope_parameters"][kind], d, length)
    q = (x @ w[p + "q_weight"].T).reshape(b, length, heads, d)
    k = (x @ w[p + "k_weight"].T).reshape(b, length, kv, d)
    v = (x @ w[p + "v_weight"].T).reshape(b, length, kv, d)
    q, k = rotate(q, table), rotate(k, table)
    # query head i reads key-value head i // (heads / kv): a group at a
    # time, so no repeated copy of k and v exists (memory only)
    qg = q.reshape(b, length, kv, heads // kv, d)

    @jax.checkpoint
    def block(xs):
        qb, first = xs
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k) / jnp.sqrt(float(d))
        seen = visible(first, qb.shape[1], length, window)
        att = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", att, v)

    size = min(QUERY_BLOCK, length)
    blocks = -(-length // size)
    pad = blocks * size - length
    qp = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    qp = jnp.moveaxis(qp.reshape((b, blocks, size) + qg.shape[2:]), 1, 0)
    ctx = lax.map(block, (qp, jnp.arange(blocks) * size))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, blocks * size, heads, d)
    ctx = ctx[:, :length]
    if cfg["gating"]:
        # one value a query head a token, of the layer's normed input
        ctx = ctx * jax.nn.sigmoid(x @ w[p + "attn_gate_weight"].T)[..., None]
    return ctx.reshape(b, length, heads * d) @ w[p + "o_weight"].T


def route(w, p, x, cfg):
    """(chosen experts (..., k), their weights (..., k)): softmax over
    all the router's experts, the top k, renormalised to sum 1, times
    ``moe_routed_scaling_factor``."""
    pr = jax.nn.softmax(x @ w[p + "router_weight"].T, -1)
    wk, chosen = lax.top_k(pr, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        wk = wk / wk.sum(-1, keepdims=True)
    return chosen, wk * cfg["moe_routed_scaling_factor"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def glu(x, gate_up, down):
    """``gate_up`` holds the gate's rows, then the up projection's."""
    width = gate_up.shape[0] // 2
    return swiglu(x, gate_up[:width], gate_up[width:], down)


def routed_experts(w, p, x, cfg):
    """The terms of the experts held here, ``expert_offset`` on; the
    other chosen experts' terms are left out, as on the chip that lacks
    them."""
    chosen, wk = route(w, p, x, cfg)
    first = cfg.get("expert_offset", 0)

    # memory only: one expert after another (unrolled, the compiler
    # runs the experts' backwards side by side), its hidden layer
    # recomputed
    @jax.checkpoint
    def add(y, held):
        e, gate_up, down = held
        we = jnp.sum(jnp.where(chosen == first + e, wk, 0.0), -1)
        return y + we[..., None] * glu(x, gate_up, down), None

    gate_up = w[p + "experts_gate_up_weight"]
    return lax.scan(add, jnp.zeros_like(x),
                    (jnp.arange(gate_up.shape[0]), gate_up,
                     w[p + "experts_down_weight"]))[0]


def shared_expert(w, p, x):
    """Every token's, ungated; every chip computes it alike."""
    return glu(x, w[p + "shared_gate_up_weight"], w[p + "shared_down_weight"])


def experts(w, p, x, cfg):
    return routed_experts(w, p, x, cfg) + shared_expert(w, p, x)


def dense_mlp(w, p, x, cfg=None):
    return glu(x, w[p + "gate_up_weight"], w[p + "down_weight"])


def forward(w, ids, cfg):
    """ids (batch, length) -> hidden states after norm_f."""
    eps = cfg["rms_norm_eps"]
    x = w["embed_weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        p = "layers%d_" % i
        mlp = dense_mlp if cfg["mlp_layer_types"][i] == "dense" else experts

        @jax.checkpoint
        def layer(x, lw, p=p, i=i, mlp=mlp):
            x = x + attention(lw, p, _rms(x, lw[p + "attn_norm_weight"], eps),
                              i, cfg)
            return x + mlp(lw, p, _rms(x, lw[p + "mlp_norm_weight"], eps),
                           cfg)

        x = layer(x, {k: v for k, v in w.items() if k.startswith(p)})
    return _rms(x, w["norm_f_weight"], eps)


def logits(w, ids, cfg):
    return forward(w, ids, cfg) @ w["head_weight"].T


def lm_loss(w, ids, labels, cfg):
    """Mean cross-entropy over every position against ``labels`` (the
    feed's next tokens)."""
    hidden = forward(w, ids, cfg).reshape(-1, w["head_weight"].shape[1])
    flat = labels.reshape(-1)
    size = min(CE_BLOCK, flat.shape[0])
    blocks = -(-flat.shape[0] // size)
    pad = blocks * size - flat.shape[0]

    @jax.checkpoint
    def block(xs):
        h, y, real = xs
        logp = jax.nn.log_softmax(h @ w["head_weight"].T, -1)
        nll = -jnp.take_along_axis(logp, y[:, None], -1)[:, 0]
        return jnp.where(real, nll, 0.0).sum()

    real = jnp.arange(blocks * size) < flat.shape[0]
    total = lax.map(block, (
        jnp.pad(hidden, ((0, pad), (0, 0))).reshape(blocks, size, -1),
        jnp.pad(flat, (0, pad)).reshape(blocks, size),
        real.reshape(blocks, size)))
    return total.sum() / flat.shape[0]


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


FROZEN = ("expert_rows",)       # counts, never read


def model_cfg(sizes):
    """The configuration file's keys as this file reads them."""
    cfg = {k: x for k, x in sizes.items()
           if isinstance(x, (int, float, str, bool))}
    for key in ("layer_types", "num_attention_heads_per_layer",
                "mlp_layer_types", "rope_parameters"):
        cfg[key] = sizes[key]
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
        raise ValueError("reference/laguna_xs2_33b_a3b.py implements AdamW, "
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
