"""Plain float32 jax.numpy reference of the causal tower of
Nemotron-Labs-TwoTower-30B-A3B (a Nemotron-H stack): Mamba-2 layers
with the recurrence written step by step, squared-ReLU experts behind
a sigmoid top-k router as a dense loop over the experts held here, one
masked softmax for the NoPE grouped-query attention, the next-token
loss, and AdamW steps through ``jax.grad``. No kernels, no bf16, no
chunked scan, no sorting of rows; callers run it under
``jax.default_matmul_precision("highest")``.

Layer equations (configs/nemotron_twotower_30b_a3b.json ``equations``):
``x <- x + mixer_i(RMSNorm(x))``, then ``norm_f`` and an untied head.
Departures from config.json are in that file under ``reduced`` and
``assumed``. Weights come by name from the Gluon parameters
(configs/nemotron_twotower_30b_a3b.py::named_weights).

So that it fits beside its own optimizer state at sequence 8,192 it
recomputes layer by layer (``jax.checkpoint`` around each layer and
around each run of ``SEGMENT`` time steps) and takes attention's
queries ``QUERY_BLOCK`` at a time; neither changes what is computed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

SEGMENT = 128           # time steps between kept states (memory only)
QUERY_BLOCK = 512       # queries a masked softmax (memory only)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _relu2_mlp(x, up, down):
    return jnp.square(jax.nn.relu(x @ up.T)) @ down.T


def _conv(x, w, b):
    """Causal depthwise conv, x (batch, length, channels), w (channels,
    k): y[t] = b + sum_j w[:, j] x[t - (k-1) + j]."""
    k, length = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return b + sum(xp[:, j:j + length] * w[:, j] for j in range(k))


def recurrence(x, dt, a, bm, cm, d):
    """S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t + d x_t,
    one time step at a time from S_0 = 0. x (batch, length, heads, p),
    dt (batch, length, heads), a (heads,), bm / cm (batch, length,
    groups, n), d (heads,)."""
    b, length, heads, p = x.shape
    rep = heads // bm.shape[2]
    bh, ch = jnp.repeat(bm, rep, axis=2), jnp.repeat(cm, rep, axis=2)

    def step(s, xs):
        xt, dtt, bt, ct = xs            # (b, heads, p) (b, heads) (b, heads, n)
        s = jnp.exp(dtt * a)[..., None, None] * s \
            + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :]
        return s, jnp.sum(s * ct[:, :, None, :], -1)

    def run(s, xs):
        return lax.scan(step, s, xs)

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, bh, ch))
    s0 = jnp.zeros((b, heads, p, bm.shape[3]), jnp.float32)
    if length > SEGMENT and length % SEGMENT == 0:
        xs = tuple(v.reshape((length // SEGMENT, SEGMENT) + v.shape[1:])
                   for v in xs)
        _, y = lax.scan(jax.checkpoint(run), s0, xs)
        y = y.reshape((length,) + y.shape[2:])
    else:
        _, y = run(s0, xs)
    return jnp.moveaxis(y, 0, 1) + d[:, None] * x


def mamba2(w, p, u, cfg):
    heads, hp = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner, gn = heads * hp, groups * n
    b, length, _ = u.shape
    zxbcdt = u @ w[p + "in_proj_weight"].T
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * gn], axis=-1)
    xbc = jax.nn.silu(_conv(xbc, w[p + "conv_weight"], w[p + "conv_bias"]))
    x, bm, cm = jnp.split(xbc, [inner, inner + gn], axis=-1)
    y = recurrence(x.reshape(b, length, heads, hp),
                   jax.nn.softplus(dt + w[p + "dt_bias"]),
                   -jnp.exp(w[p + "a_log"]),
                   bm.reshape(b, length, groups, n),
                   cm.reshape(b, length, groups, n), w[p + "d"])
    y = y.reshape(b, length, inner) * jax.nn.silu(z)
    size = inner // groups
    y = _rms(y.reshape(b, length, groups, size), 1.0,
             cfg["layer_norm_epsilon"]).reshape(b, length, inner)
    return (y * w[p + "gate_norm_weight"]) @ w[p + "out_proj_weight"].T


def route(w, p, x, cfg):
    """(chosen experts (..., k), their weights (..., k))."""
    s = jax.nn.sigmoid(x @ w[p + "router_weight"].T)
    _, chosen = lax.top_k(s + w[p + "e_score_correction_bias"],
                          cfg["num_experts_per_tok"])
    wk = jnp.take_along_axis(s, chosen, -1)
    if cfg["norm_topk_prob"]:
        wk = wk / (wk.sum(-1, keepdims=True) + 1e-20)
    return chosen, wk * cfg["routed_scaling_factor"]


def experts(w, p, x, cfg, shared=True):
    """The shared expert (once) plus the terms of the experts held
    here, ``expert_offset`` on; the other chosen experts' terms are
    left out, as on the chip that lacks them."""
    chosen, wk = route(w, p, x, cfg)
    y = _relu2_mlp(x, w[p + "shared_up_weight"], w[p + "shared_down_weight"]) \
        if shared else jnp.zeros_like(x)
    up, down = w[p + "experts_up_weight"], w[p + "experts_down_weight"]
    for e in range(up.shape[0]):
        we = jnp.sum(jnp.where(chosen == cfg.get("expert_offset", 0) + e,
                               wk, 0.0), -1)
        y = y + we[..., None] * _relu2_mlp(x, up[e], down[e])
    return y


def attention(w, p, x, cfg):
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    b, length, _ = x.shape
    q = (x @ w[p + "q_weight"].T).reshape(b, length, heads, d)
    k = (x @ w[p + "k_weight"].T).reshape(b, length, kv, d)
    v = (x @ w[p + "v_weight"].T).reshape(b, length, kv, d)
    k, v = (jnp.repeat(t, heads // kv, axis=2) for t in (k, v))
    keys = jnp.arange(length)

    @jax.checkpoint
    def block(xs):
        qb, first = xs
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(float(d))
        seen = keys[None, :] <= (first + jnp.arange(qb.shape[1]))[:, None]
        att = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", att, v)

    # one block after another (lax.map): unrolled, the compiler runs
    # the blocks' backwards side by side and the scores do not fit
    size = min(QUERY_BLOCK, length)
    blocks = -(-length // size)
    qp = jnp.pad(q, ((0, 0), (0, blocks * size - length), (0, 0), (0, 0)))
    qp = jnp.moveaxis(qp.reshape(b, blocks, size, heads, d), 1, 0)
    ctx = lax.map(block, (qp, jnp.arange(blocks) * size))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, blocks * size, heads, d)
    ctx = ctx[:, :length]
    return ctx.reshape(b, length, heads * d) @ w[p + "o_weight"].T


MIXERS = {"M": mamba2, "E": experts, "*": attention}


def forward(w, ids, cfg):
    """ids (batch, length) -> hidden states after norm_f."""
    eps = cfg["layer_norm_epsilon"]
    x = w["embed_weight"][ids]
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    for i, kind in enumerate(pattern):
        p = "layers%d_" % i

        @jax.checkpoint
        def layer(x, lw, kind=kind, p=p):
            return x + MIXERS[kind](lw, p, _rms(x, lw[p + "norm_weight"],
                                                eps), cfg)

        x = layer(x, {k: v for k, v in w.items() if k.startswith(p)})
    return _rms(x, w["norm_f_weight"], eps)


def lm_loss(w, ids, labels, cfg):
    """Mean cross-entropy over every position against ``labels`` (the
    feed's next tokens)."""
    logits = forward(w, ids, cfg) @ w["head_weight"].T
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1).mean()


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


FROZEN = ("e_score_correction_bias", "expert_rows")


def train_losses(weights, batch, sizes, optimizer, steps, lower=False):
    """The losses of ``steps`` AdamW steps on one batch (ids, labels as
    integer arrays), each loss taken before its update. The router's
    bias is an auxiliary state: it is read and never updated.

    ``lower`` is the check's control, not a reference: the same steps
    in the nearest precision below the one the configuration states,
    bf16 masters (the weights rounded to bf16 at the start and after
    every update) and products at the device's default precision (one
    bf16 pass on the chip). The cell's check has to call it wrong."""
    if optimizer["name"] != "adamw":
        raise ValueError("reference/nemotron_twotower_30b_a3b.py implements "
                         "AdamW, not %r" % optimizer["name"])
    ids, labels = (jnp.asarray(a, jnp.int32) for a in batch)
    frozen = {k: jnp.asarray(a, jnp.float32) for k, a in weights.items()
              if k.endswith(FROZEN)}
    held = (lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)) if lower \
        else (lambda a: a)
    w = {k: held(jnp.asarray(a, jnp.float32)) for k, a in weights.items()
         if k not in frozen}
    m = {k: jnp.zeros_like(a) for k, a in w.items()}
    v = {k: jnp.zeros_like(a) for k, a in w.items()}
    cfg = {k: x for k, x in sizes.items()
           if isinstance(x, (int, float, str, bool))}
    cfg["expert_offset"] = sizes["deployment"]["expert_offset"]

    def step(w, m, v, t, frozen, ids, labels):
        loss, g = jax.value_and_grad(
            lambda w: lm_loss(dict(w, **frozen), ids, labels, cfg))(w)
        new = {k: _adamw(w[k], g[k], m[k], v[k], t, optimizer) for k in w}
        return (loss, {k: held(n[0]) for k, n in new.items()},
                {k: n[1] for k, n in new.items()},
                {k: n[2] for k, n in new.items()})

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    losses = []
    with jax.default_matmul_precision("default" if lower else "highest"):
        for t in range(1, steps + 1):
            loss, w, m, v = step(w, m, v, float(t), frozen, ids, labels)
            losses.append(float(loss))
    return losses
