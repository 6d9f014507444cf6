"""Plain float32 jax.numpy reference of granite-4.0-h-micro (Granite
4.0-H, dense) on packed rows: Mamba-2 layers with the recurrence
written step by step and the state set to zero at a document's first
token, a causal depthwise conv whose taps read zeros before it, one
masked softmax (causal, and inside the query's document) for the NoPE
grouped-query attention with the model's own score factor, a dense
SwiGLU MLP in every layer, the four multipliers, the tied head, the
next-token loss, and AdamW steps through ``jax.grad``. No kernels, no
bf16, no chunked scan, no large negative decay in a start's place;
callers run it under ``jax.default_matmul_precision("highest")``. It
imports nothing of ``mxnet_tpu``.

Layer equations: configs/granite_4_0_h_micro.json ``equations``.
Departures from config.json are in that file under ``reduced`` and
``assumed``. Weights come by name from the Gluon parameters
(configs/granite_4_0_h_micro.py::named_weights).

So that it fits beside its own optimizer state at sequence 8,192 it
recomputes layer by layer (``jax.checkpoint`` around each branch of
each layer and around each run of ``SEGMENT`` time steps), takes
attention's queries ``QUERY_BLOCK`` at a time and the MLP's and the
head's tokens ``TOKEN_BLOCK`` at a time; none changes what is computed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

SEGMENT = 128           # time steps between kept states (memory only)
QUERY_BLOCK = 512       # queries a masked softmax (memory only)
TOKEN_BLOCK = 2048      # tokens a block of the MLP and the head (memory only)

MAMBA, ATTENTION = "mamba", "attention"


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _starts(seg):
    """(batch, length) bool: a token whose id is not the one before its
    own starts a document."""
    return jnp.concatenate([jnp.zeros_like(seg[:, :1], bool),
                            seg[:, 1:] != seg[:, :-1]], axis=1)


def _token_blocks(fn, x):
    """``fn`` over x (batch, length, ...) ``TOKEN_BLOCK`` tokens at a
    time, one block after another, each recomputed in the backward."""
    b, length = x.shape[:2]
    if length <= TOKEN_BLOCK or length % TOKEN_BLOCK:
        return fn(x)
    xs = jnp.moveaxis(x.reshape((b, length // TOKEN_BLOCK, TOKEN_BLOCK)
                                + x.shape[2:]), 1, 0)
    ys = lax.map(jax.checkpoint(fn), xs)
    return jnp.moveaxis(ys, 0, 1).reshape((b, length) + ys.shape[3:])


def conv(x, w, b, seg):
    """Causal depthwise conv inside documents, x (batch, length,
    channels), w (channels, k): y[t] = b + sum_j w[:, j] x[t - (k-1) +
    j], a term left out where its source token lies before the start of
    the row or in another document than ``t``."""
    k, length = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    sp = jnp.pad(seg, ((0, 0), (k - 1, 0)), constant_values=-1)
    y = b
    for j in range(k):
        same = sp[:, j:j + length] == seg
        y = y + jnp.where(same[..., None], xp[:, j:j + length], 0.0) * w[:, j]
    return y


def recurrence(x, dt, a, bm, cm, d, start):
    """S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t + d x_t,
    one time step at a time from S = 0, and S_{t-1} = 0 again wherever
    ``start`` (batch, length) says that t opens a document. x (batch,
    length, heads, p), dt (batch, length, heads), a (heads,), bm / cm
    (batch, length, groups, n), d (heads,)."""
    b, length, heads, p = x.shape
    groups, n = bm.shape[2], bm.shape[3]
    rep = heads // groups

    def step(s, xs):
        # s (b, groups, rep, p, n); a group's B and C meet its heads' states
        xt, dtt, bt, ct, new = xs   # (b, g, r, p) (b, g, r) (b, g, n) x2 (b,)
        s = jnp.where(new[:, None, None, None, None], 0.0, s)
        s = jnp.exp(dtt * ah)[..., None, None] * s \
            + (dtt[..., None] * xt)[..., None] * bt[:, :, None, None, :]
        return s, jnp.sum(s * ct[:, :, None, None, :], -1)

    def run(s, xs):
        return lax.scan(step, s, xs)

    ah = a.reshape(groups, rep)
    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (
        x.reshape(b, length, groups, rep, p),
        dt.reshape(b, length, groups, rep), bm, cm, start))
    s0 = jnp.zeros((b, groups, rep, p, n), jnp.float32)
    if length > SEGMENT and length % SEGMENT == 0:
        xs = tuple(v.reshape((length // SEGMENT, SEGMENT) + v.shape[1:])
                   for v in xs)
        _, y = lax.scan(jax.checkpoint(run), s0, xs)
        y = y.reshape((length,) + y.shape[2:])
    else:
        _, y = run(s0, xs)
    return jnp.moveaxis(y, 0, 1).reshape(x.shape) + d[:, None] * x


def mamba2(w, p, u, seg, cfg):
    heads, hp = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    groups, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    inner, gn = heads * hp, groups * n
    b, length, _ = u.shape
    zxbcdt = u @ w[p + "in_proj_weight"].T
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * gn], axis=-1)
    xbc = jax.nn.silu(conv(xbc, w[p + "conv_weight"], w[p + "conv_bias"],
                           seg))
    x, bm, cm = jnp.split(xbc, [inner, inner + gn], axis=-1)
    y = recurrence(x.reshape(b, length, heads, hp),
                   jax.nn.softplus(dt + w[p + "dt_bias"]),
                   -jnp.exp(w[p + "a_log"]),
                   bm.reshape(b, length, groups, n),
                   cm.reshape(b, length, groups, n), w[p + "d"], _starts(seg))
    y = y.reshape(b, length, inner) * jax.nn.silu(z)
    size = inner // groups
    y = _rms(y.reshape(b, length, groups, size), 1.0,
             cfg["rms_norm_eps"]).reshape(b, length, inner)
    return (y * w[p + "gate_norm_weight"]) @ w[p + "out_proj_weight"].T


def attention(w, p, x, seg, cfg):
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    b, length, _ = x.shape
    q = (x @ w[p + "q_weight"].T).reshape(b, length, heads, d)
    k = (x @ w[p + "k_weight"].T).reshape(b, length, kv, d)
    v = (x @ w[p + "v_weight"].T).reshape(b, length, kv, d)
    k, v = (jnp.repeat(t, heads // kv, axis=2) for t in (k, v))
    keys = jnp.arange(length)

    @jax.checkpoint
    def block(xs):
        qb, sb, first = xs
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * cfg["attention_multiplier"]
        seen = (keys[None, :] <= (first + jnp.arange(qb.shape[1]))[:, None]) \
            & (sb[:, :, None] == seg[:, None, :])
        att = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", att, v)

    # one block after another (lax.map): unrolled, the compiler runs
    # the blocks' backwards side by side and the scores do not fit
    size = min(QUERY_BLOCK, length)
    blocks = -(-length // size)
    pad = blocks * size - length
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qp = jnp.moveaxis(qp.reshape(b, blocks, size, heads, d), 1, 0)
    # (a padded query takes the last token's id, so that it sees a key)
    sp = jnp.moveaxis(jnp.pad(seg, ((0, 0), (0, pad)), mode="edge")
                      .reshape(b, blocks, size), 1, 0)
    ctx = lax.map(block, (qp, sp, jnp.arange(blocks) * size))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, blocks * size, heads, d)
    ctx = ctx[:, :length]
    return ctx.reshape(b, length, heads * d) @ w[p + "o_weight"].T


def mlp(w, p, x):
    def tokens(x):
        a, b = jnp.split(x @ w[p + "gate_up_weight"].T, 2, axis=-1)
        return (jax.nn.silu(a) * b) @ w[p + "down_weight"].T
    return _token_blocks(tokens, x)


MIXERS = {MAMBA: mamba2, ATTENTION: attention}


def model_cfg(sizes):
    """The sizes as the layers read them: the scalars, and the kinds of
    the layers built (the first ``num_hidden_layers`` of the published
    list)."""
    cfg = {k: v for k, v in sizes.items()
           if isinstance(v, (int, float, str, bool))}
    cfg["layer_types"] = list(sizes["layer_types"][:sizes["num_hidden_layers"]])
    return cfg


def forward(w, ids, seg, cfg):
    """ids, seg (batch, length) -> hidden states after the last norm."""
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    x = w["embed_weight"][ids] * cfg["embedding_multiplier"]
    for i, kind in enumerate(cfg["layer_types"]):
        p = "layers%d_" % i

        @jax.checkpoint
        def mixer(x, lw, kind=kind, p=p):
            return x + r * MIXERS[kind](
                lw, p, _rms(x, lw[p + "op_norm_weight"], eps), seg, cfg)

        @jax.checkpoint
        def ffn(x, lw, p=p):
            return x + r * mlp(lw, p, _rms(x, lw[p + "ffn_norm_weight"], eps))

        lw = {k: v for k, v in w.items() if k.startswith(p)}
        x = ffn(mixer(x, lw), lw)
    return _rms(x, w["norm_f_weight"], eps)


def logits(w, ids, seg, cfg):
    """(batch, length, vocabulary): the tied head over the hidden
    states, divided by ``logits_scaling``."""
    return forward(w, ids, seg, cfg) @ w["embed_weight"].T \
        / cfg["logits_scaling"]


def lm_loss(w, ids, seg, labels, cfg):
    """Mean cross-entropy over every position against ``labels`` (the
    feed's next tokens)."""
    h = forward(w, ids, seg, cfg)

    def tokens(hl):
        logp = jax.nn.log_softmax(
            hl[0] @ w["embed_weight"].T / cfg["logits_scaling"], -1)
        return -jnp.take_along_axis(
            logp, hl[1].astype(jnp.int32)[..., None], -1)

    b, length = labels.shape
    if length <= TOKEN_BLOCK or length % TOKEN_BLOCK:
        return tokens((h, labels)).mean()
    blocks = length // TOKEN_BLOCK
    hs = jnp.moveaxis(h.reshape(b, blocks, TOKEN_BLOCK, -1), 1, 0)
    ls = jnp.moveaxis(labels.reshape(b, blocks, TOKEN_BLOCK), 1, 0)
    return lax.map(jax.checkpoint(tokens), (hs, ls)).mean()


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


FROZEN = ("seq_documents",)


def train_losses(weights, batch, sizes, optimizer, steps, lower=False,
                 reset=True):
    """The losses of ``steps`` AdamW steps on one packed batch (ids,
    segment ids, labels as integer arrays), each loss taken before its
    update. ``seq_documents`` is the program's auxiliary state: no
    weight, left out.

    ``lower`` and ``reset=False`` are the check's two controls, not
    references. ``lower``: the same steps in the nearest precision
    below the one the configuration states, bf16 masters (the weights
    rounded to bf16 at the start and after every update) and products
    at the device's default precision (one bf16 pass on the chip).
    ``reset=False``: the same model on the same tokens with every id
    taken as one document's, so that taps, state and attention cross
    every boundary."""
    if optimizer["name"] != "adamw":
        raise ValueError("reference/granite_4_0_h_micro.py implements "
                         "AdamW, not %r" % optimizer["name"])
    ids, seg, labels = (jnp.asarray(a, jnp.int32) for a in batch)
    if not reset:
        seg = jnp.zeros_like(seg)
    held = (lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)) if lower \
        else (lambda a: a)
    w = {k: held(jnp.asarray(a, jnp.float32)) for k, a in weights.items()
         if not k.endswith(FROZEN)}
    m = {k: jnp.zeros_like(a) for k, a in w.items()}
    v = {k: jnp.zeros_like(a) for k, a in w.items()}
    cfg = model_cfg(sizes)

    def step(w, m, v, t, ids, seg, labels):
        loss, g = jax.value_and_grad(
            lambda w: lm_loss(w, ids, seg, labels, cfg))(w)
        new = {k: _adamw(w[k], g[k], m[k], v[k], t, optimizer) for k in w}
        return (loss, {k: held(n[0]) for k, n in new.items()},
                {k: n[1] for k, n in new.items()},
                {k: n[2] for k, n in new.items()})

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    losses = []
    with jax.default_matmul_precision("default" if lower else "highest"):
        for t in range(1, steps + 1):
            loss, w, m, v = step(w, m, v, float(t), ids, seg, labels)
            losses.append(float(loss))
    return losses
