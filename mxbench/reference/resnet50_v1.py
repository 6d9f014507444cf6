"""Plain float32 jax.numpy reference of the ResNet v1 family as He et
al. Table 1 gives it (bottleneck blocks from 50 layers up, basic blocks
below), batch norm in training mode, softmax cross-entropy, and SGD
with momentum through ``jax.grad``. No layout pass, no bf16. Weights
come by name from the Gluon parameters; within a stage the model zoo
numbers convolutions and batch norms in the order the blocks create
them (body first, then the projection shortcut)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5


def _conv(x, w, stride, pad, bias=None):
    y = lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return y if bias is None else y + bias[None, :, None, None]


def _bn(x, g, b):
    mu = x.mean((0, 2, 3), keepdims=True)
    var = ((x - mu) ** 2).mean((0, 2, 3), keepdims=True)
    return ((x - mu) / jnp.sqrt(var + BN_EPS) * g[None, :, None, None]
            + b[None, :, None, None])


class _Names:
    """conv2_d<k> / batch_norm<k> counters of one name scope."""

    def __init__(self, w, prefix):
        self.w, self.prefix, self.c, self.b = w, prefix, 0, 0

    def conv(self, x, stride, pad):
        name = "%sconv2_d%d_" % (self.prefix, self.c)
        self.c += 1
        return _conv(x, self.w[name + "weight"], stride, pad,
                     self.w.get(name + "bias"))

    def bn(self, x):
        name = "%sbatch_norm%d_" % (self.prefix, self.b)
        self.b += 1
        return _bn(x, self.w[name + "gamma"], self.w[name + "beta"])


def logits(w, x, sizes):
    bottleneck = sizes["depth"] >= 50
    top = _Names(w, "")
    x = jax.nn.relu(top.bn(top.conv(x, 2, 3)))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    c_in = sizes["stage_channels"][0]
    for stage, blocks in enumerate(sizes["stage_blocks"]):
        n = _Names(w, "stage%d_" % (stage + 1))
        c_out = sizes["stage_channels"][stage + 1]
        for blk in range(blocks):
            stride = 2 if (stage > 0 and blk == 0) else 1
            if bottleneck:
                y = jax.nn.relu(n.bn(n.conv(x, stride, 0)))
                y = jax.nn.relu(n.bn(n.conv(y, 1, 1)))
                y = n.bn(n.conv(y, 1, 0))
            else:
                y = jax.nn.relu(n.bn(n.conv(x, stride, 1)))
                y = n.bn(n.conv(y, 1, 1))
            if blk == 0 and c_in != c_out:
                x = n.bn(n.conv(x, stride, 0))
            x = jax.nn.relu(x + y)
            c_in = c_out
    x = x.mean((2, 3))
    return x @ w["dense0_weight"].T + w["dense0_bias"]


def loss_of(w, x, labels, sizes):
    logp = jax.nn.log_softmax(logits(w, x, sizes), -1)
    return -jnp.take_along_axis(logp, labels[:, None], -1).mean()


def train_losses(weights, batch, sizes, optimizer, steps):
    """The losses of ``steps`` SGD-with-momentum steps on one batch
    (images, labels), each loss taken before its update. Batch norm's
    running statistics take no part in a training-mode loss and are
    left alone."""
    if optimizer["name"] != "sgd":
        raise ValueError("reference/resnet50_v1.py implements SGD, not %r"
                         % optimizer["name"])
    x = jnp.asarray(batch[0], jnp.float32)
    labels = jnp.asarray(batch[1], jnp.int32)
    w = {k: jnp.asarray(a, jnp.float32) for k, a in weights.items()
         if "running_" not in k}
    mom = {k: jnp.zeros_like(a) for k, a in w.items()}
    lr, mu, wd = (optimizer["lr"], optimizer["momentum"],
                  optimizer.get("wd", 0.0))

    # the batch is an argument, not a constant of the program: one
    # compiled reference serves every seed from the persistent cache
    @jax.jit
    def step(w, mom, x, labels):
        loss, g = jax.value_and_grad(loss_of)(w, x, labels, sizes)
        mom = {k: mu * mom[k] - lr * (g[k] + wd * w[k]) for k in w}
        return loss, {k: w[k] + mom[k] for k in w}, mom

    losses = []
    with jax.default_matmul_precision("highest"):
        for _ in range(steps):
            loss, w, mom = step(w, mom, x, labels)
            losses.append(float(loss))
    return losses
