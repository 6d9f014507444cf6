"""What a zoo decoder's test file starts from (a plain module, not
collected): the toy model built from a seed, its weights under the
reference's names, a batch of token ids, the one-device
``ShardedTrainStep`` the benchmark trains it by, and the sizes the
reference's ``train_losses`` takes. A model's test file is then its
``CFG`` and the tests of what is new in it:

    TOY = Toy("mellum2_12b_a2_5b", zoo.MellumModel, zoo.MellumLMLoss, CFG)
    REF, CFGMOD = TOY.ref, TOY.cfgmod
"""
import jax
import numpy as np

import mxnet_tpu as mx
from mxbench import manifest
from mxnet_tpu import nd
from mxnet_tpu.parallel import MeshConfig, P, ShardedTrainStep, make_mesh
from numerics import reference

# the reference's optimizer, as its ``train_losses`` takes it
OPT = dict(name="adamw", lr=3e-3, wd=3e-5, beta1=0.9, beta2=0.95,
           epsilon=1e-8)


def ids(a):
    return nd.array(a, dtype="int32")


class Toy:
    """A benchmark configuration ``name`` (its ``mxbench/reference`` and
    ``mxbench/configs`` modules) beside the zoo's ``model`` and ``loss``
    blocks at the toy widths ``cfg``; ``prepare(net)`` changes the seeded
    values where a toy needs it."""

    def __init__(self, name, model, loss, cfg, prepare=None):
        self.ref = reference(name)
        self.cfgmod = manifest.load_module("configs", name + ".py")
        self.model, self.loss, self.cfg = model, loss, cfg
        self.prepare = prepare

    def build(self, cfg=None, seed=3):
        """(net, head) initialized from ``seed``."""
        cfg = self.cfg if cfg is None else cfg
        mx.random.seed(seed)
        net = self.model(cfg, prefix="")
        head = self.loss(cfg, prefix="")
        net.initialize()
        head.initialize()
        if self.prepare is not None:
            self.prepare(net)
        return net, head

    def weights(self, net, head):
        """{the reference's name: array} of both blocks."""
        return self.cfgmod.named_weights(net, self.cfgmod._HeadLoss(head))

    def batch(self, seed=0, shape=(2, 21)):
        """(token ids, labels) over the toy vocabulary."""
        rng = np.random.default_rng(seed)
        return (rng.integers(0, self.cfg["vocab_size"], shape, dtype=np.int32),
                rng.integers(0, self.cfg["vocab_size"], shape, dtype=np.int32))

    def step(self, net, head, dtype=None, **hp):
        """AdamW through ``ShardedTrainStep`` on one device."""
        mesh = make_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
        hp = dict(dict(lr=1e-3, wd=1e-4, beta2=0.95), **hp)
        return ShardedTrainStep(net, self.cfgmod._HeadLoss(head), mesh,
                                optimizer="adamw", dtype=dtype,
                                n_data_inputs=2, data_specs=[P(), P()], **hp)

    def reference_step(self, net, head):
        """The step under the reference's optimizer (``OPT``)."""
        return self.step(net, head,
                         **{k: v for k, v in OPT.items() if k != "name"})

    def sizes(self, cfg=None, **change):
        """``cfg`` (the toy's, with ``change``) as the reference's
        ``train_losses`` takes it: the deployment's share beside it."""
        cfg = dict(self.cfg if cfg is None else cfg, **change)
        return dict(cfg, deployment={"expert_offset": cfg["expert_offset"]})
