"""resnet50_v1: builds the Gluon block from the sizes in
resnet50_v1.json and counts the model's FLOPs. The plain reference is
``reference/resnet50_v1.py``."""
from __future__ import annotations

import numpy as np


def gluon_parts(sizes):
    """(net, loss block), initialised with shapes resolved. Data
    inputs: images (batch, 3, side, side) float32 in [0, 1], labels
    (batch,)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon.model_zoo.vision import get_resnet
    side = sizes["image_size"]
    net = get_resnet(1, sizes["depth"], classes=sizes["num_classes"])
    net.initialize(init=mx.initializer.MSRAPrelu())
    net(nd.ones((2, 3, side, side)))
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


def named_weights(net, loss=None):
    cut = len(net.prefix)
    return {name[cut:]: p.data().asnumpy().astype(np.float32)
            for name, p in net.collect_params().items()}


def _conv_macs(c_in, c_out, k, side_out):
    return c_in * c_out * k * k * side_out * side_out


def train_flops_per_sample(sizes, seq=None):
    """Model FLOPs of one training image (forward + backward ~ 3x the
    forward, 2 FLOPs a multiply-add): the convolutions and the
    classifier of He et al. Table 1, walked stage by stage. Batch norm,
    ReLU and pooling are not counted."""
    bottleneck = sizes["depth"] >= 50
    ch = sizes["stage_channels"]
    side = sizes["image_size"] // 2            # 7x7 stride 2
    macs = _conv_macs(3, ch[0], 7, side)
    side //= 2                                 # 3x3 max pool stride 2
    c_in = ch[0]
    for stage, blocks in enumerate(sizes["stage_blocks"]):
        c_out = ch[stage + 1]
        for blk in range(blocks):
            stride = 2 if (stage > 0 and blk == 0) else 1
            side_out = side // stride
            if bottleneck:
                mid = c_out // 4
                macs += _conv_macs(c_in, mid, 1, side_out)
                macs += _conv_macs(mid, mid, 3, side_out)
                macs += _conv_macs(mid, c_out, 1, side_out)
            else:
                macs += _conv_macs(c_in, c_out, 3, side_out)
                macs += _conv_macs(c_out, c_out, 3, side_out)
            if blk == 0 and c_in != c_out:
                macs += _conv_macs(c_in, c_out, 1, side_out)
            c_in, side = c_out, side_out
    macs += c_in * sizes["num_classes"]
    return macs * 2 * 3
