"""Graph-level NHWC layout pass for the TPU compute path.

Ref-parity role: the reference hand-manages kernel data layouts inside
its cuDNN operator wrappers (src/operator/nn/cudnn/ ::
CuDNNConvolutionOp chooses NHWC kernels under MXNET_CUDNN_NHWC /
AMP; nn/mkldnn/ reorders to blocked layouts). On TPU the equivalent
lever is keeping 2-D conv activations channels-last END TO END so
XLA's elementwise fusions and conv custom-calls agree on one physical
layout: profiling a ResNet-50 v1 train step (batch 128, bf16, one v5e
chip) showed the NCHW-traced graph spends ~2.4 GB/step in pure layout
conversion copies that this pass eliminates (46.9 -> 44.0 ms/step).

``convert_layout(sym)`` rebuilds the traced Symbol DAG: 4-D conv/
pool/BN islands run in NHWC (one transpose where an island starts,
one where it ends); parameters stay in MXNet's OIHW/NCHW layouts so
checkpoints, initializers, and the user-visible API are unchanged.
The pass is applied automatically both when tracing through
ShardedTrainStep (parallel/sharded.py trace_block, with weight-
transpose hoisting into parameter storage) and when a CachedOp is
built — i.e. the reference-idiomatic ``net.hybridize()`` + Gluon
``Trainer`` loop gets the NHWC graph too (cached_op.py _compile;
in-graph OIHW->HWIO weight transposes remain there because the
Trainer owns parameter storage). Gate: MXNET_LAYOUT_OPT, default on;
set 0 to disable.
"""
from __future__ import annotations

import os
from typing import Dict

__all__ = ["convert_layout", "layout_opt_enabled"]

# ops whose 4-D output layout simply follows their first input; no
# attribute rewrite needed (elementwise / shape-preserving).
# Dropout is NOT unconditionally here: structured dropout
# (Dropout(axes=...)) writes its axes against NCHW, so it only follows
# when axes is empty (handled explicitly in convert_layout).
_FOLLOW = {
    "Activation", "relu", "sigmoid", "tanh", "softrelu",
    "identity", "_copy", "negative", "abs", "square", "sqrt",
    "exp", "log", "clip", "_plus_scalar", "_minus_scalar", "_mul_scalar",
    "_div_scalar", "amp_cast", "Cast", "cast", "erf", "gelu",
}

# NCHW axis -> NHWC axis for attribute remapping
_NCHW_TO_NHWC_AXIS = {0: 0, 1: 3, 2: 1, 3: 2}

# multi-input elementwise joins: all 4-D inputs must agree on layout
_JOIN = {
    "broadcast_add", "broadcast_sub", "broadcast_mul", "broadcast_div",
    "elemwise_add", "elemwise_sub", "elemwise_mul", "elemwise_div",
    "_plus", "_sub", "_mul", "_div", "add_n", "maximum", "minimum",
    "broadcast_maximum", "broadcast_minimum", "amp_multicast",
}


def layout_opt_enabled() -> bool:
    from ..config import get as _cfg
    return _cfg("MXNET_LAYOUT_OPT")


def convert_layout(sym, target: str = "NHWC", collect_transforms=None):
    """Rewrite a traced Symbol graph so 2-D Convolution/Pooling/
    BatchNorm chains run channels-last internally. Returns a new
    Symbol; the original is untouched. Only 4-D activations move —
    parameters keep their MXNet layouts (conv weights stay OIHW; the
    NHWC Convolution op consumes OIHW weights directly)."""
    from . import Symbol, _Node, _create

    order = sym._topo()
    mapped: Dict[int, object] = {}
    # (id(new node), out_idx) -> True when that output is NHWC
    state: Dict[tuple, bool] = {}
    cache: Dict[tuple, object] = {}

    def map_sym(s):
        node, idx = s._entries[0]
        return Symbol([(mapped[id(node)], idx)]), \
            state.get((id(mapped[id(node)]), idx), False)

    def transpose(s, axes, tag):
        node, idx = s._entries[0]
        key = (id(node), idx, tag)
        got = cache.get(key)
        if got is None:
            got = _create("transpose", [s], {"axes": axes},
                          name=node.name + "_" + tag)
            cache[key] = got
        return got

    def to_nhwc(s, is_nhwc):
        return s if is_nhwc else transpose(s, (0, 2, 3, 1), "to_nhwc")

    def to_nchw(s, is_nhwc):
        return transpose(s, (0, 3, 1, 2), "to_nchw") if is_nhwc else s

    for node in order:
        if node.is_variable:
            mapped[id(node)] = node
            continue
        opname = node.op.name
        ins = [map_sym(s) for s in node.inputs]
        attrs = dict(node.attrs)
        out_nhwc = False
        new_inputs = None

        if opname == "Convolution" and len(tuple(attrs.get("kernel", ()))) == 2 \
                and attrs.get("layout") in (None, "NCHW") \
                and int(attrs.get("num_group", 1) or 1) == 1:
            attrs["layout"] = "NHWC"
            attrs["_kernel_layout"] = "HWIO"
            new_inputs = [to_nhwc(ins[0][0], ins[0][1]),
                          transpose(ins[1][0], (2, 3, 1, 0), "to_hwio")] + \
                [s for s, _ in ins[2:]]
            out_nhwc = True
        elif opname == "Pooling" and attrs.get("layout") in (None, "NCHW") \
                and ins[0][1]:
            attrs["layout"] = "NHWC"
            new_inputs = [s for s, _ in ins]
            out_nhwc = True
        elif opname == "BatchNorm" and ins[0][1] \
                and int(attrs.get("axis", 1)) == 1:
            attrs["axis"] = 3
            new_inputs = [s for s, _ in ins]
            out_nhwc = True
        elif opname == "LeakyReLU" and ins and ins[0][1] \
                and attrs.get("act_type", "leaky") != "prelu":
            # prelu broadcasts its gamma on axis 1 (NCHW) — keep it out
            new_inputs = [s for s, _ in ins]
            out_nhwc = True
        elif opname == "Dropout" and ins and ins[0][1]:
            axes = tuple(attrs.get("axes") or ())
            if axes:
                # structured dropout: remap the NCHW broadcast axes
                # through the NCHW->NHWC permutation (1->3, 2->1, 3->2)
                attrs["axes"] = tuple(sorted(_NCHW_TO_NHWC_AXIS[a]
                                             for a in axes))
            new_inputs = [s for s, _ in ins]
            out_nhwc = True
        elif opname in _FOLLOW and ins and ins[0][1]:
            new_inputs = [s for s, _ in ins]
            out_nhwc = True
        elif opname in _JOIN and ins and all(is_n for _, is_n in ins):
            # ranks are unknown at pass time, so joins stay NHWC only
            # when EVERY input already is (mixed-rank broadcasts would
            # otherwise get a wrong transpose)
            new_inputs = [s for s, _ in ins]
            out_nhwc = True

        if new_inputs is None:
            # unknown/shape-sensitive op: restore NCHW on its inputs
            new_inputs = [to_nchw(s, is_n) for s, is_n in ins]
            out_nhwc = False

        new_node = _Node(node.op, node.name, attrs, new_inputs)
        new_node.num_outputs = node.num_outputs
        mapped[id(node)] = new_node
        if out_nhwc:
            # only the primary output carries the activation layout —
            # extra outputs (BatchNorm's batch mean/var) are vectors
            n_mark = 1 if opname == "BatchNorm" else node.num_outputs
            for i in range(n_mark):
                state[(id(new_node), i)] = True

    outs = []
    for n, i in sym._entries:
        s = Symbol([(mapped[id(n)], i)])
        outs.append(to_nchw(s, state.get((id(mapped[id(n)]), i), False)))
    new_sym = outs[0] if len(outs) == 1 else \
        Symbol([o._entries[0] for o in outs])
    if collect_transforms is None:
        # hoisting changes the feed contract (weights must be supplied
        # pre-transposed) — only do it when the caller asks for the
        # transform map and can honor it
        return new_sym
    return _hoist_weight_transposes(new_sym, collect_transforms)


def _hoist_weight_transposes(sym, collect_transforms=None):
    """Replace in-graph OIHW->HWIO weight transposes with a storage
    transform: when a parameter variable's ONLY consumers are the
    "to_hwio" transposes this pass inserted, drop them and record the
    permutation in ``sym._param_transforms`` — the trainer then stores
    that master parameter pre-transposed (free at runtime) instead of
    transposing it every step (~1.3 ms/step of f32 weight traffic on
    ResNet-50)."""
    from . import Symbol, _Node

    order = sym._topo()
    consumers: Dict[int, list] = {}
    for node in order:
        if node.is_variable:
            continue
        for s in node.inputs:
            src, _ = s._entries[0]
            consumers.setdefault(id(src), []).append(node)

    hoistable = set()
    transforms: Dict[str, tuple] = {}
    for node in order:
        if node.is_variable or not node.name.endswith("_to_hwio"):
            continue
        src = node.inputs[0]._entries[0][0]
        if not src.is_variable:
            continue
        cons = consumers.get(id(src), [])
        if all(c.name.endswith("_to_hwio") for c in cons):
            hoistable.add(id(node))
            transforms[src.name] = (2, 3, 1, 0)

    if not hoistable:
        return sym
    mapped: Dict[int, object] = {}
    for node in order:
        if node.is_variable:
            mapped[id(node)] = node
            continue
        if id(node) in hoistable:
            # collapse onto the (already-transposed-in-storage) variable
            mapped[id(node)] = node.inputs[0]._entries[0][0]
            continue
        new_inputs = [Symbol([(mapped[id(s._entries[0][0])],
                               s._entries[0][1])]) for s in node.inputs]
        new_node = _Node(node.op, node.name, dict(node.attrs), new_inputs)
        new_node.num_outputs = node.num_outputs
        mapped[id(node)] = new_node
    out = Symbol([(mapped[id(n)], i) for n, i in sym._entries])
    if collect_transforms is not None:
        collect_transforms.update(transforms)
    return out


def elide_conv_bias_into_bn(sym):
    """Stop-gradient Convolution biases whose only consumer is a
    BatchNorm on the same channel axis.

    BatchNorm subtracts the mean of its input, so a per-channel
    constant added before it receives an EXACTLY-zero gradient (the BN
    output is invariant to it). The bias only exists in gluon's ResNet
    because upstream's BottleneckV1 leaves Conv2D's use_bias default
    on. Wrapping the bias in BlockGrad is therefore exact: the forward
    (and any moving-stat accumulation, and eval with an arbitrary
    checkpoint bias value) is unchanged — the bias-add fuses into the
    conv epilogue for free — while the backward drops one dead
    Σ-over-positions reduction per conv (~1.4 ms/step on ResNet-50
    batch 128). The bias parameter stays frozen at its loaded value,
    the same place its exactly-zero gradient leaves it anyway.
    """
    from . import Symbol, _Node, _create

    order = sym._topo()
    consumers: Dict[tuple, list] = {}
    for node in order:
        if node.is_variable:
            continue
        for s in node.inputs:
            src, idx = s._entries[0]
            consumers.setdefault((id(src), idx), []).append(node)

    elide = set()
    for node in order:
        if node.is_variable or node.op.name != "Convolution":
            continue
        if len(node.inputs) != 3:      # no bias input
            continue
        cons = consumers.get((id(node), 0), [])
        if len(cons) == 1 and cons[0].op.name == "BatchNorm" \
                and int(cons[0].attrs.get("axis", 1)) == 1 \
                and not cons[0].attrs.get("use_global_stats", False) \
                and node.attrs.get("layout") in (None, "NCHW"):
            elide.add(id(node))

    if not elide:
        return sym
    mapped: Dict[int, object] = {}
    blocked: Dict[int, object] = {}
    for node in order:
        if node.is_variable:
            mapped[id(node)] = node
            continue
        new_inputs = [Symbol([(mapped[id(s._entries[0][0])],
                               s._entries[0][1])]) for s in node.inputs]
        attrs = dict(node.attrs)
        if id(node) in elide:
            bias = new_inputs[2]
            bkey = id(bias._entries[0][0])
            bg = blocked.get(bkey)
            if bg is None:
                bg = _create("BlockGrad", [bias], {},
                             name=bias._entries[0][0].name + "_blockgrad")
                blocked[bkey] = bg
            new_inputs[2] = bg
        new_node = _Node(node.op, node.name, attrs, new_inputs)
        new_node.num_outputs = node.num_outputs
        mapped[id(node)] = new_node
    return Symbol([(mapped[id(n)], i) for n, i in sym._entries])
