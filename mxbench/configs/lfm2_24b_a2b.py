"""lfm2_24b_a2b: builds LFM2-24B-A2B's Gluon blocks from the sizes in
lfm2_24b_a2b.json (each layer's mixer from ``layer_types`` at the
layers the deployment names, its MLP from ``num_dense_layers``), counts
the model's FLOPs, and counts for the roofline shares the least that
the short convolution's gates and the expert buffer's scopes need and
what the attention's scope executes (the Nemotron file's rule for the
causal kernel, so that the cells' shares of the one kernel compare).
The plain reference is ``reference/lfm2_24b_a2b.py``."""
from __future__ import annotations

import math

import numpy as np

# the program's jax.named_scopes that mxbench/scopes.py reads device
# time by, innermost first (the gates and taps stand inside mx.conv,
# the attention inside mx.attn.rotary, the experts' buffer inside
# mx.moe)
SCOPES = ("mx.conv.gate", "mx.conv", "mx.attn.causal", "mx.attn.rotary",
          "mx.moe.experts", "mx.moe", "mx.mlp")

CONV, FULL = "conv", "full_attention"


class _HeadLoss:
    """(hidden states, labels) -> [mean next-token loss]: the adapter
    ShardedTrainStep wants around the parametric head."""

    def __init__(self, head):
        self.head = head

    def collect_params(self):
        return self.head.collect_params()

    def __call__(self, hidden, labels):
        return [self.head(hidden, labels)]


def layer_kinds(sizes):
    """The mixer kinds of the layers built: the published
    ``layer_types`` at the layers the deployment names."""
    return [sizes["layer_types"][i]
            for i in sizes["deployment"]["layers_built"]]


def model_cfg(sizes):
    """The file's keys as the model reads them: the file's
    ``num_experts`` counts the experts held here (it is under
    ``reduced``), the router's width is the published count; the
    model's ``layer_types`` are those of the layers built."""
    cfg = {k: v for k, v in sizes.items()
           if isinstance(v, (int, float, str, bool))}
    cfg["rope_parameters"] = sizes["rope_parameters"]
    cfg["layer_types"] = layer_kinds(sizes)
    if len(cfg["layer_types"]) != sizes["num_hidden_layers"]:
        raise ValueError("the deployment names %d layers, num_hidden_layers "
                         "is %d" % (len(cfg["layer_types"]),
                                    sizes["num_hidden_layers"]))
    cfg["experts_held"] = sizes["num_experts"]
    cfg["num_experts"] = sizes["deployment"]["router_experts"]
    cfg["expert_offset"] = sizes["deployment"]["expert_offset"]
    return cfg


def sharded_parts(sizes, dropout, seq):
    """(net, loss, number of data inputs) for ShardedTrainStep. Data
    inputs: ids, labels, each (batch, seq). The Gluon parameters are
    initialised on the host: ``ShardedTrainStep`` makes its own fp32
    masters on the chip. The loss block reads the net's embedding: one
    parameter, named once."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.lfm2 import Lfm2LMLoss, Lfm2MoeModel
    if dropout:
        raise ValueError("LFM2 has no dropout")
    cfg = model_cfg(sizes)
    net = Lfm2MoeModel(cfg, prefix="")
    head = Lfm2LMLoss(cfg, net, prefix="")
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.cpu())
    return net, _HeadLoss(head), 2


def expert_rows(aux):
    """{layer: rows routed to each held expert in the last step} from a
    step's auxiliary states, published as the program's gauges on the
    way."""
    from mxnet_tpu.gluon.model_zoo.lfm2 import publish_expert_rows
    return publish_expert_rows(aux)


def expert_even_share(sizes, tokens):
    """Rows an expert of a layer is routed on average: every token
    chooses top-k of the router's experts, whatever the routing."""
    return tokens * sizes["num_experts_per_tok"] \
        / sizes["deployment"]["router_experts"]


def named_weights(net, loss):
    """{name: float32 numpy array} of the net's parameters, as the
    reference reads them; the head's is the embedding's, once."""
    out = {}
    for block in (net, loss.head):
        for name, p in block.collect_params().items():
            out[name] = p.data().asnumpy().astype(np.float32)
    return out


# ---------------------------------------------------------------------------
# counts: multiply-adds a token, one forward
# ---------------------------------------------------------------------------
def head_dim(sizes):
    return sizes["hidden_size"] // sizes["num_attention_heads"]


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def tile_pairs(seq, tile):
    """The pairs the attention's schedule computes: each query block
    against the keys up to its end, the diagonal block whole (the
    Nemotron file's rule)."""
    return sum((min(lo + tile, seq) - lo) * min(lo + tile, seq)
               for lo in range(0, seq, tile))


def _conv_macs(sizes):
    """W_in (hidden -> 3 hidden), W_out, and a channel's taps and two
    gates."""
    u = sizes["hidden_size"]
    return 3 * u * u + u * u + u * (sizes["conv_L_cache"] + 2)


def _attn_proj_macs(sizes):
    """q and o over the query heads, k and v over the key-value heads."""
    u, d = sizes["hidden_size"], head_dim(sizes)
    return 2 * u * u + 2 * u * sizes["num_key_value_heads"] * d


def _expert_macs(sizes):
    """Three matrices an expert: gate, up, down."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def _moe_macs(sizes):
    """Router and the routed rows at their expectation under even
    routing: top-k x held / routed experts a token. No shared expert."""
    routed = sizes["deployment"]["router_experts"]
    share = sizes["num_experts_per_tok"] * sizes["num_experts"] / routed
    return sizes["hidden_size"] * routed + share * _expert_macs(sizes)


def macs_per_token(sizes, seq):
    """{part: multiply-adds a token of one forward}, over the layers
    built."""
    u = sizes["hidden_size"]
    kinds = layer_kinds(sizes)
    dense = sizes["num_dense_layers"]
    return {
        "conv": kinds.count(CONV) * _conv_macs(sizes),
        "attn_proj": kinds.count(FULL) * _attn_proj_macs(sizes),
        "attn_pairs": kinds.count(FULL) * 2 * causal_pairs(seq) / seq * u,
        "dense_mlp": dense * 3 * u * sizes["intermediate_size"],
        "experts": (len(kinds) - dense) * _moe_macs(sizes),
        "head": u * sizes["vocab_size"],
    }


def train_flops_per_sample(sizes, seq):
    """Model FLOPs of one training sequence: forward + backward ~ 3x
    the forward, 2 FLOPs a multiply-add; recomputation not counted,
    routed rows at their expectation, attention over the causal pairs
    (not the pairs a tile computes and masks) at the published 64
    lanes a head, the tied head once (over the vocabulary slice; the
    embedding's lookup is no product)."""
    return sum(macs_per_token(sizes, seq).values()) * 2 * 3 * seq


def expert_capacity(sizes, tokens):
    """Rows of an expert layer's one buffer, all held experts together:
    whole blocks (ops/decoder_ops.py::_moe_experts, its constants)."""
    from mxnet_tpu.ops.decoder_ops import BLOCK_ROWS, CAPACITY_FACTOR
    held, k = sizes["num_experts"], sizes["num_experts_per_tok"]
    even = tokens * k / sizes["deployment"]["router_experts"]
    block = min(BLOCK_ROWS, -(-math.ceil(CAPACITY_FACTOR * even) // 8) * 8)
    most = -(-tokens * min(k, held) // block) + held
    return block * min(most, math.ceil(CAPACITY_FACTOR * even * held / block)
                       + held)


def scope_costs(sizes, seq, batch):
    """{scope: (FLOPs, bytes)} of one training step inside each scope
    that has a roofline reader, all its layers together.

    - ``mx.conv.gate``: **the least bytes the gates and taps need**: a
      token's ``3 x hidden`` outputs of ``W_in`` read and ``hidden``
      values written, in the compute dtype (bf16), once in the forward
      and once in the recomputation; in the backward the ``hidden``
      gradients read and ``3 x hidden`` written: three passes of ``4 x
      hidden`` values a token a layer. (The backward's fusion also
      reads the recomputed values again where XLA does not keep them
      in the fusion that made them; the count leaves that out, so the
      share reads low exactly when the values make extra trips through
      HBM and cannot pass 100%.) FLOPs: a channel's gate, taps and gate,
      three passes; they never bound.
    - ``mx.attn.causal``: what runs, by the Nemotron file's rule, so
      that the cells' shares of the one kernel compare: the forward, a
      backward of two products for each of the forward's and Q K^T once
      more to rebuild the probabilities (7 products; the forward kernel
      is not run again) over each query block against the keys up to
      its end, the diagonal block whole, **at the published 64 lanes a
      head**: the kernel's step contracts a 128-lane tile of which one
      head fills half (two query heads a step, ops/pallas_causal_gqa.py),
      so what the MXU spends on the empty half reads as lost share.
      Bytes: q, k, v in and the context out once in bf16 a pass, twice
      in the backward.
    - ``mx.moe.experts``: **by the rows routed**, at even routing
      (``tokens x top-k x held / routed``: 16,384 at four sequences),
      not by the buffer's blocks. Three matrices an expert, gate and up
      recomputed: 3 + 2 + 6 = 11 matrix products, as the other cells
      count them. The experts' weights and the routed rows."""
    from mxnet_tpu.ops.decoder_ops import QUERY_BLOCK
    tokens = seq * batch
    u, d = sizes["hidden_size"], head_dim(sizes)
    kinds = layer_kinds(sizes)
    convs, fulls = kinds.count(CONV), kinds.count(FULL)

    gate_bytes = convs * tokens * 3 * 4 * u * 2
    gate_flops = convs * tokens * 3 * u * (2 * sizes["conv_L_cache"] + 2)

    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    causal_flops = fulls * 2 * 7 * batch * tile_pairs(seq, QUERY_BLOCK) \
        * heads * d
    causal_bytes = fulls * tokens * (2 * heads + 2 * kv) * d * 2 * (1 + 2)

    sparse = len(kinds) - sizes["num_dense_layers"]
    held = sizes["num_experts"]
    rows = expert_even_share(sizes, tokens) * held
    one = u * sizes["moe_intermediate_size"]
    moe_flops = sparse * 2 * 11 * rows * one
    weights = held * 3 * one * 2
    buf = rows * u * 2 * 2
    moe_bytes = sparse * ((1 + 1 + 2) * (weights + buf) + weights)
    return {"mx.conv.gate": (gate_flops, gate_bytes),
            "mx.attn.causal": (causal_flops, causal_bytes),
            "mx.moe.experts": (moe_flops, moe_bytes)}
