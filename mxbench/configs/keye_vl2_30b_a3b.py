"""keye_vl2_30b_a3b: builds the language model's Gluon blocks from the
sizes in keye_vl2_30b_a3b.json, counts the model's FLOPs, and counts the
least that the mathematics of the selector's three scopes needs (and
what the shared expert scope executes, by the Nemotron file's rule) for
their roofline shares. The plain reference is
``reference/keye_vl2_30b_a3b.py``."""
from __future__ import annotations

import math

import numpy as np

# the program's jax.named_scopes that mxbench/scopes.py reads device
# time by, innermost first
SCOPES = ("mx.attn.index", "mx.attn.select", "mx.attn.sparse", "mx.attn.dsa",
          "mx.moe.experts", "mx.moe")


class _HeadLoss:
    """((hidden states, index loss), labels) -> [mean next-token loss +
    index loss]: the adapter ShardedTrainStep wants around the
    parametric head. ``trace_block`` hands it the net's whole output."""

    def __init__(self, head):
        self.head = head

    def collect_params(self):
        return self.head.collect_params()

    def __call__(self, out, labels):
        hidden, index_loss = out
        return [self.head(hidden, index_loss, labels)]


def model_cfg(sizes):
    """The file's keys as the model reads them: the file's
    ``num_experts`` counts the experts held here (it is under
    ``reduced``); the router's width is the published count."""
    cfg = {k: v for k, v in sizes.items()
           if isinstance(v, (int, float, str, bool))}
    cfg["sa_config"] = sizes["sa_config"]
    cfg["rope_scaling"] = sizes["rope_scaling"]
    cfg["mlp_only_layers"] = sizes["mlp_only_layers"]
    cfg["experts_held"] = sizes["num_experts"]
    cfg["num_experts"] = sizes["deployment"]["router_experts"]
    cfg["expert_offset"] = sizes["deployment"]["expert_offset"]
    return cfg


def sharded_parts(sizes, dropout, seq):
    """(net, loss, number of data inputs) for ShardedTrainStep. Data
    inputs: ids, labels, each (batch, seq); the cell feeds text, so no
    position ids. The Gluon parameters are initialised on the host:
    ``ShardedTrainStep`` makes its own fp32 masters on the chip."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.keye_vl import (KeyeVLLMLoss,
                                                    KeyeVLTextModel)
    if dropout:
        raise ValueError("the Keye-VL language model has no dropout")
    cfg = model_cfg(sizes)
    net = KeyeVLTextModel(cfg, prefix="")
    head = KeyeVLLMLoss(cfg, prefix="")
    for block in (net, head):
        block.collect_params().setattr("grad_req", "null")
        block.initialize(ctx=mx.cpu())
    return net, _HeadLoss(head), 2


def expert_rows(aux):
    """{layer: rows routed to each held expert in the last step} from a
    step's auxiliary states, published as the program's gauges on the
    way; the selectors' states (keys a query attended, index loss) are
    published with them."""
    from mxnet_tpu.gluon.model_zoo import keye_vl
    keye_vl.publish_selector_state(aux)
    return keye_vl.publish_expert_rows(aux)


def expert_even_share(sizes, tokens):
    """Rows an expert of a layer is routed on average: every token
    chooses top-k of the router's experts, whatever the routing."""
    return tokens * sizes["num_experts_per_tok"] \
        / sizes["deployment"]["router_experts"]


def named_weights(net, loss):
    """{name: float32 numpy array} of the net's and the head's
    parameters, as the reference reads them."""
    out = {}
    for block in (net, loss.head):
        for name, p in block.collect_params().items():
            out[name] = p.data().asnumpy().astype(np.float32)
    return out


# ---------------------------------------------------------------------------
# counts: multiply-adds a token, one forward
# ---------------------------------------------------------------------------
def selected_pairs(seq, top_k):
    """sum_t min(t + 1, top_k): the pairs one head attends."""
    full = min(seq, top_k)
    return full * (full + 1) // 2 + (seq - full) * top_k


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def _attn_macs(sizes, seq):
    """Projections, and the two products over the selected pairs."""
    u, d = sizes["hidden_size"], sizes["head_dim"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    keys = selected_pairs(seq, sizes["sa_config"]["topk"]) / seq
    return 2 * u * h * d + 2 * u * kv * d + 2 * keys * h * d


def _index_macs(sizes, seq):
    """The selector's three projections and its score over the causal
    pairs (a token sees (seq + 1) / 2 keys on average)."""
    sa = sizes["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return sizes["hidden_size"] * (ih * idim + idim + ih) \
        + causal_pairs(seq) / seq * ih * idim


def _expert_macs(sizes):
    """Three matrices an expert: gate, up, down."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def _moe_macs(sizes):
    """Router, and the routed rows at their expectation under even
    routing: top-k x held / routed experts a token."""
    routed = sizes["deployment"]["router_experts"]
    share = sizes["num_experts_per_tok"] * sizes["num_experts"] / routed
    return sizes["hidden_size"] * routed + share * _expert_macs(sizes)


def train_flops_per_sample(sizes, seq):
    """Model FLOPs of one training sequence: forward + backward ~ 3x
    the forward, 2 FLOPs a multiply-add; recomputation not counted,
    routed rows at their expectation, attention over the selected pairs
    (not the pairs the masked form computes), the selector over the
    causal pairs, the head over the vocabulary slice."""
    per_tok = (sizes["num_hidden_layers"]
               * (_attn_macs(sizes, seq) + _index_macs(sizes, seq)
                  + _moe_macs(sizes))
               + sizes["hidden_size"] * sizes["vocab_size"])
    return per_tok * 2 * 3 * seq


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
    """{scope: (FLOPs, bytes)} of one training step inside each scope,
    all layers together, by the Nemotron file's conventions for passes
    (the forward, what of it the backward recomputes, a backward of two
    products for each of the forward's; bytes: inputs read and outputs
    written once in bf16, again in the recomputation, twice in the
    backward).

    The selector's three scopes count **the least the mathematics
    needs**, not what the masked form executes (it computes every
    causal pair of a query block and masks those not selected), so
    their shares read low, never over 100%:

    - ``mx.attn.sparse``: Q K^T and P V over the selected pairs,
      ``sum_t min(t + 1, top_k)`` a head; recomputed: Q K^T: 7
      products. q, k, v in and the context out.
    - ``mx.attn.index``: the index score over the causal pairs,
      ``index_heads x index_head_dim`` multiply-adds a pair; recomputed
      once: 4 products. Index queries, keys and weights in.
    - ``mx.attn.select``: no product; the float32 score rows read once
      (the causal pairs, 4 bytes each).
    - ``mx.moe.experts``: what runs, as in the Nemotron cell, so that
      the two cells' shares of the shared op compare: the buffer's
      blocks whole, three matrices an expert (gate and up in one
      product, down); recomputed: gate and up: 3 + 2 + 6 = 11 matrix
      products. The experts' weights and the buffer's rows."""
    layers, tokens = sizes["num_hidden_layers"], seq * batch
    sa = sizes["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    hq, kv, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    chosen = batch * selected_pairs(seq, sa["topk"])
    causal = batch * causal_pairs(seq)

    sparse_flops = layers * 2 * 7 * chosen * hq * d
    sparse_io = tokens * (2 * hq * d + 2 * kv * d) * 2
    index_flops = layers * 2 * 4 * causal * ih * idim
    index_io = tokens * ((ih * idim + idim) * 2 + ih * 4)
    select_bytes = layers * causal * 4

    held = sizes["num_experts"]
    rows = expert_capacity(sizes, tokens)
    one = sizes["hidden_size"] * sizes["moe_intermediate_size"]
    moe_flops = layers * 2 * 11 * rows * one
    weights = held * 3 * one * 2
    buf = rows * sizes["hidden_size"] * 2 * 2
    moe_bytes = layers * ((1 + 1 + 2) * (weights + buf) + weights)
    return {"mx.attn.sparse": (sparse_flops, layers * sparse_io * 4),
            "mx.attn.index": (index_flops, layers * index_io * 4),
            "mx.attn.select": (0, select_bytes),
            "mx.moe.experts": (moe_flops, moe_bytes)}
