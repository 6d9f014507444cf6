"""mellum2_12b_a2_5b: builds Mellum 2's Gluon blocks from the sizes in
mellum2_12b_a2_5b.json (the layers' kinds from its ``layer_types``),
counts the model's FLOPs, and counts for the roofline shares the least
that the window layers' scope needs and what the full layer's and the
shared expert scope execute (by the Nemotron file's and the Keye-VL
file's rules). The plain reference is
``reference/mellum2_12b_a2_5b.py``."""
from __future__ import annotations

import math

import numpy as np

# the program's jax.named_scopes that mxbench/scopes.py reads device
# time by, innermost first
SCOPES = ("mx.attn.window", "mx.attn.causal", "mx.attn.rotary",
          "mx.moe.experts", "mx.moe")


class _HeadLoss:
    """(hidden states, labels) -> [mean next-token loss]: the adapter
    ShardedTrainStep wants around the parametric head."""

    def __init__(self, head):
        self.head = head

    def collect_params(self):
        return self.head.collect_params()

    def __call__(self, hidden, labels):
        return [self.head(hidden, labels)]


def model_cfg(sizes):
    """The file's keys as the model reads them: the file's
    ``num_experts`` counts the experts held here (it is under
    ``reduced``); the router's width is the published count."""
    cfg = {k: v for k, v in sizes.items()
           if isinstance(v, (int, float, str, bool))}
    cfg["layer_types"] = sizes["layer_types"]
    cfg["mlp_layer_types"] = sizes["mlp_layer_types"]
    cfg["rope_parameters"] = sizes["rope_parameters"]
    cfg["experts_held"] = sizes["num_experts"]
    cfg["num_experts"] = sizes["deployment"]["router_experts"]
    cfg["expert_offset"] = sizes["deployment"]["expert_offset"]
    return cfg


def sharded_parts(sizes, dropout, seq):
    """(net, loss, number of data inputs) for ShardedTrainStep. Data
    inputs: ids, labels, each (batch, seq). The Gluon parameters are
    initialised on the host: ``ShardedTrainStep`` makes its own fp32
    masters on the chip."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.mellum import MellumLMLoss, MellumModel
    if dropout:
        raise ValueError("Mellum 2 has no dropout")
    cfg = model_cfg(sizes)
    net = MellumModel(cfg, prefix="")
    head = MellumLMLoss(cfg, prefix="")
    for block in (net, head):
        block.collect_params().setattr("grad_req", "null")
        block.initialize(ctx=mx.cpu())
    return net, _HeadLoss(head), 2


def expert_rows(aux):
    """{layer: rows routed to each held expert in the last step} from a
    step's auxiliary states, published as the program's gauges on the
    way."""
    from mxnet_tpu.gluon.model_zoo.mellum import publish_expert_rows
    return publish_expert_rows(aux)


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
def layer_kinds(sizes):
    """{kind: layers of it} among the layers built."""
    kinds = sizes["layer_types"][:sizes["num_hidden_layers"]]
    return {k: kinds.count(k) for k in ("sliding_attention",
                                        "full_attention")}


def window_pairs(seq, window):
    """sum_t min(t + 1, window): the pairs one head of a sliding layer
    attends."""
    full = min(seq, window)
    return full * (full + 1) // 2 + (seq - full) * window


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def tile_pairs(seq, tile):
    """The pairs the full layer's schedule computes: each query block
    against the keys up to its end, the diagonal block whole (the
    Nemotron file's rule)."""
    return sum((min(lo + tile, seq) - lo) * min(lo + tile, seq)
               for lo in range(0, seq, tile))


def _proj_macs(sizes):
    u, d = sizes["hidden_size"], sizes["head_dim"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return 2 * u * h * d + 2 * u * kv * d


def _pair_macs(sizes, pairs_a_token):
    """The two products over a token's pairs."""
    return 2 * pairs_a_token * sizes["num_attention_heads"] \
        * sizes["head_dim"]


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
    routed rows at their expectation, a sliding layer's attention over
    its band's pairs and a full layer's over the causal pairs (neither
    over the pairs a tile computes and masks), the head over the
    vocabulary slice."""
    n = layer_kinds(sizes)
    per_tok = (sizes["num_hidden_layers"] * (_proj_macs(sizes)
                                             + _moe_macs(sizes))
               + n["sliding_attention"] * _pair_macs(
                   sizes, window_pairs(seq, sizes["sliding_window"]) / seq)
               + n["full_attention"] * _pair_macs(sizes,
                                                  causal_pairs(seq) / seq)
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
    all its layers together, by the other two decoder files'
    conventions for passes (the forward, a backward of two products for
    each of the forward's and Q K^T once more to rebuild the
    probabilities: 7 products; the forward kernel is not run again.
    Bytes: q, k, v in and the context out once in bf16 a pass, twice
    in the backward).

    - ``mx.attn.window``: **the least the mathematics needs**: the 7
      products over the band's pairs, ``sum_t min(t + 1, window)`` a
      head (16,253,440 at 16,384 and a window of 1,024). The kernel
      computes whole 512 x 512 tiles, three a query tile (24,379,392
      pairs), and the composition a band of up to 1,535 keys a block,
      so the share reads at most 67% by construction and cannot pass
      100%.
    - ``mx.attn.causal``: what runs, by the Nemotron file's rule, so
      that the two cells' shares of the one kernel compare: each query
      block against the keys up to its end, the diagonal block whole.
    - ``mx.moe.experts``: what runs, by the Keye-VL file's rule (the
      same op in the same form): the buffer's blocks whole, three
      matrices an expert, gate and up recomputed: 3 + 2 + 6 = 11 matrix
      products. The experts' weights and the buffer's rows."""
    from mxnet_tpu.ops.decoder_ops import QUERY_BLOCK
    n = layer_kinds(sizes)
    tokens = seq * batch
    hq, kv, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    attn_io = tokens * (2 * hq * d + 2 * kv * d) * 2 * (1 + 2)
    window_flops = n["sliding_attention"] * 2 * 7 * batch * window_pairs(
        seq, sizes["sliding_window"]) * hq * d
    causal_flops = n["full_attention"] * 2 * 7 * batch * tile_pairs(
        seq, QUERY_BLOCK) * hq * d

    layers, held = sizes["num_hidden_layers"], sizes["num_experts"]
    rows = expert_capacity(sizes, tokens)
    one = sizes["hidden_size"] * sizes["moe_intermediate_size"]
    moe_flops = layers * 2 * 11 * rows * one
    weights = held * 3 * one * 2
    buf = rows * sizes["hidden_size"] * 2 * 2
    moe_bytes = layers * ((1 + 1 + 2) * (weights + buf) + weights)
    return {"mx.attn.window": (window_flops,
                               n["sliding_attention"] * attn_io),
            "mx.attn.causal": (causal_flops, n["full_attention"] * attn_io),
            "mx.moe.experts": (moe_flops, moe_bytes)}
