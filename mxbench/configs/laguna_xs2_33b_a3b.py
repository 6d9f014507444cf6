"""laguna_xs2_33b_a3b: builds Laguna-XS.2's Gluon blocks from the sizes
in laguna_xs2_33b_a3b.json (each layer's attention kind, query heads
and MLP kind from its three per-layer lists), counts the model's FLOPs,
and counts for the roofline shares the least that the window layers'
and the expert buffer's scopes need and what the full layers' scope
executes (the Mellum 2 file's rules for the two attention scopes, so
that the cells' shares of the one kernel compare). The plain reference
is ``reference/laguna_xs2_33b_a3b.py``."""
from __future__ import annotations

import math

import numpy as np

# the program's jax.named_scopes that mxbench/scopes.py reads device
# time by, innermost first (the gate and the attention stand inside
# mx.attn.rotary, the experts' buffer inside mx.moe)
SCOPES = ("mx.attn.gate", "mx.attn.window", "mx.attn.causal",
          "mx.attn.rotary", "mx.moe.experts", "mx.moe", "mx.mlp")

SLIDING, FULL = "sliding_attention", "full_attention"
PER_LAYER = ("layer_types", "num_attention_heads_per_layer",
             "mlp_layer_types")


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
    for key in PER_LAYER + ("rope_parameters",):
        cfg[key] = sizes[key]
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
    from mxnet_tpu.gluon.model_zoo.laguna import LagunaLMLoss, LagunaModel
    if dropout:
        raise ValueError("Laguna-XS.2 has no dropout")
    cfg = model_cfg(sizes)
    net = LagunaModel(cfg, prefix="")
    head = LagunaLMLoss(cfg, prefix="")
    for block in (net, head):
        block.collect_params().setattr("grad_req", "null")
        block.initialize(ctx=mx.cpu())
    return net, _HeadLoss(head), 2


def expert_rows(aux):
    """{layer: rows routed to each held expert in the last step} from a
    step's auxiliary states, published as the program's gauges on the
    way."""
    from mxnet_tpu.gluon.model_zoo.laguna import publish_expert_rows
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
def layers_built(sizes):
    """[(attention kind, query heads, MLP kind)] of the layers built."""
    return list(zip(*(sizes[key][:sizes["num_hidden_layers"]]
                      for key in PER_LAYER)))


def heads_by_kind(sizes):
    """{attention kind: the query heads of its layers built, summed}."""
    out = {SLIDING: 0, FULL: 0}
    for kind, heads, _ in layers_built(sizes):
        out[kind] += heads
    return out


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


def _proj_macs(sizes, heads):
    """q and o over the layer's query heads, k and v over the key-value
    heads, the gate's row a head."""
    u, d = sizes["hidden_size"], sizes["head_dim"]
    return (2 * u * heads * d + 2 * u * sizes["num_key_value_heads"] * d
            + (u * heads if sizes["gating"] else 0))


def _expert_macs(sizes):
    """Three matrices an expert: gate, up, down."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def _moe_macs(sizes):
    """Router, the shared expert, and the routed rows at their
    expectation under even routing: top-k x held / routed experts a
    token."""
    routed = sizes["deployment"]["router_experts"]
    share = sizes["num_experts_per_tok"] * sizes["num_experts"] / routed
    return (sizes["hidden_size"] * routed + share * _expert_macs(sizes)
            + 3 * sizes["hidden_size"]
            * sizes["shared_expert_intermediate_size"])


def train_flops_per_sample(sizes, seq):
    """Model FLOPs of one training sequence: forward + backward ~ 3x
    the forward, 2 FLOPs a multiply-add; recomputation not counted,
    routed rows at their expectation, a sliding layer's attention over
    its band's pairs and a full layer's over the causal pairs (neither
    over the pairs a tile computes and masks), each over the layer's
    own query heads, the head over the vocabulary slice."""
    u, d = sizes["hidden_size"], sizes["head_dim"]
    pairs = {SLIDING: window_pairs(seq, sizes["sliding_window"]) / seq,
             FULL: causal_pairs(seq) / seq}
    per_tok = u * sizes["vocab_size"]
    for kind, heads, mlp in layers_built(sizes):
        per_tok += _proj_macs(sizes, heads) + 2 * pairs[kind] * heads * d
        per_tok += 3 * u * sizes["intermediate_size"] if mlp == "dense" \
            else _moe_macs(sizes)
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
    """{scope: (FLOPs, bytes)} of one training step inside each scope
    that has a roofline reader, all its layers together, by the other
    decoder files' conventions for passes (the forward, a backward of
    two products for each of the forward's and Q K^T once more to
    rebuild the probabilities: 7 products; the forward kernel is not
    run again. Bytes: q, k, v in and the context out once in bf16 a
    pass, twice in the backward), each layer over its own query heads.

    - ``mx.attn.window``: **the least the mathematics needs**: the 7
      products over the band's pairs, ``sum_t min(t + 1, window)`` a
      head (4,063,488 at 8,192 and a window of 512). At a window of one
      tile the kernel computes two whole 512 x 512 tiles a query tile
      for one tile's worth of pairs (8,126,464 pairs), and the
      composition a band of up to 1,023 keys a block, so the share
      reads at most 50% by construction and cannot pass 100%.
    - ``mx.attn.causal``: what runs, by the Nemotron file's rule, so
      that the cells' shares of the one kernel compare: each query
      block against the keys up to its end, the diagonal block whole.
    - ``mx.moe.experts``: **by the rows routed**, at even routing
      (``tokens x top-k x held / routed``: 8,192 at one sequence), not
      by the buffer's blocks (32,768 rows: an expert's even share, 256
      rows, is half a block, and the buffer holds twice the share plus
      a block an expert, so it is a quarter full and the share reads
      low). Three matrices an expert, gate and up recomputed: 3 + 2 + 6
      = 11 matrix products, as the other cells count them. The
      experts' weights and the routed rows. The shared expert runs
      under ``mx.moe``, outside this scope."""
    from mxnet_tpu.ops.decoder_ops import QUERY_BLOCK
    tokens = seq * batch
    kv, d = sizes["num_key_value_heads"], sizes["head_dim"]
    heads = heads_by_kind(sizes)
    kinds = [kind for kind, _, _ in layers_built(sizes)]

    def attn_io(kind):
        return tokens * (2 * heads[kind] + 2 * kv * kinds.count(kind)) * d \
            * 2 * (1 + 2)

    window_flops = 2 * 7 * batch * window_pairs(
        seq, sizes["sliding_window"]) * heads[SLIDING] * d
    causal_flops = 2 * 7 * batch * tile_pairs(seq, QUERY_BLOCK) \
        * heads[FULL] * d

    sparse = [mlp for _, _, mlp in layers_built(sizes)].count("sparse")
    held = sizes["num_experts"]
    rows = expert_even_share(sizes, tokens) * held
    one = sizes["hidden_size"] * sizes["moe_intermediate_size"]
    moe_flops = sparse * 2 * 11 * rows * one
    weights = held * 3 * one * 2
    buf = rows * sizes["hidden_size"] * 2 * 2
    moe_bytes = sparse * ((1 + 1 + 2) * (weights + buf) + weights)
    return {"mx.attn.window": (window_flops, attn_io(SLIDING)),
            "mx.attn.causal": (causal_flops, attn_io(FULL)),
            "mx.moe.experts": (moe_flops, moe_bytes)}
