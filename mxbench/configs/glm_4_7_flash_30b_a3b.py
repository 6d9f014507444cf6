"""glm_4_7_flash_30b_a3b: builds GLM-4.7-Flash's Gluon blocks from the
sizes in glm_4_7_flash_30b_a3b.json (the layers' MLP kinds from
``first_k_dense_replace``, the multi-token-prediction module from
``num_nextn_predict_layers``), counts the model's FLOPs, and counts for
the roofline shares what the causal kernel's and the expert buffer's
scopes execute (by the Nemotron file's and the Keye-VL file's rules,
so that the cells' shares of the one kernel compare). The plain
reference is ``reference/glm_4_7_flash_30b_a3b.py``."""
from __future__ import annotations

import math

import numpy as np

# the program's jax.named_scopes that mxbench/scopes.py reads device
# time by, innermost first
SCOPES = ("mx.attn.causal", "mx.attn.mla", "mx.moe.experts", "mx.moe",
          "mx.mlp", "mx.mtp")


class _HeadLoss:
    """((hidden states, the module's hidden states), labels) -> [the
    next-token loss + the weighted multi-token-prediction loss]: the
    adapter ShardedTrainStep wants around the parametric head.
    ``trace_block`` hands it the net's whole output."""

    def __init__(self, head):
        self.head = head

    def collect_params(self):
        return self.head.collect_params()

    def __call__(self, out, labels):
        hidden, mtp_hidden = out
        return [self.head(hidden, mtp_hidden, labels)]


def model_cfg(sizes):
    """The file's keys as the model reads them: the file's
    ``n_routed_experts`` counts the experts held here (it is under
    ``reduced``); the router's width is the published count."""
    cfg = {k: v for k, v in sizes.items()
           if isinstance(v, (int, float, str, bool)) or v is None}
    cfg["experts_held"] = sizes["n_routed_experts"]
    cfg["n_routed_experts"] = sizes["deployment"]["router_experts"]
    cfg["expert_offset"] = sizes["deployment"]["expert_offset"]
    return cfg


def sharded_parts(sizes, dropout, seq):
    """(net, loss, number of data inputs) for ShardedTrainStep. Data
    inputs: ids, labels, each (batch, seq). The Gluon parameters are
    initialised on the host: ``ShardedTrainStep`` makes its own fp32
    masters on the chip."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.glm_moe_lite import (Glm4MoeLiteLMLoss,
                                                         Glm4MoeLiteModel)
    if dropout:
        raise ValueError("GLM-4.7-Flash has no dropout")
    cfg = model_cfg(sizes)
    net = Glm4MoeLiteModel(cfg, prefix="")
    head = Glm4MoeLiteLMLoss(cfg, prefix="")
    for block in (net, head):
        block.collect_params().setattr("grad_req", "null")
        block.initialize(ctx=mx.cpu())
    return net, _HeadLoss(head), 2


def expert_rows(aux):
    """{layer: rows routed to each held expert in the last step} from a
    step's auxiliary states, published as the program's gauges on the
    way; the loss block's two terms are published with them."""
    from mxnet_tpu.gluon.model_zoo import glm_moe_lite
    glm_moe_lite.publish_loss_terms(aux)
    return glm_moe_lite.publish_expert_rows(aux)


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
def blocks_built(sizes):
    """{kind: blocks of it}: the stack's dense and expert layers, and
    the multi-token-prediction module's one block of the expert kind."""
    dense = min(sizes["first_k_dense_replace"], sizes["num_hidden_layers"])
    return {"dense": dense,
            "sparse": sizes["num_hidden_layers"] - dense
            + sizes["num_nextn_predict_layers"]}


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def tile_pairs(seq, tile):
    """The pairs the causal schedule computes: each query block against
    the keys up to its end, the diagonal block whole (the Nemotron
    file's rule)."""
    return sum((min(lo + tile, seq) - lo) * min(lo + tile, seq)
               for lo in range(0, seq, tile))


def qk_width(sizes):
    return sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]


def _proj_macs(sizes):
    """The five matrices of a latent-attention layer: down to the query
    latent and up to the heads, down to the key-value latent with the
    rotary key and up to the heads, the output projection."""
    u, h = sizes["hidden_size"], sizes["num_attention_heads"]
    qr, kvr = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, vd = sizes["qk_nope_head_dim"], sizes["v_head_dim"]
    return (u * qr + qr * h * qk_width(sizes)
            + u * (kvr + sizes["qk_rope_head_dim"]) + kvr * h * (nope + vd)
            + h * vd * u)


def _pair_macs(sizes, pairs_a_token):
    """Q K^T over the q . k lanes and P V over the value lanes."""
    return pairs_a_token * sizes["num_attention_heads"] \
        * (qk_width(sizes) + sizes["v_head_dim"])


def _expert_macs(sizes):
    """Three matrices an expert: gate, up, down."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def _moe_macs(sizes):
    """Router, the shared expert, and the routed rows at their
    expectation under even routing: top-k x held / routed experts a
    token."""
    routed = sizes["deployment"]["router_experts"]
    share = sizes["num_experts_per_tok"] * sizes["n_routed_experts"] / routed
    return (sizes["hidden_size"] * routed
            + (sizes["n_shared_experts"] + share) * _expert_macs(sizes))


def train_flops_per_sample(sizes, seq):
    """Model FLOPs of one training sequence: forward + backward ~ 3x
    the forward, 2 FLOPs a multiply-add; recomputation not counted,
    routed rows at their expectation, attention over the causal pairs
    (not over the pairs a tile computes and masks), the head over the
    vocabulary slice and twice (the module's position without a target
    counted with the rest: 1 of 8,192)."""
    n = blocks_built(sizes)
    u = sizes["hidden_size"]
    module = sizes["num_nextn_predict_layers"]
    per_tok = ((n["dense"] + n["sparse"])
               * (_proj_macs(sizes)
                  + _pair_macs(sizes, causal_pairs(seq) / seq))
               + n["dense"] * 3 * u * sizes["intermediate_size"]
               + n["sparse"] * _moe_macs(sizes)
               + module * 2 * u * u
               + (1 + module) * u * sizes["vocab_size"])
    return per_tok * 2 * 3 * seq


def expert_capacity(sizes, tokens):
    """Rows of an expert layer's one buffer, all held experts together:
    whole blocks (ops/decoder_ops.py::_moe_experts, its constants)."""
    from mxnet_tpu.ops.decoder_ops import BLOCK_ROWS, CAPACITY_FACTOR
    held, k = sizes["n_routed_experts"], sizes["num_experts_per_tok"]
    even = tokens * k / sizes["deployment"]["router_experts"]
    block = min(BLOCK_ROWS, -(-math.ceil(CAPACITY_FACTOR * even) // 8) * 8)
    most = -(-tokens * min(k, held) // block) + held
    return block * min(most, math.ceil(CAPACITY_FACTOR * even * held / block)
                       + held)


def scope_costs(sizes, seq, batch):
    """{scope: (FLOPs, bytes)} of one training step inside each scope
    that has a roofline reader, all its blocks together (the stack's
    and the module's), by the other decoder files' conventions for
    passes.

    - ``mx.attn.causal``: what runs, by the Nemotron file's rule: each
      query block against the keys up to its end, the diagonal block
      whole; the forward, a backward of two products for each of the
      forward's and Q K^T once more to rebuild the probabilities: 7
      products, each over 256 lanes a pair a head (q . k over 192 + 64,
      P V over 256). Bytes: q, k, v in and the context out once in bf16
      a pass, twice in the backward; every head has its own keys and
      values here.
    - ``mx.moe.experts``: what runs, by the Keye-VL file's rule (the
      same op in the same form): the buffer's blocks whole, three
      matrices an expert, gate and up recomputed: 3 + 2 + 6 = 11 matrix
      products. The experts' weights and the buffer's rows. The shared
      expert runs under ``mx.moe``, outside this scope."""
    from mxnet_tpu.ops.decoder_ops import QUERY_BLOCK
    n = blocks_built(sizes)
    blocks = n["dense"] + n["sparse"]
    tokens = seq * batch
    h, vd = sizes["num_attention_heads"], sizes["v_head_dim"]
    pairs = batch * tile_pairs(seq, QUERY_BLOCK)
    attn_flops = blocks * 2 * pairs * h * (4 * qk_width(sizes) + 3 * vd)
    attn_io = tokens * h * (2 * qk_width(sizes) + 2 * vd) * 2 * (1 + 2)

    held = sizes["n_routed_experts"]
    rows = expert_capacity(sizes, tokens)
    one = sizes["hidden_size"] * sizes["moe_intermediate_size"]
    moe_flops = n["sparse"] * 2 * 11 * rows * one
    weights = held * 3 * one * 2
    buf = rows * sizes["hidden_size"] * 2 * 2
    moe_bytes = n["sparse"] * ((1 + 1 + 2) * (weights + buf) + weights)
    return {"mx.attn.causal": (attn_flops, blocks * attn_io),
            "mx.moe.experts": (moe_flops, moe_bytes)}
