"""Model zoo (ref: python/mxnet/gluon/model_zoo/).

``vision``: the reference's image classifiers. ``bert``: the BERT
encoder and its MLM head. ``nemotron_h``: the first decoder, a causal
Nemotron-H stack (Mamba-2, routed experts, grouped-query attention by a
pattern string) and its LM-head loss; its mixers recompute their inside
in the backward (docs/TRAINING.md "Decoder layers and recomputation").
``keye_vl``: the second, Keye-VL 2.0's language model (a learned
top-k key selector in front of every attention layer, M-RoPE, a softmax
router over SwiGLU experts), which returns a second loss beside its
hidden states. ``mellum``: the third, Mellum 2 (sliding-window and full
attention layers mixed by a per-layer list, a rotary table per layer
type with YaRN on the full ones, the same router and experts).
``glm_moe_lite``: the fourth, GLM-4.7-Flash (latent attention, a
leading dense layer before the expert layers, a shared expert beside a
sigmoid router, and a multi-token-prediction module that uses the
embedding and the head a second time). ``laguna``: the fifth,
Laguna-XS.2 (window and full attention layers whose query heads differ
by a per-layer list, a sigmoid gate a head on the context, rotary over
half a head's lanes on the full layers, a leading dense layer, a shared
expert beside a scaled softmax router). ``lfm2``: the sixth, LFM2's
expert model (gated short-convolution layers and grouped-query
attention layers with q/k norms mixed by a per-layer list, leading
dense layers by a count, a sigmoid router with a selection bias and no
shared expert, the head tied to the embedding: the loss block reads the
net's own parameter). ``granite_hybrid``: the seventh, Granite 4.0-H's
dense model (nine Mamba-2 mixers to one NoPE attention mixer by a
per-layer list, a dense gated MLP in every layer, four multipliers on
the embedding, the branches, the scores and the logits, a tied head),
the first that takes a packed row's document ids beside its tokens:
conv taps, scan state and attention stop at a document's start."""
from . import vision
from . import bert
from . import nemotron_h
from . import keye_vl
from . import mellum
from . import glm_moe_lite
from . import laguna
from . import lfm2
from . import granite_hybrid
from .vision import get_model
