"""Model zoo (ref: python/mxnet/gluon/model_zoo/).

``vision``: the reference's image classifiers. ``bert``: the BERT
encoder and its MLM head. ``nemotron_h``: the first decoder, a causal
Nemotron-H stack (Mamba-2, routed experts, grouped-query attention by a
pattern string) and its LM-head loss; its mixers recompute their inside
in the backward (docs/TRAINING.md "Decoder layers and recomputation")."""
from . import vision
from . import bert
from . import nemotron_h
from .vision import get_model
