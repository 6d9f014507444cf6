"""``causal_attn_roofline_pct.train`` for the cells judged on ``train_routed_samples_per_s``: a
per-layer metric moves one end-to-end metric, so the quantity has one
name for each."""
from mxbench import manifest

_base = manifest.layer_metric("causal_attn_roofline_pct.train")
UNIT = _base.UNIT
read = _base.read
