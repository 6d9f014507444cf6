"""Chipless compiles, the BERT path's attention kernel alone at 32,768
tokens a step, a length a case (see tests/test_chip_compile_bert.py for
what such a compile can and cannot show, and for why these cases have a
file of their own: Mosaic takes 19 to 40 s a length)."""
import pytest

import jax
import jax.numpy as jnp

from numerics import BF, described, mosaic_calls, sum32

# BERT-base: seq 128, batch 32, 12 heads x 64
L, N, H, D = 128, 32, 12, 64


# (length, batch) of 32,768 tokens a step: the s128 cell's call, the
# s512 cell's, and lengths no cell runs, up to the cap (ISSUE 39: a plan
# past 336 positions, under a VMEM limit the call states itself)
@pytest.mark.parametrize("p", [0.0, 0.1])
@pytest.mark.parametrize("length, batch", [(L, N), (384, 85), (512, 64),
                                           (768, 42), (1024, 32)],
                         ids=["L128", "L384", "L512", "L768", "L1024"])
def test_flash_attention(one_chip, compiled_mode, length, batch, p):
    from mxnet_tpu.ops.pallas_attention import flash_selfatt, selfatt_plan
    plan = selfatt_plan(length, H, batch, p, dtype=BF, head_dim=D)
    assert plan is not None

    def fwd(qkv, seeds):
        return flash_selfatt(qkv, seeds, heads=H, dropout=p,
                             block_heads=plan["bbh"])

    # value and gradient in one program: the forward kernel once (the
    # backward rule does not run it again), then the backward's
    calls = mosaic_calls(jax.jit(jax.value_and_grad(
        lambda qkv, seeds: sum32(fwd(qkv, seeds)))).lower(*described(
            one_chip, (length, batch, 3 * H * D),
            ((plan["n_blocks"],), jnp.int32))).compile().as_text())
    assert sum("pallas_selfatt_packed_fwd" in c for c in calls) == 1
    assert sum("pallas_selfatt_packed_bwd" in c for c in calls) >= 1
