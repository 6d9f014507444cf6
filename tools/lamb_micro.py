"""Micro-benchmark: LAMB update variants on BERT-base-shaped params
(dev tool for the r5 optimizer-cost work; round-5 builder tool).

Variants:
  perparam — current ShardedTrainStep structure (_apply_update): per-param
             phase1 + jnp.linalg.norm + phase2 inside one jit
  dotnorm  — same but r1/r2 via flat self-dot (MXU-friendly reduce)
  flat     — persistent flat f32 buffers (one per dtype): elementwise
             phase1 on ONE fused buffer, per-param norms via padded-row
             segment sums, ratio scatter back; params stay flat across
             steps (unflatten = free slices at feed time, not timed here)

Usage: python tools/lamb_micro.py [variant ...]
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

# BERT-base param shapes (12L/768/12H + embeddings + MLM head)
def bert_shapes():
    shapes = [(30522, 768), (512, 768), (2, 768), (768,), (768,)]
    for _ in range(12):
        shapes += [(2304, 768), (2304,), (768, 768), (768,),
                   (768,), (768,), (3072, 768), (3072,), (768, 3072),
                   (768,), (768,), (768,)]
    shapes += [(768, 768), (768,), (768,), (768,), (30522,)]  # MLM head
    return shapes

HP = dict(lr=1e-3, wd=0.01, beta1=0.9, beta2=0.999, eps=1e-6)


def make_tensors(shapes, key):
    ks = jax.random.split(key, 4)
    ws = [jax.random.normal(ks[0], s, jnp.float32) * 0.02 for s in shapes]
    gs = [jax.random.normal(ks[1], s, jnp.bfloat16) * 0.01 for s in shapes]
    ms = [jnp.zeros(s, jnp.float32) for s in shapes]
    vs = [jnp.zeros(s, jnp.float32) + 1e-4 for s in shapes]
    return ws, gs, ms, vs


def lamb_one(w, g, m, v, t, norm_via_dot=False):
    g = g.astype(jnp.float32)
    nm = HP["beta1"] * m + (1 - HP["beta1"]) * g
    nv = HP["beta2"] * v + (1 - HP["beta2"]) * jnp.square(g)
    mh = nm / (1 - HP["beta1"] ** t)
    vh = nv / (1 - HP["beta2"] ** t)
    upd = mh / (jnp.sqrt(vh) + HP["eps"]) + HP["wd"] * w
    if norm_via_dot:
        wf, uf = w.reshape(-1), upd.reshape(-1)
        r1 = jnp.sqrt(jnp.dot(wf, wf))
        r2 = jnp.sqrt(jnp.dot(uf, uf))
    else:
        r1 = jnp.linalg.norm(w)
        r2 = jnp.linalg.norm(upd)
    ratio = jnp.where((r1 > 0) & (r2 > 0), r1 / r2, 1.0)
    return w - HP["lr"] * ratio * upd, nm, nv


def step_perparam(ws, gs, ms, vs, t, dot=False):
    out = [lamb_one(w, g, m, v, t, dot)
           for w, g, m, v in zip(ws, gs, ms, vs)]
    return ([o[0] for o in out], [o[1] for o in out], [o[2] for o in out])


# --- flat variant ---------------------------------------------------------
ROW = 1024


def build_layout(shapes):
    sizes = [int(np.prod(s)) for s in shapes]
    rows = [(sz + ROW - 1) // ROW for sz in sizes]
    seg_ids = np.repeat(np.arange(len(shapes), dtype=np.int32), rows)
    offs = np.concatenate([[0], np.cumsum([r * ROW for r in rows])])
    return sizes, rows, seg_ids, offs


def to_flat(tensors, sizes, rows, offs):
    parts = []
    for x, sz, r in zip(tensors, sizes, rows):
        f = x.astype(jnp.float32).reshape(-1)
        if r * ROW != sz:
            f = jnp.concatenate([f, jnp.zeros((r * ROW - sz,), jnp.float32)])
        parts.append(f)
    return jnp.concatenate(parts)


def step_flat(fw, fg, fm, fv, t, seg_ids, n_params):
    g = fg.astype(jnp.float32)
    nm = HP["beta1"] * fm + (1 - HP["beta1"]) * g
    nv = HP["beta2"] * fv + (1 - HP["beta2"]) * jnp.square(g)
    mh = nm / (1 - HP["beta1"] ** t)
    vh = nv / (1 - HP["beta2"] ** t)
    upd = mh / (jnp.sqrt(vh) + HP["eps"]) + HP["wd"] * fw
    w_rows = jnp.sum(jnp.square(fw.reshape(-1, ROW)), axis=1)
    u_rows = jnp.sum(jnp.square(upd.reshape(-1, ROW)), axis=1)
    r1 = jnp.sqrt(jax.ops.segment_sum(w_rows, seg_ids, n_params))
    r2 = jnp.sqrt(jax.ops.segment_sum(u_rows, seg_ids, n_params))
    ratio = jnp.where((r1 > 0) & (r2 > 0), r1 / r2, 1.0)
    ratio_el = jnp.repeat(ratio[seg_ids], ROW)   # rows -> elements
    return fw - HP["lr"] * ratio_el * upd, nm, nv


def time_fn(fn, args, iters=10):
    """Device-busy ms/step from xplane (tools/devtime.py).
    ws/ms/vs are donated, so thread the outputs back as next-step
    inputs (the real training-loop pattern)."""
    from devtime import device_ms_per_step
    state = {"a": args}

    def one():
        ws, gs, ms, vs, t = state["a"]
        ws, ms, vs = fn(ws, gs, ms, vs, t)
        state["a"] = (ws, gs, ms, vs, t)
        return ws

    one()  # compile outside the trace
    return device_ms_per_step(
        one, iters, lambda o: jax.device_get(jax.tree_util.tree_leaves(o)[0]))


def main():
    shapes = bert_shapes()
    n = sum(int(np.prod(s)) for s in shapes)
    print("params: %d tensors, %.1fM elements, %.0f MB f32 "
          "(theory min ~%0.1f ms: r w,g16,m,v + w w,m,v = %.2f GB @ 819GB/s)"
          % (len(shapes), n / 1e6, n * 4 / 1e6,
             (n * (4 * 6 + 2)) / 819e9 * 1e3, n * (4 * 6 + 2) / 1e9))
    which = sys.argv[1:] or ["perparam", "dotnorm", "flat"]
    key = jax.random.key(0)
    ws, gs, ms, vs = make_tensors(shapes, key)
    t = jnp.float32(7.0)

    if "perparam" in which:
        f = jax.jit(lambda a, b, c, d, e: step_perparam(a, b, c, d, e, False),
                    donate_argnums=(0, 2, 3))
        ms_t = time_fn(f, (ws, gs, ms, vs, t))
        print("perparam: %.2f ms" % ms_t)
        ws, gs, ms, vs = make_tensors(shapes, key)
    if "dotnorm" in which:
        f = jax.jit(lambda a, b, c, d, e: step_perparam(a, b, c, d, e, True),
                    donate_argnums=(0, 2, 3))
        ms_t = time_fn(f, (ws, gs, ms, vs, t))
        print("dotnorm:  %.2f ms" % ms_t)
        ws, gs, ms, vs = make_tensors(shapes, key)
    if "flat" in which:
        sizes, rows, seg_ids, offs = build_layout(shapes)
        seg = jnp.asarray(seg_ids)
        fw = to_flat(ws, sizes, rows, offs)
        fg = to_flat(gs, sizes, rows, offs).astype(jnp.bfloat16)
        fm = to_flat(ms, sizes, rows, offs)
        fv = to_flat(vs, sizes, rows, offs)
        f = jax.jit(lambda a, b, c, d, e: step_flat(a, b, c, d, e, seg,
                                                    len(shapes)),
                    donate_argnums=(0, 2, 3))
        ms_t = time_fn(f, (fw, fg, fm, fv, t))
        print("flat:     %.2f ms" % ms_t)


if __name__ == "__main__":
    main()
