"""Device BUSY time per train step from the XLA profiler's trace
(xplane): the sum of the "XLA Modules" events on the TPU planes, read
with jax.profiler.ProfileData. Gaps between launches are not in it —
it is not wall time. Dev tool for perf work.

Usage: python tools/devtime.py [batch] [steps]
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def device_ms_per_step(step_fn, n_steps, sync):
    """Mean device-busy milliseconds per step over ``n_steps`` traced
    steps. Raises when the trace holds no TPU module events (no chip,
    or the profiler changed its naming): a zero would read as a rate."""
    import jax
    d = tempfile.mkdtemp(prefix="devtime_")
    try:
        jax.profiler.start_trace(d)
        for _ in range(n_steps):
            out = step_fn()
        sync(out)
        jax.profiler.stop_trace()
        p = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))[0]
        prof = jax.profiler.ProfileData.from_file(p)
        total_ns = 0.0
        seen = []
        for plane in prof.planes:
            seen.append(plane.name)
            if "TPU" not in plane.name:
                continue
            for line in plane.lines:
                if line.name != "XLA Modules":
                    continue
                for ev in line.events:
                    total_ns += ev.duration_ns
        if total_ns <= 0:
            raise RuntimeError(
                "devtime: no 'XLA Modules' events on a TPU plane "
                "(planes in the trace: %s)" % seen)
        return total_ns / 1e6 / n_steps
    finally:
        shutil.rmtree(d, ignore_errors=True)


def main():
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    from mxnet_tpu.parallel import MeshConfig, P, ShardedTrainStep, make_mesh

    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 8

    net = resnet50_v1()
    net.initialize(init=mx.initializer.MSRAPrelu())
    net(nd.ones((2, 3, 224, 224)))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = make_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    step = ShardedTrainStep(net, loss_fn, mesh, lr=0.1, momentum=0.9,
                            dtype="bfloat16", data_specs=[P(), P()])
    rng = np.random.RandomState(0)
    xs = nd.array(rng.rand(batch, 3, 224, 224).astype(np.float32))
    ys = nd.array(rng.randint(0, 1000, (batch,)).astype(np.float32))
    for _ in range(3):
        loss = step.step(xs, ys)
    float(jax.device_get(loss))

    ms = device_ms_per_step(lambda: step.step(xs, ys), steps,
                            lambda o: float(jax.device_get(o)))
    print(f"device_ms_per_step={ms:.3f}  img/s={batch / ms * 1000:.1f}")


if __name__ == "__main__":
    main()
