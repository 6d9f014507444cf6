#!/usr/bin/env python
"""The bytes of a training cell's whole step, compiled for a described
v5e with no chip attached: what the TPU compiler's buffer assignment
needs, or by how much the step misses the chip.

    python tools/step_bytes.py keye_vl2_30b_a3b_midtrain_s8192
    python tools/step_bytes.py <cell> --layers 2 --dump /root/scratch/d

A CPU tool (it needs the TPU compiler that the chipless tests load, and
no accelerator). It builds the cell's net and loss as the benchmark
does (``mxbench/configs/<config>.py::sharded_parts``), takes the step
function, shardings, AUTO parameter layouts and donation from
``ShardedTrainStep`` itself (no array is made: ``jax.device_put`` is
stood in for while ``_build`` runs) and compiles it from abstract
values. For the Keye-VL cell, which stands 14 MB under the chip's
memory (ROADMAP A11), it has agreed with the chip to the megabyte on
every form of the step both have read (PERF.md section 6, PR 49): run
it before chip time is spent on any change to that step. Seven to nine
minutes and 8 GB of host memory for that cell whole; ``--layers 2`` is
quicker but is another program: its buffer assignment did not always
move with the whole step's. ``--dump`` keeps the compiler's files
(``*buffer-assignment.txt`` has every buffer's offset in the heap and
the tensors live at the peak; ``*memory-usage-report.txt`` the largest).
Prints one line: ``step_bytes <cell>: temporaries .. arguments ..
code ..`` or ``step_bytes <cell>: does not fit: <the compiler's
sentence>``, and exits 1 in that case."""
import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class DoesNotFit(Exception):
    """The compiler's sentence (or the head of its error)."""


def step_bytes(name, layers=0):
    """``{"temporaries", "arguments", "code", "layers"}`` of the training
    cell ``name``'s step compiled for a described v5e (``layers``: a
    ``num_hidden_layers`` in place of the configuration's), or
    :class:`DoesNotFit`. The process's ``jax.device_put`` and the
    kernels' ``interpret_mode`` are stood in for while it runs and put
    back."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxbench import manifest
    from mxnet_tpu import random as mx_random
    from mxnet_tpu.ops import pallas_common
    from mxnet_tpu.parallel import sharded

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1), ("dp",))
    rep = NamedSharding(mesh, P())

    cell = manifest.workload(name)
    traffic, _ = manifest.traffic(cell["traffic"])
    sizes, cfgmod, _ = manifest.config(cell["config"])
    if layers:
        sizes = dict(sizes, num_hidden_layers=layers)
    seq, batch = int(traffic["seq"]), int(traffic["batch_per_chip"])
    net, loss, n_in = cfgmod.sharded_parts(
        sizes, float(traffic.get("dropout", 0.0)), seq)
    fn, data_names, names, needs_rng = sharded.trace_block(net, loss, n_in)
    shapes = {n: p.shape for block in (net, loss)
              if hasattr(block, "collect_params")
              for n, p in block.collect_params().items()}
    aux_names = [n for n in names if n in getattr(fn, "_aux_names", set())]
    names = [n for n in names if n not in aux_names]

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    opt = dict(traffic["optimizer"])
    step = object.__new__(sharded.ShardedTrainStep)
    step.mesh, step._fn, step._data_names = mesh, fn, data_names
    step._needs_rng, step._optimizer = needs_rng, opt.pop("name")
    step.grad_accum, step._split_update = 1, False
    step._hp = dict(dict(lr=0.01, momentum=0.9, wd=0.0, beta1=0.9,
                         beta2=0.999, epsilon=1e-8), clip_gradient=-1.0,
                    rescale_grad=1.0, **opt)
    step._dtype = sizes["compute_dtype"]
    step._rng_impl = needs_rng if isinstance(needs_rng, str) \
        and needs_rng != "default" else mx_random._IMPL
    step._rng = np.zeros(jax.eval_shape(lambda: jax.random.key_data(
        jax.random.key(0, impl=step._rng_impl))).shape, np.uint32)
    step._t = 0
    step.aux = {n: sds(shapes[n]) for n in aux_names}
    step.param_shardings = {n: rep for n in names}
    n_states = sharded._n_states(step._optimizer, step._hp["momentum"])
    step.state_shardings = {n: (rep,) * n_states for n in names}
    step.data_shardings = [rep] * n_in
    params = {n: sds(shapes[n]) for n in names}
    states = {n: (params[n],) * n_states for n in names}
    ids = sds((batch, seq), jnp.int32)
    put, interpret = jax.device_put, pallas_common.interpret_mode
    jax.device_put = lambda x, s=None: sds(np.shape(x), np.asarray(x).dtype)
    # kernels as the chip gets them, though only CPU devices are attached
    pallas_common.interpret_mode = lambda: False
    try:
        step._build()
        if not step._use_auto_layout:
            raise RuntimeError("the step was not built with AUTO layouts")
        lowered = step._fused.lower(
            params, step.aux, states, sds(()),
            sds(step._rng.shape, jnp.uint32), *([ids] * n_in))
        try:
            compiled = lowered.compile()
        except Exception as e:   # the compiler's own error types vary
            said = re.search(r"Used [0-9.]+G of [0-9.]+G hbm\. Exceeded hbm "
                             r"capacity by [0-9.]+[KMG]", str(e))
            raise DoesNotFit(said.group(0) if said else str(e)[:400]) from e
    finally:
        jax.device_put, pallas_common.interpret_mode = put, interpret
    m = compiled.memory_analysis()
    return {"temporaries": m.temp_size_in_bytes,
            "arguments": m.argument_size_in_bytes,
            "code": m.generated_code_size_in_bytes,
            "layers": layers or sizes.get("num_hidden_layers")}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell", help="a training cell of mxbench/workloads/")
    ap.add_argument("--layers", type=int, default=0,
                    help="num_hidden_layers in place of the configuration's")
    ap.add_argument("--dump", default="",
                    help="directory for the compiler's dump of the step")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.dump:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_dump_to=%s "
            "--xla_dump_hlo_as_text --xla_dump_hlo_module_re=jit_fused_step"
            % args.dump)
    t0 = time.time()
    try:
        found = step_bytes(args.cell, args.layers)
    except DoesNotFit as e:
        print("step_bytes %s: does not fit: %s (%d s)" % (
            args.cell, e, time.time() - t0))
        sys.exit(1)
    print("step_bytes %s: %s (%d s)" % (args.cell, json.dumps(found),
                                        time.time() - t0))


if __name__ == "__main__":
    main()
