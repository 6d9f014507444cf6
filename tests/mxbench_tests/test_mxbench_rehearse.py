"""``python -m mxbench.run --rehearse`` for every cell: the run's whole
control flow at toy sizes on the CPU, kernels interpreted. The four-chip
cell gets four virtual CPU devices from the test, not from the
program. A rehearsal prints ``"rehearsal"`` and never a metric; a real
run without a TPU exits non-zero and prints no result."""
import json
import os
import subprocess
import sys

import pytest

from mxbench import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(args, chips=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_PALLAS_INTERPRET="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=%d" % chips)
    return subprocess.run([sys.executable, "-m", "mxbench.run"] + args,
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=600)


@pytest.mark.parametrize("cell", manifest.workload_names())
def test_rehearsal(cell):
    chips = manifest.workload(cell)["chips"]
    out = _run(["--rehearse", "--workload", cell, "--seconds", "1",
                "--seed", "3000000019"], chips)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed" and last["workload"] == cell
    assert last["attempted"] > 0 and last["failed"] == 0
    assert "metrics" not in last and "device" not in last
    assert "0 XLA compile(s), 0 new watched program(s)" in out.stdout


def test_four_chip_cell_refuses_one_device():
    out = _run(["--rehearse", "--workload", "bert_base_pretrain_s128_dp4"], 1)
    assert out.returncode != 0
    assert "rehearsal" not in out.stdout


def test_no_tpu_no_result():
    out = _run(["--workload", "bert_base_pretrain_s128", "--seed", "1",
                "--seconds", "1", "--trace", "0"])
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert '"metrics"' not in out.stdout and '"correct"' not in out.stdout
