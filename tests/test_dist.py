"""Multi-process distribution tests (ref: tests/nightly/
dist_sync_kvstore.py + tools/launch.py local tracker — multi-node
simulated as multi-process with env rendezvous, SURVEY.md §4).

Each case launches real OS processes through tools/launch.py; workers
join a jax.distributed group on virtual CPU devices and assert exact
cross-process gradient sums.
"""
import os
import subprocess
import sys

import pytest

from conftest import multiprocess_collectives_supported  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAUNCH = os.path.join(ROOT, "tools", "launch.py")
WORKER = os.path.join(ROOT, "tests", "dist_worker.py")

# Some jaxlib builds cannot run cross-process collectives on the CPU
# backend ("Multiprocess computations aren't implemented..."). The
# string condition is evaluated lazily at test SETUP, so runs that
# deselect these tests (tier-1's -m 'not slow') never pay the probe.
requires_multiprocess_collectives = pytest.mark.skipif(
    "not multiprocess_collectives_supported()",
    reason="this jax backend cannot run multiprocess collectives on "
           "this host (conftest capability probe failed)")


def _run(nworkers, ndev, mode="dist_sync", script=WORKER, timeout=240):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)         # worker sets its own device count
    env["TEST_KV_MODE"] = mode
    out = subprocess.run(
        [sys.executable, LAUNCH, "-n", str(nworkers),
         "--cpu-devices", str(ndev), sys.executable, script],
        env=env, capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, (out.stdout, out.stderr)
    return out.stdout


@pytest.mark.slow
@requires_multiprocess_collectives
def test_dist_sync_exact_sums():
    stdout = _run(2, 2, "dist_sync")
    assert stdout.count("DIST_OK") == 2
    assert "nw=2" in stdout and "nloc=2" in stdout


@pytest.mark.slow
@requires_multiprocess_collectives
def test_dist_async_accepted():
    # dist_async maps onto the synchronous collective (documented
    # strictly-stronger consistency); surface must accept it
    stdout = _run(2, 1, "dist_async")
    assert stdout.count("DIST_OK") == 2


@pytest.mark.slow
@requires_multiprocess_collectives
def test_dist_trainer_matches_single_process():
    stdout = _run(2, 2, "dist_sync",
                  script=os.path.join(ROOT, "tests", "dist_trainer_worker.py"))
    assert stdout.count("TRAINER_OK") == 2


def test_num_servers_rejected():
    out = subprocess.run(
        [sys.executable, LAUNCH, "-n", "1", "-s", "2", "echo", "hi"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode != 0
    assert "parameter-server" in out.stderr


@pytest.mark.slow
@requires_multiprocess_collectives
def test_p3store_sliced_exact():
    env_extra = {"MXNET_KVSTORE_BIGARRAY_BOUND": "64"}
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["TEST_KV_MODE"] = "p3store_dist"
    env.update(env_extra)
    out = subprocess.run(
        [sys.executable, LAUNCH, "-n", "2", "--cpu-devices", "2",
         sys.executable, WORKER],
        env=env, capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert out.stdout.count("DIST_OK") == 2


@pytest.mark.slow
@requires_multiprocess_collectives
def test_sharded_train_step_multiprocess():
    """ShardedTrainStep over a process-spanning mesh: losses finite and
    identical in every process (SPMD)."""
    stdout = _run(2, 2, "dist_sync",
                  script=os.path.join(ROOT, "tests",
                                      "dist_sharded_worker.py"))
    lines = [l for l in stdout.splitlines() if "SHARDED_OK" in l]
    assert len(lines) == 2
    losses = {l.split("loss=")[1] for l in lines}
    assert len(losses) == 1, stdout
