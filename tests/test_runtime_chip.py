"""What keeps a CPU run from passing for a chip run (PR 21): the
accelerator assertion, the compile cache's placement, the MFU peak, the
native loader's loud failure, the replica platform."""
import os

import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import runtime, telemetry


def test_require_accelerator_refuses_the_cpu():
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(mx.MXNetError, match="no accelerator"):
        runtime.require_accelerator()
    # ... although the tpu context resolves, to a CPU device
    assert mx.tpu(0).jax_device.platform == "cpu"


@pytest.fixture
def cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("given", [None, "outside"])
def test_compile_cache_placed_from_outside(cache_config, tmp_path, given):
    """A directory JAX already holds (it fills the option from
    JAX_COMPILATION_CACHE_DIR) wins; otherwise <checkout>/.jax_cache,
    fixed and derived from the package's own path."""
    outside = str(tmp_path / "outside") if given else None
    jax.config.update("jax_compilation_cache_dir", outside)
    path = runtime.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(mx.__file__)))
    assert path == (outside or os.path.join(root, ".jax_cache"))
    assert jax.config.jax_compilation_cache_dir == path
    assert runtime.enable_compile_cache() == path      # idempotent


def test_no_other_code_sets_a_cache_directory():
    root = os.path.dirname(os.path.dirname(os.path.abspath(mx.__file__)))
    hits = []
    for base in ("mxnet_tpu", "tools"):
        for dirpath, _dirs, files in os.walk(os.path.join(root, base)):
            for name in files:
                if not name.endswith(".py"):
                    continue
                path = os.path.join(dirpath, name)
                with open(path) as f:
                    src = f.read()
                if "compilation_cache_dir" in src \
                        or "JAX_COMPILATION_CACHE_DIR" in src:
                    hits.append(os.path.relpath(path, root))
    for name in ("bench.py", "chip_smoke.py", "__graft_entry__.py"):
        with open(os.path.join(root, name)) as f:
            if "compilation_cache_dir" in f.read():
                hits.append(name)
    assert hits == [os.path.join("mxnet_tpu", "runtime.py")], hits


def test_unknown_device_kind_has_no_peak(monkeypatch):
    monkeypatch.delenv("MXNET_PEAK_FLOPS", raising=False)
    telemetry.refresh()
    try:
        assert telemetry.known_peak_flops() is None
        with pytest.raises(mx.MXNetError, match="MXNET_PEAK_FLOPS"):
            telemetry.peak_flops()
        monkeypatch.setenv("MXNET_PEAK_FLOPS", "2e12")
        telemetry.refresh()
        assert telemetry.peak_flops() == 2e12
    finally:
        monkeypatch.delenv("MXNET_PEAK_FLOPS", raising=False)
        telemetry.refresh()


def test_native_build_failure_is_loud(monkeypatch, tmp_path):
    """A failed make raises with the compiler's output instead of a
    silent None."""
    from mxnet_tpu import native
    (tmp_path / "Makefile").write_text(
        "libmxtpu_engine.so:\n\t@echo 'engine.cc: no such compiler' >&2; "
        "exit 3\n")
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    with pytest.raises(mx.MXNetError, match="no such compiler"):
        native._load("libmxtpu_engine.so")


def test_replica_manager_needs_a_platform():
    from mxnet_tpu.serve import fleet
    with pytest.raises(mx.MXNetError, match="platform"):
        fleet.ReplicaManager(n=1, spec={"seed": 1}, kv_addr="127.0.0.1:1")
