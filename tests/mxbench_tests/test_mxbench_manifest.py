"""BENCHMARK.json against the files under mxbench/: every name
resolves, every arrow points at a metric its cells report, and a new
cell, traffic mix and layer metric are picked up from files alone."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _cell_file(name):
    with open(os.path.join(ROOT, "mxbench", "workloads", name + ".json")) as f:
        return json.load(f)


def test_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["mxbench", "tests/mxbench_tests"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]]
    names += [w[k] for w in bench["workloads"] for k in ("config", "traffic")]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        got = [e["name"] for e in bench[k]]
        assert len(got) == len(set(got))
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for text in [w["why"] for w in bench["workloads"] + bench["configs"]] \
            + [c["source"] for c in bench["configs"]] \
            + [m["layer"] for m in bench["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_quarter_of_cells_may_take_four_chips(bench):
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_every_cell_resolves_to_files(bench):
    from mxbench import manifest
    assert sorted(w["name"] for w in bench["workloads"]) \
        == manifest.workload_names()
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    layer = {m["name"]: m for m in bench["per_layer"]}
    used = set()
    for w in bench["workloads"]:
        cell = _cell_file(w["name"])
        assert {k: cell[k] for k in ("config", "traffic", "chips", "why")} \
            == {k: w[k] for k in ("config", "traffic", "chips", "why")}
        used.add(w["config"])
        sizes, cfgmod, refmod = manifest.config(w["config"])
        assert configs[w["config"]]["file"] \
            == "mxbench/configs/%s.json" % w["config"]
        assert sizes["reduced"] == configs[w["config"]]["reduced"]
        assert hasattr(cfgmod, "train_flops_per_sample")
        assert hasattr(refmod, "train_losses")
        params, gen = manifest.traffic(w["traffic"])
        assert hasattr(gen, "run")
        assert "setup_s" in cell["metrics"] and len(cell["metrics"]) >= 2
        for m in cell["metrics"]:
            assert m in e2e and gen.UNITS[m] == e2e[m]["unit"]
            assert w["name"] in e2e[m].get("workloads", [w["name"]])
        for m in e2e.values():      # and nothing listed that it lacks
            if w["name"] in m.get("workloads", [w["name"]]):
                assert m["name"] in cell["metrics"]
        assert cell["layer_metrics"]
        for m in cell["layer_metrics"]:
            assert manifest.layer_metric(m).UNIT == layer[m]["unit"]
            assert w["name"] in layer[m].get("workloads", [w["name"]])
    assert used == set(configs)


def test_every_arrow_lands_on_a_metric_its_cells_report(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"]: _cell_file(w["name"]) for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for name in m.get("workloads", list(cells)):
            assert m["name"] in cells[name]["layer_metrics"], (m, name)
            assert m["moves"] in cells[name]["metrics"], (m, name)
    listed = {m["name"] for m in bench["per_layer"]}
    for name, cell in cells.items():
        assert set(cell["layer_metrics"]) <= listed, name


def test_unknown_device_kind_is_an_error():
    from mxbench import manifest
    assert manifest.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        manifest.peaks("cpu")
    with pytest.raises(KeyError):
        manifest.peaks("_source")


def test_drop_in_files_are_found_with_no_edit(tmp_path):
    """A new cell, traffic mix and layer metric: three new files in a
    copy of mxbench/, no file that was there edited. The names are ones
    no cell will take, so that the files dropped in are new ones."""
    drop_cell, drop_mix = "zz_drop_in_cell_s384", "zz_drop_in_mix_s384"
    pkg = tmp_path / "mxbench"
    shutil.copytree(os.path.join(ROOT, "mxbench"), pkg,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in pkg.rglob("*") if p.is_file()}
    assert not (pkg / "workloads" / (drop_cell + ".json")).exists()
    assert not (pkg / "traffic" / (drop_mix + ".json")).exists()
    cell = json.loads((pkg / "workloads" /
                       "bert_base_pretrain_s128.json").read_text())
    cell["traffic"] = drop_mix
    cell["layer_metrics"].append("steps_traced.train")
    (pkg / "workloads" / (drop_cell + ".json")).write_text(json.dumps(cell))
    mix = json.loads((pkg / "traffic" / "pretrain_mlm_s128.json").read_text())
    mix.update(seq=384, batch_per_chip=80)
    (pkg / "traffic" / (drop_mix + ".json")).write_text(json.dumps(mix))
    (pkg / "layer_metrics" / "steps_traced.train.py").write_text(
        'UNIT = "steps"\n\n\ndef read(run):\n    return run.traced_steps\n')
    env = dict(os.environ, PYTHONPATH=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-m", "mxbench.run", "--list"],
                         cwd=tmp_path, env=env, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    found = json.loads(out.stdout)
    assert drop_cell in found["workloads"]
    assert drop_mix in found["traffic"]
    assert "steps_traced.train" in found["layer_metrics"]
    assert all(p.read_bytes() == data for p, data in before.items())
    # and the new cell resolves through the copy's own manifest
    probe = ("from mxbench import manifest as m; c = m.workload("
             "'%s'); t, g = m.traffic(c['traffic']); "
             "print(t['seq'], m.layer_metric(c['layer_metrics'][-1]).UNIT, "
             "m.ROOT)" % drop_cell)
    out = subprocess.run([sys.executable, "-c", probe], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["384", "steps", str(pkg)]
