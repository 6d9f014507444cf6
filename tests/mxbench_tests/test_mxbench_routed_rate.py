"""``train_routed_samples_per_s``: the rate of the cells whose step takes
what its sequences route to the held experts, so that their runs spread
over seeds by more than the 1% of ``train_samples_per_s`` takes (PERF.md
section 2). The same quantity under a name and a bound of its own, as
``train_images_per_s`` is; and since a per-layer metric moves one
end-to-end metric, every reader those cells share with the others has a
second name, ``<metric>.train_routed``, that reads what the first
reads."""
import json
import os

import pytest

from mxbench import manifest

RATE = "train_routed_samples_per_s"
with open(os.path.join(os.path.dirname(manifest.ROOT),
                       "BENCHMARK.json")) as f:
    BENCH = json.load(f)
END = {m["name"]: m for m in BENCH["end_to_end"]}
LAYER = {m["name"]: m for m in BENCH["per_layer"]}
SECOND_NAMES = sorted(n for n in manifest.names_in("layer_metrics", ".py")
                      if n.endswith(".train_routed"))


def test_the_rate_has_its_cells_and_the_other_rate_lacks_them():
    cells = END[RATE]["workloads"]
    assert cells and not set(cells) & set(
        END["train_samples_per_s"]["workloads"])
    for key in ("unit", "better", "source"):
        assert END[RATE][key] == END["train_samples_per_s"][key]
    # the wider bound is this name's alone
    assert END["train_samples_per_s"]["bound"] == 0.01 < END[RATE]["bound"]
    for cell in manifest.workload_names():
        listed = manifest.workload(cell)["metrics"]
        assert (listed == [RATE, "setup_s"]) == (cell in cells), cell
        _, gen = manifest.traffic(manifest.workload(cell)["traffic"])
        assert gen.UNITS[RATE] == END[RATE]["unit"]
    # whatever such a cell reads moves its own rate or the set-up, and
    # nothing that moves its rate is listed for another cell
    for name, m in LAYER.items():
        ours = set(m.get("workloads", cells)) <= set(cells)
        assert (m["moves"] == RATE) == (ours and m["moves"] != "setup_s"), \
            name


@pytest.mark.parametrize("name", SECOND_NAMES)
def test_a_second_name_reads_what_the_first_reads(name):
    first = name[:-len("_routed")]
    reader, base = manifest.layer_metric(name), manifest.layer_metric(first)
    assert reader.UNIT == base.UNIT
    assert reader.read.__module__ == base.__name__       # one function
    mine, theirs = LAYER[name], LAYER[first]
    for key in ("unit", "better", "source", "layer"):
        assert mine[key] == theirs[key], key
    assert (mine["moves"], theirs["moves"]) == (RATE, "train_samples_per_s")
    assert set(mine["workloads"]) <= set(END[RATE]["workloads"])
    assert not set(theirs["workloads"]) & set(END[RATE]["workloads"])
    for cell in mine["workloads"]:
        listed = manifest.workload(cell)["layer_metrics"]
        assert name in listed and first not in listed
