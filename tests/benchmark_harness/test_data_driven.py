"""The harness is driven by data: a new configuration, traffic mix, cell or
per-layer metric is picked up from files dropped beside the others plus
one entry in BENCHMARK.json, with no edit to a file that is there. And the
manifest's own names, units and files hold to the contract's alphabet."""

import json
import os
import re

import pytest

from benchmark import loader

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME, UNIT = loader.NAME, loader.UNIT
FILE = re.compile(r"^[A-Za-z0-9_.\-/]+$")


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_manifest_names_units_and_files():
    m = manifest()
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for row in m[group]:
            assert NAME.match(row["name"]), row["name"]
            names.append((group in ("end_to_end", "per_layer"), row["name"]))
            if "unit" in row:
                assert UNIT.match(row["unit"]), row["unit"]
                assert row["better"] in ("lower", "higher")
    assert len(names) == len(set(names))
    for w in m["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    for top, _dirs, files in (t for p in m["paths"]
                              for t in os.walk(os.path.join(REPO, p))):
        if "__pycache__" in top:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(top, f), REPO)
            assert FILE.match(rel), rel


def test_every_cell_and_metric_of_the_manifest_has_its_files():
    m = manifest()
    bench = os.path.join(REPO, "benchmark")
    e2e = {row["name"] for row in m["end_to_end"]}
    for w in m["workloads"]:
        cell = loader.load_cell(REPO, w["name"])
        assert cell["config"]["name"] == w["config"]
        assert {x["name"] for x in cell["end_to_end"]} >= {"setup_s"}
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
    for row in m["per_layer"]:
        reader = loader.load_reader(bench, row["name"])
        assert reader.UNIT == row["unit"] and reader.LAYER == row["layer"]
        assert row["moves"] in e2e and callable(reader.read)
    for c in m["configs"]:
        with open(os.path.join(REPO, c["file"])) as fh:
            assert json.load(fh)["reduced"] == c["reduced"]


def test_new_files_are_picked_up_without_editing_one(tiny_root):
    """Drop a configuration, a traffic mix, a cell file and a reader into a
    copy; add the entries; nothing that was there is touched."""
    bench = os.path.join(tiny_root, "benchmark")
    before = {
        os.path.join(top, f): os.path.getmtime(os.path.join(top, f))
        for top, _d, files in os.walk(tiny_root) for f in files
        if f != "BENCHMARK.json"
    }

    def dump(rel, obj):
        with open(os.path.join(bench, rel), "w") as fh:
            json.dump(obj, fh)

    with open(os.path.join(bench, "configs", "firehose-tiny.json")) as fh:
        config = json.load(fh)
    dump("configs/firehose-new.json", dict(config, name="firehose-new"))
    dump("traffic/burst-new.json", {"members": "single",
                                    "pacing": "backlog"})
    dump("workloads/firehose-new.burst.json", {"kernel": "k",
                                               "kernel_module_match": "k",
                                               "reference_sample": 1})
    with open(os.path.join(bench, "layer_metrics", "new_metric.py"), "w") as fh:
        fh.write('LAYER, UNIT = "load generator", "ms"\n\n\n'
                 'def read(run):\n    return run["gen"].get("x")\n')
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as fh:
        m = json.load(fh)
    m["configs"].append({"name": "firehose-new", "source": "test",
                         "file": "benchmark/configs/firehose-new.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "firehose-new.burst",
                           "config": "firehose-new", "traffic": "burst-new",
                           "chips": 1, "why": "test"})
    m["per_layer"].append({"name": "new_metric.tput", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "load generator", "moves": "sigsets_per_s",
                           "workloads": ["firehose-new.burst"]})
    # a metric keyed to cells takes the new cell's name into its entry
    m["end_to_end"][0]["workloads"].append("firehose-new.burst")
    with open(path, "w") as fh:
        json.dump(m, fh)

    cell = loader.load_cell(tiny_root, "firehose-new.burst")
    assert cell["config"]["name"] == "firehose-new"
    assert cell["traffic"]["pacing"] == "backlog"
    # metrics keyed to other cells are not this cell's; one without a
    # `workloads` key (setup_s) is every cell's, new ones too
    assert [x["name"] for x in cell["per_layer"]] == ["new_metric.tput"]
    assert [x["name"] for x in cell["end_to_end"]] == ["sigsets_per_s",
                                                       "setup_s"]
    reader = loader.load_reader(bench, "new_metric.tput")
    assert reader.read({"gen": {"x": 3.0}}) == 3.0
    after = {p: os.path.getmtime(p) for p in before}
    assert after == before


@pytest.mark.parametrize("bad", ["has space", "a/b", "x" * 65, "µs", ""])
def test_loader_refuses_a_bad_name(tiny_root, bad):
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as fh:
        original = fh.read()
    m = json.loads(original)
    m["per_layer"].append({"name": bad, "unit": "ms", "better": "lower",
                           "source": "host_clock", "layer": "x",
                           "moves": "setup_s"})
    try:
        with open(path, "w") as fh:
            json.dump(m, fh)
        with pytest.raises(ValueError):
            loader.load_cell(tiny_root, m["workloads"][0]["name"])
    finally:
        with open(path, "w") as fh:
            fh.write(original)
