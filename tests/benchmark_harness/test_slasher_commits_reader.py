"""The reader of `slasher_commits_per_batch.*` (benchmark/layer_metrics/
slasher_commits_per_batch.py): on a recorded exposition with and without
the program's `slasher_storage_commits_total` (a program from before the
counter: None, no raise), and through one `--trace 1` rehearsal of the
aggregates cell at the tiny size, where the served path's slasher feed
has to read one storage transaction a batch."""

import json
import os

import pytest

from benchmark import loader, observe, run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
BASE = "slasher_commits_per_batch"
CELLS = {".tput": "firehose-tiny.singles", ".lat": "firehose-tiny.aggregates"}

RECORDED = """\
# HELP slasher_span_indices_total attesting indices folded into the slasher span store
# TYPE slasher_span_indices_total counter
slasher_span_indices_total {indices}
# HELP slasher_storage_commits_total storage transactions (put / put_batch) the slasher issued
# TYPE slasher_storage_commits_total counter
slasher_storage_commits_total {commits}
slasher_record_reads_total{{source="db"}} 4689.0
slasher_record_reads_total{{source="write_set"}} 20265.0
"""
BATCHES = [{"kind": "batch"}] * 3 + [{"kind": "breaker"}]


def entries():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        rows = json.load(fh)["per_layer"]
    return [r for r in rows if r["name"].split(".")[0] == BASE]


def test_the_manifest_has_the_two_entries_with_their_reader():
    rows = entries()
    assert [r["name"] for r in rows] == [BASE + ".tput", BASE + ".lat"]
    for row in rows:
        reader = loader.load_reader(BENCH, row["name"])
        assert (reader.UNIT, reader.LAYER) == (row["unit"], row["layer"])
        assert (row["better"], row["source"]) == ("lower", "program_counter")
        assert row["moves"] == ("sigsets_per_s" if row["name"].endswith(
            ".tput") else "verdict_p95_ms")
        assert len(row["workloads"]) == 1


@pytest.mark.parametrize("suffix", sorted(CELLS))
@pytest.mark.parametrize("before,after,want", [
    (2.0, 5.0, 1.0),            # one transaction a batch
    (2.0, 24962.0, 8320.0),     # one an attesting index, as before
    (5.0, 5.0, 0.0),            # the series is there and did not grow
])
def test_reader_on_a_recorded_exposition(suffix, before, after, want):
    seen = {
        "before": observe.parse_exposition(
            RECORDED.format(indices=16640.0, commits=before)),
        "after": observe.parse_exposition(
            RECORDED.format(indices=41600.0, commits=after)),
        "flight": BATCHES,
    }
    reader = loader.load_reader(BENCH, BASE + suffix)
    assert reader.read(seen) == pytest.approx(want)


@pytest.mark.parametrize("suffix", sorted(CELLS))
def test_reader_finds_nothing_in_a_program_from_before_the_counter(suffix):
    """As the parent commit exposes it: the slasher's other series, no
    commit counter. None, and no raise: the result line leaves it out."""
    text = "\n".join(line for line in RECORDED.format(
        indices=41600.0, commits=0.0).splitlines()
        if "commits" not in line and "record_reads" not in line)
    reader = loader.load_reader(BENCH, BASE + suffix)
    old = {"before": {}, "after": observe.parse_exposition(text),
           "flight": BATCHES}
    assert reader.read(old) is None
    assert reader.read({"before": {}, "after": {}, "flight": []}) is None
    # the counter without a batch in the window: nothing to divide by
    new = observe.parse_exposition(RECORDED.format(indices=1.0, commits=1.0))
    assert reader.read({"before": {}, "after": new,
                        "flight": [{"kind": "breaker"}]}) is None


@pytest.fixture(scope="module")
def commits_root(tiny_root):
    """The tiny root's temporary copy with this PR's two entries appended
    for the tiny cells."""
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as fh:
        original = fh.read()
    m = json.loads(original)
    for row in entries():
        suffix = "." + row["name"].rsplit(".", 1)[-1]
        m["per_layer"].append(dict(row, workloads=[CELLS[suffix]]))
    with open(path, "w") as fh:
        json.dump(m, fh)
    try:
        yield tiny_root
    finally:
        with open(path, "w") as fh:
            fh.write(original)


def test_traced_rehearsal_reads_one_transaction_a_batch(commits_root):
    """Two aggregators a committee: a batch of 4 holds validators more
    than once, the traffic that cost a transaction an index."""
    res = run.run_cell(commits_root, CELLS[".lat"], 2**31 + 25, 3.0, True,
                       require_tpu=False)
    assert res["correct"] is True, res["compared"]
    # a batch at the window's edge may be on one side of the exposition
    # and the other of the flight snapshot: one batch of slack
    assert res["metrics"][BASE + ".lat"]["value"] == pytest.approx(1.0, abs=0.5)
    assert res["metrics"][BASE + ".lat"]["unit"] == "commits"
