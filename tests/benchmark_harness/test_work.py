"""benchmark/work.py against values computed by hand, and the table of
peaks. Pure Python."""

import inspect
import json

import pytest

from benchmark import work


def test_constants_are_the_stated_ones():
    assert (work.G1_DBL, work.G1_ADD, work.G2_DBL, work.G2_ADD) == (7, 11, 16, 29)
    assert work.FINAL_EXP == 7920
    assert work.INT8_OPS_PER_M == 13824


def test_one_single_vote_by_hand():
    # n=1, w=1, m=1: ladders 64*(7+16) + 32*(11+29) = 2752; subgroup
    # 63*16 + 5*29 = 1153; no key additions; per call: 0 + 0 additions,
    # 63*36 shared squarings = 2268, two Miller loops of
    # 63*(25+39) + 5*(41+39) = 4432, final exponentiation 7920
    got = work.verify_call(1, 1, 1)
    assert got["field_mults"] == 2752 + 1153 + 2268 + 2 * 4432 + 7920 == 22957
    assert got["int8_ops"] == 22957 * 13824
    assert got["bytes"] == 128 + 1 * 100 + 1


def test_a_full_batch_by_hand():
    # n=64 aggregates of 131 members over 12 messages
    per_item = 130 * 11 + 2752 + 1153
    per_call = 52 * 11 + 63 * 29 + 2268 + 13 * 4432 + 7920
    got = work.verify_call(64, 131, 12)
    assert got["field_mults"] == 64 * per_item + per_call == 411643
    assert got["bytes"] == 64 * (128 + 131 * 100 + 1)
    # singles: the same call at width 1
    assert work.verify_call(64, 1, 12)["field_mults"] == 64 * 3905 + per_call


def test_count_takes_shapes_only():
    """No implementation parameter (MSM window, limb width, fusion) can
    reach the numerator: the function has no such argument and reads no
    such module state."""
    assert list(inspect.signature(work.verify_call).parameters) == ["n", "w", "m"]
    assert not [k for k in vars(work) if "window" in k.lower()
                or "limb" in k.lower()]


@pytest.mark.parametrize("bad", [(0, 1, 1), (4, 0, 1), (4, 1, 5), (4, 1, 0)])
def test_not_a_call(bad):
    with pytest.raises(ValueError):
        work.verify_call(*bad)


def test_peaks_known_and_unknown(tmp_path):
    v5e = work.load_peaks("TPU v5 lite")
    assert v5e["int8_ops_per_s"] == 393e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError):
        work.load_peaks("TPU v99")
    with pytest.raises(KeyError):
        work.load_peaks("cpu")
    other = tmp_path / "peaks.json"
    other.write_text(json.dumps({"chip-x": {"int8_ops_per_s": 1.0,
                                            "hbm_bytes_per_s": 1.0}}))
    assert work.load_peaks("chip-x", str(other))["int8_ops_per_s"] == 1.0


def test_least_seconds_says_which_bound():
    calls = [{"n": 64, "w": 1, "m": 12}]
    v5e = work.load_peaks("TPU v5 lite")
    least, bound = work.least_seconds(calls, v5e)
    assert bound == "compute"
    assert least == pytest.approx(320123 * 13824 / 393e12)
    slow_memory = dict(v5e, hbm_bytes_per_s=1.0)
    assert work.least_seconds(calls, slow_memory) == (14656.0, "memory")
