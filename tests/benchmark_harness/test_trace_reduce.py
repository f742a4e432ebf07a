"""benchmark/trace_reduce.py on hand-built traces: busy union, kernel sum
by name, gap attribution, the traced window. Pure Python (times in ns)."""

import pytest

from benchmark import trace_reduce as tr

S = 1e9  # one second in ns


def trace(ops, modules=(), host=()):
    return {"devices": {"/device:TPU:0": {"ops": list(ops),
                                          "modules": list(modules)}},
            "host": list(host)}


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 7), (9, 8)]) == [(0, 3), (5, 7)]


def test_busy_is_the_union_inside_the_window():
    t = trace(
        ops=[("a", 0.0 * S, 1.0 * S), ("b", 0.5 * S, 1.5 * S),
             ("c", 3.0 * S, 3.5 * S), ("late", 9.0 * S, 12.0 * S)],
        host=[("bench/traced", 0.0, 10.0 * S)],
    )
    r = tr.reduce(t, "verify")
    assert r["window_s"] == pytest.approx(10.0)
    # [0,1.5] + [3,3.5] + [9,10] (the last op is cut at the window's end)
    assert r["busy_s"] == pytest.approx(3.0)
    assert dict(map(tuple, r["device_ops"]))["late"] == pytest.approx(1.0)


def test_kernel_time_by_name_and_calls():
    t = trace(
        ops=[("fusion.1", 1 * S, 2 * S)],
        modules=[("jit_aggregate_fast_verify_msm_idx_kernel(123)", 1 * S, 1.2 * S),
                 ("jit_aggregate_fast_verify_msm_idx_kernel(123)", 2 * S, 2.3 * S),
                 ("jit_g1_decompress_kernel(9)", 3 * S, 3.9 * S)],
        host=[("bench/traced", 0.0, 4 * S)],
    )
    r = tr.reduce(t, "aggregate_fast_verify_msm_idx")
    assert r["kernel_calls"] == 2
    assert r["kernel_s"] == pytest.approx(0.5)


def test_gaps_go_to_the_host_span_that_covers_most_of_them():
    t = trace(
        ops=[("k", 2 * S, 3 * S), ("k", 6 * S, 7 * S)],
        host=[("bench/traced", 0.0, 10 * S),
              ("bench/submit", 0.0, 1.9 * S),          # gap [0,2]
              ("bls/agg_fast_verify_msm_idx/b64", 3.1 * S, 5.0 * S),  # gap [3,6]
              ("bench/generator_sleep", 5.0 * S, 5.5 * S)],
    )
    r = tr.reduce(t, "k")
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps["bench/submit"] == pytest.approx(2.0)
    assert gaps["bls/agg_fast_verify_msm_idx/b64"] == pytest.approx(3.0)
    # [7,10]: only the container overlaps it
    assert gaps["no_host_span"] == pytest.approx(3.0)
    assert r["busy_s"] + sum(gaps.values()) == pytest.approx(r["window_s"])


def test_without_a_window_span_the_devices_extent_is_the_window():
    r = tr.reduce(trace(ops=[("a", 2 * S, 3 * S), ("b", 5 * S, 6 * S)]), "a")
    assert r["window_s"] == pytest.approx(4.0)
    assert r["busy_s"] == pytest.approx(2.0)


def test_a_trace_in_which_nothing_ran_on_the_device_gives_nothing():
    assert tr.reduce(trace(ops=[]), "k") is None
    outside = trace(ops=[("a", 20 * S, 21 * S)],
                    host=[("bench/traced", 0.0, 10 * S)])
    assert tr.reduce(outside, "k") is None


def test_busy_is_averaged_over_the_chips_that_ran():
    t = {"devices": {
        "/device:TPU:0": {"ops": [("a", 0, 4 * S)], "modules": []},
        "/device:TPU:1": {"ops": [("a", 0, 2 * S)], "modules": []},
        "/device:TPU:2": {"ops": [], "modules": []},
    }, "host": [("bench/traced", 0.0, 4 * S)]}
    r = tr.reduce(t, "a")
    assert r["chips"] == 2 and r["busy_s"] == pytest.approx(3.0)


def test_host_span_names_the_reduction_reads():
    assert tr.HOST_SPAN.match("bench/submit")
    assert tr.HOST_SPAN.match("bls/agg_fast_verify_msm_idx/b64")
    assert not tr.HOST_SPAN.match("PjitFunction(fn)")
