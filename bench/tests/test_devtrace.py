"""The trace reduction: busy and idle time, time per program, idle gaps
named by the host span they fell in; then on a small recorded trace."""

import pathlib

import pytest

import devtrace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_union_counts_overlaps_once():
    assert devtrace.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert devtrace.union_ns([(20, 30), (0, 10), (2, 3)]) == 20
    assert devtrace.union_ns([]) == 0
    assert devtrace.merged([(5, 6), (0, 2), (1, 3)]) == [[0, 3], [5, 6]]


def test_time_by_name_groups_programs():
    ev = [("jit_sgd_step(1)", 0, 2_000_000), ("jit_sgd_step(1)", 5, 1_000_000),
          ("jit_predict(9)", 9, 500_000)]
    got = devtrace.time_by_name(ev, devtrace.module_name)
    assert got["jit_sgd_step"] == [pytest.approx(3e-3), 2]
    assert got["jit_predict"] == [pytest.approx(5e-4), 1]


def test_idle_gaps_named_by_innermost_span():
    busy = [(0, 10), (40, 50), (55, 60), (100, 110)]
    spans = [("train", 0, 120), ("drain", 30, 52), ("mask", 70, 95)]
    gaps = devtrace.idle_gaps(busy, spans, k=2)
    # the longest gap (60..100, middle 80) fell in the mask span, the next
    # (10..40, middle 25) only in train
    assert [g[0] for g in gaps] == ["mask", "train"]
    assert gaps[0][1] == pytest.approx(40e-9)
    assert devtrace.idle_gaps(busy, [], k=1)[0][0] == "(no bench span)"


def test_reduce_averages_devices():
    dev0 = ([("jit_a(1)", 0, 100)], [("op", 0, 60), ("op", 80, 20)])
    dev1 = ([("jit_a(1)", 0, 100)], [("op", 0, 100)])
    s = devtrace.reduce([dev0, dev1], [], window_s=1e-6)
    assert s.busy_s == pytest.approx(90e-9)
    assert s.modules["jit_a"] == [pytest.approx(100e-9), 2]
    assert s.devices == 2


def test_window_span_cuts_everything():
    dev = ([("jit_a(1)", 0, 100), ("jit_b(2)", 150, 100)], [])
    spans = [("window", 50, 200), ("train", 0, 300)]
    s = devtrace.reduce([dev], spans, window_s=9.0)
    assert s.window_s == pytest.approx(150e-9)
    assert s.busy_s == pytest.approx(100e-9)       # 50..100 and 150..200
    assert s.modules["jit_b"] == [pytest.approx(50e-9), 1]
    assert s.top_ops[0][0] in ("jit_a", "jit_b")    # programs where no ops
    assert s.idle_gaps == [["train", pytest.approx(50e-9)]]


def test_recorded_trace():
    """A trace recorded on one v5e chip with the benchmark's profiler
    options: two ``predict`` runs, a fold and a DP release, each inside a
    ``bench.`` annotation."""
    path = DATA / "v5e_small.xplane.pb"
    devices, spans = devtrace.read_xplane(str(path))
    s = devtrace.reduce(devices, spans, window_s=1.0)
    assert s.devices == 1
    assert s.modules["jit_predict"][1] == 2
    assert s.modules["jit_agg_tiled"][1] == 1
    assert s.modules["jit_dp_clip_noise_tiled"][1] == 1
    assert 0 < s.busy_s < 1.0
    names = {n for n, _, _ in spans}
    assert {"predict", "drain"} <= names
    total = sum(v[0] for v in s.modules.values())
    assert s.busy_s <= total + 1e-9
