"""The reduction from a device trace to numbers, on hand-built traces."""
import importlib

import pytest

from bench import harness, roofline
from bench import trace as T

E = T.Event


def _trace():
    # ns: ops at [0,10) [10,30) and two overlapping sorts [40,50) [45,55)
    ops = [E("fusion.1", 0, 10, "%fusion.1 = f32[8]{0} fusion()"),
           E("rng_round_pallas.3", 10, 30, "%rng_round_pallas.3 = (s32[8])"),
           E("sort.3", 40, 50), E("sort.4", 45, 55), E("late", 120, 130)]
    host = [E(T.WINDOW_SPAN, 0, 100), E("build", 0, 60),
            E("replay.wait", 60, 100)]
    return T.Trace([ops], host, (0, 100))


def test_busy_union_and_idle_share():
    t = _trace()
    assert T.union_ns(t.devices[0], 0, 100) == 45
    assert t.busy_s() == pytest.approx(45e-9)
    assert t.window_s == pytest.approx(100e-9)
    assert t.idle_share() == pytest.approx(0.55)


def test_busy_is_clipped_to_the_window_and_averaged_over_chips():
    t = _trace()
    t.devices.append([E("all", 0, 1000)])
    assert t.busy_s() == pytest.approx((45 + 100) / 2 * 1e-9)


def test_kernel_time_by_name():
    t = _trace()
    assert t.op_seconds(r"^sort") == (pytest.approx(20e-9), 2)
    assert t.op_seconds(r"^rng_round_pallas") == (pytest.approx(20e-9), 1)
    assert t.op_seconds(r"nothing") == (0, 0)


def test_idle_gaps_are_named_by_the_spans_that_cover_them():
    t = _trace()
    t.host.append(E("D2H Dispatch", 32, 38))
    assert T.gaps_ns(t.devices[0], 0, 100) == [(30, 40), (55, 100)]
    b = T.breakdown(t, {"build", "replay.wait"})
    assert b["idle_gaps"] == [["replay.wait", pytest.approx(45e-9)],
                              ["build/D2H Dispatch", pytest.approx(10e-9)]]
    assert b["device_ops"][0] == ["rng_round_pallas.3 (s32[8])",
                                  pytest.approx(20e-9)]


def test_breakdown_counts_leaf_ops_not_the_loops_around_them():
    loop = E("while.1", 0, 100, "%while.1 = (s32[]) while()")
    body = [E("fusion.2", 0, 40, "%fusion.2 = f32[4] fusion()"),
            E("sort.5", 50, 90, "%sort.5 = s32[9] sort()")]
    assert T.leaves([loop] + body) == body
    t = T.Trace([[loop] + body], [E(T.WINDOW_SPAN, 0, 100)], (0, 100))
    names = [n for n, _ in T.breakdown(t)["device_ops"]]
    assert names == ["fusion.2 f32[4]", "sort.5 s32[9]"]
    assert t.busy_s() == pytest.approx(100e-9)


class _Ev:
    def __init__(self, name, start, dur, stats=()):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = list(stats)


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def test_extract_keeps_device_ops_host_spans_and_window():
    prof = _Profile([
        _Plane("/device:TPU:0", [
            _Line("XLA Modules", [_Ev("jit_build", 0, 90)]),
            _Line("XLA Ops", [_Ev("%rng_round_pallas.1 = (s32[8]) "
                                  "custom-call(s32[8] %p)", 5, 10)])]),
        _Plane("/device:TPU:1", [_Line("XLA Ops", [_Ev("x", 0, 1)])]),
        _Plane("/host:CPU", [_Line("python", [
            _Ev(T.WINDOW_SPAN, 2, 50), _Ev("build", 3, 40)])]),
    ])
    t = T.extract(prof, chips=1)
    assert t.window == (2, 52)
    assert [e.name for e in t.devices[0]] == ["rng_round_pallas.1"]
    assert t.op_seconds("^rng_round_pallas") == (pytest.approx(10e-9), 1)
    assert [e.name for e in t.host_spans("build")] == ["build"]
    with pytest.raises(RuntimeError):
        T.extract(_Profile(prof.planes[:2]), chips=1)


def _run(counters, config, trace):
    class R:
        pass
    r = R()
    r.trace, r.counters, r.config = trace, counters, config
    r.device_kind = "TPU v5 lite"
    return r


def _reader(name):
    return harness.load_reader(harness.BENCH_DIR / "metrics" / f"{name}.py")


def test_build_readers():
    t = _trace()
    cfg = {"n": 1000, "d": 128,
           "build": {"r": 48, "pairs_per_vertex": 48}}
    run = _run({"rounds": 5, "update_rounds": 4, "chunk": 512}, cfg, t)
    assert _reader("build.round_ms")(run) == pytest.approx(45e-9 / 5 * 1e3)
    assert _reader("build.sort_share")(run) == pytest.approx(100 * 20 / 45)
    assert _reader("device.idle.build")(run) == pytest.approx(55.0)
    # one rng event: 1 of the 2 chunk calls a round makes (512 + 488 rows)
    peak = roofline.peaks("TPU v5 lite")
    need = sum(roofline.least_seconds(
        *roofline.rng_round(c, 1000, 128, 48, 48), peak) for c in (512, 488))
    assert _reader("rng_round_roofline")(run) == pytest.approx(
        100 * need / 2 / 20e-9)


def test_readers_return_nothing_without_a_trace():
    run = _run({}, {}, None)
    for name in ("build.round_ms", "build.sort_share", "rng_round_roofline",
                 "device.idle.serve", "search_expand_roofline.batch",
                 "search_expand_roofline.serve"):
        assert _reader(name)(run) is None


def test_serving_readers_from_spans_and_counters():
    import numpy as np
    calls = [[1.0, 1.5, 8], [1.5, 2.5, 16]]
    run = _run({"calls": calls, "due": np.array([0.5, 1.0, 1.25]),
                "call_of": np.array([0, 1, -1]), "mean_occupancy": 0.75,
                "n_expanded": [np.array([2, 4]), np.array([0, 0])]}, {}, None)
    assert _reader("search.batch_ms.serve")(run) == pytest.approx(750.0)
    assert _reader("engine.queue_ms.serve")(run) == pytest.approx(500.0)
    assert _reader("engine.occupancy.serve")(run) == pytest.approx(75.0)
    assert _reader("search.expand_util.batch")(run) == pytest.approx(75.0)


def test_every_metric_has_a_reader():
    import json
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert callable(_reader(m["name"])), m["name"]
    importlib.import_module("bench.roofline")
