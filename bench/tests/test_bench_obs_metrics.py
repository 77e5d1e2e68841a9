"""The serving cell's readers of the program's own spans (`repro.obs`),
on hand-built records and a hand-built trace."""
import collections
import sys

import pytest

from bench import harness
from bench import trace as T

import repro
from repro import obs

E = T.Event
R = obs.Record

OBS_READERS = ("engine.wait_ms.serve", "engine.host_ms.serve",
               "search.readback_ms.serve", "search.expand_util.serve",
               "engine.gc_ms.serve")


def _reader(name):
    return harness.load_reader(harness.BENCH_DIR / "metrics" / f"{name}.py")


class _Run:
    def __init__(self, trace=None):
        self.trace, self.counters, self.config = trace, {}, {}


def _records():
    """Two query steps, a mutation step and two collections (seconds)."""
    return [
        R("ann_engine.step", 1, None, 0.0, 0.1, {"kind": "query"}),
        R("ann_engine.form", 2, 1, 0.0, 0.002,
          {"n_real": 3, "bucket": 4, "wait_s": [0.01, 0.02, 0.03]}),
        R("worker.dispatch", 3, 1, 0.002, 0.004),
        R("worker.readback", 4, 1, 0.004, 0.094,
          {"n_expanded": [10, 20, 30, 40]}),
        R("ann_engine.fanout", 5, 1, 0.094, 0.1),
        R("host.gc", 6, None, 0.05, 0.06),
        R("ann_engine.step", 7, None, 0.2, 0.25, {"kind": "query"}),
        R("ann_engine.form", 8, 7, 0.2, 0.201,
          {"n_real": 1, "bucket": 1, "wait_s": [0.05]}),
        R("worker.dispatch", 9, 7, 0.201, 0.21),
        R("worker.readback", 10, 7, 0.21, 0.24, {"n_expanded": [5]}),
        R("ann_engine.fanout", 11, 7, 0.24, 0.25),
        R("ann_engine.step", 12, None, 0.3, 0.35, {"kind": "mutation"}),
        R("host.gc", 13, None, 0.34, 0.40),
        R("worker.readback", 14, None, 0.5, None),         # still open
    ]


@pytest.fixture
def ring(monkeypatch):
    ring = collections.deque(maxlen=obs.RING)
    monkeypatch.setattr(obs, "_ring", ring)
    return ring


def test_engine_readers_on_hand_built_records(ring):
    ring.extend(_records())
    run = _Run()
    # waits 10, 20, 30 and 50 ms
    assert _reader("engine.wait_ms.serve")(run) == pytest.approx(27.5)
    # query steps less their read-back: 100 - 90 and 50 - 30 ms
    assert _reader("engine.host_ms.serve")(run) == pytest.approx(15.0)
    assert _reader("search.readback_ms.serve")(run) == pytest.approx(60.0)
    # (10 + 20 + 30) / (4 * 40) and 5 / (1 * 5)
    assert _reader("search.expand_util.serve")(run) == pytest.approx(
        100 * (0.375 + 1.0) / 2)
    # 10 ms and the 10 ms of the second collection inside [0, 0.35] s
    assert _reader("engine.gc_ms.serve")(run) == pytest.approx(20 / 0.35)


def test_expand_util_skips_batches_without_counts(ring):
    recs = _records()
    recs[3] = R("worker.readback", 4, 1, 0.004, 0.094)    # no n_expanded
    ring.extend(recs)
    assert _reader("search.expand_util.serve")(_Run()) == pytest.approx(100.0)


def test_gc_share_is_zero_when_no_collection_ran(ring):
    ring.extend(r for r in _records() if r.name != "host.gc")
    assert _reader("engine.gc_ms.serve")(_Run()) == 0.0


def test_idle_inside_engine_steps_on_a_hand_built_trace():
    # ns: device gaps (10,30) (50,80) (90,100) = 60; steps cover 20 + 10
    ops = [E("fusion.1", 0, 10), E("fusion.2", 30, 50), E("fusion.3", 80, 90)]
    host = [E(T.WINDOW_SPAN, 0, 100), E("ann_engine.step", 0, 40),
            E("worker.readback", 5, 35), E("ann_engine.step", 60, 70),
            E("engine.step", 0, 75)]
    run = _Run(T.Trace([ops], host, (0, 100)))
    assert _reader("device.idle_in_step.serve")(run) == pytest.approx(50.0)
    assert _reader("device.idle.serve")(run) == pytest.approx(60.0)


def test_idle_inside_steps_reads_nothing_without_steps_or_trace():
    host = [E(T.WINDOW_SPAN, 0, 100), E("engine.step", 0, 40)]
    run = _Run(T.Trace([[E("fusion.1", 0, 10)]], host, (0, 100)))
    assert _reader("device.idle_in_step.serve")(run) is None
    assert _reader("device.idle_in_step.serve")(_Run()) is None


def test_readers_return_nothing_without_records(ring):
    for name in OBS_READERS:
        assert _reader(name)(_Run()) is None, name


def test_readers_return_nothing_without_the_program_module(ring, monkeypatch):
    ring.extend(_records())
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(repro, "obs")
    for name in OBS_READERS:
        assert _reader(name)(_Run()) is None, name


def test_span_check_crosschecks_on_hand_built_records(ring):
    from bench.span_check import crosschecks
    ring.extend(_records())
    ops = [E("fusion.1", 0, 10), E("fusion.2", 30, 50), E("fusion.3", 80, 90)]
    host = [E(T.WINDOW_SPAN, 0, 100), E("ann_engine.step", 0, 40),
            E("ann_engine.step", 60, 70)]
    run = _Run(T.Trace([ops], host, (0, 100)))
    # the second call's recount disagrees with the program's count
    run.counters.update(calls=[[0.0, 0.1, 4], [0.2, 0.25, 1]],
                        fresh_by_call={0: (60, 3), 1: (6, 1)})
    out = crosschecks(run)
    assert (out["query_steps"], out["worker_calls"], out["calls_in_steps"]) == (2, 2, 2)
    # dispatch + readback: 2 + 90 and 9 + 30 ms
    assert out["dispatch_readback_ms"] == pytest.approx(65.5)
    assert out["n_expanded"] == {"calls": 2, "equal": 1,
                                 "recount_total": 66, "program_total": 65}
    # idle 60 of 100 ns: 30 inside the two steps, 30 between them
    assert out["idle_in_steps_pct"] == pytest.approx(30.0)
    assert out["idle_between_steps_pct"] == pytest.approx(30.0)
    longest = out["longest_step"]
    assert longest["ms"] == pytest.approx(100.0)
    assert longest["worker.readback"] == pytest.approx(90.0)
    assert longest["gc_ms"] == pytest.approx(10.0)
    assert (longest["max_n_expanded"], longest["median_max_n_expanded"]) == (40, 22.5)


def test_span_check_crosschecks_are_null_without_records(ring):
    from bench.span_check import crosschecks
    run = _Run()
    run.counters.update(calls=[[0.0, 0.1, 4]], fresh_by_call={0: (60, 3)})
    out = crosschecks(run)
    assert (out["query_steps"], out["worker_calls"], out["calls_in_steps"]) == (0, 1, 0)
    for key in ("dispatch_readback_ms", "n_expanded", "idle_in_steps_pct",
                "idle_between_steps_pct", "longest_step"):
        assert out[key] is None, key
