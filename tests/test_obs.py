"""The span primitive of `repro.obs`: gated on the profiler, nested by a
stack, kept in a bounded ring, and host GC as root records."""

import collections
import gc

import jax
import jax.numpy as jnp
import pytest

from repro import obs


@pytest.fixture(autouse=True)
def fresh_ring(monkeypatch):
    monkeypatch.setattr(obs, "_ring", collections.deque(maxlen=obs.RING))


@pytest.fixture
def capturing(monkeypatch):
    """Record as if a profiler session captured."""
    monkeypatch.setattr(obs, "_capturing", lambda: True)


def test_nothing_is_recorded_without_a_profiler_session():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with obs.span("outer", a=1) as counts:
        assert counts is None
        with obs.span("inner"):
            pass
    gc.collect()
    assert obs.records() == []


def test_records_nest_by_the_enclosing_open_span(capturing):
    with obs.span("step", kind="query") as step:
        step["n_real"] = 3
        with obs.span("form"):
            pass
        with obs.span("readback") as rb:
            with obs.span("leaf"):
                pass
            rb["n"] = 7
    with obs.span("next"):
        pass
    by = {r.name: r for r in obs.records()}
    assert [r.name for r in obs.records()] == ["step", "form", "readback", "leaf", "next"]
    assert by["step"].parent is None and by["next"].parent is None
    assert by["form"].parent == by["step"].id
    assert by["readback"].parent == by["step"].id
    assert by["leaf"].parent == by["readback"].id
    assert by["step"].counts == {"kind": "query", "n_real": 3}
    assert by["readback"].counts == {"n": 7}
    assert all(r.t0 <= r.t1 for r in obs.records())
    assert by["step"].t0 <= by["form"].t0 and by["readback"].t1 <= by["step"].t1
    assert [r.name for r in obs.records("form")] == ["form"]


def test_a_span_closes_and_unwinds_the_stack_on_an_exception(capturing):
    with pytest.raises(ValueError):
        with obs.span("failing"):
            raise ValueError
    with obs.span("after"):
        pass
    failing, after = obs.records()
    assert failing.t1 is not None and after.parent is None


def test_the_ring_is_bounded_and_drops_the_oldest(capturing):
    for i in range(obs.RING + 5):
        with obs.span("s", i=i):
            pass
    assert len(obs.records()) == obs.RING
    # host.gc records may fill some slots, but the newest span is kept
    # and the oldest went first
    recs = obs.records("s")
    assert recs[-1].counts == {"i": obs.RING + 4}
    assert [r.counts["i"] for r in recs] == list(range(recs[0].counts["i"], obs.RING + 5))
    assert recs[0].counts["i"] >= 5


def test_gc_is_a_root_record_that_leaves_the_stack_alone(capturing):
    with obs.span("outer"):
        gc.collect()
        with obs.span("inner"):
            pass
    gcs = obs.records("host.gc")
    outer, inner = obs.records("outer")[0], obs.records("inner")[0]
    assert gcs and all(r.parent is None and r.t1 >= r.t0 and not r.counts for r in gcs)
    assert any(outer.t0 <= r.t0 and r.t1 <= outer.t1 for r in gcs)
    assert inner.parent == outer.id


def test_the_gate_follows_a_real_profiler_session(tmp_path):
    with obs.span("before"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("during", n=1) as counts:
            assert counts == {"n": 1}
            jnp.ones(4).block_until_ready()
            gc.collect()
    finally:
        jax.profiler.stop_trace()
    with obs.span("after") as counts:
        assert counts is None
    gc.collect()
    names = {r.name for r in obs.records()}
    assert names == {"during", "host.gc"}
    (during,) = obs.records("during")
    assert any(during.t0 <= r.t0 and r.t1 <= during.t1 for r in obs.records("host.gc"))
