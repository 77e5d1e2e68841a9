"""Continuous-batching ANN engine suite (serve/ann_engine.py, DESIGN.md §12).

Two tiers in one module:

* **scheduler units** — a fake clock + fake worker make every scheduling
  decision deterministic on CPU: bucket selection and padding, admission
  under backlog, mutation-interleave ordering under the quantum policy,
  and the nearest-rank p50/p99 math on a hand-computed latency trace;
* **parity** — the acceptance contract: engine-batched search results are
  BITWISE-identical to direct `core/search` calls for the same request
  set (mixed k/ef/filtered, fp32 and int8+rescore, dense and hashed
  visited, grouped+padded into pow2 buckets), and the dynamic path equals
  a twin DynamicIndex receiving the same mutations directly.

The span tier checks what the engine records while a profiler session
captures (`repro.obs`): the step, form and fan-out records with a fake
worker, and the real worker's `n_expanded`.

Runs in BOTH CI legs (kernel_parity marker): sizes stay interpret-safe.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import grnnd
from repro.core import labels as lab
from repro.core import vecstore
from repro.core.dynamic import DynamicConfig, DynamicIndex
from repro.core.pools import Pool
from repro.core.search import medoid, search
from repro.serve.ann_engine import (
    AnnEngine,
    DynamicWorker,
    EngineConfig,
    EngineSaturated,
    StaticWorker,
    bucket_q,
    normalize_ef,
    percentile,
    synth_trace,
)

pytestmark = pytest.mark.kernel_parity

N, D, NL = 192, 16, 16
CFG = grnnd.GRNNDConfig(s=8, r=16, t1=2, t2=3, pairs_per_vertex=16)


# ------------------------------------------------------------------ fakes


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class FakeWorker:
    """Deterministic worker: ids encode the query's first component, so a
    request's result proves which row of which batch served it; each call
    advances the fake clock by `service` seconds."""

    def __init__(self, clock, service=1.0):
        self.clock = clock
        self.service = service
        self.calls = []

    def search_batch(self, q, *, k, ef, fwords=None):
        self.calls.append((q.shape, k, ef, None if fwords is None else fwords.shape))
        self.clock.advance(self.service)
        ids = q[:, 0].astype(np.int32)[:, None] + np.arange(k, dtype=np.int32)
        return ids, ids.astype(np.float32)

    def apply_mutation(self, mut):
        self.clock.advance(self.service)


def fake_engine(**cfg_kw):
    clk = FakeClock()
    w = FakeWorker(clk)
    return AnnEngine(w, EngineConfig(**cfg_kw), clock=clk), w, clk


def vec(tag, d=4):
    v = np.zeros(d, np.float32)
    v[0] = tag
    return v


# -------------------------------------------------------- scheduler units


class TestScheduler:
    def test_bucket_rounding(self):
        assert [bucket_q(n) for n in (1, 2, 3, 4, 5, 8, 9)] == [1, 2, 4, 4, 8, 8, 16]

    def test_bucket_selection_pads_to_pow2(self):
        eng, w, _ = fake_engine(max_batch=8, ef_menu=(48,))
        for i in range(5):
            eng.submit(vec(i), k=5, ef=48)
        eng.run()
        # 5 real requests -> one padded (8, D) batch, occupancy 5/8
        assert w.calls == [((8, 4), 16, 48, None)]
        assert eng.log == [("query", (8, 48, False), 5)]
        assert eng.stats().mean_occupancy == pytest.approx(5 / 8)
        for i in range(5):
            assert eng.take_result(i).ids[0] == i

    def test_grouping_by_ef_preserves_fifo_within_group(self):
        eng, w, _ = fake_engine(max_batch=8, ef_menu=(32, 48))
        order = [32, 48, 32, 48, 48]
        for i, ef in enumerate(order):
            eng.submit(vec(i), k=5, ef=ef)
        eng.run()
        # head-of-line grouping: all ef=32 first (rids 0, 2), then ef=48
        assert eng.log == [("query", (2, 32, False), 2), ("query", (4, 48, False), 3)]
        for i in range(5):
            assert eng.take_result(i).ids[0] == i

    def test_filtered_and_unfiltered_never_share_a_batch(self):
        eng, w, _ = fake_engine(max_batch=8, ef_menu=(48,))
        fw = np.ones(1, np.int32)
        eng.submit(vec(0), k=5, ef=48)
        eng.submit(vec(1), k=5, ef=48, filter_words=fw)
        eng.submit(vec(2), k=5, ef=48)
        eng.run()
        assert [e[1] for e in eng.log] == [(2, 48, False), (1, 48, True)]
        assert w.calls[0][3] is None and w.calls[1][3] == (1, 1)

    def test_admission_rejects_past_max_pending(self):
        eng, _, _ = fake_engine(max_pending=4, max_batch=4, ef_menu=(48,))
        for i in range(4):
            eng.submit(vec(i), k=5, ef=48)
        with pytest.raises(EngineSaturated):
            eng.submit(vec(9), k=5, ef=48)
        assert eng.stats().n_rejected == 1
        eng.run()  # drain frees capacity; admission recovers
        eng.submit(vec(5), k=5, ef=48)
        assert eng.pending_queries == 1

    def test_mutation_interleave_quantum(self):
        # both queues backed up: 2 query batches per mutation drain, and a
        # mutation never waits for the query queue to empty (not lockstep)
        eng, _, _ = fake_engine(max_batch=1, query_quantum=2, ef_menu=(48,))
        for i in range(5):
            eng.submit(vec(i), k=5, ef=48)
        eng.submit_insert(np.zeros((3, 4), np.float32))
        eng.submit_delete(np.arange(2))
        eng.run()
        kinds = [(e[0], e[2] if e[0] == "mutation" else e[1][0]) for e in eng.log]
        assert [e[0] for e in eng.log] == [
            "query",
            "query",
            "mutation",
            "query",
            "query",
            "mutation",
            "query",
        ], kinds
        assert eng.stats().n_mutations == 5  # 3 inserted + 2 deleted items

    def test_mutations_run_immediately_on_idle_queue(self):
        eng, _, _ = fake_engine(query_quantum=4, ef_menu=(48,))
        eng.submit_insert(np.zeros((2, 4), np.float32))
        assert eng.step() and eng.log == [("mutation", "insert", 2)]

    def test_percentile_nearest_rank(self):
        assert percentile([1, 2, 3, 4], 50) == 2
        assert percentile([1, 2, 3, 4], 99) == 4
        assert percentile([7], 50) == 7
        assert percentile([], 99) == 0.0

    def test_stats_on_hand_computed_trace(self):
        # submit at t=0,1,2,3; service 1s; max_batch=1 -> completions at
        # t=4,5,6,7 -> latencies [4,4,4,4]; occupancy 1.0; window 7s
        eng, w, clk = fake_engine(max_batch=1, ef_menu=(48,))
        for i in range(4):
            eng.submit(vec(i), k=5, ef=48)
            clk.advance(1.0)
        eng.run()
        s = eng.stats()
        assert s.n_completed == 4
        assert [eng.take_result(i).latency for i in range(4)] == [5.0, 5.0, 5.0, 5.0]
        assert s.p50_ms == pytest.approx(5000.0) and s.p99_ms == pytest.approx(5000.0)
        assert s.qps == pytest.approx(4 / 8.0)
        assert s.mean_occupancy == 1.0
        assert s.n_buckets == 1 and s.bucket_runs == {(1, 48, False): 4}

    def test_ef_normalization(self):
        cfg = EngineConfig(ef_menu=(32, 64), overfetch=4)
        assert normalize_ef(cfg, 10, 20, False) == 32  # menu round-up
        assert normalize_ef(cfg, 10, 20, True) == 64  # over-fetch floor 40 -> 64
        assert normalize_ef(cfg, 10, 200, False) == 200  # beyond menu: exact
        assert normalize_ef(EngineConfig(ef_menu=()), 10, 20, False) == 20

    def test_reset_stats_keeps_bucket_set(self):
        eng, _, _ = fake_engine(max_batch=4, ef_menu=(48,))
        eng.submit(vec(0), k=5, ef=48)
        eng.run()
        eng.reset_stats()
        s = eng.stats()
        assert s.n_completed == 0 and s.bucket_runs == {}
        assert s.n_buckets == 1  # traces compiled since startup survive

    def test_synth_trace_deterministic_and_interleaved(self):
        rng1, rng2 = np.random.default_rng(7), np.random.default_rng(7)
        q = np.zeros((6, 4), np.float32)
        churn = np.zeros((2, 3, 4), np.float32)
        kw = dict(offered_qps=100.0, k_choices=(5, 10), ef_choices=(32, 48))
        tr1 = synth_trace(rng1, q, mutation_every=3, churn_vectors=churn, **kw)
        tr2 = synth_trace(rng2, q, mutation_every=3, churn_vectors=churn, **kw)
        assert [e.kind for e in tr1] == [
            "query",
            "query",
            "query",
            "insert",
            "delete_oldest",
            "query",
            "query",
            "query",
            "insert",
            "delete_oldest",
        ]
        assert [e.t for e in tr1] == [e.t for e in tr2]
        assert all(a <= b for a, b in zip([e.t for e in tr1], [e.t for e in tr1][1:]))


# ----------------------------------------------------------------- parity


@pytest.fixture(scope="module")
def built():
    x = jax.random.normal(jax.random.PRNGKey(0), (N, D), jnp.float32)
    pool = grnnd.build_graph(jax.random.PRNGKey(1), x, CFG)
    vlab = jax.random.randint(jax.random.PRNGKey(5), (N,), 0, NL)
    return x, pool, lab.encode_labels(vlab, NL)


@pytest.fixture(scope="module")
def requests():
    q = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (10, D), jnp.float32))
    fw = np.asarray(lab.random_query_filters(jax.random.PRNGKey(3), 10, NL, 0.4))
    # mixed k/ef/filtered, chosen so the admission-normalized ef equals the
    # requested ef (ef >= overfetch*k and ef in the menu): the direct call
    # below is then literally `search(..., k=k, ef=ef)` on the same numbers.
    # The (32, unfiltered) group gets 5 members -> an (8,)-bucket with 3
    # pad rows, so the padding-invisibility claim is actually exercised.
    specs = [([5, 10][i % 2], [32, 48][(i // 2) % 2], i % 3 == 0) for i in range(10)]
    return q, fw, specs


class TestEngineParity:
    @pytest.mark.parametrize(
        "precision,visited",
        [("fp32", "dense"), ("fp32", "hashed"), ("int8", "dense")],
    )
    def test_static_engine_bitwise_equals_direct(self, built, requests, precision, visited):
        x, pool, ls = built
        q, fw, specs = requests
        xt = x if precision == "fp32" else vecstore.encode(x, precision)
        rescore = None if precision == "fp32" else x
        entry = medoid(xt)
        cap = 4 * N if visited == "hashed" else None
        worker = StaticWorker(
            xt,
            pool.ids,
            entry=entry,
            visited=visited,
            visited_cap=cap,
            rescore=rescore,
            labels=ls,
        )
        eng = AnnEngine(worker, EngineConfig(ef_menu=(32, 48), max_batch=8))
        rids = [
            eng.submit(q[i], k=k, ef=ef, filter_words=fw[i] if filt else None)
            for i, (k, ef, filt) in enumerate(specs)
        ]
        eng.run()
        # grouping + pow2 padding actually happened (not 1-request batches)
        assert any(key[0] > n_real for (_, key, n_real) in eng.log)
        for i, (k, ef, filt) in enumerate(specs):
            res = eng.take_result(rids[i])
            direct = search(
                xt,
                pool.ids,
                jnp.asarray(q[i : i + 1]),
                k=k,
                ef=ef,
                entry=entry,
                visited=visited,
                visited_cap=cap,
                rescore=rescore,
                labels=ls if filt else None,
                filter=jnp.asarray(fw[i : i + 1]) if filt else None,
            )
            np.testing.assert_array_equal(res.ids, np.asarray(direct.ids)[0])
            np.testing.assert_array_equal(res.dists, np.asarray(direct.dists)[0])
            if filt:
                assert lab.predicate_fraction(res.ids[None], fw[i : i + 1], ls.words) == 1.0

    def test_static_engine_equals_one_direct_batched_call(self, built, requests):
        # the other grouping extreme: all 9 requests in ONE direct Q=9 call
        # (same ef/k) must also match — Q-composition invariance end to end
        x, pool, _ = built
        q, _, _ = requests
        entry = medoid(x)
        worker = StaticWorker(x, pool.ids, entry=entry)
        eng = AnnEngine(worker, EngineConfig(ef_menu=(48,), max_batch=4))
        rids = [eng.submit(q[i], k=10, ef=48) for i in range(9)]
        eng.run()
        assert len([e for e in eng.log if e[0] == "query"]) == 3  # 4+4+1
        direct = search(x, pool.ids, jnp.asarray(q), k=10, ef=48, entry=entry)
        for i, rid in enumerate(rids):
            res = eng.take_result(rid)
            np.testing.assert_array_equal(res.ids, np.asarray(direct.ids)[i])
            np.testing.assert_array_equal(res.dists, np.asarray(direct.dists)[i])

    def test_dynamic_engine_matches_twin_index(self, built, requests):
        # engine-scheduled insert -> delete_oldest -> queries equals a twin
        # DynamicIndex receiving the identical mutations directly (label
        # space): mutation routing through the engine is semantics-free
        x, pool, _ = built
        q, _, _ = requests
        cfg = DynamicConfig(refine_rounds=1)
        mk = lambda: DynamicIndex(x, Pool(pool.ids, pool.dists), cfg)  # noqa: E731
        idx_eng, idx_ref = mk(), mk()
        xs = np.asarray(jax.random.normal(jax.random.PRNGKey(9), (8, D), jnp.float32))

        eng = AnnEngine(DynamicWorker(idx_eng), EngineConfig(ef_menu=(48,), max_batch=8))
        eng.submit_insert(xs)
        eng.submit_delete_oldest(4)
        eng.run()  # mutations execute first (empty query queue)
        rids = [eng.submit(q[i], k=10, ef=48) for i in range(9)]
        eng.run()

        idx_ref.insert(jnp.asarray(xs))
        live = idx_ref.labels[: idx_ref.size][np.asarray(idx_ref.valid[: idx_ref.size])]
        idx_ref.delete(np.sort(live)[:4])
        direct = idx_ref.search(jnp.asarray(q), k=10, ef=48, overfetch=1)
        for i, rid in enumerate(rids):
            res = eng.take_result(rid)
            np.testing.assert_array_equal(res.ids, np.asarray(direct.ids)[i])
            np.testing.assert_array_equal(res.dists, np.asarray(direct.dists)[i])
        assert eng.stats().n_mutations == 12


# ------------------------------------------------------------------ spans


@pytest.fixture
def capturing(monkeypatch):
    """Record `repro.obs` spans into a fresh ring, as if a profiler
    session captured."""
    monkeypatch.setattr(obs, "_ring", collections.deque(maxlen=obs.RING))
    monkeypatch.setattr(obs, "_capturing", lambda: True)


class TestEngineSpans:
    def test_step_form_and_fanout_records(self, capturing):
        # submits at t=0,1,2; the batch starts at t=2.5; service 1s
        eng, w, clk = fake_engine(max_batch=8, ef_menu=(48,))
        for i in range(3):
            eng.submit(vec(i), k=5, ef=48)
            clk.advance(1.0)
        clk.advance(-0.5)
        assert eng.step()
        (step,) = obs.records("ann_engine.step")
        (form,) = obs.records("ann_engine.form")
        (fanout,) = obs.records("ann_engine.fanout")
        assert step.parent is None
        assert step.counts == {"kind": "query"}
        assert form.parent == step.id and fanout.parent == step.id
        assert form.counts == {
            "n_real": 3,
            "bucket": 4,
            "wait_s": [2.5, 1.5, 0.5],
        }
        assert form.t1 <= fanout.t0

    def test_groups_split_into_one_record_per_batch(self, capturing):
        eng, w, _ = fake_engine(max_batch=2, ef_menu=(32, 48))
        for i, ef in enumerate((32, 48, 32, 32)):
            eng.submit(vec(i), k=5, ef=ef)
        assert eng.run() == 3
        forms = obs.records("ann_engine.form")
        # batches [0, 2] (ef 32), [1] (ef 48), [3] (ef 32); each call takes 1 s
        assert [c[2] for c in w.calls] == [32, 48, 32]
        assert [f.counts["wait_s"] for f in forms] == [[0.0, 0.0], [1.0], [2.0]]
        assert [(f.counts["n_real"], f.counts["bucket"]) for f in forms] == [
            (2, 2),
            (1, 1),
            (1, 1),
        ]
        steps = obs.records("ann_engine.step")
        assert [f.parent for f in forms] == [s.id for s in steps]

    def test_mutation_steps_are_recorded_by_kind(self, capturing):
        eng, _, _ = fake_engine(max_batch=4, ef_menu=(48,))
        eng.submit_delete_oldest(2)
        eng.submit(vec(0), k=5, ef=48)
        assert eng.run() == 2
        assert not eng.step()  # an idle step is no scheduling decision
        kinds = [s.counts["kind"] for s in obs.records("ann_engine.step")]
        assert sorted(kinds) == ["mutation", "query"]
        assert len(obs.records("ann_engine.form")) == 1

    def test_nothing_is_recorded_without_a_profiler_session(self, monkeypatch):
        monkeypatch.setattr(obs, "_ring", collections.deque(maxlen=obs.RING))
        eng, _, _ = fake_engine(max_batch=4, ef_menu=(48,))
        for i in range(3):
            eng.submit(vec(i), k=5, ef=48)
        eng.run()
        assert obs.records() == []

    def test_static_worker_records_the_n_expanded_of_the_padded_batch(
        self, built, requests, capturing
    ):
        x, pool, _ = built
        q, _, _ = requests
        entry = medoid(x)
        worker = StaticWorker(x, pool.ids, entry=entry)
        eng = AnnEngine(worker, EngineConfig(ef_menu=(48,), max_batch=8))
        for i in range(5):
            eng.submit(q[i], k=10, ef=48)
        eng.run()
        (step,) = obs.records("ann_engine.step")
        (dispatch,) = obs.records("worker.dispatch")
        (readback,) = obs.records("worker.readback")
        assert dispatch.parent == step.id and readback.parent == step.id
        assert dispatch.t1 <= readback.t0
        # the same padded batch the engine formed: 5 real rows, 3 repeats
        padded = np.concatenate([q[:5], np.repeat(q[4:5], 3, axis=0)])
        direct = search(x, pool.ids, jnp.asarray(padded), k=10, ef=48, entry=entry)
        assert readback.counts["n_expanded"] == np.asarray(direct.n_expanded).tolist()
        assert len(readback.counts["n_expanded"]) == 8

    @pytest.mark.parametrize("kind", ["static", "dynamic"])
    def test_recorded_read_back_returns_what_the_unrecorded_one_does(
        self, built, requests, monkeypatch, kind
    ):
        # while capturing, the read-back fetches ids, distances and
        # n_expanded in one transfer; the answers must not change
        x, pool, _ = built
        q, _, _ = requests

        def answers():
            if kind == "static":
                worker = StaticWorker(x, pool.ids, entry=medoid(x))
            else:
                idx = DynamicIndex(x, Pool(pool.ids, pool.dists), DynamicConfig(refine_rounds=1))
                worker = DynamicWorker(idx)
            eng = AnnEngine(worker, EngineConfig(ef_menu=(48,), max_batch=8))
            rids = [eng.submit(q[i], k=10, ef=48) for i in range(6)]
            eng.run()
            return [eng.take_result(r) for r in rids]

        monkeypatch.setattr(obs, "_ring", collections.deque(maxlen=obs.RING))
        plain = answers()
        assert obs.records() == []
        monkeypatch.setattr(obs, "_capturing", lambda: True)
        recorded = answers()
        (readback,) = obs.records("worker.readback")
        assert len(readback.counts["n_expanded"]) == 8
        for a, b in zip(plain, recorded):
            np.testing.assert_array_equal(a.ids, b.ids)
            np.testing.assert_array_equal(a.dists, b.dists)
