"""
Elastic execution under preemption + the self-healing serving fleet.

Fit side: `ElasticMeshManager` geometry units (participant grouping,
the divisor shrink rule, regrow), the classic round loop shrinking on
an injected `on_host` preemption and re-growing at a round boundary
with exact outputs, the compacted iterative path riding the same
contract, and a mid-stream PREEMPTED during a BlockFeeder-driven fit
resuming via seek() + re-place on the shrunken mesh with bitwise
coefficients.

Serve side: `ReplicaSet` routing/failover/respawn — kill a replica
mid-traffic with zero failed requests, breaker-tripped replicas drain
and respawn warm (0 compiles), fleet-wide prewarm-before-publish
rollouts.

Satellites: retry jitter opt-in, the injector's targeted
`on_host`/`kill_replica` scenarios, and durable checkpoints for
streamed (ChunkedDataset) searches keyed on the dataset content
digest.
"""

import threading
import warnings

import numpy as np
import pytest

import jax

from skdist_tpu.data import ChunkedDataset
from skdist_tpu.distribute.search import DistGridSearchCV
from skdist_tpu.models import LogisticRegression, SGDClassifier
from skdist_tpu.models.streaming import stream_fit_estimator
from skdist_tpu.parallel import (
    ElasticMeshManager,
    IterativeKernelSpec,
    TPUBackend,
    faults,
)
from skdist_tpu.serve import AllReplicasUnhealthy, ReplicaSet
from skdist_tpu.testing.faultinject import FaultInjector


@pytest.fixture(autouse=True)
def _fresh_fault_state():
    faults.reset_stats()
    yield
    faults.set_injector(None)
    faults.reset_stats()


def _half_groups():
    """group_size putting the device roster into two participants —
    works at both device-count matrix cells (4 and 8)."""
    return max(1, len(jax.devices()) // 2)


def _elastic_backend(**kw):
    return TPUBackend(elastic={"group_size": _half_groups()}, **kw)


def _identity_kernel():
    import jax.numpy as jnp

    def kernel(shared, task):
        return {"v": task["w"] * 2.0 + jnp.sum(shared["X"]) * 0.0}

    return kernel


# ---------------------------------------------------------------------------
# ElasticMeshManager geometry units
# ---------------------------------------------------------------------------

class TestElasticMeshManager:
    def test_participant_grouping_and_probe(self):
        devices = jax.devices()
        gs = _half_groups()
        lost = set()
        mgr = ElasticMeshManager(devices, group_size=gs,
                                 probe=lambda: lost)
        assert mgr.participant_ids == sorted(
            {i // gs for i in range(len(devices))}
        )
        assert not mgr.degraded
        assert mgr.on_preempted() is None  # nothing lost: same extent

    def test_shrink_uses_largest_divisor_of_full_extent(self):
        devices = jax.devices()
        n = len(devices)
        lost = {0}
        mgr = ElasticMeshManager(devices, group_size=1,
                                 probe=lambda: lost)
        mesh = mgr.on_preempted()  # n-1 survivors -> n/2 extent
        assert mesh is not None
        assert mesh.devices.size == n // 2
        assert (n // 2) * 2 == n  # divisor rule: extent divides full
        assert mgr.degraded
        assert mgr.events[-1]["kind"] == "shrink"
        # the lost device is not in the shrunken mesh
        assert devices[0] not in list(mesh.devices.flat)

    def test_regrow_when_capacity_returns(self):
        devices = jax.devices()
        lost = {1}
        mgr = ElasticMeshManager(devices, group_size=_half_groups(),
                                 probe=lambda: lost)
        assert mgr.on_preempted() is not None
        assert mgr.maybe_regrow() is None  # still lost
        lost.clear()
        mesh = mgr.maybe_regrow()
        assert mesh is not None and mesh.devices.size == len(devices)
        assert not mgr.degraded
        kinds = [e["kind"] for e in mgr.events]
        assert kinds == ["shrink", "regrow"]

    def test_cannot_shrink_below_one_task_slot(self):
        devices = jax.devices()
        mgr = ElasticMeshManager(
            devices, group_size=len(devices),
            probe=lambda: {0},  # every participant lost
        )
        with pytest.raises(RuntimeError, match="below one task slot"):
            mgr.on_preempted()

    def test_data_axis_preserved_on_shrink(self):
        devices = jax.devices()
        if len(devices) < 4:
            pytest.skip("needs >= 4 devices for a 2D elastic mesh")
        lost = {len(devices) - 1}
        mgr = ElasticMeshManager(devices, data_axis_size=2,
                                 group_size=1, probe=lambda: lost)
        mesh = mgr.on_preempted()
        assert mesh.axis_names == ("tasks", "data")
        assert mesh.devices.shape[1] == 2


# ---------------------------------------------------------------------------
# classic round loop: shrink on preemption, regrow at a round boundary
# ---------------------------------------------------------------------------

class TestElasticBatchedMap:
    def test_shrink_resume_regrow_exact(self):
        backend = _elastic_backend()
        full = len(backend.devices)
        W = np.arange(8 * full, dtype=np.float32)
        inj = FaultInjector().on_host(1, at_round=2, restore_after=2)
        with inj, warnings.catch_warnings():
            warnings.simplefilter("ignore")
            out = backend.batched_map(
                _identity_kernel(), {"w": W},
                {"X": np.ones((2, 2), np.float32)}, round_size=full,
            )
        np.testing.assert_array_equal(out["v"], W * 2.0)
        snap = faults.snapshot()
        assert snap["elastic_shrinks"] == 1
        assert snap["elastic_regrows"] == 1
        # the salvaged prefix is the two rounds gathered pre-fault
        assert snap["elastic_tasks_salvaged"] == 2 * full
        # back on the full mesh after the boundary regrow
        assert len(backend.devices) == full
        assert ("lost:1" in [k for _o, k in inj.fired])

    def test_shrink_without_restore_stays_degraded(self):
        backend = _elastic_backend()
        full = len(backend.devices)
        W = np.arange(4 * full, dtype=np.float32)
        with FaultInjector().on_host(1, at_round=1), \
                warnings.catch_warnings():
            warnings.simplefilter("ignore")
            out = backend.batched_map(
                _identity_kernel(), {"w": W},
                {"X": np.ones((2, 2), np.float32)}, round_size=full,
            )
        np.testing.assert_array_equal(out["v"], W * 2.0)
        assert backend.elastic.degraded
        assert len(backend.devices) == full // 2

    def test_non_elastic_preemption_contract_unchanged(self):
        backend = TPUBackend()
        assert backend.elastic is None
        W = np.arange(2 * len(backend.devices), dtype=np.float32)
        with FaultInjector().at_round(1, kind="preempt"), \
                warnings.catch_warnings():
            warnings.simplefilter("ignore")
            out = backend.batched_map(
                _identity_kernel(), {"w": W},
                {"X": np.ones((2, 2), np.float32)},
                round_size=len(backend.devices),
            )
        np.testing.assert_array_equal(out["v"], W * 2.0)
        snap = faults.snapshot()
        assert snap["shared_replacements"] == 1
        assert snap["elastic_shrinks"] == 0

    def test_iterative_path_shrinks_on_preemption(self):
        import jax.numpy as jnp

        def init(shared, task):
            return {"v": task["w"] * 2.0 + jnp.sum(shared["X"]) * 0.0,
                    "done": jnp.bool_(True)}

        def step(shared, task, carry):
            return carry

        def fin(shared, task, carry):
            return {"out": carry["v"]}

        def fallback(shared, task):
            return {"out": task["w"] * 2.0 + jnp.sum(shared["X"]) * 0.0}

        spec = IterativeKernelSpec(init, step, fin, ("v",),
                                   fallback=fallback)
        backend = _elastic_backend()
        full = len(backend.devices)
        W = np.arange(3 * full, dtype=np.float32)
        # ordinal 0 is the first finalize round (the slice loop's own
        # dispatches do not consume injector ordinals)
        with FaultInjector().on_host(1, at_round=0), \
                warnings.catch_warnings():
            warnings.simplefilter("ignore")
            out = backend.batched_map_iterative(
                spec, {"w": W}, {"X": np.ones((2, 2), np.float32)},
                round_size=full, cache_key=("te", "elastic-iter"),
            )
        np.testing.assert_array_equal(out["out"], W * 2.0)
        assert faults.snapshot()["elastic_shrinks"] == 1
        assert len(backend.devices) == full // 2


# ---------------------------------------------------------------------------
# streamed fits: mid-stream preemption -> seek + re-place on the
# shrunken mesh, bitwise coefficients
# ---------------------------------------------------------------------------

class TestElasticStreaming:
    @pytest.fixture
    def stream_data(self):
        rng = np.random.RandomState(7)
        X = rng.normal(size=(384, 6)).astype(np.float32)
        y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.int64)
        return X, y, ChunkedDataset.from_arrays(X, y, block_rows=128)

    def test_lbfgs_midstream_preempt_resumes_exactly(self, stream_data):
        """A PREEMPTED mid-stream (the LAST block of the three-block
        first objective pass) must be indistinguishable from a
        preemption before any block ran: seek(0) + re-place on the
        shrunken mesh loses nothing and corrupts nothing — the pass
        re-runs from block 0, so both fits evaluate every pass on the
        shrunken mesh, the same programs on the same inputs — and the
        two runs are BITWISE identical. (A preemption in a LATER pass
        is not: the passes before it ran on the full mesh, and packing
        2 lanes per device re-tiles the backward pass's row
        reductions, which moves low bits — layout variance, not resume
        error. The undisturbed full-mesh run is the tolerance
        reference for the same reason.)"""
        X, y, ds = stream_data
        kw = dict(C=0.8, tol=1e-5, max_iter=50, engine="xla")
        ref = LogisticRegression(**kw)
        stream_fit_estimator(ref, ds, backend=TPUBackend())

        def preempted_fit(at_round):
            backend = _elastic_backend()
            est = LogisticRegression(**kw)
            with FaultInjector().on_host(1, at_round=at_round), \
                    warnings.catch_warnings():
                warnings.simplefilter("ignore")
                stream_fit_estimator(est, ds, backend=backend)
            assert len(backend.devices) == len(jax.devices()) // 2
            return est

        assert ds.n_blocks == 3
        mid = preempted_fit(at_round=2)   # mid-stream: resume path
        start = preempted_fit(at_round=0)  # whole fit on shrunken mesh
        np.testing.assert_array_equal(mid.coef_, start.coef_)
        np.testing.assert_array_equal(mid.intercept_, start.intercept_)
        np.testing.assert_allclose(mid.coef_, ref.coef_,
                                   rtol=1e-3, atol=1e-4)
        # dispatch 3 is block 0 of the SECOND pass (the first line
        # search probe): the initial (f, g) then came from the full
        # mesh, and the fit lands a few ulp away, not on the same bits
        later = preempted_fit(at_round=3)
        np.testing.assert_allclose(
            later.coef_, start.coef_, rtol=0,
            atol=8 * np.spacing(np.abs(start.coef_).max()))
        snap = faults.snapshot()
        assert snap["elastic_shrinks"] == 3
        assert snap["shared_replacements"] >= 3

    def test_sgd_midstream_preempt_resumes_exactly(self, stream_data):
        """SGD epochs as block streams: a mid-epoch PREEMPTED rewinds
        to the epoch-start carry snapshot on the shrunken mesh —
        bitwise-identical to a run whose preemption hit before the
        epoch started (same rewind target, nothing mid-epoch
        survives either way)."""
        X, y, ds = stream_data
        kw = dict(loss="log_loss", max_iter=4, batch_size=64,
                  shuffle=False, tol=None)
        ref = SGDClassifier(**kw)
        stream_fit_estimator(ref, ds, backend=TPUBackend())

        def preempted_fit(at_round):
            backend = _elastic_backend()
            est = SGDClassifier(**kw)
            with FaultInjector().on_host(1, at_round=at_round), \
                    warnings.catch_warnings():
                warnings.simplefilter("ignore")
                stream_fit_estimator(est, ds, backend=backend)
            return est

        mid = preempted_fit(at_round=2)    # mid-epoch 0
        start = preempted_fit(at_round=0)  # before epoch 0's block 0
        np.testing.assert_array_equal(mid.coef_, start.coef_)
        np.testing.assert_allclose(mid.coef_, ref.coef_,
                                   rtol=1e-3, atol=1e-4)
        assert faults.snapshot()["elastic_shrinks"] == 2


# ---------------------------------------------------------------------------
# retry jitter (opt-in decorrelation)
# ---------------------------------------------------------------------------

class TestRetryJitter:
    def test_default_is_jitter_free(self):
        p = faults.RetryPolicy(backoff_ms=10)
        assert p.jitter_ms == 0.0
        assert p.jitter_s() == 0.0
        slept = []
        p2 = faults.RetryPolicy(backoff_ms=10, sleep=slept.append)
        p2.backoff(1)
        assert slept == [p2.delay_s(1)]  # exactly the deterministic delay

    def test_env_knob_and_distribution(self, monkeypatch):
        monkeypatch.setenv("SKDIST_RETRY_JITTER_MS", "40")
        p = faults.RetryPolicy(backoff_ms=10)
        assert p.jitter_ms == 40.0
        draws = [p.jitter_s() for _ in range(64)]
        assert all(0.0 <= d < 0.04 for d in draws)
        assert len(set(draws)) > 1  # actually random

    def test_jitter_rides_on_top_of_backoff(self):
        class FixedRng:
            def uniform(self, lo, hi):
                return hi  # worst case draw

        slept = []
        p = faults.RetryPolicy(backoff_ms=10, jitter_ms=20,
                               sleep=slept.append, rng=FixedRng())
        p.backoff(1)
        assert slept[0] == pytest.approx(0.010 + 0.020)
        # delay_s itself stays deterministic (what logs/tests reason
        # about)
        assert p.delay_s(1) == pytest.approx(0.010)

    def test_malformed_env_falls_back(self, monkeypatch):
        monkeypatch.setenv("SKDIST_RETRY_JITTER_MS", "lots")
        assert faults.RetryPolicy().jitter_ms == 0.0


# ---------------------------------------------------------------------------
# targeted injector scenarios
# ---------------------------------------------------------------------------

class TestTargetedInjection:
    def test_on_host_marks_and_restores(self):
        inj = FaultInjector().on_host(1, at_round=1, restore_after=2)
        with inj:
            assert inj.lost_participants() == set()
            inj.round_dispatched()            # ordinal 0
            with pytest.raises(RuntimeError, match="preempt"):
                inj.round_dispatched()        # ordinal 1: raise + lose
            assert inj.lost_participants() == {1}
            inj.round_dispatched()            # ordinal 2
            assert inj.lost_participants() == {1}
            inj.round_dispatched()            # ordinal 3: restored
            assert inj.lost_participants() == set()
        assert (1, "preempt") in inj.fired
        assert (1, "lost:1") in inj.fired

    def test_on_host_never_restores_by_default(self):
        inj = FaultInjector().on_host(0, at_round=0)
        with inj:
            with pytest.raises(RuntimeError):
                inj.round_dispatched()
            for _ in range(5):
                inj.round_dispatched()
            assert inj.lost_participants() == {0}

    def test_kill_replica_plan_consumed_once(self):
        inj = FaultInjector().kill_replica(2, at_request=3)
        with inj:
            assert inj.replica_kills_due(0) == []
            assert inj.replica_kills_due(3) == [2]
            assert inj.replica_kills_due(3) == []  # consumed
        assert (3, "kill_replica:2") in inj.fired


# ---------------------------------------------------------------------------
# streamed-search durable checkpoints (ChunkedDataset digest)
# ---------------------------------------------------------------------------

class TestChunkedCheckpoints:
    @pytest.fixture
    def search_data(self):
        rng = np.random.RandomState(3)
        X = rng.normal(size=(300, 6)).astype(np.float32)
        y = (X[:, 0] - X[:, 2] > 0).astype(np.int64)
        return X, y, ChunkedDataset.from_arrays(X, y, block_rows=100)

    def _grid(self):
        return DistGridSearchCV(
            LogisticRegression(max_iter=40, engine="xla"),
            {"C": [0.1, 1.0, 10.0]}, cv=3, backend=TPUBackend(),
        )

    def test_content_digest_stable_and_content_sensitive(self, tmp_path):
        rng = np.random.RandomState(0)
        X = rng.normal(size=(200, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int64)
        d1 = ChunkedDataset.from_arrays(X, y, block_rows=64).content_digest()
        d2 = ChunkedDataset.from_arrays(X.copy(), y,
                                        block_rows=64).content_digest()
        assert d1 == d2  # same content, fresh arrays
        X2 = X.copy()
        X2[-1, -1] += 1.0  # tail block moved
        d3 = ChunkedDataset.from_arrays(X2, y,
                                        block_rows=64).content_digest()
        assert d3 != d1
        # embedded labels and weights participate (the streamed search
        # reads them AFTER the signature is computed)
        y2 = y.copy()
        y2[0] = 1 - y2[0]
        assert ChunkedDataset.from_arrays(
            X, y2, block_rows=64).content_digest() != d1
        sw = np.full(len(y), 0.5, np.float32)
        dsw = ChunkedDataset.from_arrays(X, y, sw,
                                         block_rows=64).content_digest()
        assert dsw != d1
        sw2 = sw.copy()
        sw2[0] = 2.0
        assert ChunkedDataset.from_arrays(
            X, y, sw2, block_rows=64).content_digest() != dsw
        # geometry participates: same bytes, different blocking
        d4 = ChunkedDataset.from_arrays(X, y, block_rows=50).content_digest()
        assert d4 != d1
        # a saved+reloaded dataset digests identically (resume after a
        # process kill reopens from disk)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=64)
        ds.save(str(tmp_path / "ds"))
        assert ChunkedDataset.load(
            str(tmp_path / "ds")).content_digest() == d1

    def test_streamed_search_journals_and_resumes(self, search_data,
                                                  tmp_path):
        _X, _y, ds = search_data
        g1 = self._grid()
        g1.fit(ds, checkpoint_dir=str(tmp_path))
        assert faults.snapshot()["checkpoint_hits"] == 0
        faults.reset_stats()
        g2 = self._grid()
        g2.fit(ds, checkpoint_dir=str(tmp_path))
        # every (candidate x fold) task restored from the journal
        assert faults.snapshot()["checkpoint_hits"] == 9
        np.testing.assert_array_equal(
            g1.cv_results_["mean_test_score"],
            g2.cv_results_["mean_test_score"],
        )
        assert g1.best_params_ == g2.best_params_

    def test_changed_dataset_gets_fresh_journal(self, search_data,
                                                tmp_path):
        X, y, ds = search_data
        self._grid().fit(ds, checkpoint_dir=str(tmp_path))
        X2 = X.copy()
        X2[0, 0] += 1.0
        ds2 = ChunkedDataset.from_arrays(X2, y, block_rows=100)
        faults.reset_stats()
        self._grid().fit(ds2, checkpoint_dir=str(tmp_path))
        assert faults.snapshot()["checkpoint_hits"] == 0
        assert len(list(tmp_path.glob("skdist-ckpt-*.jsonl"))) == 2


# ---------------------------------------------------------------------------
# ReplicaSet: routing, failover, respawn, rollout
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fitted_model():
    rng = np.random.RandomState(0)
    X = rng.normal(size=(160, 5)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.int64)
    return LogisticRegression(max_iter=30, engine="xla").fit(X, y), X


def _fleet(n=3, **kw):
    kw.setdefault("max_batch_rows", 64)
    kw.setdefault("max_delay_ms", 1.0)
    return ReplicaSet(n_replicas=n, backend=TPUBackend(), **kw)


class TestReplicaSet:
    def test_rollout_publishes_fleet_wide(self, fitted_model):
        model, X = fitted_model
        with _fleet(2) as rs:
            entries = rs.rollout("clf", model, methods=("predict",))
            assert len(entries) == 2
            out = rs.predict(X[:4], model="clf")
            assert out.shape == (4,)
            st = rs.stats()
            assert st["published"] == ["clf"]
            assert all(r["alive"] for r in st["replicas"])

    def test_kill_mid_traffic_zero_failures_and_respawn(self,
                                                        fitted_model):
        model, X = fitted_model
        with _fleet(3) as rs:
            rs.rollout("clf", model)
            failures, ok = [], [0]
            lock = threading.Lock()

            def worker(tid):
                r = np.random.RandomState(tid)
                for _ in range(30):
                    x = r.normal(size=(3, 5)).astype(np.float32)
                    try:
                        out = rs.predict(x, model="clf", timeout_s=30.0)
                        assert out.shape[0] == 3
                        with lock:
                            ok[0] += 1
                    except Exception as exc:  # noqa: BLE001
                        with lock:
                            failures.append(repr(exc))

            inj = FaultInjector().kill_replica(1, at_request=25)
            with inj:
                threads = [threading.Thread(target=worker, args=(i,))
                           for i in range(4)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
            assert failures == []
            assert ok[0] == 120
            assert (25, "kill_replica:1") in inj.fired
            snap = faults.snapshot()
            assert snap["replica_respawns"] >= 1
            st = rs.stats()
            rep1 = st["replicas"][1]
            assert rep1["alive"] and rep1["generation"] == 1
            # the respawned replica re-entered rotation and served
            assert rep1["engine"]["completed"] > 0
            # warm respawn: nothing compiled after the initial rollout
            assert all(
                r["engine"]["compiles_after_warmup"] == 0
                for r in st["replicas"]
            )
            # p99 bounded: no request rode a respawn/compile stall
            p99 = max(r["engine"]["p99_ms"] or 0.0
                      for r in st["replicas"])
            assert p99 < 5000.0

    def test_dead_replica_heals_explicitly(self, fitted_model):
        model, X = fitted_model
        with _fleet(2) as rs:
            rs.rollout("clf", model)
            rs.kill_replica(0)
            assert not rs.replica(0).alive
            assert rs.heal() == 1
            assert rs.replica(0).alive
            assert rs.replica(0).generation == 1
            out = rs.predict(X[:2], model="clf")
            assert out.shape == (2,)

    def test_respawn_preserves_version_history(self, fitted_model):
        """A respawned replica must hold EVERY published version under
        its original number — version-pinned name@v routing resolves
        the same model on every generation."""
        model, X = fitted_model
        rng = np.random.RandomState(1)
        Xb = rng.normal(size=(120, 5)).astype(np.float32)
        model_b = LogisticRegression(max_iter=30, engine="xla").fit(
            Xb, (Xb[:, 1] > 0).astype(np.int64)
        )
        with _fleet(2) as rs:
            e1 = rs.rollout("clf", model)
            e2 = rs.rollout("clf", model_b)
            assert [e.version for e in e1] == [1, 1]
            assert [e.version for e in e2] == [2, 2]
            ref_v1 = rs.predict(X[:4], model="clf@1")
            rs.kill_replica(0)
            rs.heal()
            # the respawned replica serves BOTH versions, same numbers
            reg = rs.replica(0).engine.registry
            assert reg.versions("clf") == [1, 2]
            np.testing.assert_array_equal(
                np.asarray(
                    reg.get("clf@1").methods["predict"].model.predict(
                        X[:4]
                    )
                ),
                np.asarray(ref_v1),
            )

    def test_request_owned_errors_do_not_failover(self, fitted_model):
        model, _X = fitted_model
        with _fleet(2) as rs:
            rs.rollout("clf", model)
            with pytest.raises(ValueError):
                # wrong width is wrong on every replica
                rs.predict(np.zeros((2, 9), np.float32), model="clf")
            assert faults.snapshot()["replica_failovers"] == 0

    def test_all_replicas_down_is_typed(self, fitted_model):
        model, X = fitted_model
        rs = _fleet(2)
        try:
            rs.rollout("clf", model)
            # kill both and drain the pending-respawn queue empty so
            # nothing can heal lazily mid-request
            rs.kill_replica(0)
            rs.kill_replica(1)
            with rs._lock:
                rs._pending_respawn.clear()
            with pytest.raises(AllReplicasUnhealthy):
                rs.predict(X[:2], model="clf")
        finally:
            rs.close()

    def test_breaker_trip_marks_replica_sick(self, fitted_model):
        model, X = fitted_model
        with _fleet(2, sick_threshold=1) as rs:
            rs.rollout("clf", model)
            # forge a breaker-tripped replica: open the circuit by
            # recording failures directly on replica 0's breaker
            r0 = rs.replica(0)
            spec = r0.engine.registry.get("clf").spec
            for _ in range(3):
                r0.engine._breaker.record_failure(spec, faults.TRANSIENT)
            # traffic keeps succeeding (failover) and replica 0 is
            # marked for drain+respawn on its first CircuitOpen
            for _ in range(8):
                out = rs.predict(X[:2], model="clf", timeout_s=30.0)
                assert out.shape == (2,)
            assert faults.snapshot()["replica_respawns"] >= 1
            assert rs.replica(0).generation >= 1


# ---------------------------------------------------------------------------
# production preemption probes + epoch agreement (PR 12)
# ---------------------------------------------------------------------------

from skdist_tpu.parallel.mesh import (  # noqa: E402 - grouped with its tests
    HeartbeatFileProbe,
    KVStoreHeartbeatProbe,
    MaintenanceEventProbe,
    combine_probes,
)


class _FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


class TestProbes:
    def test_heartbeat_file_probe_beat_and_stale(self, tmp_path):
        clock = _FakeClock()
        probe = HeartbeatFileProbe(tmp_path / "hb", participants=[0, 1],
                                   stale_s=10.0, clock=clock)
        # nothing ever beat: both lost (a worker that never came up)
        assert probe() == {0, 1}
        probe.beat(0)
        probe.beat(1)
        assert probe() == set()
        clock.t += 11.0
        probe.beat(1)  # participant 1 keeps beating, 0 goes silent
        assert probe() == {0}

    def test_kv_probe_without_cluster_reports_all_lost(self):
        probe = KVStoreHeartbeatProbe(participants=[0, 1], stale_s=5.0)
        # no jax.distributed cluster in the test process: no liveness
        # signal exists, so everyone reads as lost (fail-safe)
        assert probe() == {0, 1}

    def test_maintenance_event_probe_holds_reports(self):
        clock = _FakeClock()
        notices = []
        probe = MaintenanceEventProbe(lambda: notices, hold_s=30.0,
                                      clock=clock)
        assert probe() == set()
        notices.append(1)
        assert probe() == {1}
        notices.clear()
        clock.t += 15.0
        assert probe() == {1}  # held past the one-shot notice
        clock.t += 20.0
        assert probe() == set()  # hold expired: presumed back

    def test_combine_probes_unions(self, tmp_path):
        clock = _FakeClock()
        hb = HeartbeatFileProbe(tmp_path / "hb", participants=[0, 1],
                                stale_s=10.0, clock=clock)
        hb.beat(0)
        hb.beat(1)
        maint = MaintenanceEventProbe(lambda: [1], hold_s=60.0,
                                      clock=clock)
        combined = combine_probes(hb, maint)
        assert combined() == {1}
        clock.t += 11.0
        assert combined() == {0, 1}

    def test_injector_heartbeat_probe_leg(self, tmp_path):
        """FaultInjector.with_heartbeat_probe: lost_participants()
        reports the probe's stale participants next to the on_host
        plan — heartbeat-driven loss is expressible without raises."""
        clock = _FakeClock()
        hb = HeartbeatFileProbe(tmp_path / "hb", participants=[0, 1],
                                stale_s=10.0, clock=clock)
        hb.beat(0)
        hb.beat(1)
        inj = FaultInjector().with_heartbeat_probe(hb)
        assert inj.lost_participants() == set()
        clock.t += 11.0
        hb.beat(0)
        assert inj.lost_participants() == {1}

    def test_manager_shrinks_on_heartbeat_probe(self, tmp_path):
        """An ElasticMeshManager wired to a HeartbeatFileProbe shrinks
        around the participant whose file went stale — the production
        probe driving the same geometry the injector scenarios pin."""
        clock = _FakeClock()
        gs = _half_groups()
        probe = HeartbeatFileProbe(tmp_path / "hb", participants=[0, 1],
                                   stale_s=10.0, clock=clock)
        probe.beat(0)
        probe.beat(1)
        mgr = ElasticMeshManager(group_size=gs, probe=probe,
                                 heartbeat=probe)
        assert mgr.on_preempted() is None  # everyone beating: no change
        clock.t += 11.0
        probe.beat(1)  # participant 0 went silent
        mesh = mgr.on_preempted()
        assert mesh is not None and mgr.degraded
        assert all(d.id >= gs for d in mesh.devices.flat)
        probe.beat(0)  # capacity back: next boundary regrows
        assert mgr.maybe_regrow() is not None
        assert not mgr.degraded


class _FakeKVClient:
    """Dict-backed stand-in for the jax.distributed KV client."""

    def __init__(self):
        self.store = {}

    def key_value_set(self, key, value, allow_overwrite=False):
        self.store[key] = value

    def blocking_key_value_get(self, key, timeout_ms):
        if key not in self.store:
            raise RuntimeError(f"DEADLINE_EXCEEDED: {key}")
        return self.store[key]


class TestEpochAgreement:
    def _process_manager(self, monkeypatch, kv):
        """A manager whose roster is FORCED to look process-partitioned
        (participants {0, 1}, this process = 0 owning every device) so
        the agreement protocol is unit-testable in one process."""
        from skdist_tpu.parallel import mesh as mesh_mod

        monkeypatch.setattr(mesh_mod, "_kv_client", lambda: kv)
        mgr = ElasticMeshManager(group_size=len(jax.devices()),
                                 coordinate=True, agree_timeout_s=0.05)
        mgr._by_process = True
        mgr._pid_of = {id(d): 0 for d in mgr.full_devices}
        mgr.participant_ids = [0, 1]
        return mgr

    def test_silent_peer_declared_lost_and_prefix_kept(self,
                                                       monkeypatch):
        kv = _FakeKVClient()
        mgr = self._process_manager(monkeypatch, kv)
        assert mgr.can_coordinate
        agreed, mesh = mgr.coordinated_resume(16)
        assert agreed == 16
        # peer 1 never published: declared lost; survivors keep the
        # full extent (participant 1 owned no devices in this forced
        # roster, so the mesh itself is unchanged)
        ev = [e for e in mgr.events if e["kind"] == "epoch_agreement"]
        assert len(ev) == 1
        assert ev[0]["survivors"] == [0] and ev[0]["lost"] == [1]
        assert ev[0]["epoch"] == 1
        assert mesh is None
        assert faults.snapshot()["elastic_epoch_agreements"] == 1
        # this process's prefix landed in the store for peers to read
        key = [k for k in kv.store if k.endswith("/p0")][0]
        assert "16" in kv.store[key]

    def test_responding_peer_min_prefix_no_loss(self, monkeypatch):
        import json as json_mod

        kv = _FakeKVClient()
        # peer 1 already published a SHORTER prefix for epoch 1
        kv.store["skdist-elastic/e1/p1"] = json_mod.dumps({"prefix": 8})
        mgr = self._process_manager(monkeypatch, kv)
        agreed, mesh = mgr.coordinated_resume(16)
        # everyone responded: nobody lost, resume from the MIN prefix
        assert agreed == 8
        assert mesh is None
        ev = [e for e in mgr.events if e["kind"] == "epoch_agreement"]
        assert ev[0]["survivors"] == [0, 1] and ev[0]["lost"] == []

    def test_epochs_advance_per_agreement(self, monkeypatch):
        kv = _FakeKVClient()
        mgr = self._process_manager(monkeypatch, kv)
        mgr.coordinated_resume(8)
        mgr.coordinated_resume(24)
        eps = [e["epoch"] for e in mgr.events
               if e["kind"] == "epoch_agreement"]
        assert eps == [1, 2]
        # distinct epochs namespace distinct keys — a stale epoch-1
        # prefix can never satisfy an epoch-2 read
        assert {k for k in kv.store} == {
            "skdist-elastic/e1/p0", "skdist-elastic/e2/p0",
        }

    def test_coordinated_lost_blocks_regrow_without_probe(self,
                                                          monkeypatch):
        """A process an agreement declared lost stays lost (no regrow
        into a dead collective) until an operator probe reports it
        back."""
        kv = _FakeKVClient()
        mgr = self._process_manager(monkeypatch, kv)
        mgr.coordinated_resume(16)
        assert mgr._probe_lost() == {1}
        # an operator probe is authoritative: it reports 1 back
        mgr._probe = lambda: set()
        assert mgr._probe_lost() == set()

    def test_can_coordinate_requires_process_roster(self):
        mgr = ElasticMeshManager(group_size=_half_groups())
        assert not mgr.can_coordinate  # single-controller roster


def test_truncate_rounds_prefix():
    from skdist_tpu.parallel.backend import _truncate_rounds

    rounds = [{"s": np.arange(8)}, {"s": np.arange(8, 16)}]
    out, kept = _truncate_rounds(rounds, 12)
    assert kept == 12
    got = np.concatenate([r["s"] for r in out])
    np.testing.assert_array_equal(got, np.arange(12))
    out, kept = _truncate_rounds(rounds, 8)
    assert kept == 8 and len(out) == 1
    out, kept = _truncate_rounds(rounds, 0)
    assert kept == 0 and out == []
