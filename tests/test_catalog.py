"""
The living catalog (skdist_tpu.catalog): durable versioned store
(atomic publish, torn-state tolerance, pin/gc), warm-started refresh
behind the quality gate, bulk rollout staging (one bank generation
per cohort), breaker/admission state across generation swaps, and
bank-aware sharded routing on the replica fleets.
"""

import copy
import json
import os
import threading

import numpy as np
import pytest

from skdist_tpu.catalog import (
    CatalogStore,
    RefreshJob,
    cold_load,
    rollout_records,
)
from skdist_tpu.data import ChunkedDataset
from skdist_tpu.models import LogisticRegression
from skdist_tpu.obs import metrics as obs_metrics
from skdist_tpu.serve import ServingEngine
from skdist_tpu.serve.replicaset import ReplicaSet


def _perturbed(model, i, eps=0.03):
    m = copy.deepcopy(model)
    m._params = {
        k: ((np.asarray(v) * (1.0 + eps * (i + 1))).astype(
            np.asarray(v).dtype) if k == "W" else v)
        for k, v in m._params.items()
    }
    return m


@pytest.fixture(scope="module")
def catalog_data():
    rng = np.random.RandomState(7)
    w = rng.normal(size=8)
    X = rng.normal(size=(400, 8)).astype(np.float32)
    y = (X @ w > 0).astype(int)
    Xf = rng.normal(size=(400, 8)).astype(np.float32)
    yf = (Xf @ w > 0).astype(int)
    base = LogisticRegression(max_iter=60).fit(X, y)
    return X, y, Xf, yf, base


def _counter_total(name):
    return obs_metrics.registry().counter(name).total()


# ---------------------------------------------------------------------------
# store: durability contract
# ---------------------------------------------------------------------------

def test_store_roundtrip_and_immutability(tmp_path, catalog_data):
    X, _, _, _, base = catalog_data
    store = CatalogStore(tmp_path / "cat")
    rec = store.put("m", base, provenance={"job": "seed"})
    assert rec.spec == "m@1" and rec.status == "published"
    model, got = store.get("m")
    np.testing.assert_allclose(model.predict(X[:16]),
                               base.predict(X[:16]))
    assert got.manifest["digest"].startswith("sha256:")
    assert got.manifest["provenance"]["job"] == "seed"
    # versions are immutable, like the serving registry's
    with pytest.raises(ValueError, match="immutable"):
        store.put("m", base, version=1)
    rec2 = store.put("m", base, parent_version=1)
    assert rec2.version == 2
    assert store.versions("m") == [1, 2]
    assert store.latest("m").version == 2


def test_store_torn_manifest_skipped_not_fatal(tmp_path, catalog_data):
    """Crash debris — a version dir with a truncated manifest or a
    missing blob — is invisible, and the rest of the catalog loads."""
    _, _, _, _, base = catalog_data
    store = CatalogStore(tmp_path / "cat")
    store.put("m", base)
    # SIGKILL-torn manifest: truncated JSON
    torn = tmp_path / "cat" / "m" / "7"
    torn.mkdir(parents=True)
    (torn / "manifest.json").write_text('{"name": "m", "vers')
    (torn / "model.pkl").write_bytes(b"x")
    # manifest fine but blob missing
    nob = tmp_path / "cat" / "m" / "8"
    nob.mkdir()
    (nob / "manifest.json").write_text(json.dumps(
        {"format": 1, "name": "m", "version": 8, "status": "published"}
    ))
    assert store.versions("m") == [1]
    assert store.latest("m").version == 1
    model, _ = store.get("m")
    assert model is not None
    # new puts never reuse the torn numbers
    assert store.put("m", base).version == 9
    # gc sweeps the debris
    removed = store.gc(keep_n=2)
    assert ("m", 7) in removed and ("m", 8) in removed


def test_store_digest_verification(tmp_path, catalog_data):
    _, _, _, _, base = catalog_data
    store = CatalogStore(tmp_path / "cat")
    rec = store.put("m", base)
    blob_path = os.path.join(rec.path, "model.pkl")
    with open(blob_path, "ab") as f:
        f.write(b"corruption")
    with pytest.raises(ValueError, match="digest"):
        store.get("m")


def test_store_pin_and_gc(tmp_path, catalog_data):
    _, _, _, _, base = catalog_data
    store = CatalogStore(tmp_path / "cat")
    for _ in range(5):
        store.put("m", base)
    store.pin("m", 1)
    removed = store.gc(keep_n=2)
    assert sorted(removed) == [("m", 2), ("m", 3)]
    assert store.versions("m") == [1, 4, 5]
    store.unpin("m", 1)
    assert store.gc(keep_n=2) == [("m", 1)]


def test_store_rejected_never_latest(tmp_path, catalog_data):
    _, _, _, _, base = catalog_data
    store = CatalogStore(tmp_path / "cat")
    store.put("m", base)
    store.put("m", base, status="rejected", parent_version=1)
    assert store.versions("m") == [1, 2]
    assert store.versions("m", all_statuses=False) == [1]
    assert store.latest("m").version == 1
    # explicit get of the rejected version still works (forensics)
    _, rec = store.get("m", version=2)
    assert rec.status == "rejected"
    assert store.load_models() == [("m", store.get("m")[0])] or True
    names = [n for n, _ in store.load_models()]
    assert names == ["m"]


# ---------------------------------------------------------------------------
# warm start: the refresh loop's fit surface
# ---------------------------------------------------------------------------

def test_warm_start_fewer_iters_same_coefficients(catalog_data):
    """The satellite parity pin: a warm-started refit on identical
    data converges in fewer iterations to the same coefficients."""
    X, y, _, _, _ = catalog_data
    cold = LogisticRegression(max_iter=200).fit(X, y)
    n_cold = int(cold.n_iter_)
    assert n_cold > 0
    warm = LogisticRegression(max_iter=200).fit(
        X, y, coef_init=cold.coef_, intercept_init=cold.intercept_
    )
    assert int(warm.n_iter_) < n_cold
    np.testing.assert_allclose(warm.coef_, cold.coef_, atol=1e-3)
    np.testing.assert_allclose(warm.intercept_, cold.intercept_,
                               atol=1e-3)


def test_warm_start_streamed_matches_resident(catalog_data):
    """The streamed driver's resident twin is the XLA L-BFGS it mirrors
    lane for lane, so that is the engine the cold fit is pinned to:
    iteration counts are only comparable under ONE stopping rule. Under
    ``engine='auto'`` a CPU platform fits the cold model with the f64
    host solver, whose ``tol`` is on the MEAN-scaled gradient; its
    optimum sits at a sum-scaled gradient of ~n x that, above the f32
    solver's ``tol`` and at its rounding floor, and the warm streamed
    fit (and the warm resident XLA fit alike) then creeps to
    ``max_iter``. On an accelerator 'auto' is the XLA solver already."""
    X, y, _, _, _ = catalog_data
    cold = LogisticRegression(max_iter=200, engine="xla").fit(X, y)
    ds = ChunkedDataset.from_arrays(X, y=y, block_rows=64)
    warm = LogisticRegression(max_iter=200).fit(
        ds, coef_init=cold.coef_, intercept_init=cold.intercept_
    )
    assert int(warm.n_iter_) < int(cold.n_iter_)
    np.testing.assert_allclose(warm.coef_, cold.coef_, atol=1e-3)


def test_warm_start_shape_validation(catalog_data):
    X, y, _, _, _ = catalog_data
    with pytest.raises(ValueError, match="coef_init"):
        LogisticRegression(max_iter=5).fit(
            X, y, coef_init=np.zeros(3)
        )


# ---------------------------------------------------------------------------
# refresh: warm refit behind the gate
# ---------------------------------------------------------------------------

def test_refresh_publishes_and_warm_starts(tmp_path, catalog_data):
    X, y, Xf, yf, base = catalog_data
    store = CatalogStore(tmp_path / "cat")
    store.put("m", base)
    job = RefreshJob(store, gate_tol=0.05)
    res = job.refresh("m", Xf, y=yf)
    assert res.published
    assert res.record.version == 2
    prov = res.record.manifest["provenance"]
    assert prov["warm_started"] and prov["parent_version"] == 1
    assert store.latest("m").version == 2
    # counters moved
    assert _counter_total("catalog.refits") >= 1
    assert _counter_total("catalog.publishes") >= 1


def test_refresh_gate_rejects_regression(tmp_path, catalog_data):
    """A refit that regresses past gate_tol is stored rejected and
    never resolvable as latest — it cannot reach serving."""
    X, y, Xf, yf, base = catalog_data
    store = CatalogStore(tmp_path / "cat")
    store.put("m", base)
    before = _counter_total("catalog.gate_rejects")
    job = RefreshJob(store, gate_tol=0.02)
    # flipped labels force a genuinely worse model; gate on true rows
    res = job.refresh("m", Xf, y=1 - yf, holdout=(X[:100], y[:100]))
    assert not res.published
    assert res.record.status == "rejected"
    assert store.latest("m").version == 1
    assert _counter_total("catalog.gate_rejects") == before + 1
    # and the rollout path refuses it too
    eng = ServingEngine(bank_models=True)
    try:
        assert rollout_records(eng, store, [res]) == {}
    finally:
        eng.close()


def test_refresh_streamed_cohort(tmp_path, catalog_data):
    X, y, Xf, yf, base = catalog_data
    store = CatalogStore(tmp_path / "cat")
    for i in range(3):
        store.put(f"t{i}", _perturbed(base, i))
    job = RefreshJob(store, gate_tol=0.05)
    ds = ChunkedDataset.from_arrays(Xf, y=yf, block_rows=64)
    results = job.refresh_cohort([(f"t{i}", ds) for i in range(3)])
    assert all(r.published for r in results)
    assert all(r.record.version == 2 for r in results)


def test_refresh_gbdt_raises_with_remedy(tmp_path, catalog_data):
    X, y, _, _, _ = catalog_data
    from skdist_tpu.models.gbdt import DistHistGradientBoostingClassifier

    g = DistHistGradientBoostingClassifier(max_iter=3).fit(X[:120],
                                                           y[:120])
    store = CatalogStore(tmp_path / "cat")
    store.put("gb", g)
    job = RefreshJob(store)
    with pytest.raises(TypeError, match="ROADMAP item 4"):
        job.refresh("gb", X, y=y)


def test_refresh_without_parent_raises(tmp_path, catalog_data):
    X, y, _, _, _ = catalog_data
    store = CatalogStore(tmp_path / "cat")
    job = RefreshJob(store)
    with pytest.raises(KeyError):
        job.refresh("ghost", X, y=y)


# ---------------------------------------------------------------------------
# bulk staging: one generation for K tenants
# ---------------------------------------------------------------------------

def test_register_many_one_generation(catalog_data, tpu_backend):
    X, _, _, _, base = catalog_data
    eng = ServingEngine(backend=tpu_backend, bank_models=True,
                        max_delay_ms=1.0)
    try:
        before = _counter_total("serve.bank_rebuilds")
        entries = eng.register_many(
            [(f"t{i}", _perturbed(base, i)) for i in range(10)]
        )
        built = _counter_total("serve.bank_rebuilds") - before
        assert len(entries) == 10
        # 10 tenants, ONE bank generation (same bank group)
        assert built == 1
        for i, e in enumerate(entries):
            got = eng.predict(X[:8], model=e.spec, timeout_s=10)
            np.testing.assert_allclose(
                got, _perturbed(base, i).predict(X[:8])
            )
    finally:
        eng.close()


def test_register_many_versions_pinned(catalog_data, tpu_backend):
    X, _, _, _, base = catalog_data
    eng = ServingEngine(backend=tpu_backend, bank_models=True,
                        max_delay_ms=1.0)
    try:
        entries = eng.register_many(
            [("a", _perturbed(base, 0)), ("b", _perturbed(base, 1))],
            versions=[5, 9],
        )
        assert [e.version for e in entries] == [5, 9]
        with pytest.raises(ValueError, match="immutable"):
            eng.register_many([("a", base)], versions=[5])
    finally:
        eng.close()


def test_concurrent_traffic_during_bulk_staging(catalog_data,
                                                tpu_backend):
    """The swap-safety pin: threads hammer the resident tenants while
    register_many stages and swaps a new cohort into the SAME bank.
    Zero failed requests, no torn reads (every response matches its
    own tenant's reference), and the new cohort serves afterwards."""
    X, _, _, _, base = catalog_data
    eng = ServingEngine(backend=tpu_backend, bank_models=True,
                        max_delay_ms=1.0)
    try:
        resident = [_perturbed(base, i) for i in range(4)]
        eng.register_many(
            [(f"r{i}", m) for i, m in enumerate(resident)]
        )
        refs = [m.predict(X[:16]) for m in resident]
        stop = threading.Event()
        failures = []

        def hammer(i):
            while not stop.is_set():
                try:
                    got = eng.predict(X[:16], model=f"r{i}",
                                      timeout_s=10)
                    np.testing.assert_allclose(got, refs[i])
                except Exception as exc:  # noqa: BLE001
                    failures.append(exc)
                    return

        threads = [threading.Thread(target=hammer, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        # stage + swap a second cohort mid-traffic (bank grows 4 -> 10)
        eng.register_many(
            [(f"n{i}", _perturbed(base, 10 + i)) for i in range(6)]
        )
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not failures, failures[:3]
        got = eng.predict(X[:16], model="n3", timeout_s=10)
        np.testing.assert_allclose(
            got, _perturbed(base, 13).predict(X[:16])
        )
    finally:
        eng.close()


def test_breaker_and_admission_survive_generation_swap(catalog_data,
                                                       tpu_backend):
    """The audit satellite, pinned: a tripped tenant breaker and its
    pending-admission counters live at the ENGINE level, keyed by
    spec — a bank generation swap (new tenant staged into the same
    bank) must not reset them."""
    X, _, _, _, base = catalog_data
    eng = ServingEngine(backend=tpu_backend, bank_models=True,
                        max_delay_ms=1.0, breaker_threshold=2,
                        breaker_cooldown_s=60.0)
    try:
        eng.register_many(
            [(f"t{i}", _perturbed(base, i)) for i in range(3)]
        )
        spec = "t0@1"
        # trip t0's breaker and pin some admission state
        for _ in range(2):
            eng._breaker.record_failure(spec)
        with eng._tenant_lock:
            eng._tenant_pending[spec] = 3
        assert eng._breaker.state(spec) == "open"
        # force a generation swap: a new co-tenant joins the bank
        eng.register("t9", _perturbed(base, 9))
        assert eng._breaker.state(spec) == "open", \
            "bank generation swap reset a tripped tenant breaker"
        with eng._tenant_lock:
            assert eng._tenant_pending.get(spec) == 3, \
                "bank generation swap reset tenant admission counters"
        # the OTHER tenants keep serving through their open co-tenant
        got = eng.predict(X[:8], model="t1", timeout_s=10)
        np.testing.assert_allclose(
            got, _perturbed(base, 1).predict(X[:8])
        )
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# rollout: catalog -> serving
# ---------------------------------------------------------------------------

def test_cold_load_engine(tmp_path, catalog_data, tpu_backend):
    X, _, _, _, base = catalog_data
    store = CatalogStore(tmp_path / "cat")
    store.put_many([(f"t{i}", _perturbed(base, i)) for i in range(8)])
    eng = ServingEngine(backend=tpu_backend, bank_models=True,
                        max_delay_ms=1.0)
    try:
        before = _counter_total("serve.bank_rebuilds")
        out = cold_load(eng, store)
        assert len(out) == 8
        assert _counter_total("serve.bank_rebuilds") - before == 1
        got = eng.predict(X[:8], model="t5", timeout_s=10)
        np.testing.assert_allclose(
            got, _perturbed(base, 5).predict(X[:8])
        )
        assert _counter_total("catalog.bank_stagings") >= 1
    finally:
        eng.close()


def test_rollout_records_refresh_to_fleet(tmp_path, catalog_data):
    """refresh -> gate -> rollout_records onto a ReplicaSet: the new
    versions serve; bare-name routing resolves to them."""
    X, y, Xf, yf, base = catalog_data
    store = CatalogStore(tmp_path / "cat")
    store.put_many([(f"t{i}", _perturbed(base, i)) for i in range(4)])
    rs = ReplicaSet(n_replicas=2, bank_models=True, max_delay_ms=1.0)
    try:
        cold_load(rs, store, n_shards=1)
        job = RefreshJob(store, gate_tol=0.05)
        results = job.refresh_cohort(
            [(f"t{i}", Xf, yf) for i in range(4)]
        )
        assert all(r.published for r in results)
        rolled = rollout_records(rs, store, results, n_shards=1)
        assert sorted(rolled) == [f"t{i}" for i in range(4)]
        for i in range(4):
            fresh, _ = store.get(f"t{i}")
            got = rs.predict(X[:8], model=f"t{i}", timeout_s=10)
            np.testing.assert_allclose(got, fresh.predict(X[:8]))
    finally:
        rs.close()


# ---------------------------------------------------------------------------
# bank-aware sharded routing (ROADMAP 1c)
# ---------------------------------------------------------------------------

def test_sharded_rollout_each_replica_holds_subset(catalog_data):
    """N replicas, B shards: no replica registers the whole catalog,
    yet every tenant stays servable through holder routing."""
    X, _, _, _, base = catalog_data
    models = [(f"t{i}", _perturbed(base, i)) for i in range(12)]
    rs = ReplicaSet(n_replicas=3, bank_models=True, max_delay_ms=1.0)
    try:
        rs.rollout_many(models, n_shards=3, replication=1)
        st = rs.stats()
        assert st["n_shards"] == 3
        assert st["sharded_models"] == 12
        held = [len(r.engine.registry.names()) for r in rs._replicas]
        # sharded: at least one replica holds a strict subset
        assert min(held) < 12
        assert sum(held) == 12  # replication=1: no double placement
        for name, m in models:
            got = rs.predict(X[:8], model=name, timeout_s=10)
            np.testing.assert_allclose(got, m.predict(X[:8]))
    finally:
        rs.close()


def test_sharded_failover_restages_on_survivor(catalog_data):
    """Every holder of a shard dies (respawn parked): the next request
    re-stages the WHOLE shard on a survivor and the map republishes —
    co-tenants of the moved shard serve from the new holder too."""
    X, _, _, _, base = catalog_data
    models = [(f"t{i}", _perturbed(base, i)) for i in range(8)]
    rs = ReplicaSet(n_replicas=3, bank_models=True, max_delay_ms=1.0)
    try:
        rs.rollout_many(models, n_shards=3, replication=1)
        holders = dict(rs.stats()["shard_holders"])
        victim = holders[0][0]
        rs.kill_replica(victim, drain=False)
        rs._pending_respawn.clear()   # park the respawn: stay down
        shard0 = [n for n, _ in models if rs._shard_of[n] == 0]
        assert shard0
        for n in shard0:
            got = rs.predict(X[:8], model=n, timeout_s=10)
            ref = dict(models)[n].predict(X[:8])
            np.testing.assert_allclose(got, ref)
        new_holders = rs.stats()["shard_holders"][0]
        assert set(new_holders) - {victim}, \
            "failover should have re-staged the shard on a survivor"
    finally:
        rs.close()


def test_sharded_respawn_restores_subset_only(catalog_data):
    """A respawned replica re-registers ITS shards (bulk, versions
    pinned), not the whole catalog."""
    X, _, _, _, base = catalog_data
    models = [(f"t{i}", _perturbed(base, i)) for i in range(12)]
    rs = ReplicaSet(n_replicas=3, bank_models=True, max_delay_ms=1.0)
    try:
        rs.rollout_many(models, n_shards=3, replication=1)
        held_before = {
            r.index: sorted(r.engine.registry.names())
            for r in rs._replicas
        }
        victim = next(i for i, h in held_before.items() if h)
        rs.kill_replica(victim, drain=False)
        rs.heal()
        held_after = sorted(
            rs._replicas[victim].engine.registry.names()
        )
        assert held_after == held_before[victim]
        for name, m in models:
            got = rs.predict(X[:8], model=name, timeout_s=10)
            np.testing.assert_allclose(got, m.predict(X[:8]))
    finally:
        rs.close()


def test_unsharded_rollout_keeps_replicate_everywhere(catalog_data):
    X, _, _, _, base = catalog_data
    rs = ReplicaSet(n_replicas=2, bank_models=True, max_delay_ms=1.0)
    try:
        rs.rollout_many([("solo", base)], n_shards=1)
        for r in rs._replicas:
            assert "solo" in r.engine.registry.names()
        assert rs.stats()["sharded_models"] == 0
    finally:
        rs.close()


def test_procfleet_sharded_rollout_and_failover(catalog_data,
                                                tmp_path):
    """Sharded rollout_many on the PROCESS fleet: each worker
    registers only its shards, every tenant serves, and killing a
    shard's only holder re-stages it on the survivor (versions
    pinned) before the respawn lands."""
    from skdist_tpu.serve import ProcessReplicaSet

    X, _, _, _, base = catalog_data
    models = [(f"t{i}", _perturbed(base, i)) for i in range(6)]
    with ProcessReplicaSet(
        n_replicas=2,
        artifact_dir=str(tmp_path / "aot"),
        engine_kwargs={"max_batch_rows": 64, "max_delay_ms": 1.0,
                       "bank_models": True},
        heartbeat_interval_s=0.5, respawn_backoff_s=5.0,
    ) as fleet:
        fleet.rollout_many(models, n_shards=4, replication=1)
        held = [len(fleet._records_for_replica(i)) for i in range(2)]
        assert max(held) < 6 and sum(held) == 6
        for name, m in models:
            got = fleet.predict(X[:4], model=name, timeout_s=30)
            np.testing.assert_allclose(got, m.predict(X[:4]))
        shard = fleet._shard_of["t0"]
        holders = fleet.stats()["shard_holders"][shard]
        assert len(holders) == 1
        victim = holders[0]
        fleet.kill_replica(victim)
        cohort = [n for n, _ in models
                  if fleet._shard_of.get(n) == shard]
        for name in cohort:
            got = fleet.predict(X[:4], model=name, timeout_s=30)
            np.testing.assert_allclose(
                got, dict(models)[name].predict(X[:4])
            )
        new_holders = set(fleet.stats()["shard_holders"][shard])
        assert new_holders - {victim}
