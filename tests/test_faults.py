"""
Fault-tolerance layer tests: taxonomy/retry policy units, round-retry
integration (transient / preemption / OOM-vs-retry precedence /
exhaustion / fail-loud multi-process), NaN lane quarantine on the
search and OvR paths, durable checkpoint journal + resume, the
error_score front-door validation, the `_nan_as_worst` rank pins, and
the serving watchdog + circuit breaker.

The deterministic injection harness (`skdist_tpu.testing.faultinject`)
stands in for real device failures: its raises carry the same status
strings `faults.classify` keys on, and NaN poisoning rides the gather
path, so every integration test exercises the production handling
code, not a parallel test-only path.
"""

import os
import re
import warnings

import numpy as np
import pytest

from skdist_tpu.distribute.search import (
    DistGridSearchCV,
    FitFailedWarning,
    _nan_as_worst,
)
from skdist_tpu.models import LogisticRegression
from skdist_tpu.parallel import LocalBackend, TPUBackend, faults
from skdist_tpu.testing.faultinject import FaultInjector, inject


@pytest.fixture(autouse=True)
def _fresh_fault_state():
    faults.reset_stats()
    yield
    faults.set_injector(None)
    faults.reset_stats()


def small_grid(**kw):
    kw.setdefault("cv", 3)
    kw.setdefault("partitions", 3)
    return DistGridSearchCV(
        LogisticRegression(max_iter=30, engine="xla"),
        {"C": [0.1, 1.0, 10.0]}, **kw
    )


@pytest.fixture
def grid_data():
    rng = np.random.RandomState(3)
    X = np.vstack([
        rng.normal(loc=c, scale=0.6, size=(80, 8)) for c in (-1.0, 1.0)
    ]).astype(np.float32)
    y = np.repeat([0, 1], 80)
    perm = rng.permutation(len(y))
    return X[perm], y[perm]


# ---------------------------------------------------------------------------
# taxonomy + retry policy units
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("msg,kind", [
    ("UNAVAILABLE: socket closed", faults.TRANSIENT),
    ("INTERNAL: something flaked", faults.TRANSIENT),
    ("ABORTED: collective timed out", faults.TRANSIENT),
    ("Broken pipe", faults.TRANSIENT),
    ("the worker has been restarted", faults.PREEMPTED),
    ("UNAVAILABLE: worker preempted mid-step", faults.PREEMPTED),
    ("RESOURCE_EXHAUSTED: out of memory", faults.OOM),
    ("INTERNAL: allocator RESOURCE_EXHAUSTED", faults.OOM),
    ("ValueError: bad operand", faults.FATAL),
    ("", faults.FATAL),
    # a program the compiler refuses fails the same way every time
    ("INTERNAL: Mosaic failed to compile TPU kernel: cannot statically "
     "prove that index in dimension 1 is a multiple of 128", faults.FATAL),
    ("INTERNAL: during compilation of module jit_mapped", faults.FATAL),
    ("INTERNAL: XLA:TPU compile permanent error. RESOURCE_EXHAUSTED: Ran "
     "out of memory in memory space hbm", faults.OOM),
])
def test_classify(msg, kind):
    assert faults.classify(RuntimeError(msg)) == kind


def test_classify_precedence_and_watchdog():
    # OOM outranks the transient INTERNAL mark; WatchdogTimeout outranks
    # its message content
    assert faults.classify(
        RuntimeError("INTERNAL: RESOURCE_EXHAUSTED during allreduce")
    ) == faults.OOM
    assert faults.classify(
        faults.WatchdogTimeout("UNAVAILABLE-looking text")
    ) == faults.WATCHDOG
    assert faults.is_retryable(faults.TRANSIENT)
    assert faults.is_retryable(faults.PREEMPTED)
    assert faults.is_retryable(faults.WATCHDOG)
    assert not faults.is_retryable(faults.OOM)
    assert not faults.is_retryable(faults.FATAL)


def test_retry_policy_env_knobs(monkeypatch):
    monkeypatch.setenv("SKDIST_ROUND_RETRIES", "5")
    monkeypatch.setenv("SKDIST_RETRY_BACKOFF_MS", "10")
    p = faults.RetryPolicy()
    assert p.max_retries == 5
    assert p.backoff_ms == 10.0
    # exponential doubling, capped
    assert p.delay_s(1) == 0.01
    assert p.delay_s(2) == 0.02
    assert p.delay_s(20) == p.max_backoff_ms / 1e3
    # malformed env falls back to defaults instead of crashing
    monkeypatch.setenv("SKDIST_ROUND_RETRIES", "lots")
    assert faults.RetryPolicy().max_retries == 2


def test_nonfinite_lanes_masks():
    tree = {
        "coef": np.ones((4, 3), np.float32),
        "n_iter": np.arange(4),  # int leaves never flag
    }
    assert faults.nonfinite_lanes(tree) is None  # fast path: no mask
    tree["coef"][2, 1] = np.nan
    tree["intercept"] = np.ones(4, np.float32)
    tree["intercept"][0] = np.inf
    mask = faults.nonfinite_lanes(tree)
    assert mask.tolist() == [True, False, True, False]


def test_guard_kill_switch(monkeypatch):
    assert faults.guard_enabled()
    monkeypatch.setenv("SKDIST_FAULT_GUARD", "0")
    assert not faults.guard_enabled()


# ---------------------------------------------------------------------------
# error_score front-door validation (satellite)
# ---------------------------------------------------------------------------

def test_error_score_validated_at_fit_entry(grid_data):
    X, y = grid_data
    gs = small_grid(error_score="nan")  # the classic typo
    with pytest.raises(ValueError, match="did you mean numpy.nan"):
        gs.fit(X, y)
    with pytest.raises(ValueError):
        small_grid(error_score=True).fit(X, y)
    # legal forms pass validation (and fit)
    small_grid(error_score="raise").fit(X, y)
    small_grid(error_score=np.nan).fit(X, y)
    small_grid(error_score=0.0).fit(X, y)


# ---------------------------------------------------------------------------
# round retry integration (backend level)
# ---------------------------------------------------------------------------

def _identity_run(backend, n=24, round_size=8):
    import jax.numpy as jnp

    def kernel(shared, task):
        return {"v": task["w"] * 2.0 + jnp.sum(shared["X"]) * 0.0}

    W = np.arange(n, dtype=np.float32)
    X = np.ones((2, 2), np.float32)
    out = backend.batched_map(
        kernel, {"w": W}, {"X": X}, round_size=round_size
    )
    np.testing.assert_array_equal(out["v"], W * 2.0)
    return backend.last_round_stats


def test_transient_round_retry_exact(tpu_backend):
    """A transient fault mid-run: salvaged prefix + re-dispatch must
    reproduce the exact task order (contiguous-prefix contract)."""
    with FaultInjector().at_round(1, kind="transient") as inj, \
            warnings.catch_warnings():
        warnings.simplefilter("ignore")
        stats = _identity_run(tpu_backend)
    assert ("transient" in inj.fired_kinds())
    assert stats["retries"] == 1
    assert faults.snapshot()["rounds_retried"] == 1


def test_transient_retry_local_backend():
    with FaultInjector().at_round(1, kind="transient"), \
            warnings.catch_warnings():
        warnings.simplefilter("ignore")
        stats = _identity_run(LocalBackend())
    assert stats["retries"] == 1


def test_preemption_replaces_shared_args(tpu_backend):
    with FaultInjector().at_round(1, kind="preempt"), \
            warnings.catch_warnings():
        warnings.simplefilter("ignore")
        _identity_run(tpu_backend)
    snap = faults.snapshot()
    assert snap["rounds_retried"] == 1
    assert snap["shared_replacements"] == 1


def test_preemption_compacted_replaces_plan(tpu_backend):
    """The compacted iterative path shares the classic path's
    preemption contract: device state is presumed lost, so the retry
    must re-place the shared args through a fresh plan (broadcast
    cache dropped) — not burn the whole budget against dead buffers."""
    import jax.numpy as jnp

    from skdist_tpu.parallel import IterativeKernelSpec

    def init(shared, task):
        return {"v": task["w"] * 2.0 + jnp.sum(shared["X"]) * 0.0,
                "done": jnp.bool_(True)}

    def step(shared, task, carry):
        return carry

    def fin(shared, task, carry):
        return {"out": carry["v"]}

    def fallback(shared, task):
        return {"out": task["w"] * 2.0 + jnp.sum(shared["X"]) * 0.0}

    spec = IterativeKernelSpec(init, step, fin, ("v",), fallback=fallback)
    W = np.arange(24, dtype=np.float32)
    shared = {"X": np.ones((2, 2), np.float32)}
    # ordinal 0 is the first finalize round (the slice loop's own
    # dispatches do not consume injector ordinals)
    with FaultInjector().at_round(0, kind="preempt") as inj, \
            warnings.catch_warnings():
        warnings.simplefilter("ignore")
        out = tpu_backend.batched_map_iterative(
            spec, {"w": W}, shared, round_size=8,
            cache_key=("tf", "preempt-compacted"),
        )
    np.testing.assert_array_equal(out["out"], W * 2.0)
    assert "preempt" in inj.fired_kinds()
    snap = faults.snapshot()
    assert snap["rounds_retried"] == 1
    assert snap["shared_replacements"] == 1


def test_retry_budget_exhausts_to_original_error(tpu_backend, monkeypatch):
    monkeypatch.setenv("SKDIST_ROUND_RETRIES", "1")
    monkeypatch.setenv("SKDIST_RETRY_BACKOFF_MS", "0")
    # the same round keeps failing: 1 retry allowed, then the cause
    # surfaces (times=10 > budget)
    with FaultInjector().at_round(1, kind="transient", times=10) \
            .at_round(2, kind="transient", times=10), \
            warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(RuntimeError, match="UNAVAILABLE"):
            _identity_run(tpu_backend)
    assert faults.snapshot()["retries_exhausted"] == 1


def test_budget_is_per_round_not_global(tpu_backend, monkeypatch):
    """One hiccup per round across many rounds must NOT exhaust: the
    counter resets when the offset advances."""
    monkeypatch.setenv("SKDIST_ROUND_RETRIES", "1")
    monkeypatch.setenv("SKDIST_RETRY_BACKOFF_MS", "0")
    # rounds 1 and 3 each fail once (their retries land on later
    # ordinals and succeed)
    inj = (FaultInjector().at_round(1, kind="transient")
           .at_round(3, kind="transient"))
    with inj, warnings.catch_warnings():
        warnings.simplefilter("ignore")
        stats = _identity_run(tpu_backend, n=32, round_size=8)
    assert stats["retries"] == 2
    assert faults.snapshot()["retries_exhausted"] == 0


def test_fatal_fault_never_retried(tpu_backend):
    with FaultInjector().at_round(1, kind="fatal"):
        with pytest.raises(RuntimeError, match="injected fatal"):
            _identity_run(tpu_backend)
    assert faults.snapshot()["rounds_retried"] == 0


def test_oom_keeps_resume_machinery(tpu_backend):
    """RESOURCE_EXHAUSTED still takes the dedicated shrink-and-resume
    path (halved round size), not the retry path."""
    with FaultInjector().at_round(1, kind="oom"), \
            warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        _identity_run(tpu_backend, n=32, round_size=16)
    assert faults.snapshot()["rounds_retried"] == 0
    assert any("resuming at round_size" in str(w.message) for w in caught)


def test_multiprocess_fail_loud_with_remedy(tpu_backend, monkeypatch):
    """_RoundsExhausted regression (satellite): on a multi-process mesh
    the OOM branch must fail loud, and the remedy's suggested
    partitions value must actually produce rounds that fit (i.e. round
    size <= half the chunk that OOMed)."""
    monkeypatch.setattr(TPUBackend, "_spans_processes", lambda self: True)
    n, round_size = 32, 16
    with FaultInjector().at_round(1, kind="oom", times=10):
        with pytest.raises(RuntimeError, match="multi-process") as ei:
            _identity_run(tpu_backend, n=n, round_size=round_size)
    m = re.search(r"partitions>=(\d+)", str(ei.value))
    assert m, f"no partitions remedy in: {ei.value}"
    suggested = int(m.group(1))
    implied_round = -(-n // suggested)
    assert implied_round <= round_size // 2, (
        f"suggested partitions={suggested} implies round size "
        f"{implied_round}, which does not fit below {round_size // 2}"
    )


def test_multiprocess_fail_loud_on_retryable(tpu_backend, monkeypatch):
    """Transient faults too: no local retry on SPMD meshes — a
    collective-consistent message pointing at checkpoints instead."""
    monkeypatch.setattr(TPUBackend, "_spans_processes", lambda self: True)
    with FaultInjector().at_round(1, kind="transient"):
        with pytest.raises(RuntimeError,
                           match="SKDIST_CHECKPOINT_DIR"):
            _identity_run(tpu_backend)
    assert faults.snapshot()["rounds_retried"] == 0


def test_singleprocess_oom_resume_contiguous_prefix(tpu_backend):
    """_RoundsExhausted regression (satellite): the single-process
    resume yields a contiguous task prefix — exact per-task outputs in
    original order after the mid-run shrink."""
    with FaultInjector().at_round(1, kind="oom"), \
            warnings.catch_warnings():
        warnings.simplefilter("ignore")
        _identity_run(tpu_backend, n=40, round_size=16)  # asserts order


# ---------------------------------------------------------------------------
# search-level retry + quarantine
# ---------------------------------------------------------------------------

def test_search_transient_bitwise_parity(grid_data):
    X, y = grid_data
    base = small_grid().fit(X, y)
    with FaultInjector().every(2, kind="transient"), \
            warnings.catch_warnings():
        warnings.simplefilter("ignore")
        faulty = small_grid().fit(X, y)
    assert faults.snapshot()["rounds_retried"] >= 1
    for k, v in base.cv_results_.items():
        if "test_score" in k:
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(faulty.cv_results_[k]), err_msg=k
            )


def test_nan_lane_maps_to_error_score(grid_data):
    X, y = grid_data
    base = small_grid().fit(X, y)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with inject(ordinal=0, kind="nan", lanes=[1]):
            q = small_grid(error_score=0.25).fit(X, y)
    assert any(issubclass(w.category, FitFailedWarning) for w in caught)
    assert faults.snapshot()["lanes_quarantined"] == 1
    splits = [k for k in base.cv_results_ if k.startswith("split")
              and k.endswith("test_score")]
    flat_base = np.stack([base.cv_results_[k] for k in splits])
    flat_q = np.stack([np.asarray(q.cv_results_[k]) for k in splits])
    changed = flat_base != flat_q
    assert changed.sum() == 1  # exactly the poisoned task moved
    assert flat_q[changed][0] == 0.25  # ...to error_score


def test_nan_lane_error_score_raise(grid_data):
    X, y = grid_data
    with inject(ordinal=0, kind="nan", lanes=[0]):
        with pytest.raises(RuntimeError, match="non-finite"):
            small_grid(error_score="raise").fit(X, y)


def test_guard_disabled_lets_nan_through(grid_data, monkeypatch):
    monkeypatch.setenv("SKDIST_FAULT_GUARD", "0")
    X, y = grid_data
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with inject(ordinal=0, kind="nan", lanes=[0]):
            q = small_grid(error_score=0.25).fit(X, y)
    assert not any(
        issubclass(w.category, FitFailedWarning) for w in caught
    )
    splits = np.stack([
        np.asarray(v) for k, v in q.cv_results_.items()
        if k.startswith("split") and k.endswith("test_score")
    ])
    assert np.isnan(splits).sum() == 1  # raw NaN, not error_score
    assert faults.snapshot()["lanes_quarantined"] == 0


def test_ovr_nan_lane_warns(grid_data):
    from skdist_tpu.distribute.multiclass import DistOneVsRestClassifier

    rng = np.random.RandomState(5)
    X = np.vstack([
        rng.normal(loc=c, scale=0.6, size=(50, 6))
        for c in (-2.0, 0.0, 2.0)
    ]).astype(np.float32)
    y = np.repeat([0, 1, 2], 50)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with inject(ordinal=0, kind="nan", lanes=[1]):
            DistOneVsRestClassifier(
                LogisticRegression(max_iter=30, engine="xla")
            ).fit(X, y)
    msgs = [w for w in caught if issubclass(w.category, FitFailedWarning)]
    assert msgs and "one-vs-rest" in str(msgs[0].message)
    assert faults.snapshot()["lanes_quarantined"] >= 1


# ---------------------------------------------------------------------------
# durable checkpoints
# ---------------------------------------------------------------------------

def test_checkpoint_journal_resume_batched(grid_data, tmp_path):
    X, y = grid_data
    base = small_grid().fit(X, y)
    small_grid().fit(X, y, checkpoint_dir=str(tmp_path))
    journals = list(tmp_path.glob("*.jsonl"))
    assert len(journals) == 1
    lines = journals[0].read_text().strip().split("\n")
    assert len(lines) == 9  # 3 candidates x 3 folds, all journaled
    # simulate a kill that kept 4 tasks, then resume
    journals[0].write_text("\n".join(lines[:4]) + "\n")
    resumed = small_grid().fit(X, y, checkpoint_dir=str(tmp_path))
    assert faults.snapshot()["checkpoint_hits"] == 4
    for k in base.cv_results_:
        if "test_score" in k and not k.startswith("rank"):
            np.testing.assert_allclose(
                np.asarray(base.cv_results_[k], float),
                np.asarray(resumed.cv_results_[k], float),
                atol=1e-12, err_msg=k,
            )


def test_checkpoint_torn_tail_dropped(grid_data, tmp_path):
    X, y = grid_data
    small_grid().fit(X, y, checkpoint_dir=str(tmp_path))
    j = next(tmp_path.glob("*.jsonl"))
    # SIGKILL mid-append: a torn half-line must not poison the reload
    with open(j, "a") as fh:
        fh.write('{"t": 99, "r": {"test_sc')
    resumed = small_grid().fit(X, y, checkpoint_dir=str(tmp_path))
    assert faults.snapshot()["checkpoint_hits"] == 9
    assert len(resumed.cv_results_["mean_test_score"]) == 3


def test_checkpoint_signature_isolation(grid_data, tmp_path):
    """A different grid / different data must journal under a different
    signature — never resume from another search's results."""
    X, y = grid_data
    small_grid().fit(X, y, checkpoint_dir=str(tmp_path))
    DistGridSearchCV(
        LogisticRegression(max_iter=30, engine="xla"),
        {"C": [0.5, 2.0]}, cv=3, partitions=3,
    ).fit(X, y, checkpoint_dir=str(tmp_path))
    X2 = X + 1.0
    small_grid().fit(X2, y, checkpoint_dir=str(tmp_path))
    assert len(list(tmp_path.glob("*.jsonl"))) == 3


def test_checkpoint_host_path_resume(grid_data, tmp_path):
    X, y = grid_data

    def host_grid():
        return DistGridSearchCV(
            LogisticRegression(max_iter=30, engine="host"),
            {"C": [0.1, 1.0, 10.0]}, cv=3,
        )

    base = host_grid().fit(X, y)
    host_grid().fit(X, y, checkpoint_dir=str(tmp_path))
    resumed = host_grid().fit(X, y, checkpoint_dir=str(tmp_path))
    assert faults.snapshot()["checkpoint_hits"] == 9
    np.testing.assert_allclose(
        base.cv_results_["mean_test_score"],
        resumed.cv_results_["mean_test_score"], atol=1e-12,
    )


def test_checkpoint_env_var(grid_data, tmp_path, monkeypatch):
    monkeypatch.setenv("SKDIST_CHECKPOINT_DIR", str(tmp_path))
    X, y = grid_data
    small_grid().fit(X, y)
    assert list(tmp_path.glob("*.jsonl"))


def test_checkpoint_signature_stable_for_callable_scoring():
    """repr(callable) embeds an object address, which re-randomises on
    exactly the process restart a resume spans — the canonical form
    must not. A same-code function object with a different address
    stands in for 'the same scorer after a restart'."""
    import types

    from skdist_tpu.distribute.search import _canonical_value

    def my_scorer(est, X, y):
        return 0.0

    restarted = types.FunctionType(
        my_scorer.__code__, my_scorer.__globals__, my_scorer.__name__
    )
    restarted.__qualname__ = my_scorer.__qualname__
    restarted.__module__ = my_scorer.__module__
    assert repr(restarted) != repr(my_scorer)  # the failure mode
    c = _canonical_value(my_scorer)
    assert "0x" not in c
    assert _canonical_value(restarted) == c
    assert _canonical_value(len) != c
    # nested containers canonicalise element-wise, not by repr
    assert (_canonical_value({"score": my_scorer})
            == _canonical_value({"score": restarted}))


def test_canonical_value_sees_estimator_and_scorer_config():
    """The bare type name is not enough: a retuned nested estimator or
    a different make_scorer must change the signature, or a resume
    silently restores scores computed under the old configuration."""
    from sklearn.metrics import f1_score, make_scorer, precision_score

    from skdist_tpu.distribute.search import _canonical_value

    a = LogisticRegression(max_iter=100)
    b = LogisticRegression(max_iter=2000)
    assert _canonical_value(a) != _canonical_value(b)
    assert _canonical_value(a) == _canonical_value(
        LogisticRegression(max_iter=100)
    )
    f1 = make_scorer(f1_score, average="weighted")
    prec = make_scorer(precision_score, average="weighted")
    assert _canonical_value(f1) != _canonical_value(prec)
    assert _canonical_value(f1) == _canonical_value(
        make_scorer(f1_score, average="weighted")
    )
    assert _canonical_value(f1) != _canonical_value(
        make_scorer(f1_score, average="macro")
    )


def test_object_data_digest_sees_tail_and_size():
    """Object-dtype (raw text) digests must react to tail edits and
    truncation, not just the head sample."""
    docs = np.array([f"document {i}" for i in range(500)], dtype=object)
    tail_edit = docs.copy()
    tail_edit[-1] = "regenerated"
    assert faults.data_digest(docs) == faults.data_digest(docs.copy())
    assert faults.data_digest(docs) != faults.data_digest(tail_edit)
    assert faults.data_digest(docs) != faults.data_digest(docs[:-1])


# ---------------------------------------------------------------------------
# rank-with-NaN pins (satellite): sklearn's rank_test_score convention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("means,expected", [
    # mixed NaN: failed candidates rank strictly last
    ([0.9, np.nan, 0.8], [1, 3, 2]),
    ([np.nan, 0.5, np.nan], [2, 1, 2]),
    # all NaN: everything ties at rank 1 (min method)
    ([np.nan, np.nan, np.nan], [1, 1, 1]),
    # ties: min-method integer ranks, next rank skips
    ([0.9, 0.9, 0.8], [1, 1, 3]),
    ([0.8, 0.9, 0.9, np.nan], [3, 1, 1, 4]),
])
def test_nan_rank_convention(means, expected):
    from scipy.stats import rankdata

    ranks = np.asarray(
        rankdata(-_nan_as_worst(np.asarray(means, float)), method="min"),
        dtype=np.int32,
    )
    assert ranks.tolist() == expected


def test_rank_matches_sklearn_with_failures():
    """End-to-end pin against sklearn: a candidate whose fits all fail
    (error_score=0 stand-in) must rank exactly where sklearn puts it."""
    from sklearn.model_selection import GridSearchCV
    from sklearn.svm import SVC

    rng = np.random.RandomState(0)
    X = rng.normal(size=(60, 4))
    y = (X[:, 0] > 0).astype(int)
    grid = {"C": [1.0, 1e-8]}  # the tiny C scores near-chance
    sk = GridSearchCV(SVC(), grid, cv=3).fit(X, y)
    ours = DistGridSearchCV(SVC(), grid, cv=3).fit(X, y)
    assert (ours.cv_results_["rank_test_score"]
            == sk.cv_results_["rank_test_score"]).all()


# ---------------------------------------------------------------------------
# log_suppressed (satellite: narrowed except swallows)
# ---------------------------------------------------------------------------

def test_log_suppressed_counts_and_dedups(caplog):
    import logging

    with caplog.at_level(logging.WARNING, logger="skdist_tpu.faults"):
        faults.log_suppressed("test.site", ValueError("boom"))
        faults.log_suppressed("test.site", ValueError("boom again"))
    assert faults.snapshot()["suppressed"] == 2
    warned = [r for r in caplog.records if r.levelno >= logging.WARNING
              and "test.site" in r.getMessage()]
    assert len(warned) == 1  # first occurrence warns, repeats go DEBUG


# ---------------------------------------------------------------------------
# serving: circuit breaker + watchdog
# ---------------------------------------------------------------------------

class _StubModel:
    def __init__(self, fail=0, hang_s=0.0):
        self.classes_ = np.array([0, 1])
        self.fail = fail
        self.hang_s = hang_s

    def predict(self, X):
        import time

        if self.hang_s:
            time.sleep(self.hang_s)
        if self.fail > 0:
            self.fail -= 1
            raise RuntimeError("UNAVAILABLE: stub transport down")
        return np.zeros(len(X))

    def get_params(self, deep=False):
        return {}


def test_circuit_breaker_unit_fake_clock():
    t = [0.0]
    cb = faults.CircuitBreaker(threshold=2, cooldown_s=10.0,
                               clock=lambda: t[0])
    key = "m@1"
    assert cb.allow(key)
    assert not cb.record_failure(key, faults.TRANSIENT)
    assert cb.record_failure(key, faults.TRANSIENT)  # opened
    assert cb.state(key) == "open"
    assert not cb.allow(key)
    t[0] = 11.0  # cooldown passed: exactly one probe admitted
    assert cb.state(key) == "half-open"
    assert cb.allow(key)
    assert not cb.allow(key)
    cb.record_success(key)
    assert cb.state(key) == "closed"
    assert cb.allow(key)
    # failed probe re-opens and restarts the cooldown
    cb.record_failure(key, faults.TRANSIENT)
    cb.record_failure(key, faults.TRANSIENT)
    t[0] = 22.0
    assert cb.allow(key)
    cb.record_failure(key, faults.TRANSIENT)
    assert not cb.allow(key)
    # an ABANDONED probe (outcome never reported) expires after another
    # cooldown instead of latching the circuit open forever
    t[0] = 33.0
    assert cb.allow(key)  # probe taken, then dropped
    t[0] = 44.0
    assert cb.allow(key)


def test_serving_circuit_opens_and_sheds():
    from skdist_tpu.serve import CircuitOpen, ServingEngine

    eng = ServingEngine(max_delay_ms=0.5, breaker_threshold=2,
                        breaker_cooldown_s=60.0)
    try:
        eng.register("sick", _StubModel(fail=100), prewarm=False)
        eng.register("ok", _StubModel(), prewarm=False)
        seen = []
        for _ in range(4):
            try:
                eng.predict(np.zeros((2, 4), np.float32), model="sick",
                            timeout_s=5.0)
            except CircuitOpen:
                seen.append("open")
            except RuntimeError:
                seen.append("err")
        assert seen == ["err", "err", "open", "open"]
        stats = eng.stats()
        assert stats["circuit_breaker"]["sick@1"] == "open"
        # load-shed rejections must NOT pollute the dispatch-error
        # alerting signal: only the 2 real failures count there
        assert stats["rejected_circuit"] == 2
        assert stats["dispatch_errors"] == 2
        # a healthy version keeps serving
        out = eng.predict(np.zeros((2, 4), np.float32), model="ok",
                          timeout_s=5.0)
        assert out.shape == (2,)
    finally:
        eng.close(timeout=5.0)


def test_serving_breaker_recovers_on_success():
    from skdist_tpu.serve import ServingEngine

    eng = ServingEngine(max_delay_ms=0.5, breaker_threshold=3,
                        breaker_cooldown_s=60.0)
    try:
        eng.register("flaky", _StubModel(fail=2), prewarm=False)
        for _ in range(2):
            with pytest.raises(RuntimeError):
                eng.predict(np.zeros((1, 4), np.float32), model="flaky",
                            timeout_s=5.0)
        # third request succeeds -> consecutive counter resets, closed
        eng.predict(np.zeros((1, 4), np.float32), model="flaky",
                    timeout_s=5.0)
        assert eng.stats()["circuit_breaker"]["flaky@1"] == "closed"
    finally:
        eng.close(timeout=5.0)


def test_serving_watchdog_trips():
    from skdist_tpu.serve import ServingEngine

    eng = ServingEngine(max_delay_ms=0.5, watchdog_ms=80.0)
    try:
        eng.register("slow", _StubModel(hang_s=1.5), prewarm=False)
        with pytest.raises(faults.WatchdogTimeout):
            eng.predict(np.zeros((1, 4), np.float32), model="slow",
                        timeout_s=5.0)
        assert faults.snapshot()["watchdog_trips"] == 1
        assert eng.stats()["watchdog_ms"] == 80.0
    finally:
        eng.close(timeout=5.0)


def test_serving_watchdog_env_default(monkeypatch):
    from skdist_tpu.serve import ServingEngine

    monkeypatch.setenv("SKDIST_SERVE_WATCHDOG_MS", "123")
    eng = ServingEngine()
    assert eng.watchdog_s == 0.123
    eng.close()
    monkeypatch.setenv("SKDIST_SERVE_WATCHDOG_MS", "fast")
    eng = ServingEngine()
    assert eng.watchdog_s is None  # malformed -> disabled, not a crash
    eng.close()
    # 0 means OFF (the repo's env-knob convention), not a 0 ms budget
    # that would trip every dispatch and open every circuit
    monkeypatch.setenv("SKDIST_SERVE_WATCHDOG_MS", "0")
    eng = ServingEngine()
    assert eng.watchdog_s is None
    eng.close()
    eng = ServingEngine(watchdog_ms=0)
    assert eng.watchdog_s is None
    eng.close()


# ---------------------------------------------------------------------------
# injection harness self-checks
# ---------------------------------------------------------------------------

def test_injector_rules_and_budget():
    inj = FaultInjector().at_round(0, kind="transient").every(
        3, kind="nan", lanes=[1], times=2
    )
    with inj:
        assert faults.active_injector() is inj
        with pytest.raises(RuntimeError, match="UNAVAILABLE"):
            inj.round_dispatched()
        for _ in range(6):
            inj.round_dispatched()
    assert faults.active_injector() is None
    # ordinal 0 fired transient; ordinals 2 and 5 fired nan (times
    # budget is per matching ordinal)
    assert inj.fired == [(0, "transient"), (2, "nan"), (5, "nan")]


def test_injector_unknown_kind():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultInjector().at_round(0, kind="gremlins")


def test_injector_nan_poisons_only_planned_lanes():
    inj = FaultInjector().at_round(0, kind="nan", lanes=[0, 2])
    with inj:
        o = inj.round_dispatched()
        out = inj.transform_output(o, {"v": np.ones((4, 2), np.float32)})
    assert np.isnan(out["v"][0]).all() and np.isnan(out["v"][2]).all()
    assert np.isfinite(out["v"][1]).all() and np.isfinite(out["v"][3]).all()
