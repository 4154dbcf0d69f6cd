"""Out-of-core streaming data plane: ChunkedDataset, the
double-buffered block pipeline, streamed solver drivers, streamed
predict/search/OvR, and the fault-retry offset contract."""

import os
import warnings

import numpy as np
import pytest
import scipy.sparse as sp
from sklearn.datasets import make_classification
from sklearn.model_selection import KFold, ShuffleSplit

from skdist_tpu.data import ChunkedDataset, is_chunked
from skdist_tpu.distribute.multiclass import (
    DistOneVsOneClassifier,
    DistOneVsRestClassifier,
)
from skdist_tpu.distribute.predict import batch_predict
from skdist_tpu.distribute.search import DistGridSearchCV
from skdist_tpu.models.linear import (
    LinearSVC,
    LogisticRegression,
    Ridge,
    RidgeClassifier,
    SGDClassifier,
)
from skdist_tpu.parallel import LocalBackend, faults
from skdist_tpu.parallel.backend import BlockFeeder
from skdist_tpu.testing.faultinject import FaultInjector


def _clf_data(n=640, d=12, k=3, seed=0, sep=1.0):
    X, y = make_classification(
        n_samples=n, n_features=d, n_informative=max(2, d - 4),
        n_classes=k, class_sep=sep, random_state=seed,
    )
    return X.astype(np.float32), y


# ---------------------------------------------------------------------------
# ChunkedDataset unit behaviour
# ---------------------------------------------------------------------------

class TestChunkedDataset:
    def test_shape_blocks_and_padding(self):
        X, y = _clf_data(n=250)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=100)
        assert ds.shape == (250, 12)
        assert ds.n_blocks == 3
        b = ds.read_block(2)  # padded tail
        assert b.X.shape == (100, 12)
        assert b.n_real == 50
        assert (b.sw[50:] == 0).all()  # padding rows carry zero weight
        raw = ds.read_block(2, pad=False)
        assert raw.X.shape == (50, 12)
        np.testing.assert_array_equal(ds.load_y(), y)

    def test_save_load_roundtrip_memmap(self, tmp_path):
        X, y = _clf_data(n=330)
        sw = np.random.RandomState(0).rand(330).astype(np.float32)
        ds = ChunkedDataset.from_arrays(X, y, sw, block_rows=64)
        ds.save(str(tmp_path / "ds"))
        back = ChunkedDataset.load(str(tmp_path / "ds"))
        assert back.shape == ds.shape
        assert back.block_rows == 64
        np.testing.assert_array_equal(back.load_y(), y)
        np.testing.assert_allclose(back.load_sw(), sw)
        np.testing.assert_array_equal(back.materialize(), X)
        # readers are lazy views of the memmap: loading holds no X copy
        assert back.block_nbytes < X.nbytes

    def test_packed_blocks_uniform_width(self, tmp_path):
        Xs = sp.random(300, 256, density=0.02, format="csr",
                       random_state=0, dtype=np.float32)
        ds = ChunkedDataset.from_arrays(Xs, block_rows=90, pack=True)
        assert ds.x_format == "packed"
        widths = {ds.read_block(i).X.m for i in range(ds.n_blocks)}
        assert len(widths) == 1  # dataset-wide m: one compiled shape
        ds.save(str(tmp_path / "sp"))
        back = ChunkedDataset.load(str(tmp_path / "sp"))
        assert back.x_format == "packed"
        np.testing.assert_allclose(
            back.materialize().toarray(), Xs.toarray(), atol=1e-6
        )

    def test_from_arrays_is_lazy_over_memmap(self, tmp_path):
        path = str(tmp_path / "X.npy")
        X = np.arange(500 * 8, dtype=np.float32).reshape(500, 8)
        np.save(path, X)
        mm = np.load(path, mmap_mode="r")
        ds = ChunkedDataset.from_arrays(mm, block_rows=128)
        np.testing.assert_array_equal(ds.read_block(1).X, X[128:256])

    def test_map_blocks(self):
        X, y = _clf_data(n=200)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=64)
        doubled = ds.map_blocks(
            lambda b, s, e: {"X": b["X"] * 2.0}, n_features=12
        )
        np.testing.assert_allclose(
            doubled.read_block(0).X, X[:64] * 2.0
        )
        np.testing.assert_array_equal(doubled.load_y(), y)


# ---------------------------------------------------------------------------
# the block feeder
# ---------------------------------------------------------------------------

class TestBlockFeeder:
    def _reads(self, log):
        def read(i):
            log.append(i)
            return {"x": np.full(4, i, np.float32)}

        return read

    def test_order_and_stats(self):
        log = []
        stats = {}
        feeder = BlockFeeder(self._reads(log), 5, lambda t: t,
                             stats=stats)
        seen = [i for i, _ in feeder]
        assert seen == [0, 1, 2, 3, 4]
        assert stats["blocks_fed"] == 5
        assert stats["streamed_bytes"] == 5 * 16
        assert stats["peak_block_bytes"] == 16
        feeder.close()

    def test_sync_mode(self):
        log = []
        stats = {}
        feeder = BlockFeeder(self._reads(log), 3, lambda t: t,
                             sync=True, stats=stats)
        assert [i for i, _ in feeder] == [0, 1, 2]
        assert stats["stream_mode"] == "serial"

    def test_seek_reopens_reader_at_offset(self):
        log = []
        feeder = BlockFeeder(self._reads(log), 4, lambda t: t)
        i0, _ = feeder.next()
        i1, _ = feeder.next()
        assert (i0, i1) == (0, 1)
        feeder.seek(1)
        i, dev = feeder.next()
        assert i == 1  # the reader RE-OPENED at the failed offset
        assert log.count(1) >= 2  # genuinely re-read, nothing stale
        feeder.close()

    def test_read_error_surfaces_at_next(self):
        def bad(i):
            if i == 1:
                raise OSError("disk gone")
            return {"x": np.zeros(1)}

        feeder = BlockFeeder(bad, 3, lambda t: t)
        feeder.next()
        with pytest.raises(OSError):
            feeder.next()
            feeder.next()
        feeder.close()


# ---------------------------------------------------------------------------
# streamed-vs-resident parity: both solver families, dense and packed,
# weighted and fold-masked
# ---------------------------------------------------------------------------

class TestStreamedFitParity:
    @pytest.mark.parametrize("seed,block_rows,k", [
        (0, 100, 3), (1, 128, 2), (2, 90, 4),
    ])
    def test_lbfgs_dense_vs_resident_fuzz(self, seed, block_rows, k):
        X, y = _clf_data(seed=seed, k=k)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=block_rows)
        s = LogisticRegression(C=0.7, tol=1e-6, max_iter=200,
                               engine="xla").fit(ds)
        r = LogisticRegression(C=0.7, tol=1e-6, max_iter=200,
                               engine="xla").fit(X, y)
        np.testing.assert_allclose(s.coef_, r.coef_, atol=5e-4)
        assert (s.predict(X) == r.predict(X)).mean() > 0.995

    def test_lbfgs_weighted(self):
        X, y = _clf_data(k=2)
        sw = np.random.RandomState(1).rand(len(y)).astype(np.float32)
        ds = ChunkedDataset.from_arrays(X, y, sw, block_rows=128)
        s = LinearSVC(C=0.5, tol=1e-6, max_iter=300,
                      engine="xla").fit(ds)
        r = LinearSVC(C=0.5, tol=1e-6, max_iter=300,
                      engine="xla").fit(X, y, sample_weight=sw)
        np.testing.assert_allclose(s.coef_, r.coef_, atol=5e-4)

    def test_lbfgs_packed_csr(self):
        rng = np.random.RandomState(2)
        Xs = sp.random(400, 512, density=0.02, format="csr",
                       random_state=2, dtype=np.float32)
        y = rng.randint(0, 2, 400)
        ds = ChunkedDataset.from_arrays(Xs, y, block_rows=100, pack=True)
        assert ds.x_format == "packed"
        s = LogisticRegression(C=1.0, tol=1e-6, max_iter=100,
                               engine="xla").fit(ds)
        r = LogisticRegression(C=1.0, tol=1e-6, max_iter=100,
                               engine="xla").fit(Xs, y)
        np.testing.assert_allclose(s.coef_, r.coef_, atol=5e-4)

    @pytest.mark.parametrize("seed,loss,penalty,k", [
        (0, "log_loss", "l2", 3),
        (1, "hinge", "l2", 2),
        (2, "squared_hinge", "elasticnet", 2),
    ])
    def test_sgd_aligned_bitwise_vs_resident_fuzz(self, seed, loss,
                                                  penalty, k):
        # block boundaries aligned to batches + shuffle=False: the
        # streamed visit order IS the resident scan's — bitwise
        X, y = _clf_data(n=640, seed=seed, k=k)
        sw = np.random.RandomState(seed).rand(640).astype(np.float32)
        ds = ChunkedDataset.from_arrays(X, y, sw, block_rows=128)
        kw = dict(loss=loss, penalty=penalty, max_iter=8,
                  batch_size=64, shuffle=False, tol=None)
        s = SGDClassifier(**kw).fit(ds)
        r = SGDClassifier(**kw).fit(X, y, sample_weight=sw)
        # equal_nan: a hyper config that diverges must diverge
        # IDENTICALLY on both paths (same trajectory, same NaNs)
        assert np.array_equal(np.asarray(s.coef_), np.asarray(r.coef_),
                              equal_nan=True)
        assert np.array_equal(np.asarray(s.intercept_),
                              np.asarray(r.intercept_), equal_nan=True)

    def test_sgd_early_stop_bitwise(self):
        X, y = _clf_data(n=512, k=2)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=128)
        kw = dict(loss="log_loss", max_iter=30, batch_size=64,
                  shuffle=False, tol=1e-3)
        s = SGDClassifier(**kw).fit(ds)
        r = SGDClassifier(**kw).fit(X, y)
        assert int(np.asarray(s.n_iter_)) == int(np.asarray(r.n_iter_))
        assert np.array_equal(np.asarray(s.coef_), np.asarray(r.coef_))

    def test_sgd_wrap_tail_runs(self):
        # n not divisible by batch_size: the tail batch wraps to the
        # dataset head, like the resident arange(padded) % n
        X, y = _clf_data(n=500, k=2)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=128)
        s = SGDClassifier(loss="log_loss", max_iter=4, batch_size=64,
                          shuffle=False, tol=None).fit(ds)
        r = SGDClassifier(loss="log_loss", max_iter=4, batch_size=64,
                          shuffle=False, tol=None).fit(X, y)
        assert np.array_equal(np.asarray(s.coef_), np.asarray(r.coef_))

    def test_sgd_dataset_smaller_than_batch(self):
        # a dataset smaller than one batch cycles its rows exactly
        # like the resident arange(padded) % n wrap
        X, y = _clf_data(n=10, k=2, d=4)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=10)
        kw = dict(loss="log_loss", max_iter=3, batch_size=64,
                  shuffle=False, tol=None)
        s = SGDClassifier(**kw).fit(ds)
        r = SGDClassifier(**kw).fit(X, y)
        # NOT bitwise, unlike its siblings: with 10 rows under a
        # 64-row batch the two sides are differently SHAPED XLA
        # programs (the streamed step sees the 10-row block cycled,
        # the resident one a gathered (64, d) batch), the batch
        # gradient's row reduction is tiled differently, and the
        # installed XLA rounds the two orders 1-2 ulp apart in f32.
        # Three epochs of that stay within a few ulp OF THE UPDATES'
        # SCALE (the largest coefficient: a small coefficient is a sum
        # of such updates and inherits their absolute rounding); where
        # both sides reduce batches of one shape (the tests around
        # this one) the equality stays exact.
        sc, rc = np.asarray(s.coef_), np.asarray(r.coef_)
        np.testing.assert_allclose(
            sc, rc, rtol=0, atol=4 * np.spacing(np.abs(rc).max()))

    def test_sgd_single_block_wrap(self):
        # one full block whose row count is not a batch multiple: the
        # epoch's wrap batch must still run (resident arange % n)
        X, y = _clf_data(n=100, k=2, d=6)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=100)
        kw = dict(loss="log_loss", max_iter=3, batch_size=64,
                  shuffle=False, tol=None)
        s = SGDClassifier(**kw).fit(ds)
        r = SGDClassifier(**kw).fit(X, y)
        assert np.array_equal(np.asarray(s.coef_), np.asarray(r.coef_))

    def test_sgd_misaligned_blocks_raise(self):
        X, y = _clf_data(n=300)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=100)
        with pytest.raises(ValueError, match="divisible"):
            SGDClassifier(batch_size=64, loss="log_loss").fit(ds)

    def test_sgd_shuffled_l1_converges(self):
        X, y = _clf_data(n=512, k=2, sep=2.0)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=128)
        s = SGDClassifier(loss="log_loss", penalty="elasticnet",
                          l1_ratio=0.3, max_iter=20, batch_size=64,
                          shuffle=True, tol=None).fit(ds)
        assert (s.predict(X) == y).mean() > 0.9

    def test_gram_families(self):
        X, y = _clf_data(n=500, k=3)
        rng = np.random.RandomState(0)
        yr = (X @ rng.randn(12).astype(np.float32)).astype(np.float32)
        dsr = ChunkedDataset.from_arrays(X, yr, block_rows=100)
        rs = Ridge(alpha=2.0).fit(dsr)
        rr = Ridge(alpha=2.0).fit(X, yr)
        np.testing.assert_allclose(rs.coef_, rr.coef_, rtol=2e-2,
                                   atol=2e-2)
        np.testing.assert_allclose(
            rs.predict(X), rr.predict(X), atol=1e-2, rtol=1e-2
        )
        dsc = ChunkedDataset.from_arrays(X, y, block_rows=100)
        cs = RidgeClassifier(alpha=1.0).fit(dsc)
        cr = RidgeClassifier(alpha=1.0).fit(X, y)
        assert (cs.predict(X) == cr.predict(X)).mean() > 0.99

    def test_serial_vs_pipelined_bitwise(self):
        # the double buffer must be invisible in the numbers: same
        # blocks, same order, same programs
        X, y = _clf_data(n=512, k=2)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=128)
        piped = LogisticRegression(C=1.0, tol=1e-5, max_iter=50,
                                   engine="xla").fit(ds)
        os.environ["SKDIST_SYNC_ROUNDS"] = "1"
        try:
            serial = LogisticRegression(C=1.0, tol=1e-5, max_iter=50,
                                        engine="xla").fit(ds)
        finally:
            del os.environ["SKDIST_SYNC_ROUNDS"]
        assert np.array_equal(np.asarray(piped.coef_),
                              np.asarray(serial.coef_))

    def test_disk_backed_equals_in_memory(self, tmp_path):
        X, y = _clf_data(n=384, k=2)
        ds_mem = ChunkedDataset.from_arrays(X, y, block_rows=128)
        ds_mem.save(str(tmp_path / "d"))
        ds_disk = ChunkedDataset.load(str(tmp_path / "d"))
        a = LogisticRegression(max_iter=40, engine="xla").fit(ds_mem)
        b = LogisticRegression(max_iter=40, engine="xla").fit(ds_disk)
        assert np.array_equal(np.asarray(a.coef_), np.asarray(b.coef_))

    def test_engine_host_pin_rejected(self):
        X, y = _clf_data(n=200, k=2)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=100)
        with pytest.raises(ValueError, match="engine='host'"):
            LogisticRegression(engine="host").fit(ds)

    def test_balanced_class_weight_rejected(self):
        X, y = _clf_data(n=200, k=2)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=100)
        with pytest.raises(ValueError, match="balanced"):
            LogisticRegression(class_weight="balanced").fit(ds)

    def test_byte_accounting(self):
        X, y = _clf_data(n=512, k=2)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=128)
        backend = LocalBackend()
        from skdist_tpu.models.streaming import stream_fit_estimator

        stream_fit_estimator(
            LogisticRegression(max_iter=20, engine="xla"), ds,
            backend=backend,
        )
        stats = backend.last_round_stats
        assert stats["mode"] == "streamed"
        assert stats["streamed_bytes"] > 0
        assert stats["peak_block_bytes"] >= ds.block_nbytes // 2
        assert stats["peak_block_bytes"] <= 2 * ds.block_nbytes
        assert stats["blocks_fed"] >= ds.n_blocks


# ---------------------------------------------------------------------------
# fault injection: mid-stream transient -> reader re-opened at offset
# ---------------------------------------------------------------------------

class TestStreamFaults:
    def test_transient_midstream_retries_to_identical_fit(self):
        X, y = _clf_data(n=512, k=2)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=128)
        kw = dict(loss="log_loss", max_iter=5, batch_size=64,
                  shuffle=False, tol=None)
        clean = SGDClassifier(**kw).fit(ds)
        faults.reset_stats()
        inj = FaultInjector().at_round(2, kind="transient")
        with inj, warnings.catch_warnings():
            warnings.simplefilter("ignore")
            faulted = SGDClassifier(**kw).fit(ds)
        assert "transient" in [kind for _ord, kind in inj.fired]
        assert faults.snapshot().get("rounds_retried", 0) >= 1
        # the failed block re-read at the right offset and re-run:
        # bitwise identical to the undisturbed fit
        assert np.array_equal(np.asarray(clean.coef_),
                              np.asarray(faulted.coef_))

    def test_transient_lbfgs_pass(self):
        X, y = _clf_data(n=384, k=2)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=128)
        clean = LogisticRegression(max_iter=30, tol=1e-5,
                                   engine="xla").fit(ds)
        inj = FaultInjector().at_round(1, kind="transient")
        with inj, warnings.catch_warnings():
            warnings.simplefilter("ignore")
            faulted = LogisticRegression(max_iter=30, tol=1e-5,
                                         engine="xla").fit(ds)
        assert np.array_equal(np.asarray(clean.coef_),
                              np.asarray(faulted.coef_))

    def test_fatal_propagates(self):
        X, y = _clf_data(n=256, k=2)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=128)
        inj = FaultInjector().at_round(1, kind="fatal")
        with inj, pytest.raises(Exception, match="(?i)fatal|injected"):
            LogisticRegression(max_iter=10, engine="xla").fit(ds)


# ---------------------------------------------------------------------------
# streamed predict
# ---------------------------------------------------------------------------

class TestStreamedPredict:
    def test_byte_identical_to_blocked_resident(self):
        X, y = _clf_data(n=1000, k=3)
        est = LogisticRegression(max_iter=50, engine="xla").fit(X, y)
        ds = ChunkedDataset.from_arrays(X, block_rows=128)
        np.testing.assert_array_equal(
            batch_predict(est, ds), batch_predict(est, X, batch_size=128)
        )
        np.testing.assert_array_equal(
            batch_predict(est, ds, method="predict_proba"),
            batch_predict(est, X, method="predict_proba",
                          batch_size=128),
        )

    def test_packed_dataset_predict(self):
        Xs = sp.random(500, 512, density=0.02, format="csr",
                       random_state=0, dtype=np.float32)
        y = np.arange(500) % 2
        est = LogisticRegression(max_iter=30, engine="xla").fit(Xs, y)
        ds = ChunkedDataset.from_arrays(Xs, block_rows=100)
        np.testing.assert_array_equal(
            batch_predict(est, ds), est.predict(Xs)
        )

    def test_host_model_block_fallback(self):
        from sklearn.linear_model import LogisticRegression as SkLR

        X, y = _clf_data(n=300, k=2)
        est = SkLR(max_iter=200).fit(X, y)
        ds = ChunkedDataset.from_arrays(X, block_rows=100)
        np.testing.assert_array_equal(
            batch_predict(est, ds), est.predict(X)
        )

    def test_decision_function_redirects(self):
        X, y = _clf_data(n=200, k=2)
        est = LogisticRegression(max_iter=20, engine="xla").fit(X, y)
        ds = ChunkedDataset.from_arrays(X, block_rows=100)
        with pytest.raises(TypeError, match="batch_predict"):
            est.decision_function(ds)

    def test_default_batch_size_hbm_derived(self):
        # CPU backends report no memory stats -> historical ceiling
        from skdist_tpu.distribute.predict import (
            _MAX_DEFAULT_BATCH, _default_batch_size, device_predict_plan,
        )

        X, y = _clf_data(n=100, k=2)
        est = LogisticRegression(max_iter=10, engine="xla").fit(X, y)
        plan = device_predict_plan(est, "predict")
        backend = LocalBackend()
        assert _default_batch_size(10 ** 9, backend, plan) == \
            _MAX_DEFAULT_BATCH

        class _CappedBackend(LocalBackend):
            def hbm_round_cap(self, bytes_per_task, headroom=0.85):
                # pretend free HBM fits ~1000 rows of this width
                return (1000 * 4 * 14) // bytes_per_task

            _free_device_bytes = None

        capped = _default_batch_size(10 ** 9, _CappedBackend(), plan)
        assert capped < _MAX_DEFAULT_BATCH
        assert capped == 1000


# ---------------------------------------------------------------------------
# streamed search / OvR / encoder
# ---------------------------------------------------------------------------

class TestStreamedSearch:
    def test_grid_parity_and_refit(self):
        X, y = _clf_data(n=600, k=3, sep=2.0)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=120)
        grid = {"C": [0.5, 5.0]}
        gs_s = DistGridSearchCV(
            LogisticRegression(max_iter=80, tol=1e-6, engine="xla"),
            grid, cv=KFold(3),
        ).fit(ds)
        gs_r = DistGridSearchCV(
            LogisticRegression(max_iter=80, tol=1e-6, engine="xla"),
            grid, cv=KFold(3),
        ).fit(X, y)
        np.testing.assert_allclose(
            gs_s.cv_results_["mean_test_score"],
            gs_r.cv_results_["mean_test_score"], atol=1e-5,
        )
        assert gs_s.best_params_ == gs_r.best_params_
        assert hasattr(gs_s.best_estimator_, "_params")
        import pickle

        pickle.loads(pickle.dumps(gs_s))  # artifact pickles clean

    def test_sgd_grid_bitwise_scores(self):
        X, y = _clf_data(n=768, k=2)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=128)
        kw = dict(loss="log_loss", max_iter=5, batch_size=64,
                  shuffle=False, tol=None)
        grid = {"alpha": [1e-4, 1e-2]}
        gs_s = DistGridSearchCV(SGDClassifier(**kw), grid,
                                cv=KFold(3)).fit(ds)
        gs_r = DistGridSearchCV(SGDClassifier(**kw), grid,
                                cv=KFold(3)).fit(X, y)
        np.testing.assert_allclose(
            gs_s.cv_results_["mean_test_score"],
            gs_r.cv_results_["mean_test_score"], atol=1e-6,
        )

    def test_weighted_fold_masked(self):
        X, y = _clf_data(n=600, k=2, sep=2.0)
        sw = np.random.RandomState(3).rand(600).astype(np.float32)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=120)
        gs_s = DistGridSearchCV(
            LogisticRegression(max_iter=60, tol=1e-6, engine="xla"),
            {"C": [1.0]}, cv=KFold(3),
        ).fit(ds, sample_weight=sw)
        gs_r = DistGridSearchCV(
            LogisticRegression(max_iter=60, tol=1e-6, engine="xla"),
            {"C": [1.0]}, cv=KFold(3),
        ).fit(X, y, sample_weight=sw)
        np.testing.assert_allclose(
            gs_s.cv_results_["mean_test_score"],
            gs_r.cv_results_["mean_test_score"], atol=1e-5,
        )

    def test_multimetric_and_train_scores(self):
        X, y = _clf_data(n=480, k=3)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=120)
        gs = DistGridSearchCV(
            LogisticRegression(max_iter=40, engine="xla"),
            {"C": [1.0]}, cv=KFold(3),
            scoring=["accuracy", "f1_macro"], refit="accuracy",
            return_train_score=True,
        ).fit(ds)
        for key in ("mean_test_accuracy", "mean_test_f1_macro",
                    "mean_train_accuracy"):
            assert key in gs.cv_results_
            assert np.isfinite(gs.cv_results_[key]).all()

    def test_train_scores_ignore_tail_padding(self):
        # n not a block multiple: padded rows (fold id -1, label 0,
        # zero X) must not score as correct class-0 train hits
        X, y = _clf_data(n=100, k=2, d=6, sep=2.0)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=64)
        kw = dict(max_iter=60, tol=1e-6, engine="xla")
        gs_s = DistGridSearchCV(
            LogisticRegression(**kw), {"C": [1.0]}, cv=KFold(2),
            return_train_score=True,
        ).fit(ds)
        gs_r = DistGridSearchCV(
            LogisticRegression(**kw), {"C": [1.0]}, cv=KFold(2),
            return_train_score=True,
        ).fit(X, y)
        np.testing.assert_allclose(
            gs_s.cv_results_["mean_train_score"],
            gs_r.cv_results_["mean_train_score"], atol=1e-5,
        )

    def test_non_partition_cv_raises(self):
        X, y = _clf_data(n=300, k=2)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=100)
        with pytest.raises(ValueError, match="partition"):
            DistGridSearchCV(
                LogisticRegression(engine="xla"), {"C": [1.0]},
                cv=ShuffleSplit(n_splits=3, random_state=0),
            ).fit(ds)

    def test_unsupported_scoring_raises(self):
        X, y = _clf_data(n=300, k=2)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=100)
        with pytest.raises(ValueError, match="roc_auc"):
            DistGridSearchCV(
                LogisticRegression(engine="xla"), {"C": [1.0]},
                scoring="roc_auc",
            ).fit(ds)

    def test_unsupported_estimator_raises(self):
        from sklearn.tree import DecisionTreeClassifier

        X, y = _clf_data(n=300, k=2)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=100)
        with pytest.raises(ValueError, match="streamed fit driver"):
            DistGridSearchCV(
                DecisionTreeClassifier(), {"max_depth": [2]},
            ).fit(ds)


class TestStreamedOvR:
    def test_ovr_parity(self):
        X, y = _clf_data(n=600, k=4, d=8)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=120)
        s = DistOneVsRestClassifier(
            LogisticRegression(max_iter=60, tol=1e-6, engine="xla")
        ).fit(ds)
        r = DistOneVsRestClassifier(
            LogisticRegression(max_iter=60, tol=1e-6, engine="xla")
        ).fit(X, y)
        assert (s.predict(X) == r.predict(X)).mean() == 1.0
        # chunked predict rides batch_predict per class
        assert (s.predict(ds) == s.predict(X)).mean() == 1.0

    def test_ovr_binary_reduction(self):
        X, y = _clf_data(n=400, k=2, d=6)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=100)
        s = DistOneVsRestClassifier(
            LogisticRegression(max_iter=50, engine="xla")
        ).fit(ds)
        assert len(s.estimators_) == 1  # positive column only
        proba = s.predict_proba(X)
        np.testing.assert_allclose(proba.sum(axis=1), 1.0, atol=1e-5)

    def test_ovo_pair_masked_streaming_parity(self):
        # each block streams ONCE per solver pass for all k(k-1)/2
        # pairs (pair masks composed on device) and matches the
        # resident batched OvO prediction for prediction
        X, y = _clf_data(n=600, k=4, d=8)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=120)
        s = DistOneVsOneClassifier(
            LogisticRegression(max_iter=60, tol=1e-6, engine="xla")
        ).fit(ds)
        assert len(s.estimators_) == 6
        assert len(s.pairs_) == 6
        r = DistOneVsOneClassifier(
            LogisticRegression(max_iter=60, tol=1e-6, engine="xla")
        ).fit(X, y)
        assert (s.predict(X) == r.predict(X)).mean() == 1.0

    def test_ovo_streamed_guards(self):
        X, y = _clf_data(n=200, k=3)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=100)
        with pytest.raises(ValueError, match="engine='host'"):
            DistOneVsOneClassifier(
                LogisticRegression(engine="host")
            ).fit(ds, y)
        with pytest.raises(ValueError, match="class_weight"):
            DistOneVsOneClassifier(
                LogisticRegression(engine="xla", class_weight="balanced")
            ).fit(ds, y)

    def test_ovr_downsampling_rejected(self):
        X, y = _clf_data(n=200, k=3)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=100)
        with pytest.raises(ValueError, match="max_negatives"):
            DistOneVsRestClassifier(
                LogisticRegression(engine="xla"), max_negatives=0.5
            ).fit(ds)


class TestEncoderPassThrough:
    def test_transform_chunked_blockwise(self):
        from skdist_tpu.distribute.encoder import Encoderizer

        rng = np.random.RandomState(0)
        X = np.column_stack([
            rng.rand(300), rng.rand(300) * 10.0
        ]).astype(np.float32)
        enc = Encoderizer(
            col_names=["a", "b"],
            config={"a": "numeric", "b": "numeric"}, size="small",
        ).fit(X)
        resident = enc.transform(
            __import__("pandas").DataFrame(X, columns=["a", "b"])
        )
        ds = ChunkedDataset.from_arrays(X, block_rows=64)
        out = enc.transform(ds)
        assert is_chunked(out)
        assert out.shape == (300, resident.shape[1])
        np.testing.assert_allclose(
            out.materialize(), np.asarray(resident), atol=1e-5
        )


class TestStreamedMesh:
    """8-virtual-device mesh: the task axis must slot-pad (candidates
    x folds rarely divide the device count) and streamed predict must
    group blocks onto the task slots."""

    def _mesh_backend(self):
        from skdist_tpu.parallel import TPUBackend

        return TPUBackend()  # all 8 virtual CPU devices

    def test_search_on_mesh_slot_pads(self):
        X, y = _clf_data(n=600, k=2, sep=2.0)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=120)
        # 1 candidate x 3 folds = 3 tasks on an 8-slot mesh
        gs_m = DistGridSearchCV(
            LogisticRegression(max_iter=40, tol=1e-6, engine="xla"),
            {"C": [1.0]}, cv=KFold(3), backend=self._mesh_backend(),
        ).fit(ds)
        gs_l = DistGridSearchCV(
            LogisticRegression(max_iter=40, tol=1e-6, engine="xla"),
            {"C": [1.0]}, cv=KFold(3),
        ).fit(ds)
        np.testing.assert_allclose(
            gs_m.cv_results_["mean_test_score"],
            gs_l.cv_results_["mean_test_score"], atol=1e-5,
        )

    def test_sgd_fit_on_mesh(self):
        X, y = _clf_data(n=512, k=2)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=128)
        kw = dict(loss="log_loss", max_iter=4, batch_size=64,
                  shuffle=False, tol=None)
        from skdist_tpu.models.streaming import stream_fit_estimator

        s = stream_fit_estimator(SGDClassifier(**kw), ds,
                                 backend=self._mesh_backend())
        r = SGDClassifier(**kw).fit(X, y)
        np.testing.assert_allclose(np.asarray(s.coef_),
                                   np.asarray(r.coef_), atol=1e-6)

    def test_predict_groups_blocks_on_mesh(self):
        X, y = _clf_data(n=1000, k=3)
        est = LogisticRegression(max_iter=40, engine="xla").fit(X, y)
        ds = ChunkedDataset.from_arrays(X, block_rows=128)  # 8 blocks
        p_mesh = batch_predict(est, ds, backend=self._mesh_backend())
        np.testing.assert_array_equal(p_mesh, est.predict(X))


class TestNoRecompileStreaming:
    def test_second_fit_hits_caches(self):
        from skdist_tpu.parallel import compile_cache

        X, y = _clf_data(n=512, k=2)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=128)
        kw = dict(C=1.0, tol=1e-5, max_iter=30, engine="xla")
        LogisticRegression(**kw).fit(ds)  # warm
        before = compile_cache.snapshot()
        LogisticRegression(**kw).fit(ds)
        after = compile_cache.snapshot()
        assert after["jit_misses"] == before["jit_misses"]
        assert after["kernel_misses"] == before["kernel_misses"]
