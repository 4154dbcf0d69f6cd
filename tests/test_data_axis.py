"""A mesh with a ``data`` axis (``TPUBackend(data_axis_size=...)``):
the rows of every shared operand cut between that many devices, every
lane on every device. Small sizes on the CPU's virtual devices: what a
search answers there against the 1D mesh, where its X lies (four row
shards, never whole on one device, the refit included), what round
sizing counts (one device's share), how a row-sharded host array is
placed (in blocks a shard), and what the spans and the round stats say
of it. The step program at the benchmark's size is compiled for a
described chip in ``tests/test_tpu_compile.py``."""

import jax
import numpy as np
import pytest

from skdist_tpu.distribute.search import DistGridSearchCV
from skdist_tpu.models import LogisticRegression
from skdist_tpu.obs import trace as obs_trace
from skdist_tpu.parallel import TPUBackend
from skdist_tpu.parallel import backend as backend_mod


def _blobs(n=480, d=24, k=10, seed=0):
    """Ten overlapping, well-conditioned classes: fits that converge,
    the best of them under real regularisation, so that two orders of
    summation end at the same point."""
    rng = np.random.RandomState(seed)
    centres = 0.6 * rng.normal(size=(k, d))
    y = np.arange(n) % k
    X = (centres[y] + rng.normal(size=(n, d))).astype(np.float32)
    return X, y


def _search(backend, **kw):
    return DistGridSearchCV(
        LogisticRegression(max_iter=300, tol=1e-6, engine="xla"),
        {"C": [0.001, 0.01, 0.1, 1.0, 10.0]}, cv=5,
        scoring="neg_log_loss", backend=backend, **kw)


def _buffers(arr):
    return [s.data.unsafe_buffer_pointer() for s in arr.addressable_shards]


def _fold_scores(gs):
    return np.array([gs.cv_results_[f"split{s}_test_score"]
                     for s in range(5)])


@pytest.fixture
def tracing():
    obs_trace.clear()
    obs_trace.set_enabled(True)
    yield
    obs_trace.set_enabled(False)
    obs_trace.clear()


def test_a_data_axis_search_answers_as_the_1d_mesh_and_never_holds_x_whole(
        monkeypatch, tracing):
    """(a) of ISSUE 35: every fold's ``neg_log_loss`` within 1e-5 of
    the 1D backend's, ``best_estimator_.coef_`` within 1e-4; every
    placement of X is four row shards, the refit's too (it takes the
    shards the search placed), and nothing puts X whole on a device."""
    X, y = _blobs()
    flat = _search(TPUBackend(devices=jax.devices()[:1])).fit(X, y)
    whole, sharded, handed_on = [], [], []
    real_put, real_scoped = (backend_mod.put_host_array,
                             backend_mod._put_mesh_scoped)

    def put_host_array(x, sharding=None):
        if getattr(x, "shape", None) == X.shape:
            whole.append(sharding)
        return real_put(x, sharding)

    def put_mesh_scoped(x, sharding):
        out = real_scoped(x, sharding)
        if getattr(x, "shape", None) == X.shape:
            (sharded if isinstance(x, np.ndarray) else handed_on).append(
                (x, out))
        return out

    monkeypatch.setattr(backend_mod, "put_host_array", put_host_array)
    monkeypatch.setattr(backend_mod, "_put_mesh_scoped", put_mesh_scoped)
    obs_trace.clear()
    backend = TPUBackend(devices=jax.devices()[:4], data_axis_size=4)
    cut = _search(backend).fit(X, y)
    fit_whole = list(whole)  # (a later ``predict`` places its own input)

    np.testing.assert_allclose(_fold_scores(cut), _fold_scores(flat),
                               atol=1e-5)
    assert cut.best_params_ == flat.best_params_
    np.testing.assert_allclose(cut.best_estimator_.coef_,
                               flat.best_estimator_.coef_, atol=1e-4)
    np.testing.assert_allclose(cut.best_estimator_.intercept_,
                               flat.best_estimator_.intercept_, atol=1e-4)
    assert (cut.predict(X) == flat.predict(X)).all()
    # X went to the devices ONCE, as four shards of 120 rows: the
    # search placed it, and its dispatch was handed those shards and
    # left them where they were
    assert not fit_whole
    assert len(sharded) == 1
    placed = sharded[0][1]
    assert placed.sharding.shard_shape(X.shape) == (120, 24)
    assert sorted(s.index[0].start or 0
                  for s in placed.addressable_shards) == [0, 120, 240, 360]
    assert {s.device for s in placed.addressable_shards} == set(
        jax.devices()[:4])
    assert handed_on and all(
        x is placed and _buffers(out) == _buffers(placed)
        for x, out in handed_on)
    # the refit is a span with a placement of its own under it (labels
    # and weights; X by reference), on four shards
    spans = [e for e in obs_trace.events() if e[1] == "X"]
    refit = next(e for e in spans if e[0] == "refit")
    under = [e for e in spans if e[0] == "place_shared"
             and e[5]["parent_id"] == refit[5]["span_id"]]
    assert len(under) == 1 and under[0][5]["shards"] == 4
    # the search's stats are the backend's last: the refit books none
    assert backend.last_round_stats["tasks"] == 25


@pytest.mark.parametrize("n_devices", [1, 4])
def test_on_a_1d_mesh_x_crosses_once_and_every_program_runs_over_it(
        monkeypatch, tracing, n_devices):
    """ISSUE 36: without a ``data`` axis too the search places X once
    a fit — a replica a device — and the operand every bucket's
    dispatch and the refit's kernel receive IS that array, same
    buffers; the refit runs over the first replica, as the one-device
    program a standalone ``fit`` runs, and places labels and weights
    only."""
    from skdist_tpu.models import linear

    X, y = _blobs()
    host, handed_on, refit_X = [], [], []
    real_scoped, real_kernel = (backend_mod._put_mesh_scoped,
                                linear.get_kernel)

    def put_mesh_scoped(x, sharding):
        out = real_scoped(x, sharding)
        if getattr(x, "shape", None) == X.shape:
            (host if isinstance(x, np.ndarray) else handed_on).append(
                (x, out))
        return out

    def get_kernel(cls, which, meta, static):
        kernel = real_kernel(cls, which, meta, static)
        if which != "fit":
            return kernel

        def fit_kernel(X_, *rest):
            refit_X.append(X_)
            return kernel(X_, *rest)

        return fit_kernel

    monkeypatch.setattr(backend_mod, "_put_mesh_scoped", put_mesh_scoped)
    real_put = backend_mod.put_host_array
    monkeypatch.setattr(
        backend_mod, "put_host_array",
        lambda x, sharding=None: real_put(x, sharding)
        if sharding is not None or getattr(x, "shape", None) != X.shape
        else pytest.fail("X went to the default device: a second crossing"))
    monkeypatch.setattr(linear, "get_kernel", get_kernel)
    backend = TPUBackend(devices=jax.devices()[:n_devices])
    # two static buckets: two dispatches over one placement
    gs = DistGridSearchCV(
        LogisticRegression(max_iter=300, tol=1e-6, engine="xla"),
        {"C": [0.01, 1.0], "fit_intercept": [True, False]}, cv=5,
        scoring="neg_log_loss", backend=backend).fit(X, y)
    monkeypatch.undo()

    (_, placed), = host
    assert placed.sharding.is_fully_replicated
    assert {s.device for s in placed.addressable_shards} == set(
        jax.devices()[:n_devices])
    assert len(handed_on) == 2 and all(
        x is placed and _buffers(out) == _buffers(placed)
        for x, out in handed_on)
    (over,) = refit_X
    assert over.shape == X.shape and len(over.devices()) == 1
    assert _buffers(over) == _buffers(placed)[:1]
    # the spans: X by itself, a dispatch a bucket with X by reference,
    # and under the refit labels and weights only
    spans = [e for e in obs_trace.events() if e[1] == "X"]
    refit = next(e for e in spans if e[0] == "refit")
    places = [e for e in spans if e[0] == "place_shared"]
    assert [e[5]["bytes"] == X.nbytes for e in places] == [
        True, False, False, False]
    (under,) = [e for e in places
                if e[5]["parent_id"] == refit[5]["span_id"]]
    assert under[5]["bytes"] == 8 * len(y) == refit[5]["bytes"]
    assert refit[5]["x_placed"] is True
    # and the model is the standalone fit's, to the bit
    alone = LogisticRegression(
        max_iter=300, tol=1e-6, engine="xla", **gs.best_params_).fit(X, y)
    np.testing.assert_array_equal(gs.best_estimator_.coef_, alone.coef_)
    np.testing.assert_array_equal(gs.best_estimator_.intercept_,
                                  alone.intercept_)
    assert not hasattr(gs, "_rounds_X_")


def test_the_spans_and_the_round_stats_say_what_a_device_holds(tracing):
    """``place_shared`` carries ``shards`` and ``bytes_per_device``;
    the round stats ``data_shards``, a device's share under
    ``shared_bytes`` / ``logits_bytes``, and the collectives of the
    compiled step program — 1 / the whole / none on one device."""
    X, y = _blobs(n=800)
    stats, placed = {}, {}
    for shards in (1, 4):
        obs_trace.clear()
        backend = TPUBackend(devices=jax.devices()[:shards],
                             data_axis_size=shards)
        _search(backend, refit=False).fit(X, y)
        stats[shards] = dict(backend.last_round_stats)
        placed[shards] = [e[5] for e in obs_trace.events()
                          if e[0] == "place_shared"]
    one, four = stats[1], stats[4]
    assert (one["data_shards"], four["data_shards"]) == (1, 4)
    assert one["mode"] == four["mode"] == "compacted"
    # X, y, the weights and both masks are all cut by rows
    assert one["shared_bytes"] == 4 * four["shared_bytes"]
    assert one["logits_bytes"] == 4 * four["logits_bytes"] > 0
    # weights and history are whole on every device
    assert (one["lane_bytes"] - one["logits_bytes"]
            == pytest.approx(four["lane_bytes"] - four["logits_bytes"],
                             rel=0.02))
    assert four["round_bytes_estimate"] < one["round_bytes_estimate"]
    assert (one["collective_ops_compiled"],
            one["collective_bytes_compiled"]) == (0, 0)
    # partial sums only: a few all-reduces whose results are a round's
    # losses and gradients, nothing with the rows' axis
    assert 1 <= four["collective_ops_compiled"] <= 8
    lanes = four["chunk"]
    assert 0 < four["collective_bytes_compiled"] <= (
        3 * lanes * (25 * 10 + 2) * 4)
    # on every mesh the search places X by itself first (every bucket
    # and the refit are to run over it), and the dispatch's span then
    # counts the whole tree, X by reference
    (x1, p1), (x4, p4) = placed[1], placed[4]
    assert (x1["shards"], p1["shards"], x4["shards"], p4["shards"]) == (
        1, 1, 4, 4)
    assert x1["bytes"] == X.nbytes == x1["bytes_per_device"]
    assert x4["bytes"] == X.nbytes == 4 * x4["bytes_per_device"]
    assert p1["bytes_per_device"] == p1["bytes"] == p4["bytes"]
    assert p4["bytes_per_device"] * 4 == p4["bytes"]


def test_round_sizing_counts_one_devices_share(monkeypatch):
    """(b) of ISSUE 35: ``_size_iterative_round`` on a data-axis
    backend with a faked ``_free_device_bytes`` — what it is handed
    (``last_shared_bytes``, the lane's footprint) is a device's share,
    and ``lanes_fit`` is what that share allows: free memory that holds
    few lanes of the WHOLE logits holds four times as many of a
    quarter."""
    from skdist_tpu.distribute.search import (
        _CV_SAMPLE_AXES, _cached_cv_kernel, _cv_iterative_spec,
        _cv_kernel_key, _resolve_device_scoring,
    )
    from skdist_tpu.models.linear import _freeze, extract_aux
    from skdist_tpu.parallel.backend import (
        _lane_footprint, _size_iterative_round, resolve_slice_iters,
        row_sharded_specs,
    )

    n, d, k, n_tasks = 40_000, 8, 10, 50
    X, y = _blobs(n=n, d=d, k=k)
    est = LogisticRegression(max_iter=100)
    data, meta = est._prep_fit_data(X, y, None)
    static = _freeze(est._static_config(meta))
    scoring = _resolve_device_scoring(est, "neg_log_loss")
    key = _cv_kernel_key(type(est), meta, static, scoring, False)
    classic = _cached_cv_kernel(type(est), meta, static, scoring, False,
                                key=key)
    spec, _ = _cv_iterative_spec(
        type(est), meta, static, scoring, False, resolve_slice_iters(100),
        fallback=classic, fallback_key=key)
    shared = {"X": data["X"], "y": data["y"], "sw": data["sw"],
              "aux": extract_aux(data),
              "train_masks": np.ones((5, n), np.float32),
              "test_masks": np.ones((5, n), np.float32)}
    task = {"hyper": {name: np.ones(n_tasks, np.float32)
                      for name in type(est)._hyper_names},
            "split": np.zeros(n_tasks, np.int32)}
    sized = {}
    for shards in (1, 4):
        backend = TPUBackend(devices=jax.devices()[:shards],
                             data_axis_size=shards)
        plan = backend.prepare_batched_iterative(
            spec, shared,
            shared_specs=row_sharded_specs(backend, shared,
                                           _CV_SAMPLE_AXES))
        assert plan.data_shards == shards and plan.n_task_slots == 1
        # a device with 25 MB free beside the shared operands
        monkeypatch.setattr(
            backend, "_free_device_bytes", lambda: 25_000_000, raising=False)
        sized[shards] = (
            backend.last_shared_bytes, _lane_footprint(plan, task),
            _size_iterative_round(backend, plan, task, n_tasks, None))
    (shared1, foot1, (chunk1, basis1, fit1)), (
        shared4, foot4, (chunk4, basis4, fit4)) = sized[1], sized[4]
    assert shared1 == 4 * shared4
    # (resident, transient, fixed, rows): rows and the one-hot a
    # quarter, the carry's weights and history whole
    assert foot1[3] == 4 * foot4[3] >= 4 * 4 * n * k
    assert foot1[2] == 4 * foot4[2] == 4 * n * k
    assert foot1[0] == foot4[0]
    lane1, lane4 = foot1[0] + foot1[1], foot4[0] + foot4[1]
    assert basis1 == basis4 == "memory"
    room = int(25_000_000 * 0.85)
    assert fit1 == max(1, (room - foot1[2]) // lane1)
    assert fit4 == (room - foot4[2]) // lane4 >= 3 * fit1
    assert chunk4 <= fit4 and chunk4 > chunk1


def test_a_row_sharded_host_array_goes_in_blocks_a_shard(monkeypatch):
    """(c) of ISSUE 35: over the (patched) bound a shard is placed in
    row blocks written into that device's part, block-major across the
    devices; what lands equals its source, shard by shard."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    rng = np.random.RandomState(0)
    X = rng.rand(4012, 7).astype(np.float32)
    calls = []
    real = backend_mod._write_rows()
    monkeypatch.setattr(backend_mod, "_BLOCK_PUT_BYTES", 4096)
    monkeypatch.setattr(
        backend_mod, "_write_rows",
        lambda: lambda whole, block, at: calls.append(
            (next(iter(block.devices())), int(at), len(block)))
        or real(whole, block, at))
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4),
                ("tasks", "data"))
    sharding = NamedSharding(mesh, P("data"))
    placed = backend_mod._put_mesh_scoped(X, sharding)
    np.testing.assert_array_equal(np.asarray(placed), X)
    assert placed.sharding == sharding and placed.shape == X.shape
    for shard in placed.addressable_shards:
        np.testing.assert_array_equal(np.asarray(shard.data),
                                      X[shard.index])
    # every device took its 1003 rows in the same blocks of under a
    # 64th of the bound, the last over the end of the one before
    per_device = {}
    for device, at, rows in calls:
        per_device.setdefault(device, []).append((at, rows))
    assert set(per_device) == set(jax.devices()[:4])
    blocks = per_device[jax.devices()[0]]
    assert all(b == blocks for b in per_device.values())
    assert len(blocks) >= 64 and max(m for _, m in blocks) * 28 <= 64 + 28
    assert blocks[-1][0] + blocks[-1][1] == 1003
    # block-major: no device's second block before every device's first
    first_seen = [calls.index(next(c for c in calls if c[0] == dev))
                  for dev in per_device]
    assert max(first_seen) < 4
    # the masks' layout, cut on the second axis, and a small array: as
    # one ``device_put``
    n_calls = len(calls)
    masks = rng.rand(5, 4012).astype(np.float32)
    cut = backend_mod._put_mesh_scoped(masks[:, :400],
                                       NamedSharding(mesh, P(None, "data")))
    np.testing.assert_array_equal(np.asarray(cut), masks[:, :400])
    backend_mod._put_mesh_scoped(X[:400], sharding)
    assert len(calls) == n_calls


def test_place_shared_hands_back_what_a_dispatch_leaves_in_place(
        monkeypatch):
    """``place_shared`` places as a dispatch would; a placed leaf
    handed to a later placement is not moved; and a small host array
    (a round's task slice on the ``tasks`` axis, every operand under
    the bound) never enters the shard-by-shard put."""
    X, _ = _blobs()
    backend = TPUBackend(devices=jax.devices()[:4], data_axis_size=2)
    specs = backend_mod.row_sharded_specs(backend, {"X": X}, {"X": 0})
    monkeypatch.setattr(
        backend_mod, "_put_row_shards",
        lambda *a: pytest.fail("an array under the bound went shard by shard"))
    a = backend.place_shared({"X": X}, specs)["X"]
    assert a.sharding.shard_shape(X.shape) == (240, 24)
    assert backend.last_shared_bytes == X.nbytes // 2
    b = backend.place_shared({"X": a}, specs)["X"]
    assert _buffers(b) == _buffers(a)
    np.testing.assert_array_equal(np.asarray(b), X)
    assert not backend_mod._BCAST_CACHE
    flat = TPUBackend(devices=jax.devices()[:2])
    task_sharding = flat._resolve_placement((), None)[0]
    lanes = backend_mod._put_mesh_scoped(np.arange(8, dtype=np.int32),
                                         task_sharding)
    assert lanes.sharding.shard_shape((8,)) == (4,)


def test_hlo_collectives_counts_each_instruction_once():
    """The reading behind ``collective_ops_compiled`` /
    ``collective_bytes_compiled``: every collective instruction of a
    program's text once, by its result — a tuple's shapes summed, of an
    asynchronous pair the ``-done`` half, operands that merely NAME a
    collective not at all."""
    hlo = """
%region_1 (a: f32[], b: f32[]) -> f32[] {
  ROOT %add = f32[] add(%a, %b)
}
%body (p: (f32[13,10,2025000])) -> (f32[13,10,2025000]) {
  %all-reduce.18 = f32[13]{0:T(128)S(1)} all-reduce(%fusion.36), channel_id=1, replica_groups=[1,4]<=[4], to_apply=%region_1, metadata={op_name="jit(mapped)/while/body/lr/softmax/reduce_sum"}
  %all-reduce.17 = (f32[13]{0:T(128)S(1)}, f32[13,785,10]{1,0,2:T(8,128)S(1)}) all-reduce(%copy-done.52, %pad_add_fusion.2), channel_id=4, to_apply=%region_1
  %all-gather-start.1 = (bf16[4,128]{1,0}, bf16[16,128]{1,0}) all-gather-start(%x), dimensions={0}
  %all-gather-done.1 = bf16[16,128]{1,0} all-gather-done(%all-gather-start.1)
  %fusion.9 = f32[13,10,2025000]{2,1,0} fusion(%all-reduce.18, %p), kind=kLoop
  %cp = u32[2]{0} collective-permute(%ids), source_target_pairs={{0,1},{1,0}}
}
"""
    found = backend_mod.hlo_collectives(hlo)
    assert found == [
        ([(13,)], 52),
        ([(13,), (13, 785, 10)], 52 + 13 * 785 * 10 * 4),
        ([(16, 128)], 16 * 128 * 2),
        ([(2,)], 8),
    ]
    assert backend_mod.hlo_collectives("ENTRY %main { ROOT %r = f32[] add(%a, %b) }") == []
