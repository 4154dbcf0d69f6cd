"""
Base protocol tests (reference: skdist/distribute/tests/test_base.py).
"""

import pickle

import numpy as np
import pytest

from skdist_tpu.base import BaseEstimator, clone, strip_runtime
from skdist_tpu.parallel import LocalBackend, TPUBackend, get_value, parse_partitions


class Toy(BaseEstimator):
    def __init__(self, a=1, b="x", backend=None):
        self.a = a
        self.b = b
        self.backend = backend


def test_get_set_params():
    t = Toy(a=3)
    assert t.get_params()["a"] == 3
    t.set_params(a=5, b="y")
    assert t.a == 5 and t.b == "y"
    with pytest.raises(ValueError):
        t.set_params(nope=1)


def test_clone_carries_backend_by_reference():
    backend = LocalBackend(n_jobs=2)
    t = Toy(a=2, backend=backend)
    c = clone(t)
    assert c is not t
    assert c.a == 2
    assert c.backend is backend  # reference semantics: reattached, not copied


def test_clone_nested():
    inner = Toy(a=7)
    outer = Toy(a=1, b=inner)
    c = clone(outer)
    assert c.b is not inner
    assert c.b.a == 7


def test_strip_runtime_makes_picklable():
    t = Toy(backend=LocalBackend())
    strip_runtime(t)
    assert t.backend is None
    pickle.dumps(t)


def test_backend_refuses_pickle():
    with pytest.raises(TypeError):
        pickle.dumps(LocalBackend())


def test_parse_partitions():
    # returns tasks-per-round: 'auto'/None -> single full round;
    # int p -> ceil(n/p) tasks per round (p rounds)
    assert parse_partitions("auto", 10) == 10
    assert parse_partitions(None, 10) == 10
    assert parse_partitions(4, 10) == 3
    assert parse_partitions(1, 10) == 10


def test_get_value_roundtrip():
    b = LocalBackend()
    h = b.broadcast({"x": np.ones(3)})
    assert np.allclose(get_value(h)["x"], 1.0)
    assert get_value(42) == 42


def test_tpu_backend_broadcast_and_batched_map(tpu_backend):
    import jax.numpy as jnp

    def kernel(shared, task):
        return {"s": jnp.sum(shared["X"]) * task["m"]}

    X = np.arange(6, dtype=np.float32).reshape(2, 3)
    out = tpu_backend.batched_map(
        kernel, {"m": np.arange(11, dtype=np.float32)}, {"X": X}
    )
    assert np.allclose(out["s"], 15.0 * np.arange(11))


def test_local_backend_batched_map_matches(tpu_backend):
    import jax.numpy as jnp

    def kernel(shared, task):
        return {"v": shared["X"] @ task["w"]}

    X = np.random.RandomState(0).normal(size=(4, 3)).astype(np.float32)
    W = np.random.RandomState(1).normal(size=(5, 3)).astype(np.float32)
    local = LocalBackend().batched_map(kernel, {"w": W}, {"X": X})
    dist = tpu_backend.batched_map(kernel, {"w": W}, {"X": X})
    assert np.allclose(local["v"], dist["v"], atol=1e-6)


def test_resolve_backend_adopts_2d_mesh():
    """Passing a tasks x data Mesh as backend= must keep the data axis
    (regression: it was flattened to a 1D mesh)."""
    from skdist_tpu.parallel import resolve_backend
    from skdist_tpu.parallel.mesh import task_data_mesh

    mesh = task_data_mesh(data_axis_size=2)
    be = resolve_backend(mesh)
    assert be.data_axis_size == 2
    assert be.mesh is mesh
    with pytest.raises(ValueError):
        TPUBackend(axis_name="work", data_axis_size=2)


def test_tpu_backend_rounds(tpu_backend):
    """Chunked rounds (round_size) must give identical results."""
    import jax.numpy as jnp

    def kernel(shared, task):
        return {"v": task["w"] * 2.0}

    W = np.arange(13, dtype=np.float32)
    tpu_backend.round_size = 8
    try:
        out = tpu_backend.batched_map(kernel, {"w": W}, {})
    finally:
        tpu_backend.round_size = None
    assert np.allclose(out["v"], W * 2.0)


def test_batched_map_halves_round_on_oom(tpu_backend, monkeypatch):
    """A round that exhausts device memory retries at half size
    (device-aligned) instead of failing the whole search."""
    import jax
    import jax.numpy as jnp

    from skdist_tpu.parallel import backend as backend_mod

    real_jit = backend_mod._jit_vmapped
    seen_chunks = []

    def fussy_jit(kernel, static_args, *rest):
        fn = real_jit(kernel, static_args, *rest)

        def wrapper(shared, tasks):
            chunk = jax.tree_util.tree_leaves(tasks)[0].shape[0]
            seen_chunks.append(chunk)
            if chunk > 8:
                raise RuntimeError(
                    "RESOURCE_EXHAUSTED: Out of memory (simulated)"
                )
            return fn(shared, tasks)

        return wrapper

    monkeypatch.setattr(backend_mod, "_jit_vmapped", fussy_jit)
    tasks = {"x": np.arange(32, dtype=np.float32)}
    with pytest.warns(UserWarning, match="exhausted device memory"):
        out = tpu_backend.batched_map(
            lambda shared, t: {"y": t["x"] * 2.0}, tasks
        )
    np.testing.assert_allclose(out["y"], np.arange(32) * 2.0)
    assert max(seen_chunks) > 8          # the too-big round was tried
    assert seen_chunks[-1] <= 8          # and halved until it fit


def test_batched_map_oom_resumes_from_completed_rounds(tpu_backend,
                                                       monkeypatch):
    """After an OOM, completed rounds are KEPT and the run resumes at
    the first unfinished task at a smaller chunk — no recomputation."""
    import jax

    from skdist_tpu.parallel import backend as backend_mod

    real_jit = backend_mod._jit_vmapped
    calls = []

    def fussy_jit(kernel, static_args, *rest):
        fn = real_jit(kernel, static_args, *rest)

        def wrapper(shared, tasks):
            chunk = jax.tree_util.tree_leaves(tasks)[0].shape[0]
            first = float(jax.tree_util.tree_leaves(tasks)[0][0])
            calls.append((chunk, first))
            # the SECOND big round blows up; the first succeeds
            if chunk > 8 and first >= 16:
                raise RuntimeError("RESOURCE_EXHAUSTED (simulated)")
            return fn(shared, tasks)

        return wrapper

    monkeypatch.setattr(backend_mod, "_jit_vmapped", fussy_jit)
    tasks = {"x": np.arange(32, dtype=np.float32)}
    with pytest.warns(UserWarning, match="exhausted device memory"):
        out, timings = tpu_backend.batched_map(
            lambda shared, t: {"y": t["x"] * 2.0}, tasks, round_size=16,
            return_timings=True,
        )
    np.testing.assert_allclose(out["y"], np.arange(32) * 2.0)
    # tasks 0-15 ran once at chunk 16 and were never re-dispatched
    assert calls[0] == (16, 0.0)
    assert all(first >= 16 for _, first in calls[1:])
    # timings cover every task exactly once
    assert sum(keep for _, keep in timings) == 32


def test_batched_map_oom_in_gather_keeps_prefix_contiguous(tpu_backend,
                                                          monkeypatch):
    """An OOM that surfaces inside the GATHER of a round (the normal
    case under async dispatch) must not let later pending rounds slide
    into the completed prefix: the failed round was already popped, so
    draining the queue would misalign later outputs to earlier tasks
    and the resume would silently skip the failed round's tasks
    (round-3 advisor, high)."""
    import jax

    from skdist_tpu.parallel import backend as backend_mod

    real_gather = backend_mod._gather_host
    blown = []

    def fussy_gather(tree):
        out = real_gather(tree)
        leaf = jax.tree_util.tree_leaves(out)[0]
        # blow up once, on the gather of the SECOND 16-task round
        # (tasks 16-31, first output 2*16=32) while round 3 is pending
        if not blown and leaf.shape[0] == 16 and float(leaf[0]) == 32.0:
            blown.append(True)
            raise RuntimeError("RESOURCE_EXHAUSTED (simulated, gather)")
        return out

    monkeypatch.setattr(backend_mod, "_gather_host", fussy_gather)
    tasks = {"x": np.arange(64, dtype=np.float32)}
    with pytest.warns(UserWarning, match="exhausted device memory"):
        out = tpu_backend.batched_map(
            lambda shared, t: {"y": t["x"] * 2.0}, tasks, round_size=16,
        )
    assert blown, "the simulated gather failure never fired"
    # every task's output at its own position — the buggy drain put
    # round 3's outputs at round 2's task offsets
    np.testing.assert_allclose(out["y"], np.arange(64) * 2.0)


def test_cached_device_put_reuse_and_safety():
    """reuse_broadcast cache: (a) same host array + sharding returns the
    SAME device buffer; (b) an entry whose weakref no longer targets the
    keyed array (id recycling) is never served; (c) FIFO bound holds."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from skdist_tpu.parallel import TPUBackend
    from skdist_tpu.parallel import backend as backend_mod

    bk = TPUBackend(reuse_broadcast=True)
    sharding = NamedSharding(bk.mesh, P())
    a = np.ones((512, 1024), np.float32)  # > _BCAST_MIN_BYTES

    backend_mod._BCAST_CACHE.clear()
    d1 = backend_mod._cached_device_put(a, sharding, True)
    d2 = backend_mod._cached_device_put(a, sharding, True)
    assert d1 is d2, "second put must hit the cache"

    # disabled / small arrays bypass the cache
    small = np.ones(4, np.float32)
    s1 = backend_mod._cached_device_put(small, sharding, True)
    s2 = backend_mod._cached_device_put(small, sharding, True)
    assert s1 is not s2

    # plant an entry whose weakref targets a DIFFERENT array under a's
    # key (simulating id() recycling): must re-put, not serve the plant
    import weakref

    other = np.zeros((512, 1024), np.float32)
    backend_mod._BCAST_CACHE[(id(a), sharding)] = (
        weakref.ref(other), "STALE-SENTINEL",
    )
    d3 = backend_mod._cached_device_put(a, sharding, True)
    assert d3 != "STALE-SENTINEL"
    np.testing.assert_array_equal(np.asarray(d3), a)

    # FIFO bound
    keep = [np.full((512, 1024), i, np.float32) for i in range(8)]
    for arr in keep:
        backend_mod._cached_device_put(arr, sharding, True)
    assert len(backend_mod._BCAST_CACHE) <= backend_mod._BCAST_MAX
    backend_mod._BCAST_CACHE.clear()


def test_reuse_broadcast_results_identical_and_engaged(clf_data):
    """batched_map with reuse_broadcast (a) actually ENGAGES on the
    library path — the second fit on the same X must record cache hits
    (regression: when _prep_fit_data eagerly jnp.asarray'd its leaves,
    the host-identity-keyed cache was silently inert) — and (b) gives
    bit-identical results to a fresh put."""
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.parallel import TPUBackend
    from skdist_tpu.parallel import backend as backend_mod

    X, y = clf_data
    # make X big enough to cross the cache's min-bytes bar
    Xb = np.tile(X, (1, 200)).astype(np.float32)
    grid = {"C": [0.1, 1.0]}
    est = LogisticRegression(max_iter=15)
    backend_mod._BCAST_CACHE.clear()
    r1 = DistGridSearchCV(
        est, grid, backend=TPUBackend(reuse_broadcast=True), cv=3
    ).fit(Xb, y).cv_results_
    assert len(backend_mod._BCAST_CACHE) >= 1, \
        "first fit must populate the cache with the big X leaf"
    hits_before = backend_mod._BCAST_HITS
    r2 = DistGridSearchCV(
        est, grid, backend=TPUBackend(reuse_broadcast=True), cv=3
    ).fit(Xb, y).cv_results_  # second fit: cache-hit path
    assert backend_mod._BCAST_HITS > hits_before, \
        "second fit on the same X must hit the cache"
    r3 = DistGridSearchCV(
        est, grid, backend=TPUBackend(), cv=3
    ).fit(Xb, y).cv_results_  # no cache
    np.testing.assert_array_equal(r1["mean_test_score"], r2["mean_test_score"])
    np.testing.assert_array_equal(r1["mean_test_score"], r3["mean_test_score"])
    backend_mod._BCAST_CACHE.clear()


def test_broadcast_cache_evicts_on_host_gc(monkeypatch):
    """Collecting the host array must evict its cache entry promptly
    (freeing pinned device HBM), via the weakref finalizer.

    device_put is stubbed with a non-aliasing placeholder: on the CPU
    backend the real device_put keeps a reference to the numpy buffer
    (zero-copy), so the host array can never die and there is no pinned
    memory to free — the eviction path only matters (and only fires)
    where placement copies, i.e. on real device backends."""
    import gc

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from skdist_tpu.parallel import TPUBackend
    from skdist_tpu.parallel import backend as backend_mod

    bk = TPUBackend(reuse_broadcast=True)
    sharding = NamedSharding(bk.mesh, P())
    monkeypatch.setattr(jax, "device_put", lambda x, s: object())
    backend_mod._BCAST_CACHE.clear()
    a = np.ones((512, 1024), np.float32)
    backend_mod._cached_device_put(a, sharding, True)
    assert len(backend_mod._BCAST_CACHE) == 1
    del a
    gc.collect()
    assert len(backend_mod._BCAST_CACHE) == 0, \
        "dead host array must not pin its device replica"


def test_proactive_round_sizing(tpu_backend):
    """_aot_exec_fn shrinks the first round (device-count aligned) when
    the compiled footprint exceeds free memory, leaves it alone when
    memory is ample, and its executables compute the same results the
    plain jit path would."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from skdist_tpu.parallel import backend as backend_mod

    bk = tpu_backend
    mesh = bk.mesh
    ts = NamedSharding(mesh, P(bk.axis_name))
    rs = NamedSharding(mesh, P())

    def kernel(shared, t):
        return {"s": jnp.sum(shared["X"]) * t["c"]}

    fn = backend_mod._jit_vmapped(kernel, None, ts, rs)
    shared = jax.device_put({"X": np.ones((64, 8), np.float32)}, rs)
    tasks = {"c": np.arange(32, dtype=np.float32)}
    d = bk.n_devices

    # ample memory: chunk untouched
    exec_fn, chunk = backend_mod._aot_exec_fn(
        fn, shared, tasks, 32, d, free_bytes=1 << 40
    )
    assert chunk == 32

    # tiny budget: shrinks, stays a positive multiple of the device count
    with pytest.warns(UserWarning, match="compiled round footprint"):
        exec_fn2, chunk2 = backend_mod._aot_exec_fn(
            fn, shared, tasks, 32, d, free_bytes=64
        )
    assert chunk2 < 32 and chunk2 >= d and chunk2 % d == 0

    # executables agree with the plain jit call
    sl = jax.device_put(
        {"c": tasks["c"][:d]}, ts
    )
    np.testing.assert_allclose(
        np.asarray(exec_fn(shared, sl)["s"]),
        np.asarray(fn(shared, sl)["s"]),
    )


def test_proactive_round_sizing_when_the_round_does_not_compile(
        tpu_backend, monkeypatch):
    """On a TPU a round too big for the device is refused AT COMPILE
    TIME (RESOURCE_EXHAUSTED from the compiler's allocation plan):
    _aot_exec_fn then reads the footprint from a smaller round that
    does compile and sizes the first round from it — no fault counter,
    no reactive shrink. Any other compile failure raises where it
    happened."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from skdist_tpu.parallel import backend as backend_mod
    from skdist_tpu.parallel import compile_cache, faults

    bk = tpu_backend
    d = bk.n_devices
    ts = NamedSharding(bk.mesh, P(bk.axis_name))
    rs = NamedSharding(bk.mesh, P())

    def kernel(shared, t):
        return {"s": jnp.sum(shared["X"]) * t["c"]}

    fn = backend_mod._jit_vmapped(kernel, None, ts, rs)
    shared = jax.device_put({"X": np.ones((64, 8), np.float32)}, rs)
    tasks = {"c": np.arange(64 * d, dtype=np.float32)}
    real = compile_cache.aot_executable
    refused = []

    def picky(fn_, shared_, task_like, n_chunk, **kw):
        if n_chunk > 16 * d:
            refused.append(n_chunk)
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Allocation (size=19660800000) would "
                "exceed memory (size=17179869184)")
        return real(fn_, shared_, task_like, n_chunk, **kw)

    monkeypatch.setattr(compile_cache, "aot_executable", picky)
    faults.reset_stats()
    with pytest.warns(UserWarning, match="does not compile into device"):
        _, chunk = backend_mod._aot_exec_fn(
            fn, shared, tasks, 64 * d, d, free_bytes=1 << 40)
    assert refused == [64 * d]  # one refused compile, then an eighth
    assert chunk < 64 * d and chunk >= 8 * d and chunk % d == 0
    assert faults.snapshot()["suppressed"] == 0

    def broken(*a, **kw):
        raise RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(compile_cache, "aot_executable", broken)
    with pytest.raises(RuntimeError, match="Mosaic"):
        backend_mod._aot_exec_fn(fn, shared, tasks, 64 * d, d,
                                 free_bytes=1 << 40)
