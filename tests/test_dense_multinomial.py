"""
The dense multinomial path (``sparse._DenseOperator.logits``, the
multinomial problem of ``LogisticRegression``, ``backend._lane_footprint``
and ``backend.put_host_array``): logits classes-first and rows-minor
under ``vmap``, the loss and its gradient the plain ``X̃ @ W`` ones, a
search's answers the plain reference's, and round sizing's count of a
lane against a count done by hand from the shapes.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from skdist_tpu.models import LogisticRegression
from skdist_tpu.models.linear import _freeze, maybe_exact_matmuls

N, D, K = 120, 12, 10


def _seeded(n=N, d=D, k=K, seed=0):
    rng = np.random.RandomState(seed)
    centres = rng.normal(size=(k, d))
    y = rng.permutation(n) % k
    X = (centres[y] + 1.5 * rng.normal(size=(n, d))).astype(np.float32)
    return np.abs(X) / np.abs(X).max(), y


def _problem(X, y, sw, C=0.7, **est_kw):
    est = LogisticRegression(**est_kw)
    data, meta = est._prep_fit_data(X, y, sw)
    static = _freeze(est._static_config(meta))
    problem = maybe_exact_matmuls(
        LogisticRegression, LogisticRegression._build_fit_problem(meta, static))
    return problem(jnp.asarray(data["X"]), jnp.asarray(data["y"]),
                   jnp.asarray(data["sw"]),
                   {"C": jnp.asarray(C, jnp.float32),
                    "tol": jnp.float32(1e-4)})


def _plain_loss(X, y, sw, C):
    """``sum_i sw_i (lse(z_i) - z_i[y_i]) + ||W[:d]||^2 / (2 C)`` with
    ``z = [X | 1] @ W``, written plainly."""
    Xa = jnp.concatenate([jnp.asarray(X), jnp.ones((len(X), 1))], axis=1)
    d = X.shape[1]

    def loss(wflat):
        W = wflat.reshape(d + 1, -1)
        z = jnp.matmul(Xa, W, precision="highest")
        own = z[jnp.arange(len(y)), jnp.asarray(y)]
        return (jnp.sum(jnp.asarray(sw) * (jax.nn.logsumexp(z, axis=1) - own))
                + 0.5 / C * jnp.sum(W[:d] * W[:d]))

    return loss


def test_the_multinomial_loss_is_the_plain_one_value_and_gradient():
    X, y = _seeded()
    sw = np.random.RandomState(1).rand(N).astype(np.float32) + 0.5
    loss, w0, _ = _problem(X, y, sw)
    plain = _plain_loss(X, y, sw, 0.7)
    rng = np.random.RandomState(2)
    assert w0.shape == ((D + 1) * K,)
    for scale in (0.0, 0.3, 3.0):
        w = jnp.asarray(scale * rng.normal(size=w0.shape), jnp.float32)
        f, g = jax.value_and_grad(loss)(w)
        fp, gp = jax.value_and_grad(plain)(w)
        np.testing.assert_allclose(f, fp, rtol=2e-6)
        np.testing.assert_allclose(g, gp, atol=2e-5 * float(jnp.abs(gp).max()))
        # ... and along a ray, where the solver takes them
        d = jnp.asarray(rng.normal(size=w0.shape), jnp.float32)
        along, value_and_grad_at = loss.ray(w, d)
        for t in (1.0, 0.25):
            fp, gp = jax.value_and_grad(plain)(w + t * d)
            np.testing.assert_allclose(along(t), fp, rtol=5e-6)
            f, g = value_and_grad_at(t)
            np.testing.assert_allclose(f, fp, rtol=5e-6)
            np.testing.assert_allclose(
                g, gp, atol=5e-5 * float(jnp.abs(gp).max()))


def _row_shaped(jaxpr, n, found=None):
    """The shapes of every value, in ``jaxpr`` and everything nested in
    it, that has an axis of ``n`` rows."""
    found = set() if found is None else found
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            if n in shape:
                found.add(tuple(shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _row_shaped(sub, n, found)
    return found


@pytest.mark.parametrize("bf16", [False, True])
def test_under_vmap_every_logits_shaped_value_is_rows_minor(bf16):
    """A round's lanes: whatever the solver's iteration computes from a
    lane's weights over the rows is ``(lanes, k, n)`` or ``(lanes, n)``
    — never ``(n, lanes, k)``, which a ``while`` holds ``k``-minor."""
    from skdist_tpu.models.solvers import lbfgs_carry_init, lbfgs_resume

    X, y = _seeded()
    sw = np.ones(N, np.float32)
    kw = {"matmul_dtype": "bfloat16"} if bf16 else {}
    lanes = 3

    def slice_of(C):
        loss, w0, _ = _problem(X, y, sw, C=C, **kw)
        carry = lbfgs_carry_init(loss, w0, 10, 1e-4)
        return lbfgs_resume(loss, carry, 2, 10, 1e-4)["w"]

    jaxpr = jax.make_jaxpr(jax.vmap(slice_of))(jnp.ones(lanes)).jaxpr
    shapes = _row_shaped(jaxpr, N)
    batched = {s for s in shapes if lanes in s}
    assert (lanes, K, N) in batched
    assert all(s[0] == lanes and s[-1] == N for s in batched), batched
    # what is shared by the lanes has no second X in it
    assert not any(s in ((N, D + 1), (D + 1, N)) for s in shapes)


def test_a_search_answers_what_the_plain_reference_answers():
    """``DistGridSearchCV`` over seeded dense multiclass data through
    the compacted path (25 lanes), every fold's log-loss against the
    reference that walks X in blocks."""
    from chipbench.reference.softmax_lr import stratified_folds
    from chipbench.reference.softmax_lr_blocked import BlockedSoftmaxLR
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.parallel import TPUBackend

    X, y = _seeded(600, 48, 10, seed=3)
    Cs = [float(c) for c in np.logspace(-3, 0, 5)]
    backend = TPUBackend(devices=jax.devices()[:1])
    gs = DistGridSearchCV(
        LogisticRegression(max_iter=40, tol=1e-4), {"C": Cs},
        backend=backend, cv=5, scoring="neg_log_loss",
    ).fit(X, y)
    stats = backend.last_round_stats
    assert stats["mode"] == "compacted" and stats["refused"] == 0
    assert 0 < stats["logits_bytes"] < stats["lane_bytes"]
    assert stats["round_bytes_estimate"] > stats["shared_bytes"]
    got = np.array([[gs.cv_results_[f"split{f}_test_score"][c]
                     for f in range(5)] for c in range(5)])
    ref = BlockedSoftmaxLR(X, y, 10, block_rows=150)
    folds = stratified_folds(y, 5)
    want = np.array(ref.fold_scores(
        folds, [(f, C) for C in Cs for f in range(5)], 40, 1e-4, 10)
    ).reshape(5, 5)
    gap = np.abs(got - want)
    assert np.median(gap) < 2e-5 and gap.max() < 1e-3, (
        np.median(gap), gap.max())


def test_the_lanes_footprint_is_the_count_done_by_hand():
    """Round sizing's count of one lane of the multinomial dense
    program at the benchmark's shapes (an abstract trace: nothing is
    compiled or placed) against the same count from the shapes."""
    from tests.test_tpu_compile import _cv_step_program
    from skdist_tpu.parallel.backend import IterativePlan, _lane_footprint

    n, d, k = 2_000_000, 784, 10
    step_fn, shared, task, _, init_fn = _cv_step_program(n, d, k, 50)
    plan = IterativePlan(init_fn, step_fn, None, None, shared, None)
    resident, transient, fixed, rows = _lane_footprint(plan, task)
    vector = 4 * (d + 1) * k
    # weights, gradient, two histories of ten: what a lane keeps
    assert 22 * vector <= resident <= 22 * vector + 256
    # at the accepted point of a line search: both products' logits
    # (held across the halving loop), the trial point's, its softmax
    # and the residual; the fold's row weights and the log-sum-exp
    logits, row = 4 * n * k, 4 * n
    assert rows == 5 * logits + 2 * row
    # ... beside the carry the step writes and the step it takes
    assert rows + 22 * vector <= transient <= rows + 24 * vector
    # nothing of the shared operands is held twice: the one-hot
    assert fixed == logits


def test_a_large_host_array_reaches_the_device_in_blocks(monkeypatch):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from skdist_tpu.models.linear import _to_jnp
    from skdist_tpu.parallel import backend as backend_mod

    rng = np.random.RandomState(0)
    X = rng.rand(1003, 7).astype(np.float32)
    calls = []
    real = backend_mod._write_rows()
    monkeypatch.setattr(backend_mod, "_BLOCK_PUT_BYTES", 4096)
    monkeypatch.setattr(
        backend_mod, "_write_rows",
        lambda: lambda whole, block, at: calls.append(
            (int(at), len(block))) or real(whole, block, at))
    # every write is waited for before the next block is enqueued: a
    # block holds its device buffer from then on, and a loop that ran
    # ahead would hold them all beside the whole
    real_wait, order = jax.block_until_ready, []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda t: order.append(len(calls)) or real_wait(t))
    placed = backend_mod.put_host_array(X)
    monkeypatch.setattr(jax, "block_until_ready", real_wait)
    assert order == list(range(1, len(calls) + 1))
    np.testing.assert_array_equal(np.asarray(placed), X)
    # blocks of under a quarter of the bound, the last over the end
    assert len(calls) >= 4 and max(m for _, m in calls) * 28 <= 1024 + 28
    assert calls[-1][0] + calls[-1][1] == 1003
    # a replica on each device of a mesh: the whole that the blocks
    # are written into is made ON each device — never as
    # ``jnp.zeros(..., device=sharding)``, which fills the default
    # device and copies from there (a second whole beside the first)
    mesh = Mesh(np.array(jax.devices()[2:4]), ("tasks",))
    real_zeros, made_on = jnp.zeros, []

    def zeros(shape, dtype=None, **kw):
        assert not kw, "zeros made on the default device and copied"
        made_on.append(jax.config.jax_default_device)
        return real_zeros(shape, dtype)

    monkeypatch.setattr(jnp, "zeros", zeros)
    rep = backend_mod._put_mesh_scoped(X, NamedSharding(mesh, P()))
    monkeypatch.setattr(jnp, "zeros", real_zeros)
    assert sorted(made_on, key=lambda d: d.id) == jax.devices()[2:4]
    np.testing.assert_array_equal(np.asarray(rep), X)
    assert rep.sharding == NamedSharding(mesh, P())
    assert {s.device for s in rep.addressable_shards} == set(
        jax.devices()[2:4])
    for shard in rep.addressable_shards:
        np.testing.assert_array_equal(np.asarray(shard.data), X)
    n_calls = len(calls)
    tree = _to_jnp({"X": X, "y": np.arange(5), "small": X[:10]})
    np.testing.assert_array_equal(np.asarray(tree["X"]), X)
    assert len(calls) > n_calls and tree["y"].dtype == jnp.int32
    # a small array goes as it always did, and so does one sharded by
    # rows whose shards are small (large shards go in blocks a shard:
    # tests/test_data_axis.py)
    n_calls = len(calls)
    backend_mod.put_host_array(X[:30])
    backend_mod._put_mesh_scoped(X[:200], NamedSharding(mesh, P("tasks")))
    assert len(calls) == n_calls
