"""
The L-BFGS history in age order (``models/solvers.LBFGS_CARRY_KEYS``
states the layout): no batched index under ``vmap``, and the arithmetic
of a ring addressed by ``k % m`` — the same pairs in the same order.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from skdist_tpu.models.solvers import (
    LBFGS_CARRY_KEYS,
    lbfgs_carry_init,
    lbfgs_resume,
)

M = 10
LANES = 7


# ---------------------------------------------------------------------------
# problems: logistic regressions small enough to write out, hard enough
# that k passes m, with and without a ray
# ---------------------------------------------------------------------------

def _data(seed, n=60, d=6):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, d)) * np.logspace(0, 1.5, d)
    y = np.where(X @ rng.normal(size=d) / np.sqrt(d)
                 + 0.5 * rng.normal(size=n) > 0, 1.0, -1.0)
    return X.astype(np.float32), y.astype(np.float32)


def _logistic(X, y, C, with_ray):
    """``loss(w)`` of an L2-penalised logistic regression and, with
    ``with_ray``, its ``ray(w, d)`` in the solver's protocol."""

    def row_loss(z):
        return jnp.sum(jax.nn.softplus(-y * z))

    def loss(w):
        return C * row_loss(X @ w) + 0.5 * jnp.dot(w, w)

    if with_ray:
        def ray(w, d):
            z0, dz = X @ w, X @ d

            def along(t):
                wt = w + t * d
                return C * row_loss(z0 + t * dz) + 0.5 * jnp.dot(wt, wt)

            def value_and_grad_at(t):
                return jax.value_and_grad(loss)(w + t * d)

            return along, value_and_grad_at

        loss.ray = ray
    return loss


def _lanes(with_ray, max_iter, tol, seed=0, Cs=None):
    """``(init, step(n_steps))`` over ``LANES`` lanes that share X and
    differ in C — so they stop after different numbers of iterations and
    disagree on ``k``."""
    X, y = _data(seed)
    Cs = jnp.asarray(np.logspace(-3, 2, LANES) if Cs is None else Cs,
                     jnp.float32)

    def init(C):
        return lbfgs_carry_init(_logistic(X, y, C, with_ray),
                                jnp.zeros(X.shape[1], jnp.float32),
                                max_iter, tol, M)

    def step(n_steps):
        def one(C, carry):
            return lbfgs_resume(_logistic(X, y, C, with_ray), carry,
                                n_steps, max_iter, tol, M)
        return jax.jit(lambda carry: jax.vmap(one)(Cs, carry))

    return jax.jit(lambda: jax.vmap(init)(Cs)), step, (X, y, Cs)


# ---------------------------------------------------------------------------
# (a) the vmapped slice moves no history through a batched index
# ---------------------------------------------------------------------------

def _eqns(jaxpr, depth=0):
    """``(equation, while-depth)`` of a jaxpr and everything nested in
    it, as ``tests/test_lbfgs_ray.py::_products`` walks products."""
    for eqn in jaxpr.eqns:
        yield eqn, depth
        nested = depth + (eqn.primitive.name == "while")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, nested)


@pytest.mark.parametrize("with_ray", [True, False], ids=["ray", "plain"])
def test_vmapped_slice_holds_no_scatter_and_no_history_gather(with_ray):
    """The jaxpr of ``vmap(lbfgs_resume)`` at 7 lanes: no ``scatter``,
    no ``gather`` or dynamic slice that reads or writes a history with
    an index of a lane's own (a row index every lane shares is a slice:
    its indices have no lanes axis), and a history (S, Y) costs one
    ``concatenate`` and at most two ``select_n`` of shape
    ``(lanes, m, p)`` an iteration — the store's, and the one the
    vmapped ``while`` lays over every carry leaf (XLA fuses the two into
    one pass)."""
    init, step, (X, _, _) = _lanes(with_ray, 30, 1e-6)
    carry = jax.eval_shape(init)
    history = (LANES, M, X.shape[1])
    found = {"concatenate": 0, "select_n": 0}
    for eqn, depth in _eqns(jax.make_jaxpr(step(4))(carry).jaxpr):
        name = eqn.primitive.name
        shapes = [tuple(v.aval.shape) for v in (*eqn.invars, *eqn.outvars)
                  if hasattr(v.aval, "shape")]
        assert "scatter" not in name, eqn
        if history in shapes:
            assert name != "dynamic_update_slice", eqn
            if name == "gather":
                assert LANES not in eqn.invars[1].aval.shape, eqn
        if name in found and tuple(eqn.outvars[0].aval.shape) == history:
            # the solver's loop is the only while at depth 1; the line
            # search inside it never touches a history
            assert depth == 1, eqn
            found[name] += 1
    assert found["concatenate"] == 2  # S and Y
    assert found["select_n"] <= 4


# ---------------------------------------------------------------------------
# (b) against a ring-buffer L-BFGS written out plainly
# ---------------------------------------------------------------------------

def _ring_lbfgs(f, vg, w0, max_iter, tol, m=M, max_ls=20):
    """The solver's semantics with the history as a ring of ``m`` slots
    addressed by ``k % m`` — numpy, scalar indices, one lane. Returns
    ``(w, it, nfev, k, stores)``."""
    w = w0.copy()
    fw, g = vg(w)
    S, Y, rho = np.zeros((m, len(w))), np.zeros((m, len(w))), np.zeros(m)
    k, it, nfev, stores = 0, 0, 1, []
    done = np.max(np.abs(g)) <= tol or max_iter <= 0
    while not done and it < max_iter:
        n_corr, q, alphas = min(k, m), g.copy(), np.zeros(m)
        for i in range(n_corr):
            idx = (k - 1 - i) % m
            alphas[idx] = rho[idx] * np.dot(S[idx], q)
            q = q - alphas[idx] * Y[idx]
        last = (k - 1) % m
        gamma = (np.dot(S[last], Y[last])
                 / (np.dot(Y[last], Y[last]) + 1e-12)) if k else 1.0
        r = gamma * q
        for i in range(n_corr):
            idx = (k - n_corr + i) % m
            beta = rho[idx] * np.dot(Y[idx], r)
            r = r + S[idx] * (alphas[idx] - beta)
        d = -r
        descent = np.dot(g, d) < 0
        if not descent:
            d = -g
        if not descent or k == 0:
            d = d / (np.linalg.norm(d) + 1e-12)
        gd, t, halved = np.dot(g, d), 1.0, 0
        f_new = f(w + t * d)
        while not f_new <= fw + 1e-4 * t * gd and halved < max_ls:
            t, halved = t * 0.5, halved + 1
            f_new = f(w + t * d)
        ok = f_new <= fw + 1e-4 * t * gd
        w_new = w + t * d
        f_new, g_new = vg(w_new)
        s, yv = w_new - w, g_new - g
        sy = np.dot(s, yv)
        stores.append(bool(sy > 1e-10))
        if stores[-1]:
            S[k % m], Y[k % m], rho[k % m] = s, yv, 1.0 / (sy + 1e-12)
            k += 1
        w, fw, g, it, nfev = w_new, f_new, g_new, it + 1, nfev + halved + 2
        done = np.max(np.abs(g)) <= tol or not ok or it >= max_iter
    return w, it, nfev, k, stores


@pytest.mark.parametrize("seed", [0, 1, 3])
def test_solver_equals_a_plain_ring_buffer_lbfgs(seed):
    """Seven lanes of the solver against the plain ring, lane by lane,
    on problems where ``k`` passes ``m`` and where lanes skip pairs
    (``store`` false: the last steps before ``tol`` have ``s·y`` under
    1e-10): equal ``it``, ``nfev``, ``k``, and ``w`` to 1e-6. Both sides
    in float64 and stopped above its floor, so that a decision is the
    algorithm's and not a rounding's (at seed 2 the two largest C halve
    to the limit in their last iterations, where rounding decides)."""
    max_iter, tol = 60, 1e-5
    Xf, yf = _data(seed)
    X, y = Xf.astype(np.float64), yf.astype(np.float64)
    Cs = np.logspace(-3, 2, LANES)
    with jax.enable_x64(True):
        def solve(C):
            loss = _logistic(jnp.asarray(X), jnp.asarray(y), C, False)
            carry = lbfgs_carry_init(loss, jnp.zeros(X.shape[1]), max_iter,
                                     tol, M)
            return lbfgs_resume(loss, carry, max_iter, max_iter, tol, M)

        got = jax.tree_util.tree_map(
            np.asarray, jax.jit(jax.vmap(solve))(jnp.asarray(Cs)))
    assert got["w"].dtype == np.float64
    wrapped = skipped = 0
    for lane, C in enumerate(Cs):
        def f(w):
            return C * np.sum(np.logaddexp(0, -y * (X @ w))) + 0.5 * w @ w

        def value_and_grad(w):
            z = -y * (X @ w)
            return f(w), C * (X.T @ (-y / (1 + np.exp(-z)))) + w

        w, it, nfev, k, stores = _ring_lbfgs(
            f, value_and_grad, np.zeros(X.shape[1]), max_iter, tol)
        assert (int(got["it"][lane]), int(got["nfev"][lane]),
                int(got["k"][lane])) == (it, nfev, k), lane
        np.testing.assert_allclose(got["w"][lane], w, rtol=1e-6, atol=1e-6)
        wrapped += k > M
        skipped += not all(stores)
    assert wrapped >= 3 and skipped >= 1
    assert len(set(got["k"].tolist())) > 1


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_skipped_pair_leaves_the_history_as_it_was(seed):
    """Where the curvature test fails (``s·y <= 1e-10``) nothing shifts:
    a lane that stalls in place keeps S, Y, rho and ``k``, while the
    lanes beside it store — ``store`` is per lane."""
    X, y = _data(seed)
    Cs = jnp.asarray([1.0, 10.0], jnp.float32)

    def slice_(C, carry, n):
        return lbfgs_resume(_logistic(X, y, C, True), carry, n, 50, 0.0, M)

    def init(C):
        return lbfgs_carry_init(_logistic(X, y, C, True),
                                jnp.zeros(X.shape[1], jnp.float32), 50, 0.0,
                                M)

    carry = jax.vmap(init)(Cs)
    carry = jax.vmap(lambda C, c: slice_(C, c, 5))(Cs, carry)
    # lane 0 is handed a gradient of zero: its next direction is zero,
    # s = 0, s·y = 0, and the pair is skipped; lane 1 goes on storing
    frozen = {**carry, "g": carry["g"].at[0].set(0.0)}
    after = jax.vmap(lambda C, c: slice_(C, c, 1))(Cs, frozen)
    assert int(after["k"][0]) == int(carry["k"][0])
    assert int(after["k"][1]) == int(carry["k"][1]) + 1
    for key in ("S", "Y", "rho"):
        np.testing.assert_array_equal(after[key][0], carry[key][0])
        np.testing.assert_array_equal(after[key][1][:-1], carry[key][1][1:])


# ---------------------------------------------------------------------------
# (c) the layout: row m-1 is the newest pair, rows in age order
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("with_ray", [True, False], ids=["ray", "plain"])
def test_rows_are_in_age_order_after_the_history_wraps(with_ray):
    """One lane stepped an iteration at a time through ``m + 3`` stored
    pairs: after every store row ``m-1`` is that iteration's
    ``s = w_new - w`` (and ``y``, ``1 / s·y``), the rows above it are
    the earlier pairs shifted up by one, and rows that were never
    written stay zero."""
    X, y = _data(3)
    loss = _logistic(X, y, jnp.float32(30.0), with_ray)
    carry = jax.jit(lambda: lbfgs_carry_init(
        loss, jnp.zeros(X.shape[1], jnp.float32), 100, 0.0, M))()
    step = jax.jit(lambda c: lbfgs_resume(loss, c, 1, 100, 0.0, M))
    pairs = []
    while len(pairs) < M + 3:
        new = step(carry)
        assert int(new["it"]) == int(carry["it"]) + 1
        if int(new["k"]) > int(carry["k"]):
            s = np.asarray(new["w"] - carry["w"])
            yv = np.asarray(new["g"] - carry["g"])
            pairs.append((s, yv, np.float32(1) / (np.dot(s, yv)
                                                  + np.float32(1e-12))))
        carry = new
        held = pairs[-M:]
        first = M - len(held)
        for j, (s, yv, rho) in enumerate(held):
            np.testing.assert_array_equal(carry["S"][first + j], s)
            np.testing.assert_array_equal(carry["Y"][first + j], yv)
            np.testing.assert_allclose(carry["rho"][first + j], rho,
                                       rtol=1e-6)
        assert not np.any(np.asarray(carry["S"][:first]))
        assert not np.any(np.asarray(carry["rho"][:first]))
    assert int(carry["k"]) == M + 3


# ---------------------------------------------------------------------------
# (d) sliced equals unsliced, bit for bit, on a problem that wraps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("with_ray", [True, False], ids=["ray", "plain"])
def test_sliced_in_steps_of_4_equals_unsliced_when_the_history_wraps(
        with_ray):
    """Seven lanes that stop apart, run whole and in slices of 4: every
    carry leaf equal bit for bit, the histories included."""
    max_iter = 40
    init, step, _ = _lanes(with_ray, max_iter, 1e-3)
    whole = step(max_iter)(init())
    sliced, by4 = init(), step(4)
    for _ in range(-(-max_iter // 4)):
        sliced = by4(sliced)
    assert sorted(whole) == sorted(LBFGS_CARRY_KEYS)
    for key in LBFGS_CARRY_KEYS:
        np.testing.assert_array_equal(np.asarray(whole[key]),
                                      np.asarray(sliced[key]), err_msg=key)
    assert int(np.max(whole["k"])) > M
    assert len(set(np.asarray(whole["it"]).tolist())) > 2


# ---------------------------------------------------------------------------
# (e) a lane's answer does not depend on how many lanes share its program
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("with_ray", [True, False], ids=["ray", "plain"])
def test_a_lane_is_the_same_in_programs_of_2_3_and_7_lanes(with_ray):
    """The lanes of a 7-wide program, run again in programs of 2, 3 and
    2 lanes (what a mesh of 8 devices makes of rounds of 16 and 24):
    every carry leaf bit for bit. XLA:CPU compiles the two-loop's small
    ``while`` loops in another way at 2 and 3 lanes, and each axpy has
    to come out of both as the same multiply-add."""
    max_iter = 40
    Cs = np.logspace(-3, 2, LANES)
    init, step, _ = _lanes(with_ray, max_iter, 1e-5)
    wide = step(max_iter)(init())
    assert int(np.max(wide["k"])) > M
    for lo, hi in ((0, 2), (2, 5), (5, 7)):
        init_n, step_n, _ = _lanes(with_ray, max_iter, 1e-5, Cs=Cs[lo:hi])
        narrow = step_n(max_iter)(init_n())
        for key in LBFGS_CARRY_KEYS:
            np.testing.assert_array_equal(
                np.asarray(wide[key])[lo:hi], np.asarray(narrow[key]),
                err_msg=f"{key}, lanes {lo}:{hi}")


def test_carry_shapes_and_dtypes_are_the_pinned_contract():
    """``LBFGS_CARRY_KEYS`` and every leaf's shape and dtype: round
    sizing (``_lane_footprint``) and the compaction gather read them."""
    p = 6
    carry = jax.eval_shape(lambda: lbfgs_carry_init(
        lambda w: jnp.dot(w, w), jnp.zeros(p, jnp.float32), 30, 1e-4, M))
    assert LBFGS_CARRY_KEYS == ("w", "f", "g", "S", "Y", "rho", "k", "it",
                                "nfev", "done")
    assert sorted(carry) == sorted(LBFGS_CARRY_KEYS)
    int_ = jnp.asarray(0).dtype
    want = {"w": ((p,), jnp.float32), "f": ((), jnp.float32),
            "g": ((p,), jnp.float32), "S": ((M, p), jnp.float32),
            "Y": ((M, p), jnp.float32), "rho": ((M,), jnp.float32),
            "k": ((), int_), "it": ((), int_), "nfev": ((), int_),
            "done": ((), jnp.bool_)}
    for key, (shape, dtype) in want.items():
        assert carry[key].shape == shape and carry[key].dtype == dtype, key
