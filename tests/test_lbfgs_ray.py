"""
The line search along a ray (``models/linear._ray_loss``,
``models/solvers._lbfgs_body``): every loss ``_LbfgsFitMixin`` minimises
offers ``ray(w, d) -> (along, value_and_grad_at)`` from one helper,
whatever the representation of X; an iteration of the solver takes its
products over X once a direction — two forward, one transposed, none
inside the halving loop — and ``nfev`` keeps its one meaning.
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from skdist_tpu import sparse as sx
from skdist_tpu.models import LinearSVC, LogisticRegression
from skdist_tpu.models.linear import (
    _freeze,
    maybe_exact_matmuls,
    prepare_fit_X,
)
from skdist_tpu.models.solvers import (
    LBFGS_CARRY_KEYS,
    _lbfgs_body,
    lbfgs_carry_init,
    lbfgs_resume,
)

N, D = 240, 40


def _matrix(representation, seed=0):
    """(what the estimator is handed, the same matrix dense): a dense
    array; a CSR of even rows (packs to a ``PackedX``); a skewed CSR
    (packs to a ``BucketedX``)."""
    rng = np.random.RandomState(seed)
    if representation == "dense":
        X = rng.normal(size=(N, D)).astype(np.float32)
        return X, X
    if representation == "bucketed":
        lens = np.clip(rng.lognormal(2.0, 1.0, N).astype(int), 1, 300)
        lens[:3] = (600, 450, 300)
        d = 1500
        rows = np.repeat(np.arange(N), lens)
        cols = np.concatenate(
            [rng.choice(d, n, replace=False) for n in lens])
        X = sp.csr_matrix(
            (rng.rand(len(rows)).astype(np.float32), (rows, cols)),
            shape=(N, d))
        assert sx.pack_decision(X)[1] == "bucketed"
        return X, X.toarray()
    X = sp.random(N, 512, density=0.03, format="csr", dtype=np.float32,
                  random_state=rng)
    assert sx.pack_decision(X)[1] == "packed"
    return X, X.toarray()


def _problem(est, representation, seed=0):
    """The estimator's own fit problem over ``representation``, and the
    same problem over the dense matrix: ``(loss, dense_loss, p)``, ``p``
    the length of the dense problem's flat vector. A weight matrix lies
    in a problem's flat vector as the operator of its representation
    lays it (``LinearOperator.weights``), so each loss carries the way
    across: ``loss.seed`` takes a rows-major vector of ``p`` into the
    problem's own layout (the warm start's way in), ``loss.rows`` a
    vector of the problem's back (``unpack``'s way out), and
    ``loss.width`` is the problem's own length."""
    X, Xd = _matrix(representation, seed)
    k = 2 if est.binary else 4
    y = np.random.RandomState(seed + 1).randint(0, k, N)
    sw = np.random.RandomState(seed + 2).rand(N).astype(np.float32) + 0.5
    hyper = {"C": jnp.float32(0.7), "tol": jnp.float32(1e-4)}
    out = []
    for M in (X, Xd):
        model = est.cls(**est.kwargs)
        Xp = prepare_fit_X(M, est.cls)
        data, meta = model._prep_fit_data(Xp, y, sw)
        static = _freeze(model._static_config(meta))
        problem = maybe_exact_matmuls(
            est.cls, est.cls._build_fit_problem(meta, static))
        Xj = jax.tree_util.tree_map(jnp.asarray, data["X"])
        args = (Xj, jnp.asarray(data["y"]), jnp.asarray(data["sw"]), hyper)
        loss, w0, unpack = problem(*args)
        out.append(maybe_exact_matmuls(est.cls, loss))
        if hasattr(loss, "ray"):
            out[-1].ray = loss.ray
        out[-1].seed = lambda v, problem=problem, args=args: problem(
            *args, seed=v)[1]
        out[-1].rows = lambda v, unpack=unpack: jnp.ravel(
            unpack(v, 0)["W"])
        out[-1].width = w0.shape[0]
    return out[0], out[1], out[1].width


class _Est:
    """An estimator class, whether its problem is the binary one, and
    its constructor arguments."""

    def __init__(self, cls, binary, **kwargs):
        self.cls, self.binary, self.kwargs = cls, binary, kwargs


PROBLEMS = {
    "lr-binary": _Est(LogisticRegression, True),
    "lr-multinomial": _Est(LogisticRegression, False),
    "lr-binary-unpenalized": _Est(LogisticRegression, True, penalty=None),
    "svc-binary": _Est(LinearSVC, True),
    "svc-multiclass": _Est(LinearSVC, False),
}
REPRESENTATIONS = ["dense", "padded", "bucketed"]


@pytest.mark.parametrize("representation", REPRESENTATIONS)
@pytest.mark.parametrize("name", sorted(PROBLEMS))
def test_ray_equals_the_loss_and_its_gradient_along_the_direction(
        name, representation):
    """``along(t) == loss(w + t d)`` and ``value_and_grad_at(t) ==
    value_and_grad(loss)(w + t d)`` to float32 rounding at several
    ``t`` of the halving grid, for every problem over every
    representation — and the same against the dense problem."""
    loss, dense_loss, p = _problem(PROBLEMS[name], representation)
    rng = np.random.RandomState(5)
    w_rows = jnp.asarray(0.3 * rng.normal(size=p).astype(np.float32))
    d_rows = jnp.asarray(rng.normal(size=p).astype(np.float32))
    d_rows = d_rows / jnp.linalg.norm(d_rows)
    w, d = loss.seed(w_rows), loss.seed(d_rows)
    assert w.shape == (loss.width,)
    np.testing.assert_array_equal(loss.rows(w), w_rows)
    with jax.default_matmul_precision("highest"):
        along, value_and_grad_at = loss.ray(w, d)
        for t in (1.0, 0.5, 0.125, 2.0 ** -12, 0.0):
            f, g = jax.value_and_grad(loss)(w + t * d)
            np.testing.assert_allclose(along(t), f, rtol=2e-6)
            f_ray, g_ray = value_and_grad_at(t)
            np.testing.assert_allclose(f_ray, f, rtol=2e-6)
            scale = float(jnp.max(jnp.abs(g)))
            np.testing.assert_allclose(g_ray, g, atol=2e-5 * scale)
            # what a layout pads has no gradient: a solve leaves it 0
            np.testing.assert_array_equal(loss.seed(loss.rows(g_ray)),
                                          g_ray)
            f_d, g_d = jax.value_and_grad(dense_loss)(
                w_rows + t * d_rows)
            np.testing.assert_allclose(f_ray, f_d, rtol=2e-5)
            np.testing.assert_allclose(loss.rows(g_ray), g_d,
                                       atol=2e-4 * scale)


def _products(jaxpr, min_size, in_while=False):
    """``(equation, in_while)`` for every ``dot_general`` of a jaxpr —
    and of everything nested in it — with an operand of at least
    ``min_size`` elements; ``in_while`` says whether a ``while``
    encloses it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
                np.prod(v.aval.shape) >= min_size for v in eqn.invars):
            yield eqn, in_while
        nested = in_while or eqn.primitive.name == "while"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _products(sub, min_size, nested)


@pytest.mark.parametrize("name", sorted(PROBLEMS))
def test_an_iteration_takes_three_products_over_x(name):
    """The jaxpr of one ``_lbfgs_body`` iteration of a dense problem
    holds exactly three contractions against X — two forward, one
    transposed — and none inside the halving ``while``, whatever the
    line search's limit; the plain search of the same loss holds one
    inside it."""
    loss, _dense, p = _problem(PROBLEMS[name], "dense")
    carry = lbfgs_carry_init(loss, jnp.zeros(p, jnp.float32), 10, 1e-4)
    state = tuple(carry[key] for key in LBFGS_CARRY_KEYS)

    def one_iteration(fun):
        body = _lbfgs_body(fun, jax.value_and_grad(fun), 10, 1e-4, 10, 20)
        return list(_products(jax.make_jaxpr(body)(state).jaxpr, N * D))

    along_the_ray = one_iteration(loss)
    assert [in_while for _, in_while in along_the_ray] == [False] * 3
    # X is (N, D), the intercept rides beside the products: two of them
    # contract its columns with a weight vector (or matrix), the third
    # its rows with the residual
    contracted = [
        eqn.invars[0].aval.shape[eqn.params["dimension_numbers"][0][0][0]]
        for eqn, _ in along_the_ray]
    assert sorted(contracted) == [D, D, N]

    def plain(w):
        return loss(w)

    assert sorted(in_while for _, in_while in one_iteration(plain)) == [
        False, False, False, True]


@pytest.mark.parametrize("representation", REPRESENTATIONS)
@pytest.mark.parametrize("name", ["lr-binary", "lr-multinomial",
                                  "svc-binary"])
def test_ray_solve_sliced_equals_unsliced_and_the_plain_search(
        name, representation):
    """Along the ray the sliced solve stays bitwise the unsliced one
    (``w``, ``f``, ``it``, ``nfev``), and it reaches the plain search's
    answer: the same rule, the same grid, trial values that round
    differently."""
    loss, _dense, _p = _problem(PROBLEMS[name], representation)
    w0 = jnp.zeros(loss.width, jnp.float32)
    max_iter, tol = 12, 1e-4

    @jax.jit
    def whole(w0):
        carry = lbfgs_carry_init(loss, w0, max_iter, tol)
        return lbfgs_resume(loss, carry, max_iter, max_iter, tol)

    init = jax.jit(lambda w0: lbfgs_carry_init(loss, w0, max_iter, tol))
    step = jax.jit(
        lambda carry: lbfgs_resume(loss, carry, 5, max_iter, tol))
    a = whole(w0)
    b = init(w0)
    for _ in range(3):
        b = step(b)
    for key in ("w", "f", "g", "it", "nfev", "done"):
        np.testing.assert_array_equal(np.asarray(a[key]),
                                      np.asarray(b[key]))
    assert int(a["it"]) > 3

    def plain(w):
        return loss(w)

    c = jax.jit(lambda w0: lbfgs_resume(
        plain, lbfgs_carry_init(plain, w0, max_iter, tol), max_iter,
        max_iter, tol))(w0)
    assert abs(float(a["f"]) - float(c["f"])) <= 1e-4 * abs(float(c["f"]))


@pytest.mark.parametrize("name", ["lr-binary", "lr-multinomial"])
def test_the_bfloat16_path_keeps_the_plain_search(name):
    """``matmul_dtype='bfloat16'`` rounds the operand of its products,
    so its ``matvec`` is not linear in the weights — the premise of the
    ray — and its loss offers none: the lower-precision path (the
    benchmark's control) stays the program it was."""
    est = PROBLEMS[name]
    low = _Est(est.cls, est.binary, matmul_dtype="bfloat16")
    loss, _dense, p = _problem(low, "dense")
    assert not hasattr(loss, "ray")
    exact, _dense, _p = _problem(est, "dense")
    assert hasattr(exact, "ray")


# ---------------------------------------------------------------------------
# the dense programs are pinned
# ---------------------------------------------------------------------------

def _dense_sliced_program(k, which, n=64, d=12, lanes=4, n_slice=3):
    """``(lowered text, equations by primitive)`` of one entry of
    ``LogisticRegression``'s sliced fit — ``init``, ``step`` or
    ``finalize`` — over a dense ``(n, d)`` X with ``k`` classes,
    vmapped over ``lanes`` as a round runs it (X and the labels shared;
    row weights, hyperparameters and the carry a lane's own)."""
    import collections

    X = np.random.RandomState(0).normal(size=(n, d)).astype(np.float32)
    est = LogisticRegression(max_iter=9, engine="xla")
    data, meta = est._prep_fit_data(X, np.arange(n) % k, None)
    kernels = LogisticRegression._build_fit_slice_kernels(
        meta, _freeze(est._static_config(meta)), n_slice)
    args = (jnp.asarray(X), jnp.asarray(data["y"]), jnp.ones((lanes, n)),
            {"C": jnp.ones(lanes), "tol": jnp.full(lanes, 1e-4)})
    entry = {name: jax.vmap(
        maybe_exact_matmuls(LogisticRegression, kernels[name]),
        in_axes=(None, None, 0, 0) + (0,) * (name != "init"))
        for name in ("init", "step", "finalize")}
    if which != "init":
        args += (jax.eval_shape(entry["init"], *args),)
    counts = collections.Counter()

    def count(jaxpr):
        for eqn in jaxpr.eqns:
            counts[eqn.primitive.name] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                count(sub)

    count(jax.make_jaxpr(entry[which])(*args).jaxpr)
    return jax.jit(entry[which]).lower(*args).as_text(), dict(counts)


#: sha256 of the lowered text (what the persistent compile cache keys
#: on, locations apart) and the number of jaxpr equations, nested ones
#: included, read on the tree of PR 32 (jax 0.9.0) and unchanged by
#: PR 34
DENSE_PROGRAMS = {
    (2, "init"): ("3e931c3eceedc0a0", 467),
    (2, "step"): ("e5b16ef0e5808b30", 384),
    (2, "finalize"): ("8678980d46fbfeb0", 3),
    (3, "init"): ("5b8401f1e6cb99ba", 540),
    (3, "step"): ("cf5ba08c10698117", 430),
    (3, "finalize"): ("d377e0e1e65b812a", 7),
}


@pytest.mark.parametrize("k, which", sorted(DENSE_PROGRAMS))
def test_dense_step_programs_are_pinned(k, which):
    """The programs a dense search runs — the binary problem's
    (``search-epsilon``) and the multinomial's (``search-mnist8m``) —
    are the ones the benchmark's accepted numbers and the machines'
    compile caches were read on: an edit to the fit problems, the
    operator or the solver that reaches them changes a digest HERE and
    not in a chip check (PR 33 was refused on such a cell). A change
    that means to move them re-reads the table on its own tree and
    says so; a newer jax prints other text and re-reads it on the
    parent."""
    import hashlib

    text, counts = _dense_sliced_program(k, which)
    digest = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert (digest, sum(counts.values())) == DENSE_PROGRAMS[k, which], (
        f"the dense {'binary' if k == 2 else 'multinomial'} {which} "
        f"program changed; its equations by primitive: "
        f"{dict(sorted(counts.items()))}")
