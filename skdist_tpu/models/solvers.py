"""
Jittable optimisers for the linear-model kernels.

A compact L-BFGS (two-loop recursion, Armijo backtracking) written
directly in ``lax`` control flow so it is safe under ``jit`` *and*
``vmap`` — the property that lets a whole hyperparameter grid of fits
run as one XLA program. This replaces the scipy/liblinear solvers the
reference reached through sklearn (e.g. LogisticRegression in
``/root/reference/examples/search/basic_usage.py:99``).

Design notes for TPU:
- fixed-size history (static ``history``) held in age order, no
  dynamic shapes and no per-lane index (see ``LBFGS_CARRY_KEYS``)
- convergence handled with a ``done`` flag in the carry so converged
  vmap lanes freeze while others keep iterating (vmap of while_loop
  steps all lanes until every lane's predicate is false)
- all dot products are on flat f32 vectors; the heavy lifting (loss and
  gradient) is the caller's X @ W matmuls, which land on the MXU

Resumable carry form (convergence-compacted scheduling): both solvers
also expose an explicit carry-in/carry-out API —
``lbfgs_carry_init`` / ``lbfgs_resume`` and ``sgd_carry_init`` /
``sgd_resume`` — so a solve can run in bounded iteration *slices* and
resume exactly where it left off. The carries are plain dict pytrees
(every leaf a fixed-shape array), so a vmapped batch of carries is a
batch of arrays the fan-out backend can gather, compact to the
still-running lanes, and re-dispatch. ``lbfgs_minimize`` /
``sgd_minimize`` are themselves implemented as init + one full-length
resume, which is what makes a sliced run *bitwise identical* to the
unsliced solve: both apply the same traced body the same number of
times to the same carried state — slicing only changes where the host
observes the carry. The carries are also *scoreable* mid-solve: the
current iterate (:func:`carry_iterate`) is a valid model at every slice
boundary, which is what lets the adaptive (ASHA) scheduler evaluate
live lanes on the validation fold without touching the trajectory.

Data-representation agnosticism: neither solver ever touches X — the
heavy contractions live in the caller's loss/grad closures, built over
the ``skdist_tpu.sparse.LinearOperator`` matvec interface. A packed-CSR
X (gather ``X @ w`` forward, whose autodiff VJP is the scatter-add
``X.T @ r``) therefore runs through BOTH solvers — and the iteration-
sliced carry forms, and the convergence-compacted scheduler above them
— without a single sparse-specific line here: per-iteration cost drops
from O(n·d) to O(nnz) purely through the closures.
"""

import jax
import jax.numpy as jnp
from jax import lax

_EPS = 1e-12

#: order of the L-BFGS carry leaves (the ISSUE-pinned pytree contract)
#: ``S``, ``Y`` (``(m, p)``) and ``rho`` (``(m,)``) hold the stored
#: pairs in age order: row ``m-1`` the newest, the last ``min(k, m)``
#: rows valid, the rest zero. ``k`` counts the pairs stored and no index
#: is derived from it: ``k`` is a leaf of each lane's carry, and a row
#: index of a lane's own is a gather or scatter with a batched index
#: under ``vmap``, where a row named by position is the same slice for
#: every lane. Nothing outside the solver reads them.
#: ``nfev`` counts the evaluations of the loss the lane ASKED for (1 for
#: the initial value-and-gradient; per iteration the trial at t0, one
#: per line-search halving, and the value-and-gradient at the accepted
#: point: ``n_halved + 2``) — the work count that tells a change of
#: speed from a change of work, with ONE meaning whether or not the
#: loss offers a ray. Under ``vmap`` a round EXECUTES the halvings of
#: its slowest lane; each lane still counts its own. It is not the
#: count of products over the data: where the loss offers
#: ``ray(w, d)`` an iteration takes three whatever its halvings, so a
#: solve takes ``3 * it + 2`` (the initial value-and-gradient is a
#: forward and a transposed product).
LBFGS_CARRY_KEYS = ("w", "f", "g", "S", "Y", "rho", "k", "it", "nfev",
                    "done")


def carry_iterate(carry):
    """Current weight iterate of a solver carry — the leaf the adaptive
    (ASHA) rung evaluator scores MID-SOLVE.

    Both solver families keep the live iterate under ``"w"`` and keep
    it valid at every slice boundary: L-BFGS writes ``w`` only after an
    accepted (or stalled-in-place) line-search step, and the SGD epoch
    body freezes stopped lanes' weights in place — so ``carry["w"]`` is
    always a usable model, never a half-updated scratch buffer. The
    score-from-carry kernels (``models/linear.py``
    ``_build_fit_slice_kernels[...]["score_params"]``) read it through
    this helper so the contract has one name."""
    return carry["w"]


def _lbfgs_body(fun, value_and_grad, max_iter, tol, history, max_ls):
    """One L-BFGS iteration on the tuple state
    ``(w, f, g, S, Y, rho, k, it, nfev, done)`` — shared verbatim by the
    unsliced solve and every resume slice, so their trajectories cannot
    diverge."""
    m = history

    def row(H, j):
        # ``j`` is a loop counter, which ``vmap`` leaves unbatched
        return lax.dynamic_index_in_dim(H, j, 0, keepdims=False)

    def two_loop(g, S, Y, rho, k):
        first_valid = m - jnp.minimum(k, m)

        def bwd(i, carry):  # newest first
            q, alphas = carry
            j = m - 1 - i
            alpha = row(rho, j) * jnp.dot(row(S, j), q)
            alpha = jnp.where(j >= first_valid, alpha, 0.0)
            q = q - alpha * row(Y, j)
            return q, jnp.where(jnp.arange(m) == j, alpha, alphas)

        q, alphas = lax.fori_loop(0, m, bwd, (g, jnp.zeros(m, g.dtype)))
        sy = jnp.dot(S[m - 1], Y[m - 1])
        yy = jnp.dot(Y[m - 1], Y[m - 1])
        gamma = jnp.where(k > 0, sy / (yy + _EPS), 1.0)
        r = gamma * q

        def fwd(j, r):  # oldest first
            beta = row(rho, j) * jnp.dot(row(Y, j), r)
            # the mask is on the coefficient, as in ``bwd``, so that the
            # axpy is one multiply-add however XLA compiles the loop
            coef = jnp.where(j >= first_valid, row(alphas, j) - beta, 0.0)
            return r + coef * row(S, j)

        return -lax.fori_loop(0, m, fwd, r)

    # a problem whose loss is a function of LINEAR products of w offers
    # ``fun.ray(w, d) -> (along, value_and_grad_at)`` with ``along(t) ==
    # fun(w + t * d)`` and ``value_and_grad_at(t) ==
    # value_and_grad(fun)(w + t * d)``, both from products taken ONCE a
    # direction: the trial steps and the accepted point's value and
    # gradient read the ray's logits, never the data. A plain function
    # pays a product a trial step and two more at the accepted point.
    ray = getattr(fun, "ray", None)

    def plain_ray(w, d):
        return (lambda t: fun(w + t * d),
                lambda t: value_and_grad(w + t * d))

    def line_search(along, f, gd):
        """Armijo backtracking along ``along``; returns (step, f_new,
        accepted, halvings)."""

        def cond(carry):
            t, f_new, it = carry
            armijo = f_new <= f + 1e-4 * t * gd
            return jnp.logical_and(~armijo, it < max_ls)

        def body(carry):
            t, _, it = carry
            t = t * 0.5
            return t, along(t), it + 1

        t0 = 1.0
        f1 = along(t0)
        t, f_new, n_halved = lax.while_loop(cond, body, (t0, f1, 0))
        ok = f_new <= f + 1e-4 * t * gd
        return t, f_new, ok, n_halved

    def body(state):
        w, f, g, S, Y, rho, k, it, nfev, done = state
        with jax.named_scope("lbfgs/two_loop"):
            d = two_loop(g, S, Y, rho, k)
        # safeguard: fall back to steepest descent if d isn't a descent dir
        descent = jnp.dot(g, d) < 0
        d = jnp.where(descent, d, -g)
        # a raw -g direction (first iteration, or the fallback above)
        # has arbitrary scale: on unscaled data |g| can be ~1e6, and
        # max_ls backtracking halvings from t=1 cannot reach a usable
        # step — the line search "stalls" and the solver would stop
        # after one iteration. Normalise those directions so the unit
        # backtracking grid covers them; curvature-scaled directions
        # (k > 0 via two_loop's gamma) are already well-sized.
        raw_scale = jnp.logical_or(~descent, k == 0)
        d = jnp.where(
            raw_scale, d / (jnp.linalg.norm(d) + _EPS), d
        )
        with jax.named_scope("lbfgs/line_search"):
            along, value_and_grad_at = (ray or plain_ray)(w, d)
            # a ray's trial values are sums of two products, and the
            # carried f is a value of the PREVIOUS ray: the two round
            # apart by an ulp or two, which near a solve's float32
            # floor is more than the decrease — so the Armijo test
            # holds phi(t) against phi(0) of the same ray (a pass over
            # the logits, no product), as the plain search holds
            # fun(w + t d) against fun(w)
            f0 = f if ray is None else along(0.0)
            t, f_new, ok, n_halved = line_search(along, f0, jnp.dot(g, d))
        w_new = w + t * d
        with jax.named_scope("lbfgs/value_and_grad"):
            f_new2, g_new = value_and_grad_at(t)
        with jax.named_scope("lbfgs/history_update"):
            s = w_new - w
            yv = g_new - g
            sy = jnp.dot(s, yv)
            # curvature check: only store pairs with s·y > 0
            store = sy > 1e-10
            # a stored pair becomes row m-1 and every row ages by one

            def push(H, newest):
                return jnp.where(
                    store, jnp.concatenate([H[1:], newest[None]]), H)

            S = push(S, s)
            Y = push(Y, yv)
            rho = push(rho, 1.0 / (sy + _EPS))
            k_new = k + jnp.where(store, 1, 0)
        converged = jnp.max(jnp.abs(g_new)) <= tol
        stalled = ~ok  # line search failed to find decrease
        # ``done`` also latches the iteration cap so the flag alone
        # answers "will more steps change this lane?" — what the
        # backend's flags-only compaction gather reads
        done_new = converged | stalled | (it + 1 >= max_iter)
        # trial at t0 + the halvings + the value-and-gradient above
        nfev_new = nfev + n_halved + 2
        return (w_new, f_new2, g_new, S, Y, rho, k_new, it + 1, nfev_new,
                done_new)

    return body


def lbfgs_carry_init(fun, w0, max_iter=100, tol=1e-4, history=10):
    """Initial L-BFGS carry for ``fun(w) -> scalar`` from ``w0``.

    The carry is a dict pytree over :data:`LBFGS_CARRY_KEYS`; feed it to
    :func:`lbfgs_resume` to advance it. ``done`` is True when no further
    step can change the lane (converged at ``tol``, line-search stall,
    or ``max_iter`` reached)."""
    value_and_grad = jax.value_and_grad(fun)
    p = w0.shape[0]
    m = history
    f0, g0 = value_and_grad(w0)
    done0 = (jnp.max(jnp.abs(g0)) <= tol) | jnp.asarray(max_iter <= 0)
    return dict(zip(LBFGS_CARRY_KEYS, (
        w0, f0, g0,
        jnp.zeros((m, p), w0.dtype),
        jnp.zeros((m, p), w0.dtype),
        jnp.zeros(m, w0.dtype),
        jnp.array(0), jnp.array(0), jnp.array(1), done0,
    )))


def lbfgs_resume(fun, carry, n_steps, max_iter=100, tol=1e-4, history=10,
                 max_ls=20):
    """Advance an L-BFGS carry by at most ``n_steps`` iterations.

    Applies the exact iteration body of :func:`lbfgs_minimize` (they
    share one closure), stopping early when the lane converges/stalls
    or hits ``max_iter``. ``n_steps >= max_iter`` therefore runs the
    solve to completion in one call — which is precisely how
    ``lbfgs_minimize`` is implemented, making chained short resumes
    bitwise identical to the unsliced solve."""
    value_and_grad = jax.value_and_grad(fun)
    body = _lbfgs_body(fun, value_and_grad, max_iter, tol, history, max_ls)
    state = tuple(carry[k] for k in LBFGS_CARRY_KEYS)

    def cond_j(state_j):
        (_, _, _, _, _, _, _, it, _, done), j = state_j
        return (j < n_steps) & (it < max_iter) & ~done

    def body_j(state_j):
        state, j = state_j
        return body(state), j + 1

    state, _ = lax.while_loop(cond_j, body_j, (state, jnp.array(0)))
    return dict(zip(LBFGS_CARRY_KEYS, state))


def lbfgs_minimize(fun, w0, max_iter=100, tol=1e-4, history=10, max_ls=20):
    """Minimise ``fun(w) -> scalar`` from ``w0`` (flat vector).

    Returns ``(w, n_iter)``. Convergence: ``max|grad| <= tol`` (the same
    criterion sklearn passes to scipy's lbfgs as ``gtol``). Implemented
    as :func:`lbfgs_carry_init` + one full-length
    :func:`lbfgs_resume`, so iteration-sliced runs share its exact
    trajectory."""
    carry = lbfgs_carry_init(fun, w0, max_iter=max_iter, tol=tol,
                             history=history)
    carry = lbfgs_resume(fun, carry, max_iter, max_iter=max_iter, tol=tol,
                         history=history, max_ls=max_ls)
    return carry["w"], carry["it"]


# ---------------------------------------------------------------------------
# SGD
# ---------------------------------------------------------------------------

#: order of the SGD carry leaves (``pstate`` is the post_step pytree)
SGD_CARRY_KEYS = ("w", "pstate", "step", "best", "bad", "n_done", "it",
                  "done")


def sgd_batch_scan(grad_fn, learning_rate_fn, post_step, loss_fn, track,
                   carry4, batches):
    """Advance the ``(w, pstate, step, acc)`` quadruple over a fixed
    stack of sample-index ``batches`` — the inner mini-batch loop,
    extracted so the resident epoch body (:func:`_sgd_epoch_body`) and
    the streamed block kernels (``models/streaming.py``; an epoch there
    is a SEQUENCE of these scans, one per row block) apply the exact
    same traced update — identical op sequence, so a block-streamed
    epoch that visits the same rows in the same order is bitwise
    identical to the resident scan."""

    def one(carry, idx):
        w, pstate, step, acc = carry
        g = grad_fn(w, idx)
        lr = learning_rate_fn(step)
        w_new = w - lr * g
        if post_step is not None:
            w_new, pstate = post_step(w_new, pstate, lr)
        if track:
            acc = acc + loss_fn(w_new, idx)
        return (w_new, pstate, step + 1, acc), None

    return lax.scan(one, carry4, batches)[0]


def _sgd_epoch_body(grad_fn, keys, n_samples, max_epochs, batch_size,
                    learning_rate_fn, shuffle, loss_fn, tol,
                    n_iter_no_change, post_step):
    """One SGD epoch on the tuple state
    ``(w, pstate, step, best, bad, n_done, it, done)``, keyed by the
    *global* epoch index (``it``-relative) so a resumed slice draws the
    same shuffles the unsliced scan would. Shared by the unsliced solve
    and every slice."""
    n_batches = -(-n_samples // batch_size)
    padded = n_batches * batch_size
    track = loss_fn is not None and tol is not None

    def epoch(carry, e):
        w, pstate, step, best, bad, n_done, it, done = carry
        # global epoch index -> the SAME per-epoch key as the unsliced
        # scan; clamped for overhanging slice tails (frozen below)
        ekey = keys[jnp.minimum(e, max_epochs - 1)]
        if shuffle:
            perm = jax.random.permutation(ekey, padded) % n_samples
        else:
            perm = jnp.arange(padded) % n_samples
        batches = perm.reshape(n_batches, batch_size)

        w_new, pstate_new, step_new, acc = sgd_batch_scan(
            grad_fn, learning_rate_fn, post_step, loss_fn, track,
            (w, pstate, step, jnp.float32(0.0)), batches,
        )
        # frozen lanes keep everything: early-stopped lanes, and every
        # lane of an epoch index past max_epochs (a slice tail that
        # overhangs the cap — the unsliced scan never reaches it)
        keep = done | (e >= max_epochs)

        def pick(a, b):
            return jnp.where(keep, a, b)

        if track:
            loss = acc / n_batches
            improved = loss < best - tol
            bad_new = jnp.where(improved, 0, bad + 1)
            newly_stopped = bad_new >= n_iter_no_change
            best_new = jnp.minimum(best, loss)
        else:
            bad_new = bad
            newly_stopped = jnp.asarray(False)
            best_new = best
        it_new = jnp.where(e >= max_epochs, it, it + 1)
        done_new = keep | newly_stopped | (it_new >= max_epochs)
        return (
            pick(w, w_new),
            jax.tree_util.tree_map(pick, pstate, pstate_new),
            pick(step, step_new),
            pick(best, best_new),
            pick(bad, bad_new),
            pick(n_done, n_done + 1),
            it_new,
            done_new,
        ), None

    return epoch


def sgd_carry_init(w0, post_state=()):
    """Initial SGD carry (dict over :data:`SGD_CARRY_KEYS`)."""
    return dict(zip(SGD_CARRY_KEYS, (
        w0, post_state, jnp.array(0), jnp.float32(jnp.inf),
        jnp.array(0), jnp.array(0), jnp.array(0), jnp.array(False),
    )))


def sgd_resume(grad_fn, carry, n_steps, n_samples, key, max_epochs,
               batch_size, learning_rate_fn, shuffle=True, loss_fn=None,
               tol=None, n_iter_no_change=5, post_step=None):
    """Advance an SGD carry by ``n_steps`` epochs (a fixed-shape scan;
    lanes already stopped — and slice tails overhanging ``max_epochs``
    — freeze in place, exactly as the unsliced scan freezes stopped
    lanes). ``key`` must be the same PRNG key every call: per-epoch
    keys are re-derived from it and indexed by the carry's global epoch
    clock, so slice boundaries cannot change the shuffle sequence."""
    keys = jax.random.split(key, max_epochs)
    epoch = _sgd_epoch_body(
        grad_fn, keys, n_samples, max_epochs, batch_size,
        learning_rate_fn, shuffle, loss_fn, tol, n_iter_no_change,
        post_step,
    )
    state = tuple(carry[k] for k in SGD_CARRY_KEYS)
    it0 = carry["it"]
    state, _ = lax.scan(epoch, state, it0 + jnp.arange(n_steps))
    return dict(zip(SGD_CARRY_KEYS, state))


def sgd_minimize(grad_fn, w0, n_samples, key, max_epochs, batch_size,
                 learning_rate_fn, shuffle=True, loss_fn=None, tol=None,
                 n_iter_no_change=5, post_step=None, post_state=None):
    """Mini-batch SGD with per-step learning-rate schedule.

    ``grad_fn(w, idx) -> grad`` computes the (penalised) gradient on the
    sample index batch ``idx``. Fixed-shape batches: ``n_samples`` is
    padded up to a multiple of ``batch_size`` with wrap-around indices —
    acceptable for the stochastic setting and keeps shapes static.

    Early stopping (sklearn ``SGDClassifier``'s no-validation rule):
    when ``loss_fn(w, idx) -> weighted mean batch loss`` and ``tol`` (a
    traced scalar is fine — it may ride a vmapped hyper axis) are
    given, the mean per-batch training loss of each epoch is tracked;
    an epoch that fails to beat ``best_loss - tol`` counts against
    ``n_iter_no_change``, and once the count is reached the lane
    FREEZES — the scan still runs ``max_epochs`` iterations (static
    shape, vmap-batchable), but stopped lanes keep their weights, so
    ``tol`` semantics hold per task without dynamic trip counts. A
    ``tol`` of ``-inf`` (the mapping for sklearn's ``tol=None``) never
    triggers and reproduces the fixed-epoch behaviour.

    ``post_step(w, state, lr) -> (w, state)``: stateful per-update
    transform applied AFTER each gradient step, threaded through the
    scan from ``post_state`` (an arbitrary pytree; frozen lanes keep
    it). The truncated-gradient L1 penalty (Tsuruoka et al.'s
    cumulative penalty, what sklearn's SGD applies) lives here — it is
    a proximal-style elementwise operation with persistent (u, q)
    state, not a gradient term.

    Implemented as :func:`sgd_carry_init` + one ``max_epochs``-long
    :func:`sgd_resume`, so iteration-sliced runs share its exact
    epoch sequence. Returns ``(w, n_epochs_run)``.
    """
    if post_step is None:
        post_state = ()
    carry = sgd_carry_init(w0, post_state)
    carry = sgd_resume(
        grad_fn, carry, max_epochs, n_samples, key, max_epochs,
        batch_size, learning_rate_fn, shuffle=shuffle, loss_fn=loss_fn,
        tol=tol, n_iter_no_change=n_iter_no_change, post_step=post_step,
    )
    return carry["w"], carry["n_done"]
