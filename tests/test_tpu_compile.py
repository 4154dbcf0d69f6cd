"""
Compile rehearsals: the kernels of the main paths, at the widths
``chip_smoke.py`` runs them, compiled for a DESCRIBED ``v5e:2x2`` with
no chip attached — what the TPU's compiler refuses (an unsupported
primitive in a Pallas kernel, a misaligned block, a program that does
not fit 16 GB) it refuses here, at no chip time.

A compile that passes is not a chip run: nothing executes, so these say
nothing about results or times.

The topology is described inside a module-scoped fixture (never at
import, in a ``skipif`` or in ``parametrize``): only one process may
load the TPU's library, so only the xdist worker that is GIVEN this
file may make the call, and it compiles in its own process. JAX's
persistent compilation cache is off around these tests — an entry
compiled for a described chip is written but cannot be read back
without one. Code that asks ``jax.default_backend()`` still sees the
CPU here, so each test compiles the kernel or jitted step itself and
names the engine (``hist_mode='matmul'``, ``interpret=False``) that the
chip would resolve.
"""

import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

#: one v5e chip's HBM
HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def sds(one_chip):
    """``sds(shape, dtype)``: a shape on the described chip."""

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    return make


def _on_chip(tree, sds):
    """The same shapes, placed on the described chip."""
    return jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), tree)


def _device_bytes(compiled):
    ma = compiled.memory_analysis()
    return (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)


# ---------------------------------------------------------------------------
# the Pallas kernel: must be IN the program as a Mosaic custom call
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d,B,nl,C", [
    (200_000, 28, 64, 32, 2),   # chip_smoke's kernels phase
    (100_000, 54, 64, 16, 3),   # covtype-shaped, three channels
])
def test_level_histogram_compiles(sds, n, d, B, nl, C):
    from skdist_tpu.ops.pallas_hist import level_histogram

    compiled = jax.jit(
        lambda Xb, key, Ych: level_histogram(
            Xb, key, Ych, nl=nl, n_bins=B, interpret=False)
    ).lower(sds((n, d), jnp.int32), sds((n,), jnp.int32),
            sds((n, C))).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# the padded pair's gathers and scatters: the forms that run on the chip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel", ["matvec", "rmatvec", "gram"])
def test_padded_pair_kernels_compile(sds, kernel):
    """(11,314 rows, m=128, p=4,096, k=20): the gather, the scatter-add
    and the m² scatter of the gram, as XLA lowers them for the chip —
    no Mosaic kernel, and each beside its operands well inside 16 GB."""
    from skdist_tpu import sparse as sx

    n, m, p, k = 11_314, 128, 4096, 20
    idx, val = sds((n, m), jnp.int32), sds((n, m))
    fn, arg = {
        "matvec": (sx.packed_matvec, sds((p, k))),
        "rmatvec": (lambda i, v, r: sx.packed_rmatvec(i, v, r, p),
                    sds((n, k))),
        "gram": (lambda i, v, sw: sx.packed_weighted_gram(i, v, sw, p),
                 sds((n,))),
    }[kernel]
    compiled = jax.jit(fn).lower(idx, val, arg).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert ("gather" if kernel == "matvec" else "scatter") in text
    assert _device_bytes(compiled) < 0.25 * HBM_BYTES


# ---------------------------------------------------------------------------
# the jitted steps of the main paths, at the smoke's widths
# ---------------------------------------------------------------------------

def _cv_step_program(n, d, k, n_lanes, mesh=None):
    """The vmapped L-BFGS step slice DistGridSearchCV builds for the
    compacted path, as an un-sharded jit entry plus the shapes it
    takes — or, on a ``('tasks', 'data')`` ``mesh``, the entry the
    backend builds there (the task axis on ``tasks``, the rows of the
    shared operands on ``data``) and shapes that carry those
    shardings."""
    from skdist_tpu.distribute.search import (
        _cached_cv_kernel, _cv_iterative_spec, _cv_kernel_key,
        _resolve_device_scoring,
    )
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.models.linear import _freeze, extract_aux
    from skdist_tpu.parallel.backend import (
        _iterative_jit_entries, resolve_slice_iters,
    )

    est = LogisticRegression(max_iter=30, tol=1e-4)
    rng = np.random.RandomState(0)
    # meta depends on the width and the classes only: prep on few rows
    data, meta = est._prep_fit_data(
        rng.rand(4 * k, d).astype(np.float32), np.arange(4 * k) % k, None)
    static = _freeze(est._static_config(meta))
    specs = _resolve_device_scoring(est, "accuracy")
    key = _cv_kernel_key(type(est), meta, static, specs, False)
    classic = _cached_cv_kernel(type(est), meta, static, specs, False,
                                key=key)
    spec, _ = _cv_iterative_spec(
        type(est), meta, static, specs, False, resolve_slice_iters(30),
        fallback=classic, fallback_key=key)
    task_sharding = shared_shardings = None
    cut = {}
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from skdist_tpu.distribute.search import _CV_SAMPLE_AXES

        task_sharding = NamedSharding(mesh, P("tasks"))
        shared_shardings = {
            name: NamedSharding(
                mesh, P() if name not in _CV_SAMPLE_AXES else
                P(*([None] * _CV_SAMPLE_AXES[name]), "data"))
            for name in ("X", "y", "sw", "aux", "train_masks",
                         "test_masks")}
        cut = {name: {"sharding": sh}
               for name, sh in shared_shardings.items()}
    init_fn, step_fn, _, _ = _iterative_jit_entries(
        spec, None, task_sharding, shared_shardings, None)

    def f32(shape, dtype, name=None):
        return jax.ShapeDtypeStruct(shape, dtype, **cut.get(name, {}))

    shared = {
        "X": f32((n, d), jnp.float32, "X"),
        "y": f32((n,), data["y"].dtype, "y"),
        "sw": f32((n,), jnp.float32, "sw"),
        "aux": extract_aux(data),
        "train_masks": f32((5, n), jnp.float32, "train_masks"),
        "test_masks": f32((5, n), jnp.float32, "test_masks"),
    }
    task = {
        "hyper": {name: f32((n_lanes,), jnp.float32)
                  for name in type(est)._hyper_names},
        "split": f32((n_lanes,), jnp.int32),
    }
    carry = jax.eval_shape(init_fn, shared, task)
    return step_fn, shared, task, carry, init_fn


@pytest.mark.parametrize("n, d, k, n_tasks, want_lanes", [
    # the 480-fit text proxy on one device: eight rounds of 60 lanes;
    # lanes x (n, k) softmax temporaries and the L-BFGS history are the
    # likely limit
    (11_314, 4096, 20, 480, 60),
    # the benchmark's cell: all 50 lanes in one round over the 3.2 GB
    # matrix, and the program's own copy of it with the ones column
    (400_000, 2000, 2, 50, 50),
])
def test_lbfgs_cv_step_compiles_and_fits_hbm(sds, n, d, k, n_tasks,
                                             want_lanes):
    """The step slice of the compacted search at the round the backend
    picks on one device, read against 16 GB."""
    from skdist_tpu.parallel.backend import (
        IterativePlan, _size_iterative_round, tree_nbytes,
    )

    step_fn, shared, task, carry, init_fn = _cv_step_program(
        n, d, k, n_tasks)

    class Chip:
        last_shared_bytes = tree_nbytes(shared)

        def _free_device_bytes(self):
            return HBM_BYTES - self.last_shared_bytes

    n_lanes, _, lanes_fit = _size_iterative_round(
        Chip(), IterativePlan(init_fn, step_fn, None, None, shared, None),
        task, n_tasks, None)
    assert n_lanes == want_lanes <= lanes_fit
    task, carry = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((n_lanes,) + a.shape[1:], a.dtype),
        (task, carry))
    shared, task, carry = (_on_chip(t, sds) for t in (shared, task, carry))
    # the step slice is the program the search spends its time in; the
    # init slice and the finalize were rehearsed with it and are smaller
    compiled = step_fn.lower(
        shared, {"task": task, "carry": carry}).compile()
    assert _device_bytes(compiled) < 0.85 * HBM_BYTES


@pytest.mark.parametrize("n, d, k, n_tasks, free, want", [
    # epsilon (the benchmark's cell): 3.2 GB shared, a lane a few MB
    (400_000, 2000, 2, 50, None, (50, "all_tasks")),
    (400_000, 2000, 2, 50, HBM_BYTES - 3_300_000_000, (50, "all_tasks")),
    # the same matrix under a grid of 1,000 fits: as many lanes as
    # weigh what the matrix weighs (238 of 13.6 MB), not all, and not
    # the 125 of eight rounds
    (400_000, 2000, 2, 1000, None, (None, "amortised")),
    # the 480-fit text proxy: a lane's history outweighs its share of
    # the 185 MB matrix, so eight rounds to merge, as ever
    (11_314, 4096, 20, 480, None, (60, "target_rounds")),
    (11_314, 4096, 20, 480, HBM_BYTES - 200_000_000,
     (60, "target_rounds")),
    # ... and on a device with 6 GB left, what fits beside the 480
    # carries that stay resident whatever the round size
    (11_314, 4096, 20, 480, 6_000_000_000, (None, "memory")),
])
def test_round_size_rule_on_real_programs(n, d, k, n_tasks, free, want):
    """The compacted path's round size, from an abstract trace of the
    search's own init program at the two shapes ISSUE 27 pins (nothing
    compiles, so no topology is needed)."""
    from skdist_tpu.parallel.backend import (
        IterativePlan, _lane_footprint, _size_iterative_round, tree_nbytes,
    )

    step_fn, shared, task, _, init_fn = _cv_step_program(n, d, k, n_tasks)
    plan = IterativePlan(init_fn, step_fn, None, None, shared, None)

    class Device:
        last_shared_bytes = tree_nbytes(shared)

        def _free_device_bytes(self):
            return free

    chunk, basis, lanes_fit = _size_iterative_round(
        Device(), plan, task, n_tasks, None)
    assert basis == want[1]
    assert chunk == (want[0] or chunk)
    assert (lanes_fit is None) == (free is None)
    resident, transient, fixed, rows = _lane_footprint(plan, task)
    # the carry: two histories of ten vectors; the temporaries: the
    # carry a step writes and a lane's row-sized values — both products'
    # logits and the residual at the least; no value derived from the
    # shared operands alone is a second X (a transposed operand is the
    # contraction's reading of it): the largest is a row vector, the
    # classes' one-hot, or an empty history before it is a lane's
    columns = 1 if k == 2 else k
    width = (d + 1) * columns
    assert 80 * width <= resident <= 120 * width
    assert transient >= rows
    if k == 2:
        # rows outweigh weights here: the fullest point holds both
        # products' logits, the residual and the fold's weights
        assert rows >= 4 * 4 * n
        assert transient - rows >= resident - 1024
    assert fixed == max(4 * n * columns, 4 * 10 * width) < n * d
    if basis == "amortised":
        # as many rounds as lanes weighing the matrix would make,
        # evenly filled
        assert n_tasks // 8 < chunk < n_tasks
        lanes = -(-Device.last_shared_bytes // (resident + transient))
        rounds = -(-n_tasks // lanes)
        assert chunk == -(-n_tasks // rounds)
    if basis == "memory":
        # not every carry fits beside two running rounds, so one round
        # runs at a time and the cap is what that round may hold: the
        # eight rounds of the rule fit under it
        assert chunk == 60 <= lanes_fit < n_tasks


def _skewed_text_csr(n, d, nnz, seed=0):
    """A CSR in the shape of a vectorised corpus: log-normal row
    lengths (mean ``nnz / n``, the longest some twenty times that),
    Zipf term popularity, rows of unit norm."""
    import scipy.sparse as sp

    rng = np.random.RandomState(seed)
    lens = rng.lognormal(0.0, 0.861, n)
    lens = np.maximum(1, np.round(lens * (nnz / lens.sum()))).astype(int)
    cdf = np.cumsum(1.0 / (np.arange(d) + 10.0))
    doc = np.repeat(np.arange(n), 2 * lens)
    term = np.searchsorted(cdf, rng.random_sample(doc.size) * cdf[-1])
    X = sp.csr_matrix((np.ones(doc.size, np.float32),
                       (doc, np.minimum(term, d - 1))), shape=(n, d))
    X.sum_duplicates()
    return sp.diags(1.0 / np.sqrt(X.multiply(X).sum(axis=1)).A1).dot(
        X).astype(np.float32).tocsr()


def test_round_size_rule_at_the_text_cell():
    """``search-20news130k``'s shapes (a corpus of its size and skew,
    packed bucketed; nothing compiles): 50 lanes of 229 MB outweigh the
    matrix and the chip, so the rule is ``memory``: one round on the
    device at a time, the eight rounds of 7 that ``target_rounds``
    asks for, under the cap of what 15.75 GiB hold of one round."""
    from skdist_tpu import sparse as sx
    from skdist_tpu.distribute.search import (
        _cached_cv_kernel, _cv_iterative_spec, _cv_kernel_key,
        _resolve_device_scoring,
    )
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.models.linear import _freeze, extract_aux
    from skdist_tpu.parallel.backend import (
        IterativePlan, _iterative_jit_entries, _lane_footprint,
        _size_iterative_round, resolve_slice_iters, tree_nbytes,
    )

    n, d, k, n_tasks = 11_314, 130_107, 20, 50
    X = _skewed_text_csr(n, d, 1_787_565)
    y = np.arange(n) % k
    rows = np.diff(X.indptr)
    assert rows.max() > 4 * np.percentile(rows, 95)
    B = sx.pack_for_fit(X)
    assert isinstance(B, sx.BucketedX)
    # most of the stored elements sit in a dense head of under 1 GiB;
    # the rest pay a quarter in padding, not the 15 x of max-row padding
    assert B.head.nbytes < 1 << 30 and B.head_nnz > 0.6 * X.nnz
    assert (B.placed - B.head_nnz) / (B.slots - B.head.size) > 0.7
    est = LogisticRegression(max_iter=100, tol=1e-4)
    data, meta = est._prep_fit_data(B, y, None)
    static = _freeze(est._static_config(meta))
    specs = _resolve_device_scoring(est, "neg_log_loss")
    key = _cv_kernel_key(type(est), meta, static, specs, False)
    classic = _cached_cv_kernel(type(est), meta, static, specs, False,
                                key=key)
    spec, _ = _cv_iterative_spec(
        type(est), meta, static, specs, False, resolve_slice_iters(100),
        fallback=classic, fallback_key=key)
    init_fn, _, _, _ = _iterative_jit_entries(spec, None, None, None, None)
    f32 = jax.ShapeDtypeStruct
    shared = {
        "X": B, "y": f32((n,), data["y"].dtype),
        "sw": f32((n,), jnp.float32), "aux": extract_aux(data),
        "train_masks": f32((5, n), jnp.float32),
        "test_masks": f32((5, n), jnp.float32),
    }
    task = {
        "hyper": {name: f32((n_tasks,), jnp.float32)
                  for name in type(est)._hyper_names},
        "split": f32((n_tasks,), jnp.int32),
    }
    # no step program: the rule alone, not the check against a compile
    plan = IterativePlan(init_fn, None, None, None, shared, None)

    class Chip:
        last_shared_bytes = tree_nbytes(shared)

        def _free_device_bytes(self):
            return int(15.75 * 1024 ** 3) - self.last_shared_bytes

    chunk, basis, lanes_fit = _size_iterative_round(
        Chip(), plan, task, n_tasks, None)
    assert basis == "memory"
    assert chunk == 7 <= lanes_fit < 50
    resident, transient, fixed, rows = _lane_footprint(plan, task)
    width = (d + 1) * k
    # W, gradient and two histories of ten: 22 vectors, resident; a
    # running lane holds a carry more and the store's two histories in
    # the making (0.85 GB was read on the chip where the old estimate
    # said 0.67: PERF.md), next to nothing of it shaped like the rows
    assert 4 * 22 * width <= resident <= 4 * 23 * width
    assert 2 * resident < transient < 3 * resident
    assert rows < transient // 100
    assert Chip.last_shared_bytes < 4 * resident


@pytest.mark.parametrize("free_gib, want", [
    # the v5e beside the 6.4 GB matrix: four rounds of 13 lanes, one
    # at a time; a lane is its logits, five of 80 MB and two row
    # vectors, so 21 fit where the old two-values rule said 54
    (15.75, (13, "memory", 21)),
    # half the chip gone: what is left holds five rounds of 10
    (15.75 / 2 + 3, (10, "memory", None)),
    # a chip of twice the memory: as many lanes as weigh the matrix
    (31.5, (13, "amortised", None)),
])
def test_round_size_rule_at_the_mnist_cell(free_gib, want):
    """``search-mnist8m``'s shapes (nothing compiles): 50 lanes whose
    weights are nothing (0.7 MB) and whose logits are 0.42 GB a lane
    while a round runs."""
    from skdist_tpu.parallel.backend import (
        IterativePlan, _lane_footprint, _size_iterative_round, tree_nbytes,
    )

    n, d, k, n_tasks = 2_000_000, 784, 10, 50
    step_fn, shared, task, _, init_fn = _cv_step_program(n, d, k, n_tasks)
    plan = IterativePlan(init_fn, step_fn, None, None, shared, None)

    class Chip:
        last_shared_bytes = tree_nbytes(shared)

        def _free_device_bytes(self):
            return int(free_gib * 1024 ** 3) - self.last_shared_bytes

    chunk, basis, lanes_fit = _size_iterative_round(
        Chip(), plan, task, n_tasks, None)
    assert (chunk, basis) == want[:2]
    assert lanes_fit == (want[2] or lanes_fit) >= chunk
    resident, transient, fixed, rows = _lane_footprint(plan, task)
    assert rows > 0.99 * (resident + transient)
    assert 5 * 4 * n * k <= transient <= 5.3 * 4 * n * k


def _buffer_opcodes(hlo, shape):
    """The opcode of every instruction outside a fused computation
    whose result holds ``shape`` — the values the program keeps in
    memory; what a fusion computes on the way is not one."""
    fused = True
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) \(.*\{$", line)
        if head:
            fused = head.group(1).startswith("%fused_computation")
        elif not fused and " = " in line:
            # "<name> = <result type> <opcode>(<operands>)...": a type
            # has no blank before a "(", an opcode always has
            rhs = line.split(" = ", 1)[1]
            op = re.search(r"\s([a-z][\w\-]*)\(", rhs)
            if op and shape in rhs[:op.start()]:
                yield op.group(1)


def _tiled_buffers(hlo, dims):
    """``(opcode, device bytes)`` of every value outside a fused
    computation whose shape holds exactly ``dims`` in some order, the
    bytes as its layout tiles them (``{minor_to_major:T(8,128)}``: the
    minor axis padded to 128, the next to 8)."""
    fused = True
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) \(.*\{$", line)
        if head:
            fused = head.group(1).startswith("%fused_computation")
            continue
        found = not fused and re.search(
            r" = f32\[([\d,]+)\]\{([\d,]+):T\((\d+),(\d+)\)[^}]*\} "
            r"([a-z][\w\-]*)\(", line)
        if not found:
            continue
        shape = [int(n) for n in found.group(1).split(",")]
        if sorted(shape) != sorted(dims):
            continue
        order = [int(i) for i in found.group(2).split(",")]
        for axis, tile in zip(order, (int(found.group(4)),
                                      int(found.group(3)))):
            shape[axis] = -(-shape[axis] // tile) * tile
        yield found.group(5), 4 * int(np.prod(shape))


def test_dense_multinomial_step_holds_its_logits_rows_minor(sds):
    """``search-mnist8m``'s step program (2,000,000 x 784, 10 classes)
    at the round the backend picks against 15.75 GiB: every
    logits-shaped value the program keeps is rows-minor — its device
    bytes within 1.7 x of ``lanes * k * n * 4``, where a ``k``-minor
    one is 12.8 x — there are no more than five of them, no copy of X
    with a ones column exists, and the whole fits the chip."""
    from skdist_tpu.parallel.backend import (
        IterativePlan, _lane_footprint, _size_iterative_round, tree_nbytes,
    )

    n, d, k = 2_000_000, 784, 10
    step_fn, shared, task, carry, init_fn = _cv_step_program(n, d, k, 50)

    class Chip:
        last_shared_bytes = tree_nbytes(shared)

        def _free_device_bytes(self):
            return HBM_BYTES - self.last_shared_bytes

    lanes, basis, lanes_fit = _size_iterative_round(
        Chip(), IterativePlan(init_fn, step_fn, None, None, shared, None),
        task, 50, None)
    assert (lanes, basis) == (13, "memory") and lanes_fit >= lanes
    task, carry = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((lanes,) + a.shape[1:], a.dtype),
        (task, carry))
    shared, task, carry = (_on_chip(t, sds) for t in (shared, task, carry))
    compiled = step_fn.lower(
        shared, {"task": task, "carry": carry}).compile()
    hlo = compiled.as_text()
    logits = list(_tiled_buffers(hlo, (lanes, k, n)))
    kept = [size for op, size in logits
            if op not in ("get-tuple-element", "bitcast", "parameter")]
    assert kept and len(kept) <= 5, logits
    assert max(size for _, size in logits) <= 1.7 * lanes * k * n * 4
    assert not list(_tiled_buffers(hlo, (n, d + 1)))
    assert {op for op, _ in _tiled_buffers(hlo, (n, d))} <= {
        "parameter", "get-tuple-element"}
    # it fits what the chip reports (15.75 GiB), at 1.25 x what round
    # sizing booked: the small axis of a (lanes, 10, n) value is padded
    # to a tile's 16 and XLA keeps two layouts of it here, which the
    # count of the traced program does not see (PERF.md section 7)
    assert _device_bytes(compiled) < 15.75 * 1024 ** 3
    resident, transient, fixed, _ = _lane_footprint(
        IterativePlan(init_fn, step_fn, None, None,
                      jax.tree_util.tree_map(
                          lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          shared), None), task)
    booked = Chip.last_shared_bytes + fixed + lanes * (resident + transient)
    assert 0.75 * _device_bytes(compiled) < booked < _device_bytes(compiled)


def test_row_sharded_step_fits_a_chip_and_reduces_partial_sums_only(topo):
    """``search-mnist8m-full-4chip``'s step program — ALL of mnist8m,
    8,100,000 x 784, row-sharded over the four described chips on a
    ``tasks`` 1 x ``data`` 4 mesh — at the round the backend picks
    against a chip's 15.75 GiB beside its 6.45 GB of the shared
    operands: 13 lanes by memory, as ``search-mnist8m`` (a device holds
    the same rows there); the program fits a device, and every
    collective in it is a sum of partial sums — a round's losses and
    its ``(lanes, 785, 10)`` gradients — none with an axis of the
    data's rows or of a shard's: the partitioner gathers neither X nor
    the logits."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from skdist_tpu.parallel.backend import (
        IterativePlan, _lane_footprint, _size_iterative_round,
        hlo_collectives, tree_nbytes,
    )

    n, d, k, n_tasks, shards = 8_100_000, 784, 10, 50, 4
    mesh = Mesh(np.array(topo.devices[:shards]).reshape(1, shards),
                ("tasks", "data"))
    step_fn, shared, task, carry, init_fn = _cv_step_program(
        n, d, k, n_tasks, mesh)

    class Chip:
        last_shared_bytes = tree_nbytes(shared, per_device=True)

        def _free_device_bytes(self):
            return int(15.75 * 1024 ** 3) - self.last_shared_bytes

    assert 6.4e9 < Chip.last_shared_bytes < 6.5e9
    plan = IterativePlan(init_fn, step_fn, None, None, shared, None,
                         data_shards=shards)
    lanes, basis, lanes_fit = _size_iterative_round(
        Chip(), plan, task, n_tasks, None)
    # (``search-mnist8m``'s cap reads 21: its 2,000,000 rows are a few
    # fewer than a shard's 2,025,000 here)
    assert (lanes, basis, lanes_fit) == (13, "memory", 20)
    resident, transient, fixed, rows = _lane_footprint(plan, task)
    # a device's share of a lane: five values of (10, 2,025,000) floats
    assert 5 * 4 * (n // shards) * k <= transient <= 5.3 * 4 * (
        n // shards) * k
    assert rows > 0.99 * (resident + transient)
    tasks_on = NamedSharding(mesh, P("tasks"))
    task, carry = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((lanes,) + a.shape[1:], a.dtype,
                                       sharding=tasks_on),
        (task, carry))
    compiled = step_fn.lower(
        shared, {"task": task, "carry": carry}).compile()
    # memory_analysis() of a partitioned program is one device's
    assert _device_bytes(compiled) < 15.75 * 1024 ** 3
    booked = Chip.last_shared_bytes + fixed + lanes * (resident + transient)
    assert 0.7 * _device_bytes(compiled) < booked < 1.1 * _device_bytes(
        compiled)
    found = hlo_collectives(compiled.as_text())
    assert 1 <= len(found) <= 8, found
    for shapes, _ in found:
        for dims in shapes:
            assert n not in dims and n // shards not in dims, found
            assert math.prod(dims) <= lanes * (d + 1) * k, found
    assert sum(size for _, size in found) < 10e6


def test_lbfgs_history_moves_no_row_lane_by_lane(sds):
    """The vmapped L-BFGS slice alone (a quadratic loss, so that only
    the solver is in the program) at the text cell's lane shape: 7
    lanes, ``p = 20 * 130,108``, ``m = 10``. The history's rows are read
    by position, so the optimised program has no ``gather``, no
    ``scatter``, no ``dynamic-update-slice`` and no loop that builds a
    ``f32[lanes,1,p]`` row lane by lane; the loops are the slice's, the
    line search's and the two-loop's own two. The newest pair
    ``s[None]`` reaches the store's fusion as a ``(lanes, 1, p)`` view
    of a ``(lanes, p)`` buffer; nothing writes one in place or carries
    one through a loop."""
    from skdist_tpu.models.solvers import lbfgs_carry_init, lbfgs_resume

    lanes, p, m = 7, 20 * 130_108, 10

    def quadratic(a, b):
        return lambda w: 0.5 * jnp.dot(a * w, w) - jnp.dot(b, w)

    def init(a, b):
        return lbfgs_carry_init(quadratic(a, b), jnp.zeros_like(b), 30,
                                1e-4, m)

    def step(a, carry, b):
        return lbfgs_resume(quadratic(a, b), carry, 4, 30, 1e-4, m)

    a, b = sds((p,)), sds((lanes, p))
    carry = _on_chip(
        jax.eval_shape(jax.vmap(init, in_axes=(None, 0)), a, b), sds)
    assert carry["S"].shape == carry["Y"].shape == (lanes, m, p)
    compiled = jax.jit(jax.vmap(step, in_axes=(None, 0, 0)),
                       donate_argnums=1).lower(a, carry, b).compile()
    hlo = compiled.as_text()
    assert not re.search(r"\b(gather|scatter)\(", hlo)
    assert "dynamic-update-slice(" not in hlo
    assert len(re.findall(r"\bwhile\(", hlo)) <= 4
    # the parser reads XLA's text: it has to find the history, which is
    # there, before what it does not find of a row means anything
    assert "while" in set(_buffer_opcodes(hlo, f"f32[{lanes},{m},{p}]"))
    row = f"f32[{lanes},1,{p}]"
    # no loop carries one (parameter, tuple, while), nothing writes one
    # in place
    opcodes = set(_buffer_opcodes(hlo, row))
    assert opcodes <= {"fusion", "get-tuple-element", "copy-start",
                       "copy-done", "bitcast"}, opcodes
    assert _device_bytes(compiled) < HBM_BYTES


def test_bucketed_multinomial_ray_moves_no_weights_lane_by_lane(sds):
    """The lane-batched ray of the multinomial problem (``X̃w``, ``X̃d``,
    a halving loop over ``along``, the VJP ``X̃ᵀr``) over a ``BucketedX``
    of the text cell's WIDTH — 7 lanes, ``d`` 130,107, ``k`` 20; a
    corpus of 2,048 rows, so that it compiles in seconds. A round's
    weights lie classes-major in 128-aligned rows
    (``_BucketedOperator``): ``(7, 20·130,176)`` is ``(140, 130,176)``
    as it lies, and a 2-D copy on each side of a product is all the
    relayout there is. Rows-major (PR 32) the same program kept
    ``f32[7,130108,20]`` buffers — 20 classes padded to a tile's 128 —
    and flattened them lane by lane through a 1-D ``f32[18215120]``:
    a third of the cell's device time. The loops left are the gathers'
    own scans and the line search's."""
    from skdist_tpu import sparse as sx
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.models.linear import _freeze, maybe_exact_matmuls

    n, d, k, lanes = 2048, 130_107, 20, 7
    B = sx.pack_csr_buckets(_skewed_text_csr(n, d, 320_000))
    est = LogisticRegression(max_iter=30, tol=1e-4)
    data, meta = est._prep_fit_data(B, np.arange(n) % k, None)
    problem = LogisticRegression._build_fit_problem(
        meta, _freeze(est._static_config(meta)))

    def ray_step(X, y, sw, C, w, direction, bound):
        loss, w0, _ = problem(X, y, sw, {"C": C})
        assert w0.shape == w.shape
        along, value_and_grad_at = loss.ray(w, direction)
        t = jax.lax.while_loop(
            lambda t: (along(t) > bound) & (t > 1e-6),
            lambda t: 0.5 * t, jnp.float32(1.0))
        return value_and_grad_at(t)

    width = 20 * 130_176
    assert sx.LinearOperator(B, True).flat_size(k) == width
    step = jax.jit(jax.vmap(
        maybe_exact_matmuls(LogisticRegression, ray_step),
        in_axes=(None, None, 0, 0, 0, 0, 0)))
    compiled = step.lower(
        _on_chip(B, sds), sds((n,), jnp.int32), sds((lanes, n)),
        sds((lanes,)), sds((lanes, width)), sds((lanes, width)),
        sds((lanes,))).compile()
    hlo = compiled.as_text()
    for gone in ("f32[7,130108,20]", "f32[130108,7,20]", "f32[18215120]",
                 "f32[18224640]"):
        assert gone not in hlo, gone
    # the parser has to find what is there before what it does not find
    # means anything: the transposes, as copies of their own
    assert "copy" in set(_buffer_opcodes(hlo, "f32[140,130176]"))
    scans = 2 * sum(idx.shape[0] > 1 for idx, _ in B.rows) + sum(
        idx.shape[0] > 1 for idx, _ in B.cols)
    assert 0 < len(re.findall(r"\bwhile\(", hlo)) <= scans + 1
    assert _device_bytes(compiled) < HBM_BYTES


def test_matmul_tree_level_step_compiles(sds):
    """The forest tree kernel in ``hist_mode='matmul'`` (what a TPU
    resolves with no calibration entry) at 200,000 x 28, 32 bins, depth
    8, vmapped over a round of trees."""
    from skdist_tpu.models.forest import make_forest_tree_kernel
    from skdist_tpu.models.tree import resolve_max_features

    n, d, n_trees = 200_000, 28, 8
    kernel = make_forest_tree_kernel(
        d=d, n_bins=32, channels=3, max_depth=8,
        max_features=resolve_max_features("sqrt", d),
        min_samples_split=2, min_samples_leaf=1,
        min_impurity_decrease=0.0, extra=False, classification=True,
        bootstrap=True, hist_mode="matmul", fractional_weights=False,
    )
    shared = {"Xb": sds((n, d), jnp.int32), "y": sds((n,), jnp.int32),
              "sw": sds((n,))}
    compiled = jax.jit(
        lambda sh, t: jax.vmap(lambda one: kernel(sh, one))(t)
    ).lower(shared, {"seed": sds((n_trees,), jnp.int32)}).compile()
    assert _device_bytes(compiled) < 0.85 * HBM_BYTES


def test_banked_predict_compiles(sds):
    """One banked predict program of the serving registry: 1,024 int8
    tenants of a 64-feature, 10-class linear model stacked in one bank,
    a flush of 64 slots x 8 rows, each slot gathering its tenant's row
    before the member kernel (in-program dequant included)."""
    from skdist_tpu.distribute.predict import device_predict_plan
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.serve.bank import banked_kernel

    rng = np.random.RandomState(0)
    X = rng.rand(200, 64).astype(np.float32)
    model = LogisticRegression(max_iter=5, engine="xla").fit(
        X, np.arange(200) % 10)
    plan = device_predict_plan(model, "predict_proba", serve_dtype="int8")
    bank, slots, rows = 1024, 64, 8
    stacked = jax.tree_util.tree_map(
        lambda leaf: sds((bank,) + np.shape(leaf), np.asarray(leaf).dtype),
        plan.params)
    kernel = banked_kernel(plan.kernel)
    compiled = jax.jit(
        lambda sh, t: jax.vmap(lambda one: kernel(sh, one))(t)
    ).lower(
        {"params": stacked},
        {"X": sds((slots, rows, 64)), "tid": sds((slots,), jnp.int32)},
    ).compile()
    assert "s8[" in compiled.as_text()  # the bank stays int8 in HBM
