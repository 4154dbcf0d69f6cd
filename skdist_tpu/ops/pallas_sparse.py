"""
Pallas TPU kernels for the packed-CSR contractions.

The sparse plane's two hot contractions (``skdist_tpu.sparse``) lower
poorly through autogenerated XLA on TPU:

- ``packed_matvec`` (``X @ W``) is a gather + row-dot — the gather of
  ``W`` rows serialises through dynamic-slice loops;
- ``packed_rmatvec`` (``X.T @ r``) is ``zeros.at[idx].add(...)`` — a
  scatter-add, the op the histogram work (``ops/pallas_hist.py``)
  already measured as the TPU's weak spot.

These kernels run the SAME contractions as one-hot matmuls whose
one-hot factor is built on the fly in VMEM — the ``pallas_hist``
M-matrix trick applied to the packed pair. For a sample chunk of S
rows and a column block of DB columns, the dense (S, DB) sub-block of
X is rebuilt in VMEM:

    M[i, b] = Σ_j val[i, j] · [idx[i, j] == col0 + b]

(an m-step VPU accumulation — never an (S, m, DB) intermediate, so the
VMEM bill is S·DB·4 bytes however wide the packing is), and the MXU
then contracts it:

    matvec : out[i, :]  += M @ W[col0 : col0+DB]      (accumulate over
                                                       column blocks)
    rmatvec: out[cols]  += Mᵀ @ r[chunk]              (accumulate over
                                                       sample chunks)

The kernels hold the packed pair and the rebuilt block TRANSPOSED
(``(m, S)`` and ``Mᵀ (DB, S)``): step j of the rebuild then reads row j
of the pair through the ref — a dynamic index on the sublane axis,
which the TPU lowering accepts, where a dynamic slice of a loaded value
or a dynamic index on the lane axis is refused — and broadcasts it down
the sublanes.

Nothing of size (n, d) ever exists in HBM — HBM traffic is the packed
pair re-read once per column block (matvec) or once total (rmatvec)
plus the operand/result — and both contractions accumulate in f32 on
the MXU. Padding entries (``idx=0, val=0``) contribute exactly 0.0, so
the kernels are EXACT on the packed representation, matching the XLA
kernels' semantics bit-for-bit on integer data.

``interpret=None`` defers to :func:`skdist_tpu.ops.pallas_interpret`:
the Pallas interpreter off-TPU so the CPU mesh tests the kernels, the
compiled path whenever the default backend is a TPU. Autodiff: ``pallas_call`` has no VJP
rule, so the consumer (``sparse.LinearOperator`` in ``mode='pallas'``)
wraps :func:`packed_matvec` in a ``jax.custom_vjp`` whose backward IS
:func:`packed_rmatvec` (the true transpose) — see
:func:`matvec_with_vjp`.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import pallas_interpret

__all__ = [
    "packed_matvec",
    "packed_rmatvec",
    "packed_weighted_gram",
    "matvec_with_vjp",
]

#: default sample-chunk rows (multiple of the f32 sublane tile)
DEFAULT_S = 256
#: default column-block width (multiple of the 128-lane tile)
DEFAULT_DB = 512


def _ceil_to(x, m):
    return -(-x // m) * m


def _resolve_interpret(interpret):
    return pallas_interpret() if interpret is None else interpret


def _dense_block_t(idx_ref, val_ref, col0, DB):
    """Rebuild the TRANSPOSED dense (DB, S) sub-block of X in VMEM from
    the transposed packed refs ``(m, S)``: an m-step accumulation of
    one-hot-masked packed values, row j read through the refs. The loop
    carries only the (DB, S) accumulator — the (m, DB, S) broadcast
    never exists."""
    from jax.experimental import pallas as pl

    m, S = idx_ref.shape
    cols = col0 + lax.broadcasted_iota(jnp.int32, (DB, S), 0)

    def body(j, MT):
        ij = idx_ref[pl.ds(j, 1), :]  # (1, S)
        vj = val_ref[pl.ds(j, 1), :]
        return MT + jnp.where(ij == cols, vj, 0.0)

    return lax.fori_loop(0, m, body, jnp.zeros((DB, S), val_ref.dtype))


@functools.partial(jax.jit, static_argnames=("interpret", "S", "DB"))
def _matvec_2d(idx, val, W, interpret, S, DB):
    from jax.experimental import pallas as pl

    n, m = idx.shape
    p, k = W.shape
    n_pad = _ceil_to(max(n, S), S)
    p_pad = _ceil_to(max(p, DB), DB)
    if n_pad != n:
        idx = jnp.pad(idx, ((0, n_pad - n), (0, 0)))
        val = jnp.pad(val, ((0, n_pad - n), (0, 0)))
    if p_pad != p:
        # zero rows: padded column ids match no idx entry anyway
        W = jnp.pad(W, ((0, p_pad - p), (0, 0)))

    def kernel(idx_ref, val_ref, w_ref, out_ref):
        di = pl.program_id(1)
        MT = _dense_block_t(idx_ref, val_ref, di * DB, DB)
        part = lax.dot_general(
            MT, w_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (S, k)

        @pl.when(di == 0)
        def _():
            out_ref[...] = part

        @pl.when(di != 0)
        def _():
            out_ref[...] = out_ref[...] + part

    # column blocks on the LAST grid dim: consecutive steps revisit the
    # same output block, which is what makes the += accumulation legal
    grid = (n_pad // S, p_pad // DB)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, S), lambda i, j: (0, i)),
            pl.BlockSpec((m, S), lambda i, j: (0, i)),
            pl.BlockSpec((DB, k), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((S, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, k), jnp.float32),
        interpret=interpret,
    )(idx.T, val.T, W)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("n_cols", "interpret", "S",
                                             "DB"))
def _rmatvec_2d(idx, val, r, n_cols, interpret, S, DB):
    from jax.experimental import pallas as pl

    n, m = idx.shape
    k = r.shape[1]
    n_pad = _ceil_to(max(n, S), S)
    p_pad = _ceil_to(max(n_cols, DB), DB)
    if n_pad != n:
        # zero-val padded rows scatter 0.0 — exact, like the packing
        idx = jnp.pad(idx, ((0, n_pad - n), (0, 0)))
        val = jnp.pad(val, ((0, n_pad - n), (0, 0)))
        r = jnp.pad(r, ((0, n_pad - n), (0, 0)))

    def kernel(idx_ref, val_ref, r_ref, out_ref):
        di = pl.program_id(0)
        si = pl.program_id(1)
        MT = _dense_block_t(idx_ref, val_ref, di * DB, DB)
        part = lax.dot_general(
            MT, r_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (DB, k)

        @pl.when(si == 0)
        def _():
            out_ref[...] = part

        @pl.when(si != 0)
        def _():
            out_ref[...] = out_ref[...] + part

    # sample chunks on the LAST grid dim — the scatter-add recast as a
    # revisited-block MXU accumulation (the pallas_hist trick)
    grid = (p_pad // DB, n_pad // S)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, S), lambda d, s: (0, s)),
            pl.BlockSpec((m, S), lambda d, s: (0, s)),
            pl.BlockSpec((S, k), lambda d, s: (s, 0)),
        ],
        out_specs=pl.BlockSpec((DB, k), lambda d, s: (d, 0)),
        out_shape=jax.ShapeDtypeStruct((p_pad, k), jnp.float32),
        interpret=interpret,
    )(idx.T, val.T, r)
    return out[:n_cols]


def packed_matvec(idx, val, W, *, interpret=None, S=DEFAULT_S,
                  DB=DEFAULT_DB):
    """``X @ W`` on the packed pair via the Pallas rebuild-and-matmul
    kernel. Same contract as ``sparse.packed_matvec``: ``W`` is
    ``(p,)`` or ``(p, k)``; returns ``(n,)`` / ``(n, k)`` f32.
    ``interpret=None`` defers to ``ops.pallas_interpret``."""
    interpret = _resolve_interpret(interpret)
    W = jnp.asarray(W)
    if W.ndim == 1:
        return _matvec_2d(idx, val, W[:, None], interpret, S, DB)[:, 0]
    return _matvec_2d(idx, val, W, interpret, S, DB)


def packed_rmatvec(idx, val, r, n_cols, *, interpret=None, S=DEFAULT_S,
                   DB=DEFAULT_DB):
    """``X.T @ r`` on the packed pair via the Pallas kernel — the
    scatter-add recast as a per-grid-block one-hot contraction
    accumulated in VMEM. Same contract as ``sparse.packed_rmatvec``:
    ``r`` is ``(n,)`` or ``(n, k)``; returns ``(n_cols,)`` /
    ``(n_cols, k)`` f32."""
    interpret = _resolve_interpret(interpret)
    r = jnp.asarray(r)
    if r.ndim == 1:
        return _rmatvec_2d(idx, val, r[:, None], int(n_cols), interpret,
                           S, DB)[:, 0]
    return _rmatvec_2d(idx, val, r, int(n_cols), interpret, S, DB)


@functools.partial(jax.jit, static_argnames=("n_cols", "interpret", "S",
                                             "DB"))
def _gram_2d(idx, val, sw, n_cols, interpret, S, DB):
    from jax.experimental import pallas as pl

    n, m = idx.shape
    n_pad = _ceil_to(max(n, S), S)
    p_pad = _ceil_to(max(n_cols, DB), DB)
    if n_pad != n:
        # zero-weight padded rows contribute exactly 0.0 to every block
        idx = jnp.pad(idx, ((0, n_pad - n), (0, 0)))
        val = jnp.pad(val, ((0, n_pad - n), (0, 0)))
        sw = jnp.pad(sw, ((0, n_pad - n),))
    sw2 = sw[None, :]  # (1, n): a row, to scale Mᵀ along its lanes

    def kernel(idx_ref, val_ref, sw_ref, out_ref):
        a = pl.program_id(0)
        b = pl.program_id(1)
        si = pl.program_id(2)
        MTa = _dense_block_t(idx_ref, val_ref, a * DB, DB)
        MTb = _dense_block_t(idx_ref, val_ref, b * DB, DB)
        part = lax.dot_general(
            MTa * sw_ref[...], MTb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (DB, DB)

        @pl.when(si == 0)
        def _():
            out_ref[...] = part

        @pl.when(si != 0)
        def _():
            out_ref[...] = out_ref[...] + part

    # sample chunks on the LAST grid dim: consecutive steps revisit the
    # same (a, b) output block, making the += accumulation legal — the
    # m² scatter-add recast as rebuilt-sub-block MXU contractions (the
    # pallas_hist trick, applied twice: both gram factors are rebuilt
    # in VMEM, nothing (n, d)-sized touches HBM)
    grid = (p_pad // DB, p_pad // DB, n_pad // S)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, S), lambda a, b, s: (0, s)),
            pl.BlockSpec((m, S), lambda a, b, s: (0, s)),
            pl.BlockSpec((1, S), lambda a, b, s: (0, s)),
        ],
        out_specs=pl.BlockSpec((DB, DB), lambda a, b, s: (a, b)),
        out_shape=jax.ShapeDtypeStruct((p_pad, p_pad), jnp.float32),
        interpret=interpret,
    )(idx.T, val.T, sw2)
    return out[:n_cols, :n_cols]


def packed_weighted_gram(idx, val, sw, n_cols, *, interpret=None,
                         S=DEFAULT_S, DB=DEFAULT_DB):
    """``XᵀSX`` on the packed pair via the Pallas rebuild-and-matmul
    kernel — the last packed contraction to get an on-chip form
    (``sparse.packed_weighted_gram`` is the XLA m²-scatter twin; the
    ridge family's ``LinearOperator(mode='pallas')`` routes here).
    Both (S, DB) sub-block factors of one grid step are rebuilt in
    VMEM and contracted on the MXU with f32 accumulation; symmetric
    off-diagonal blocks are computed independently (each grid step
    stays embarrassingly data-parallel — no cross-block mirroring).
    Same exactness contract as the other kernels: padding entries
    contribute exactly 0.0, bitwise-exact on integer data.
    ``interpret=None`` defers to ``ops.pallas_interpret``."""
    interpret = _resolve_interpret(interpret)
    return _gram_2d(idx, val, jnp.asarray(sw), int(n_cols), interpret,
                    S, DB)


def matvec_with_vjp(idx, val, n_cols, *, interpret=None, S=DEFAULT_S,
                    DB=DEFAULT_DB):
    """``W -> X @ W`` with a custom VJP, for a FIXED packed pair.

    ``pallas_call`` has no autodiff rule, and the solvers differentiate
    the loss through the forward matvec; the true linear transpose of
    ``X @ W`` is ``X.T @ ḡ``, so the backward pass IS the Pallas
    rmatvec kernel. The packed pair is closed over (constant w.r.t.
    the solve), which keeps the custom_vjp signature purely in the
    differentiated argument — safe under jit, vmap (the task axis may
    batch W), scan and while_loop."""
    interpret = _resolve_interpret(interpret)

    @jax.custom_vjp
    def mv(W):
        return packed_matvec(idx, val, W, interpret=interpret, S=S, DB=DB)

    def fwd(W):
        return mv(W), None

    def bwd(_, g):
        return (packed_rmatvec(idx, val, g, n_cols, interpret=interpret,
                               S=S, DB=DB),)

    mv.defvjp(fwd, bwd)
    return mv
