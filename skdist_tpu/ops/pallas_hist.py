"""
Pallas TPU kernel for the per-level tree histogram.

The histogram is the hot op of tree building (models/tree.py): per
level, ``hist[f, j, b, c] = Σ_i [Xb[i,f]==b][node[i]==j]·Ych[i,c]``.
The XLA formulations either scatter (serialises on TPU) or contract a
materialised one-hot ``Xoh (n, d·B)`` against ``NW (n, nl·C)``
(``hist_mode='matmul'``) — one big MXU matmul whose operands round-trip
HBM every level.

This kernel runs the SAME contraction with both one-hot factors built
on the fly in VMEM:

    grid (f, lane-block, sample-chunk):
      M  (S, B)   = [Xb_chunk[f] == bin]          (VPU compares)
      NW (S, LB)  = [node_chunk == lane//C] · Ych_chunk[:, lane%C]
      out[f, :, lane-block] += Mᵀ @ NW            (MXU, f32 accumulate)

so nothing of size (n, d·B) or (n, nl·C) ever exists in HBM; HBM
traffic is the raw inputs re-read ``nl·C/LB`` times. FLOPs are
identical to 'matmul' (d·B·n·nl·C — no padding waste: the node axis
rides the MXU lane dimension fused with channels).

``interpret=None`` defers to :func:`skdist_tpu.ops.pallas_interpret`:
the Pallas interpreter off-TPU, so correctness is testable on the CPU
mesh, and the compiled path whenever the default backend is a TPU.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from . import pallas_interpret


def _ceil_to(x, m):
    return -(-x // m) * m


def level_histogram(Xb, node_key, Ych, *, nl, n_bins, interpret=None,
                    S=512, LB=128):
    """Per-level histogram via a Pallas kernel.

    Args:
      Xb: (n, d) int32 binned features.
      node_key: (n,) int32 — node id relative to the level start in
        [0, nl), or any value >= nl for samples not at this level.
      Ych: (n, C) f32 per-sample channels.
      nl: nodes at this level (static).
      n_bins: B (static).

    Returns (d, nl, B, C) f32.
    """
    if interpret is None:
        interpret = pallas_interpret()
    return _level_histogram(Xb, node_key, Ych, nl=nl, n_bins=n_bins,
                            interpret=interpret, S=S, LB=LB)


@functools.partial(
    jax.jit, static_argnames=("nl", "n_bins", "interpret", "S", "LB")
)
def _level_histogram(Xb, node_key, Ych, *, nl, n_bins, interpret, S, LB):
    from jax.experimental import pallas as pl

    n, d = Xb.shape
    C = Ych.shape[1]
    B = n_bins
    L = nl * C
    n_pad = _ceil_to(max(n, S), S)
    L_pad = _ceil_to(max(L, LB), LB)

    XbT = Xb.T  # (d, n)
    if n_pad != n:
        XbT = jnp.pad(XbT, ((0, 0), (0, n_pad - n)))
        # padded samples: key >= nl matches no lane's node id
        node_key = jnp.pad(node_key, (0, n_pad - n),
                           constant_values=np.int32(nl))
        Ych = jnp.pad(Ych, ((0, n_pad - n), (0, 0)))
    # Mosaic tiles the LAST TWO dims of each block; a (1, S) block over
    # the (d, n) array would put a size-1 block on the d axis (neither
    # 8-divisible nor full). Lift d to a leading grid-only dim so the
    # last two block dims are (1==full, S).
    XbT = XbT.reshape(d, 1, n_pad)
    node_key = node_key.reshape(1, n_pad)

    def kernel(xb_ref, nk_ref, ych_ref, out_ref):
        si = pl.program_id(2)
        li = pl.program_id(1)

        # M (S, B): bin one-hot of this feature's sample chunk
        bins = xb_ref[0, 0, :]  # (S,) int32
        M = (
            bins[:, None] == lax.broadcasted_iota(jnp.int32, (S, B), 1)
        ).astype(jnp.float32)

        # NW (S, LB): lane l encodes (node j = l//C, channel c = l%C)
        lane = li * LB + lax.broadcasted_iota(jnp.int32, (1, LB), 1)
        node_of_lane = lane // C  # (1, LB)
        chan_of_lane = lane % C
        nodes = nk_ref[0, :]  # (S,)
        ych = ych_ref[:]  # (S, C)
        # spread channels along lanes with a constant (C, LB) one-hot
        # matmul — constant along the sample axis, so built once per
        # step, not per sample (C is tiny; static gather lowers poorly
        # on some backends)
        chan_oh = (
            lax.broadcasted_iota(jnp.int32, (C, LB), 0) == chan_of_lane
        ).astype(jnp.float32)
        ych_lane = lax.dot_general(
            ych, chan_oh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (S, LB)
        NW = jnp.where(nodes[:, None] == node_of_lane, ych_lane, 0.0)

        part = lax.dot_general(
            M, NW, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (B, LB)

        @pl.when(si == 0)
        def _():
            out_ref[0, :, :] = part

        @pl.when(si != 0)
        def _():
            out_ref[0, :, :] = out_ref[0, :, :] + part

    grid = (d, L_pad // LB, n_pad // S)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, S), lambda f, l, s: (f, 0, s)),
            pl.BlockSpec((1, S), lambda f, l, s: (0, s)),
            pl.BlockSpec((S, C), lambda f, l, s: (s, 0)),
        ],
        out_specs=pl.BlockSpec((1, B, LB), lambda f, l, s: (f, 0, l)),
        out_shape=jax.ShapeDtypeStruct((d, B, L_pad), jnp.float32),
        interpret=interpret,
    )(XbT, node_key, Ych)

    hist_bnc = out[:, :, :L].reshape(d, B, nl, C)
    return hist_bnc.transpose(0, 2, 1, 3)  # (d, nl, B, C)
