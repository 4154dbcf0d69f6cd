"""
Packed-CSR shared-data plane: sparse X as a first-class fit/predict
representation.

The flagship workloads are hashed-text grids (the reference's 20news
OvR/OvO examples, BASELINE config 3): a HashingVectorizer matrix at
2**18 columns and ~1% density. Densifying that input — the original
fit-path policy — inflates it ~100x in host RAM, replicates the dense
copy into every device's HBM, and pays O(n·d) solver FLOPs on zeros.
This module is the shared alternative, promoted from the predict-side
CSR path (``distribute/predict.py``'s former private ``_pack_csr_rows``)
and consumed by the fit plane, the batched search/multiclass paths, and
batch prediction alike:

- :class:`PackedX` — the device representation: ``idx (n, m) int32`` /
  ``val (n, m) float32``, one padded row per sample, ``m`` = max nnz
  per row. Padding entries are ``(0, 0.0)``: every kernel below treats
  them as "add 0.0 to column 0", so the representation is EXACT. It is
  a registered JAX pytree, which is what makes the rest of the stack
  indifferent to it: backend placement (``_resolve_placement``), the
  broadcast-reuse cache (keyed per host leaf), row-sharded
  ``shared_specs``, ``shape_sig``/AOT keys, and donation all operate on
  its two leaves like any other shared array.
- the two contractions every linear solver needs:
  :func:`packed_matvec` (``X @ W``: gather + row-dot, O(nnz·k)) and
  :func:`packed_rmatvec` (``X.T @ r``: scatter-add over the packed
  columns, O(nnz·k)) — plus :func:`packed_to_dense` (one device scatter
  rebuilds the dense block: the predict path's fallback and the tests'
  reference) and :func:`packed_weighted_gram` (``XᵀSX`` via the m²
  scatter, for the closed-form ridge family).
- :class:`BucketedX` — the representation of a matrix whose row
  lengths are SKEWED (a vectorised text corpus: most documents near a
  hundred distinct terms, a few in the thousands), where max-row
  padding would bill every row for a handful of heavy ones: rows
  bucketed by length into a few blocks of their own width, both
  orientations placed (so ``X @ W`` and ``X.T @ r`` are the same
  gather-and-row-sum, :func:`bucketed_matvec` / :func:`bucketed_rmatvec`,
  and no scatter runs), the densest columns held as a dense head. The
  buckets' cost follows nnz; the head's is ``n x h`` dense floats,
  zeros and all (at 11,314 x 130,107 with 1.79 M stored elements:
  15,229 columns, 689 MB, for the 78 % of the elements they hold),
  built on the device from its stored elements, never on the host. A
  batch of weight matrices (``vmap``) rides on the gathers' contiguous
  axis, not on an axis of its own.
- :class:`LinearOperator` — ``[X | 1]`` behind the five contractions
  the fit problems take, one implementation of each per representation
  (ndarray, :class:`PackedX`, :class:`BucketedX`), chosen by the type
  of X and nothing else.
- routing (:func:`pack_for_fit`): pack exactly when packing wins.
  The padded pair costs ``n·m·8`` bytes vs ``n·d·4`` dense, so the
  decision is byte-driven (``d >= 2·m·savings``; savings 4x, see
  :data:`PACK_MIN_SAVINGS`); rows of skewed length
  (:data:`OUTLIER_FACTOR`) pack bucketed — there is no densify
  fallback for skew, which at the widths where it arises cannot fit.
  ``SKDIST_SPARSE_FIT=0`` disables packing entirely; ``=1``/``force``
  packs any 2-D sparse input.

The 1-tuple-shape special case of scipy's 1-D sparse arrays
(``csr_array`` of a vector) is handled ONCE here, in
:func:`sparse_to_dense_f32` — 1-D sparse input is a column vector,
exactly as the dense path treats a 1-D ndarray.
"""

import functools
import json
import os

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "PackedX",
    "BucketedX",
    "is_packed",
    "is_sparse_2d",
    "max_nnz_per_row",
    "pack_csr_rows",
    "pack_csr_buckets",
    "pack_decision",
    "would_pack",
    "pack_for_fit",
    "sparse_to_dense_f32",
    "packed_matvec",
    "packed_rmatvec",
    "packed_to_dense",
    "packed_weighted_gram",
    "bucketed_matvec",
    "bucketed_rmatvec",
    "bucketed_to_dense",
    "matvec_any",
    "LinearOperator",
]

#: kill switch / force switch for the packed fit plane: "0" restores
#: the densify-everything policy, "1"/"force" packs any 2-D sparse
#: input regardless of the byte heuristic
SPARSE_FIT_ENV = "SKDIST_SPARSE_FIT"

#: how many times smaller (bytes) the packed pair must be than the
#: dense f32 matrix before the fit path packs — below this the MXU's
#: dense matmul beats gather/scatter indexing
PACK_MIN_SAVINGS = 4.0

#: nnz skew: when the max row nnz exceeds this multiple of the 95th
#: percentile AND padding to it would inflate the packed pair past the
#: same multiple of the true nnz, max-row padding would bill every row
#: for a handful of heavy ones — such a matrix packs BUCKETED
#: (:class:`BucketedX`: a dense head, and buckets whose cost follows nnz)
OUTLIER_FACTOR = 4.0

#: slots (row x packed column) one step of a bucketed contraction
#: gathers: the gathered block is ``TILE_SLOTS x columns`` floats, so
#: this bounds a contraction's temporaries whatever the matrix
TILE_SLOTS = 16384

#: merging neighbouring buckets may add this share of nnz in padding
BUCKET_MERGE_SHARE = 0.03

#: a column of a bucketed matrix at least this dense is held DENSE (the
#: head: a few thousand frequent terms hold most of a corpus's stored
#: elements): a dense column costs the MXU n multiply-adds a product
#: column, a packed one a gathered row per stored element — on a v5e
#: 2 to 10 ns each whatever the row's width (PERF.md) — which crosses
#: near one element in a few hundred
HEAD_MIN_DENSITY = 1.0 / 512
#: ... while the head stays under this many bytes and an eighth of the
#: columns
HEAD_MAX_BYTES = 1 << 30

#: a weight matrix's rows in a bucketed solve's flat vector are padded to
#: a multiple of this, the lanes of a TPU tile
#: (:class:`_BucketedOperator`)
ROW_ALIGN = 128

#: explicit row-chunk override for the weighted-gram contraction; the
#: automatic chunking derives from the meminfo budget (see
#: :func:`packed_weighted_gram`)
GRAM_CHUNK_ENV = "SKDIST_GRAM_CHUNK_ROWS"


# ---------------------------------------------------------------------------
# the packed representation
# ---------------------------------------------------------------------------

class PackedX:
    """Padded-row packed CSR: ``idx (n, m) int32``, ``val (n, m) f32``.

    A registered JAX pytree whose leaves are the two arrays and whose
    static treedef carries ``n_cols`` — so the logical width ``d`` is a
    compile-time constant wherever the pytree flows (kernels read it
    without tracing it), and two packings of different widths can never
    share a compiled program.
    """

    __slots__ = ("idx", "val", "n_cols")

    def __init__(self, idx, val, n_cols):
        self.idx = idx
        self.val = val
        self.n_cols = int(n_cols)

    @property
    def shape(self):
        """Logical (n, d) — what shape-generic callers read."""
        return (self.idx.shape[0], self.n_cols)

    def __len__(self):
        return int(self.idx.shape[0])

    @property
    def m(self):
        """Packed width: max nnz per row (plus padding)."""
        return int(self.idx.shape[1])

    @property
    def nbytes(self):
        return int(self.idx.nbytes) + int(self.val.nbytes)

    @property
    def dense_nbytes(self):
        """What the densified f32 matrix would cost."""
        return int(self.shape[0]) * int(self.n_cols) * 4

    def __repr__(self):  # pragma: no cover - debugging nicety
        n, d = self.shape
        return (f"PackedX(n={n}, d={d}, m={self.m}, "
                f"{self.nbytes >> 10} KiB packed vs "
                f"{self.dense_nbytes >> 10} KiB dense)")


jax.tree_util.register_pytree_node(
    PackedX,
    lambda x: ((x.idx, x.val), x.n_cols),
    lambda n_cols, leaves: PackedX(leaves[0], leaves[1], n_cols),
)


class BucketedX:
    """A dense head of the densest columns plus packed buckets whose
    cost follows nnz, for matrices with skewed row lengths (a few
    documents of thousands of terms among thousands of a hundred):
    rows sorted by nnz and cut into a few buckets, each a
    padded block ``idx (tiles, rows, m_b) int32`` / ``val f32`` of its
    own width ``m_b`` — a stack of tiles of at most :data:`TILE_SLOTS`
    slots, the unit a contraction gathers at a time — so a row pays
    padding only up to its bucket's width.

    Both orientations are placed: ``rows`` holds X bucketed by row
    length with ``inv (n,)`` — where each original row sits in the
    concatenation of the buckets — and ``cols`` holds ``X.T`` bucketed
    by column length with ``tinv (d,)``. ``X.T @ r`` is then the same
    gather-and-row-sum over ``cols`` that ``X @ W`` is over ``rows``:
    no scatter runs in either direction, and every product comes back
    in the matrix's own row (column) order, so nothing outside this
    module (fold masks, labels, weights) sees the permutation. Padding
    entries are ``(0, 0.0)`` and padding rows all padding: exact.

    The densest columns (:data:`HEAD_MIN_DENSITY`) are not in the
    buckets: ``head (n, h) f32`` holds them dense, ``head_cols (h,)``
    says which they are, and both products add a matmul over them to
    the gathers over the rest (``head`` is None where no column is
    that dense). The head is held whole, zeros and all — ``n x h x
    4`` bytes, capped by :data:`HEAD_MAX_BYTES` — so it, not nnz, is
    most of what such a matrix weighs on the device; it is a device
    array from the pack on (:func:`pack_csr_buckets` scatters its
    stored elements there), the other leaves host arrays until they
    are placed. The density at
    which a column joins it and that cap are one v5e's crossing, read
    at one shape (PERF.md, PR 28).

    A registered pytree: the leaves are the blocks, the permutations
    and the head; the static treedef carries ``n_cols``, ``nnz`` and
    the head's share of it.
    """

    __slots__ = ("rows", "inv", "cols", "tinv", "head", "head_cols",
                 "n_cols", "nnz", "head_nnz")

    def __init__(self, rows, inv, cols, tinv, head, head_cols, n_cols,
                 nnz, head_nnz=0):
        self.rows = tuple(tuple(b) for b in rows)
        self.inv = inv
        self.cols = tuple(tuple(b) for b in cols)
        self.tinv = tinv
        self.head, self.head_cols = head, head_cols
        self.n_cols = int(n_cols)
        self.nnz, self.head_nnz = int(nnz), int(head_nnz)

    @property
    def shape(self):
        return (self.inv.shape[0], self.n_cols)

    def __len__(self):
        return int(self.inv.shape[0])

    @property
    def slots(self):
        """Slots placed: the buckets' of both orientations, padding and
        all, and the head's every entry."""
        return sum(int(np.prod(i.shape)) for i, _ in self.rows + self.cols
                   ) + (0 if self.head is None
                        else int(np.prod(self.head.shape)))

    @property
    def placed(self):
        """Stored elements placed: the buckets hold each twice."""
        return 2 * (self.nnz - self.head_nnz) + self.head_nnz

    @property
    def nbytes(self):
        return sum(int(leaf.nbytes)
                   for leaf in jax.tree_util.tree_leaves(self))

    @property
    def dense_nbytes(self):
        return int(self.shape[0]) * int(self.n_cols) * 4

    def __repr__(self):  # pragma: no cover - debugging nicety
        n, d = self.shape
        return (f"BucketedX(n={n}, d={d}, nnz={self.nnz}, "
                f"row widths={[i.shape[2] for i, _ in self.rows]}, "
                f"head={None if self.head is None else self.head.shape}, "
                f"{self.nbytes >> 10} KiB)")


jax.tree_util.register_pytree_node(
    BucketedX,
    lambda x: ((x.rows, x.inv, x.cols, x.tinv, x.head, x.head_cols),
               (x.n_cols, x.nnz, x.head_nnz)),
    lambda aux, leaves: BucketedX(*leaves, *aux),
)


def _register_for_export():
    """The export tier of the compile cache serialises a program's
    argument treedefs; the static parts are small integers."""
    from jax import export

    for cls, name in ((PackedX, "skdist_tpu.PackedX"),
                      (BucketedX, "skdist_tpu.BucketedX")):
        export.register_pytree_node_serialization(
            cls, serialized_name=name,
            serialize_auxdata=lambda aux: json.dumps(aux).encode(),
            deserialize_auxdata=lambda b: (
                tuple(v) if isinstance(v := json.loads(b), list) else v),
        )


_register_for_export()


def is_packed(X):
    """Either packed representation of the sparse plane."""
    return isinstance(X, (PackedX, BucketedX))


# ---------------------------------------------------------------------------
# host-side packing + routing
# ---------------------------------------------------------------------------

def is_sparse_2d(X):
    """scipy-sparse duck test, 2-D only (1-D sparse arrays are column
    vectors for the dense path — see :func:`sparse_to_dense_f32`)."""
    return (hasattr(X, "toarray") and hasattr(X, "tocsr")
            and len(X.shape) == 2)


def max_nnz_per_row(X):
    """Packed width m from ``indptr`` alone — shared by the budget
    guardrails and the pack so they can never disagree about the
    padding rule (a changed rule here changes both)."""
    nnz = np.diff(np.asarray(X.indptr))
    return max(1, int(nnz.max()) if nnz.size else 1)


def pack_csr_rows(X):
    """CSR → ``(idx (n, m) int32, val (n, m) f32)``, m = max nnz per
    row, padded with ``(0, 0.0)``. Every consumer kernel treats padding
    as "add 0.0 to column 0", so the packed form is exact."""
    indptr = np.asarray(X.indptr)
    nnz = np.diff(indptr)
    m = max_nnz_per_row(X)
    n = X.shape[0]
    pos = indptr[:-1, None] + np.arange(m)[None, :]
    mask = np.arange(m)[None, :] < nnz[:, None]
    idx = np.zeros((n, m), np.int32)
    val = np.zeros((n, m), np.float32)
    idx[mask] = np.asarray(X.indices)[pos[mask]]
    val[mask] = np.asarray(X.data)[pos[mask]]
    return idx, val


def _tiling(count, m):
    """``(tiles, rows a tile)`` of a bucket of ``count`` rows of width
    ``m``: as few tiles as hold it at :data:`TILE_SLOTS` slots a tile,
    evenly filled (eight rows or more a tile a multiple of 8, the
    sublane tile)."""
    tiles = -(-count * int(m) // TILE_SLOTS)
    rows = -(-count // tiles)
    return tiles, rows if rows < 8 else -(-rows // 8) * 8


def bucket_widths(nnz):
    """``(widths, counts)`` of the buckets a vector of row lengths is
    cut into, from the lengths alone: a ladder of two widths an octave
    (1, 2, 4, 8, 12, 16, 24, 32, ...), every row in the narrowest bucket
    that holds it, then the cheapest neighbours merged upward while all
    merges together add under :data:`BUCKET_MERGE_SHARE` of nnz in
    padding — so a handful of very long rows share one block instead
    of compiling a loop each."""
    nnz = np.asarray(nnz)
    ladder = [1, 2, 4, 8]
    while ladder[-1] < (int(nnz.max()) if nnz.size else 0):
        ladder.append(ladder[-1] * 3 // 2 if ladder[-1] % 3 else
                      ladder[-1] * 4 // 3)
    which = np.searchsorted(ladder, nnz, side="left")
    counts = np.bincount(which, minlength=len(ladder))
    buckets = [[ladder[i], int(c)] for i, c in enumerate(counts) if c]
    room = BUCKET_MERGE_SHARE * max(1, int(nnz.sum()))
    while len(buckets) > 1:
        costs = [c * (buckets[i + 1][0] - w)
                 for i, (w, c) in enumerate(buckets[:-1])]
        i = int(np.argmin(costs))
        if costs[i] > room:
            break
        room -= costs[i]
        buckets[i + 1][1] += buckets[i][1]
        del buckets[i]
    return [w for w, _ in buckets], [c for _, c in buckets]


def _pack_buckets(X):
    """One orientation of :func:`pack_csr_buckets`: ``(blocks, inv)``
    of a CSR matrix — its rows stably sorted by length, cut at
    :func:`bucket_widths`, each bucket a stack of tiles
    ``(tiles, rows, m)`` (:func:`_tiling`) ending in all-padding rows."""
    indptr = np.asarray(X.indptr)
    indices, data = np.asarray(X.indices), np.asarray(X.data)
    nnz = np.diff(indptr)
    order = np.argsort(nnz, kind="stable")
    widths, counts = bucket_widths(nnz)
    inv = np.empty(len(nnz), np.int32)
    blocks, lo, off = [], 0, 0
    for m, count in zip(widths, counts):
        rows = order[lo:lo + count]
        tiles, tile = _tiling(count, m)
        n_pad = tiles * tile
        idx = np.zeros((n_pad, m), np.int32)
        val = np.zeros((n_pad, m), np.float32)
        mask = np.arange(m)[None, :] < nnz[rows][:, None]
        pos = (indptr[rows][:, None] + np.arange(m)[None, :])[mask]
        idx[:count][mask] = indices[pos]
        val[:count][mask] = data[pos]
        inv[rows] = off + np.arange(count, dtype=np.int32)
        blocks.append((idx.reshape(tiles, tile, m),
                       val.reshape(tiles, tile, m)))
        lo, off = lo + count, off + n_pad
    return tuple(blocks), inv


def head_columns(X):
    """The columns of a CSR matrix dense enough to hold dense
    (:data:`HEAD_MIN_DENSITY`), densest first, as many as
    :data:`HEAD_MAX_BYTES` and an eighth of the columns allow; sorted."""
    n, d = X.shape
    counts = np.bincount(np.asarray(X.indices), minlength=d)
    dense = np.flatnonzero(counts >= max(2.0, n * HEAD_MIN_DENSITY))
    room = int(min(HEAD_MAX_BYTES // max(1, 4 * n), d // 8))
    dense = dense[np.argsort(-counts[dense], kind="stable")[:room]]
    return np.sort(dense).astype(np.int32)


def head_sent_slots(head_nnz):
    """Elements :func:`pack_csr_buckets` sends to the device to build a
    head of ``head_nnz`` stored elements: the next power of two (1024 at
    least), padding that the build drops, so that a matrix of another
    nnz but the same head shape rarely compiles another build."""
    return max(1024, 1 << (int(head_nnz) - 1).bit_length())


@functools.partial(jax.jit, static_argnums=(2, 3))
def _build_head(flat, val, n, h):
    """The dense ``(n, h)`` head from its stored elements: ``flat``
    (``row * h + column``, ascending and distinct, the padding past
    ``n * h``) and their float32 values, scattered onto zeros."""
    return jnp.zeros((n, h), jnp.float32).at[flat // h, flat % h].add(
        val, mode="drop", indices_are_sorted=True, unique_indices=True)


def _dense_head(X, head_cols, local):
    """``X[:, head_cols].toarray()`` as float32, built on JAX's default
    device from the head's stored elements: no ``(n, h)`` array is made
    on the host, and what crosses is ``8 * head_sent_slots(head_nnz)``
    bytes, not ``4 * n * h``. ``local`` is each stored element's column
    in the head, -1 outside it. A canonical CSR's head elements, in
    CSR order, are already ascending and distinct (``head_cols`` is
    sorted); any other has its duplicates summed here first, in its own
    dtype and CSR order, as ``toarray`` sums them — so the head is
    ``toarray``'s to the bit either way."""
    n, h = X.shape[0], head_cols.size
    indptr = np.asarray(X.indptr)
    in_head = local >= 0
    rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(indptr))
    flat = rows[in_head] * np.int32(h) + local[in_head]
    val = np.asarray(X.data)[in_head]
    slots = head_sent_slots(flat.size)
    if not X.has_canonical_format:
        flat, at = np.unique(flat, return_inverse=True)
        summed = np.zeros(flat.size, val.dtype)
        np.add.at(summed, at, val)
        val = summed
    assert n * h + slots < 2 ** 31, "the head's flat index is int32"
    pad = slots - flat.size
    flat = np.concatenate([flat, n * h + np.arange(pad, dtype=np.int32)])
    val = np.concatenate([val.astype(np.float32), np.zeros(pad, np.float32)])
    return _build_head(flat, val, n, h)


def pack_csr_buckets(X):
    """CSR → :class:`BucketedX`: the densest columns dense, the rest in
    both orientations bucketed by length (``indptr`` and the column
    counts alone decide). The head is built on JAX's default device
    (:func:`_dense_head`); the buckets are host arrays, placed later."""
    import scipy.sparse as sp

    X = X.tocsr()
    head_cols = head_columns(X)
    head, head_nnz, tail = None, 0, X
    if head_cols.size:
        lookup = np.full(X.shape[1], -1, np.int32)
        lookup[head_cols] = np.arange(head_cols.size, dtype=np.int32)
        local = lookup[np.asarray(X.indices)]
        keep = local < 0
        head_nnz = int(X.nnz - keep.sum())
        head = _dense_head(X, head_cols, local)
        kept = np.concatenate([[0], np.cumsum(keep)])[np.asarray(X.indptr)]
        tail = sp.csr_matrix(
            (np.asarray(X.data)[keep], np.asarray(X.indices)[keep], kept),
            shape=X.shape)
    else:
        head_cols = None
    rows, inv = _pack_buckets(tail)
    cols, tinv = _pack_buckets(tail.T.tocsr())
    return BucketedX(rows, inv, cols, tinv, head, head_cols, X.shape[1],
                     X.nnz, head_nnz)


def pack_decision(X):
    """Routing decision for a 2-D CSR input: ``(pack, reason, m)``.

    ``pack`` is True when the packed pair beats the dense matrix by at
    least :data:`PACK_MIN_SAVINGS` in device bytes (``n·m·8`` vs
    ``n·d·4``, with ``m`` the width rows are padded to). Rows of skewed
    length (:data:`OUTLIER_FACTOR`) pack bucketed, so their ``m`` is
    the mean slots a row takes over its buckets, not the longest row —
    reason ``"bucketed"``; there is no densify fallback for skew (at
    the widths where it arises the dense matrix does not fit). All
    statistics come from ``indptr`` alone — no data is touched before
    the decision, so declining costs nothing.
    """
    env = os.environ.get(SPARSE_FIT_ENV, "").strip().lower()
    if env in ("0", "false", "no", "off"):
        return False, "disabled via " + SPARSE_FIT_ENV, None
    nnz = np.diff(np.asarray(X.indptr))
    m = max(1, int(nnz.max()) if nnz.size else 1)
    n, d = X.shape
    reason = "packed"
    if n:
        p95 = float(np.percentile(nnz, 95))
        total = max(1, int(nnz.sum()))
        if (m > OUTLIER_FACTOR * max(p95, 1.0)
                and n * m > OUTLIER_FACTOR * total):
            widths, counts = bucket_widths(nnz)
            m = max(1, -(-int(np.dot(widths, counts)) // n))
            reason = "bucketed"
    if env in ("1", "true", "force", "on"):
        return True, "forced via " + SPARSE_FIT_ENV, m
    if n == 0:
        return False, "empty input", m
    if m * 8 * PACK_MIN_SAVINGS > d * 4:
        return False, (
            f"dense-competitive density (m={m} of d={d}: the packed "
            f"pair saves < {PACK_MIN_SAVINGS}x device bytes)"
        ), m
    return True, reason, m


def would_pack(X):
    """Whether :func:`pack_for_fit` would return a packed
    representation for ``X`` — the same routing decision (sparsity,
    byte heuristic, pack-budget check), decided from shape and
    ``indptr`` alone without building anything. Callers that only need
    the routing outcome (e.g. to order a host-path bail before paying
    a dense conversion) use this instead of packing and discarding."""
    if not is_sparse_2d(X):
        return False
    X = X.tocsr()
    pack, _reason, m = pack_decision(X)
    if not pack:
        return False
    from .utils.meminfo import densify_budget_bytes

    budget, _ = densify_budget_bytes()
    n, _d = X.shape
    if budget is not None and n * max(1, m) * 8 * 3 > budget:
        # the pack itself is budget-checked (the pair plus its build
        # intermediates must fit host RAM — if they don't, dense
        # certainly doesn't either, and the densify guardrail owns the
        # error message)
        return False
    return True


def pack_for_fit(X):
    """:class:`PackedX` — or :class:`BucketedX` where the row lengths
    are skewed — when the fit plane should consume ``X`` packed, else
    None (callers densify). Non-sparse and 1-D sparse inputs always
    return None; the routing decision lives in :func:`would_pack`."""
    if not would_pack(X):
        return None
    from .obs import trace as obs_trace

    # host seconds to bucket and pack (the head's build on the device
    # is enqueued, not waited for); the counts are filled in at the
    # span's end
    args = {} if obs_trace.enabled() else None
    with obs_trace.span("pack_x", args):
        X = X.tocsr()
        if pack_decision(X)[1] == "bucketed":
            packed = pack_csr_buckets(X)
            slots, buckets = packed.slots, len(packed.rows + packed.cols)
        else:
            packed = PackedX(*pack_csr_rows(X), X.shape[1])
            slots, buckets = int(np.prod(packed.idx.shape)), 1
        if args is not None:
            head = getattr(packed, "head", None)
            args.update(nnz=int(X.nnz), slots=slots, buckets=buckets,
                        head_on_device=isinstance(head, jax.Array),
                        head_sent_bytes=0)
            if head is not None:
                args.update(head_cols=int(head.shape[1]),
                            head_sent_bytes=8 * head_sent_slots(
                                packed.head_nnz))
    return packed


def sparse_to_dense_f32(X):
    """Densify a scipy-sparse input to float32, with the budget
    guardrail. The sparse leg of ``models.linear.as_dense_f32``; the
    1-tuple-shape special case of scipy's 1-D sparse arrays is handled
    here (column vector), once, for every caller."""
    if len(X.shape) == 1:
        # csr_array of a vector: 1-tuple shape; a 1-D input is a
        # single feature column, exactly like a 1-D ndarray
        out = np.asarray(X.toarray(), dtype=np.float32)
        return np.ascontiguousarray(out.reshape(-1, 1))
    _check_densify_budget(X.shape[0], X.shape[1])
    if hasattr(X, "tocsr") and X.shape[0] * X.shape[1] >= (1 << 22):
        from .native import csr_to_dense_f32

        return csr_to_dense_f32(X)
    out = np.asarray(X.toarray())
    if out.ndim == 1:
        out = out.reshape(-1, 1)
    return np.ascontiguousarray(out, dtype=np.float32)


def _check_densify_budget(n_rows, n_cols):
    """Refuse a densification that cannot fit, with remedies."""
    from .utils.meminfo import BUDGET_ENV, densify_budget_bytes

    est = int(n_rows) * int(n_cols) * 4
    budget, source = densify_budget_bytes()
    if budget is None or est <= budget:
        return

    def _fmt(b):
        return (f"{b / 1e9:.2f} GB" if b >= 1e8 else f"{b / 1e6:.1f} MB")

    raise ValueError(
        f"densifying this ({n_rows}, {n_cols}) sparse input needs "
        f"~{_fmt(est)} as float32, but only ~{_fmt(budget)} "
        f"is available ({source}). Hashed-text widths this large do not "
        "belong on the dense path. Options: (1) FIT without densifying "
        "— the packed-CSR sparse fit plane (skdist_tpu.sparse) handles "
        "2-D sparse input at packable density automatically for the "
        "linear families; reaching this error means the input was "
        "routed dense (density/nnz-outlier heuristics, or "
        f"{SPARSE_FIT_ENV}=0) — force packing with {SPARSE_FIT_ENV}=1; "
        "(2) for inference use distribute.batch_predict, which streams "
        "sparse rows in groups (device models take the packed CSR "
        "path) and never materialises the full dense matrix; (3) "
        "re-hash to a bounded width — the Encoderizer configs cap "
        "HashingVectorizer at 2**12..2**14 (distribute/_defaults.py) — "
        "or reduce features first (TruncatedSVDTransformer); (4) raise "
        f"the limit explicitly via {BUDGET_ENV} if you know better."
    )


# ---------------------------------------------------------------------------
# device kernels: the two contractions + the dense rebuild
# ---------------------------------------------------------------------------

def packed_matvec(idx, val, W):
    """``X @ W`` on the packed pair: gather + row-dot, O(nnz·k) FLOPs.

    ``W`` is ``(d[+1],)`` or ``(d[+1], k)``; padding entries gather row
    0 of W with weight 0.0 and contribute nothing. vmap-safe (the task
    axis may batch W)."""
    g = W[idx]  # (n, m) or (n, m, k)
    if g.ndim == 2:
        return jnp.sum(val * g, axis=1)
    return jnp.einsum("nm,nmk->nk", val, g)


def packed_rmatvec(idx, val, r, n_cols):
    """``X.T @ r`` on the packed pair: scatter-add over the packed
    columns, O(nnz·k). ``r`` is ``(n,)`` or ``(n, k)``; returns
    ``(n_cols,)`` / ``(n_cols, k)``. Padding scatters 0.0 into row 0."""
    if r.ndim == 1:
        out = jnp.zeros((n_cols,), r.dtype)
        return out.at[idx].add(val * r[:, None])
    out = jnp.zeros((n_cols, r.shape[-1]), r.dtype)
    return out.at[idx].add(val[:, :, None] * r[:, None, :])


def packed_to_dense(idx, val, n_cols):
    """Scatter-rebuild the dense ``(n, n_cols)`` block on device (H2D
    ships only the packed pair). Duplicate (row, col) entries
    accumulate, matching CSR semantics."""
    n = idx.shape[0]
    rows = jnp.arange(n)[:, None]
    return jnp.zeros((n, n_cols), val.dtype).at[rows, idx].add(val)


#: task-batch factor billed by the automatic gram chunking: the gram
#: usually runs inside a vmapped round (batched CV ridge fits), where
#: EVERY lane of the traced program materialises its own (chunk, m, m)
#: tensor simultaneously — and at trace time the kernel cannot see how
#: many lanes the round stacked. Billing a conservative per-trace lane
#: count keeps the guard effective in the batched case; over-chunking
#: only lengthens the fori_loop, under-chunking OOMs.
GRAM_BATCH_ASSUMPTION = 16


def _gram_row_chunk(n, m):
    """Rows per chunk for :func:`packed_weighted_gram`, or None for the
    single-shot scatter. Env override first (absolute — the operator
    knows the real round shape); otherwise the (n, m, m) contribution
    tensor × :data:`GRAM_BATCH_ASSUMPTION` vmap lanes is billed against
    the meminfo budget (the same plumbing the densify guardrail uses)
    at 1/8 — the tensor, its XLA temps, and the scatter's operands
    coexist — and chunking engages only when that bill overshoots the
    share. The budget is host-RAM-derived (the plumbing the ISSUE
    reuses); device-HBM-aware sizing stays the backend round sizer's
    job."""
    env = os.environ.get(GRAM_CHUNK_ENV, "").strip()
    if env:
        try:
            v = int(float(env))
            if v > 0:
                return min(v, n)
        except ValueError:
            pass
    from .utils.meminfo import densify_budget_bytes

    budget, _ = densify_budget_bytes()
    if budget is None:
        return None
    lane_bytes = int(m) * int(m) * 4 * GRAM_BATCH_ASSUMPTION
    share = budget // 8
    if int(n) * lane_bytes <= share:
        return None
    return max(1, int(share // max(lane_bytes, 1)))


def packed_weighted_gram(idx, val, sw, n_cols, row_chunk=None):
    """``Xᵀ S X`` via the m² scatter: contribution
    ``sw[n]·val[n,a]·val[n,b]`` lands at ``(idx[n,a], idx[n,b])`` —
    O(nnz·m) scatter ops instead of the dense gram's O(n·d²) FLOPs.

    The (n, m, m) contribution tensor is materialised, which suits the
    moderate-m regimes the ridge family usually runs at — but above a
    budget threshold (:func:`_gram_row_chunk`, reusing the meminfo
    budget plumbing; ``SKDIST_GRAM_CHUNK_ROWS`` overrides) the
    contraction switches to a row-chunked accumulation: a fori_loop
    over fixed-size row chunks, each materialising only
    (chunk, m, m). Chunk padding uses zero weights/values, so the
    chunked result equals the single-shot scatter (exactly on integer
    data; to f32 addition-order noise otherwise). The chunk decision
    is made at TRACE time from static shapes, so it is vmap-safe (a
    batched ``sw`` rides through the dynamic slices untouched)."""
    n, m = idx.shape
    if row_chunk is None:
        row_chunk = _gram_row_chunk(n, m)
    if row_chunk is None or int(row_chunk) >= n:
        vw = val * sw[:, None]
        contrib = vw[:, :, None] * val[:, None, :]
        out = jnp.zeros((n_cols, n_cols), val.dtype)
        return out.at[idx[:, :, None], idx[:, None, :]].add(contrib)
    chunk = max(1, int(row_chunk))
    n_pad = -(-n // chunk) * chunk
    if n_pad != n:
        # zero-weight padded rows contribute 0.0 at (0, 0) — exact
        idx = jnp.concatenate(
            [idx, jnp.zeros((n_pad - n, m), idx.dtype)], axis=0
        )
        val = jnp.concatenate(
            [val, jnp.zeros((n_pad - n, m), val.dtype)], axis=0
        )
        sw = jnp.concatenate(
            [sw, jnp.zeros((n_pad - n,), sw.dtype)], axis=0
        )

    def body(c, acc):
        i0 = c * chunk
        ii = jax.lax.dynamic_slice_in_dim(idx, i0, chunk, axis=0)
        vv = jax.lax.dynamic_slice_in_dim(val, i0, chunk, axis=0)
        ss = jax.lax.dynamic_slice_in_dim(sw, i0, chunk, axis=0)
        vw = vv * ss[:, None]
        contrib = vw[:, :, None] * vv[:, None, :]
        return acc.at[ii[:, :, None], ii[:, None, :]].add(contrib)

    out0 = jnp.zeros((n_cols, n_cols), val.dtype)
    return jax.lax.fori_loop(0, n_pad // chunk, body, out0)


# ---------------------------------------------------------------------------
# the bucketed contractions
# ---------------------------------------------------------------------------

def _gather_rowsum(idx, val, W, bf16):
    """One bucket's ``X_b @ W`` for ``W (p, c)``, a tile a step: gather
    the tile's ``W`` rows, scale, sum each row's slots. The gathered
    block is at most ``TILE_SLOTS x c`` floats whatever the bucket, and
    ``c`` is the gather's contiguous axis — which is why a batch of
    weight matrices rides on it (:func:`_spmm`) instead of on an axis
    of its own."""
    if bf16:
        W, val = W.astype(jnp.bfloat16), val.astype(jnp.bfloat16)

    def step(_, iv):
        i, v = iv
        g = v[:, :, None] * W[i]
        return None, jnp.sum(g.astype(jnp.float32), axis=1)

    return jax.lax.scan(step, None, (idx, val))[1].reshape(
        -1, W.shape[1])


def _head_product(head, operand, bf16, transposed):
    """The dense head's share of a product: ``head @ operand`` or
    ``head.T @ operand``, float32 at ``highest`` — or the bf16
    contract's one pass with float32 accumulation."""
    if bf16:
        head, operand = (a.astype(jnp.bfloat16) for a in (head, operand))
    return jax.lax.dot_general(
        head, operand, (((0 if transposed else 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.DEFAULT if bf16
                   else jax.lax.Precision.HIGHEST))


@functools.lru_cache(maxsize=None)
def _spmm(bf16, transposed, ones, d, width):
    """``(side, At (c, r)) -> (c, r')``: the product of one orientation
    of a :class:`BucketedX` of ``d`` columns with a matrix, operand and
    result both CLASSES-MAJOR (``(M @ At.T).T``) — ``side`` is the
    orientation's ``(blocks, inv, head, head_cols)`` — bucket by
    bucket, back in the matrix's own row order, plus the dense head's
    matmul. The weights' side of either product has rows of ``width``
    entries: the ``d`` coefficients, the bias where ``ones`` (the
    design matrix's column of ones), zeros after (the aligned rows of
    :class:`_BucketedOperator`). Forward, ``At (c, width) -> (c, n)``:
    the bias at ``d`` is added to every output, the zeros are never
    gathered. ``transposed``, ``At (c, n) -> (c, width)``: entry ``d``
    holds ``At``'s row sums and the rest comes back zero.

    The gathers take rows of ``At.T``, the classes on the contiguous
    axis: ONE two-dimensional transpose on a product's way in and one
    on its way out, which XLA runs as its native tiled copy. Under
    ``vmap`` the lanes join the classes — ``(L, c, r) -> (L·c, r)``, a
    reshape that moves nothing — and the same contraction runs once
    for all lanes: every gathered row is ``L·c`` contiguous floats,
    where the batching rule of a plain gather would leave rows of ``c``
    (20 classes pad to the 128 lanes of a TPU tile, six times the
    bytes, in the operand and in every gathered block). The lanes lay
    on the OTHER side of the rows once, ``(L, p, c) -> (p, L·c)``: no
    axis stayed minor, and XLA moved a round's weights lane by lane
    through ``(L, p, c)`` buffers padded to 128, a third of the text
    cell's device time (PERF.md, PR 34)."""
    def product(side, At):
        blocks, inv, head, head_cols = side
        with jax.named_scope("sparse/spmm"):
            W = At.T
            out = jnp.concatenate(
                [_gather_rowsum(idx, val, W, bf16) for idx, val in blocks])[inv]
            if head is not None and not transposed:
                out = out + _head_product(head, W[head_cols], bf16, False)
            if head is not None and transposed:
                out = out.at[head_cols].add(
                    _head_product(head, W, bf16, True))
            if not transposed:
                if ones:
                    out = out + W[d]
                # the head's matmul stays as written, ``head`` streaming
                # against the weights: fused with the transpose below
                # XLA turns it round (``Wᵀ @ headᵀ``, the 0.7 GB head
                # the stationary operand for a round's 140 columns) and
                # it takes 3.1 ms where this takes 1.9 (PERF.md, PR 34)
                return jax.lax.optimization_barrier(out).T
            tail = [jnp.sum(W, axis=0)[None]] if ones else []
            if width > d + len(tail):
                tail.append(jnp.zeros(
                    (width - d - len(tail), W.shape[1]), out.dtype))
            return jnp.concatenate([out] + tail).T

    spmm = jax.custom_batching.custom_vmap(product)

    @spmm.def_vmap
    def _(axis_size, in_batched, side, At):
        if any(jax.tree_util.tree_leaves(in_batched[0])):
            # a batch of matrices: no caller makes one; plain batching
            axes = jax.tree_util.tree_map(
                lambda b: 0 if b else None, tuple(in_batched))
            return jax.vmap(product, axes)(side, At), True
        c, r = At.shape[1:]
        wide = spmm(side, At.reshape(axis_size * c, r))
        return wide.reshape(axis_size, c, -1), True

    return spmm


def _bucketed_product(X, transposed, bf16, intercept, width=None):
    """``[X | 1] @ W`` — or, ``transposed``, ``[X | 1].T @ r`` — for a
    :class:`BucketedX`, classes-major on both sides (:func:`_spmm`):
    ``At (c, width) -> (c, n)``, ``(c, n) -> (c, width)``. ``width`` is
    the weights' own ``d [+ 1]`` unless their rows are padded."""
    ones = bool(intercept)
    spmm = _spmm(bf16, transposed, ones, X.n_cols,
                 X.n_cols + ones if width is None else width)
    side = ((X.cols, X.tinv) if transposed else (X.rows, X.inv)) + (
        X.head, X.head_cols)
    scope = "sparse/rmatvec" if transposed else "sparse/matvec"

    def product(At):
        with jax.named_scope(scope):
            return spmm(side, At)

    return product


def _classes_major(fn, W):
    """``fn``, which takes and gives matrices classes-major, on ``W`` a
    vector or a matrix with the classes on its columns."""
    W = jnp.asarray(W)
    return fn(W[None])[0] if W.ndim == 1 else fn(W.T).T


def bucketed_matvec(X, W, bf16=False, intercept=False):
    """``X @ W`` on a :class:`BucketedX`; ``W`` is ``(d,)`` or
    ``(d, k)`` — with ``intercept``, ``[X | 1] @ W`` for ``W`` of
    ``d + 1`` rows. No autodiff rule of its own beyond the gather's:
    the fit problems take :func:`bucketed_matvec_with_vjp`."""
    return _classes_major(_bucketed_product(X, False, bf16, intercept), W)


def bucketed_rmatvec(X, r, bf16=False, intercept=False):
    """``X.T @ r`` (``[X | 1].T @ r`` with ``intercept``) on a
    :class:`BucketedX` — the same gather-and-row-sum over the
    transposed orientation; ``r`` is ``(n,)`` or ``(n, k)``."""
    return _classes_major(_bucketed_product(X, True, bf16, intercept), r)


def _with_transpose_as_vjp(forward, backward):
    """``forward`` with ``backward``, its true transpose, as its
    backward pass: the solvers differentiate the loss through the
    forward product, and the gather's own transpose would be a
    scatter-add."""
    @jax.custom_vjp
    def mv(W):
        return forward(W)

    mv.defvjp(lambda W: (mv(W), None), lambda _, g: (backward(g),))
    return mv


def bucketed_matvec_with_vjp(X, bf16=False, intercept=False):
    """``W -> X @ W`` (``W`` a vector or ``(p, k)``) for a fixed
    :class:`BucketedX`, whose backward pass IS
    :func:`bucketed_rmatvec`."""
    return _with_transpose_as_vjp(
        lambda W: bucketed_matvec(X, W, bf16, intercept),
        lambda g: bucketed_rmatvec(X, g, bf16, intercept))


def _bucket_rows(X, i):
    """The packed rows ``i`` (original row numbers) of a
    :class:`BucketedX`'s buckets, one ``(idx, val)`` pair a bucket, a
    row all padding in every bucket but its own — the mini-batch forms'
    view: their cost follows the sum of the bucket widths, not nnz."""
    pos, off, out = X.inv[i], 0, []
    for idx, val in X.rows:
        idx, val = (a.reshape(-1, a.shape[2]) for a in (idx, val))
        n_b = idx.shape[0]
        here = (pos >= off) & (pos < off + n_b)
        at = jnp.clip(pos - off, 0, n_b - 1)
        out.append((idx[at], jnp.where(here[:, None], val[at], 0.0)))
        off += n_b
    return out


def bucketed_to_dense(X, fit_intercept=False):
    """Scatter-rebuild the dense ``(n, d[+1])`` matrix of a
    :class:`BucketedX` on device (the closed-form ridge family's gram
    and the predict path: feasible only where d is small)."""
    p = X.n_cols + int(bool(fit_intercept))
    dense = jnp.concatenate([
        packed_to_dense(idx.reshape(-1, idx.shape[2]),
                        val.reshape(-1, idx.shape[2]), p)
        for idx, val in X.rows])[X.inv]
    if X.head is not None:
        dense = dense.at[:, X.head_cols].add(X.head)
    return dense.at[:, X.n_cols].set(1.0) if fit_intercept else dense


def matvec_any(X, W):
    """``X @ W`` for any representation — the decision/proba
    kernels' one entry point, so a model fit packed scores packed
    shared data AND dense predict blocks through one closure."""
    if isinstance(X, PackedX):
        return packed_matvec(X.idx, X.val, W)
    if isinstance(X, BucketedX):
        return bucketed_matvec(X, W)
    return X @ W


# ---------------------------------------------------------------------------
# the matvec interface the fit problems consume
# ---------------------------------------------------------------------------

class LinearOperator:
    """The augmented design matrix ``X̃ = [X | 1]`` behind one matvec
    interface — what lets the LogReg/LinearSVC/SGD/Ridge fit problems
    (and through them the iteration-sliced solvers and the
    convergence-compacted scheduler) run unchanged on sparse data.
    ``LinearOperator(X, ...)`` is the implementation of X's
    representation, and the type of X alone picks it: an ndarray's
    (:class:`_DenseOperator`), a :class:`PackedX`'s
    (:class:`_PackedOperator`) or a :class:`BucketedX`'s
    (:class:`_BucketedOperator`). Each holds the five contractions:
    ``matvec`` (``X̃ @ W``; ``logits`` is the same product of a weight
    matrix in the representation's own layout), ``rmatvec``
    (``X̃ᵀ @ r``), the SGD
    mini-batch forms ``row_matvec`` / ``row_rmatvec`` over rows ``i``,
    and ``weighted_gram_rhs`` (``(X̃ᵀSX̃, (SX̃)ᵀT)``, the two sides of
    the ridge normal equations).

    The representation also owns how a weight MATRIX ``(p, k)`` lies in
    the flat vector a solve carries: :meth:`flat_size` entries,
    :meth:`weights` the view of them :meth:`logits` takes (and
    :meth:`coef_sq_sum` penalises), :meth:`matrix` and :meth:`flat` the
    way to the true ``(p, k)`` and back — once a fit each, for the
    fitted parameters and a warm start. Here, and for a dense or
    padded-pair X, that is ``W.reshape(-1)``; a :class:`BucketedX`'s
    lies classes-major in aligned rows (:class:`_BucketedOperator` says
    why). A weight VECTOR has no layout.

    What the L-BFGS family makes of the products has moved since its
    line search runs along a ray (``models/linear._ray_loss``): a trial
    step's logits are the sum of two of them, ``X̃ @ w + t · X̃ @ d``,
    and not one product of ``w + t·d``, so trial values round
    differently than they did.

    ``matmul_dtype='bfloat16'`` applies the LogReg bf16 contract: bf16
    operands, f32 accumulation, solver state f32. On the packed
    representations the products round to bf16 before the f32 row-sum
    (inside the bucketed products' fused scan the compiler may keep
    the float32 product) — same opt-in-screening precision class as
    the dense bf16 pass.
    """

    __slots__ = ("d", "p", "n", "dtype", "bf16")

    #: the axis of :meth:`logits`'s result that carries the classes
    class_axis = 1

    def __new__(cls, X, fit_intercept, matmul_dtype=None):
        if cls is LinearOperator:
            cls = (_BucketedOperator if isinstance(X, BucketedX)
                   else _PackedOperator if isinstance(X, PackedX)
                   else _DenseOperator)
        return object.__new__(cls)

    def __init__(self, X, fit_intercept, matmul_dtype=None):
        self.bf16 = matmul_dtype == "bfloat16"
        self.n, self.d = X.shape
        self.p = self.d + int(bool(fit_intercept))

    def logits(self, W):
        """``X̃ @ W`` for a weight MATRIX as :meth:`weights` views it,
        in the layout this representation's product comes out in, the
        classes on :attr:`class_axis` — what the multinomial loss
        reduces over."""
        return self.matvec(W)

    def flat_size(self, k):
        """Entries of the flat vector of a ``(p, k)`` weight matrix."""
        return self.p * k

    def weights(self, wflat, k):
        """The flat vector as :meth:`logits` takes it: ``(p, k)``."""
        return wflat.reshape(self.p, k)

    def coef_sq_sum(self, W):
        """``Σ coef²`` of :meth:`weights`' view, what an L2 penalty
        weighs: the intercepts are left out."""
        return jnp.sum(W[:self.d] * W[:self.d])

    def matrix(self, wflat, k):
        """The flat vector as the true ``(p, k)`` weight matrix."""
        return wflat.reshape(self.p, k)

    def flat(self, W):
        """``(p, k) -> (flat_size(k),)``: :meth:`matrix`'s inverse."""
        return W.reshape(-1)


class _DenseOperator(LinearOperator):
    """The plain dense products, the intercept BESIDE them (``X @ W[:d]
    + W[d]``, ``[Xᵀr ; Σr]``, the gram in blocks): no copy of X with a
    ones column exists, which at every program call cost a second X.
    A weight VECTOR's product is written ``w @ X.T`` and a weight
    MATRIX's logits (:meth:`logits`) ``Wᵀ @ X.T``: the same
    contractions lanes- and classes-first, rows minor."""

    __slots__ = ("X", "_Xmm", "_icpt")

    #: :meth:`logits` carries the classes first, ``(k, n)``
    class_axis = 0

    def __init__(self, X, fit_intercept, matmul_dtype=None):
        super().__init__(X, fit_intercept, matmul_dtype)
        self.dtype = X.dtype
        self.X, self._Xmm, self._icpt = X, None, bool(fit_intercept)

    def _split(self, W):
        """``(W[:d], W[d])`` — the intercept rounded as the bf16
        contract rounds every operand of its pass."""
        if not self._icpt:
            return W, None
        b = W[self.d]
        if self.bf16:
            b = b.astype(jnp.bfloat16).astype(jnp.float32)
        return W[:self.d], b

    def _bf16_dot(self, lhs, rhs, contract):
        """The bf16 pass: bf16 operands, f32 accumulation; precision
        pinned so the library-wide 'highest' tracing default doesn't
        promote it."""
        return jax.lax.dot_general(
            lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16),
            (contract, ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        )

    def _X16(self):
        if self._Xmm is None:
            self._Xmm = self.X.astype(jnp.bfloat16)
        return self._Xmm

    def matvec(self, W):
        with jax.named_scope("dense/matvec"):
            A, b = self._split(W)
            if self.bf16:
                z = self._bf16_dot(self._X16(), A, ((1,), (0,)))
            elif W.ndim == 1:
                # the same contraction, written lanes-first: under
                # ``vmap`` over a round's lanes the logits come out
                # ``(lanes, n)`` — ``X @ w`` batches to ``(n, lanes)``,
                # which XLA holds lanes-minor wherever a ``while``
                # carries it (the line search's trial steps), every row
                # of 50 lanes padded to a 128-wide tile
                z = A @ self.X.T
            else:
                z = self.X @ A
            return z if b is None else z + b

    def logits(self, W):
        # ``(k, n)``, under ``vmap`` ``(lanes, k, n)``: ``X @ W`` batches
        # to ``(n, lanes, k)``, ten classes padded to a 128-wide tile
        # wherever a ``while`` carries it
        with jax.named_scope("dense/matvec"):
            A, b = self._split(W)
            if self.bf16:
                z = self._bf16_dot(A, self._X16(), ((0,), (1,)))
            else:
                z = A.T @ self.X.T
            return z if b is None else z + b[:, None]

    def _with_sum(self, G, r):
        """``[G ; Σ_rows r]``: the ones column's row of a transposed
        product."""
        if not self._icpt:
            return G
        return jnp.concatenate([G, jnp.sum(r, axis=0)[None]])

    def rmatvec(self, r):
        with jax.named_scope("dense/rmatvec"):
            return self._with_sum(self.X.T @ r, r)

    def row_matvec(self, i, W):
        A, b = self._split(W)
        z = self.X[i] @ A
        return z if b is None else z + b

    def row_rmatvec(self, i, g):
        return self._with_sum(self.X[i].T @ g, g)

    def weighted_gram_rhs(self, sw, T):
        Xw = self.X * sw[:, None]
        G, rhs = self.X.T @ Xw, Xw.T @ T
        if not self._icpt:
            return G, rhs
        c = jnp.sum(Xw, axis=0)
        G = jnp.block([[G, c[:, None]],
                       [c[None, :], jnp.sum(sw)[None, None]]])
        return G, jnp.concatenate([rhs, (sw @ T)[None]])


class _PackedOperator(LinearOperator):
    """The intercept is one extra packed column (``idx=d, val=1``);
    every product is a gather or a scatter of the kernels above."""

    __slots__ = ("pidx", "pval")

    def __init__(self, X, fit_intercept, matmul_dtype=None):
        super().__init__(X, fit_intercept, matmul_dtype)
        self.dtype = X.val.dtype
        idx, val = X.idx, X.val
        if fit_intercept:
            idx = jnp.concatenate(
                [idx, jnp.full((self.n, 1), self.d, idx.dtype)], axis=1
            )
            val = jnp.concatenate(
                [val, jnp.ones((self.n, 1), val.dtype)], axis=1
            )
        self.pidx, self.pval = idx, val

    def matvec(self, W):
        if self.bf16:
            g = W.astype(jnp.bfloat16)[self.pidx]
            v = self.pval.astype(jnp.bfloat16)
            if g.ndim == 2:
                return jnp.sum((v * g).astype(jnp.float32), axis=1)
            return jnp.sum(
                (v[:, :, None] * g).astype(jnp.float32), axis=1
            )
        return packed_matvec(self.pidx, self.pval, W)

    def rmatvec(self, r):
        return packed_rmatvec(self.pidx, self.pval, r, self.p)

    def row_matvec(self, i, W):
        return packed_matvec(self.pidx[i], self.pval[i], W)

    def row_rmatvec(self, i, g):
        return packed_rmatvec(self.pidx[i], self.pval[i], g, self.p)

    def weighted_gram_rhs(self, sw, T):
        # the m² scatter for the gram; the rhs rides the rmatvec
        G = packed_weighted_gram(self.pidx, self.pval, sw, self.p)
        return G, self.rmatvec(sw[:, None] * T)


class _BucketedOperator(LinearOperator):
    """The intercept is NOT one more packed column here: a bucket's
    padding rows would carry it. It is the same column of ones applied
    beside the gathers (a broadcast add forward, a column sum backward:
    :func:`_spmm`). The forward products carry a custom VJP whose
    backward IS the transposed product, so the solvers differentiate
    the loss through them.

    A weight matrix lies CLASSES-MAJOR in the flat vector, each class's
    row padded to ``width = ⌈p / 128⌉ · 128`` entries
    (:data:`ROW_ALIGN`): class ``c``'s ``p`` weights at ``[c·width,
    c·width + p)``, its intercept at ``c·width + d``, zeros after. The
    zeros stay zero through a solve — the transposed product returns
    zero there, the penalty leaves them out, and every vector of the
    solver is a sum of gradients — and no gather reads them. A round's
    ``(lanes, k·width)`` vectors are then ``(lanes·k, width)`` as they
    lie, the operand of :func:`_spmm` under ``vmap`` with nothing
    moved, and the one transpose on each side of a product has a
    multiple of a tile's 128 on its minor axis going in and coming
    out. :meth:`logits` comes out classes-first, ``(k, n)``, as the
    dense operator's. ``matvec`` and ``rmatvec`` keep the true
    ``(p, k)`` on their weights' side, for the callers that shape a
    flat vector by hand."""

    __slots__ = ("bx", "_mv", "_logits", "_icpt", "width")

    #: :meth:`logits` carries the classes first, ``(k, n)``
    class_axis = 0

    def __init__(self, X, fit_intercept, matmul_dtype=None):
        super().__init__(X, fit_intercept, matmul_dtype)
        self.dtype = X.rows[0][1].dtype
        self.bx, self._icpt = X, bool(fit_intercept)
        self.width = -(-self.p // ROW_ALIGN) * ROW_ALIGN
        self._mv = bucketed_matvec_with_vjp(X, self.bf16, self._icpt)
        self._logits = _with_transpose_as_vjp(*(
            _bucketed_product(X, transposed, self.bf16, self._icpt,
                              self.width)
            for transposed in (False, True)))

    def logits(self, W):
        return self._logits(W)

    def flat_size(self, k):
        return self.width * k

    def weights(self, wflat, k):
        return wflat.reshape(k, self.width)

    def coef_sq_sum(self, W):
        if not self._icpt:  # the padding is zero: it adds nothing
            return jnp.sum(W * W)
        # the intercepts masked where they lie, inside the one
        # reduction: a slice of the coefficients beside it makes every
        # trial step of a line search write its ``w + t·d`` out
        at = jax.lax.broadcasted_iota(jnp.int32, W.shape, 1)
        return jnp.sum(jnp.where(at == self.d, 0.0, W * W))

    def matrix(self, wflat, k):
        return self.weights(wflat, k)[:, :self.p].T

    def flat(self, W):
        return jnp.pad(
            W.T, ((0, 0), (0, self.width - self.p))).reshape(-1)

    def matvec(self, W):
        return self._mv(W)

    def rmatvec(self, r):
        return bucketed_rmatvec(self.bx, r, self.bf16, self._icpt)

    def row_matvec(self, i, W):
        out = sum(packed_matvec(idx, val, W[:self.d])
                  for idx, val in _bucket_rows(self.bx, i))
        if self.bx.head is not None:
            out = out + self.bx.head[i] @ W[self.bx.head_cols]
        return out + W[self.d] if self._icpt else out

    def row_rmatvec(self, i, g):
        out = sum(packed_rmatvec(idx, val, g, self.d)
                  for idx, val in _bucket_rows(self.bx, i))
        if self.bx.head is not None:
            out = out.at[self.bx.head_cols].add(self.bx.head[i].T @ g)
        if not self._icpt:
            return out
        return jnp.concatenate([out, jnp.sum(g, axis=0)[None]])

    def weighted_gram_rhs(self, sw, T):
        # a (p, p) gram exists only where p is small: rebuild
        Xa = bucketed_to_dense(self.bx, self._icpt)
        Xw = Xa * sw[:, None]
        return Xa.T @ Xw, Xw.T @ T
