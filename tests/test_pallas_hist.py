"""Direct unit tests for ops/pallas_hist.level_histogram (interpret
mode on the CPU mesh; the compiled path is exercised on real TPU by
build_tools/tpu_tree_sweep.py).

The kernel contracts on-the-fly one-hot factors in VMEM; these tests
pin its semantics against a plain numpy histogram oracle, exercising
the sample-padding path (n not a multiple of the chunk S), the lane
padding path (nl*C far below the lane block LB), and the exclusion of
samples whose node key is >= nl (not at this level / padding).
"""

import numpy as np
import pytest

from skdist_tpu.ops.pallas_hist import level_histogram


def _oracle(Xb, node_key, Ych, nl, B):
    n, d = Xb.shape
    C = Ych.shape[1]
    hist = np.zeros((d, nl, B, C), np.float64)
    for i in range(n):
        j = node_key[i]
        if j >= nl:
            continue
        for f in range(d):
            hist[f, j, Xb[i, f]] += Ych[i]
    return hist.astype(np.float32)


@pytest.mark.parametrize("n,nl", [(37, 3), (64, 1), (130, 8)])
def test_level_histogram_matches_oracle(n, nl):
    rng = np.random.RandomState(n + nl)
    d, C, B = 3, 2, 4
    Xb = rng.randint(0, B, size=(n, d)).astype(np.int32)
    # ~1/4 of samples not at this level (key == nl sentinel)
    node_key = rng.randint(0, nl + (nl // 2 or 1), size=n).astype(np.int32)
    Ych = rng.rand(n, C).astype(np.float32)

    out = np.asarray(level_histogram(
        Xb, node_key, Ych, nl=nl, n_bins=B, interpret=True, S=32,
    ))
    ref = _oracle(Xb, node_key, Ych, nl, B)
    assert out.shape == (d, nl, B, C)
    np.testing.assert_allclose(out, ref, rtol=0, atol=1e-4)


def test_level_histogram_total_mass_excludes_padding():
    """Σ hist over (node, bin) per feature == Σ Ych over included
    samples — the padded sample rows (n -> n_pad) must contribute 0."""
    rng = np.random.RandomState(7)
    n, d, C, B, nl = 41, 2, 3, 8, 4
    Xb = rng.randint(0, B, size=(n, d)).astype(np.int32)
    node_key = rng.randint(0, nl, size=n).astype(np.int32)
    Ych = rng.rand(n, C).astype(np.float32)
    out = np.asarray(level_histogram(
        Xb, node_key, Ych, nl=nl, n_bins=B, interpret=True, S=32,
    ))
    want = Ych.sum(axis=0)
    for f in range(d):
        np.testing.assert_allclose(
            out[f].sum(axis=(0, 1)), want, rtol=1e-5
        )


def test_hist_auto_pallas_degrades_below_8_bins(monkeypatch, tmp_path):
    from skdist_tpu.models.hist_calib import PATH_ENV, record_calibration
    from skdist_tpu.models.tree import build_tree_kernel, resolve_hist_config

    scratch = tmp_path / "hist_calib.json"
    monkeypatch.setenv(PATH_ENV, str(scratch))
    record_calibration("cpu", "pallas", source="test")
    # auto resolution: degrade to an XLA engine, never 'pallas'
    mode, _ = resolve_hist_config(10, 4, "auto")
    assert mode in ("scatter", "matmul")
    # and the kernel builder accepts it (the explicit-request path at
    # models/tree.py raises; auto must not reach that raise)
    kern = build_tree_kernel(
        n_features=6, n_bins=4, channels=3, max_depth=2,
        max_features=None, min_samples_split=2, min_samples_leaf=1,
        min_impurity_decrease=0.0, extra=False, classification=True,
        hist_mode="auto",
    )
    assert callable(kern)
    # >= 8 bins keeps the calibrated pallas pick
    mode8, _ = resolve_hist_config(10, 8, "auto")
    assert mode8 == "pallas"
    # an EXPLICIT pallas request below 8 bins still raises
    with pytest.raises(ValueError, match="n_bins >= 8"):
        build_tree_kernel(
            n_features=6, n_bins=4, channels=3, max_depth=2,
            max_features=None, min_samples_split=2, min_samples_leaf=1,
            min_impurity_decrease=0.0, extra=False, classification=True,
            hist_mode="pallas",
        )
