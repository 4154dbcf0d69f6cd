"""
Compile-cache layer + pipelined round scheduler tests.

Covers the execution-speed layer of the fan-out backend:
- structural-key memo caches shared across backend instances in one
  process (counters observable via compile_cache.snapshot());
- the on-disk XLA compilation cache reused by a SECOND process
  (tests/test_multiproc.py-style subprocess harness);
- pipelined rounds produce bit-identical results to the
  forced-synchronous debug mode;
- OOM-resume still works with task-buffer donation enabled (the
  default).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from skdist_tpu.parallel import LocalBackend, TPUBackend, compile_cache

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _grid_fit(backend, X, y, partitions=None):
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression

    return DistGridSearchCV(
        LogisticRegression(max_iter=15, engine="xla"),
        {"C": [0.1, 1.0, 10.0]}, backend=backend, cv=3,
        scoring="accuracy", partitions=partitions,
    ).fit(X, y)


def test_structural_cache_hits_across_backends(clf_data):
    """TWO backend instances in one process share the kernel/jit/AOT
    memos: the second fit is pure cache hits — no new closures traced,
    no new programs compiled."""
    X, y = clf_data
    _grid_fit(TPUBackend(), X, y)  # prime (may or may not miss)
    snap1 = compile_cache.snapshot()
    _grid_fit(TPUBackend(), X, y)  # fresh backend, same mesh/semantics
    snap2 = compile_cache.snapshot()
    assert snap2["kernel_hits"] > snap1["kernel_hits"]
    assert snap2["jit_hits"] > snap1["jit_hits"]
    assert snap2["jit_misses"] == snap1["jit_misses"]
    assert snap2["aot_misses"] == snap1["aot_misses"]
    assert snap2["kernel_misses"] == snap1["kernel_misses"]


def test_structural_key_spans_local_and_device_jit_tiers(clf_data):
    """LocalBackend and TPUBackend compile DIFFERENT programs (no mesh
    vs mesh sharding) — the structural key must keep them apart while
    still deduplicating within each tier."""
    X, y = clf_data
    r_local = _grid_fit(LocalBackend(), X, y).cv_results_
    r_dev = _grid_fit(TPUBackend(), X, y).cv_results_
    # CPU mesh executes the same program semantics: scores agree
    np.testing.assert_allclose(
        r_local["mean_test_score"], r_dev["mean_test_score"], atol=1e-6
    )


def test_pipelined_matches_sync_bitwise(clf_data):
    """The default pipelined scheduler and the forced-synchronous debug
    mode must gather BITWISE-identical outputs on a multi-round
    workload (acceptance criterion)."""
    X, y = clf_data
    bk_pipe = TPUBackend()
    bk_sync = TPUBackend(sync_rounds=True)
    r1 = _grid_fit(bk_pipe, X, y, partitions=3).cv_results_
    r2 = _grid_fit(bk_sync, X, y, partitions=3).cv_results_
    assert bk_pipe.last_round_stats["mode"] == "pipelined"
    assert bk_pipe.last_round_stats["rounds"] >= 2
    assert bk_sync.last_round_stats["mode"] == "synchronous"
    for key in r1:
        if key.startswith(("split", "mean_test", "std_test")):
            np.testing.assert_array_equal(r1[key], r2[key], err_msg=key)


def test_sync_rounds_env_flag(monkeypatch):
    monkeypatch.setenv("SKDIST_SYNC_ROUNDS", "1")
    assert TPUBackend().sync_rounds is True
    assert LocalBackend().sync_rounds is True
    monkeypatch.delenv("SKDIST_SYNC_ROUNDS")
    assert TPUBackend().sync_rounds is False


def test_oom_resume_with_donation_enabled(monkeypatch):
    """The reactive OOM halving + contiguous-prefix resume must survive
    task-buffer donation (the default): resumed rounds re-place fresh
    slices, so donated (consumed) buffers are never reused."""
    import jax

    from skdist_tpu.parallel import backend as backend_mod

    bk = TPUBackend(donate_tasks=True)
    assert bk.donate_tasks is True
    real_jit = backend_mod._jit_vmapped
    seen = []

    def fussy_jit(kernel, static_args, *rest):
        fn = real_jit(kernel, static_args, *rest)

        def wrapper(shared, tasks):
            chunk = jax.tree_util.tree_leaves(tasks)[0].shape[0]
            seen.append(chunk)
            if chunk > 8:
                raise RuntimeError("RESOURCE_EXHAUSTED (simulated)")
            return fn(shared, tasks)

        return wrapper

    monkeypatch.setattr(backend_mod, "_jit_vmapped", fussy_jit)
    tasks = {"x": np.arange(32, dtype=np.float32)}
    with pytest.warns(UserWarning, match="exhausted device memory"):
        out = bk.batched_map(lambda shared, t: {"y": t["x"] * 3.0}, tasks)
    np.testing.assert_allclose(out["y"], np.arange(32) * 3.0)
    assert max(seen) > 8 and seen[-1] <= 8


_CHILD = """
import numpy as np
from skdist_tpu.distribute.search import DistGridSearchCV
from skdist_tpu.models import LogisticRegression
from skdist_tpu.parallel import LocalBackend, TPUBackend, compile_cache

rng = np.random.RandomState(0)
X = rng.normal(size=(90, 5)).astype(np.float32)
y = (X[:, 0] > 0).astype(np.int64)
dev = DistGridSearchCV(
    LogisticRegression(max_iter=10, engine="xla"), {"C": [0.5, 1.0]},
    backend=TPUBackend(), cv=3, scoring="accuracy",
).fit(X, y)
import os
assert compile_cache.disk_cache_dir() == os.environ["JAX_COMPILATION_CACHE_DIR"]
# the device path ran through the export disk layer (or wrote it);
# the plain-jit LocalBackend leg must agree — guards the exported
# program's numerics
loc = DistGridSearchCV(
    LogisticRegression(max_iter=10, engine="xla"), {"C": [0.5, 1.0]},
    backend=LocalBackend(), cv=3, scoring="accuracy",
).fit(X, y)
np.testing.assert_allclose(
    np.asarray(dev.cv_results_["mean_test_score"], dtype=float),
    np.asarray(loc.cv_results_["mean_test_score"], dtype=float),
    atol=1e-6,
)
print("CHILD OK", compile_cache.snapshot())
"""


def test_disk_cache_reused_across_processes(tmp_path):
    """Two FRESH processes with JAX_COMPILATION_CACHE_DIR set: the first
    writes every compiled program to disk; the second runs the same
    workload and adds NO new cache entries — every XLA compile was
    served from disk. (The entry set is deterministic: fixed seeds,
    pinned engine, same flags.)"""
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

    def run():
        proc = subprocess.run(
            [sys.executable, "-c", _CHILD], env=env, cwd=REPO,
            capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
        return {
            f for f in os.listdir(tmp_path) if f.endswith("-cache")
        }

    files1 = run()
    assert files1, "first process must write compiled programs to disk"
    # the export tier lives under the same directory
    assert os.listdir(tmp_path / "aot_exports")
    files2 = run()
    assert files2 == files1, (
        "second process recompiled programs the disk cache should have "
        f"served: {sorted(files2 - files1)}"
    )


def test_resolve_cache_dir_rule(monkeypatch, tmp_path):
    """JAX's own variable wins, as written; an explicit path yields to
    it; with neither, the fixed ``<checkout>/.jax_cache``."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.resolve_cache_dir() == os.path.join(
        REPO, ".jax_cache"
    )
    assert compile_cache.resolve_cache_dir(str(tmp_path)) == str(tmp_path)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.resolve_cache_dir() == "/some/dir"
    assert compile_cache.resolve_cache_dir(str(tmp_path)) == "/some/dir"


def test_enable_disk_cache_conflicting_path_raises(monkeypatch, tmp_path):
    """conftest enabled the cache by the program's rule; with JAX's
    variable unset a later explicit path cannot re-point it (and with
    it set, the explicit path yields instead of conflicting)."""
    import jax

    first = compile_cache.disk_cache_dir()
    assert first == jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_disk_cache() == first
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    with pytest.raises(ValueError, match="already"):
        compile_cache.enable_disk_cache(str(tmp_path / "elsewhere"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", first)
    assert compile_cache.enable_disk_cache(str(tmp_path / "elsewhere")) == first


def test_snapshot_and_reset():
    snap = compile_cache.snapshot()
    for key in ("kernel_hits", "kernel_misses", "jit_hits", "jit_misses",
                "aot_hits", "aot_misses", "lower_time_s",
                "disk_cache_dir",
                # what JAX reports of every compile of the process
                "backend_compiles", "xla_cache_misses"):
        assert key in snap
    compile_cache.reset_stats()
    snap2 = compile_cache.snapshot()
    assert snap2["jit_hits"] == 0 and snap2["kernel_misses"] == 0
    assert snap2["backend_compiles"] == 0
    assert snap2["xla_cache_misses"] == 0
    # disk config survives a counter reset
    assert snap2["disk_cache_dir"] == snap["disk_cache_dir"]


def test_structural_key_qualnames():
    from skdist_tpu.models import LogisticRegression

    key = compile_cache.structural_key("cv", LogisticRegression, ("a", 1))
    assert key[0] == "cv"
    name, token = key[1]
    assert name.endswith("LogisticRegression")
    assert "." in name  # module-qualified: survives re-import
    assert token  # kernel-builder bytecode digest
    assert key == compile_cache.structural_key(
        "cv", LogisticRegression, ("a", 1)
    )
    # a subclass redefining kernel math must NOT alias its parent
    class Tweaked(LogisticRegression):
        @classmethod
        def _build_fit_kernel(cls, meta, static):
            return super()._build_fit_kernel(meta, static)

    key2 = compile_cache.structural_key("cv", Tweaked, ("a", 1))
    assert key2 != key and key2[1][1] != token
