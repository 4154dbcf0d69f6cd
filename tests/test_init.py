"""
Import-smoke tests (reference pattern: per-module `_import_error is
None` checks, e.g. distribute/tests/test_search.py:20-34) — catches
dependency/packaging breakage early.
"""

import importlib

import pytest

MODULES = [
    "skdist_tpu",
    "skdist_tpu.base",
    "skdist_tpu.metrics",
    "skdist_tpu.preprocessing",
    "skdist_tpu.postprocessing",
    "skdist_tpu.models",
    "skdist_tpu.models.linear",
    "skdist_tpu.models.solvers",
    "skdist_tpu.models.tree",
    "skdist_tpu.models.forest",
    "skdist_tpu.models.naive_bayes",
    "skdist_tpu.ops",
    "skdist_tpu.ops.binning",
    "skdist_tpu.parallel",
    "skdist_tpu.parallel.backend",
    "skdist_tpu.parallel.mesh",
    "skdist_tpu.distribute",
    "skdist_tpu.distribute.search",
    "skdist_tpu.distribute.multiclass",
    "skdist_tpu.distribute.ensemble",
    "skdist_tpu.distribute.eliminate",
    "skdist_tpu.distribute.encoder",
    "skdist_tpu.distribute._defaults",
    "skdist_tpu.distribute.predict",
    "skdist_tpu.native",
    "skdist_tpu.utils",
    "skdist_tpu.utils.validation",
    "skdist_tpu.utils.childproc",
    "skdist_tpu.ops.pallas_hist",
    "skdist_tpu.parallel.compile_cache",
]


@pytest.mark.parametrize("name", MODULES)
def test_module_imports(name):
    mod = importlib.import_module(name)
    for export in getattr(mod, "__all__", []):
        if hasattr(mod, export) or export in getattr(mod, "_EXPORTS", {}):
            continue
        # packages may list submodules in __all__ (import-* semantics)
        importlib.import_module(f"{name}.{export}")


def test_top_level_exports_resolve():
    import skdist_tpu

    for name in skdist_tpu._EXPORTS:
        assert getattr(skdist_tpu, name) is not None


def test_version():
    import skdist_tpu

    assert skdist_tpu.__version__