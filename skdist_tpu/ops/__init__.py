"""
Low-level XLA/pallas ops supporting the estimator kernels.
"""

import jax

from .binning import apply_bins, quantile_bin_edges

__all__ = ["quantile_bin_edges", "apply_bins", "pallas_interpret"]


def pallas_interpret():
    """Whether the package's Pallas kernels run through the Pallas
    interpreter instead of being compiled.

    The ONE place that decides it: every ``interpret=None`` of
    ``ops/pallas_hist.py`` resolves here, and nothing else in the
    package may pass ``interpret=True``. It is ``False`` whenever the
    default backend is a TPU — there a kernel compiles or raises, it
    never runs interpreted — and ``True`` elsewhere, which is what lets
    the CPU tests fit through ``hist_mode='pallas'``."""
    return jax.default_backend() != "tpu"
