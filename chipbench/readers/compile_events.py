"""What JAX itself reports of the process's compiles, counted by the
program's registry whether tracing is on or not.

Reads ``skdist_tpu.parallel.compile_cache.snapshot()[key]`` in the same
process, after the window and before the reference takes the device:
the set-up's count and the window's together, of which the window's
share is ``window_xla_compiles.search`` (0 in a steady fit). ``key``:

- ``backend_compiles``: every backend compile, whichever path asked for
  it (the AOT tier, a plain ``jax.jit`` at its first call, an eager
  ``jnp`` op) — on a persistent-cache hit it is the read;
- ``xla_cache_misses``: those XLA's persistent cache did not serve — 0
  in a process over a warm cache directory, so a reading above 0 in a
  second run says a program's cache key moved.

``None`` — the metric is left out — on a program whose ``snapshot()``
has no such key: the parent of the PR that added them."""


def read(ctx, key):
    from skdist_tpu.parallel import compile_cache

    count = compile_cache.snapshot().get(key)
    return None if count is None else float(count)
