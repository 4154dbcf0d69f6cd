"""
Persistent cross-process compile cache + process-wide kernel caches.

Compilation is the dominant non-compute cost of a cold fan-out (every
grid shape, tree level and predict bucket compiles once per process).
This module concentrates every layer of compile reuse in one place:

1. **In-process memo caches** with *structural* keys. Kernel builders
   return fresh closures, and ``jax.jit`` keys its own cache on
   function identity — so a fresh closure per fit silently recompiles
   an identical program. Callers therefore pass a ``cache_key`` built
   from the estimator class qualname + static/meta signature (+ any
   shape constants the closure captures); two closures with the same
   structural key share one traced/compiled function. Three tiers:

   - kernel memo (``kernel_memo``): built Python closures
     (``models/linear._KERNEL_CACHE``, ``distribute/search``'s cv
     kernels) keyed on semantic signature;
   - jit memo (``jit_vmapped``): ``jit(vmap(kernel))`` per
     (structural key, static args, shardings);
   - AOT memo (``aot_executable``): ``fn.lower(...).compile()``
     executables per (jit entry, shared shape signature, chunk).

2. **On-disk XLA compilation cache** (``enable_disk_cache``): JAX's
   persistent cache plus this module's export tier (``aot_exports/``
   beside it), so *repeated processes* skip XLA compilation and Python
   tracing — the cold-start killer for short-lived workers. Always on;
   :func:`resolve_cache_dir` is the ONE rule for where it lives: JAX's
   own ``JAX_COMPILATION_CACHE_DIR`` where that is set, else an
   explicit ``TPUBackend(compile_cache_dir=...)``, else the fixed
   ``<checkout>/.jax_cache`` (the directory is part of the cache key,
   so it is never a temporary name). Entries key on the serialized
   HLO + compile flags + jaxlib version, so a cache directory is safe
   to share between processes and survives code edits that do not
   change the compiled program.

3. **Counters** (``snapshot()``): hits/misses per tier plus cumulative
   wall time inside this module's own miss paths, so benchmarks and
   tests can *see* the cold-vs-warm gap instead of inferring it from
   wall clock — and what JAX itself reports of EVERY compile of the
   process, whichever path asked for it (a plain ``jax.jit`` called
   for the first time, an eager ``jnp`` op, this module's AOT tier
   alike): one pair of ``jax.monitoring`` listeners (:func:`_listen`)
   counts backend compiles and those XLA's persistent cache did not
   serve, and with tracing on records each stage as a span
   (``jax_trace``, ``jax_lower``, ``xla_compile``: the seconds live
   there, once) under whatever span is open on the compiling thread.

Thread safety: counters and memo insertion take a module lock; the
underlying dicts are plain (reads are GIL-atomic, and double-building
a cache entry is benign — last writer wins, both entries are correct).
"""

import os
import sys
import threading
import time
import warnings

__all__ = [
    "resolve_cache_dir",
    "enable_disk_cache",
    "disk_cache_dir",
    "structural_key",
    "kernel_memo",
    "jit_vmapped",
    "aot_executable",
    "prewarm",
    "aot_executables",
    "snapshot",
    "scoped_misses",
    "last_stats",
    "reset_stats",
    "clear_memos",
]

from ..obs import trace as _trace

#: JAX's own variable: where it is set, that directory IS the cache
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
#: the fixed default, beside the package (git-ignored in a checkout)
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)

_LOCK = threading.RLock()

#: the counter kinds of the compile plane — billed into the telemetry
#: registry (``skdist_tpu.obs.metrics``) as ``compile.events{kind=...}``
#: plus a float ``compile.lower_time_s`` wall accumulator; snapshot()
#: below is a VIEW over the registry, so the same numbers surface in
#: the Prometheus/JSON exporters with no second bookkeeping path
_COUNTER_KINDS = (
    "kernel_hits",
    "kernel_misses",
    "jit_hits",
    "jit_misses",
    "aot_hits",
    "aot_misses",
    # the on-disk EXPORT layer (serialized AOT programs; skips Python
    # tracing in warm-disk processes): file served / file written
    "aot_export_hits",
    "aot_export_writes",
    # what JAX reports (``_listen``): every backend compile of the
    # process — on a persistent-cache hit it is the read — and those
    # XLA's persistent cache did not serve (a cold start; 0 in a
    # process over a warm directory)
    "backend_compiles",
    "xla_cache_misses",
)

#: jit(vmap(kernel)) entries: (structural-or-identity key, static args,
#: shardings) -> jitted fn
_JIT_CACHE = {}
#: AOT executables: (jit fn, shared shape sig, chunk) -> compiled
_AOT_CACHE = {}
#: built kernel closures: namespaced semantic key -> closure
_KERNEL_MEMO = {}
#: jit fn -> (process-stable key string, re-jit wrapper) for entries
#: built with a structural cache_key — the export disk layer's filename
#: basis, and how it jits the deserialized program like the original
_JIT_EXPORT_KEY = {}

_DISK_DIR = None


# ---------------------------------------------------------------------------
# on-disk XLA compilation cache
# ---------------------------------------------------------------------------

def resolve_cache_dir(path=None):
    """Where the persistent compile cache (XLA entries and the export
    tier's ``aot_exports/``) lives: ``JAX_COMPILATION_CACHE_DIR`` as it
    is written, where that is set — an explicit ``path`` yields to it —
    else ``path``, else ``<checkout>/.jax_cache``."""
    env = os.environ.get(CACHE_DIR_ENV)
    if env:
        return env
    return os.path.abspath(path) if path else _DEFAULT_DIR


def enable_disk_cache(path=None):
    """Turn on JAX's persistent compilation cache at
    :func:`resolve_cache_dir` and return the active directory.

    Called from every compile path and backend constructor, so the
    cache is on whatever the entry point. Idempotent; the first caller
    wins for the lifetime of the process (JAX's cache config is global
    — re-pointing it mid-process would split warm state across
    directories, so a conflicting later ``path`` raises). Thresholds
    are dropped to cache-everything: a service process's cold start
    pays for EVERY kernel, not only the slow ones.
    """
    global _DISK_DIR
    if path is None and _DISK_DIR is not None:
        return _DISK_DIR
    with _LOCK:
        target = resolve_cache_dir(path)
        if _DISK_DIR is not None:
            if _DISK_DIR != target:
                raise ValueError(
                    "the persistent compile cache is already at "
                    f"{_DISK_DIR!r}; JAX's cache config is process-global "
                    f"and cannot be re-pointed to {target!r}"
                )
            return _DISK_DIR
        import jax
        # the export layer needs it; importing now keeps its ~0.3 s
        # module-exec cost out of the first timed fit
        from jax import export as _export  # noqa: F401

        # the cache backend skips a directory it cannot open; create it
        # up front so the very first compile already writes through
        os.makedirs(target, exist_ok=True)
        if jax.config.jax_compilation_cache_dir != target:
            jax.config.update("jax_compilation_cache_dir", target)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        _DISK_DIR = target
        _listen()
        return _DISK_DIR


def disk_cache_dir():
    """The active on-disk cache directory, or None before the first
    backend or compile of the process."""
    return _DISK_DIR


# ---------------------------------------------------------------------------
# structural keys + counters
# ---------------------------------------------------------------------------

_CLS_CODE_TOKENS = None


def _cls_code_token(cls):
    """Digest of a class's kernel-builder bytecode (inherited methods
    included). Part of every structural key: a module-qualified NAME
    alone would let an in-process class redefinition (REPL/notebook
    re-execution with edited kernel math, same qualname) silently
    serve the old class's compiled kernel. Bytecode is deterministic
    for identical source under one Python version, so the token stays
    process-stable for the export disk layer while distinguishing
    redefinitions. Memoised per class object (weakly — REPL classes
    must be collectable)."""
    global _CLS_CODE_TOKENS
    if _CLS_CODE_TOKENS is None:
        import weakref

        _CLS_CODE_TOKENS = weakref.WeakKeyDictionary()
    token = _CLS_CODE_TOKENS.get(cls)
    if token is None:
        import hashlib

        import types

        h = hashlib.sha256()

        def hash_code(code):
            h.update(code.co_code)
            for const in code.co_consts:
                if isinstance(const, types.CodeType):
                    # recurse into nested closures' bytecode: their
                    # repr() embeds per-process memory addresses, which
                    # would make the token differ in every process and
                    # silently defeat the cross-process export layer
                    hash_code(const)
                else:
                    h.update(repr(const).encode())

        for name in sorted(dir(cls)):
            # every _build_* method participates: kernel math also
            # lives in the shared _build_fit_problem /
            # _build_fit_slice_kernels builders the sliced-solver
            # variants are generated from
            if name.startswith("_build_"):
                fn = getattr(cls, name, None)
                code = getattr(getattr(fn, "__func__", fn), "__code__", None)
                if code is not None:
                    h.update(name.encode())
                    hash_code(code)
        token = h.hexdigest()[:12]
        _CLS_CODE_TOKENS[cls] = token
    return token


def structural_key(family, est_cls, *parts):
    """Stable cache key for a kernel closure's *semantics*.

    ``family`` names the fan-out call site ("cv", "ovr", "predict",
    ...); ``est_cls`` is the estimator class (stored as
    module-qualified name + kernel-builder bytecode token, so the key
    survives reload/re-import, is identical across processes, AND
    distinguishes an in-process redefinition with edited kernel math);
    ``parts`` must capture EVERYTHING the closure bakes in beyond its
    argument shapes — static config, meta signature, scorer names,
    captured shape constants. Two closures with equal structural keys
    are promised interchangeable.
    """
    if isinstance(est_cls, type):
        est_cls = (f"{est_cls.__module__}.{est_cls.__qualname__}",
                   _cls_code_token(est_cls))
    return (family, est_cls) + tuple(parts)


_FAMILIES = None
_LISTENING = False


def _families():
    """(events counter, lower-time counter, scoped-miss counter) —
    registry handles, built once."""
    global _FAMILIES
    if _FAMILIES is None:
        from ..obs import metrics as obs_metrics

        _FAMILIES = (
            obs_metrics.counter(
                "compile.events",
                help="compile-cache tier hits/misses; backend compiles "
                     "and persistent-cache misses as JAX reports them",
            ),
            obs_metrics.counter(
                "compile.lower_time_s",
                help="wall seconds inside compile_cache's own miss "
                     "paths (closure build, jit wrap, export "
                     "read/write + lower + compile), NOT JAX's "
                     "lowering: that is the jax_lower span",
            ),
            obs_metrics.counter(
                "compile.scoped_misses",
                help="compile-shaped misses attributed to an active "
                     "obs.metrics.compile_scope (serving engines)",
            ),
        )
    if not _LISTENING:
        _listen()
    return _FAMILIES


# ---------------------------------------------------------------------------
# what JAX reports of a compile
# ---------------------------------------------------------------------------

#: per compiling thread: how the backend compile in progress met XLA's
#: persistent cache ("miss" once the request uses the cache, "hit" once
#: it is served), read by the duration that follows on the same thread.
#: JAX announces no failed compile, so the state is dropped at every
#: lowering too: a "miss" left by a compile that raised survives only
#: until the thread next lowers or compiles
_COMPILING = threading.local()

_JAX_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_JAX_COMPILE = "/jax/core/compile/backend_compile_duration"
_JAX_CACHE_USED = "/jax/compilation_cache/compile_requests_use_cache"
_JAX_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_JAX_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_STAGE_SPANS = {"/jax/core/compile/jaxpr_trace_duration": "jax_trace",
                _JAX_LOWER: "jax_lower", _JAX_COMPILE: "xla_compile"}


def _on_jax_event(event, **_kw):
    if event == _JAX_CACHE_USED:
        _COMPILING.cache = "miss"
    elif event == _JAX_CACHE_HIT:
        _COMPILING.cache = "hit"
    elif event == _JAX_CACHE_MISS:
        _families()[0].inc(1, kind="xla_cache_misses")


def _on_jax_time_span(event, start, end, fun_name=None, **_kw):
    name = _STAGE_SPANS.get(event)
    if name is None:
        return
    args = {"fun": fun_name} if _trace.enabled() else None
    if event == _JAX_COMPILE:
        _families()[0].inc(1, kind="backend_compiles")
        if args is not None:
            args["cache"] = getattr(_COMPILING, "cache", None) or "off"
        _COMPILING.cache = None
    elif event == _JAX_LOWER:
        _COMPILING.cache = None
    if args is not None:
        # JAX times a stage on time.time(); the ring's clock is
        # perf_counter, and the stage ended a moment ago
        t0 = time.perf_counter() - (time.time() - start)
        _trace.complete(name, t0, end - start, args)


def _listen():
    """Register this module's ``jax.monitoring`` listeners, once a
    process. Called where the module first touches JAX
    (:func:`enable_disk_cache`) and from :func:`_families`; a process
    that has not imported JAX compiles nothing and is left without it.
    The listeners run only when JAX traces, lowers or compiles
    something: a warm round's dispatch never reaches them. A JAX
    without these hooks leaves the program as it was without them — no
    ``jax_*`` / ``xla_compile`` span, the two counters at 0 — and says
    so once."""
    global _LISTENING
    if _LISTENING or "jax" not in sys.modules:
        return
    with _LOCK:
        if _LISTENING:
            return
        _LISTENING = True
        try:
            from jax import monitoring

            monitoring.register_event_listener(_on_jax_event)
            monitoring.register_event_time_span_listener(_on_jax_time_span)
        except Exception as exc:  # observability never stops a backend
            warnings.warn(
                f"compile_cache: this JAX announces no compile events "
                f"({type(exc).__name__}: {exc}); backend compiles go "
                f"uncounted and unspanned", RuntimeWarning)


def _record(counter, dt=0.0):
    events, lower, scoped = _families()
    events.inc(1, kind=counter)
    if dt:
        lower.inc(float(dt))
    if counter.endswith("_misses"):
        # scoped attribution: a serving engine's dispatch threads tag
        # themselves (obs.metrics.compile_scope) so compiles THEY cause
        # are separable from concurrent non-serving work — the basis of
        # ServingStats.compiles_after_warmup's per-engine delta
        from ..obs import metrics as obs_metrics

        tag = obs_metrics.current_scope()
        if tag is not None:
            scoped.inc(1, scope=tag)


def scoped_misses(tag):
    """Compile-shaped misses billed while ``compile_scope(tag)`` was
    active on the recording thread — the per-engine counter
    ``ServingStats.compiles_after_warmup`` snapshots."""
    return int(_families()[2].get(scope=str(tag)))


def snapshot():
    """Current counters (plus the disk cache dir), as a plain dict —
    a view over the telemetry registry's ``compile.*`` families. One
    ``children()`` read per family (single lock acquisition), so the
    event counters are mutually consistent within the snapshot."""
    events, lower, _scoped = _families()
    kids = events.children()
    out = {
        k: int(kids.get((("kind", k),), 0)) for k in _COUNTER_KINDS
    }
    out["lower_time_s"] = round(float(lower.get()), 4)
    out["disk_cache_dir"] = _DISK_DIR
    return out


def last_stats():
    """Alias of :func:`snapshot` — the name the compaction tests/smoke
    read when asserting "no recompile after warmup" (counter deltas
    between two snapshots around the flags-only slice loop)."""
    return snapshot()


def reset_stats():
    """Zero the counters (memo contents and disk config are kept).
    Scoped-miss attribution resets too — engines holding a warm mark
    across a reset re-baseline on their next ``mark_warm``."""
    for fam in _families():
        fam.reset()


def clear_memos():
    """Drop every in-process memo (tests; frees compiled executables)."""
    with _LOCK:
        _JIT_CACHE.clear()
        _AOT_CACHE.clear()
        _KERNEL_MEMO.clear()
        _JIT_EXPORT_KEY.clear()


# ---------------------------------------------------------------------------
# tier 1: kernel closures
# ---------------------------------------------------------------------------

def kernel_memo(key, build):
    """Return the memoised kernel closure for ``key``, building (and
    timing) it on first use. ``key`` must be namespaced by the caller
    (e.g. via :func:`structural_key`)."""
    fn = _KERNEL_MEMO.get(key)
    if fn is not None:
        _record("kernel_hits")
        return fn
    t0 = time.perf_counter()
    with _trace.span("compile", {"tier": "kernel"}
                     if _trace.enabled() else None):
        fn = build()
    _record("kernel_misses", time.perf_counter() - t0)
    with _LOCK:
        return _KERNEL_MEMO.setdefault(key, fn)


# ---------------------------------------------------------------------------
# tier 2: jit(vmap(kernel))
# ---------------------------------------------------------------------------

def jit_vmapped(kernel, static_args, task_sharding=None,
                shared_shardings=None, cache_key=None, donate_tasks=False):
    """jit(vmap(kernel)) with the task axis mapped; memoised.

    ``kernel(shared_args, one_task_args, **static)`` → pytree of arrays.
    ``shared_shardings`` may be a single sharding (replicated) or a
    pytree mirroring the shared args (row-sharded 'data' leaves).

    ``cache_key`` (see :func:`structural_key`) replaces closure
    identity in the memo key so per-call closures still reuse one
    traced program; without it the kernel object itself keys the entry
    (safe default — distinct closures never alias).

    ``donate_tasks=True`` donates the task-slice argument's buffers to
    the computation: each round's input chunk is freshly placed and
    never reused, so XLA may overwrite it in place — reclaiming one
    round's task-argument HBM for outputs/temps.
    """
    import jax

    enable_disk_cache()
    static_args = tuple(sorted((static_args or {}).items()))
    # NamedSharding hashes by (mesh, spec): distinct meshes/device sets
    # must never share a compiled fn. Sharding pytrees are flattened to
    # a hashable key.
    shared_leaves, shared_def = jax.tree_util.tree_flatten(shared_shardings)
    key = (cache_key or kernel, static_args, task_sharding,
           tuple(shared_leaves), shared_def, bool(donate_tasks))
    fn = _JIT_CACHE.get(key)
    if fn is not None:
        _record("jit_hits")
        return fn
    t0 = time.perf_counter()
    static = dict(static_args)

    def mapped(shared, tasks):
        return jax.vmap(lambda t: kernel(shared, t, **static))(tasks)

    jit_kwargs = {"donate_argnums": (1,)} if donate_tasks else {}

    def wrap(f):
        # also how the export tier re-jits a deserialized program: the
        # SAME shardings and donation, or the executable would expect
        # differently placed arguments than the round loop hands it
        if task_sharding is not None:
            return jax.jit(
                f,
                in_shardings=(shared_shardings, task_sharding),
                out_shardings=task_sharding,
                **jit_kwargs,
            )
        return jax.jit(f, **jit_kwargs)

    with _trace.span("compile",
                     {"tier": "jit", "key": repr(cache_key)[:120]}
                     if _trace.enabled() else None):
        fn = wrap(mapped)
    _record("jit_misses", time.perf_counter() - t0)
    with _LOCK:
        fn = _JIT_CACHE.setdefault(key, fn)
        if cache_key is not None and fn not in _JIT_EXPORT_KEY:
            # a structural key makes the entry PROCESS-STABLE: record
            # the string form (+ mesh topology) the export disk layer
            # uses as its filename basis. Identity-keyed entries (no
            # cache_key) are not stable across processes and never
            # reach the export layer.
            _JIT_EXPORT_KEY[fn] = (
                repr((cache_key, static_args,
                      _sharding_desc(task_sharding),
                      tuple(_sharding_desc(s) for s in shared_leaves),
                      bool(donate_tasks))),
                wrap,
            )
        return fn


def _sharding_desc(s):
    """Process-stable description of a sharding (mesh topology + spec),
    NOT its object repr (which may embed per-process device ids)."""
    try:
        if s is None:
            return None
        mesh = s.mesh
        kinds = (
            str(mesh.devices.flat[0].device_kind)
            if mesh.devices.size else ""
        )
        return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
                kinds, repr(s.spec))
    except Exception:
        return repr(s)


# ---------------------------------------------------------------------------
# tier 3: AOT executables
# ---------------------------------------------------------------------------

def aot_executable(fn, shared_args, task_like, n_chunk, shared_sig=None):
    """AOT-compile ``fn`` for a task chunk of ``n_chunk`` (memoised).

    ``fn`` must be an AOT-capable jitted function (``.lower``);
    ``task_like`` supplies per-task leaf shapes/dtypes (its leading
    axis is replaced by ``n_chunk``). The memo keys on the jit entry
    itself — jitted fns are memoised structurally in tier 2, so this
    composes to the same lifetime jit's own compilation cache would
    have had, plus explicit counters and the on-disk write-through.
    The task leaves' TRAILING shapes are part of the key: one jit
    entry legitimately serves several task widths (jit re-traces by
    shape; e.g. sparse predict's packed nnz width), and an executable
    compiled for one width must never be served for another.
    """
    import jax

    if shared_sig is None:
        shared_sig = shape_sig(shared_args)
    task_sig = tuple(
        (tuple(l.shape[1:]), str(l.dtype))
        for l in jax.tree_util.tree_leaves(task_like)
    )
    key = (fn, shared_sig, task_sig, n_chunk)
    comp = _AOT_CACHE.get(key)
    if comp is not None:
        _record("aot_hits")
        return comp
    t0 = time.perf_counter()
    structs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            (n_chunk,) + tuple(a.shape[1:]), a.dtype
        ),
        task_like,
    )
    with warnings.catch_warnings():
        # donated task leaves too small/oddly-shaped to alias an output
        # (scalar hypers, split ids) are expected and harmless — the
        # donation exists for the big leaves; don't warn per compile
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        span_args = ({"tier": "aot", "chunk": int(n_chunk), "export": "off"}
                     if _trace.enabled() else None)
        with _trace.span("compile", span_args):
            comp = _exported_executable(
                fn, shared_args, structs, shared_sig, task_sig, n_chunk,
                span_args,
            )
            if comp is None:
                comp = fn.lower(shared_args, structs).compile()
    _record("aot_misses", time.perf_counter() - t0)
    with _LOCK:
        return _AOT_CACHE.setdefault(key, comp)


def prewarm(fn, shared_args, task_like, n_chunk=None, shared_sig=None):
    """AOT-prewarm ``fn`` for an explicit task shape, with NO task data.

    The public entry point for shape-driven warmup (the serving
    registry's bucket prewarm): ``task_like`` is a pytree whose leaves
    are arrays OR ``jax.ShapeDtypeStruct``s — only ``.shape``/``.dtype``
    are read — and whose leading axis is the chunk (overridable via
    ``n_chunk``). Compilation goes through the same memo + disk layers
    as live dispatch (:func:`aot_executable`), so a later real call of
    the same shape is a pure in-process cache hit, and a warm-disk
    process skips tracing and XLA compilation entirely. Returns the
    compiled executable.
    """
    import jax

    if n_chunk is None:
        leaves = jax.tree_util.tree_leaves(task_like)
        if not leaves:
            raise ValueError("prewarm needs at least one task leaf")
        n_chunk = int(leaves[0].shape[0])
    return aot_executable(
        fn, shared_args, task_like, n_chunk, shared_sig=shared_sig
    )


def aot_executables():
    """The AOT executables this process holds, in compile order — for
    diagnostics that read a compiled program itself
    (``memory_analysis()``, ``as_text()``: which collectives and custom
    calls the compiler put in)."""
    with _LOCK:
        return list(_AOT_CACHE.values())


_SOURCE_DIGEST = None


def _source_digest():
    """Digest of every .py file in the skdist_tpu package (computed
    once per process, ~ms). Part of the export filename: structural
    keys name WHAT a kernel computes, not HOW — a source edit that
    changes kernel math under an unchanged structural key must
    invalidate the serialized program, or a warm cache directory would
    silently serve stale math across a package upgrade. (The XLA tier
    keys on HLO bytes and self-invalidates; this tier exists to skip
    producing the HLO, so it needs its own invalidation basis.)
    Over-invalidates on unrelated edits, which a cache may."""
    global _SOURCE_DIGEST
    if _SOURCE_DIGEST is None:
        import hashlib

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        h = hashlib.sha256()
        for dirpath, dirnames, filenames in sorted(os.walk(root)):
            dirnames.sort()
            for name in sorted(filenames):
                if name.endswith(".py"):
                    path = os.path.join(dirpath, name)
                    h.update(path[len(root):].encode())
                    try:
                        with open(path, "rb") as f:
                            h.update(f.read())
                    except OSError:
                        h.update(b"?")
        _SOURCE_DIGEST = h.hexdigest()[:16]
    return _SOURCE_DIGEST


def _export_path(keystr, shared_sig, task_sig, n_chunk):
    import hashlib

    import jax

    payload = repr((keystr, shared_sig, task_sig, n_chunk,
                    jax.__version__, _source_digest()))
    h = hashlib.sha256(payload.encode()).hexdigest()[:32]
    return os.path.join(_DISK_DIR, "aot_exports", h + ".jaxexp")


def _exported_executable(fn, shared_args, structs, shared_sig, task_sig,
                         n_chunk, span_args=None):
    """The export disk layer: serialized AOT programs next to the XLA
    disk cache, so a warm-disk process skips PYTHON TRACING as well as
    XLA compilation — the two costs that dominate service cold-start.

    Active only when (a) the on-disk cache is enabled, (b) the jit
    entry carries a process-stable structural key, and (c) the run is
    single-process (exported device assignments don't transplant
    across multi-process topologies). First process: traces once via
    ``jax.export``, persists the serialized program, and compiles the
    EXPORTED form — both processes then execute byte-identical
    programs, and the exported form's XLA compile is what the disk
    cache holds, so the warm process's compile is a pure cache read.
    A failure to export, persist or re-trace the program
    (un-exportable program, version skew, disk trouble) returns None
    and the caller falls back to the direct lower+compile path; a
    failure of the COMPILE itself propagates.

    With tracing on, the file's read (``export_read``: read +
    ``deserialize``) or its making (``export_write``: ``jexport.export``
    — Python tracing and StableHLO — serialize, write) is a span with
    the file's ``bytes``, and ``span_args`` (the enclosing ``compile``
    span's) says which way it went: ``export`` ``"hit"`` or ``"write"``
    where the caller's ``"off"`` stood. The re-lowering of ``exp.call``
    and the ``compile()`` report themselves (``jax_lower``,
    ``xla_compile``: :func:`_listen`).
    """
    ent = _JIT_EXPORT_KEY.get(fn)
    if _DISK_DIR is None or ent is None:
        return None
    keystr, wrap = ent
    try:
        import jax
        from jax import export as jexport

        if jax.process_count() > 1:
            return None
        path = _export_path(keystr, shared_sig, task_sig, n_chunk)
        how = "hit" if os.path.exists(path) else "write"
        # a span keeps its dict until it closes: filled in inside it
        io_args = {}
        if how == "hit":
            with _trace.span("export_read", io_args):
                with open(path, "rb") as f:
                    blob = f.read()
                exp = jexport.deserialize(bytearray(blob))
                io_args["bytes"] = len(blob)
            _record("aot_export_hits")
        else:
            with _trace.span("export_write", io_args):
                exp = jexport.export(fn)(shared_args, structs)
                blob = exp.serialize()
                os.makedirs(os.path.dirname(path), exist_ok=True)
                tmp = path + f".tmp{os.getpid()}"
                with open(tmp, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)
                io_args["bytes"] = len(blob)
            _record("aot_export_writes")
        if span_args is not None:
            span_args["export"] = how
        lowered = wrap(exp.call).lower(shared_args, structs)
    except Exception as exc:
        if span_args is not None:
            span_args["export"] = "off"
        warnings.warn(
            f"compile_cache export layer disabled for this program "
            f"({type(exc).__name__}: {exc}); falling back to direct "
            "compilation"
        )
        return None
    # outside the fallback: what the compiler refuses in the exported
    # form (a round that does not fit device memory, a kernel Mosaic
    # rejects) it refuses in the direct form too, and the caller must
    # see that, not a warning and a second refused compile
    return lowered.compile()


def shape_sig(tree):
    import jax

    return tuple(
        (tuple(l.shape), str(l.dtype)) for l in jax.tree_util.tree_leaves(tree)
    )
