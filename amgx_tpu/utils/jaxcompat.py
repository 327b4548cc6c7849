"""Small shims over JAX private APIs (written for the installed JAX,
0.9).

``trace_state_clean`` guards the lazy device-pack caches: a value
produced while a trace is active is a tracer and must never be cached
past the trace.

``install_compile_counter`` hooks ``jax.monitoring`` duration events to
count jit cache misses for the telemetry registry: a retrace fires
``.../jaxpr_trace_duration`` (python-cache miss), an actual XLA backend
compile fires ``.../backend_compile_duration``.  NOTE (measured on the
pinned jax): the backend-compile duration event wraps
``compile_or_get_cached`` and therefore fires on persistent-compile-
cache HITS too — the cache-event listener below flags those per thread
so a warm-cache executable load is counted as
``amgx_compile_cache_hits_total``, NOT as an ``amgx_jit_compile_total``
recompile (which is the operational meaning callers assert on, e.g. the
cross-process zero-recompile test).  Every trace is also counted, with
its seconds, by the traced function's name whether telemetry is on or
not (:func:`trace_stats`).  The listeners are process-wide and
permanent — JAX has no unregister — and cost a few dict updates when
telemetry is disabled.

``configure_compilation_cache`` is the ONE place that decides where
JAX's persistent compilation cache lives (see its docstring);
``serialize_compiled`` / ``deserialize_compiled`` wrap
``jax.experimental.serialize_executable`` for the explicit AOT
executable store (:mod:`amgx_tpu.serve.aot`).
"""
from __future__ import annotations

import os
import pickle
import sys
import threading
from pathlib import Path
from typing import Optional

from jax._src.core import trace_state_clean  # noqa: F401  (re-export)


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` — the distributed tier (sharded SpMV, halo
    exchange, slab smoothers) routes every shard_map through here.
    ``check_vma=False`` is the escape hatch for bodies wrapping
    pallas_calls whose out_shapes carry no vma annotation."""
    import jax
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def axis_type_auto():
    """``jax.sharding.AxisType.Auto``: the GSPMD mode the distributed
    layer builds its meshes in."""
    import jax
    return jax.sharding.AxisType.Auto


_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_compile_listener_installed = False

#: ungated process totals of persistent-compile-cache traffic — the
#: cross-process warm-start probes (bench cold/warm child, tier-1 test)
#: read these without having to enable the telemetry recorder first
_cc_stats = {"hits": 0, "misses": 0}
_cc_lock = threading.Lock()
_cc_tls = threading.local()


def compile_cache_stats() -> dict:
    """Process totals of persistent-compile-cache hits/misses (counted
    since :func:`install_compile_counter`; independent of telemetry)."""
    with _cc_lock:
        return dict(_cc_stats)


#: ungated process totals of jit traces (python-cache misses) and their
#: seconds, overall and by traced function name
_trace_totals = {"traces": 0, "trace_s": 0.0}
_trace_by_fun: dict = {}
#: function names that keep their own ``fun`` label on
#: ``amgx_jit_trace_total``; later names count under "other"
_TRACE_FUN_LABELS = 32
_trace_fun_labels: set = set()


def trace_stats() -> dict:
    """Process totals of jit traces, counted since
    :func:`install_compile_counter` whether telemetry is on or not:
    ``{"traces": n, "trace_s": seconds, "by_fun": {name: {"traces": n,
    "trace_s": seconds}}}``, ``by_fun`` ordered by traces, most first.

    A jit called while another traces is traced inside it, so seconds
    are counted once: ``trace_s`` is the wall time in outermost traces,
    and a function's ``trace_s`` its own time, without the traces nested
    in it (the functions' seconds sum to the total)."""
    with _cc_lock:
        by_fun = sorted(_trace_by_fun.items(),
                        key=lambda kv: (-kv[1][0], -kv[1][1]))
        return {"traces": _trace_totals["traces"],
                "trace_s": _trace_totals["trace_s"],
                "by_fun": {k: {"traces": n, "trace_s": sec}
                           for k, (n, sec) in by_fun}}


def _trace_started():
    """A trace began on this thread (JAX reports its start time as a
    scalar): open a frame that its nested traces charge."""
    stack = getattr(_cc_tls, "traces", None)
    if stack is None:
        stack = _cc_tls.traces = []
    stack.append(0.0)


def _count_trace(fun: str, duration: float) -> str:
    """Add one finished trace to the process totals; returns the
    function's ``fun`` label."""
    stack = getattr(_cc_tls, "traces", None)
    nested = stack.pop() if stack else 0.0
    if stack:
        stack[-1] += duration
    with _cc_lock:
        _trace_totals["traces"] += 1
        if not stack:
            _trace_totals["trace_s"] += duration
        c = _trace_by_fun.setdefault(fun, [0, 0.0])
        c[0] += 1
        c[1] += max(duration - nested, 0.0)
        if fun not in _trace_fun_labels \
                and len(_trace_fun_labels) < _TRACE_FUN_LABELS:
            _trace_fun_labels.add(fun)
        return fun if fun in _trace_fun_labels else "other"


def thread_cache_hits() -> int:
    """Persistent-cache hits observed on THIS thread (monotonic) —
    compile events fire on the compiling thread, so a delta across a
    ``lower().compile()`` call answers "was MY compile served from the
    cache" immune to concurrent compiles on other threads."""
    return getattr(_cc_tls, "hits_seen", 0)


def install_compile_counter() -> bool:
    """Register the jit cache-miss + persistent-cache listeners
    (idempotent); returns True when listeners are in place.  Counts land
    in :func:`trace_stats` and, with telemetry on, in
    ``amgx_jit_trace_total{fun}`` / ``amgx_jit_compile_total`` /
    ``amgx_compile_cache_{hits,misses}_total`` and compile durations in
    the ``amgx_jit_compile_seconds`` histogram."""
    global _compile_listener_installed
    if _compile_listener_installed:
        return True

    def _on_event(event, **kwargs):
        try:
            if event == _CACHE_HIT_EVENT:
                with _cc_lock:
                    _cc_stats["hits"] += 1
                # flag the thread: the backend-compile duration event
                # that follows this hit is an executable LOAD, not a
                # compile (see module docstring)
                _cc_tls.hit = True
                # never-consumed per-thread total: lets a caller detect
                # post hoc that a compile IT ran was served from the
                # cache (thread_cache_hits; the flag above is consumed
                # by the duration listener)
                _cc_tls.hits_seen = getattr(_cc_tls, "hits_seen", 0) + 1
            elif event == _CACHE_MISS_EVENT:
                with _cc_lock:
                    _cc_stats["misses"] += 1
            else:
                return
            from ..telemetry import metrics, recorder
            if not recorder.is_enabled():
                return
            name = ("amgx_compile_cache_hits_total"
                    if event == _CACHE_HIT_EVENT
                    else "amgx_compile_cache_misses_total")
            metrics.counter_inc(name, layer="xla")
        except Exception:   # a metrics bug must never break compilation
            pass

    def _on_duration(event, duration, **kwargs):
        try:
            cache_hit = False
            fun = None
            if event == _COMPILE_EVENT:
                cache_hit = getattr(_cc_tls, "hit", False)
                _cc_tls.hit = False
            elif event == _TRACE_EVENT:
                fun = _count_trace(str(kwargs.get("fun_name", "?")),
                                   float(duration))
            from ..telemetry import metrics, recorder
            if not recorder.is_enabled():
                return
            if event == _TRACE_EVENT:
                metrics.counter_inc("amgx_jit_trace_total", fun=fun)
            elif event == _COMPILE_EVENT:
                if not cache_hit:
                    metrics.counter_inc("amgx_jit_compile_total")
                    metrics.hist_observe("amgx_jit_compile_seconds",
                                         float(duration))
            else:
                return
            # setup attribution (telemetry/setup_profile.py): the
            # duration lands on the innermost open setup phase of the
            # firing thread — compiles run synchronously on the thread
            # that triggered them, so this answers "which setup phase
            # paid that compile" exactly.  A cache-hit load still
            # forwards (it is wall time the phase spent in the compile
            # pipeline), it just isn't a recompile.
            from ..telemetry import setup_profile
            setup_profile.note_duration(event == _COMPILE_EVENT,
                                        float(duration))
        except Exception:   # a metrics bug must never break compilation
            pass

    def _on_scalar(event, value, **kwargs):
        if event == _TRACE_EVENT:
            _trace_started()

    try:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_scalar_listener(_on_scalar)
    except Exception:    # pragma: no cover - depends on the jax version
        return False
    _compile_listener_installed = True
    return True


# ------------------------------------------------------ warm-start layer
#: the fixed in-checkout cache directory (gitignored); a stable path is
#: part of the cache's usefulness — a directory that moves never hits
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_cache_notes: set = set()


def _note_once(msg: str):
    if msg not in _cache_notes:
        _cache_notes.add(msg)
        print(f"[amgx_tpu] {msg}", file=sys.stderr)


def compile_cache_dir() -> Optional[str]:
    """Where JAX's persistent compilation cache lives — decided here and
    nowhere else:

    * ``JAX_COMPILATION_CACHE_DIR``, when set, is used as given (the
      caller places the cache from outside);
    * ``AMGX_TPU_COMPILE_CACHE=0`` turns the cache off;
    * otherwise the fixed in-checkout ``.jax_cache/`` — its ``cpu/``
      subdirectory for processes held to the CPU (test runs), so host
      executables never share a directory with the chip's.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if os.environ.get("AMGX_TPU_COMPILE_CACHE") == "0":
        return None
    import jax
    cpu = (jax.config.jax_platforms or
           os.environ.get("JAX_PLATFORMS", "")) == "cpu"
    return str(CHECKOUT_CACHE_DIR / "cpu" if cpu else CHECKOUT_CACHE_DIR)


def note_ignored_cache_knob(value: str):
    """The ``compile_cache_dir`` config knob places nothing: log once
    (AMGConfig.set calls this for a non-empty value)."""
    _note_once(f"the compile_cache_dir knob={value!r} ignored: the "
               f"persistent compile cache is {compile_cache_dir()!r} "
               "(set JAX_COMPILATION_CACHE_DIR to place it)")


def configure_compilation_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return it (None when off).  Runs at
    ``import amgx_tpu``; an ``AMGX_TPU_COMPILE_CACHE`` path no longer
    places the cache — it is logged once as ignored.

    Size/time floors are zeroed whenever a cache is on — AMG setup
    compiles many small-but-numerous executables whose aggregate, not
    individual, cost is the cold-start problem.  Safe to call after
    compiles already ran: the initialized-once cache singleton is reset
    when the directory changes."""
    import jax
    cache_dir = compile_cache_dir()
    env_path = os.environ.get("AMGX_TPU_COMPILE_CACHE", "")
    if env_path and env_path != "0" and env_path != cache_dir:
        _note_once(f"AMGX_TPU_COMPILE_CACHE={env_path!r} ignored: the "
                   f"persistent compile cache is {cache_dir!r} (set "
                   "JAX_COMPILATION_CACHE_DIR to place it)")
    if cache_dir is None:
        return None
    changed = jax.config.jax_compilation_cache_dir != cache_dir
    if changed:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    if jax.config.jax_persistent_cache_min_compile_time_secs != 0.0:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
    if jax.config.jax_persistent_cache_min_entry_size_bytes != 0:
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if changed:
        from jax._src import compilation_cache as _cc
        if _cc.is_initialized():
            _cc.reset_cache()
    install_compile_counter()
    return cache_dir


def backend_fingerprint() -> str:
    """Identity of the executable-compatibility domain: platform +
    device kind + device count.  Part of every AOT-store key — an
    executable serialized for one domain never deserializes into
    another (jax/jaxlib VERSIONS are deliberately meta-checked instead
    of key-mixed, so an upgrade surfaces as a loud
    ``compile_cache_fallback`` rather than a silent miss)."""
    import jax
    try:
        devs = jax.devices()
        kind = devs[0].device_kind if devs else "?"
        return f"{jax.default_backend()}:{kind}:{len(devs)}"
    except Exception:       # pragma: no cover - backend init failure
        return "unknown"


def runtime_versions() -> dict:
    """The version tuple an AOT entry was built under (checked at load;
    a mismatch falls back to a normal compile)."""
    import jax
    try:
        import jaxlib
        jl = getattr(jaxlib, "__version__", "?")
    except Exception:       # pragma: no cover
        jl = "?"
    return {"jax": jax.__version__, "jaxlib": jl}


def aval_signature(args) -> str:
    """Stable digest input of an argument pytree's shapes/dtypes +
    structure — what decides whether one compiled executable can serve a
    call (all values ride as arguments in this codebase, so the aval
    signature IS the executable's shape identity)."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(args)
    parts = [str(treedef)]
    for l in leaves:
        dt = getattr(l, "dtype", None)
        sh = getattr(l, "shape", None)
        if dt is None or sh is None:
            import numpy as np
            a = np.asarray(l)
            dt, sh = a.dtype, a.shape
        parts.append(f"{dt}{tuple(sh)}")
    return ";".join(parts)


def compile_uncached(jit_fn, args):
    """``jit_fn.lower(*args).compile()`` with the XLA persistent cache
    scoped OFF — producing an executable that is safe to serialize.

    Why: on XLA CPU (measured on the pinned jax), serializing an
    executable that was itself LOADED from the persistent compilation
    cache yields a blob whose JIT-registered kernel symbols are missing
    — every later ``deserialize_executable`` fails with "Symbols not
    found", in any process.  An AOT-store entry must therefore come
    from a genuine compile; the one-time extra compile (only when the
    XLA cache is warm but the AOT store is cold) buys a permanently
    loadable entry.  The config scope is thread-local, so concurrent
    compiles on other threads keep their caching."""
    from jax._src import compilation_cache as _cc
    from jax._src.config import enable_compilation_cache
    # one uncached compile at a time: the reset/compile/reset dance
    # manipulates jax's process-global check-once singleton, so two
    # concurrent AOT compiles would race each other's resets.  A jit
    # on an UNRELATED thread can still flip the global verdict back to
    # cached mid-compile — callers detect that case with a
    # thread_cache_hits() delta and skip persisting (serve/aot.py).
    with _UNCACHED_LOCK:
        try:
            with enable_compilation_cache(False):
                # the used-or-not verdict is a check-once singleton:
                # once any compile ran with the cache on, the scoped
                # disable above is ignored — reset forces a re-check,
                # which sees the disabled scope and compiles for real
                _cc.reset_cache()
                return jit_fn.lower(*args).compile()
        finally:
            # ...and a second reset lets the NEXT normal compile
            # re-enable caching (the verdict would otherwise stick at
            # False)
            _cc.reset_cache()


_UNCACHED_LOCK = threading.Lock()


def serialize_compiled(compiled) -> bytes:
    """One self-contained blob for a ``jax.stages.Compiled`` —
    (payload, in_tree, out_tree, device ids) pickled together
    (PyTreeDefs pickle on the pinned jax; the payload is XLA's own
    serialized executable)."""
    from jax.experimental.serialize_executable import serialize
    payload, in_tree, out_tree = serialize(compiled)
    device_ids = [d.id for d in
                  compiled.runtime_executable().local_devices()]
    return pickle.dumps((payload, in_tree, out_tree, device_ids),
                        protocol=pickle.HIGHEST_PROTOCOL)


def deserialize_compiled(blob: bytes):
    """Rehydrate a :func:`serialize_compiled` blob into a callable
    executable bound to the CURRENT backend, on the devices it was
    compiled for (JAX 0.9 otherwise loads it onto every visible device
    and the call fails with a shard-count mismatch).  Raises on any
    incompatibility — callers treat that as a cache fallback."""
    import jax
    from jax.experimental.serialize_executable import \
        deserialize_and_load
    payload, in_tree, out_tree, device_ids = pickle.loads(blob)
    by_id = {d.id: d for d in jax.devices()}
    devices = [by_id[i] for i in device_ids]
    return deserialize_and_load(payload, in_tree, out_tree,
                                execution_devices=devices)
