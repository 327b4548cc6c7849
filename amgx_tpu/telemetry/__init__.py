"""Structured solver telemetry.

One structured layer over what the reference scattered across the CPU
profiler tree (``amgx_timer.h``), per-level ``levelProfile`` timers and
the grid-stats table:

* **spans/events** (:mod:`.recorder`): ``span(name, **attrs)`` phase
  markers (which also aggregate into the legacy profiler tree and
  enter a ``jax.profiler.TraceAnnotation`` named ``amgx/<marker>``)
  and ``event(name, **attrs)`` point records, appended to a bounded
  ring buffer;
* **metrics** (:mod:`.metrics`): counters/gauges/histograms under
  stable, versioned names (``METRICS``) — SpMV pack-selection counts,
  jit recompiles, phase durations, hierarchy complexities, per-solve
  iteration/residual gauges;
* **exporters** (:mod:`.export`): JSONL traces (``dump_jsonl`` /
  incremental ``flush_jsonl``), a Prometheus text snapshot
  (``prometheus_text``), the schema validator used by
  ``scripts/telemetry_check.py``, and the multi-process session merger
  (``aggregate_sessions`` — one mesh-wide view of per-rank traces);
* **analysis** (PR 3): :mod:`.costmodel` (static bytes/FLOPs/padding
  descriptors per SpMV pack, roofline fractions), :mod:`.tracefile`
  (Chrome-trace export — view a solve in Perfetto), :mod:`.doctor`
  (``python -m amgx_tpu.telemetry.doctor trace.jsonl`` diagnosis,
  ``--diff`` for two-trace A/B comparison);
* **convergence forensics** (:mod:`.forensics`): per-level cycle
  anatomy (residual norms at the four cut points of every cycle),
  hierarchy quality probes at setup, asymptotic convergence-factor
  estimates — gated by the ``forensics`` config knob;
* **device-time attribution** (PR 17): :mod:`.scopes` (the versioned
  ``amgx/<area>/<name>`` ``jax.named_scope`` contract every
  instrumented kernel carries), :mod:`.proftrace` (the capture
  readers: ``.xplane.pb`` device ops joined to their scopes, and
  chrome-trace JSON), :mod:`.deviceprof` (the profiler-trace
  correlator: per-level / per-pack / per-stage **measured device
  seconds** + measured SpMV bandwidth vs the modelled roofline,
  emitted as the ``device_anatomy`` event and
  ``amgx_device_time_seconds_total{scope}``), and :mod:`.overlap`
  (measured interior/halo overlap riding the same plumbing);
* **HBM ledger** (PR 18): :mod:`.memledger` — device-memory ownership
  attribution under the versioned ``amgx/<owner>/<name>`` taxonomy
  (registry + ``jax.live_arrays`` census + backend ``memory_stats``
  truth, honesty invariant ``accounted + unaccounted ≡ bytes_in_use``),
  ``hbm_snapshot`` sampling and ``oom_postmortem`` bundles — gated by
  the ``memledger`` knob;
* **mesh flight recorder** (PR 20): :mod:`.meshtrace` — clock-aligned
  cross-rank timelines (per-session offset+drift fit over the paired
  ``t_perf``/``t_unix`` samples), collective-rendezvous reconstruction
  (halo hops, fused Krylov reductions, agglomerations matched by
  (op, group, sequence)), per-rank wait/straggler attribution under
  the honesty invariant ``compute + wait + unattributed ≡ wall``
  (schema-enforced ``mesh_health`` events), and silent-rank/desync
  detection — surfaced as ``amgx_mesh_*`` metrics, the doctor's
  "Mesh health" section, Chrome-trace rendezvous flow arrows and
  ``/debug/mesh``;
* **live serving observability**: :mod:`.slo` (time-windowed
  request-outcome reservoir → attainment / error-budget burn rate /
  overload detection) and :mod:`.httpd` (in-process
  ``/metrics`` ``/healthz`` ``/statusz`` ``/debug/trace``
  ``/debug/profile`` endpoint behind the ``metrics_port`` knob).

Everything is **off by default** and compiled down to one attribute
check per instrument; enable globally with :func:`enable`, per config
with the ``telemetry=1`` knob (plus ``telemetry_path`` /
``telemetry_ring_size``), or scoped with :func:`capture` in tests.
"""
from __future__ import annotations

from . import (costmodel, deviceprof, export, forensics, memledger,
               meshtrace, metrics, overlap, proftrace, recorder,
               runstate, scopes, setup_profile, slo, tracefile)
from .export import (aggregate_sessions, dump_jsonl, flush_jsonl,
                     prometheus_text, read_sessions, validate_jsonl,
                     validate_record)
from .metrics import (METRICS, counter_inc, gauge_set, hist_observe,
                      registry)
from .recorder import (SCHEMA_VERSION, Capture, capture, clear, disable,
                       dropped_count, enable, event, is_enabled, records,
                       span)
from .tracefile import (chrome_trace, validate_chrome_trace,
                        write_chrome_trace)

__all__ = [
    "SCHEMA_VERSION", "METRICS", "Capture",
    "enable", "disable", "is_enabled", "capture", "clear", "records",
    "span", "event", "dropped_count",
    "counter_inc", "gauge_set", "hist_observe", "registry",
    "dump_jsonl", "flush_jsonl", "prometheus_text",
    "validate_record", "validate_jsonl",
    "read_sessions", "aggregate_sessions",
    "chrome_trace", "write_chrome_trace", "validate_chrome_trace",
    "costmodel", "forensics", "setup_profile", "runstate",
    "slo", "httpd",
    "proftrace", "scopes", "deviceprof", "overlap", "memledger",
    "meshtrace",
    "reset",
]


def __getattr__(name):
    # httpd is the ONLY lazily-bound submodule: it pulls the stdlib
    # http.server → http.client → email import chain, which every
    # non-serving `import amgx_tpu` would otherwise pay for an endpoint
    # that is off by default (serve/service.py lazy-imports it too)
    if name == "httpd":
        # importlib, not `from . import`: the fromlist resolution calls
        # getattr on this package and would re-enter this hook forever
        import importlib
        mod = importlib.import_module(".httpd", __name__)
        globals()["httpd"] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def reset():
    """Drop buffered records, zero the metrics registry and the
    ring-overflow counter (test/bench isolation helper; recording stays
    in whatever on/off state it was)."""
    recorder.clear()
    recorder.reset_dropped()
    metrics.registry().reset()
    setup_profile.reset()
    memledger.reset()
