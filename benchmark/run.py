"""The benchmark: one cell, one process, one result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, makes its inputs from the
seed, sets the solver up, warms the cell's one path with one step, then
runs steps back to back for ``--seconds`` (``benchmark/traffic.py``).
After the window it reads the device's memory peak, frees the solver,
and checks a seeded sample of the window's answers against the plain
f64 reference (``benchmark/systems/``).  The last line of standard
output is the JSON result; the numbers compared, each beside its limit,
are the last lines of standard error and the result's last key.

With ``--trace 0`` the result carries the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and the result
carries its per-layer metrics, each computed by its reader
``benchmark/metrics/<name>.py`` from the device planes of the trace or
from the run's own records.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.  ``--rehearse-cpu`` runs the same code at the
configuration's rehearsal size on the CPU (virtual devices for a
multi-chip cell) and prints no metric.  ``--list`` prints the cells.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
# the harness's own modules, then the checkout's solver package
for _p in (BENCH_DIR.parent, BENCH_DIR):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import spec  # noqa: E402

#: answers checked after each window
SAMPLE = 8
#: seconds at the start of a --trace 1 window that the profiler records
TRACE_SECONDS = 5.0
#: fixed in-checkout directories (gitignored)
CACHE_DIR = spec.ROOT / ".jax_cache" / "benchmark"
TRACE_DIR = spec.ROOT / ".bench_trace"


def log(msg: str):
    print(f"[benchmark] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts tracings and backend compilations (a persistent-cache load
    included), with their seconds, and persistent-cache hits: in set-up
    and, apart, while ``on``.  A warm window has none."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.on = False
        self.setup = {"traces": 0, "compiles": 0, "compile_s": 0.0,
                      "cache_hits": 0}
        self.window = dict(self.setup)

    def duration(self, event, duration, **_):
        c = self.window if self.on else self.setup
        if event == self.TRACE:
            c["traces"] += 1
        elif event == self.COMPILE:
            c["compiles"] += 1
            c["compile_s"] += duration

    def event(self, event, **_):
        if event == self.HIT:
            (self.window if self.on else self.setup)["cache_hits"] += 1


class Context:
    """What a metric reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run at the rehearsal size on the CPU; prints "
                         "no metric")
    ap.add_argument("--list", action="store_true",
                    help="print the cells and exit")
    return ap.parse_args(argv)


def _environment(chips: int, rehearse: bool):
    """Placement of the compile cache and, in a rehearsal, the CPU."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if chips == 1:
            os.environ["AMGX_PALLAS_INTERPRET"] = "1"
        else:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}")
    elif os.environ.get("AMGX_PALLAS_INTERPRET", "0") not in ("", "0"):
        raise SystemExit("AMGX_PALLAS_INTERPRET is set: the kernels would "
                         "run in the interpreter, not on the chip")
    cache = CACHE_DIR.with_name(CACHE_DIR.name + "-cpu") if rehearse \
        else CACHE_DIR
    cache.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    # no eviction: a size limit set around the process would make JAX
    # keep access times that entries written without one lack
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def _device(jax, chips: int, rehearse: bool):
    devs = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devs[0].platform != want:
        log(f"no TPU: JAX found {devs[0].platform!r} "
            f"({devs[0].device_kind}); the benchmark runs only on the chip "
            "(--rehearse-cpu for the CPU rehearsal)")
        return None
    if len(devs) < chips:
        log(f"the cell asks for {chips} chips; JAX sees {len(devs)}")
        return None
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))


def compare(systems, system, samples, records, tol: float):
    """The numbers compared, each with its limit: the worst true f64
    relative residual among the sampled answers against the
    configuration's tolerance, and the steps whose solve failed or did
    not report success against none."""
    refs, worst = {}, 0.0
    for _k, x, rec in samples:
        j = rec.get("values")
        if j not in refs:
            refs[j] = system.reference(j)
        r = systems.relres(refs[j], x, system.rhs[rec["rhs"]])
        worst = max(worst, r) if r == r else float("inf")
    failed = sum(1 for r in records if not r.get("ok"))
    return {"relres_max": {"value": worst if samples else float("inf"),
                           "limit": tol},
            "failed_steps": {"value": failed, "limit": 0}}


def _read_metrics(entries, ctx):
    out = {}
    for m in entries:
        v = spec.reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def _breakdown(tr):
    ops = sorted(tr.op_seconds().items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in tr.idle_gaps()[:10]]}


def main(argv=None) -> int:
    args = _args(argv)
    bench = spec.load()
    if args.list:
        for w in bench["workloads"]:
            print(f"{w['name']}\t{w['config']}\t{w['traffic']}\t"
                  f"{w['chips']}")
        return 0
    cell = spec.cell(bench, args.workload)
    conf = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    chips = int(cell["chips"])
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])
    _environment(chips, args.rehearse_cpu)

    import jax

    device = _device(jax, chips, args.rehearse_cpu)
    if device is None:
        return 2
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter.duration)
    jax.monitoring.register_event_listener(counter.event)

    import sut
    import traffic
    systems = spec.system(conf["system"])
    n = int(conf["rehearse"]["n"] if args.rehearse_cpu else conf["n"])
    system = systems.build(conf, mix, args.seed, n)
    t_sys = time.perf_counter()
    prog = sut.Program(conf, system, chips)
    t_prog = time.perf_counter()
    prog.setup()
    t_setup = time.perf_counter()
    rec0 = {}
    traffic.step(prog, system, -1, traffic.spans(False), rec0)
    if not rec0.get("ok"):
        log(f"the warm-up step failed: {rec0}")
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.1f} s: to inputs {t_sys - T_START:.1f}, "
        f"operator {t_prog - t_sys:.1f}, solver setup "
        f"{t_setup - t_prog:.1f}, warm step "
        f"{T_START + setup_s - t_setup:.1f}; {counter.setup}")

    sampler = traffic.Sampler(SAMPLE, systems.rng(args.seed, 2))
    traced = bool(args.trace)
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    counter.on = True
    records, window_s, err = traffic.run_window(
        prog, system, seconds, sampler,
        (str(TRACE_DIR), TRACE_SECONDS) if traced else None)
    counter.on = False
    tr = None
    if traced:
        tr = spec.local("trace").load(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    if err:
        log(f"the window ended on an error: {err}")
    device["memory_peak_bytes"] = prog.memory_peak_bytes()
    prog.close()
    log("steps (iterations, resetup, solve, fetch s): " + " ".join(
        f"{r.get('iterations')},{r.get('resetup_s', 0):.3f},"
        f"{r.get('solve_s', 0):.3f},{r.get('fetch_s', 0):.3f}"
        for r in records[:24]))
    if tr is not None:
        top = sorted(tr.select(lambda o: True), key=lambda o: o.start - o.end)
        for o in top[:3]:
            log(f"longest op {o.end - o.start:.6f} s: {o.text[:300]}")
    print(f"window: steps={len(records)} seconds={window_s!r} "
          f"compiles={counter.window['compiles']} "
          f"traces={counter.window['traces']}",
          flush=True)

    t = time.perf_counter()
    compared = compare(systems, system, sampler.kept, records,
                        float(conf["tolerance"]))
    log(f"reference check of {len(sampler.kept)} answers: "
        f"{time.perf_counter() - t:.1f} s")
    correct = bool(records) and all(
        c["value"] <= c["limit"] for c in compared.values())

    ctx = Context(cell=cell, config=conf, traffic=mix, records=records,
                  window_s=window_s, setup_s=setup_s, trace=tr,
                  device=device, n_rows=system.n_rows)
    entries = (spec.per_layer(bench, cell["name"]) if traced
               else spec.end_to_end(bench, cell["name"]))
    metrics = _read_metrics(entries, ctx)
    result = {"correct": correct, "attempted": len(records),
              "failed": compared["failed_steps"]["value"],
              "metrics": metrics, "device": device}
    if args.rehearse_cpu:
        result["metrics"] = {}
        result["rehearsal"] = {"metrics_read": sorted(metrics)}
    elif tr is not None:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = _breakdown(tr)
    result["compared"] = compared
    for name, c in compared.items():
        log(f"compared {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
