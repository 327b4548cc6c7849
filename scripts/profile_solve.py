#!/usr/bin/env python
"""Profile one warm solve of a 7-point Poisson cell and print where its
device time and its set-up's traces go.

    python scripts/profile_solve.py --config benchmark/configs/poisson7-agg.json \
        --seed 1 --out chiprun_out/profile [--rehearse-cpu]

Reads the cell's configuration file (``solver_config``, ``n``,
``dtype``, ``device_generator``), generates the operator on the device,
sets the solver up, runs one warm solve, then records one more solve
with ``jax.profiler`` into ``--out`` (the benchmark's profiler options:
level-1 host events, no Python tracer).  The last line of standard
output is JSON: the device, set-up and solve seconds, the traces of the
set-up by function (``utils/jaxcompat.trace_stats``), and the device
anatomy of the capture (``telemetry/deviceprof.measure_anatomy``: per
level and component, cycle shares, unscoped ops by source, idle time
by the program's host span).  ``--rehearse-cpu`` runs at the
configuration's rehearsal size on the CPU, where the capture has no
device plane.
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    with open(args.config) as f:
        conf = json.load(f)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["AMGX_PALLAS_INTERPRET"] = "1"
    n = int(conf["rehearse"]["n"] if args.rehearse_cpu else conf["n"])

    import jax
    import numpy as np

    import amgx_tpu as amgx
    import amgx_tpu.io as aio
    from amgx_tpu.telemetry import deviceprof
    from amgx_tpu.utils.jaxcompat import trace_stats

    dtype = np.dtype(conf["dtype"])
    m = getattr(aio, conf["device_generator"])(n, n, n, device_dtype=dtype)
    slv = amgx.create_solver(amgx.AMGConfig(conf["solver_config"]))
    slv.setup(m)
    rng = np.random.default_rng(args.seed)
    b = [rng.standard_normal(n ** 3) for _ in range(2)]
    res = slv.solve(b[0], zero_initial_guess=True)
    np.asarray(res.x)
    setup_s = time.perf_counter() - T_START
    traces = trace_stats()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(args.out, profiler_options=opts)
    t0 = time.perf_counter()
    res = slv.solve(b[1], zero_initial_guess=True)
    np.asarray(res.x)
    solve_s = time.perf_counter() - t0
    jax.profiler.stop_trace()

    t0 = time.perf_counter()
    anatomy = deviceprof.measure_anatomy(args.out)
    read_s = time.perf_counter() - t0
    by_fun = traces.pop("by_fun")
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "n": n, "setup_s": setup_s, "solve_s": solve_s,
           "iterations": int(res.iterations), "read_s": read_s,
           "setup_traces": traces,
           "top_by_traces": list(by_fun.items())[:5],
           "top_by_trace_s": sorted(by_fun.items(),
                                    key=lambda kv: -kv[1]["trace_s"])[:5],
           "anatomy": anatomy}
    with open(os.path.join(args.out, "profile.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
