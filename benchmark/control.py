"""The control of a cell's comparison: the plain reference put in the
program's place and computed one precision below what the configuration
states, then judged by the same comparison as a run.

The configurations state answers to an f64 relative residual (their
tolerance) from an f32 device pack with f64 refinement.  The step that would tempt
a later change is to drop the f64 rung and solve in f32 alone, so the
control solves the cell's own systems (same seed, same right-hand sides
and value sets, the cell's own size) with a plain conjugate gradient in
float32 throughout, to the configuration's tolerance on its own
residual, and hands its answers to ``run.compare``.  The comparison must
find them not correct.  The package has no f32-only path of its own
that runs: a solve below the f32 floor without the f64 rung is refused.

    python3 benchmark/control.py --workload <cell> --seed <n> [--solves K]

Runs on the host's CPU alone (no chip needed); prints the numbers
compared with their limits, and a JSON line with ``correct``.
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import spec  # noqa: E402


def cg32(A, b, tol: float, max_iters: int):
    """Plain conjugate gradient in float32 from x = 0, stopped when its
    own residual falls to ``tol`` times the first."""
    import numpy as np
    A = A.astype(np.float32)
    b = b.astype(np.float32)
    x = np.zeros_like(b)
    r = b.copy()
    p = r.copy()
    rr = np.float32(r @ r)
    stop = np.float32(tol) ** 2 * rr
    for it in range(max_iters):
        if rr <= stop:
            break
        q = A @ p
        alpha = rr / np.float32(p @ q)
        x += alpha * p
        r -= alpha * q
        rr_new = np.float32(r @ r)
        p = r + (rr_new / rr) * p
        rr = rr_new
    return x, it


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--solves", type=int, default=2)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the configuration's rehearsal size")
    args = ap.parse_args(argv)
    bench = spec.load()
    cell = spec.cell(bench, args.workload)
    conf = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    systems = spec.system(conf["system"])
    n = int(conf["rehearse"]["n"] if args.rehearse_cpu else conf["n"])
    system = systems.build(conf, mix, args.seed, n)
    run = spec.local("run")
    tol = float(conf["tolerance"])
    samples, records = [], []
    for k in range(args.solves):
        rec = {"rhs": k % len(system.rhs)}
        j = None
        if system.values is not None:
            j = rec["values"] = k % len(system.values)
        x, its = cg32(system.host_csr(j), system.rhs[rec["rhs"]], tol,
                      max_iters=20 * n)
        rec.update(iterations=its, ok=True)
        records.append(rec)
        samples.append((k, x, rec))
    compared = run.compare(systems, system, samples, records, tol)
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    print(json.dumps({"control": "reference cg in float32",
                      "workload": args.workload, "seed": args.seed,
                      "n": n, "iterations": [r["iterations"]
                                             for r in records],
                      "correct": correct, "compared": compared}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
