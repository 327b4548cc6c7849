"""Run one cell of the benchmark in its CPU rehearsal with the timed path
broken underneath the harness, to show that the comparison catches it.

    python3 benchmark/tests/fault_run.py <fault> <chips> <run.py args...>

Faults (each patches the solver package before the harness starts):

``answer``   one entry of every solution altered where solve() returns it
``half``     half of every solution left out (zeros)
``replace``  replace_coefficients leaves the values unchanged
``resetup``  resetup returns with the solver's state unchanged
``exchange`` the halo exchange between chips left out (zeros arrive)
``none``     nothing broken: the same run, for comparison
"""
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def _rehearsal_env(chips: int):
    os.environ["JAX_PLATFORMS"] = "cpu"
    if chips == 1:
        os.environ["AMGX_PALLAS_INTERPRET"] = "1"
    else:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")


def install(fault: str):
    import jax.numpy as jnp

    from amgx_tpu.core.matrix import Matrix
    from amgx_tpu.distributed import matrix as dm
    from amgx_tpu.solvers.base import Solver

    if fault in ("answer", "half"):
        solve = Solver.solve

        def broken(self, *a, **kw):
            res = solve(self, *a, **kw)
            x = jnp.asarray(res.x)
            if fault == "answer":
                res.x = x.at[x.shape[0] // 2].add(1e-3)
            else:
                res.x = x.at[x.shape[0] // 2:].set(0)
            return res
        Solver.solve = broken
    elif fault == "replace":
        Matrix.replace_coefficients = lambda self, data: self
    elif fault == "resetup":
        Solver.resetup = lambda self, A: self
    elif fault == "exchange":
        def no_exchange(buf, dists, axis, n_parts):
            return jnp.zeros((len(dists) * buf.shape[0],) + buf.shape[1:],
                             buf.dtype)
        dm._exchange = no_exchange
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


def main(argv):
    fault, chips, args = argv[0], int(argv[1]), argv[2:]
    _rehearsal_env(chips)
    sys.path[:0] = [str(BENCH.parent), str(BENCH)]
    install(fault)
    import run
    return run.main(args + ["--rehearse-cpu"])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
