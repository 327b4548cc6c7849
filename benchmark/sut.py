"""The system under test, driven only through the solver package's public
API: ``Matrix``, ``create_solver(...).setup/resetup/solve`` and
``Matrix.replace_coefficients`` (AmgX's ``AMGX_matrix_upload_all``,
``AMGX_solver_setup``, ``AMGX_solver_resetup``, ``AMGX_solver_solve``
and ``AMGX_matrix_replace_coefficients``).

This is the one module of the benchmark that imports the package.
"""
from __future__ import annotations

import numpy as np


class Program:
    """One solver on one operator, on the cell's chips.

    The operator is uploaded as the host CSR of the reference's values,
    except where the configuration names a device generator of the
    package (``"upload": "device"``) and the traffic keeps the values
    fixed: then it is generated on the device, as AmgX's own
    ``AMGX_generate_distributed_poisson_7pt`` does.
    """

    def __init__(self, conf: dict, system, chips: int):
        import jax

        import amgx_tpu as amgx
        self._jax = jax
        dtype = np.dtype(conf["dtype"])
        if conf.get("upload") == "device" and system.values is None \
                and chips == 1:
            import amgx_tpu.io as aio
            gen = getattr(aio, conf["device_generator"])
            n = system.n
            m = gen(n, n, n, device_dtype=dtype)
        else:
            m = amgx.Matrix(system.host_csr(
                None if system.values is None else 0))
            m.device_dtype = dtype
            if chips > 1:
                from amgx_tpu.distributed.matrix import make_mesh
                m.set_distribution(make_mesh(chips))
        self.matrix = m
        self.solver = amgx.create_solver(
            amgx.AMGConfig(conf["solver_config"]))
        from amgx_tpu.errors import SolveStatus
        self._success = SolveStatus.SUCCESS

    def _sync(self):
        self._jax.block_until_ready(self._jax.live_arrays())

    def setup(self):
        self.solver.setup(self.matrix)
        self._sync()

    def replace(self, data: np.ndarray):
        self.matrix.replace_coefficients(data)

    def resetup(self):
        self.solver.resetup(self.matrix)
        self._sync()

    def solve(self, b: np.ndarray):
        """Start one solve from a zero initial guess; returns the result,
        whose ``x`` may still be on the device."""
        return self.solver.solve(b, zero_initial_guess=True)

    def fetch(self, res):
        """(x on the host, iterations, whether the solver reports
        success)."""
        return (np.asarray(res.x), int(res.iterations),
                res.status == self._success)

    def memory_peak_bytes(self):
        """Peak bytes in use on the fullest device the solver uses; None
        where the backend keeps no memory statistics."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self._jax.devices()]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None

    def close(self):
        self.solver = None
        self.matrix = None
        import gc
        gc.collect()
