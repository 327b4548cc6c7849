"""setup.jit_traces: JAX traces (python-cache misses) in this process,
as the program counts them with its own listener
(``amgx_tpu.utils.jaxcompat.trace_stats``), read after the window.  A
warm window traces nothing, so this is the set-up's count.  None where
the program is not loaded or keeps no such count."""
import sys


def read(ctx):
    jc = sys.modules.get("amgx_tpu.utils.jaxcompat")
    stats = getattr(jc, "trace_stats", None)
    return None if stats is None else stats()["traces"]
