"""setup.jit_trace_s: seconds spent in JAX traces in this process, as
the program times them with its own listener
(``amgx_tpu.utils.jaxcompat.trace_stats``), read after the window; the
set-up's, since a warm window traces nothing.  None where the program
is not loaded or keeps no such count."""
import sys


def read(ctx):
    jc = sys.modules.get("amgx_tpu.utils.jaxcompat")
    stats = getattr(jc, "trace_stats", None)
    return None if stats is None else stats()["trace_s"]
