"""The one general load loop.  A traffic mix is a data file
(``benchmark/traffic/<mix>.json``) of the parameters it reads:

``rhs_pool``
    distinct seeded right-hand sides, used round-robin;
``coefficients``
    null for a fixed operator; otherwise the per-cell coefficient field
    (``{"kind": "lognormal", "sigma": ...}``) whose ``value_pool``
    seeded value sets the loop cycles through, one per step, each step
    being ``replace_coefficients`` -> ``resetup`` -> solve;
``value_pool``
    how many value sets.

The loop is closed with one caller: the next step starts when the last
one's answer is on the host.  Every step gets a zero initial guess.  The
window ends at the end of the first step that finishes at or after
``seconds``, so every step in it is whole.

The benchmark's host spans (``bench/...``) mark each phase of a step in
the profiler's trace when one is recorded.
"""
from __future__ import annotations

import contextlib
import time


def spans(traced: bool):
    if not traced:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


class Sampler:
    """A uniform sample of up to ``size`` steps' answers, drawn from the
    seed (reservoir sampling), so that the check after the window costs
    the same however many steps the window holds."""

    def __init__(self, size: int, rng):
        self.size, self.rng, self.seen, self.kept = size, rng, 0, []

    def offer(self, item):
        self.seen += 1
        if len(self.kept) < self.size:
            self.kept.append(item)
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.size:
                self.kept[j] = item


def step(prog, system, k: int, span, rec: dict):
    """Step ``k``: new values and re-setup where the mix has them, then
    one solve of right-hand side ``k mod rhs_pool``.  Fills ``rec`` and
    returns the answer on the host."""
    if system.values is not None:
        j = k % len(system.values)
        rec["values"] = j
        t = time.perf_counter()
        with span("bench/replace_coefficients"):
            prog.replace(system.values[j])
        t1 = time.perf_counter()
        with span("bench/resetup"):
            prog.resetup()
        t2 = time.perf_counter()
        rec["replace_s"], rec["resetup_s"] = t1 - t, t2 - t1
    r = k % len(system.rhs)
    rec["rhs"] = r
    t = time.perf_counter()
    with span("bench/solve"):
        res = prog.solve(system.rhs[r])
    t1 = time.perf_counter()
    with span("bench/fetch"):
        x, iters, ok = prog.fetch(res)
    rec["solve_s"], rec["fetch_s"] = t1 - t, time.perf_counter() - t1
    rec["iterations"], rec["ok"] = iters, ok
    return x


def run_window(prog, system, seconds: float, sampler: Sampler,
               profile=None):
    """Steps back to back for ``seconds``.  Returns (records, window
    seconds, error or None); a step that raises ends the window.

    ``profile`` = (directory, seconds): the window is that many seconds
    (at most ``seconds``), all of it recorded by the profiler inside the
    span ``bench/window``.  The device tracer keeps a bounded number of
    events (on a TPU v5e a 128^3 aggregation solve writes ~17,000, and
    about 90 solves fill it), so a longer window would be cut short
    without a mark.  Nothing runs after the trace stops: freeing the
    profiler's buffers changes the host allocator's state, and the host
    re-setup of steps run after it took half the time it took in a
    fresh process.
    """
    traced = profile is not None
    if traced:
        import jax
        directory, traced_s = profile
        seconds = min(seconds, traced_s)
        # the benchmark's spans are level-1 host TraceMe events; the
        # Python tracer and the host's level-2 events would add every
        # call besides, and took a minute to write out on the chip
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(directory, profiler_options=opts)
    span = spans(traced)
    records, err = [], None
    t0 = time.perf_counter()
    with span("bench/window"):
        k = 0
        while True:
            rec = {}
            try:
                x = step(prog, system, k, span, rec)
            except Exception as e:   # the run reports it as a failure
                rec["ok"], rec["error"] = False, f"{type(e).__name__}: {e}"
                records.append(rec)
                err = rec["error"]
                break
            records.append(rec)
            sampler.offer((k, x, rec))
            k += 1
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    if traced:
        jax.profiler.stop_trace()
    return records, window_s, err
