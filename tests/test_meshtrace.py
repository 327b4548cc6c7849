"""Mesh flight recorder (ISSUE 20): clock-aligned cross-rank
rendezvous reconstruction, wait/straggler attribution under the
``compute + wait + unattributed ≡ wall`` honesty invariant, desync
detection, and the surfacing layers (doctor / chrome trace / schema).

Synthetic fixture ground truth (``_mesh_lines``), all in ideal wall
seconds relative to the mesh epoch:

* every rank runs one top-level ``solve`` span over ``[0, 2.0]``;
* ``N_HALO = 6`` ring-1 halo exchanges; on-time ranks BEGIN hop k at
  ``0.2 + 0.25·k`` and sit in the exchange for ``0.1`` s (they arrive
  early and wait inside the collective); the straggler begins
  ``LATE_S = 0.05`` s later and leaves after only ``0.02`` s;
* so per on-time rank, per hop: ``wait = last_arrival − my_arrival =
  LATE_S``, clamped by its 0.1 s span (no clamp); ground-truth wait
  per on-time rank = ``6 × 0.05 = 0.3`` s, straggler wait = 0, and the
  straggler induces ALL mesh wait → straggler score 1.0;
* one fused ``krylov_comm`` event per rank at ``t = 1.9`` (zero
  spread: the reduction itself is not the problem);
* each rank writes records on its OWN perf_counter clock, related to
  wall time by ``wall = t·(1 + drift) + offset`` with per-rank offsets
  (unrelated perf epochs — the thing clock alignment must undo).
"""
import json

import pytest

from amgx_tpu import telemetry
from amgx_tpu.telemetry import doctor, export, meshtrace

pytestmark = [pytest.mark.meshtrace, pytest.mark.telemetry]

N_HALO = 6
LATE_S = 0.05
ON_TIME_WAIT = N_HALO * LATE_S      # 0.3 s ground truth


def _rank_lines(pid, session, offset, drift=0.0, late_s=0.0,
                span_dur=0.1, stop_at=None, clock_samples=(),
                host="host0"):
    """One rank's JSONL session with the fixture timeline above.

    ``offset``/``drift`` define the rank's clock (``wall = t·(1+drift)
    + offset``); records are written in the rank's PERF time, i.e.
    ``t = (wall − offset) / (1 + drift)``.  ``stop_at`` truncates the
    timeline at that wall time (the silent-rank scenario);
    ``clock_samples`` adds re-sample events at the given wall times.
    """
    def perf(wall):
        return (wall - offset) / (1.0 + drift)

    meta = {"kind": "meta", "name": "amgx-telemetry",
            "schema": telemetry.SCHEMA_VERSION, "pid": pid,
            "session": session, "host": host,
            "t_perf": perf(0.0), "t_unix": 0.0, "dropped": 0}
    lines = [json.dumps(meta)]
    recs = []
    recs.append({"kind": "span_begin", "name": "solve", "t": perf(0.0),
                 "tid": 1, "sid": 1, "parent": None, "attrs": {}})
    for k in range(N_HALO):
        t0 = 0.2 + 0.25 * k + late_s
        recs.append({"kind": "span_begin", "name": "exchange_halo",
                     "t": perf(t0), "tid": 1, "sid": 10 + k,
                     "parent": 1, "attrs": {"ring": 1}})
        recs.append({"kind": "span_end", "name": "exchange_halo",
                     "t": perf(t0 + span_dur), "tid": 1, "sid": 10 + k,
                     "dur": perf(t0 + span_dur) - perf(t0)})
    recs.append({"kind": "event", "name": "krylov_comm", "t": perf(1.9),
                 "tid": 1, "attrs": {"solver": "out", "mode": "CA",
                                     "iterations": 10,
                                     "per_iter": {"all_reduce": 1},
                                     "collectives_per_iter": 1,
                                     "fused": True, "n_parts": 3}})
    recs.append({"kind": "counter", "name": "amgx_halo_bytes_total",
                 "t": perf(1.95), "tid": 1, "value": 4096,
                 "labels": {"ring": 1}})
    recs.append({"kind": "span_end", "name": "solve", "t": perf(2.0),
                 "tid": 1, "sid": 1, "dur": perf(2.0) - perf(0.0)})
    for wall in clock_samples:
        recs.append({"kind": "event", "name": "clock_sample",
                     "t": perf(wall), "tid": 1,
                     "attrs": {"t_perf": perf(wall), "t_unix": wall}})
    if stop_at is not None:
        recs = [r for r in recs if r["t"] <= perf(stop_at)]
    recs.sort(key=lambda r: r["t"])
    for i, r in enumerate(recs):
        r["seq"] = i + 1
        lines.append(json.dumps(r))
    return lines


def _mesh_lines(late_s=LATE_S, **kw2):
    """Three ranks with wildly different perf epochs; rank 2 late."""
    return (_rank_lines(100, "aaaaaaaaaaa0", offset=1000.0)
            + _rank_lines(101, "aaaaaaaaaaa1", offset=500.0)
            + _rank_lines(102, "aaaaaaaaaaa2", offset=2000.0,
                          late_s=late_s, span_dur=0.02, **kw2))


# --------------------------------------------------------- clock fitting
def test_fit_clock_recovers_offset_and_drift():
    """fit_clock inverts wall = t·(1+drift) + offset: points sampled
    from a known clock recover both parameters; one point pins
    drift=0 (the meta-only legacy case)."""
    off, drift = 123.456, 2e-5
    pts = [(t, t * (1 + drift) + off) for t in (0.0, 250.0, 500.0,
                                                750.0, 1000.0)]
    o, d, n = meshtrace.fit_clock(pts)
    assert n == 5
    assert o == pytest.approx(off, abs=1e-6)
    assert d == pytest.approx(drift, rel=1e-6)
    o1, d1, n1 = meshtrace.fit_clock(pts[:1])
    assert (o1, d1, n1) == (pytest.approx(off), 0.0, 1)
    assert meshtrace.fit_clock([]) == (0.0, 0.0, 0)


def test_clock_alignment_with_injected_skew_and_drift():
    """Ranks with unrelated perf epochs (offsets 1000/500/2000) and an
    injected 40 ppm drift still align: the per-rank fit recovers the
    drift from the clock_sample re-samples, and the rendezvous waits
    match ground truth despite the skew."""
    lines = (_rank_lines(100, "bbbbbbbbbbb0", offset=1000.0,
                         drift=40e-6, clock_samples=(0.5, 1.0, 1.5))
             + _rank_lines(101, "bbbbbbbbbbb1", offset=500.0)
             + _rank_lines(102, "bbbbbbbbbbb2", offset=2000.0,
                           late_s=LATE_S, span_dur=0.02))
    mesh = meshtrace.analyze(lines)
    assert mesh["measured"] and mesh["n_ranks"] == 3
    r0 = mesh["ranks"][0]
    assert r0["clock_samples"] == 4          # meta + 3 re-samples
    assert r0["clock_drift_ppm"] == pytest.approx(40.0, rel=0.05)
    # skew vs rank 0 = offset difference (same-epoch caveat in README)
    assert mesh["ranks"][1]["clock_skew_s"] == pytest.approx(-500.0,
                                                             abs=1e-3)
    assert r0["wait_s"] == pytest.approx(ON_TIME_WAIT, rel=0.10)


# ----------------------------------------------- rendezvous/wait/score
def test_rendezvous_wait_within_ground_truth():
    """Wait attribution within 10% of the documented arithmetic:
    on-time ranks wait LATE_S at each of the N_HALO hops, the straggler
    waits 0, and the krylov rendezvous (zero spread) adds none."""
    mesh = meshtrace.analyze(_mesh_lines())
    assert mesh["measured"]
    assert mesh["collectives"] == {"halo": N_HALO, "krylov": 1}
    for r in (0, 1):
        assert mesh["ranks"][r]["wait_s"] == pytest.approx(
            ON_TIME_WAIT, rel=0.10)
    assert mesh["ranks"][2]["wait_s"] == pytest.approx(0.0, abs=1e-6)
    assert mesh["wait_by_op"]["halo"] == pytest.approx(
        2 * ON_TIME_WAIT, rel=0.10)
    assert mesh["wait_by_op"].get("krylov", 0.0) == pytest.approx(
        0.0, abs=1e-6)
    # every reconstructed rendezvous saw all three ranks
    assert all(rv["n_ranks"] == 3 for rv in mesh["rendezvous"])
    halos = [rv for rv in mesh["rendezvous"] if rv["op"] == "halo"]
    assert [rv["seq"] for rv in halos] == list(range(N_HALO))
    assert all(rv["last_rank"] == 2 for rv in halos)
    assert all(rv["spread_s"] == pytest.approx(LATE_S, rel=0.10)
               for rv in halos)


def test_straggler_score_and_group_decomposition():
    """Rank 2 arrives last in 100% of halo hops and induces ALL the
    mesh wait → score 1.0; the group decomposition names it and
    carries the mean arrival spread (the compute-skew number)."""
    mesh = meshtrace.analyze(_mesh_lines())
    assert mesh["ranks"][2]["straggler_score"] == pytest.approx(1.0)
    # all N_HALO hops, plus possibly the zero-spread krylov tie
    assert mesh["ranks"][2]["arrived_last"] >= N_HALO
    assert mesh["ranks"][0]["straggler_score"] == pytest.approx(0.0)
    g = mesh["groups"]["halo ring-1"]
    assert g["collectives"] == N_HALO
    assert g["last_rank_mode"] == 2 and g["last_share"] == 1.0
    assert g["mean_spread_s"] == pytest.approx(LATE_S, rel=0.10)
    assert g["wait_s"] == pytest.approx(2 * ON_TIME_WAIT, rel=0.10)


def test_wait_clamped_to_span_duration():
    """A rank cannot be charged more wait than it spent inside the
    collective: with a straggler 0.2 s late but on-time spans only
    0.1 s long, per-hop wait clamps to the 0.1 s span."""
    mesh = meshtrace.analyze(_mesh_lines(late_s=0.2))
    assert mesh["ranks"][0]["wait_s"] == pytest.approx(N_HALO * 0.1,
                                                       rel=0.10)


# ------------------------------------------------------ honesty invariant
def test_honesty_invariant_on_every_rank_and_schema_enforced():
    """compute + wait + unattributed ≡ wall holds on every rank;
    emitted mesh_health events pass the schema, and a tampered one (the
    invariant broken) is rejected — the schema is the enforcement."""
    mesh = meshtrace.analyze(_mesh_lines())
    for r in mesh["ranks"].values():
        assert r["compute_s"] + r["wait_s"] + r["unattributed_s"] == \
            pytest.approx(r["wall_s"], abs=1e-6)
        assert r["wall_s"] == pytest.approx(2.0, rel=0.05)
    prev = telemetry.is_enabled()
    telemetry.enable()
    try:
        with telemetry.capture() as cap:
            meshtrace.emit(mesh)
        health = cap.events("mesh_health")
        assert len(health) == 3
        for ev in health:
            export.validate_record(ev)
            assert ev["attrs"]["measured"] is True
        rvs = cap.events("mesh_rendezvous")
        assert len(rvs) == N_HALO + 1
        for ev in rvs:
            export.validate_record(ev)
        bad = json.loads(json.dumps(health[0]))
        bad["attrs"]["wait_s"] = bad["attrs"]["wait_s"] + 1.0
        with pytest.raises(ValueError, match="invariant"):
            export.validate_record(bad)
        # the metric family landed under per-rank labels
        assert cap.counter_total("amgx_mesh_wait_seconds_total",
                                 rank=0) == pytest.approx(
            mesh["ranks"][0]["wait_s"])
        assert cap.gauge_last("amgx_mesh_straggler_score",
                              rank=2) == pytest.approx(1.0)
    finally:
        if not prev:
            telemetry.disable()


# ------------------------------------------------------------- desync
def test_silent_rank_detected():
    """A rank whose records stop at t=1.0 while peers run to 2.0 goes
    silent for half the mesh span → a silent desync entry plus
    missing_collectives for the hops it never reached."""
    mesh = meshtrace.analyze(_mesh_lines(stop_at=1.0))
    silent = [e for e in mesh["desync"] if e["kind"] == "silent"]
    assert len(silent) == 1 and silent[0]["rank"] == 2
    assert silent[0]["gap_fraction"] == pytest.approx(0.5, abs=0.05)
    miss = [e for e in mesh["desync"]
            if e["kind"] == "missing_collectives"]
    assert any(e["rank"] == 2 and e["op"] == "halo" and
               e["ran"] < e["peers_ran"] for e in miss)


def test_balanced_mesh_has_no_desync():
    mesh = meshtrace.analyze(_mesh_lines(late_s=0.0))
    assert mesh["desync"] == []


# ------------------------------------------------- truncated-tail rescue
def test_truncated_trailing_line_tolerated(tmp_path):
    """A rank killed mid-write leaves a torn last line: read_sessions
    skips it with a mesh_truncated_tail warning event instead of
    raising, and the trace stays joinable."""
    path = tmp_path / "torn.jsonl"
    lines = _mesh_lines()
    path.write_text("\n".join(lines) + "\n"
                    + '{"kind": "event", "name": "krylo')  # torn write
    sessions = export.read_sessions(str(path))
    tails = [r for s in sessions for r in s["records"]
             if r["name"] == "mesh_truncated_tail"]
    assert len(tails) == 1
    export.validate_record(tails[0])
    assert tails[0]["attrs"]["line"] == len(lines) + 1
    mesh = meshtrace.analyze_sessions(sessions)
    assert mesh["measured"] and mesh["truncated_tails"] == 1
    assert any("truncated" in n for n in mesh["notes"])
    # a torn line that is NOT trailing still raises (real corruption)
    bad = tmp_path / "corrupt.jsonl"
    bad.write_text(lines[0] + "\n" + '{"kind": "ev\n'
                   + "\n".join(lines[1:]) + "\n")
    with pytest.raises(ValueError):
        export.read_sessions(str(bad))


# ------------------------------------------------- single-rank honesty
def test_single_rank_trace_degrades_honestly(tmp_path):
    """One rank → no rendezvous to reconstruct: measured=False, zero
    waits, a note saying why — and the doctor stays silent (no Mesh
    health section, no mesh hints)."""
    mesh = meshtrace.analyze(_rank_lines(100, "ccccccccccc0",
                                         offset=1000.0))
    assert mesh["measured"] is False and mesh["n_ranks"] == 1
    assert mesh["rendezvous"] == [] and mesh["total_wait_s"] == 0.0
    assert any("single-rank" in n for n in mesh["notes"])
    prev = telemetry.is_enabled()
    telemetry.enable()
    try:
        with telemetry.capture() as cap:
            meshtrace.emit(mesh)
        for ev in cap.events("mesh_health"):
            export.validate_record(ev)
            assert ev["attrs"]["measured"] is False
    finally:
        if not prev:
            telemetry.disable()
    path = tmp_path / "solo.jsonl"
    path.write_text("\n".join(_rank_lines(100, "ccccccccccc0",
                                          offset=1000.0)) + "\n")
    d = doctor.diagnose([str(path)])
    assert d["mesh"] is None
    out = doctor.render(d)
    assert "Mesh health" not in out
    assert not any("mesh" in h for h in d["hints"])


# ------------------------------------------------------ doctor surfacing
def test_doctor_mesh_section_hints_and_diff(tmp_path):
    """The skewed trace renders a Mesh health rank table and fires the
    straggler hint; the balanced trace stays hint-silent; --diff puts
    the per-rank wait drift in the callouts."""
    skewed = tmp_path / "skewed.jsonl"
    skewed.write_text("\n".join(_mesh_lines()) + "\n")
    balanced = tmp_path / "balanced.jsonl"
    balanced.write_text("\n".join(_mesh_lines(late_s=0.0)) + "\n")

    d = doctor.diagnose([str(skewed)])
    assert d["mesh"] and d["mesh"]["measured"]
    out = doctor.render(d)
    assert "Mesh health" in out
    assert "rank" in out and "straggler" in out
    assert any("mesh straggler: rank 2" in h for h in d["hints"])
    # zero-spread fused reductions must NOT fire the krylov-wait hint
    assert not any("fused" in h and "mesh" in h for h in d["hints"])

    db = doctor.diagnose([str(balanced)])
    assert db["mesh"] and db["mesh"]["measured"]
    assert not any("straggler" in h for h in db["hints"])

    dd = doctor.diff(d, db)
    assert dd["mesh"] is not None
    assert dd["mesh"]["ranks"][0]["a"] == pytest.approx(ON_TIME_WAIT,
                                                        rel=0.10)
    assert any("mesh wait rank 0" in s for s in dd["drifts"])
    assert "mesh wait (A vs B" in doctor.render_diff(dd)


# -------------------------------------------------- chrome trace arrows
def test_chrome_trace_rendezvous_flow_arrows(tmp_path):
    """Multi-rank traces export one track per rank with s/f flow-arrow
    pairs (cat=rendezvous) from each early rank to the last arrival;
    single-rank traces carry none.  The strict validator passes."""
    path = tmp_path / "mesh.jsonl"
    path.write_text("\n".join(_mesh_lines()) + "\n")
    trace = telemetry.chrome_trace(str(path))
    telemetry.validate_chrome_trace(trace)
    flows = [e for e in trace["traceEvents"]
             if e["ph"] in ("s", "f")]
    assert flows and all(e["cat"] == "rendezvous" for e in flows)
    starts = {e["id"] for e in flows if e["ph"] == "s"}
    finishes = {e["id"] for e in flows if e["ph"] == "f"}
    assert starts == finishes            # every arrow is a matched pair
    assert all(e.get("bp") == "e" for e in flows if e["ph"] == "f")
    solo = tmp_path / "solo.jsonl"
    solo.write_text("\n".join(_rank_lines(100, "ddddddddddd0",
                                          offset=0.0)) + "\n")
    trace1 = telemetry.chrome_trace(str(solo))
    telemetry.validate_chrome_trace(trace1)
    assert not [e for e in trace1["traceEvents"]
                if e["ph"] in ("s", "f")]


# ----------------------------------------------------- end-to-end solve
def test_virtual_mesh_solve_reconciles_with_halo_counters(tmp_path):
    """8-part distributed PCG solve streaming a JSONL trace; mirrored
    into two rank identities (the house single-process SPMD pattern),
    the mesh join reconstructs one halo rendezvous per traced dist_spmv
    hop — reconciling against amgx_halo_exchange_total — and the
    honesty invariant holds on every emitted mesh_health event."""
    import numpy as np

    import amgx_tpu as amgx
    from amgx_tpu.distributed.matrix import make_mesh
    from amgx_tpu.io import poisson7pt

    path = str(tmp_path / "mesh8.jsonl")
    A = poisson7pt(8, 8, 8)
    m = amgx.Matrix(A)
    m.set_distribution(make_mesh(8))
    cfg = amgx.AMGConfig(
        "config_version=2, solver(s)=PCG, "
        "s:preconditioner(p)=BLOCK_JACOBI, p:max_iters=2, "
        "s:max_iters=50, s:monitor_residual=1, s:tolerance=1e-8, "
        "s:convergence=RELATIVE_INI, s:telemetry=1, "
        f"s:telemetry_path={path}")
    prev = telemetry.is_enabled()
    # the trace's first flush writes the whole ring: start it empty, or
    # records an earlier test left there (a ring another test shrank
    # may keep a halo span whose counters it evicted) join the session
    telemetry.reset()
    try:
        slv = amgx.create_solver(cfg)
        slv.setup(m)
        res = slv.solve(np.ones(A.shape[0]))
    finally:
        if not prev:
            telemetry.disable()
    assert res.status == amgx.SolveStatus.SUCCESS
    lines = open(path).readlines()
    # mirror the session as a second rank (same pattern as
    # test_telemetry_dist.py — one process IS the virtual mesh)
    meta2 = json.loads(lines[0])
    meta2["pid"] += 1
    meta2["session"] = "feedc0de0002"
    with open(path, "a") as f:
        f.write(json.dumps(meta2) + "\n")
        for l in lines[1:]:
            f.write(l)

    agg = telemetry.aggregate_sessions(path)
    mesh = meshtrace.analyze_sessions(agg["sessions"])
    assert mesh["measured"] and mesh["n_ranks"] == 2
    # reconciliation: every traced dist_spmv hop became one halo
    # rendezvous, so the count equals ONE rank's exchange counter
    # (the aggregate sums both mirrored sessions — halve it)
    exchanges = sum(v for (n, _), v in agg["counters"].items()
                    if n == "amgx_halo_exchange_total")
    assert exchanges > 0 and exchanges % 2 == 0
    assert mesh["collectives"]["halo"] == exchanges // 2
    per_rank = [r["collectives"] for r in mesh["ranks"].values()]
    assert per_rank[0] == per_rank[1] >= mesh["collectives"]["halo"]
    # mirrored timelines → every wait is (near) zero, invariant exact
    assert mesh["total_wait_s"] == pytest.approx(0.0, abs=1e-6)
    prev = telemetry.is_enabled()
    telemetry.enable()
    try:
        with telemetry.capture() as cap:
            meshtrace.emit(mesh)
        for ev in cap.events("mesh_health"):
            export.validate_record(ev)
            a = ev["attrs"]
            assert a["compute_s"] + a["wait_s"] + a["unattributed_s"] \
                == pytest.approx(a["wall_s"], abs=1e-6)
        assert len(cap.events("mesh_rendezvous")) == \
            len(mesh["rendezvous"])
    finally:
        if not prev:
            telemetry.disable()
