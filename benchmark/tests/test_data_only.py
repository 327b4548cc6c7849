"""A cell, a traffic mix, a configuration and a per-layer metric are
added as files and entries alone: in a copy of the benchmark, new files
and new ``BENCHMARK.json`` entries make a new cell that the harness
lists and runs, with no file of the harness edited."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _digest(root: Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "benchmark").rglob("*.py"))}


def test_new_cell_from_files_only(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    os.symlink(ROOT / "amgx_tpu", tmp_path / "amgx_tpu")
    before = _digest(tmp_path)

    b = tmp_path / "benchmark"
    conf = json.loads((b / "configs" / "poisson7-agg.json").read_text())
    conf.update(name="poisson7-agg-96", n=96, rehearse={"n": 8})
    (b / "configs" / "poisson7-agg-96.json").write_text(json.dumps(conf))
    (b / "traffic" / "solve-pool4.json").write_text(json.dumps(
        {"name": "solve-pool4", "why": "four right-hand sides",
         "rhs_pool": 4, "coefficients": None, "value_pool": 0}))
    (b / "metrics" / "krylov.iterations_max.solve.py").write_text(
        "def read(ctx):\n"
        "    its = [r['iterations'] for r in ctx.records"
        " if 'iterations' in r]\n"
        "    return max(its) if its else None\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "poisson7-agg-96", "source": conf["source"],
        "file": "benchmark/configs/poisson7-agg-96.json",
        "reduced": ["n"], "why": "a smaller grid"})
    bench["workloads"].append({
        "name": "poisson7-agg-96.solve-pool4", "config": "poisson7-agg-96",
        "traffic": "solve-pool4", "chips": 1, "why": "a test cell"})
    bench["per_layer"].append({
        "name": "krylov.iterations_max.solve", "unit": "iters",
        "better": "lower", "source": "program_counter", "layer": "Krylov",
        "moves": "solve_s", "workloads": ["poisson7-agg-96.solve-pool4"]})
    bench["end_to_end"][0]["workloads"].append("poisson7-agg-96.solve-pool4")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    run = [sys.executable, str(b / "run.py")]
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    listed = subprocess.run(run + ["--list"], cwd=tmp_path, env=env,
                            capture_output=True, text=True, timeout=60)
    assert "poisson7-agg-96.solve-pool4" in listed.stdout
    p = subprocess.run(run + ["--workload", "poisson7-agg-96.solve-pool4",
                              "--seed", "2147483647", "--seconds", "1",
                              "--trace", "1", "--rehearse-cpu"],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    assert "krylov.iterations_max.solve" in out["rehearsal"]["metrics_read"]
    after = _digest(tmp_path)
    assert all(after[p] == h for p, h in before.items())
