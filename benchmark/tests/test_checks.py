"""The comparison that decides ``correct`` fails where it should.

The control (the reference solved in float32, ``control.py``) and each
fault of the timed path that a cell can have are run at the rehearsal
size on the CPU, through the whole harness, and must read not correct;
the same runs unbroken read correct.

Besides the cells of ``BENCHMARK.json``, the paths that its mixes and
configurations can take but no cell takes yet (a re-setup every step,
a system sharded over four chips) run as cells added to a copy of the
benchmark, so that a later cell on them starts from a tested check.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

#: cells added to the copy: name -> (configuration changes, traffic, chips)
EXTRA = {
    "poisson7-agg.resetup": ({}, "resetup", 1),
    "poisson7-agg-dd4.solve": ({"name": "poisson7-agg-dd4",
                                "upload": "host_csr"}, "solve", 4),
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark holding its cells and the ``EXTRA`` ones."""
    top = tmp_path_factory.mktemp("bench")
    shutil.copytree(BENCH, top / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    os.symlink(ROOT / "amgx_tpu", top / "amgx_tpu")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    base = json.loads((BENCH / "configs" / "poisson7-agg.json").read_text())
    for cell, (change, mix, chips) in EXTRA.items():
        conf = dict(base, **change)
        if conf["name"] != base["name"]:
            path = f"benchmark/configs/{conf['name']}.json"
            (top / path).write_text(json.dumps(conf))
            bench["configs"].append({
                "name": conf["name"], "source": conf["source"],
                "file": path, "reduced": [], "why": "a test cell"})
        bench["workloads"].append({
            "name": cell, "config": conf["name"], "traffic": mix,
            "chips": chips, "why": "a test cell"})
    (top / "BENCHMARK.json").write_text(json.dumps(bench))
    return top


def _last_json(root, cmd, timeout=600):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, *cmd], cwd=root, env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["poisson7-agg.solve",
                                  "poisson7-agg.resetup"])
def test_control_in_float32_is_not_correct(root, cell):
    out = _last_json(root, [
        str(root / "benchmark" / "control.py"), "--workload", cell,
        "--seed", "2147483659", "--solves", "3", "--rehearse-cpu"])
    c = out["compared"]["relres_max"]
    assert out["correct"] is False
    assert c["value"] > 3 * c["limit"]


FAULTS = [
    ("none", 1, "poisson7-agg.solve", True),
    ("answer", 1, "poisson7-agg.solve", False),
    ("half", 1, "poisson7-agg.solve", False),
    ("none", 1, "poisson7-agg.resetup", True),
    ("replace", 1, "poisson7-agg.resetup", False),
    ("resetup", 1, "poisson7-agg.resetup", False),
    ("none", 4, "poisson7-agg-dd4.solve", True),
    ("exchange", 4, "poisson7-agg-dd4.solve", False),
]


@pytest.mark.parametrize("fault,chips,cell,correct", FAULTS)
def test_a_broken_timed_path_is_not_correct(root, fault, chips, cell,
                                            correct):
    out = _last_json(root, [
        str(root / "benchmark" / "tests" / "fault_run.py"), fault,
        str(chips), "--workload", cell, "--seed", "3000000007",
        "--seconds", "1"])
    assert out["correct"] is correct, out["compared"]
    assert out["attempted"] > 0
    assert out["metrics"] == {}            # a rehearsal prints none
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "compared"


def test_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"),
                        "--workload", "poisson7-agg.solve", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip()
