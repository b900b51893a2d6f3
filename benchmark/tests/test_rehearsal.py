"""A CPU rehearsal of every cell at 20,000 rows: the command runs end to
end, its last line parses, has the contract's keys, names the platform it ran
on and carries no device metric. Run by hand: pytest benchmark/tests"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def run(*args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, *BENCH["command"][1:], *args], cwd=ROOT,
                          env=env, capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal(cell, trace):
    p = run("--workload", cell, "--seed", str(2**31 + 7), "--seconds", "2",
            "--trace", str(trace), "--rehearse", "--rows", "20000")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1
    assert line["metrics"] == {}  # a CPU run writes no device metric
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert line["rehearsal"]["passes"] > 0
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]
    # every number compared stands beside its limit at the end of stderr too
    tail = p.stderr.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and "limit" in t for t in tail)


def test_no_chip_no_result():
    p = run("--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "does not fall back" in p.stderr
