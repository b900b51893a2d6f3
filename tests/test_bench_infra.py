"""Bench infrastructure guards — a chip call is budgeted, so bench.py, the
artifact gate, chip_smoke.py and the compile-cache placement must not
bitrot between calls. Cheap structural checks run in the default tier; one
real phase runs in the slow tier."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_extra=None, timeout=600):
    env = dict(os.environ, **(env_extra or {}))
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=ROOT,
    )


def test_latest_bench_ok_gate(monkeypatch):
    """The gate's phase list must track bench._PHASES (minus headline)."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "tools"))
    import latest_bench_ok as gate

    import bench

    assert set(gate.POST_HEADLINE) == set(bench._PHASES) - {"headline"}


@pytest.mark.parametrize(
    "payload,want_rc",
    [
        ({"value": 2.5, "glm_1m": {"seconds": 1},
          "metrics_registry": {"tree_dispatches_total": 4}}, 0),
        ({"value": 2.5, "glm_1m_error": "boom",
          "metrics_registry": {"tree_dispatches_total": 4}}, 1),  # r4 cascade
        # headline + phases but NO registry-snapshot block: produced by a
        # pre-observability bench — must not stand the watcher down
        ({"value": 2.5, "glm_1m": {"seconds": 1}}, 1),
        ({"value": 2.5, "glm_1m": {"seconds": 1}, "metrics_registry": {}}, 1),
        ({"value": 0.0, "error": "init hung"}, 1),
        ({}, 1),
    ],
)
def test_latest_bench_ok_cases(tmp_path, payload, want_rc):
    # run against a scratch dir via a copied script (the tool globs its
    # parent dir, so exercise it with a fabricated artifact set)
    import shutil

    from datetime import datetime, timezone

    tool = os.path.join(ROOT, "tools", "latest_bench_ok.py")
    scratch_tools = tmp_path / "tools"
    scratch_tools.mkdir()
    shutil.copy(tool, scratch_tools / "latest_bench_ok.py")
    # recency comes from the UTC stamp in the FILENAME (mtime is re-stamped
    # by git checkouts and proves nothing)
    stamp = datetime.now(timezone.utc).strftime("%Y%m%dT%H%M%SZ")
    (tmp_path / f"BENCH_builder_{stamp}.json").write_text(
        json.dumps(payload) + "\n"
    )
    # an OLD full artifact must never qualify, whatever its mtime
    (tmp_path / "BENCH_builder_20200101T000000Z.json").write_text(
        json.dumps({"value": 9.9, "glm_1m": {"seconds": 1}}) + "\n"
    )
    r = subprocess.run(
        [sys.executable, str(scratch_tools / "latest_bench_ok.py")],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == want_rc, r.stdout + r.stderr


def test_latest_bench_ok_tolerates_missing_and_garbage(tmp_path):
    """Missing or torn bench files must yield a clean message + rc 1, never
    a traceback (the watcher parses this output)."""
    import shutil

    from datetime import datetime, timezone

    tool = os.path.join(ROOT, "tools", "latest_bench_ok.py")
    scratch_tools = tmp_path / "tools"
    scratch_tools.mkdir()
    shutil.copy(tool, scratch_tools / "latest_bench_ok.py")

    def run():
        return subprocess.run(
            [sys.executable, str(scratch_tools / "latest_bench_ok.py")],
            capture_output=True, text=True, timeout=60,
        )

    # no artifacts at all
    r = run()
    assert r.returncode == 1 and "Traceback" not in r.stderr, r.stderr
    assert "no recent BENCH_builder artifacts" in r.stdout
    # a recent artifact that is NOT json
    stamp = datetime.now(timezone.utc).strftime("%Y%m%dT%H%M%SZ")
    (tmp_path / f"BENCH_builder_{stamp}.json").write_text("NOT { JSON\n")
    r = run()
    assert r.returncode == 1 and "Traceback" not in r.stderr, r.stderr
    assert "unparseable" in r.stdout


def test_knob_docs_check_gate():
    """Every H2O3_TPU_* knob in config.py must be documented under docs/
    (tools/knob_docs_check.py), and the gate must actually fail on an
    undocumented knob (the --extra self-test)."""
    r = _run(["tools/knob_docs_check.py"], timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    r = _run(["tools/knob_docs_check.py",
              "--extra", "H2O3_TPU_NOT_A_REAL_KNOB"], timeout=120)
    assert r.returncode == 1
    assert "H2O3_TPU_NOT_A_REAL_KNOB" in r.stdout


def test_bench_phases_registry():
    import bench

    # every phase has a runner and a positive budget; headline first (the
    # driver contract requires its fields even on failure)
    names = list(bench._PHASES)
    assert names[0] == "headline"
    for name, (fn, budget) in bench._PHASES.items():
        assert callable(fn) and budget > 0, name
    assert bench.BASELINE_TREES_PER_SEC > 1.0  # measured, not the old 1.0


@pytest.mark.slow
def test_glm_phase_emits_valid_json():
    """One real phase end-to-end in a fresh subprocess at 1% scale — the
    exact invocation shape the bench parent uses."""
    r = _run(
        ["bench.py", "--phase", "glm_1m"],
        env_extra={
            "H2O3_TPU_BENCH_SCALE": "0.01",
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        },
        timeout=500,
    )
    assert r.stdout.strip(), f"no stdout (rc={r.returncode}):\n{r.stderr[-2000:]}"
    line = r.stdout.strip().splitlines()[-1]
    out = json.loads(line)
    assert "error" not in out, out
    assert out["rows"] >= 10_000 and "auc" in out


def test_bench_knows_no_peak_for_cpu():
    """A device not in the peak table is an error, not a default: a CPU run
    must never produce a number under a device metric's name."""
    import bench

    assert not any("cpu" in k for k in bench._PEAK_FLOPS)
    assert bench._peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(RuntimeError, match="knows no peak"):
        bench._peak_flops("cpu")


def test_bench_failed_phase_exits_nonzero(monkeypatch, capsys):
    """A failed phase makes the run exit non-zero while the other phases'
    results are still printed (no error-JSON-and-exit-0)."""
    import bench

    def fake(phase, budget):
        if phase == "glm_1m":
            return {"error": "RuntimeError('boom')", "traceback": "tb"}
        return {"value": 1.0} if phase == "headline" else {"seconds": 1}

    monkeypatch.setattr(bench, "_run_phase_subprocess", fake)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    assert bench.main() == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["failed_phases"] == ["glm_1m"]
    assert out["glm_1m_error"] == "RuntimeError('boom')"
    assert out["value"] == 1.0 and out["dl_100k"] == {"seconds": 1}
    monkeypatch.setattr(bench, "_run_phase_subprocess",
                        lambda phase, budget: {"seconds": 1})
    assert bench.main() == 0


def test_bench_child_phase_error_exits_nonzero(monkeypatch, capsys):
    """The phase boundary reports the failure and returns 1 (it used to
    print the error JSON and exit 0)."""
    import bench
    import h2o3_tpu

    monkeypatch.setattr(h2o3_tpu, "init", lambda **kw: {})
    assert bench._child_main("no_such_phase") == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "no_such_phase" in out["error"] and out["traceback"]
