"""Fake-multiprocess recovery harness (ISSUE 10 satellite; ROADMAP item 2
asks for this explicitly): a subprocess-based TWO-PROCESS cloud pytest
fixture that drives the degraded latch, generation fencing, and supervised
recovery across a real ``jax.distributed`` process boundary.

Reuses the PR-4 bounded capability probe from test_multihost: jaxlib builds
that refuse cross-process CPU collectives (this CI container among them)
auto-skip with the root cause instead of carrying environmental failures as
red — the tests run for real on any host whose jaxlib allows it.
"""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.parse
import urllib.request

import pytest

from test_multihost import _skip_unless_two_process_capable


@pytest.fixture()
def two_process_cloud(tmp_path):
    """Boot a 2-process launch.py cloud (2 CPU devices per process) with a
    synthetic dead-member fault armed on the first replicated command and
    the recovery supervisor enabled. Yields the coordinator's REST base URL;
    tears both processes down (and dumps log tails) afterwards."""
    _skip_unless_two_process_capable()
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord_port = s.getsockname()[1]
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        rest_port = s.getsockname()[1]
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        JAX_PLATFORMS="cpu",
        # the coordinator's first replicated command dies with a
        # coordination-service signature (one-shot) — the degraded-latch
        # driver; followers never call spmd.run, so only rank 0 raises
        H2O3_TPU_FAULTS="death:spmd_run",
        H2O3_TPU_RECOVERY="1",
        # keep the launch.py background watcher's auto-reform far away
        # (30 s backoff): the test drives the reform explicitly through
        # POST /3/Recover so the latched window is observable first
        H2O3_TPU_RECOVERY_BACKOFF="30",
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    logs = [open(tmp_path / f"rproc{i}.log", "wb") for i in range(2)]
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "h2o3_tpu.launch",
             "--coordinator", f"127.0.0.1:{coord_port}",
             "--num-processes", "2", "--process-id", str(i),
             "--ip", "127.0.0.1", "--port", str(rest_port)],
            stdout=logs[i], stderr=subprocess.STDOUT, cwd=repo, env=env,
        )
        for i in range(2)
    ]
    base = f"http://127.0.0.1:{rest_port}"
    try:
        deadline = time.time() + 120
        up = False
        while time.time() < deadline and not up:
            if any(p.poll() is not None for p in procs):
                break
            try:
                _req(base, "GET", "/3/Ping", timeout=5)
                up = True
            except Exception:
                time.sleep(1.0)
        assert up, "coordinator REST never came up"
        yield base
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
        for f in logs:
            f.close()
        for i in range(2):
            sys.stderr.write(f"--- rproc{i} log tail ---\n")
            tail = (tmp_path / f"rproc{i}.log").read_bytes()[-2000:]
            sys.stderr.write(tail.decode(errors="replace") + "\n")


def _req(base, method, path, data=None, timeout=60):
    body = urllib.parse.urlencode(data).encode() if data else None
    r = urllib.request.Request(base + path, data=body, method=method)
    return json.loads(urllib.request.urlopen(r, timeout=timeout).read())


@pytest.mark.slow
def test_cross_process_latch_recover_and_fenced_commands(two_process_cloud):
    """The full cross-process self-healing sequence on a REAL two-process
    cloud: (1) the armed death signature latches the degraded fail-stop on
    the coordinator's first replicated command and /3/Cloud reports it;
    (2) a queued command fail-stops instead of broadcasting into the dead
    cloud; (3) POST /3/Recover re-forms — generation 0 -> 1; (4) a fresh
    replicated command carries the new stamp, the FOLLOWER adopts the
    generation through the command stream, and the command executes on both
    ranks (the CreateFrame result proves follower participation: replicated
    commands hang without it)."""
    base = two_process_cloud

    # (1) first replicated command dies with the death signature → latch
    with pytest.raises(urllib.error.HTTPError) as ei:
        _req(base, "POST", "/3/CreateFrame",
             {"dest": "mp0", "rows": "100", "cols": "2", "seed": "1"})
    assert ei.value.code >= 500
    cloud = _req(base, "GET", "/3/Cloud")
    assert cloud["cloud_healthy"] is False
    assert "degraded" in cloud and cloud["generation"] == 0

    # (2) queued commands fail-stop at admission, never broadcast
    with pytest.raises(urllib.error.HTTPError) as ei:
        _req(base, "POST", "/3/CreateFrame",
             {"dest": "mp1", "rows": "100", "cols": "2", "seed": "1"})
    assert ei.value.code >= 500

    # (3) supervised reform over REST: degraded → recovering → healthy
    out = _req(base, "POST", "/3/Recover", {})
    assert out["recovered"] is True and out["generation"] == 1
    cloud = _req(base, "GET", "/3/Cloud")
    assert cloud["cloud_healthy"] is True and cloud["generation"] == 1

    # (4) post-reform replicated command: the follower adopts generation 1
    # from the command stamp and executes — cross-process again
    cf = _req(base, "POST", "/3/CreateFrame",
              {"dest": "mp2", "rows": "300", "cols": "3", "seed": "2",
               "has_response": "true"}, timeout=120)
    assert cf["rows"] == 300
    fr = _req(base, "GET", "/3/Frames/mp2")["frames"][0]
    assert fr["rows"] == 300
