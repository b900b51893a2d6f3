"""Multi-host + observability smoke (SURVEY.md §4 CI strategy row, §5.1/§5.8).

The 2-process jax.distributed test backs the multi-host claim in
cluster/cloud.py: two OS processes form a cloud through the coordination
service (the Paxos successor) and run a psum across both processes' devices.
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

# ---------------------------------------------------------------------------
# environment probe: some jaxlib builds (including this CI container's)
# accept jax.distributed.initialize but then refuse CROSS-PROCESS
# computations on the CPU backend ("Multiprocess computations aren't
# implemented on the CPU backend"). The two-process tests below cannot pass
# there for environmental reasons — probe ONCE (bounded) and auto-skip with
# the real reason instead of carrying known-environmental failures as red.

_TWO_PROC_REASON: str | None = None  # None = not probed; "" = capable


def _two_process_blocker() -> str:
    global _TWO_PROC_REASON
    if _TWO_PROC_REASON is not None:
        return _TWO_PROC_REASON
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    prog = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
        jax.distributed.initialize(coordinator_address="127.0.0.1:{port}",
                                   num_processes=2,
                                   process_id=int(sys.argv[1]))
        assert jax.device_count() == 4, jax.device_count()
        # the real capability test: an actual cross-process collective
        import numpy as np
        from jax.experimental import multihost_utils as mh
        out = mh.broadcast_one_to_all(np.array([7], np.int32))
        assert int(out[0]) == 7, out
        print("PROBE OK", sys.argv[1])
    """)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", prog, str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        for i in range(2)
    ]
    outs, timed_out = [], False
    for p in procs:
        try:
            out, _ = p.communicate(timeout=90)
            outs.append(out.decode(errors="replace"))
        except subprocess.TimeoutExpired:
            p.kill()
            timed_out = True
            outs.append("")
    if timed_out:
        _TWO_PROC_REASON = "2-process jax.distributed probe timed out (90s)"
    elif all(p.returncode == 0 for p in procs):
        _TWO_PROC_REASON = ""
    else:
        # surface the root-cause line when recognizable, else the tail
        joined = "\n".join(outs)
        reason = next(
            (ln.strip() for ln in joined.splitlines()
             if "Error" in ln or "error" in ln), joined[-300:])
        _TWO_PROC_REASON = reason[-300:]
    return _TWO_PROC_REASON


def _skip_unless_two_process_capable() -> None:
    reason = _two_process_blocker()
    if reason:
        pytest.skip(
            "two-process jax.distributed is unavailable in this environment "
            f"(auto-skip, pre-existing environmental limitation): {reason}"
        )


@pytest.mark.slow
def test_two_process_jax_distributed_psum(tmp_path):
    _skip_unless_two_process_capable()
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    prog = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P

        pid = int(sys.argv[1])
        import h2o3_tpu
        info = h2o3_tpu.init(coordinator="127.0.0.1:{port}", num_processes=2,
                             process_id=pid)
        assert info["processes"] == 2, info
        assert info["cloud_size"] == 4, info  # 2 procs x 2 local cpu devices

        # a psum over the GLOBAL mesh — the MRTask.reduce successor crossing
        # the process boundary. The global array is assembled from each
        # process's addressable shards of one logical numpy array.
        from jax.sharding import NamedSharding
        mesh = Mesh(np.array(jax.devices()).reshape(4), ("rows",))
        sharding = NamedSharding(mesh, P("rows"))
        np_global = np.arange(8.0)
        x = jax.make_array_from_callback((8,), sharding, lambda idx: np_global[idx])
        def body(x):
            return jax.lax.psum(jnp.sum(x), "rows")
        out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("rows"),
                                    out_specs=P()))(x)
        total = float(np.asarray(jax.device_get(out.addressable_shards[0].data)))
        assert total == 28.0, total  # sum(0..7)
        print(f"proc {{pid}} OK total={{total}}")
    """)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", prog, str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=180)
        outs.append(out.decode())
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out[-3000:]}"
        assert f"proc {i} OK total=28.0" in out


def test_timeline_records_compiles():
    import jax.numpy as jnp
    import jax

    import h2o3_tpu
    from h2o3_tpu.utils import telemetry

    h2o3_tpu.init()
    # force a fresh compile with a unique shape
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(173)).block_until_ready()
    tl = telemetry.timeline()
    assert tl["compile_count"] >= 1
    assert any(e["kind"] == "compile" for e in tl["events"])


def test_profiler_writes_trace(tmp_path):
    import jax.numpy as jnp

    import h2o3_tpu

    h2o3_tpu.init()
    logdir = str(tmp_path / "prof")
    with h2o3_tpu.profiler(logdir):
        (jnp.ones(64) * 2).block_until_ready()
    import glob

    assert glob.glob(logdir + "/**/*.xplane.pb", recursive=True)


def test_launch_rest_train_across_two_processes(tmp_path):
    """End-to-end multi-host: two launch.py processes form a cloud; a GBM
    trains THROUGH REST with the spmd command replication executing the same
    device programs on both ranks (VERDICT r3 item 3 / SURVEY §4 multi-node
    row). Default tier: tiny shapes, 2 CPU devices per process."""
    _skip_unless_two_process_capable()
    import json
    import time
    import urllib.error
    import urllib.request

    import numpy as np
    import pandas as pd

    rng = np.random.default_rng(1)
    n = 400
    X = rng.normal(size=(n, 3))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0)
    df = pd.DataFrame(X, columns=["a", "b", "c"])
    df["label"] = np.where(y, "p", "n")
    csv = tmp_path / "mh.csv"
    df.to_csv(csv, index=False)

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
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    logs = [open(tmp_path / f"proc{i}.log", "wb") for i in range(2)]
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

    def req(method, path, data=None, timeout=60):
        import urllib.parse

        body = urllib.parse.urlencode(data).encode() if data else None
        r = urllib.request.Request(base + path, data=body, method=method)
        return json.loads(urllib.request.urlopen(r, timeout=timeout).read())

    try:
        # wait for the coordinator's REST to come up
        deadline = time.time() + 120
        cloud = None
        while time.time() < deadline:
            if any(p.poll() is not None for p in procs):
                break
            try:
                cloud = req("GET", "/3/Cloud", timeout=5)
                break
            except Exception:
                time.sleep(1.0)
        assert cloud is not None, "REST coordinator never came up"
        assert cloud["cloud_size"] == 4  # 2 procs x 2 devices

        req("POST", "/3/ImportFiles", {"path": str(csv)})
        req("POST", "/3/Parse", {"source_frames": str(csv),
                                 "destination_frame": "mh"})
        job = req("POST", "/3/ModelBuilders/gbm",
                  {"training_frame": "mh", "response_column": "label",
                   "ntrees": "3", "max_depth": "3", "seed": "7"})
        jid = (job.get("job") or job)["key"]["name"]
        deadline = time.time() + 240
        status = None
        while time.time() < deadline:
            j = req("GET", f"/3/Jobs/{jid}")["jobs"][0]
            status = j["status"]
            if status in ("DONE", "FAILED", "CANCELLED"):
                break
            time.sleep(1.0)
        assert status == "DONE", f"build ended {status}: {j.get('exception')}"
        mkey = j["dest"]["name"]
        mm = req("GET", f"/3/Models/{mkey}")["models"][0]
        auc = mm["output"]["training_metrics"]["auc"]
        assert auc > 0.8, auc

        pred = req("POST", f"/3/Predictions/models/{mkey}/frames/mh", {})
        assert pred["predictions_frame"]["name"]

        # -- spmd v3 surfaces on the SAME live cloud (boot is the expensive
        # part): Rapids eval, frame summary, CSV download, export, and
        # binary model save + load all replicate across both ranks --------
        r = req("POST", "/99/Rapids",
                {"ast": "(tmp= mh_sub (cols_py mh ['a' 'b']))"})
        assert r["num_cols"] == 2 and r["num_rows"] == 400, r
        r = req("POST", "/99/Rapids", {"ast": "(mean (cols_py mh 'a'))"})
        assert "scalar" in r or "key" in r, r

        s = req("GET", "/3/Frames/mh/summary")
        assert s["summary"], s
        # the replicated describe cached rollups: plain frame GET now serves
        # real per-column stats even on the multi-process cloud
        fg = req("GET", "/3/Frames/mh")["frames"][0]
        acol = next(c for c in fg["columns"] if c["label"] == "a")
        assert acol["mean"] is not None

        raw = urllib.request.urlopen(
            f"{base}/3/DownloadDataset?frame_id=mh", timeout=60).read()
        assert raw.decode().count("\n") >= 400

        out_csv = tmp_path / "mh_export.csv"
        req("POST", "/3/Frames/mh/export",
            {"path": str(out_csv), "force": "true"})
        assert out_csv.exists() and out_csv.stat().st_size > 1000

        sv = req("POST", f"/99/Models.bin/{mkey}", {"dir": str(tmp_path)})
        assert sv["dir"], sv
        lr = req("POST", "/99/Models.bin", {"dir": sv["dir"]})
        assert lr["models"][0]["model_id"]["name"] == mkey
        pred2 = req("POST", f"/3/Predictions/models/{mkey}/frames/mh", {})
        assert pred2["predictions_frame"]["name"]

        # unseeded random ops must be rejected (cross-rank divergence)
        try:
            req("POST", "/99/Rapids", {"ast": "(tmp= rnd (h2o.runif mh -1))"})
            raise AssertionError("unseeded h2o.runif should 4xx on a "
                                 "multi-process cloud")
        except urllib.error.HTTPError as e:
            assert e.code in (400, 412), e.code
        r = req("POST", "/99/Rapids", {"ast": "(tmp= rnd (h2o.runif mh 42))"})
        assert r["num_rows"] == 400, r

        # frame-utility commands replicate on the same live cloud:
        # SplitFrame (seeded), CreateFrame (coordinator-drawn seed),
        # Interaction — then a model trains on a replicated product
        sp = req("POST", "/3/SplitFrame",
                 {"dataset": "mh", "ratios": "[0.75]",
                  "destination_frames": '["mh_tr", "mh_te"]', "seed": "5"})
        tr_rows = req("GET", "/3/Frames/mh_tr")["frames"][0]["rows"]
        te_rows = req("GET", "/3/Frames/mh_te")["frames"][0]["rows"]
        assert tr_rows + te_rows == 400, (tr_rows, te_rows)
        cf = req("POST", "/3/CreateFrame",
                 {"dest": "mh_cf", "rows": "300", "cols": "4",
                  "categorical_fraction": "0.5", "factors": "3",
                  "has_response": "true"})
        assert cf["rows"] == 300, cf
        it = req("POST", "/3/Interaction",
                 {"source_frame": "mh_cf", "factor_columns": '["C3", "C4"]'})
        ikey = it["destination_frame"]["name"]
        ifr = req("GET", f"/3/Frames/{ikey}")["frames"][0]
        assert ifr["columns"][0]["type"] == "enum", ifr
        job2 = req("POST", "/3/ModelBuilders/gbm",
                   {"training_frame": "mh_tr", "response_column": "label",
                    "ntrees": "2", "max_depth": "2", "seed": "3"})
        jid2 = job2["job"]["key"]["name"]
        deadline = time.time() + 180
        while time.time() < deadline:
            j2 = req("GET", f"/3/Jobs/{jid2}")["jobs"][0]
            if j2["status"] in ("DONE", "FAILED", "CANCELLED"):
                break
            time.sleep(1.0)
        assert j2["status"] == "DONE", j2.get("exception")
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
        for f in logs:
            f.close()
        for i in range(2):
            sys.stderr.write(f"--- proc{i} log tail ---\n")
            tail = (tmp_path / f"proc{i}.log").read_bytes()[-2000:]
            sys.stderr.write(tail.decode(errors="replace") + "\n")


def test_sharded_parse_single_process(tmp_path):
    """parse_sharded degenerates to a plain parse on one process — values,
    domains and NA placement must match the eager reader."""
    import numpy as np
    import pandas as pd

    import h2o3_tpu
    from h2o3_tpu.frame.parse import parse, parse_sharded

    rng = np.random.default_rng(3)
    n = 3001  # deliberately not a shard multiple
    df = pd.DataFrame({
        "x": rng.normal(size=n),
        "g": rng.choice(["u", "v", "w"], n),
        "i": rng.integers(0, 9, n),
    })
    df.loc[::13, "x"] = np.nan
    csv = tmp_path / "s.csv"
    df.to_csv(csv, index=False)
    a = parse({"source_frames": [str(csv)]}, destination_frame="sp_a")
    b = parse_sharded({"source_frames": [str(csv)]}, destination_frame="sp_b")
    assert b.nrow == a.nrow == n
    np.testing.assert_allclose(
        b.vec("x").to_numpy(), a.vec("x").to_numpy(), rtol=1e-6
    )
    assert tuple(b.vec("g").domain) == tuple(a.vec("g").domain)
    np.testing.assert_array_equal(b.vec("g").to_numpy(), a.vec("g").to_numpy())


def test_sharded_parse_two_processes(tmp_path):
    """Each rank parses ONLY its own row range (ParseDataset distributed
    ingest successor) and the global frame is correct: per-rank host reads
    are asserted disjoint and the global sums match the full-file truth."""
    _skip_unless_two_process_capable()
    import numpy as np
    import pandas as pd

    rng = np.random.default_rng(9)
    n = 5000
    df = pd.DataFrame({
        "x": rng.normal(size=n),
        "g": rng.choice(["aa", "bb", "cc", "dd"], n),
    })
    csv = tmp_path / "mh2.csv"
    df.to_csv(csv, index=False)
    want_sum = float(np.nansum(df["x"]))

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    prog = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np
        pid = int(sys.argv[1])
        import h2o3_tpu
        h2o3_tpu.init(coordinator="127.0.0.1:{port}", num_processes=2, process_id=pid)
        import pandas as pd
        reads = {{}}
        orig = pd.read_csv
        def spy(path, *a, **k):
            out = orig(path, *a, **k)
            if str(path).endswith("mh2.csv"):
                reads.setdefault("rows", []).append(len(out))
            return out
        pd.read_csv = spy
        from h2o3_tpu.frame.parse import parse_sharded
        from h2o3_tpu.cluster import spmd
        fr = parse_sharded({{"source_frames": [{str(csv)!r}]}}, destination_frame="mh2")
        assert fr.nrow == {n}, fr.nrow
        # the big read this rank did must be ONLY its range (< 60% of rows)
        big = max(reads["rows"])
        assert big <= 0.6 * {n}, big
        with spmd.replicated_section():
            x = fr.vec("x").to_numpy()
            g = fr.vec("g").to_numpy()
        assert abs(float(np.nansum(x)) - {want_sum!r}) < 1e-3
        assert g.min() >= 0 and tuple(fr.vec("g").domain) == ("aa", "bb", "cc", "dd")
        print(f"proc {{pid}} OK sharded ingest")
    """)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", prog, str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for i in range(2)
    ]
    outs = [p.communicate(timeout=240)[0].decode() for p in procs]
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out[-3000:]}"
        assert f"proc {i} OK sharded ingest" in out


@pytest.mark.slow
def test_grid_over_rest_across_two_processes(tmp_path):
    """Grid search replicates as ONE spmd command: the deterministic key
    sequence keeps every rank's grid-model keys aligned (registry.make_key
    replicated mode), so /99/Grids and predictions work afterwards."""
    _skip_unless_two_process_capable()
    import json
    import time
    import urllib.parse
    import urllib.request

    import numpy as np
    import pandas as pd

    rng = np.random.default_rng(4)
    n = 400
    X = rng.normal(size=(n, 3))
    df = pd.DataFrame(X, columns=["a", "b", "c"])
    df["label"] = np.where(X[:, 0] + 0.5 * X[:, 1] > 0, "p", "n")
    csv = tmp_path / "grid.csv"
    df.to_csv(csv, index=False)

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord_port = s.getsockname()[1]
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        rest_port = s.getsockname()[1]
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    logs = [open(tmp_path / f"gproc{i}.log", "wb") for i in range(2)]
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

    def req(method, path, data=None, as_json=False, timeout=60):
        if as_json:
            body = json.dumps(data).encode()
            r = urllib.request.Request(base + path, data=body, method=method,
                                       headers={"Content-Type": "application/json"})
        else:
            body = urllib.parse.urlencode(data).encode() if data else None
            r = urllib.request.Request(base + path, data=body, method=method)
        return json.loads(urllib.request.urlopen(r, timeout=timeout).read())

    try:
        deadline = time.time() + 120
        up = False
        while time.time() < deadline and not up:
            try:
                req("GET", "/3/Ping", timeout=5)
                up = True
            except Exception:
                time.sleep(1.0)
        assert up, "coordinator REST never came up"

        req("POST", "/3/ImportFiles", {"path": str(csv)})
        pj = req("POST", "/3/Parse", {"source_frames": str(csv),
                                      "destination_frame": "gfr"})
        pjid = pj["job"]["key"]["name"]
        while req("GET", f"/3/Jobs/{pjid}")["jobs"][0]["status"] not in ("DONE", "FAILED"):
            time.sleep(0.5)

        g = req("POST", "/99/Grid/gbm", {
            "training_frame": "gfr", "response_column": "label",
            "ntrees": 3, "max_depth": 2, "seed": 3,
            "hyper_parameters": {"learn_rate": [0.1, 0.3]},
        }, as_json=True)
        gid = g["grid_id"]["name"]
        jid = g["job"]["key"]["name"]
        deadline = time.time() + 300
        while time.time() < deadline:
            j = req("GET", f"/3/Jobs/{jid}")["jobs"][0]
            if j["status"] in ("DONE", "FAILED", "CANCELLED"):
                break
            time.sleep(1.0)
        assert j["status"] == "DONE", j.get("exception")
        grid = req("GET", f"/99/Grids/{gid}")["grids"][0]
        ids = [m["name"] for m in grid.get("model_ids", [])]
        assert len(ids) == 2, grid
        # the grid's models are predictable cross-rank (keys aligned)
        pred = req("POST", f"/3/Predictions/models/{ids[0]}/frames/gfr", {})
        assert pred["predictions_frame"]["name"]
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
        for f in logs:
            f.close()
        for i in range(2):
            sys.stderr.write(f"--- gproc{i} tail ---\n")
            sys.stderr.write((tmp_path / f"gproc{i}.log").read_bytes()[-1500:]
                             .decode(errors="replace") + "\n")


@pytest.mark.slow
def test_dead_rank_fails_stop(tmp_path):
    """SURVEY §5.3 failure semantics: killing a member kills the CLOUD within
    the heartbeat bound — the jax distributed runtime aborts every surviving
    process when a task stops heartbeating (observed: "Terminating process
    because the JAX distributed service detected fatal errors"). That is
    exactly H2O's fail-stop contract (a dead node makes the cluster
    unusable; restart + checkpoints are the recovery path). The assertion is
    BOUNDED DEATH, not survival: the coordinator must exit, not hang."""
    _skip_unless_two_process_capable()
    import json
    import signal
    import time
    import urllib.parse
    import urllib.request

    import numpy as np
    import pandas as pd

    rng = np.random.default_rng(2)
    df = pd.DataFrame(rng.normal(size=(300, 3)), columns=["a", "b", "c"])
    df["label"] = np.where(df["a"] + df["b"] > 0, "p", "n")
    csv = tmp_path / "dead.csv"
    df.to_csv(csv, index=False)

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord_port = s.getsockname()[1]
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        rest_port = s.getsockname()[1]
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu",
               H2O3_TPU_HEARTBEAT_TIMEOUT="10")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    logs = [open(tmp_path / f"dproc{i}.log", "wb") for i in range(2)]
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

    def req(method, path, data=None, timeout=30):
        body = urllib.parse.urlencode(data).encode() if data else None
        r = urllib.request.Request(base + path, data=body, method=method)
        return json.loads(urllib.request.urlopen(r, timeout=timeout).read())

    try:
        deadline = time.time() + 120
        up = False
        while time.time() < deadline and not up:
            try:
                req("GET", "/3/Ping", timeout=5)
                up = True
            except Exception:
                time.sleep(1.0)
        assert up, "coordinator REST never came up"

        # a healthy cloud first: parse succeeds across both ranks
        req("POST", "/3/ImportFiles", {"path": str(csv)})
        req("POST", "/3/Parse", {"source_frames": str(csv),
                                 "destination_frame": "dfr"})
        time.sleep(5)

        procs[1].send_signal(signal.SIGKILL)  # kill the follower
        procs[1].wait(timeout=10)

        # fail-stop, bounded by the 10 s heartbeat (+ polling margin): the
        # surviving coordinator must DIE, not hang serving a broken cloud
        deadline = time.time() + 90
        while time.time() < deadline and procs[0].poll() is None:
            time.sleep(2.0)
        assert procs[0].poll() is not None, (
            "coordinator still alive 90 s after member death — fail-stop "
            "violated (hung cloud)"
        )
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
    tail = (tmp_path / "dproc0.log").read_bytes()[-3000:].decode(errors="replace")
    assert ("unhealthy" in tail or "heartbeat" in tail
            or "distributed service detected fatal errors" in tail), tail
