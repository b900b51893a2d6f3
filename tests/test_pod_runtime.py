"""Multihost pod runtime (ISSUE 14): env-driven bootstrap + formation,
pod-restart wiring, coordinator-free sharded ingest byte-parity, and the
satellite lanes (object-store watch etags, the categorical iforest serving
lane, serving-registry warm boot).
"""

import os
import socket
import subprocess
import sys
import textwrap
import time

import numpy as np
import pandas as pd
import pytest

from test_multihost import _skip_unless_two_process_capable


# ---------------------------------------------------------------------------
# env-driven bootstrap + formation


def test_pod_env_parsing(monkeypatch):
    from h2o3_tpu.cluster import multihost

    for var in ("H2O3_TPU_COORDINATOR", "H2O3_TPU_NUM_PROCESSES",
                "H2O3_TPU_PROCESS_ID", "H2O3_TPU_POD_NAME", "POD_NAME"):
        monkeypatch.delenv(var, raising=False)
    assert multihost.pod_env() is None  # unset → single-host mode

    monkeypatch.setenv("H2O3_TPU_COORDINATOR", "pod-0.svc:1234")
    with pytest.raises(ValueError, match="NUM_PROCESSES"):
        multihost.pod_env()  # half-configured pods must fail loudly

    monkeypatch.setenv("H2O3_TPU_NUM_PROCESSES", "4")
    monkeypatch.setenv("POD_NAME", "h2o3-tpu-2")  # StatefulSet ordinal
    env = multihost.pod_env()
    assert env == {"coordinator": "pod-0.svc:1234", "num_processes": 4,
                   "process_id": 2}

    monkeypatch.setenv("H2O3_TPU_PROCESS_ID", "3")  # explicit id wins
    assert multihost.pod_env()["process_id"] == 3

    monkeypatch.setenv("H2O3_TPU_PROCESS_ID", "9")  # out of range
    with pytest.raises(ValueError, match="out of range"):
        multihost.pod_env()


def test_formation_single_process():
    """The degenerate 1-process pod still forms: barrier no-ops, per-host
    device enumeration covers the local devices, and the record carries the
    mesh shape the program caches will key on."""
    from h2o3_tpu.cluster import multihost

    rec = multihost.formation()
    assert rec["processes"] == 1 and rec["process_index"] == 0
    assert rec["devices"] == 8 and rec["hosts"] == {
        "0": list(range(8))}
    assert rec["mesh"] in ({"rows": 8}, {"rows": 1, "cols": 8})
    assert multihost.probe_capability() == ""  # single-process: capable


def test_pod_restart_watcher_inert_by_default():
    """H2O3_TPU_POD_EXIT_DEGRADED=0 (default) + single-process: the watcher
    installs, never exits the process even with the latch set, and
    uninstalls cleanly — the two-process recovery fixture depends on the
    in-process survivor island staying available."""
    from h2o3_tpu.cluster import cloud, multihost

    multihost.install_pod_restart(poll=0.05)
    try:
        cloud.mark_degraded("pod-restart inertness probe")
        time.sleep(0.3)  # an exit would kill this pytest process
        assert cloud.degraded_reason() is not None
    finally:
        cloud.clear_degraded()
        multihost.uninstall_pod_restart()


@pytest.mark.slow
def test_two_process_bootstrap_formation_and_capability(tmp_path):
    """Env-driven bootstrap on a REAL two-process cloud: both ranks form
    through cluster/multihost.bootstrap_from_env (no args), the formation
    barrier passes, per-host device enumeration shows 2 hosts × 2 devices,
    and the runtime capability probe agrees with the test-suite probe.
    Auto-skips with root cause where this jaxlib refuses cross-process CPU
    collectives (the PR-4 contract)."""
    _skip_unless_two_process_capable()
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    prog = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["H2O3_TPU_COORDINATOR"] = "127.0.0.1:{port}"
        os.environ["H2O3_TPU_NUM_PROCESSES"] = "2"
        os.environ["H2O3_TPU_POD_NAME"] = "h2o3-tpu-" + sys.argv[1]
        import jax
        jax.config.update("jax_platforms", "cpu")
        from h2o3_tpu.cluster import multihost
        rec = multihost.bootstrap_from_env()
        assert rec is not None
        assert rec["processes"] == 2, rec
        assert rec["devices"] == 4, rec
        assert len(rec["hosts"]) == 2, rec
        assert all(len(v) == 2 for v in rec["hosts"].values()), rec
        assert multihost.probe_capability() == "", multihost.probe_capability()
        print(f"proc {{sys.argv[1]}} FORMED", rec["mesh"])
    """)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", prog, str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for i in range(2)
    ]
    outs = [p.communicate(timeout=180)[0].decode() for p in procs]
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out[-3000:]}"
        assert f"proc {i} FORMED" in out


# ---------------------------------------------------------------------------
# coordinator-free sharded ingest: byte-range parses pinned byte-equal


def test_sharded_ingest_multirange_byte_equal(tmp_path, monkeypatch):
    """H2O3_TPU_INGEST_SHARDS=3 splits the parse into three byte ranges
    (each located by the streaming newline scan and tokenized by the native
    byte-range parser) — values, categorical codes and domains must be
    BYTE-equal to the one-shot parse (the pod ingest acceptance pin)."""
    from h2o3_tpu.frame.parse import parse, parse_sharded

    rng = np.random.default_rng(3)
    n = 3001  # deliberately not a shard multiple
    df = pd.DataFrame({
        "x": rng.normal(size=n),
        "g": rng.choice(["u", "v", "w"], n),
        "i": rng.integers(0, 9, n),
    })
    df.loc[::13, "x"] = np.nan
    csv = tmp_path / "pod.csv"
    df.to_csv(csv, index=False)
    a = parse({"source_frames": [str(csv)]}, destination_frame="pod_a")
    monkeypatch.setenv("H2O3_TPU_INGEST_SHARDS", "3")
    b = parse_sharded({"source_frames": [str(csv)]},
                      destination_frame="pod_b")
    assert b.nrow == a.nrow == n
    for col in ("x", "i"):
        assert (np.asarray(a.vec(col).to_numpy(), np.float32).tobytes()
                == np.asarray(b.vec(col).to_numpy(), np.float32).tobytes()), col
    assert tuple(a.vec("g").domain) == tuple(b.vec("g").domain)
    assert (a.vec("g").to_numpy().tobytes()
            == b.vec("g").to_numpy().tobytes())


def test_sharded_ingest_seeds_chunkstore_mirrors(tmp_path, monkeypatch):
    """With an HBM window configured (the out-of-core plane armed), the
    single-process sharded parse seeds each Vec's spill-tier host mirror so
    streaming builds never pay a device pull per column."""
    from h2o3_tpu.frame.parse import parse_sharded

    rng = np.random.default_rng(5)
    n = 2000
    df = pd.DataFrame({"x": rng.normal(size=n), "i": rng.integers(0, 5, n)})
    csv = tmp_path / "mirror.csv"
    df.to_csv(csv, index=False)
    monkeypatch.setenv("H2O3_TPU_HBM_WINDOW_BYTES", str(1 << 20))
    fr = parse_sharded({"source_frames": [str(csv)]},
                       destination_frame="pod_mirror")
    for col in ("x", "i"):
        assert fr.vec(col)._hostbuf is not None, col


# ---------------------------------------------------------------------------
# satellite: object-store etags (the registry's model store need not be FS)


class _FakeS3:
    """Minimal boto3-client stand-in: enough surface for probe/list_dir."""

    def __init__(self):
        self.objects = {
            ("bucket", "models/m1"): (b"one", "etag-1"),
            ("bucket", "models/m2"): (b"twotwo", "etag-2"),
            ("bucket", "models/sub/nested"): (b"x", "etag-3"),
            ("bucket", "other/m3"): (b"y", "etag-4"),
        }

    def head_object(self, Bucket, Key):
        data, etag = self.objects[(Bucket, Key)]
        return {"ETag": f'"{etag}"', "ContentLength": len(data)}

    def list_objects_v2(self, Bucket, Prefix, Delimiter,
                        ContinuationToken=None):
        names = set()
        for (b, k) in self.objects:
            if b != Bucket or not k.startswith(Prefix):
                continue
            rest = k[len(Prefix):]
            if Delimiter in rest:
                continue  # pseudo-directory: excluded like a real listing
            names.add(k)
        return {"Contents": [{"Key": k} for k in sorted(names)],
                "IsTruncated": False}


def test_s3_probe_and_list_dir_etags():
    from h2o3_tpu.persist import PersistS3

    b = PersistS3.__new__(PersistS3)  # skip boto3 import (not in image)
    b._s3 = _FakeS3()
    # probe: ETag + size, changes when content does, never a read
    assert b.probe("s3://bucket/models/m1") == ("etag-1", 3)
    assert b.probe("s3://bucket/models/gone") is None
    # list_dir: direct children only, sorted
    assert b.list_dir("s3://bucket/models") == ["m1", "m2"]


class _FakeBlob:
    def __init__(self, name, etag, generation, size):
        self.name, self.etag = name, etag
        self.generation, self.size = generation, size

    def reload(self):
        if self.etag is None:
            raise FileNotFoundError(self.name)


class _FakeGSClient:
    def __init__(self, blobs):
        self._blobs = blobs

    def bucket(self, name):
        client = self

        class _B:
            def blob(self, key):
                for bl in client._blobs:
                    if bl.name == key:
                        return bl
                return _FakeBlob(key, None, 0, 0)

        return _B()

    def list_blobs(self, bucket, prefix, delimiter):
        return [b for b in self._blobs
                if b.name.startswith(prefix)
                and delimiter not in b.name[len(prefix):]]


def test_gs_probe_and_list_dir_etags():
    from h2o3_tpu.persist import PersistGS

    b = PersistGS.__new__(PersistGS)
    b._client = _FakeGSClient([
        _FakeBlob("models/m1", "e1", 7, 11),
        _FakeBlob("models/m2", "e2", 3, 22),
        _FakeBlob("models/sub/nested", "e3", 1, 5),
    ])
    assert b.probe("gs://bucket/models/m1") == ("e1", 7, 11)
    assert b.probe("gs://bucket/models/gone") is None
    assert b.list_dir("gs://bucket/models") == ["m1", "m2"]


def test_fs_probe_unchanged_pin(tmp_path):
    """The FS backend's etag/listing behavior is byte-identical to before
    the object-store SPI growth: (mtime_ns, size) stats, sorted names."""
    from h2o3_tpu import persist

    p = tmp_path / "m"
    p.write_bytes(b"abc")
    st = os.stat(p)
    assert persist.probe(str(p)) == (st.st_mtime_ns, st.st_size)
    (tmp_path / "b").write_bytes(b"")
    assert persist.list_dir(str(tmp_path)) == ["b", "m"]


# ---------------------------------------------------------------------------
# satellite: categorical isolation-forest serving lane


def test_iforest_categorical_lane_byte_equal():
    """An IF trained on a frame WITH categorical features rides the
    compiled iforest lane (no generic fallback) and row-payload scores are
    byte-equal to the frame path — including a scoring frame whose local
    category interning DIFFERS from training (the training-domain codes
    satellite, ROADMAP 3b)."""
    from h2o3_tpu import serving
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.isolation_forest import IsolationForest

    rng = np.random.default_rng(9)
    n = 300
    df = pd.DataFrame({
        "a": rng.normal(size=n),
        "c": pd.Categorical(rng.choice(list("pqrs"), n)),
    })
    fr = Frame.from_pandas(df, destination_frame="pod_if_train")
    m = IsolationForest(ntrees=10, sample_size=64, seed=5).train(
        x=["a", "c"], training_frame=fr)
    assert m.output["feature_domains"][1] == ("p", "q", "r", "s")
    assert serving.scorer_for(m).lane == "iforest"

    rows = [{"a": 0.3, "c": "q"}, {"a": None, "c": "zz"},  # zz: unseen
            {"a": -1.0, "c": None}, {"a": 2.0, "c": "s"}]
    out = serving.score_rows(m, rows)
    # the scoring frame interns only the levels it SEES (q, s, zz) — its
    # frame-local codes differ from training; the remap must reconcile
    sf = Frame.from_pandas(pd.DataFrame({
        "a": [r["a"] for r in rows],
        "c": pd.Categorical([r["c"] for r in rows]),
    }))
    assert tuple(sf.vec("c").domain) != m.output["feature_domains"][1]
    pf = m.predict(sf)
    for col in ("predict", "mean_length"):
        assert (pf.vec(col).to_numpy()[:4].tobytes()
                == np.asarray(out[col]).tobytes()), col


def test_iforest_training_frame_predictions_unchanged():
    """On the training frame itself the domain remap is the identity —
    numeric-only models keep their exact pre-change scores (regression
    guard for the feature_domains growth)."""
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.isolation_forest import IsolationForest

    rng = np.random.default_rng(4)
    n = 200
    df = pd.DataFrame({"a": rng.normal(size=n), "b": rng.normal(size=n)})
    fr = Frame.from_pandas(df)
    m = IsolationForest(ntrees=8, sample_size=64, seed=3).train(
        x=["a", "b"], training_frame=fr)
    raw1 = m._predict_raw(fr)
    m.output.pop("feature_domains")  # a pre-ISSUE-14 snapshot
    raw0 = m._predict_raw(fr)
    assert raw1.tobytes() == raw0.tobytes()


# ---------------------------------------------------------------------------
# satellite: serving-registry warm boot


def test_registry_warm_boot_prepages_and_precompiles(tmp_path, monkeypatch):
    """With H2O3_TPU_SERVE_WARM_MODELS=2 and three snapshots in the store,
    warm_boot loads the newest two, leaves their scorers built (compiled
    lane + device residency) and the third untouched until the regular
    poll."""
    from h2o3_tpu import persist
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models import GBM
    from h2o3_tpu.serving.registry import ServingRegistry

    rng = np.random.default_rng(11)
    n = 400
    df = pd.DataFrame({
        "a": rng.normal(size=n), "b": rng.normal(size=n),
        "y": np.where(rng.random(n) < 0.5, "dog", "cat"),
    })
    fr = Frame.from_pandas(df, destination_frame="warm_train")
    wd = str(tmp_path / "store")
    os.makedirs(wd)
    models = []
    for i in range(3):
        m = GBM(ntrees=3, max_depth=3, seed=40 + i).train(
            y="y", training_frame=fr)
        persist.save_model(m, os.path.join(wd, f"warm_m{i}"))
        os.utime(os.path.join(wd, f"warm_m{i}"),
                 ns=(1_000_000_000 * (1000 + i),) * 2)  # deterministic age
        models.append(m)
    monkeypatch.setenv("H2O3_TPU_SERVE_WATCH_DIR", wd)
    monkeypatch.setenv("H2O3_TPU_SERVE_WARM_MODELS", "2")
    reg = ServingRegistry()
    try:
        assert reg.warm_boot() == 2
        # the two NEWEST snapshots (m1, m2) are serving with scorers built
        for m in models[1:]:
            served = reg.resolve(m.key)
            assert served is not None, m.key
            sc = served.__dict__.get("_h2o3_batch_scorer")
            assert sc is not None and sc.lane == "tree"
        assert reg.resolve(models[0].key) is None  # oldest: not warmed
        assert reg.poll_once() == 1  # the regular poll picks it up
        assert reg.resolve(models[0].key) is not None
    finally:
        reg.reset()
