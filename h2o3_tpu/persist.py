"""Binary model save/load + persistence SPI — successor of
``water.persist.Persist`` (URI-scheme byte store) and the ``/99/Models.bin``
save/load endpoints (``water.api.ModelsHandler``) [UNVERIFIED upstream
paths, SURVEY.md §2.1, §5.4].

H2O serializes the whole ``Model`` Iced graph with AutoBuffer; the Python-
native equivalent is pickle — with two twists handled here:
- device arrays (tree level records, betas, DL params) are pulled to host
  numpy on save in ONE batched transfer (a networked TPU charges ~100ms per
  transfer — per-array pulls would take minutes on a big forest);
- jax-traced closures (GLM family objects, the DL apply_fn) are stripped on
  save and rebuilt from their defining parameters on load.

Scheme dispatch mirrors the Persist SPI: ``file:`` (and bare paths) are
implemented; ``s3:``/``hdfs:``/``gs:`` raise cleanly until a backend is
registered (the SPI point is the registry, not any one cloud SDK).

Durability contract (the fail-stop cloud's other half, SURVEY §5.3):
- **atomic publish** — every FS write lands in a same-directory temp file
  and is ``os.replace``d into place on clean close, so a crash mid-write
  never leaves a partial file at the target path (the cloud backends get
  the same guarantee from ``_UploadOnClose``: no partial object is ever
  published);
- **retry with backoff** — transient IO errors are retried
  ``H2O3_TPU_PERSIST_RETRIES`` times with exponential backoff and
  *deterministic* jitter (identical on every rank, preserving the spmd
  lockstep contract), while deterministic errors (collision, bad path,
  corrupt file) fail fast on the first attempt.
"""

from __future__ import annotations

import io
import os
import pickle
import tempfile
import time
import urllib.parse
import zlib
from typing import BinaryIO, Callable

import jax
import numpy as np

from h2o3_tpu.cluster.registry import DKV
from h2o3_tpu.models.model_base import Model
from h2o3_tpu.utils import faults
from h2o3_tpu.utils import metrics as _mx
from h2o3_tpu.utils.log import Log

# flaky storage must be visible BEFORE it becomes an outage: every transient
# retry bumps this (alongside the Log.warn), and write durations feed the
# checkpoint-cost histogram
_RETRIES_TOTAL = _mx.counter(
    "persist_retries_total", "transient persist IO retries, by operation kind")
_WRITE_SECONDS = _mx.histogram(
    "persist_write_seconds",
    "durable persist write wall time (incl. retries/backoff), by kind")

FORMAT_MAGIC = b"H2O3TPU1"


# ---------------------------------------------------------------------------
# Persist SPI


class PersistBackend:
    def open_read(self, path: str) -> BinaryIO:
        raise NotImplementedError

    def open_write(self, path: str) -> BinaryIO:
        raise NotImplementedError

    def exists(self, path: str) -> bool:
        """Scheme-correct existence probe (collision checks, ``force=False``)."""
        raise NotImplementedError

    def is_dir(self, path: str) -> bool:
        """True when the path is a directory-like container. Object stores
        have no real directories — they return False and rely on the
        trailing-``/`` convention for directory-append semantics."""
        return False

    def probe(self, path: str) -> tuple | None:
        """Cheap change-detection etag for ``path`` — any hashable tuple
        that changes when the content does (FS: mtime_ns + size; object
        stores would surface their ETag/generation). ``None`` means the
        backend cannot probe without reading bytes; the serving registry's
        watch loop (serving/registry.py) requires a probing backend."""
        return None

    def list_dir(self, path: str) -> list[str]:
        """File names directly inside a directory-like path (no recursion,
        no directories). Backends without listings raise — the watch loop
        reports the scheme as unwatchable instead of spinning."""
        raise NotImplementedError(f"{type(self).__name__} cannot list {path}")


class _AtomicFile(io.FileIO):
    """FS write handle that publishes atomically on clean close.

    Bytes land in a same-directory temp file; ``os.replace`` moves it onto
    the target only after a successful close — a crash or an exception in
    the ``with`` block deletes the temp and leaves NO partial file at the
    target path. close() stays idempotent like every other file object.
    """

    def __init__(self, fd: int, tmp_path: str, final_path: str):
        super().__init__(fd, "wb")
        self._tmp = tmp_path
        self._final = final_path
        self._aborted = False
        self._published = False

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self._aborted = True
        self.close()

    def close(self) -> None:
        if self.closed:
            return
        if not self._aborted:
            try:
                self.flush()
                os.fsync(self.fileno())
            except OSError:  # fsync is best-effort (some FS reject it)
                pass
        super().close()
        if self._aborted or self._published:
            try:
                os.unlink(self._tmp)
            except OSError:
                pass
            return
        self._published = True
        os.replace(self._tmp, self._final)


class PersistFS(PersistBackend):
    def open_read(self, path: str) -> BinaryIO:
        return open(path, "rb")

    def open_write(self, path: str) -> BinaryIO:
        d = os.path.dirname(path) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(
            dir=d, prefix="." + os.path.basename(path) + ".", suffix=".tmp"
        )
        return _AtomicFile(fd, tmp, path)

    def exists(self, path: str) -> bool:
        return os.path.exists(path)

    def is_dir(self, path: str) -> bool:
        return os.path.isdir(path)

    def probe(self, path: str) -> tuple | None:
        try:
            st = os.stat(path)
        except OSError:
            return None
        return (st.st_mtime_ns, st.st_size)

    def list_dir(self, path: str) -> list[str]:
        with os.scandir(path) as it:
            return sorted(e.name for e in it if e.is_file())


class _UploadOnClose(io.BytesIO):
    """Write buffer that publishes atomically on clean close.

    A with-block that raises marks the buffer aborted, so NO partial object
    is ever published; close() is idempotent like every other file object.
    """

    def __init__(self, publish):
        super().__init__()
        self._publish = publish
        self._done = False
        self._aborted = False

    def close(self) -> None:
        if not self._done and not self.closed:
            self._done = True
            if not self._aborted:
                self._publish(self.getvalue())
        super().close()

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self._aborted = True
        self.close()


class PersistS3(PersistBackend):
    """``s3://bucket/key`` via boto3 (gated: clean error when absent)."""

    def __init__(self):
        import boto3  # raises ImportError when the SDK is not in the image

        self._s3 = boto3.client("s3")

    def _split(self, uri: str) -> tuple[str, str]:
        p = urllib.parse.urlparse(uri)
        return p.netloc, p.path.lstrip("/")

    def open_read(self, path: str) -> BinaryIO:
        bucket, key = self._split(path)
        body = self._s3.get_object(Bucket=bucket, Key=key)["Body"].read()
        return io.BytesIO(body)

    def open_write(self, path: str) -> BinaryIO:
        bucket, key = self._split(path)
        return _UploadOnClose(
            lambda data: self._s3.put_object(Bucket=bucket, Key=key, Body=data)
        )

    def exists(self, path: str) -> bool:
        bucket, key = self._split(path)
        try:
            self._s3.head_object(Bucket=bucket, Key=key)
            return True
        except Exception:  # botocore ClientError 404 — SDK-typed, gated import
            return False

    def probe(self, path: str) -> tuple | None:
        """ETag-based change etag (ISSUE 14: the serving registry's model
        store need not be a filesystem): one HEAD per file per poll — the
        object-store analog of the FS mtime_ns+size stat, never a read."""
        bucket, key = self._split(path)
        try:
            head = self._s3.head_object(Bucket=bucket, Key=key)
        except Exception:  # 404/permission — watch loop treats as vanished
            return None
        return (head.get("ETag", "").strip('"'),
                int(head.get("ContentLength", 0)))

    def list_dir(self, path: str) -> list[str]:
        """Direct children of an s3 'directory' (Delimiter-scoped listing —
        no recursion, no pseudo-directories), paginated."""
        bucket, key = self._split(path)
        prefix = key.rstrip("/") + "/" if key else ""
        names: list[str] = []
        token = None
        while True:
            kw = {"Bucket": bucket, "Prefix": prefix, "Delimiter": "/"}
            if token:
                kw["ContinuationToken"] = token
            resp = self._s3.list_objects_v2(**kw)
            for obj in resp.get("Contents", ()):
                name = obj["Key"][len(prefix):]
                if name:  # skip the prefix marker object itself
                    names.append(name)
            if not resp.get("IsTruncated"):
                break
            token = resp.get("NextContinuationToken")
        return sorted(names)


class PersistGS(PersistBackend):
    """``gs://bucket/key`` via google-cloud-storage (gated)."""

    def __init__(self):
        from google.cloud import storage

        self._client = storage.Client()

    def _blob(self, uri: str):
        p = urllib.parse.urlparse(uri)
        return self._client.bucket(p.netloc).blob(p.path.lstrip("/"))

    def open_read(self, path: str) -> BinaryIO:
        return io.BytesIO(self._blob(path).download_as_bytes())

    def open_write(self, path: str) -> BinaryIO:
        blob = self._blob(path)
        return _UploadOnClose(lambda data: blob.upload_from_string(data))

    def exists(self, path: str) -> bool:
        return bool(self._blob(path).exists())

    def probe(self, path: str) -> tuple | None:
        """Generation/ETag change etag (one metadata GET, never a read).
        GCS generations are monotone per object — strictly stronger than
        mtime: an overwrite ALWAYS changes the etag."""
        blob = self._blob(path)
        try:
            blob.reload()
        except Exception:  # NotFound/permission — treated as vanished
            return None
        return (blob.etag or "", int(blob.generation or 0),
                int(blob.size or 0))

    def list_dir(self, path: str) -> list[str]:
        p = urllib.parse.urlparse(path)
        prefix = p.path.lstrip("/")
        prefix = prefix.rstrip("/") + "/" if prefix else ""
        it = self._client.list_blobs(p.netloc, prefix=prefix, delimiter="/")
        names = [b.name[len(prefix):] for b in it]
        return sorted(n for n in names if n)


class PersistHDFS(PersistBackend):
    """``hdfs://namenode/path`` via pyarrow's HadoopFileSystem (gated)."""

    def __init__(self):
        from pyarrow import fs

        self._fs_mod = fs
        self._conns: dict[tuple[str, int], object] = {}

    def _fs_path(self, uri: str):
        p = urllib.parse.urlparse(uri)
        host = p.hostname or "default"
        port = p.port or 8020
        conn = self._conns.get((host, port))
        if conn is None:
            conn = self._fs_mod.HadoopFileSystem(host, port)
            self._conns[(host, port)] = conn
        return conn, p.path

    def open_read(self, path: str) -> BinaryIO:
        f, pth = self._fs_path(path)
        return f.open_input_stream(pth)

    def open_write(self, path: str) -> BinaryIO:
        f, pth = self._fs_path(path)
        return f.open_output_stream(pth)

    def _info(self, path: str):
        f, pth = self._fs_path(path)
        return f.get_file_info(pth)

    def exists(self, path: str) -> bool:
        return self._info(path).type != self._fs_mod.FileType.NotFound

    def is_dir(self, path: str) -> bool:
        return self._info(path).type == self._fs_mod.FileType.Directory


_BACKENDS: dict[str, PersistBackend] = {"file": PersistFS(), "": PersistFS()}

# cloud schemes construct lazily on first touch: the SDK import happens then,
# and a missing SDK surfaces as a clear registration error, not at import
_LAZY_BACKENDS: dict[str, type] = {
    "s3": PersistS3,
    "gs": PersistGS,
    "hdfs": PersistHDFS,
}


def register_backend(scheme: str, backend: PersistBackend) -> None:
    _BACKENDS[scheme] = backend


def _backend_for(uri: str) -> tuple[PersistBackend, str]:
    parsed = urllib.parse.urlparse(uri)
    scheme = parsed.scheme if len(parsed.scheme) > 1 else ""  # windows-drive safe
    b = _BACKENDS.get(scheme)
    if b is None and scheme in _LAZY_BACKENDS:
        try:
            b = _LAZY_BACKENDS[scheme]()
        except ImportError as e:
            raise ValueError(
                f"persist scheme {scheme!r} needs its SDK ({e.name}) which is "
                "not installed in this image; register a backend with "
                "h2o3_tpu.persist.register_backend"
            ) from e
        _BACKENDS[scheme] = b
    if b is None:
        raise ValueError(
            f"no persist backend for scheme {scheme!r} "
            f"(registered: {sorted(k for k in _BACKENDS if k)}); "
            "register one with h2o3_tpu.persist.register_backend"
        )
    path = uri[len(scheme) + 1:].lstrip("/") if scheme == "file" else uri
    if scheme == "file":
        path = "/" + path if not path.startswith("/") else path
    return b, path


# ---------------------------------------------------------------------------
# retry/backoff wrapper for transient IO


def _is_transient(e: BaseException) -> bool:
    """Transient (retry) vs deterministic (fail fast) classification.

    Deterministic errors must raise identically on every rank and on every
    attempt — retrying them burns the budget AND desynchronizes nothing, so
    they surface immediately. The deterministic OSError subclasses are the
    path-shape family; everything else OS-level (EIO, ENOSPC-after-cleanup,
    connection resets, injected faults) is worth retrying.
    """
    if isinstance(e, (FileNotFoundError, FileExistsError, PermissionError,
                      IsADirectoryError, NotADirectoryError)):
        return False
    return isinstance(e, OSError)


def _retry_delays(desc: str) -> list[float]:
    """The backoff schedule for one operation: exponential with deterministic
    jitter keyed on (op, attempt) — every rank computes the same delays."""
    from h2o3_tpu import config

    retries = max(0, config.get_int("H2O3_TPU_PERSIST_RETRIES"))
    base = max(0.0, config.get_float("H2O3_TPU_PERSIST_BACKOFF"))
    out = []
    for attempt in range(retries):
        jitter = (zlib.crc32(f"{desc}:{attempt}".encode()) % 1000) / 2000.0
        out.append(base * (2 ** attempt) * (1.0 + jitter))
    return out


def _with_retries(op: Callable[[], "T"], desc: str):  # noqa: F821 - doc type
    """Run ``op`` retrying transient IO errors with backoff; the final
    attempt's (or any deterministic) error surfaces unchanged. Every retry
    is LOUD — a Log.warn with op/attempt/backoff plus a
    ``persist_retries_total`` bump — so flaky storage shows up in logs and
    on /3/Metrics before it becomes an outage."""
    delays = _retry_delays(desc)
    kind = desc.split(" ", 1)[0]  # "write"/"read"/"export"/... bounded labels
    for attempt in range(len(delays) + 1):
        try:
            return op()
        except Exception as e:
            if attempt >= len(delays) or not _is_transient(e):
                raise
            _RETRIES_TOTAL.inc(op=kind)
            Log.warn(
                f"persist: transient failure on {desc} (attempt "
                f"{attempt + 1}/{len(delays) + 1}): {e!r} — retrying in "
                f"{delays[attempt]:.2f}s"
            )
            time.sleep(delays[attempt])


def write_bytes(data: bytes, path: str) -> str:
    """Atomic, retried byte write through the scheme dispatch — the one
    durable-write primitive (models, grid/AutoML manifests)."""
    backend, p = _backend_for(path)

    def attempt():
        faults.io_check("persist_write", p)
        with backend.open_write(p) as f:
            f.write(data)

    t0 = time.perf_counter()
    _with_retries(attempt, f"write {p}")
    _WRITE_SECONDS.observe(time.perf_counter() - t0, kind="bytes")
    return p


def probe(path: str) -> tuple | None:
    """Change-detection etag through the scheme dispatch (None = the
    backend cannot probe cheaply, or the path does not exist). The serving
    registry's watch loop stats every candidate file each poll — this must
    stay a metadata call, never a read."""
    backend, p = _backend_for(path)
    return backend.probe(p)


def list_dir(path: str) -> list[str]:
    """File names inside a directory URI through the scheme dispatch."""
    backend, p = _backend_for(path)
    return backend.list_dir(p)


def read_bytes(path: str) -> bytes:
    """Retried whole-file read through the scheme dispatch."""
    backend, p = _backend_for(path)

    def attempt():
        faults.io_check("persist_read", p)
        with backend.open_read(p) as f:
            return f.read()

    return _with_retries(attempt, f"read {p}")


# ---------------------------------------------------------------------------
# device → host conversion of the whole model state, in one batched pull


def _pull_tree_output(out: dict) -> dict:
    out = dict(out)
    if "trees" in out:
        # collect every device array across the forest, fetch once
        import dataclasses as _dc

        from h2o3_tpu.models.tree.shared_tree import Tree, TreeLevel

        # derive from the dataclass so new record fields (node_w burned us
        # once: silently-zero TreeSHAP covers after reload) can't be dropped
        fields = tuple(f.name for f in _dc.fields(TreeLevel))
        flat = [
            [[getattr(lv, f) for f in fields] for lv in tree.levels]
            for group in out["trees"] for tree in group
        ]
        pulled = jax.device_get(flat)
        host_trees: list[list[Tree]] = []
        i = 0
        for group in out["trees"]:
            hgroup = []
            for _ in group:
                t = Tree()
                for vals in pulled[i]:
                    t.levels.append(TreeLevel(*[np.asarray(v) for v in vals]))
                hgroup.append(t)
                i += 1
            host_trees.append(hgroup)
        out["trees"] = host_trees
    if "params" in out:  # flax pytree
        out["params"] = jax.device_get(out["params"])
    if "opt_state" in out and out["opt_state"] is not None:  # optax pytree
        out["opt_state"] = jax.device_get(out["opt_state"])
    for k, v in list(out.items()):
        if isinstance(v, jax.Array):
            out[k] = np.asarray(v)
    return out


_STRIP: dict[str, tuple[str, ...]] = {
    "glm": ("family_obj",),
    "deeplearning": ("apply_fn", "probe"),
}

_REBUILDERS: dict[str, Callable[[Model], None]] = {}


def _rebuild_glm(model: Model) -> None:
    from h2o3_tpu.models.glm_families import get_family

    p = model.params
    fam = model.output["family"]
    if fam in ("multinomial", "ordinal"):
        # these fits carry a binomial family_obj only for metric plumbing
        # (scoring goes through beta_multinomial_std / theta directly)
        model.output["family_obj"] = get_family("binomial")
        return
    model.output["family_obj"] = get_family(
        fam, p.link,
        float(p.tweedie_variance_power or 1.5),
        float(p.tweedie_link_power), float(p.theta),
    )


def _rebuild_deeplearning(model: Model) -> None:
    from h2o3_tpu.models.deeplearning import _MLP

    p = model.params
    params = model.output["params"]
    inner = params["params"] if "params" in params else params
    last = sorted(inner.keys(), key=lambda k: int(k.split("_")[-1]))[-1]
    n_out = int(np.asarray(inner[last]["bias"]).shape[0])
    dropout = tuple(p.hidden_dropout_ratios or (0.0,) * len(p.hidden))
    mlp = _MLP(hidden=tuple(p.hidden), n_out=n_out, activation=p.activation,
               dropout=dropout, input_dropout=p.input_dropout_ratio)
    model.output["apply_fn"] = jax.jit(lambda prm, xx: mlp.apply(prm, xx, train=False))


_REBUILDERS["glm"] = _rebuild_glm
_REBUILDERS["deeplearning"] = _rebuild_deeplearning


# ---------------------------------------------------------------------------
# save / load


def _portable_params(params):
    """A pickle-light copy of the params dataclass: live Frame/Model refs
    collapse to their DKV keys (the model must not embed the training data —
    a periodic snapshot would otherwise re-serialize the whole frame every
    scoring interval, and sharded device columns don't pickle at all on a
    multi-process cloud). Resume passes frames explicitly, like H2O."""
    import copy
    import dataclasses

    if params is None or not dataclasses.is_dataclass(params):
        return params
    params = copy.copy(params)
    for fname in ("training_frame", "validation_frame", "calibration_frame",
                  "checkpoint"):
        ref = getattr(params, fname, None)
        if ref is not None and not isinstance(ref, str):
            setattr(params, fname, getattr(ref, "key", None))
    return params


def _portable_submodel(m: Model) -> Model:
    """A pickle-clean shallow clone of a nested model (CV folds, ensemble
    bases): device pulls + jit-closure strip + params lightening, without
    mutating the live object."""
    import copy

    clone = copy.copy(m)
    clone.__dict__.pop("_h2o3_batch_scorer", None)  # locks don't pickle
    clone.__dict__.pop("serving_generation", None)
    out = _pull_tree_output(dict(m.output))
    for k in _STRIP.get(m.algo, ()):
        out.pop(k, None)
    clone.output = out
    clone.params = _portable_params(m.params)
    clone.cv_models = []  # folds of folds don't exist; don't nest
    return clone


def serialize_model(model: Model) -> bytes:
    """Model → portable byte string (the device→host pulls happen here).

    Split out of :func:`save_model` so a multi-process cloud can run the
    pulls — collectives when output arrays span processes — on EVERY rank
    while only the coordinator writes the file (cluster/spmd.py)."""
    state = dict(model.__dict__)
    # serving-plane state is process-local: the cached batch scorer holds
    # locks + device arrays, and the registry generation is assigned by the
    # process that loads the snapshot, not baked into it
    state.pop("_h2o3_batch_scorer", None)
    state.pop("serving_generation", None)
    out = _pull_tree_output(state.pop("output"))
    for k in _STRIP.get(model.algo, ()):
        out.pop(k, None)
    state["output"] = out
    state["params"] = _portable_params(state.get("params"))
    if state.get("cv_models"):
        # fold models carry the same jit closures as the main model (a CV'd
        # GLM save used to die on the family_obj lambda here)
        state["cv_models"] = [_portable_submodel(m) for m in state["cv_models"]]
    payload = {"cls_module": type(model).__module__,
               "cls_name": type(model).__qualname__,
               "algo": model.algo,
               "state": state}
    buf = io.BytesIO()
    buf.write(FORMAT_MAGIC)
    pickle.dump(payload, buf, protocol=pickle.HIGHEST_PROTOCOL)
    return buf.getvalue()


def model_path_in_dir(dir_uri: str, model_key: str) -> tuple[PersistBackend, str]:
    """(backend, path) for a model file named after its key INSIDE a
    directory URI — the interval-checkpoint writer's path rule (the dir may
    not exist yet; FS open_write creates it)."""
    backend, p = _backend_for(dir_uri)
    if isinstance(backend, PersistFS):
        return backend, os.path.join(p, model_key)
    return backend, p.rstrip("/") + "/" + model_key


def resolve_model_path(path: str, model_key: str, force: bool = True):
    """(backend, final_path) for a model save; raises FileExistsError when
    ``force`` is off and the target exists. Shared by :func:`save_model` and
    the replicated spmd save command (which writes coordinator-side only).

    Existence/directory probes go through the backend SPI so ``s3://`` /
    ``gs://`` / ``hdfs://`` targets are checked on THEIR filesystem, not the
    coordinator's local disk."""
    backend, p = _backend_for(path)
    if path.endswith(("/", os.sep)) or backend.is_dir(p):
        if isinstance(backend, PersistFS):
            p = os.path.join(p, model_key)
        else:
            p = p.rstrip("/") + "/" + model_key
    if not force and backend.exists(p):
        raise FileExistsError(p)
    return backend, p


def write_model_bytes(data: bytes, backend, p: str, model_key: str) -> str:
    def attempt():
        faults.io_check("persist_write", p)
        with backend.open_write(p) as f:
            f.write(data)

    t0 = time.perf_counter()
    _with_retries(attempt, f"write model {model_key} -> {p}")
    _WRITE_SECONDS.observe(time.perf_counter() - t0, kind="model")
    Log.info(f"saved model {model_key} to {p}")
    return p


def save_model(model: Model, path: str, force: bool = True) -> str:
    """``h2o.save_model`` successor. ``path`` may be a directory (H2O
    convention: file named after the model key) or a full file path."""
    backend, p = resolve_model_path(path, model.key, force)
    return write_model_bytes(serialize_model(model), backend, p, model.key)


def load_model(path: str) -> Model:
    """``h2o.load_model`` successor: restores the model into the registry.

    Accepts final saves and in-training interval snapshots alike — a partial
    snapshot loads into a scoreable Model whose key can be passed as
    ``checkpoint=`` to continue training (docs/RECOVERY.md)."""
    backend, p = _backend_for(path)

    def attempt():
        faults.io_check("persist_read", p)
        with backend.open_read(p) as f:
            return f.read()

    blob = _with_retries(attempt, f"read model {p}")
    if blob[: len(FORMAT_MAGIC)] != FORMAT_MAGIC:
        raise ValueError(f"{path}: not an h2o3_tpu model file")
    try:
        payload = pickle.loads(blob[len(FORMAT_MAGIC):])
        cls_module = payload["cls_module"]
        cls_name = payload["cls_name"]
        state = payload["state"]
    except ValueError:
        raise
    except Exception as e:
        # a crash mid-write can't truncate an atomically published file, but
        # foreign/bit-rotted files still deserve a named error, not a bare
        # unpickling traceback
        raise ValueError(
            f"{path}: corrupt or truncated model file "
            f"({type(e).__name__}: {e})"
        ) from e

    import functools
    import importlib

    # qualname-aware lookup: nested model classes ("Outer.Inner") resolve by
    # walking the attribute chain, not just the first segment
    cls = functools.reduce(
        getattr, cls_name.split("."), importlib.import_module(cls_module)
    )
    model = cls.__new__(cls)
    model.__dict__.update(state)
    rebuild = _REBUILDERS.get(payload["algo"])
    if rebuild:
        rebuild(model)
    for cv in getattr(model, "cv_models", ()) or ():
        cv_rebuild = _REBUILDERS.get(cv.algo)
        if cv_rebuild:
            cv_rebuild(cv)
    DKV.put(model.key, model)
    Log.info(f"loaded model {model.key} from {p}")
    return model


def export_file(frame, path: str, force: bool = False, format: str | None = None) -> str:
    """``h2o.export_file`` successor — frame → CSV/Parquet through the
    Persist scheme dispatch (ref upstream water/api FramesHandler export +
    Persist SPI [UNVERIFIED], SURVEY.md §5.4)."""
    return export_df(frame.to_pandas(), path, force=force, format=format)


def export_df(df, path: str, force: bool = False, format: str | None = None) -> str:
    """Write an already-materialized pandas frame (the host pull — a
    collective on multi-process clouds — happens in the caller, so every
    rank can pull while only the coordinator writes; cluster/spmd.py)."""
    backend, p = _backend_for(path)
    try:
        if not force and backend.exists(p):
            raise FileExistsError(p)
    except NotImplementedError:  # probe-less custom backend: overwrite
        pass
    fmt = (format or "").lower() or ("parquet" if p.endswith((".parquet", ".pq")) else "csv")

    def attempt():
        faults.io_check("persist_write", p)
        with backend.open_write(p) as f:
            if fmt == "parquet":
                df.to_parquet(f, index=False)
            elif fmt == "csv":
                df.to_csv(f, index=False)
            else:
                raise ValueError(f"unsupported export format {fmt!r}")

    t0 = time.perf_counter()
    _with_retries(attempt, f"export {p}")
    _WRITE_SECONDS.observe(time.perf_counter() - t0, kind="export")
    return p
