"""Model/ModelBuilder framework — successor of ``hex.ModelBuilder`` /
``hex.Model`` / ``hex.ScoreKeeper`` [UNVERIFIED upstream paths, SURVEY.md
§2.2].

Responsibilities mirrored from H2O:
- parameter validation and train/validation frame adaptation,
- response handling (enum → classification, numeric → regression),
- the cross-validation driver (N fold models as sub-jobs, holdout
  predictions aggregated for Stacked Ensembles, CV metrics),
- early stopping via a ScoreKeeper ring,
- ``Model.predict`` (the ``BigScore`` successor: a batched device scoring
  pass writing a new Frame) and ``model_performance``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Sequence

import jax
import numpy as np

from h2o3_tpu.cluster.job import Job
from h2o3_tpu.cluster.registry import DKV
from h2o3_tpu.frame.frame import CAT, STR, Frame, Vec
from h2o3_tpu.models import metrics as MM
from h2o3_tpu.utils import metrics as _mx
from h2o3_tpu.utils.log import Log
from h2o3_tpu.utils.timer import Timer

_MODELS_BUILT = _mx.counter(
    "models_built_total", "models trained to completion, by algo")


@dataclass
class CommonParams:
    training_frame: Any = None
    validation_frame: Any = None
    response_column: str | None = None
    ignored_columns: Sequence[str] = field(default_factory=tuple)
    weights_column: str | None = None
    offset_column: str | None = None
    nfolds: int = 0
    fold_assignment: str = "modulo"  # modulo | random
    keep_cross_validation_predictions: bool = False
    seed: int = -1
    max_runtime_secs: float = 0.0
    stopping_rounds: int = 0
    stopping_metric: str = "AUTO"
    stopping_tolerance: float = 1e-3
    # key (or Model) of a previous model to CONTINUE training from — more
    # trees for GBM/DRF, more epochs for DeepLearning (ref upstream
    # hex/ModelBuilder checkpoint plumbing, SURVEY.md §5.4)
    checkpoint: Any = None
    export_checkpoints_dir: str | None = None


class ScoreKeeper:
    """Early-stopping ring — successor of ``hex.ScoreKeeper``. H2O stops when
    the moving average of the last k scores stops improving on the best of
    the earlier window by more than the relative tolerance."""

    def __init__(self, rounds: int, tolerance: float, larger_is_better: bool):
        self.rounds = rounds
        self.tol = tolerance
        self.larger = larger_is_better
        self.history: list[float] = []

    def record(self, value: float) -> None:
        self.history.append(float(value))

    def should_stop(self) -> bool:
        k = self.rounds
        if k <= 0 or len(self.history) < 2 * k:
            return False
        h = np.array(self.history, dtype=np.float64)
        recent = h[-k:].mean()
        ref = h[:-k]
        best_ref = ref.max() if self.larger else ref.min()
        if self.larger:
            return recent <= best_ref * (1 + self.tol) - (0 if best_ref >= 0 else 2 * best_ref * self.tol)
        return recent >= best_ref * (1 - self.tol) + (0 if best_ref >= 0 else -2 * best_ref * self.tol)


def stopping_metric_direction(metric: str, classification: bool, nclasses: int) -> tuple[str, bool]:
    """Resolve AUTO and return (metric_name, larger_is_better)."""
    m = metric.lower()
    if m == "auto":
        # AUTO: logloss for classification, deviance for regression (h2o);
        # rmse orders identically to gaussian deviance and is always present
        m = "logloss" if classification else "rmse"
    elif m == "deviance":
        m = "logloss" if classification else "mean_residual_deviance"
    larger = m in ("auc", "pr_auc", "accuracy", "f1", "r2", "lift_top_group")
    return m, larger


class Model:
    """A trained model. Subclasses implement ``_predict_raw``."""

    algo = "base"

    def __init__(self, key: str, params, output: dict):
        self.key = key
        self.params = params
        self.output = output  # names/domains/varimp/... (the Model._output analog)
        self.training_metrics: MM.ModelMetrics | None = None
        self.validation_metrics: MM.ModelMetrics | None = None
        self.cross_validation_metrics: MM.ModelMetrics | None = None
        self.cv_predictions: np.ndarray | None = None  # holdout preds (for SE)
        self.cv_models: list["Model"] = []
        self.scoring_history: list[dict] = []
        self.run_time_ms: int = 0
        # fitted feature transformers (e.g. AutoML target encoding) applied
        # to incoming frames before scoring; transforms must be idempotent
        self.preprocessors: list = []
        DKV.put(key, self)

    def download_mojo(self, path: str) -> str:
        # lazy bootstrap: importing models.export rebinds Model.download_mojo
        # / save_mojo to the real implementation (the h2o surface), so direct
        # model users don't depend on estimator-module import order
        import h2o3_tpu.models.export  # noqa: F401

        return type(self).download_mojo(self, path)

    save_mojo = download_mojo

    # -- to be provided by subclasses ---------------------------------------
    def _predict_raw(self, frame: Frame) -> np.ndarray:
        """Regression: (n,) predictions. Classification: (n, K) class probs."""
        raise NotImplementedError

    # -- public surface ------------------------------------------------------
    @property
    def is_classifier(self) -> bool:
        return self.output.get("response_domain") is not None

    @property
    def nclasses(self) -> int:
        d = self.output.get("response_domain")
        return len(d) if d else 1

    def _apply_preprocessors(self, frame: Frame) -> Frame:
        for pre in self.preprocessors:
            frame = pre.transform(frame)
        return frame

    def predict(self, frame: Frame) -> Frame:
        """``model.predict`` — returns a Frame with ``predict`` (+ per-class
        probability columns for classifiers), matching the H2O layout."""
        frame = self._apply_preprocessors(frame)
        raw = self._predict_raw(frame)
        if not self.is_classifier:
            return Frame([Vec.from_numpy(np.asarray(raw), "real")], ["predict"])
        domain = self.output["response_domain"]
        probs = np.asarray(raw)
        if probs.ndim == 1:
            probs = np.stack([1 - probs, probs], axis=1)
        if self.nclasses == 2:
            # H2O uses max-F1 threshold for the binary label, not argmax
            thr = 0.5
            if self.training_metrics is not None:
                thr = self.training_metrics._v.get("default_threshold", 0.5)
            labels = (probs[:, 1] >= thr).astype(np.int32)
        else:
            labels = probs.argmax(axis=1).astype(np.int32)
        vecs = [Vec.from_numpy(labels, CAT, domain=domain)]
        names = ["predict"]
        for k, d in enumerate(domain):
            vecs.append(Vec.from_numpy(probs[:, k], "real"))
            names.append(str(d))
        cal = self.output.get("calibration")
        if cal is not None and probs.shape[1] == 2:
            from h2o3_tpu.models.calibration import apply_calibration

            cp1 = apply_calibration(cal, probs[:, 1])
            vecs.append(Vec.from_numpy(1.0 - cp1, "real"))
            names.append("cal_p0")
            vecs.append(Vec.from_numpy(cp1, "real"))
            names.append("cal_p1")
        return Frame(vecs, names)

    def model_performance(self, test_data: Frame | None = None) -> MM.ModelMetrics:
        if test_data is None:
            return self.training_metrics
        return self._score_metrics(test_data)

    def _response_and_weights(self, frame: Frame, device: bool = False):
        """The response (classifiers: codes on the model's domain) and the
        weights of ``frame``'s rows. ``device``: as slices of the frame's
        resident columns, for the device statistics, where the codes need
        no remap — a frame with another domain takes the host remap."""
        y_name = self.params.response_column
        yv = frame.vec(y_name)
        remap = (self.is_classifier and yv.is_categorical()
                 and yv.domain != tuple(self.output["response_domain"]))
        on_dev = device and not remap and yv.kind != STR
        if on_dev:
            y = yv.data[: frame.nrow]
        elif remap:
            y = _remap_response(yv, self.output["response_domain"])
        else:
            y = yv.to_numpy()
        w = None
        if self.params.weights_column:
            wv = frame.vec(self.params.weights_column)
            w = wv.data[: frame.nrow] if on_dev else wv.to_numpy()
        return y, w

    def _device_predictor(self):
        """The model's ``_predict_raw_dev(frame)``: ``_predict_raw`` with no
        pull to the host (the same mathematics, jax arrays out). None where
        the model has none, or declines for this fit."""
        return getattr(self, "_predict_raw_dev", None)

    def _score_metrics(self, frame: Frame, raw=None) -> MM.ModelMetrics:
        """On an accelerator a model that can predict there hands device
        arrays to ``_make_metrics``: metrics.py then reduces the sufficient
        statistics on the device and KBs come down instead of a prediction
        column. Everywhere else the predictions are pulled and the host path
        computes the exact float64 summaries. ``raw``: a builder that still
        holds what the raw predictions on ``frame`` come from (GLM: the
        design matrix it fitted on) passes a function that returns them as
        device arrays, and the frame is not predicted on again."""
        predict_dev = (
            self._device_predictor() if jax.default_backend() != "cpu" else None
        )
        path = "host" if predict_dev is None else "device"
        with _mx.span("model.score_metrics", algo=self.algo, path=path):
            frame = self._apply_preprocessors(frame)
            # host: ends in the pull to numpy; device: enqueues, and the
            # metric's pull of its statistics is the sync
            with _mx.span("model.predict_raw"):
                if raw is not None:
                    raw = raw() if predict_dev is not None else np.asarray(raw())
                elif predict_dev is None:
                    raw = np.asarray(self._predict_raw(frame))
                else:
                    raw = predict_dev(frame)
            y, w = self._response_and_weights(
                frame, device=predict_dev is not None)
            return _make_metrics(self, raw, y, w)

    def _distribution_for_metrics(self) -> str:
        return getattr(self.params, "distribution", "gaussian") or "gaussian"

    # -- persistence hooks (export layer fills these in) ---------------------
    def summary(self) -> dict:
        return {
            "algo": self.algo,
            "key": self.key,
            "classification": self.is_classifier,
            "nclasses": self.nclasses,
            "training_metrics": self.training_metrics.to_dict()
            if self.training_metrics
            else None,
            "validation_metrics": self.validation_metrics.to_dict()
            if self.validation_metrics
            else None,
            "run_time_ms": self.run_time_ms,
        }


def _remap_response(yv: Vec, domain) -> np.ndarray:
    if yv.domain == tuple(domain):
        return yv.to_numpy()
    lut = {d: i for i, d in enumerate(domain)}
    remap = np.full(len(yv.domain or ()) + 1, -1, dtype=np.int32)
    for j, d in enumerate(yv.domain or ()):
        remap[j] = lut.get(d, -1)
    codes = yv.to_numpy()
    return np.where(codes >= 0, remap[np.clip(codes, 0, None)], -1)


def _make_metrics(model: Model, raw: np.ndarray, y: np.ndarray, w) -> MM.ModelMetrics:
    if not model.is_classifier:
        return MM.regression_metrics(y, raw, w, model._distribution_for_metrics())
    domain = model.output["response_domain"]
    if raw.ndim == 1 or raw.shape[1] == 1:
        raw = raw.reshape(-1)
        return MM.binomial_metrics(y, raw, w, domain=domain)
    if raw.shape[1] == 2:
        return MM.binomial_metrics(y, raw[:, 1], w, domain=domain)
    if isinstance(y, np.ndarray):  # device codes stay as they are stored
        y = y.astype(np.int64)
    return MM.multinomial_metrics(y, raw, w, domain=domain)


class ModelBuilder:
    """Base builder. Subclasses set ``algo`` / ``PARAMS_CLS`` and implement
    ``_build(job, train, valid) -> Model``."""

    algo = "base"
    PARAMS_CLS = CommonParams
    SUPPORTS_CLASSIFICATION = True
    SUPPORTS_REGRESSION = True
    # builders that honor weights_column can use weight-mask CV folds;
    # the rest fall back to physical row subsetting
    SUPPORTS_WEIGHTS = True

    def __init__(self, **kwargs):
        import dataclasses

        # builder-declared param aliases (XGBoost's eta, GLM's upstream
        # "lambda") resolve to their canonical field name here so every
        # entry point (REST, estimators, direct construction) accepts both
        for alias, canon in (getattr(self, "PARAM_ALIASES", None) or {}).items():
            if alias in kwargs:
                if canon in kwargs:
                    raise ValueError(
                        f"{alias!r} and {canon!r} are aliases — pass one"
                    )
                kwargs[canon] = kwargs.pop(alias)
        valid_names = {f.name for f in dataclasses.fields(self.PARAMS_CLS)}
        unknown = set(kwargs) - valid_names
        if unknown:
            raise ValueError(f"{self.algo}: unknown parameter(s) {sorted(unknown)}")
        self.params = self.PARAMS_CLS(**kwargs)
        self.model: Model | None = None
        self._x: list[str] = []
        # stable key for this build's periodic in-training snapshot (minted
        # on first export; every interval overwrites the same file so the
        # latest interval wins — docs/RECOVERY.md)
        self._ckpt_key: str | None = None

    # -- feature selection (ignored_columns / x handling) --------------------
    def _features(self, frame: Frame, y: str | None) -> list[str]:
        drop = set(self.params.ignored_columns or ())
        if y:
            drop.add(y)
        for extra in (self.params.weights_column, self.params.offset_column, getattr(self.params, "fold_column", None)):
            if extra:
                drop.add(extra)
        feats = [n for n in frame.names if n not in drop and frame.vec(n).kind != "string"]
        return feats

    def train(
        self,
        x: Sequence[str] | None = None,
        y: str | None = None,
        training_frame: Frame | None = None,
        validation_frame: Frame | None = None,
        **kwargs,
    ) -> Model:
        p = self.params
        if training_frame is not None:
            p.training_frame = training_frame
        if validation_frame is not None:
            p.validation_frame = validation_frame
        if y is not None:
            p.response_column = y
        train = _resolve_frame(p.training_frame)
        valid = _resolve_frame(p.validation_frame) if p.validation_frame is not None else None
        assert train is not None, "training_frame is required"
        if x is not None:
            self._x = [train.names[c] if isinstance(c, int) else str(c) for c in x]
        else:
            self._x = self._features(train, p.response_column)

        job = Job(lambda j: self._drive(j, train, valid), f"{self.algo} build")
        # the job's key is the trace id, so the trace opens here, on the
        # calling thread, and Job.start's trace() joins it: `job` parents
        # under `train` in one tree (entered there first, a new trace would
        # root its own tree and `train` would sit in none)
        with _mx.trace(job.key), _mx.span("train", algo=self.algo):
            job.run_sync()
        return self.model

    # -- the Job body --------------------------------------------------------
    def _drive(self, job: Job, train: Frame, valid: Frame | None):
        p = self.params
        t = Timer()
        if getattr(p, "max_runtime_secs", 0.0):
            # soft budget: iterative builders poll job.stop_requested and
            # keep the partial model (h2o's per-model max_runtime contract)
            import time as _time

            job.soft_deadline = _time.time() + float(p.max_runtime_secs)
        self._validate(train, valid)
        if getattr(p, "checkpoint", None) is not None and p.nfolds and p.nfolds > 1:
            raise ValueError("checkpoint cannot be combined with cross-validation")
        with _mx.span(f"{self.algo}.build"):
            model = self._build(job, train, valid)
        model.run_time_ms = int(t.time_ms())
        self.model = model
        _MODELS_BUILT.inc(algo=self.algo)
        # cross-validation driver (after main model, like modern H2O order)
        if p.nfolds and p.nfolds > 1:
            with _mx.span(f"{self.algo}.cv", nfolds=p.nfolds):
                self._cross_validate(job, train)
        if getattr(p, "export_checkpoints_dir", None):
            # H2O semantics: every finished model auto-saves to the dir
            import os

            from h2o3_tpu.persist import save_model

            os.makedirs(p.export_checkpoints_dir, exist_ok=True)
            save_model(model, p.export_checkpoints_dir, force=True)
        Log.info(f"{self.algo} model {model.key} built in {t}")
        return model

    def _validate(self, train: Frame, valid: Frame | None) -> None:
        p = self.params
        if p.response_column is not None:
            assert p.response_column in train, f"response {p.response_column!r} not in frame"
            yv = train.vec(p.response_column)
            if getattr(p, "calibrate_model", False):
                # reject misconfiguration BEFORE the expensive build
                from h2o3_tpu.models.calibration import validate_calibration_params

                validate_calibration_params(p, yv)
            if yv.is_categorical() and not self.SUPPORTS_CLASSIFICATION:
                raise ValueError(f"{self.algo} does not support classification")
            if not yv.is_categorical() and not self.SUPPORTS_REGRESSION and self.algo != "glm":
                raise ValueError(f"{self.algo} does not support regression")

    def _build(self, job: Job, train: Frame, valid: Frame | None) -> Model:
        raise NotImplementedError

    # -- periodic in-training checkpoints (crash durability, SURVEY §5.3) ----
    def _export_interval_checkpoint(self, job: Job | None, make_model) -> str | None:
        """Snapshot the partial model to ``export_checkpoints_dir`` at a
        scoring-interval boundary.

        ``make_model(key)`` builds a throwaway Model holding the CURRENT
        partial state; it is serialized through the standard persist path
        (every-rank device pull, coordinator-only atomic+retried write — the
        ``_exec_model_save`` contract) and removed from the registry again.
        A kill -9 any time after this call loses at most one scoring
        interval: restart, ``load_model`` the snapshot, and pass it as
        ``checkpoint=`` to reproduce the uninterrupted run (pinned by the
        chaos suite). No-op unless ``export_checkpoints_dir`` is set."""
        p = self.params
        ckdir = getattr(p, "export_checkpoints_dir", None)
        if not ckdir:
            return None
        from h2o3_tpu import persist
        from h2o3_tpu.cluster import spmd

        if self._ckpt_key is None:
            self._ckpt_key = DKV.make_key(f"{self.algo}_ckpt")
        key = self._ckpt_key
        model = make_model(key)
        try:
            data = persist.serialize_model(model)  # every-rank pull
            backend, pth = persist.model_path_in_dir(ckdir, key)
            if spmd.is_coordinator():
                persist.write_model_bytes(data, backend, pth, key)
        finally:
            DKV.remove(key)  # snapshots never linger in the registry
        if job is not None:
            # surfaced over /3/Jobs: operators polling a failed job see
            # where to resume from (api/server._job_schema). set_recovery
            # walks the parent chain so the OUTER (REST-visible) job carries
            # the pointer, not just the nested builder job
            info = {
                "checkpoint_key": key,
                "checkpoint_path": pth,
                "hint": "load_model(checkpoint_path), then rebuild with "
                        "checkpoint=checkpoint_key to resume",
            }
            if hasattr(job, "set_recovery"):
                job.set_recovery(info)
            else:  # follower _JobShim
                job.recovery = info
        return pth

    # -- CV driver (successor of ModelBuilder.computeCrossValidation) --------
    def _cross_validate(self, job: Job, train: Frame) -> None:
        p = self.params
        n = train.nrow
        nfolds = int(p.nfolds)
        seed = p.seed if p.seed and p.seed > 0 else 12345
        if getattr(p, "fold_column", None):
            fold = train.vec(p.fold_column).to_numpy().astype(np.int64)
            folds = sorted(set(fold.tolist()))
        elif p.fold_assignment == "random":
            rng = np.random.default_rng(seed)
            fold = rng.integers(0, nfolds, size=n)
            folds = list(range(nfolds))
        else:  # modulo (default, deterministic like h2o AUTO for small data)
            fold = np.arange(n) % nfolds
            folds = list(range(nfolds))

        main = self.model
        # Folds are WEIGHT MASKS over the one padded sharded frame — every
        # fold model trains and predicts on identical shapes, so the compiled
        # programs from fold 1 are reused verbatim by folds 2..k and nothing
        # is re-uploaded (former subset_rows CV re-uploaded and re-compiled
        # per fold). Holdout rows carry weight 0: they contribute nothing to
        # histograms/Gram/SGD, metrics, or leaf values. (Quantile bin edges
        # still see holdout FEATURE values — a label-free approximation.)
        user_w = None
        if getattr(p, "weights_column", None):
            user_w = np.nan_to_num(train.vec(p.weights_column).to_numpy())
        y_all, w_all = None, None
        holdout: np.ndarray | None = None
        fold_metrics = []
        for fi, f in enumerate(folds):
            te_mask = fold == f
            sub = type(self)(**_params_dict(p, drop_cv=True))
            sub.params.response_column = p.response_column
            if self.SUPPORTS_WEIGHTS:
                w_np = (~te_mask).astype(np.float32)
                if user_w is not None:
                    w_np = w_np * user_w.astype(np.float32)
                fr_f = _with_cv_weights(train, w_np)
                sub.params.weights_column = _CV_WEIGHTS
            else:  # weights-unaware builder: physically remove holdout rows
                fr_f = train.subset_rows(~te_mask)
            m = sub.train(x=self._x, y=p.response_column, training_frame=fr_f)
            m_raw = np.asarray(m._predict_raw(train))  # full frame: fold-invariant shapes
            if holdout is None:
                holdout = np.zeros((n,) + m_raw.shape[1:], dtype=np.float64)
            holdout[te_mask] = m_raw[te_mask]
            if y_all is None:
                y_all, w_all = main._response_and_weights(train)
            w_arr = w_all if w_all is not None else np.ones(n)
            fold_metrics.append(
                _make_metrics(m, m_raw[te_mask], y_all[te_mask], np.asarray(w_arr)[te_mask])
            )
            main.cv_models.append(m)
            job.update(0.9 + 0.1 * (fi + 1) / len(folds))

        main.cross_validation_metrics = _make_metrics(main, holdout, y_all, w_all)
        if p.keep_cross_validation_predictions:
            main.cv_predictions = holdout


def resolve_checkpoint(cp) -> "Model | None":
    """Checkpoint param → prior Model (key lookup, pass-through, or — the
    kill→restart→resume runbook — a saved model/snapshot FILE path loaded
    through persist when the key is not in the registry)."""
    if cp is None:
        return None
    if isinstance(cp, Model):
        return cp
    got = DKV.get(str(cp))
    if isinstance(got, Model):
        return got
    try:
        from h2o3_tpu import persist

        backend, p = persist._backend_for(str(cp))
        found = backend.exists(p) and not backend.is_dir(p)
    except (ValueError, NotImplementedError):
        found = False
    if found:
        return persist.load_model(str(cp))
    raise ValueError(
        f"checkpoint {cp!r} is not a model in the DKV (nor a readable "
        "model/snapshot file)"
    )


def check_checkpoint_compat(prior: "Model", builder: "ModelBuilder", frozen: Sequence[str]) -> None:
    """H2O-style checkpoint restrictions: same algo, same feature set, and
    the structural hyperparameters unchanged (only budget params may grow)."""
    if prior.algo != builder.algo:
        raise ValueError(
            f"checkpoint algo {prior.algo!r} does not match builder {builder.algo!r}"
        )
    if list(prior.output.get("names", [])) != list(builder._x):
        raise ValueError("checkpoint was trained on a different feature set")
    for f in frozen:
        a, b = getattr(prior.params, f, None), getattr(builder.params, f, None)
        if a != b:
            raise ValueError(
                f"checkpoint requires {f} unchanged (was {a!r}, now {b!r})"
            )


_CV_WEIGHTS = "__cv_weights__"


def _with_cv_weights(train: Frame, w_np: np.ndarray) -> Frame:
    """A frame SHARING every vec of ``train`` plus the fold-weight column —
    no data movement beyond the single weight upload."""
    from h2o3_tpu.frame.frame import Vec

    wv = Vec.from_numpy(w_np, "num", _CV_WEIGHTS)
    names = [n for n in train.names if n != _CV_WEIGHTS]
    vecs = [train.vec(nm) for nm in names]
    return Frame(vecs + [wv], names + [_CV_WEIGHTS])


def _params_dict(p, drop_cv: bool) -> dict:
    import dataclasses

    d = {f.name: getattr(p, f.name) for f in dataclasses.fields(p)}
    d.pop("training_frame", None)
    d.pop("validation_frame", None)
    if drop_cv:
        d["nfolds"] = 0
        d["keep_cross_validation_predictions"] = False
        # fold models must NOT inherit continuation or auto-save: a checkpoint
        # was trained on all rows (holdout leakage), and export dirs would be
        # overwritten by every fold
        d["checkpoint"] = None
        d["export_checkpoints_dir"] = None
        # fold models' predict frames are never consumed — refitting the
        # calibrator per fold would be pure waste
        if "calibrate_model" in d:
            d["calibrate_model"] = False
    return d


def _resolve_frame(fr) -> Frame | None:
    if fr is None or isinstance(fr, Frame):
        return fr
    got = DKV.get(str(fr))
    assert isinstance(got, Frame), f"no frame under key {fr!r}"
    return got
