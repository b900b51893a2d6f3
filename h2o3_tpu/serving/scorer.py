"""Compiled, shape-bucketed batch scorers — one jitted program per model
*bucket*, not per model or per batch size, for EVERY algo family the fleet
serves (ISSUE 12 closes ROADMAP item 3c: no mainstream algo falls back to
the slow frame path).

Lanes (fallback matrix in docs/MIGRATION.md):

- **tree** (GBM/XGBoost and DRF/XRT): the forest is pre-stacked ONCE into
  host tensors grouped exactly like ``SharedTreeModel._replay_all_dev`` (by
  class, then by recorded depth, in insertion order — the grouping is
  load-bearing for bit-exactness) and the whole replay + head transform
  (link for the GBM family, tree-averaging for the DRF family) compiles
  into a single program. The stacked forest is a program *argument*, so two
  models of the same shape bucket hit the same compiled program.
- **iforest** (IsolationForest, numeric-feature models): the per-tree
  device walk (``_path_lengths``) scans over the stacked ``(T, L, N)``
  split arrays inside ONE program, accumulating path lengths in the frame
  path's tree order; the host tail (c(n) normalizer, 2^-E[h]/c) reuses the
  identical numpy expressions, so scores are byte-equal.
- **eif** (ExtendedIsolationForest): same shape, with per-level oblique
  hyperplane arrays stacked over trees (short trees pad with leaf levels —
  inert by the walk's ``done`` mask).
- **glm** (binomial/regression/multinomial GLMs): the DataInfo transform
  feeds ONE jitted link-transformed matvec (softmax matmul for
  multinomial) whose coefficient vector is an argument; parity 1e-6.
- **dl** (non-autoencoder DeepLearning): the stacked MLP forward + softmax
  as one jitted program keyed by architecture, parameters as arguments;
  parity 1e-6.
- **generic** (everything else — preprocessed/offset models, ordinal GLM,
  autoencoders, categorical-feature IF): the batch still coalesces into
  one ``model.predict`` pass over a temporary frame.

Model payloads (stacked forests, betas, MLP params) are built once as host
numpy pytrees and uploaded through the device-residency LRU
(:mod:`h2o3_tpu.serving.residency`, ``H2O3_TPU_SERVE_HBM_BYTES``): an idle
model costs host RAM, not HBM, and page-out/page-in round-trips bit-exactly.

Bit-exactness contract (pinned by tests/test_serving.py and
tests/test_serving_fleet.py): tree-family lanes are byte-equal to
``Model.predict`` through the frame path — same replay/walk ops in the
same order, no cross-row reductions anywhere; GLM/DL lanes pin 1e-6.
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.frame.frame import CAT, Frame, Vec
from h2o3_tpu.serving import DISPATCH_SECONDS, SCORER_PROGRAMS

# ---------------------------------------------------------------------------
# payload adaptation (the adaptTestForTrain analog for row payloads)


def _rows_to_table(rows) -> dict[str, list]:
    """list-of-row-dicts | dict-of-columns -> {col: list}."""
    if isinstance(rows, dict):
        out = {str(k): (list(v) if isinstance(v, (list, tuple, np.ndarray))
                        else [v])
               for k, v in rows.items()}
        ns = {len(v) for v in out.values()}
        if len(ns) > 1:
            raise ValueError(f"ragged column table: lengths {sorted(ns)}")
        return out
    if isinstance(rows, (list, tuple)):
        if not rows:
            raise ValueError("rows is empty")
        if not all(isinstance(r, dict) for r in rows):
            raise ValueError("rows must be dicts of {column: value}")
        keys: list[str] = []
        for r in rows:
            for k in r:
                if k not in keys:
                    keys.append(str(k))
        return {k: [r.get(k) for r in rows] for k in keys}
    raise ValueError(f"cannot score rows of type {type(rows).__name__}")


def _coerce_numeric(vals, dtype=np.float32) -> np.ndarray:
    """Payload values -> float with NaN NAs (unparseable strings are NA, the
    parse-time coercion contract). f32 for the binned/stacked lanes; f64
    for lanes whose frame path goes through pandas (GLM/DL design)."""
    out = np.full(len(vals), np.nan, dtype)
    for i, v in enumerate(vals):
        if v is None or (isinstance(v, float) and v != v):
            continue
        if isinstance(v, bool):
            out[i] = 1.0 if v else 0.0
            continue
        if isinstance(v, (int, float, np.integer, np.floating)):
            out[i] = dtype(v)
            continue
        try:
            out[i] = dtype(float(str(v)))
        except (TypeError, ValueError):
            pass  # NA
    return out


def _coerce_cat(vals, domain: tuple) -> np.ndarray:
    """Payload values -> training-domain int32 codes; unseen/None -> -1
    (NA), matching ``_adapt_codes``' unseen-level policy. Numeric payloads
    against a string domain match on their canonical string form ("1" and
    1.0 both hit a "1" level)."""
    lut = {str(d): i for i, d in enumerate(domain or ())}
    out = np.full(len(vals), -1, np.int32)
    for i, v in enumerate(vals):
        if v is None or (isinstance(v, float) and v != v):
            continue
        code = lut.get(v if isinstance(v, str) else str(v), -1)
        if code < 0 and isinstance(v, (int, float, np.integer, np.floating)):
            f = float(v)
            if f.is_integer():
                code = lut.get(str(int(f)), -1)
        out[i] = code
    return out


def bucket_batch_rows(n: int, lo: int = 64) -> int:
    """Batch-row bucket: next power of two (min ``lo`` = one full 8-shard
    row block). Every batch size in a bucket reuses one compiled program —
    the serving twin of the PR-1 row ladder."""
    b = lo
    while b < n:
        b <<= 1
    return b


# ---------------------------------------------------------------------------
# compiled programs, one per lane *structure*; jit's own cache handles the
# shape axes (rows bucket, tree counts, node widths, design columns)


_PROG_CACHE: dict = {}
_SHAPES_SEEN: set = set()
_CACHE_LOCK = threading.Lock()


def _cached_program(struct_key, build):
    prog = _PROG_CACHE.get(struct_key)
    if prog is not None:
        return prog
    prog = build()
    with _CACHE_LOCK:
        _PROG_CACHE.setdefault(struct_key, prog)
    return _PROG_CACHE[struct_key]


def _note_shapes(shape_key) -> None:
    """compile-vs-hit accounting for the serving_scorer_programs_total
    counter (a proxy for jit's per-shape cache, shared across models)."""
    with _CACHE_LOCK:
        seen = shape_key in _SHAPES_SEEN
        _SHAPES_SEEN.add(shape_key)
    SCORER_PROGRAMS.inc(event="hit" if seen else "compile")


def _tree_program(struct_key):
    """One jitted callable per forest *structure*: (head kind, head mode,
    class count, per-class depth-group layout). ``bins`` is donated — it is
    freshly built per batch and dead after the dispatch. The head transform
    mirrors ``GBMModel._predict_raw_dev`` / ``DRFModel._predict_raw_dev``
    op-for-op (the byte-equality contract)."""

    def build():
        head_kind, mode, K = struct_key[0], struct_key[1], struct_key[2]
        from h2o3_tpu.models.tree.distributions import response_transform
        from h2o3_tpu.models.tree.shared_tree import _partition_update

        def run(bins, groups, head):
            outs = []
            for gk in groups:  # per class, by depth like _replay_all_dev
                pk = jnp.zeros(bins.shape[0], jnp.float32)
                for stacked in gk:

                    def body(p, recs):
                        nid = jnp.zeros(bins.shape[0], jnp.int32)
                        for rec in recs:  # unrolled over recorded levels
                            nid, p = _partition_update(
                                bins, nid, p, rec["split_col"],
                                rec["split_bin"], rec["is_cat"],
                                rec["cat_mask"], rec["na_left"],
                                rec["leaf_now"], rec["leaf_val"],
                                rec["child_base"],
                            )
                        return p, None

                    pk, _ = jax.lax.scan(body, pk, stacked)
                outs.append(pk)
            raw = jnp.stack(outs, axis=1) if K > 1 else outs[0]
            if head_kind == "drf":
                avg = raw / head  # head = ntrees (f32 scalar)
                if mode == "reg":
                    return avg
                if mode == "binom":
                    p1 = jnp.clip(avg, 0.0, 1.0)
                    return jnp.stack([1 - p1, p1], axis=1)
                P = jnp.clip(avg, 1e-9, None)
                return P / P.sum(axis=1, keepdims=True)
            # gbm family: head = init_f
            if mode == "multinomial":
                return jax.nn.softmax(raw + head[None, :], axis=1)
            f = raw + head
            mu = response_transform(mode, f)
            if mode == "bernoulli":
                return jnp.stack([1 - mu, mu], axis=1)
            return mu

        return jax.jit(run, donate_argnums=(0,))

    return _cached_program(struct_key, build)


def _iforest_program(struct_key):
    """Scan the frame path's per-tree walk (``_path_lengths``) over the
    stacked forest in insertion order — the accumulation order IS the
    frame path's eager tree loop, so the total is bit-identical."""

    def build():
        n_levels = struct_key[1]
        from h2o3_tpu.models.isolation_forest import _path_lengths

        def run(X, feat, thr, leaf):
            def body(total, tree):
                f, t, ll = tree
                return total + _path_lengths(X, f, t, ll, n_levels), None

            total, _ = jax.lax.scan(
                body, jnp.zeros(X.shape[0], jnp.float32), (feat, thr, leaf))
            return total

        return jax.jit(run)

    return _cached_program(struct_key, build)


def _eif_program(struct_key):
    def build():
        n_levels = struct_key[1]
        from h2o3_tpu.models.extended_isolation_forest import _eif_paths

        def run(X, normals, ds, is_leaf, lens):
            def body(total, tree):
                nr, d_, il, ln = tree
                return total + _eif_paths(X, nr, d_, il, ln, n_levels), None

            total, _ = jax.lax.scan(
                body, jnp.zeros(X.shape[0], jnp.float32),
                (normals, ds, is_leaf, lens))
            return total

        return jax.jit(run)

    return _cached_program(struct_key, build)


def _glm_program(struct_key):
    """Link-transformed matvec (softmax matmul for multinomial) with the
    coefficient vector as an ARGUMENT — one program per family/link config,
    shared by every model that shape-bucket-matches."""

    def build():
        (_, family, link, var_power, link_power, theta, multinomial,
         classifier) = struct_key
        from h2o3_tpu.models.glm import _HI
        from h2o3_tpu.models.glm_families import get_family

        fam = None if multinomial else get_family(
            family, link, var_power, link_power, theta)

        def run(X, beta):
            if multinomial:
                eta = jnp.einsum("np,pk->nk", X, beta, precision=_HI)
                return jax.nn.softmax(eta, axis=1)
            eta = jnp.einsum("np,p->n", X, beta, precision=_HI)
            mu = fam.link.inv(eta)
            if classifier:
                return jnp.stack([1 - mu, mu], axis=1)
            return mu

        return jax.jit(run)

    return _cached_program(struct_key, build)


def _dl_program(struct_key):
    """Stacked MLP forward (+ softmax head) with the parameter pytree as an
    ARGUMENT — one program per architecture."""

    def build():
        _, hidden, activation, n_out, pad, classifier = struct_key
        from h2o3_tpu.models.deeplearning import _MLP

        mlp = _MLP(hidden=tuple(hidden), n_out=n_out, activation=activation,
                   dropout=(0.0,) * len(hidden), input_dropout=0.0)

        def run(X, prm):
            if pad:
                X = jnp.pad(X, ((0, 0), (0, pad)))
            logits = mlp.apply(prm, X, train=False)
            if classifier:
                return jax.nn.softmax(logits, axis=1)
            return logits[:, 0]

        return jax.jit(run)

    return _cached_program(struct_key, build)


def _group_shapes(groups) -> tuple:
    return tuple(
        tuple(
            tuple(sorted((k, v.shape) for k, v in lvl.items()))
            for lvl in stacked
        )
        for gk in groups for stacked in gk
    )


# ---------------------------------------------------------------------------


class BatchScorer:
    """Per-model scorer. ``prepare`` adapts a payload to canonical column
    arrays (cheap host work, runs on the request thread); ``score_table``
    runs one device pass over a whole coalesced batch, holding the model's
    device payload through the residency LRU."""

    def __init__(self, model):
        self.model = model
        self.model_key = model.key
        self.lane = "generic"
        self._lock = threading.Lock()  # one dispatch at a time per model
        self._host_args = None  # numpy pytree; the pageable device payload
        out = model.output if isinstance(model.output, dict) else {}
        if model.preprocessors or getattr(
                model.params, "offset_column", None):
            return  # generic: per-algo preprocessing owns these paths
        from h2o3_tpu.models.deeplearning import DeepLearningModel
        from h2o3_tpu.models.extended_isolation_forest import (
            ExtendedIsolationForestModel,
        )
        from h2o3_tpu.models.glm import GLMModel
        from h2o3_tpu.models.isolation_forest import IsolationForestModel
        from h2o3_tpu.models.tree.gbm import GBMModel, SharedTreeModel

        if (isinstance(model, SharedTreeModel)
                and out.get("trees") and out.get("bin_spec") is not None
                and model.algo in ("gbm", "xgboost", "drf", "xrt")):
            self._init_tree(out, gbm_family=isinstance(model, GBMModel))
        elif (isinstance(model, IsolationForestModel) and out.get("trees")
                and out.get("feature_kinds") is not None
                and (all(k == "num" for k in out["feature_kinds"])
                     or out.get("feature_domains") is not None)):
            # categorical forests ride the lane when the model carries its
            # TRAINING-domain feature codes (ISSUE 14) — payload values
            # then encode through _coerce_cat byte-identically to the
            # frame path's training-domain remap; older snapshots without
            # feature_domains stay numeric-only (generic lane otherwise)
            self._init_iforest(out)
        elif (isinstance(model, ExtendedIsolationForestModel)
                and out.get("stacked_trees")):
            self._init_eif(out)
        elif (isinstance(model, GLMModel) and not out.get("ordinal")
                and out.get("datainfo") is not None
                and not any(c.pair for c in out["datainfo"].columns)):
            self._init_glm(out)
        elif (isinstance(model, DeepLearningModel)
                and not out.get("autoencoder")
                and out.get("datainfo") is not None
                and not any(c.pair for c in out["datainfo"].columns)):
            self._init_dl(out)
        if self._host_args is not None:
            from h2o3_tpu.serving.residency import MANAGER

            MANAGER.register(self)

    # -- lane constructors (host-tier payload stacking) ---------------------
    def _init_tree(self, out, gbm_family: bool) -> None:
        self.lane = "tree"
        self._spec = out["bin_spec"]
        self._K = out.get("n_tree_classes", 1)
        groups = self._stack_forest(out["trees"])
        if gbm_family:
            dist = out["distribution"]
            if dist == "multinomial":
                head = np.asarray(out["init_f"], np.float32)
            else:
                head = np.float32(out["init_f"])
            kind, mode = "gbm", dist
        else:
            m = self.model
            mode = ("reg" if not m.is_classifier
                    else ("binom" if self._K == 1 else "multi"))
            head = np.float32(max(out["ntrees_actual"], 1))
            kind = "drf"
        self._host_args = {"groups": groups, "head": head}
        # partition passes a dispatch runs: every recorded level of every tree
        self._part_levels = sum(
            len(stacked) * len(stacked[0]["split_col"])
            for gk in groups for stacked in gk if stacked)
        self._struct = (
            kind, mode, self._K,
            tuple(tuple(len(s) for s in gk) for gk in groups),
            jax.default_backend(),
        )

    def _stack_forest(self, trees):
        """Stack per-(class, depth) groups in the SAME insertion order as
        ``SharedTreeModel._replay_all_dev`` — the accumulation order is part
        of the bit-exactness contract. Host numpy; the residency LRU owns
        the device copies."""
        from collections import defaultdict

        from h2o3_tpu.models.tree.gbm import SharedTreeModel

        fields = SharedTreeModel._REPLAY_FIELDS
        groups = []
        for k in range(self._K):
            by_depth = defaultdict(list)
            for group in trees:
                t = group[k]
                by_depth[len(t.levels)].append(t)
            gk = []
            for depth, ts in by_depth.items():
                vals = jax.device_get(
                    [
                        [
                            [getattr(t.levels[li], f) for f in fields]
                            for li in range(depth)
                        ]
                        for t in ts
                    ]
                )
                stacked = tuple(
                    {
                        f: np.stack([vals[ti][li][fi]
                                     for ti in range(len(ts))])
                        for fi, f in enumerate(fields)
                    }
                    for li in range(depth)
                )
                gk.append(stacked)
            groups.append(tuple(gk))
        return tuple(groups)

    def _init_iforest(self, out) -> None:
        trees = out["trees"]
        shapes = {np.asarray(f).shape for f, _t, _l in trees}
        if len(shapes) != 1:
            return  # ragged forest (shouldn't happen): generic lane
        self.lane = "iforest"
        self._names = list(out["names"])
        self._domains = list(
            out.get("feature_domains") or [None] * len(self._names))
        self._host_args = {
            "feat": np.stack([np.asarray(f, np.int32) for f, _, _ in trees]),
            "thr": np.stack([np.asarray(t, np.float32)
                             for _, t, _ in trees]),
            "leaf": np.stack([np.asarray(ll, np.float32)
                              for _, _, ll in trees]),
        }
        self._struct = ("iforest", int(shapes.pop()[0]),
                        jax.default_backend())

    def _init_eif(self, out) -> None:
        self.lane = "eif"
        self._names = list(out["names"])
        self._col_means = np.asarray(out["col_means"], np.float64)
        stacked = out["stacked_trees"]
        T = len(stacked)
        C = len(self._names)
        L = max(len(levels) for levels in stacked)
        normals, ds, is_leaf, lens = [], [], [], []
        for d in range(L):
            w = 1 << d
            nr = np.zeros((T, w, C), np.float32)
            dd = np.zeros((T, w), np.float32)
            il = np.ones((T, w), bool)  # pad levels are all-leaf (inert)
            ln = np.zeros((T, w), np.float32)
            for ti, levels in enumerate(stacked):
                if d < len(levels):
                    nr[ti], dd[ti], il[ti], ln[ti] = levels[d]
            normals.append(nr)
            ds.append(dd)
            is_leaf.append(il)
            lens.append(ln)
        self._host_args = {"normals": tuple(normals), "ds": tuple(ds),
                           "is_leaf": tuple(is_leaf), "lens": tuple(lens)}
        self._struct = ("eif", L, C, jax.default_backend())

    def _init_glm(self, out) -> None:
        self.lane = "glm"
        self._di = out["datainfo"]
        p = self.model.params
        multinomial = bool(out.get("multinomial"))
        beta = (out["beta_multinomial_std"] if multinomial
                else out["beta_std"])
        self._host_args = {"beta": np.asarray(beta, np.float32)}
        self._struct = (
            "glm", out["family"], p.link,
            float(p.tweedie_variance_power or 1.5),
            float(p.tweedie_link_power), float(p.theta),
            multinomial, self.model.is_classifier,
        )

    def _init_dl(self, out) -> None:
        self.lane = "dl"
        self._di = out["datainfo"]
        params = jax.device_get(out["params"])
        inner = params["params"] if "params" in params else params
        last = sorted(inner.keys(), key=lambda k: int(k.split("_")[-1]))[-1]
        n_out = int(np.asarray(inner[last]["bias"]).shape[0])
        hidden = tuple(out.get("hidden") or self.model.params.hidden)
        self._host_args = {"params": params}
        self._struct = (
            "dl", tuple(int(h) for h in hidden),
            self.model.params.activation, n_out,
            int(out.get("input_pad") or 0), self.model.is_classifier,
        )

    # -- payload -> canonical columns ---------------------------------------
    def prepare(self, rows) -> tuple[dict[str, np.ndarray], int]:
        table = _rows_to_table(rows)
        ns = {len(v) for v in table.values()}
        if not ns or max(ns) == 0:
            raise ValueError("rows is empty")
        n = ns.pop()
        if self.lane == "tree":
            spec = self._spec
            cols = {}
            for ci, name in enumerate(spec.names):
                vals = table.get(name)
                if vals is None:
                    vals = [None] * n  # absent column scores as all-NA
                if spec.is_cat[ci]:
                    dom = (spec.domains[ci] if spec.domains else None) or ()
                    cols[name] = _coerce_cat(vals, tuple(dom))
                else:
                    cols[name] = _coerce_numeric(vals)
            return cols, n
        if self.lane in ("iforest", "eif"):
            doms = (getattr(self, "_domains", None)
                    if self.lane == "iforest" else None)
            cols = {}
            for ci, name in enumerate(self._names):
                vals = table.get(name) or [None] * n
                dom = doms[ci] if doms else None
                # categorical features encode into TRAINING-domain codes
                # (unseen/None -> -1) — the same floats the frame path's
                # training-domain remap produces, so the lane stays
                # byte-equal on categorical frames too
                cols[name] = (
                    _coerce_cat(vals, tuple(dom)).astype(np.float32)
                    if dom else _coerce_numeric(vals))
            return cols, n
        if self.lane in ("glm", "dl"):
            # normalized to the DataInfo base columns so coalesced batches
            # always concatenate the same column set; the frame-adaptation
            # path (from_pandas kinds + _adapt_codes) does the rest
            cols = {}
            for c in self._di.columns:
                vals = table.get(c.name)
                if vals is None:
                    vals = [None] * n
                if c.kind == "num":
                    cols[c.name] = _coerce_numeric(vals, np.float64)
                else:  # cat / hash: raw values, coded against the frame
                    cols[c.name] = np.asarray(list(vals), dtype=object)
            return cols, n
        # generic lane: raw object columns; the model's own frame-adaptation
        # path (from_pandas kinds + per-algo adapt) does the rest
        return {k: np.asarray(v, dtype=object) for k, v in table.items()}, n

    # -- scoring ------------------------------------------------------------
    def score_table(self, cols: dict[str, np.ndarray], n: int) -> dict:
        from h2o3_tpu.utils import flightrec as _fr

        t0 = time.perf_counter()
        with _fr.dispatch("serving_batch", lane=self.lane,
                          model=self.model_key, rows=n):
            with self._lock:
                if self.lane == "generic":
                    out = self._score_generic(cols, n)
                else:
                    from h2o3_tpu.serving.residency import MANAGER

                    with MANAGER.hold(self) as dev:
                        out = getattr(self, "_score_" + self.lane)(
                            cols, n, dev)
        DISPATCH_SECONDS.observe(time.perf_counter() - t0, lane=self.lane)
        return out

    def _score_tree(self, cols, n: int, dev) -> dict:
        from h2o3_tpu.models.tree.binning import bin_frame
        from h2o3_tpu.models.tree.shared_tree import count_partition_levels

        spec = self._spec
        b = bucket_batch_rows(n)
        vecs, names = [], []
        for ci, name in enumerate(spec.names):
            arr = cols[name]
            if spec.is_cat[ci]:
                pad = np.full(b, -1, np.int32)
                pad[:n] = arr
                dom = (spec.domains[ci] if spec.domains else None) or ()
                vecs.append(Vec.from_numpy(pad, CAT, name=name,
                                           domain=tuple(dom)))
            else:
                pad = np.full(b, np.nan, np.float32)
                pad[:n] = arr
                vecs.append(Vec.from_numpy(pad, "real", name=name))
            names.append(name)
        fr = Frame(vecs, names)  # unregistered temporary
        bins = bin_frame(spec, fr)
        _note_shapes((self._struct, bins.shape,
                      _group_shapes(self._host_args["groups"])))
        prog = _tree_program(self._struct)
        count_partition_levels(self._part_levels)
        raw = np.asarray(jax.device_get(
            prog(bins, dev["groups"], dev["head"])))[:n]
        if not self.model.is_classifier:
            return {"predict": raw.astype(np.float32, copy=False)}
        return self._format_probs(raw, n)

    def _score_iforest(self, cols, n: int, dev) -> dict:
        b = bucket_batch_rows(n)
        X = np.full((b, len(self._names)), np.nan, np.float32)
        for ci, name in enumerate(self._names):
            X[:n, ci] = cols[name]
        _note_shapes((self._struct, X.shape, self._host_args["feat"].shape))
        prog = _iforest_program(self._struct)
        total = np.asarray(jax.device_get(
            prog(jnp.asarray(X), dev["feat"], dev["thr"], dev["leaf"])))[:n]
        ntrees = len(self._host_args["feat"])
        # host tail mirrors IsolationForestModel._predict_raw op-for-op
        from h2o3_tpu.models.isolation_forest import _c

        mean_len = total / ntrees
        cn = _c(self.model.params.sample_size)
        score = np.power(2.0, -mean_len / max(cn, 1e-9))
        return {"predict": np.asarray(score, np.float32),
                "mean_length": np.asarray(mean_len, np.float32)}

    def _score_eif(self, cols, n: int, dev) -> dict:
        b = bucket_batch_rows(n)
        C = len(self._names)
        X64 = np.full((b, C), np.nan, np.float64)
        for ci, name in enumerate(self._names):
            X64[:n, ci] = cols[name].astype(np.float64)
        X = np.where(np.isnan(X64), self._col_means[None, :],
                     X64).astype(np.float32)
        _note_shapes((self._struct, X.shape))
        prog = _eif_program(self._struct)
        total = np.asarray(jax.device_get(prog(
            jnp.asarray(X), dev["normals"], dev["ds"], dev["is_leaf"],
            dev["lens"])))[:n]
        # host tail mirrors ExtendedIsolationForestModel._predict_raw
        from h2o3_tpu.models.extended_isolation_forest import _c

        ntrees = len(self._host_args["normals"][0])
        mean_len = total / max(ntrees, 1)
        score = 2.0 ** (-mean_len / max(_c(self.model.output["sample_size"]),
                                        1e-9))
        return {"anomaly_score": np.asarray(score, np.float32),
                "mean_length": np.asarray(mean_len, np.float32)}

    def _design_matrix(self, cols, n: int):
        """Payload columns -> the model's (padded-bucket, p) design matrix
        through the SAME DataInfo transform as the frame path."""
        import pandas as pd

        b = bucket_batch_rows(n)
        padded = {}
        for name, arr in cols.items():
            if arr.dtype == object:
                buf = np.full(b, None, dtype=object)
            else:
                buf = np.full(b, np.nan, arr.dtype)
            buf[:n] = arr
            padded[name] = buf
        fr = Frame.from_pandas(pd.DataFrame(padded))
        X, _ = self._di.transform(fr)
        return X

    def _score_glm(self, cols, n: int, dev) -> dict:
        X = self._design_matrix(cols, n)
        _note_shapes((self._struct, X.shape, dev["beta"].shape))
        prog = _glm_program(self._struct)
        raw = np.asarray(jax.device_get(prog(X, dev["beta"])))[:n]
        if not self.model.is_classifier:
            return {"predict": raw.astype(np.float32, copy=False)}
        return self._format_probs(raw, n)

    def _score_dl(self, cols, n: int, dev) -> dict:
        X = self._design_matrix(cols, n)
        _note_shapes((self._struct, X.shape))
        prog = _dl_program(self._struct)
        raw = np.asarray(jax.device_get(prog(X, dev["params"])))[:n]
        if not self.model.is_classifier:
            return {"predict": raw.astype(np.float32, copy=False)}
        return self._format_probs(raw, n)

    def _format_probs(self, raw: np.ndarray, n: int) -> dict:
        """Label + probability columns from raw predictions — the same host
        math as ``Model.predict`` (threshold, calibration), so the two
        surfaces cannot disagree."""
        m = self.model
        domain = m.output["response_domain"]
        probs = raw if raw.ndim > 1 else np.stack([1 - raw, raw], axis=1)
        if m.nclasses == 2:
            thr = 0.5
            if m.training_metrics is not None:
                thr = m.training_metrics._v.get("default_threshold", 0.5)
            idx = (probs[:, 1] >= thr).astype(np.int32)
        else:
            idx = probs.argmax(axis=1).astype(np.int32)
        out = {"predict": np.asarray(domain, dtype=object)[idx]}
        for k, d in enumerate(domain):
            out[str(d)] = probs[:, k]
        cal = m.output.get("calibration")
        if cal is not None and probs.shape[1] == 2:
            from h2o3_tpu.models.calibration import apply_calibration

            cp1 = apply_calibration(cal, probs[:, 1])
            out["cal_p0"] = 1.0 - cp1
            out["cal_p1"] = cp1
        return out

    def _score_generic(self, cols, n: int) -> dict:
        import pandas as pd

        df = pd.DataFrame({k: v for k, v in cols.items()})
        fr = Frame.from_pandas(df)
        pf = self.model.predict(fr)
        out = {}
        for name in pf.names:
            v = pf.vec(name)
            if v.is_categorical():
                codes = v.to_numpy()
                dom = np.asarray(v.domain, dtype=object)
                col = np.full(len(codes), None, dtype=object)
                ok = codes >= 0
                col[ok] = dom[codes[ok]]
                out[name] = col[:n]
            else:
                out[name] = v.to_numpy()[:n]
        return out


def scorer_for(model) -> BatchScorer:
    """The per-model scorer, cached on the model object (models are
    immutable after build; the cache — and the residency entry, via its
    weakref — dies with the model)."""
    sc = model.__dict__.get("_h2o3_batch_scorer")
    if sc is None:
        sc = BatchScorer(model)
        model.__dict__["_h2o3_batch_scorer"] = sc
    return sc
