"""GBM — successor of ``hex.tree.gbm.GBM`` / ``GBMModel`` [UNVERIFIED
upstream paths, SURVEY.md §2.2, §3.3] on the level-wise histogram builder.

The BASELINE.json north-star loop: per tree, distribution-specific
pseudo-residuals (one fused device op), then per level one ScoreBuildHistogram
pass + split scan + partition update — all XLA on the row-sharded binned
matrix, with psum as the only cross-chip traffic. Leaf values are Newton
steps from the same histogram stats, shrunk by ``learn_rate``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.cluster.job import Job
from h2o3_tpu.cluster.registry import DKV
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models import metrics as MM
from h2o3_tpu.models.model_base import (
    CommonParams,
    Model,
    ModelBuilder,
    ScoreKeeper,
    stopping_metric_direction,
)
from h2o3_tpu.models.tree.binning import MAX_BINS, BinSpec, bin_frame, fit_bins, fit_bins_for
from h2o3_tpu.models.tree.distributions import (
    grad_hess,
    init_score,
    init_score_from_sums,
    multinomial_grad_hess,
    resolve_distribution,
    response_transform,
)
from h2o3_tpu.models.tree.shared_tree import Tree, build_tree
from h2o3_tpu.utils import faults
from h2o3_tpu.utils import metrics as _mx
from h2o3_tpu.utils.log import Log

_RESPONSE_LANES = _mx.counter(
    "tree_response_lanes_total",
    "GBM builds by where their response and weight lanes were made: "
    "path=device (a program over the frame's columns; only the initial "
    "score's sums come down), path=host (the lanes pulled to the host: the "
    "laplace/quantile initial score, the streamed build)", always=True)


@dataclass
class SharedTreeParams(CommonParams):
    ntrees: int = 50
    max_depth: int = 5
    min_rows: float = 10.0
    nbins: int = MAX_BINS  # static quantile bins (h2o re-bins per level at 20)
    # upstream's categorical-bin cap: domains wider than nbins_cats group
    # their tail levels into the last bin (ours additionally caps at the
    # uint8 code space, 254)
    nbins_cats: int = 1024
    # accepted for surface parity; upstream starts each tree at
    # nbins_top_level bins and halves per level down to nbins — the static
    # quantile design bins ONCE, so this knob has no effect here
    nbins_top_level: int = 1024
    min_split_improvement: float = 1e-5
    sample_rate: float = 1.0
    col_sample_rate_per_tree: float = 1.0
    score_tree_interval: int = 5
    # ISSUE 16 leaf-wise growth: "depthwise" (default, upstream's level
    # order) or "lossguide" (xgboost-surface loss-guide — each level's
    # splits are rationed by gain rank against a max_leaves budget; runs on
    # the fused whole-tree lane). max_leaves bounds the leaf count and is
    # only consulted under lossguide.
    grow_policy: str = "depthwise"
    max_leaves: int = 0
    # probability calibration (upstream calibrate_model/calibration_frame on
    # tree models): fits Platt scaling or isotonic regression on a holdout
    # frame's predictions; predict() then appends cal_p0/cal_p1 columns
    calibrate_model: bool = False
    calibration_frame: Any = None
    calibration_method: str = "AUTO"  # AUTO -> PlattScaling | IsotonicRegression


@dataclass
class GBMParams(SharedTreeParams):
    learn_rate: float = 0.1
    learn_rate_annealing: float = 1.0
    distribution: str = "AUTO"
    col_sample_rate: float = 1.0
    max_abs_leafnode_pred: float = float("inf")
    quantile_alpha: float = 0.5
    tweedie_power: float = 1.5
    huber_alpha: float = 0.9
    # {col: +1|-1} monotone direction constraints (numeric features only;
    # enforced via split rejection + child-bound propagation, like upstream)
    monotone_constraints: Any = None


class SharedTreeModel(Model):
    """Common prediction/replay machinery for GBM/DRF/IF models."""

    _REPLAY_FIELDS = (
        "split_col", "split_bin", "is_cat", "cat_mask",
        "na_left", "leaf_now", "leaf_val", "child_base",
    )

    def offered_columns(self, tree_index: int, tree_class: int = 0) -> list:
        """Per level of the tree, ``(nodes, C)`` bool: the columns each node
        was offered for its split (exactly ``mtries`` of them; none on the
        terminal level, which scans nothing), as the builder's split scan
        saw them — ``split_col`` of a decided node is one of its row's."""
        C = len(self.output["names"])
        levels = self.output["trees"][tree_index][tree_class].levels
        return [np.asarray(lv.col_offer)[:, :C] for lv in levels]

    def _replay_all(self, frame: Frame) -> np.ndarray:
        out = self._replay_all_dev(frame)
        return np.asarray(out)[: frame.nrow]

    def _replay_all_dev(self, frame: Frame):
        """Sum of tree contributions per class, DEVICE-resident: (npad, K) or
        (npad,).

        Trees are re-stacked by depth and replayed with ONE dispatch per
        (class, depth) group instead of one per tree per level.
        """
        from collections import defaultdict

        from h2o3_tpu.models.tree.shared_tree import replay_batch

        spec: BinSpec = self.output["bin_spec"]
        bins = bin_frame(spec, frame)
        trees: list[list[Tree]] = self.output["trees"]  # [iter][class]
        K = self.output.get("n_tree_classes", 1)
        npad = bins.shape[0]
        preds = []
        for k in range(K):
            pk = jnp.zeros(npad, jnp.float32)
            by_depth: dict[int, list[Tree]] = defaultdict(list)
            for group in trees:
                t = group[k]
                by_depth[len(t.levels)].append(t)
            for depth, ts in by_depth.items():
                # ONE transfer for the whole group if levels are device-backed
                # (per-field np.asarray would be thousands of ~66 ms pulls)
                vals = jax.device_get(
                    [
                        [
                            [getattr(t.levels[li], f) for f in self._REPLAY_FIELDS]
                            for li in range(depth)
                        ]
                        for t in ts
                    ]
                )
                stacked = tuple(
                    {
                        f: np.stack([vals[ti][li][fi] for ti in range(len(ts))])
                        for fi, f in enumerate(self._REPLAY_FIELDS)
                    }
                    for li in range(depth)
                )
                pk = replay_batch(bins, stacked, pk)
            preds.append(pk)
        return jnp.stack(preds, axis=1) if K > 1 else preds[0]

    def _varimp_table(self):
        vi = self.output.get("varimp")
        if vi is None:
            return None
        names = self.output["names"]
        order = np.argsort(-vi)
        rel = vi / max(vi.max(), 1e-30)
        pct = vi / max(vi.sum(), 1e-30)
        return [
            {
                "variable": names[i],
                "relative_importance": float(vi[i]),
                "scaled_importance": float(rel[i]),
                "percentage": float(pct[i]),
            }
            for i in order
        ]

    def varimp(self):
        return self._varimp_table()

    def predict_contributions(self, frame: Frame) -> Frame:
        """Per-feature SHAP contributions + BiasTerm (hex.tree.TreeSHAP
        successor); Σ row = raw margin."""
        from h2o3_tpu.models.tree.shap import predict_contributions

        return predict_contributions(self, frame)

    def tree_view(self, tree_number: int = 0, tree_class: int = 0) -> dict:
        """Node-table dump of one tree (hex.tree.TreeHandler successor)."""
        from h2o3_tpu.models.tree.shap import tree_view

        return tree_view(self, tree_number, tree_class)

    def predict_leaf_node_assignment(self, frame: Frame, type: str = "Path") -> Frame:
        """Terminal leaf per (row, tree, class): decision-path strings or
        node ids (upstream Model.LeafNodeAssignment contract)."""
        from h2o3_tpu.models.tree.shap import predict_leaf_node_assignment

        return predict_leaf_node_assignment(self, frame, type)

    def model_summary(self) -> dict:
        """The upstream model_summary table for tree models: tree counts
        and the depth/leaf distribution over the forest. Computed once and
        cached (trees are immutable after build; device-backed levels pull
        one batched transfer per tree via Tree.to_host)."""
        cached = self.output.get("_model_summary_cache")
        if cached is not None:
            return cached
        trees = self.output.get("trees") or []
        flat = [t for group in trees for t in group]
        depths = [t.depth for t in flat]
        leaves = [t.n_leaves for t in flat]
        K = self.output.get("n_tree_classes", 1)
        out = {
            "number_of_trees": len(trees),
            "number_of_internal_trees": len(flat),
            "model_size_in_bytes": None,
            "min_depth": int(min(depths)) if depths else 0,
            "max_depth": int(max(depths)) if depths else 0,
            "mean_depth": float(np.mean(depths)) if depths else 0.0,
            "min_leaves": int(min(leaves)) if leaves else 0,
            "max_leaves": int(max(leaves)) if leaves else 0,
            "mean_leaves": float(np.mean(leaves)) if leaves else 0.0,
            "n_classes_per_iteration": K,
        }
        self.output["_model_summary_cache"] = out
        return out


class GBMModel(SharedTreeModel):
    algo = "gbm"

    def _predict_raw(self, frame: Frame) -> np.ndarray:
        # same math as the device flavor (jnp runs fine on the CPU backend);
        # a single implementation keeps the two paths from diverging
        return np.asarray(self._predict_raw_dev(frame))

    def _distribution_for_metrics(self) -> str:
        d = self.output["distribution"]
        return d if d in ("poisson", "gamma", "laplace") else "gaussian"

    def _predict_raw_dev(self, frame: Frame):
        """Device flavor of _predict_raw (same math, jnp end-to-end)."""
        dist = self.output["distribution"]
        raw = self._replay_all_dev(frame)
        if dist == "multinomial":
            F = raw + jnp.asarray(np.asarray(self.output["init_f"]))[None, :]
            return jax.nn.softmax(F, axis=1)[: frame.nrow]
        f = raw + self.output["init_f"]
        if self.params.offset_column and self.params.offset_column in frame:
            f = f + jnp.nan_to_num(frame.vec(self.params.offset_column).data)
        mu = response_transform(dist, f)
        if dist == "bernoulli":
            return jnp.stack([1 - mu, mu], axis=1)[: frame.nrow]
        return mu[: frame.nrow]


class GBM(ModelBuilder):
    algo = "gbm"
    PARAMS_CLS = GBMParams
    MODEL_CLS = GBMModel

    def _partial_model(self, key, p, spec, trees, K, dist, f0, varimp_dev,
                       domain, F, yn, wn, nrow, history) -> Model:
        """The interval-snapshot factory: a scoreable Model holding the
        forest SO FAR, shaped exactly like the final model so ``checkpoint=``
        resume (and plain predict) treat it as a short uninterrupted run."""
        out = {
            "bin_spec": spec,
            "trees": [list(g) for g in trees],
            "n_tree_classes": K,
            "distribution": dist,
            "init_f": f0,
            "names": list(self._x),
            "varimp": np.asarray(varimp_dev).astype(np.float64),
            "response_domain": domain,
            "ntrees_actual": len(trees),
        }
        m = self.MODEL_CLS(key, p, out)
        m.scoring_history = list(history)
        m.training_metrics = _metrics_from_F(dist, F, yn, wn, nrow, domain=domain)
        return m

    def _plan_streamed(self, train: Frame):
        """ChunkStore for this build's lanes, or None for the resident
        path: bins (1 B/row/col) + the six f32 per-row lanes + nid int32."""
        from h2o3_tpu.frame import chunkstore as cs

        return cs.ChunkStore.plan(train.npad, len(self._x) + 28)

    def _build_streamed(self, job, train, valid, p, spec, dist, aux, yv,
                        prior, store, classification, mono_vec=None):
        """Out-of-core GBM: per-block binning into the store's host tier,
        compressed device residency for the source columns, and the
        interval loop driving :func:`build_trees_streamed`. Metrics come
        from the running score lane (host tier) — no resident replay."""
        from collections import defaultdict

        from h2o3_tpu.frame import chunkstore as cs
        from h2o3_tpu.models.tree.shared_tree import (
            build_trees_streamed,
            replay_batch,
        )

        npad, nrow = train.npad, train.nrow
        n_bins = spec.max_bins
        C = len(self._x)
        K = 1
        Log.info(
            f"GBM out-of-core streaming: {store.n_blocks} blocks x "
            f"{store.block_rows} rows through a {store.window} B HBM window"
        )

        # response / weights (host tier; same rules as the resident build)
        y_np = yv.to_numpy().astype(np.float64)
        w_np = np.zeros(npad, np.float32)
        w_np[:nrow] = 1.0
        if p.weights_column:
            w_np[:nrow] *= np.nan_to_num(
                train.vec(p.weights_column).to_numpy()
            ).astype(np.float32)
        w_np[:nrow] *= ~np.isnan(y_np) if not classification else (y_np >= 0)
        ybuf = np.zeros(npad, np.float32)
        ybuf[:nrow] = np.nan_to_num(y_np, nan=0.0)
        spw = float(getattr(p, "scale_pos_weight", 1.0))
        w_train = w_np
        if spw != 1.0:
            if dist != "bernoulli":
                raise ValueError("scale_pos_weight requires a binary response")
            w_train = w_np.copy()
            w_train[:nrow] *= np.where(
                ybuf[:nrow] == 1.0, spw, 1.0
            ).astype(np.float32)
        offset_np = np.zeros(npad, np.float32)
        if p.offset_column:
            offset_np = np.nan_to_num(
                train.vec(p.offset_column).host_values().astype(np.float32)
            )
        wn, yn = w_np, ybuf

        store.add("y", ybuf)
        store.add("w", w_train)
        for name in ("F", "wt", "wy", "wh"):
            store.add_empty(name, (npad,), np.float32)
        store.add_empty("nid", (npad,), np.int32)

        # per-block binning: the binning transform is per-row, so each
        # block lane equals the resident bin_frame row-for-row
        bins_lane = store.add_empty("bins", (npad, C), np.uint8)
        for bi in range(store.n_blocks):
            lo, hi = store.span(bi)
            bf = cs.host_block_frame(train, list(spec.names), lo, hi)
            bins_lane[lo:hi] = np.asarray(
                jax.device_get(bin_frame(spec, bf)))
        # compressed residency: features now live as u8 codes in the host
        # tier; drop their f32/int device copies (lazy rebuild on demand)
        cs.release_frame_features(train, spec.names)

        rngkey = jax.random.PRNGKey(
            abs(p.seed) if p.seed and p.seed > 0 else 1234)
        metric_name, larger = stopping_metric_direction(
            p.stopping_metric, classification, 2)
        keeper = ScoreKeeper(p.stopping_rounds, p.stopping_tolerance, larger)
        history: list[dict] = []
        trees: list[list[Tree]] = []
        varimp_dev = jnp.zeros(C, jnp.float32)
        domain = tuple(yv.domain) if classification else None

        # validation stays resident (a holdout is window-sized in practice;
        # docs/MIGRATION.md fallback matrix)
        bins_v = yv_np = wv_np = Fv = None
        if valid is not None:
            bins_v = bin_frame(spec, valid)
            vv = valid.vec(p.response_column)
            from h2o3_tpu.models.model_base import _remap_response

            yv_np = (
                _remap_response(vv, yv.domain).astype(np.float64)
                if classification else vv.to_numpy().astype(np.float64)
            )
            wv_np = np.ones(valid.nrow, np.float32)
            if p.weights_column and p.weights_column in valid:
                wv_np *= np.nan_to_num(
                    valid.vec(p.weights_column).to_numpy()).astype(np.float32)

        start_trees = 0
        if prior is not None:
            f0 = prior.output["init_f"]
            trees.extend([list(g) for g in prior.output["trees"]])
            varimp_dev = jnp.asarray(
                np.asarray(prior.output["varimp"], np.float32))
            start_trees = prior.output["ntrees_actual"]
            # per-block replay of the prior forest into the running score
            # lane (the resident path's prior._replay_all_dev, blockwise)
            by_depth: dict[int, list[Tree]] = defaultdict(list)
            for group in trees:
                t = group[0]
                by_depth[len(t.levels)].append(t)
            stacked_by_depth = {}
            for depth, ts in by_depth.items():
                vals = jax.device_get(
                    [[[getattr(t.levels[li], f)
                       for f in SharedTreeModel._REPLAY_FIELDS]
                      for li in range(depth)] for t in ts]
                )
                stacked_by_depth[depth] = tuple(
                    {
                        f: np.stack([vals[ti][li][fi]
                                     for ti in range(len(ts))])
                        for fi, f in enumerate(SharedTreeModel._REPLAY_FIELDS)
                    }
                    for li in range(depth)
                )
            for bi, blk in store.stream(("bins",)):
                lo, hi = store.span(bi)
                pk = jnp.asarray(
                    np.float32(f0) + offset_np[lo:hi])
                for depth in stacked_by_depth:
                    pk = replay_batch(blk["bins"], stacked_by_depth[depth], pk)
                store.update(bi, F=pk)
        else:
            f0 = init_score(dist, yn[:nrow], wn[:nrow], aux)
            store.lane("F")[:] = np.float32(f0) + offset_np
        if bins_v is not None:
            offset_v = jnp.zeros(bins_v.shape[0], jnp.float32)
            if p.offset_column and p.offset_column in valid:
                offset_v = jnp.nan_to_num(valid.vec(p.offset_column).data)
            Fv = jnp.full(bins_v.shape[0], np.float32(f0), jnp.float32) + offset_v
            if prior is not None:
                Fv = Fv + prior._replay_all_dev(valid)

        lr = p.learn_rate * (p.learn_rate_annealing ** start_trees)
        interval = max(1, p.score_tree_interval)
        m_done = start_trees
        while m_done < p.ntrees and (
            m_done == start_trees or not job.stop_requested
        ):
            chunk = min(interval, p.ntrees - m_done)
            lrs = lr * (p.learn_rate_annealing ** np.arange(chunk))
            with _mx.span("gbm.build_tree", trees=chunk, tree_offset=m_done,
                          streamed=store.n_blocks):
                new_trees, varimp_dev = build_trees_streamed(
                    store, chunk, base_key=rngkey, tree_offset=m_done,
                    grad_fn=lambda F_, y_, w_: grad_hess(dist, F_, y_, w_, aux),
                    grad_key=("gbm", dist, aux),
                    sample_rate=p.sample_rate,
                    n_bins=n_bins,
                    is_cat_cols=spec.is_cat,
                    max_depth=p.max_depth,
                    min_rows=p.min_rows,
                    min_split_improvement=p.min_split_improvement,
                    learn_rates=lrs,
                    max_abs_leaf=p.max_abs_leafnode_pred,
                    col_sample_rate=p.col_sample_rate,
                    col_sample_rate_per_tree=p.col_sample_rate_per_tree,
                    varimp=varimp_dev,
                    reg_lambda=getattr(p, "reg_lambda", 0.0),
                    reg_alpha=getattr(p, "reg_alpha", 0.0),
                    monotone=mono_vec,
                )
            lr *= p.learn_rate_annealing ** chunk
            trees.extend([[t] for t in new_trees])
            if Fv is not None:
                for t in new_trees:
                    _, Fv = t.replay(
                        bins_v, jnp.zeros(bins_v.shape[0], jnp.int32), Fv)
            m_done += chunk

            F_host = store.lane("F")
            mval = _train_metric(dist, F_host, yn, wn, nrow, metric_name, K)
            entry = {"ntrees": m_done, f"training_{metric_name}": mval}
            stop_val = mval
            if Fv is not None:
                vval = _train_metric(
                    dist, Fv, yv_np, wv_np, valid.nrow, metric_name, K)
                entry[f"validation_{metric_name}"] = vval
                stop_val = vval
            history.append(entry)
            keeper.record(stop_val)
            self._export_interval_checkpoint(
                job,
                lambda key: self._partial_model(
                    key, p, spec, trees, K, dist, f0, varimp_dev, domain,
                    F_host, yn, wn, nrow, history,
                ),
            )
            faults.die_check(self.algo)  # chaos: worker death at boundary
            faults.abort_check(self.algo, m_done)
            faults.slow_check(self.algo)
            if keeper.should_stop():
                Log.info(
                    f"GBM early stop at {m_done} trees "
                    f"({metric_name}={stop_val:.5f})"
                )
                break
            job.update(0.05 + 0.9 * m_done / p.ntrees)

        out = {
            "bin_spec": spec,
            "trees": trees,
            "n_tree_classes": K,
            "distribution": dist,
            "init_f": f0,
            "names": list(self._x),
            "varimp": np.asarray(varimp_dev).astype(np.float64),
            "response_domain": domain,
            "ntrees_actual": len(trees),
        }
        model = self.MODEL_CLS(DKV.make_key(self.algo), p, out)
        model.scoring_history = history
        model.training_metrics = _metrics_from_F(
            dist, store.lane("F"), yn, wn, nrow, domain=domain)
        if valid is not None:
            model.validation_metrics = _metrics_from_F(
                dist, Fv, yv_np, wv_np, valid.nrow, domain=domain)
        store.close()
        from h2o3_tpu.models.calibration import maybe_fit_calibration

        maybe_fit_calibration(self, model)
        return model

    def _build(self, job: Job, train: Frame, valid: Frame | None) -> Model:
        p: GBMParams = self.params
        if p.ntrees < 1 or p.max_depth < 1:
            raise ValueError("ntrees and max_depth must be >= 1")
        yv = train.vec(p.response_column)
        dist, aux = resolve_distribution(
            p.distribution, yv, p.quantile_alpha, p.tweedie_power, p.huber_alpha
        )
        classification = dist in ("bernoulli", "multinomial")
        K = yv.cardinality if dist == "multinomial" else 1

        from h2o3_tpu.models.model_base import check_checkpoint_compat, resolve_checkpoint

        prior = resolve_checkpoint(p.checkpoint)
        if prior is not None:
            check_checkpoint_compat(
                prior, self,
                ("max_depth", "nbins", "min_rows", "distribution", "learn_rate",
                 "sample_rate", "col_sample_rate", "col_sample_rate_per_tree",
                 # xgboost-surface regime params (absent on plain GBMParams;
                 # compat check must tolerate missing fields)
                 "reg_lambda", "reg_alpha", "scale_pos_weight"),
            )
            if p.ntrees <= prior.output["ntrees_actual"]:
                raise ValueError(
                    f"checkpoint continuation needs ntrees > {prior.output['ntrees_actual']}"
                )
            # identical binning is what makes prior trees replayable here
            spec = prior.output["bin_spec"]
        else:
            spec = fit_bins_for(p, train, self._x)

        # monotone constraints resolve BEFORE the lane gates: the streamed
        # lane and the per-level loop accept them
        mono_vec = None
        if p.monotone_constraints:
            if dist not in ("gaussian", "bernoulli", "tweedie", "quantile"):
                raise ValueError(
                    "monotone_constraints supports gaussian/bernoulli/"
                    "tweedie/quantile distributions"
                )
            mono_vec = np.zeros(len(self._x), np.int32)
            for cname, d in dict(p.monotone_constraints).items():
                if int(d) == 0:  # upstream accepts 0 = unconstrained
                    continue
                if cname not in self._x:
                    raise ValueError(f"monotone constraint on unknown column {cname!r}")
                ci = self._x.index(cname)
                if spec.is_cat[ci]:
                    raise ValueError(
                        f"monotone constraint on categorical column {cname!r}"
                    )
                if int(d) not in (-1, 1):
                    raise ValueError("monotone directions must be -1, 0 or 1")
                mono_vec[ci] = int(d)
            if not mono_vec.any():
                mono_vec = None

        # leaf-wise growth (ISSUE 16): lossguide rations each level's splits
        # by gain rank against the remaining max_leaves budget; the budget
        # rides the whole-tree program's level carry, so the policy is
        # whole-tree-only (the per-level host loops, which every monotone
        # build takes, never see it)
        if p.grow_policy not in ("depthwise", "lossguide"):
            raise ValueError(
                f"grow_policy must be 'depthwise' or 'lossguide', got {p.grow_policy!r}"
            )
        from h2o3_tpu.models.tree.shared_tree import use_fused_trees

        max_leaves = 0
        if p.grow_policy == "lossguide":
            if p.max_leaves < 2:
                raise ValueError("grow_policy=lossguide requires max_leaves >= 2")
            if not use_fused_trees(p.max_depth) or mono_vec is not None:
                raise ValueError(
                    "grow_policy=lossguide runs on the fused whole-tree lane "
                    "(H2O3_TPU_WHOLE_TREE=1 within H2O3_TPU_FUSED_MAX_DEPTH) "
                    "and does not combine with monotone_constraints"
                )
            max_leaves = int(p.max_leaves)

        # out-of-core streaming (ISSUE 11, frame/chunkstore.py): when the
        # frame's per-row training lanes exceed the configured HBM window,
        # train as a block-accumulate outer loop around the existing
        # compiled programs instead of materializing the resident arrays.
        # Fallback matrix (docs/MIGRATION.md): multinomial (K per-class
        # trees share row state) stays resident; monotone builds stream
        # too since ISSUE 15 (the bound state is per-node, not per-block).
        if dist != "multinomial":
            stream = self._plan_streamed(train)
            if stream is not None:
                if max_leaves:
                    raise ValueError(
                        "grow_policy=lossguide is resident-only: raise the "
                        "HBM window (H2O3_TPU_HBM_WINDOW_MB) or drop the "
                        "frame below the streaming threshold"
                    )
                _RESPONSE_LANES.inc(path="host")
                return self._build_streamed(
                    job, train, valid, p, spec, dist, aux, yv, prior, stream,
                    classification, mono_vec=mono_vec,
                )

        # response / weights on the device, from the frame's resident columns
        # (span gbm.response_lanes): one program makes the lanes and the sums
        # behind the initial score, and only those sums come down — but for
        # laplace and quantile, whose initial score is an order statistic of
        # the labels and pulls the lanes. Made before bin_frame, so that the
        # pull waits for this program alone and a binning pass runs while the
        # host traces the tree program. xgboost-surface scale_pos_weight
        # (XGBoostParams only) goes into the TRAINING row weights alone:
        # xgboost scales grad/hess (≡ row weights in our Newton leaves) but
        # evaluates metrics unweighted, so the metric weights must not carry it
        with _mx.span("gbm.response_lanes"):
            spw = float(getattr(p, "scale_pos_weight", 1.0))
            if spw != 1.0 and dist != "bernoulli":
                raise ValueError("scale_pos_weight requires a binary response")
            y, w_metric, w_train, sums = _response_lanes(
                yv.data,
                train.vec(p.weights_column).data if p.weights_column else None,
                train.nrow, spw=spw, n_classes=K if dist == "multinomial" else 0,
            )
            w = w_metric if w_train is None else w_train
            lanes_path = "device"
            if prior is not None:
                f0 = prior.output["init_f"]
            elif dist in ("laplace", "quantile"):
                lanes_path = "host"
                f0 = init_score(dist, np.asarray(y)[: train.nrow],
                                np.asarray(w_metric)[: train.nrow], aux)
            else:
                f0 = _initial_score(dist, np.asarray(sums))
        _RESPONSE_LANES.inc(path=lanes_path)

        bins = bin_frame(spec, train)
        n_bins = spec.max_bins
        npad = train.npad

        # EFB (ISSUE 16, H2O3_TPU_TREE_EFB): host-side greedy bundling of
        # mutually-exclusive sparse/one-hot columns into shared u8 code
        # columns — the histogram grid accumulates over the bundled Cb < C
        # axis and expands back to real columns right after (split records,
        # varimp, MOJO and scoring never see bundle space). Whole-tree
        # programs only: a monotone build (per-level loop) or a streamed
        # build (which returns above) skips bundling entirely.
        efb = bins_b = None
        from h2o3_tpu import config as _config

        if _config.get_bool("H2O3_TPU_TREE_EFB"):
            from h2o3_tpu.models.tree.binning import bundle_bins, fit_efb

            if use_fused_trees(p.max_depth) and mono_vec is None:
                efb = fit_efb(spec, bins, nrow=train.nrow)
                if efb is not None:
                    bins_b = bundle_bins(efb, bins)

        offset = jnp.zeros(npad, jnp.float32)
        if p.offset_column:
            offset = jnp.nan_to_num(train.vec(p.offset_column).data)

        rngkey = jax.random.PRNGKey(abs(p.seed) if p.seed and p.seed > 0 else 1234)

        trees: list[list[Tree]] = []
        varimp_dev = jnp.zeros(len(self._x), jnp.float32)
        history: list[dict] = []

        metric_name, larger = stopping_metric_direction(
            p.stopping_metric, classification, K or 2
        )
        keeper = ScoreKeeper(p.stopping_rounds, p.stopping_tolerance, larger)

        # validation scoring state: bin once, replay only new trees per
        # scoring event (H2O scores the validation frame with the current
        # model at each ScoreKeeper tick)
        bins_v = yv_np = wv_np = None
        if valid is not None:
            bins_v = bin_frame(spec, valid)
            vv = valid.vec(p.response_column)
            from h2o3_tpu.models.model_base import _remap_response

            yv_np = (
                _remap_response(vv, yv.domain).astype(np.float64)
                if classification
                else vv.to_numpy().astype(np.float64)
            )
            wv_np = np.ones(valid.nrow, np.float32)
            if p.weights_column and p.weights_column in valid:
                wv_np *= np.nan_to_num(valid.vec(p.weights_column).to_numpy()).astype(
                    np.float32
                )

        # validation offsets enter Fv at init so F-based validation metrics
        # match what a replay-scored prediction (init + offset + trees) gives
        offset_v = None
        if bins_v is not None:
            offset_v = jnp.zeros(bins_v.shape[0], jnp.float32)
            if p.offset_column and p.offset_column in valid:
                offset_v = jnp.nan_to_num(valid.vec(p.offset_column).data)

        if dist == "multinomial":
            F = jnp.tile(jnp.asarray(f0)[None, :], (npad, 1)) + offset[:, None]
            Y1h = (y[:, None] == jnp.arange(K)[None, :]).astype(jnp.float32)
            Fv = (
                [jnp.full(bins_v.shape[0], f0[k], jnp.float32) + offset_v for k in range(K)]
                if bins_v is not None
                else None
            )
        else:
            F = jnp.full(npad, f0, jnp.float32) + offset
            Fv = (
                [jnp.full(bins_v.shape[0], f0, jnp.float32) + offset_v]
                if bins_v is not None
                else None
            )

        # Chunk-scanned path: build a whole scoring interval of trees in ONE
        # device dispatch (see build_trees_scanned). Default on EVERY backend
        # — fewer dispatches and host syncs; on the CPU mesh per-level
        # dispatch overhead × levels × trees was ~a third of build wall-clock.
        # H2O3_TPU_WHOLE_TREE=0 restores the per-tree per-level loop.
        # Monotone builds keep the per-level loop (build_tree's mono step
        # carries the per-node bound state from level to level).
        use_scan = (dist != "multinomial" and use_fused_trees(p.max_depth)
                    and mono_vec is None)

        start_trees = 0
        if prior is not None:
            # continue exactly where the prior model stopped: its init score
            # (f0 above), its trees replayed into F (identical bin spec), its
            # varimp
            raw = prior._replay_all_dev(train)
            if dist == "multinomial":
                F = jnp.asarray(np.asarray(f0))[None, :] + offset[:, None] + raw
            else:
                F = jnp.full(npad, np.float32(f0)) + offset + raw
            trees.extend([list(g) for g in prior.output["trees"]])
            varimp_dev = jnp.asarray(np.asarray(prior.output["varimp"], np.float32))
            start_trees = prior.output["ntrees_actual"]
            if Fv is not None:
                rawv = prior._replay_all_dev(valid)
                if dist == "multinomial":
                    Fv = [
                        jnp.full(bins_v.shape[0], f0[k], jnp.float32) + offset_v + rawv[:, k]
                        for k in range(K)
                    ]
                else:
                    Fv = [jnp.full(bins_v.shape[0], np.float32(f0)) + offset_v + rawv]
            if p.sample_rate < 1.0 and not use_scan:
                # advance the per-tree loop's split chain so continuation
                # equals an uninterrupted run; the scanned path keys by the
                # global tree id off the PRISTINE key and must not advance
                for _ in range(start_trees):
                    rngkey, _ = jax.random.split(rngkey)

        lr = p.learn_rate * (p.learn_rate_annealing**start_trees)

        if use_scan:
            from h2o3_tpu.models.tree.shared_tree import (
                build_trees_scanned,
                replay_batch,
                scan_chunk_cap,
                trees_from_stacked,
            )

            cap = scan_chunk_cap(p.max_depth, n_bins)
            interval = max(1, p.score_tree_interval)
            m_done = start_trees
            # first chunk always runs: a max_runtime that expires during
            # setup/compile must still leave a scoreable 1+-tree model
            # (upstream keeps a non-empty partial model)
            while m_done < p.ntrees and (
                m_done == start_trees or not job.stop_requested
            ):
                chunk = min(interval, cap, p.ntrees - m_done)
                lrs = lr * (p.learn_rate_annealing ** np.arange(chunk))
                with _mx.span("gbm.build_tree", trees=chunk,
                              tree_offset=m_done):
                    F, varimp_dev, stacked = build_trees_scanned(
                        bins, w, y, F, varimp_dev, rngkey, chunk,
                        tree_offset=m_done,
                        grad_fn=lambda F_, y_, w_: grad_hess(dist, F_, y_, w_, aux),
                        grad_key=("gbm", dist, aux),
                        sample_rate=p.sample_rate,
                        n_bins=n_bins,
                        is_cat_cols=spec.is_cat,
                        max_depth=p.max_depth,
                        min_rows=p.min_rows,
                        min_split_improvement=p.min_split_improvement,
                        learn_rates=lrs,
                        max_abs_leaf=p.max_abs_leafnode_pred,
                        col_sample_rate=p.col_sample_rate,
                        col_sample_rate_per_tree=p.col_sample_rate_per_tree,
                        reg_lambda=getattr(p, "reg_lambda", 0.0),
                        reg_alpha=getattr(p, "reg_alpha", 0.0),
                        max_leaves=max_leaves,
                        efb=efb,
                        bins_b=bins_b,
                    )
                lr *= p.learn_rate_annealing ** chunk
                with _mx.span("gbm.pull_records", trees=chunk):
                    trees.extend([[t] for t in trees_from_stacked(stacked, chunk)])
                if Fv is not None:
                    Fv[0] = replay_batch(bins_v, stacked, Fv[0])
                m_done += chunk

                mval = _train_metric(dist, F, y, w_metric, None, metric_name, K)
                entry = {"ntrees": m_done, f"training_{metric_name}": mval}
                stop_val = mval
                if Fv is not None:
                    vval = _train_metric(
                        dist, Fv[0], yv_np, wv_np, valid.nrow, metric_name, K
                    )
                    entry[f"validation_{metric_name}"] = vval
                    stop_val = vval
                history.append(entry)
                keeper.record(stop_val)
                self._export_interval_checkpoint(
                    job,
                    lambda key: self._partial_model(
                        key, p, spec, trees, K, dist, f0, varimp_dev,
                        tuple(yv.domain) if classification else None,
                        F, y, w_metric, None, history,
                    ),
                )
                faults.die_check(self.algo)  # chaos: worker death at boundary
                faults.abort_check(self.algo, m_done)
                faults.slow_check(self.algo)  # chaos: slow training interval
                if keeper.should_stop():
                    Log.info(
                        f"GBM early stop at {m_done} trees ({metric_name}={stop_val:.5f})"
                    )
                    break
                job.update(0.05 + 0.9 * m_done / p.ntrees)

        for m in range(start_trees if not use_scan else p.ntrees, p.ntrees):
            if job.stop_requested and m > start_trees:
                break  # always ≥1 tree (see scan loop comment)
            # row sampling (per tree)
            if p.sample_rate < 1.0:
                rngkey, sk = jax.random.split(rngkey)
                mask = jax.random.bernoulli(sk, p.sample_rate, (npad,)).astype(jnp.float32)
                w_tree = w * mask
            else:
                w_tree = w
            tree_key = jax.random.fold_in(rngkey, m)

            group: list[Tree] = []
            # manual enter/exit keeps the two dist branches unindented; an
            # exception between them kills the whole Job (and its context)
            # so the unexited span leaks nothing
            _tree_span = _mx.span("gbm.build_tree", tree=m)
            _tree_span.__enter__()
            if dist == "multinomial":
                T, H = multinomial_grad_hess(F, Y1h, w_tree, K)
                newF = []
                for k in range(K):
                    tree, fk, varimp_dev = build_tree(
                        bins,
                        w_tree,
                        T[:, k],
                        H[:, k],
                        n_bins=n_bins,
                        is_cat_cols=spec.is_cat,
                        max_depth=p.max_depth,
                        min_rows=p.min_rows,
                        min_split_improvement=p.min_split_improvement,
                        learn_rate=lr,
                        preds=F[:, k],
                        key=jax.random.fold_in(tree_key, k),
                        varimp=varimp_dev,
                        col_sample_rate=p.col_sample_rate,
                        col_sample_rate_per_tree=p.col_sample_rate_per_tree,
                        max_abs_leaf=p.max_abs_leafnode_pred,
                        reg_lambda=getattr(p, "reg_lambda", 0.0),
                        reg_alpha=getattr(p, "reg_alpha", 0.0),
                        max_leaves=max_leaves,
                        efb=efb,
                        bins_b=bins_b,
                    )
                    group.append(tree)
                    newF.append(fk)
                F = jnp.stack(newF, axis=1)
            else:
                t, h = grad_hess(dist, F, y, w_tree, aux)
                tree, F, varimp_dev = build_tree(
                    bins,
                    w_tree,
                    t,
                    h,
                    n_bins=n_bins,
                    is_cat_cols=spec.is_cat,
                    max_depth=p.max_depth,
                    min_rows=p.min_rows,
                    min_split_improvement=p.min_split_improvement,
                    learn_rate=lr,
                    preds=F,
                    key=tree_key,
                    varimp=varimp_dev,
                    col_sample_rate=p.col_sample_rate,
                    col_sample_rate_per_tree=p.col_sample_rate_per_tree,
                    max_abs_leaf=p.max_abs_leafnode_pred,
                    monotone=mono_vec,
                    reg_lambda=getattr(p, "reg_lambda", 0.0),
                    reg_alpha=getattr(p, "reg_alpha", 0.0),
                    max_leaves=max_leaves,
                    efb=efb,
                    bins_b=bins_b,
                )
                group.append(tree)
            _tree_span.__exit__(None, None, None)
            trees.append(group)
            lr *= p.learn_rate_annealing

            if Fv is not None:
                for k, tree in enumerate(group):
                    _, Fv[k] = tree.replay(
                        bins_v, jnp.zeros(bins_v.shape[0], jnp.int32), Fv[k]
                    )

            if (m + 1) % max(1, p.score_tree_interval) == 0 or m == p.ntrees - 1:
                mval = _train_metric(dist, F, y, w_metric, None, metric_name, K)
                entry = {"ntrees": m + 1, f"training_{metric_name}": mval}
                stop_val = mval
                if Fv is not None:
                    Fv_s = jnp.stack(Fv, axis=1) if dist == "multinomial" else Fv[0]
                    vval = _train_metric(
                        dist, Fv_s, yv_np, wv_np, valid.nrow, metric_name, K
                    )
                    entry[f"validation_{metric_name}"] = vval
                    stop_val = vval
                history.append(entry)
                keeper.record(stop_val)
                self._export_interval_checkpoint(
                    job,
                    lambda key: self._partial_model(
                        key, p, spec, trees, K, dist, f0, varimp_dev,
                        tuple(yv.domain) if classification else None,
                        F, y, w_metric, None, history,
                    ),
                )
                faults.die_check(self.algo)  # chaos: worker death at boundary
                faults.abort_check(self.algo, m + 1)
                faults.slow_check(self.algo)  # chaos: slow training interval
                if keeper.should_stop():
                    Log.info(f"GBM early stop at {m + 1} trees ({metric_name}={stop_val:.5f})")
                    break
            job.update(0.05 + 0.9 * (m + 1) / p.ntrees)

        out = {
            "bin_spec": spec,
            "trees": trees,
            "n_tree_classes": K,
            "distribution": dist,
            "init_f": f0,
            "names": list(self._x),
            "varimp": np.asarray(varimp_dev).astype(np.float64),
            "response_domain": tuple(yv.domain) if classification else None,
            "ntrees_actual": len(trees),
        }
        model = self.MODEL_CLS(DKV.make_key(self.algo), p, out)
        model.scoring_history = history
        dom = out["response_domain"]
        # the model's metrics come from the running scores F (no replay of
        # the trees, so no model.predict_raw child); ends in the stats' pull
        with _mx.span("model.score_metrics", algo=self.algo):
            model.training_metrics = _metrics_from_F(
                dist, F, y, w_metric, None, domain=dom
            )
            if valid is not None:
                Fv_s = jnp.stack(Fv, axis=1) if dist == "multinomial" else Fv[0]
                model.validation_metrics = _metrics_from_F(
                    dist, Fv_s, yv_np, wv_np, valid.nrow, domain=dom
                )
        from h2o3_tpu.models.calibration import maybe_fit_calibration

        maybe_fit_calibration(self, model)
        return model


@partial(jax.jit, static_argnames=("spw", "n_classes"))
@jax.named_scope("ph_std")
def _response_lanes(ydata, weights, nrow, spw: float = 1.0, n_classes: int = 0):
    """The resident build's row lanes from the frame's device columns:
    ``y`` (0 where the label is missing: a categorical code < 0, a NaN),
    ``w_metric`` (row valid, ``iota < nrow``, times the frame's weights with
    NaN as 0, times label present), ``w_train`` (``w_metric`` times ``spw``
    on the positive rows; None where ``spw`` is 1) and the sums behind the
    initial score: Σw and Σw·y, or with ``n_classes`` Σw·[y == k] for each
    class and then Σw. The missing-label rules are GLM's
    (``glm._response_lanes``, which the tests hold these lanes to), written
    out here: importing the GLM module (scipy.linalg, through ops/gram.py)
    inside this program's first trace took 1.2 s of a GBM process's first
    build on a TPU v5e host."""
    if jnp.issubdtype(ydata.dtype, jnp.floating):
        yna = jnp.isnan(ydata)
        y = jnp.nan_to_num(ydata.astype(jnp.float32), nan=0.0)
    else:
        yna = ydata < 0
        y = jnp.where(yna, 0, ydata).astype(jnp.float32)
    w = (jnp.arange(ydata.shape[0]) < nrow).astype(jnp.float32)
    if weights is not None:
        w = w * jnp.nan_to_num(weights)
    w = w * (1.0 - yna.astype(jnp.float32))
    sw = w.sum()
    w_train = None
    if spw != 1.0:
        w_train = w * jnp.where(y == 1.0, jnp.float32(spw), jnp.float32(1.0))
    if n_classes:
        per_class = (w[:, None] * (y[:, None] == jnp.arange(n_classes))).sum(0)
        sums = jnp.append(per_class, sw)
    else:
        sums = jnp.stack([sw, (w * y).sum()])
    return y, w, w_train, sums


def _initial_score(dist, sums):
    """f0 from :func:`_response_lanes`' float32 sums, pulled to the host: the
    multinomial log class priors, else ``init_score_from_sums``."""
    if dist == "multinomial":
        *per_class, sw = sums
        prior_p = np.array([max(s / max(sw, 1e-30), 1e-9) for s in per_class])
        return np.log(prior_p).astype(np.float32)
    return init_score_from_sums(dist, *sums)


def _metrics_from_F(dist, F, yn, wn, nrow, domain=None) -> MM.ModelMetrics:
    """Full ModelMetrics from the RUNNING scores — the training loop already
    holds F, so the recorded trees are not replayed to re-derive it. On
    accelerators the transformed scores stay on device (metrics.py reduces
    sufficient statistics there). The resident build hands in its padded
    device lanes whole (``nrow`` None; padded rows weigh 0); host lanes of
    ``nrow`` rows (the streamed build, a validation frame) cut the scores to
    them. On the CPU backend everything is pulled (an allgather across
    processes: the build runs on every rank) and reduced in numpy."""
    from h2o3_tpu.parallel.mesh import pull_to_host

    on_host = jax.default_backend() == "cpu"

    def conv(x):
        x = pull_to_host(x) if on_host else x
        return x if nrow is None else x[:nrow]

    if dist == "multinomial":
        yk = conv(yn)
        if isinstance(yk, np.ndarray):  # the host path indexes by class id
            yk = yk.astype(np.int64)
        return MM.multinomial_metrics(
            yk, conv(jax.nn.softmax(F, axis=1)), conv(wn), domain=domain or ())
    if dist == "bernoulli":
        p1 = conv(response_transform("bernoulli", F))
        return MM.binomial_metrics(conv(yn), p1, conv(wn), domain=domain or ("0", "1"))
    mu = conv(response_transform(dist, F))
    mdist = dist if dist in ("poisson", "gamma", "laplace") else "gaussian"
    return MM.regression_metrics(conv(yn), mu, conv(wn), mdist)


def _train_metric(dist, F, yn, wn, nrow, metric_name, K) -> float:
    """Cheap training metric from the running scores (span
    ``gbm.train_metric``: ends in the pull of the device statistics, so it
    waits for the trees enqueued before it)."""
    with _mx.span("gbm.train_metric", metric=metric_name):
        m = _metrics_from_F(dist, F, yn, wn, nrow)
    v = m._v.get(metric_name)
    if v is None:
        v = m._v.get("logloss" if dist in ("bernoulli", "multinomial") else "rmse")
    return float(v)
