"""DRF (distributed random forest) — successor of ``hex.tree.drf.DRF`` /
``DRFModel`` [UNVERIFIED upstream paths, SURVEY.md §2.2] on the shared
level-wise histogram builder.

Differences from GBM, mirroring H2O: bootstrap row sampling per tree
(``sample_rate`` without replacement ≈ bernoulli mask), per-split column
subsampling (``mtries``: √C for classification, C/3 for regression), deep
trees (default depth 20, enabled by the active-leaf frontier), leaf values =
node means (learn_rate 1), predictions averaged across trees; for multiclass
one tree per class per iteration on the one-hot indicator.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.cluster.job import Job
from h2o3_tpu.cluster.registry import DKV
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.model_base import ScoreKeeper, stopping_metric_direction
from h2o3_tpu.models.tree.binning import bin_frame, fit_bins, fit_bins_for
from h2o3_tpu.models.tree.gbm import SharedTreeModel, SharedTreeParams
from h2o3_tpu.models.tree.shared_tree import Tree, bootstrap_mask, build_tree
from h2o3_tpu.models import metrics as MM
from h2o3_tpu.models.model_base import ModelBuilder
from h2o3_tpu.utils import faults
from h2o3_tpu.utils import metrics as _mx
from h2o3_tpu.utils.log import Log


@dataclass
class DRFParams(SharedTreeParams):
    ntrees: int = 50
    max_depth: int = 20
    min_rows: float = 1.0
    mtries: int = -1
    sample_rate: float = 0.632
    binomial_double_trees: bool = False


class DRFModel(SharedTreeModel):
    algo = "drf"

    def inbag_rows(self, tree_index: int) -> np.ndarray:
        """``(npad,)`` bool: the training rows in tree ``tree_index``'s bag
        (pad rows never), re-derived from what the model stores — the row
        key, the sample rate and the frame's padded length — through
        :func:`shared_tree.bootstrap_mask`, the function the builder's
        program called. A bagged row whose weight is zero (a weights column,
        a missing response) still counted for nothing."""
        bag = self.output["bootstrap"]
        first, n = bag["first_tree"], self.output["ntrees_actual"]
        if not first <= tree_index < n:
            raise ValueError(
                f"tree {tree_index}: this model drew the bags of trees "
                f"{first}..{n - 1} (earlier ones are its checkpoint's)")
        npad = bag["npad"]
        if bag["sample_rate"] >= 1.0:
            mask = np.ones(npad, bool)
        else:
            mask = np.asarray(bootstrap_mask(
                jnp.asarray(bag["row_key"]), tree_index, bag["sample_rate"],
                (npad,)))
        return mask & (np.arange(npad) < bag["nrow"])

    def _predict_raw(self, frame: Frame) -> np.ndarray:
        return np.asarray(self._predict_raw_dev(frame))

    def _predict_raw_dev(self, frame: Frame):
        # sum of per-tree leaf means, averaged
        raw = self._replay_all_dev(frame)[: frame.nrow]
        ntrees = max(self.output["ntrees_actual"], 1)
        avg = raw / ntrees
        if not self.is_classifier:
            return avg
        if self.nclasses == 2:
            p1 = jnp.clip(avg, 0.0, 1.0)
            return jnp.stack([1 - p1, p1], axis=1)
        P = jnp.clip(avg, 1e-9, None)
        return P / P.sum(axis=1, keepdims=True)


class DRF(ModelBuilder):
    algo = "drf"
    PARAMS_CLS = DRFParams
    MODEL_CLS = DRFModel

    # XRT ("extremely randomized trees") reuses this builder via the
    # histogram_type=Random analog — see XRT subclass below.
    _extra_random = False

    def _partial_model(self, key, p, spec, trees, n_out, domain, F, yn, wn,
                       nrow, K, classification, varimp_dev, history):
        """Interval-snapshot factory (see GBM._partial_model)."""
        out = {
            "bootstrap": self._bootstrap,
            "bin_spec": spec,
            "trees": [list(g) for g in trees],
            "n_tree_classes": n_out,
            "names": list(self._x),
            "varimp": np.asarray(varimp_dev).astype(np.float64),
            "response_domain": domain,
            "ntrees_actual": len(trees),
        }
        m = self.MODEL_CLS(key, p, out)
        m.scoring_history = list(history)
        m.training_metrics = self._metrics_from_F(
            F, yn, wn, nrow, max(len(trees), 1), K, classification, domain=domain
        )
        return m

    def _build(self, job: Job, train: Frame, valid: Frame | None):
        p: DRFParams = self.params
        if p.ntrees < 1 or p.max_depth < 1:
            raise ValueError("ntrees and max_depth must be >= 1")
        yv = train.vec(p.response_column)
        classification = yv.is_categorical()
        K = yv.cardinality if classification and yv.cardinality > 2 else 1
        binary = classification and K == 1

        from h2o3_tpu.models.model_base import check_checkpoint_compat, resolve_checkpoint

        prior = resolve_checkpoint(p.checkpoint)
        if prior is not None:
            check_checkpoint_compat(
                prior, self,
                ("max_depth", "nbins", "min_rows", "mtries", "sample_rate"),
            )
            if p.ntrees <= prior.output["ntrees_actual"]:
                raise ValueError(
                    f"checkpoint continuation needs ntrees > {prior.output['ntrees_actual']}"
                )
            spec = prior.output["bin_spec"]
        else:
            spec = fit_bins_for(p, train, self._x)
        bins = bin_frame(spec, train)
        n_bins = spec.max_bins
        npad = train.npad
        C = len(self._x)

        mtries = p.mtries
        if mtries in (-1, 0):
            mtries = max(1, int(np.sqrt(C))) if classification else max(1, C // 3)
        elif mtries == -2:
            mtries = C
        col_rate = min(1.0, mtries / C)

        # response / weights on device (span drf.response_lanes, as GBM's:
        # starts with the label's pull, ends in enqueued uploads)
        with _mx.span("drf.response_lanes"):
            y_np = yv.to_numpy().astype(np.float64)
            w_np = np.zeros(npad, np.float32)
            w_np[: train.nrow] = 1.0
            if p.weights_column:
                w_np[: train.nrow] *= np.nan_to_num(
                    train.vec(p.weights_column).to_numpy()
                ).astype(np.float32)
            w_np[: train.nrow] *= (y_np >= 0) if classification else ~np.isnan(y_np)
            ybuf = np.zeros(npad, np.float32)
            ybuf[: train.nrow] = np.nan_to_num(y_np, nan=0.0)
            w = jnp.asarray(w_np)
            y = jnp.asarray(ybuf)
        wn, yn = w_np, ybuf  # host copies already exist — never pull from device

        rngkey = jax.random.PRNGKey(abs(p.seed) if p.seed and p.seed > 0 else 5678)
        row_key = rngkey  # pristine: the bags are keyed by it and the tree's index

        n_out = K if K > 1 else 1
        F = [jnp.zeros(npad, jnp.float32) for _ in range(n_out)]
        if K > 1:
            targets = [(y == k).astype(jnp.float32) for k in range(K)]
        else:
            targets = [y]

        metric_name, larger = stopping_metric_direction(
            p.stopping_metric, classification, K or 2
        )
        keeper = ScoreKeeper(p.stopping_rounds, p.stopping_tolerance, larger)
        trees: list[list[Tree]] = []
        varimp_dev = jnp.zeros(C, jnp.float32)
        history: list[dict] = []

        bins_v = yv_np = wv_np = Fv = None
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
            Fv = [jnp.zeros(bins_v.shape[0], jnp.float32) for _ in range(n_out)]

        start_trees = 0
        if prior is not None:
            raw = prior._replay_all_dev(train)  # (npad,) or (npad, K) leaf-sum
            F = [raw[:, k] for k in range(K)] if n_out > 1 else [raw]
            trees.extend([list(g) for g in prior.output["trees"]])
            varimp_dev = jnp.asarray(np.asarray(prior.output["varimp"], np.float32))
            start_trees = prior.output["ntrees_actual"]
            if Fv is not None:
                rawv = prior._replay_all_dev(valid)
                Fv = [rawv[:, k] for k in range(K)] if n_out > 1 else [rawv]
            from h2o3_tpu.models.tree.shared_tree import use_fused_trees

            if not use_fused_trees(p.max_depth):
                # only the per-tree loop consumes the split chain; the
                # scanned path keys by global tree id off the pristine key
                for _ in range(start_trees):
                    rngkey, _ = jax.random.split(rngkey)

        # what DRFModel.inbag_rows re-derives a tree's bag from
        self._bootstrap = {
            "row_key": np.asarray(row_key), "sample_rate": float(p.sample_rate),
            "npad": int(npad), "nrow": int(train.nrow),
            "first_tree": int(start_trees),
        }

        # Chunk-scanned path (see gbm.py / build_trees_scanned): one device
        # dispatch per scoring interval per class, on every backend. The
        # bootstrap row mask is keyed by the shared row_key so all K
        # class-trees of iteration m draw the SAME bootstrap (H2O
        # semantics), while column/level randomness differs per class.
        # depth policy lives in use_fused_trees (depth-20 DRF — the H2O
        # default regime — runs its saturated levels as an on-device
        # lax.while_loop with early exit, so the scanned path holds at any
        # depth; H2O3_TPU_WHOLE_TREE=0 restores the per-level loop)
        from h2o3_tpu.models.tree.shared_tree import use_fused_trees

        use_scan = use_fused_trees(p.max_depth)
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
            # first chunk always runs (≥1 tree even if max_runtime expired
            # during setup — upstream keeps a non-empty partial model)
            while m_done < p.ntrees and (
                m_done == start_trees or not job.stop_requested
            ):
                chunk = min(interval, cap, p.ntrees - m_done)
                chunk_trees: list[list[Tree]] = [[] for _ in range(chunk)]
                for k in range(n_out):
                    with _mx.span("drf.build_tree", trees=chunk,
                                  tree_offset=m_done):
                        F[k], varimp_dev, stacked = build_trees_scanned(
                            bins, w, targets[k], F[k], varimp_dev,
                            jax.random.fold_in(rngkey, 7919 + k), chunk,
                            row_key=row_key,
                            tree_offset=m_done,
                            grad_fn=lambda F_, y_, w_: (y_, w_),  # leaf = node mean
                            grad_key=("drf",),
                            sample_rate=p.sample_rate,
                            n_bins=n_bins,
                            is_cat_cols=spec.is_cat,
                            max_depth=p.max_depth,
                            min_rows=p.min_rows,
                            min_split_improvement=p.min_split_improvement,
                            learn_rates=np.ones(chunk, np.float32),
                            max_abs_leaf=float("inf"),
                            col_sample_rate=col_rate,
                            col_sample_rate_per_tree=1.0,
                        )
                    # waits for the chunk — unless build_tree already did: a
                    # tree with a saturated region syncs on its executed
                    # levels (_run_counted), and the busy time shows there
                    with _mx.span("drf.pull_records", trees=chunk):
                        for ti, tr in enumerate(trees_from_stacked(stacked, chunk)):
                            chunk_trees[ti].append(tr)
                    if Fv is not None:
                        Fv[k] = replay_batch(bins_v, stacked, Fv[k])
                trees.extend(chunk_trees)
                m_done += chunk

                mval = self._train_metric(
                    F, yn, wn, train.nrow, m_done, K, classification, metric_name
                )
                entry = {"ntrees": m_done, f"training_{metric_name}": mval}
                stop_val = mval
                if Fv is not None:
                    vval = self._train_metric(
                        Fv, yv_np, wv_np, valid.nrow, m_done, K, classification,
                        metric_name,
                    )
                    entry[f"validation_{metric_name}"] = vval
                    stop_val = vval
                history.append(entry)
                keeper.record(stop_val)
                self._export_interval_checkpoint(
                    job,
                    lambda key: self._partial_model(
                        key, p, spec, trees, n_out,
                        tuple(yv.domain) if classification else None,
                        F, yn, wn, train.nrow, K, classification,
                        varimp_dev, history,
                    ),
                )
                faults.die_check(self.algo)  # chaos: worker death at boundary
                faults.abort_check(self.algo, m_done)
                faults.slow_check(self.algo)  # chaos: slow training interval
                if keeper.should_stop():
                    Log.info(f"DRF early stop at {m_done} trees")
                    break
                job.update(0.05 + 0.9 * m_done / p.ntrees)

        for m in range(start_trees if not use_scan else p.ntrees, p.ntrees):
            if job.stop_requested and m > start_trees:
                break  # always ≥1 tree (see scan loop comment)
            rngkey, _ = jax.random.split(rngkey)
            mask = bootstrap_mask(row_key, m, p.sample_rate, (npad,))
            w_tree = w * mask.astype(jnp.float32)
            group = []
            tree_key = jax.random.fold_in(rngkey, m)
            for k in range(n_out):
                with _mx.span("drf.build_tree", tree=m):
                    tree, fk, varimp_dev = build_tree(
                        bins,
                        w_tree,
                        targets[k],
                        w_tree,  # hessian = weight → leaf = node mean
                        n_bins=n_bins,
                        is_cat_cols=spec.is_cat,
                        max_depth=p.max_depth,
                        min_rows=p.min_rows,
                        min_split_improvement=p.min_split_improvement,
                        learn_rate=1.0,
                        preds=F[k],
                        key=jax.random.fold_in(tree_key, k),
                        varimp=varimp_dev,
                        col_sample_rate=col_rate,
                    )
                group.append(tree)
                F[k] = fk
            trees.append(group)

            if Fv is not None:
                for k, tree in enumerate(group):
                    _, Fv[k] = tree.replay(
                        bins_v, jnp.zeros(bins_v.shape[0], jnp.int32), Fv[k]
                    )

            if (m + 1) % max(1, p.score_tree_interval) == 0 or m == p.ntrees - 1:
                mval = self._train_metric(F, yn, wn, train.nrow, m + 1, K, classification, metric_name)
                entry = {"ntrees": m + 1, f"training_{metric_name}": mval}
                stop_val = mval
                if Fv is not None:
                    vval = self._train_metric(
                        Fv, yv_np, wv_np, valid.nrow, m + 1, K, classification, metric_name
                    )
                    entry[f"validation_{metric_name}"] = vval
                    stop_val = vval
                history.append(entry)
                keeper.record(stop_val)
                self._export_interval_checkpoint(
                    job,
                    lambda key: self._partial_model(
                        key, p, spec, trees, n_out,
                        tuple(yv.domain) if classification else None,
                        F, yn, wn, train.nrow, K, classification,
                        varimp_dev, history,
                    ),
                )
                faults.die_check(self.algo)  # chaos: worker death at boundary
                faults.abort_check(self.algo, m + 1)
                faults.slow_check(self.algo)  # chaos: slow training interval
                if keeper.should_stop():
                    Log.info(f"DRF early stop at {m + 1} trees")
                    break
            job.update(0.05 + 0.9 * (m + 1) / p.ntrees)

        out = {
            "bootstrap": self._bootstrap,
            "bin_spec": spec,
            "trees": trees,
            "n_tree_classes": n_out,
            "names": list(self._x),
            "varimp": np.asarray(varimp_dev).astype(np.float64),
            "response_domain": tuple(yv.domain) if classification else None,
            "ntrees_actual": len(trees),
        }
        model = DRFModel(DKV.make_key("drf"), p, out)
        model.scoring_history = history
        nt = max(len(trees), 1)
        dom = out["response_domain"]
        # from the running sums F (no replay of the trees), over ALL rows of
        # the frame, in-bag and out: H2O reports out-of-bag training metrics
        # (ROADMAP B-R); ends in the statistics' pull
        with _mx.span("model.score_metrics", algo=self.algo):
            model.training_metrics = self._metrics_from_F(
                F, yn, wn, train.nrow, nt, K, classification, domain=dom
            )
            if valid is not None:
                model.validation_metrics = self._metrics_from_F(
                    Fv, yv_np, wv_np, valid.nrow, nt, K, classification, domain=dom
                )
        from h2o3_tpu.models.calibration import maybe_fit_calibration

        maybe_fit_calibration(self, model)
        return model

    def _metrics_from_F(self, F, yn, wn, nrow, ntrees, K, classification, domain=None):
        """Full ModelMetrics from the running per-class sums (no replay)."""
        dev = jax.default_backend() != "cpu"
        avg = [(f[:nrow] if dev else np.asarray(f)[:nrow]) / ntrees for f in F]
        xp = jnp if dev else np
        if K > 1:
            P = xp.stack(avg, axis=1)
            P = xp.clip(P, 1e-9, None)
            P = P / P.sum(axis=1, keepdims=True)
            return MM.multinomial_metrics(
                yn[:nrow].astype(np.int64), P, wn[:nrow], domain=domain or ()
            )
        if classification:
            p1 = xp.clip(avg[0], 0.0, 1.0)
            return MM.binomial_metrics(
                yn[:nrow], p1, wn[:nrow], domain=domain or ("0", "1")
            )
        return MM.regression_metrics(yn[:nrow], avg[0], wn[:nrow])

    def _train_metric(self, F, yn, wn, nrow, ntrees, K, classification, metric_name) -> float:
        """Span ``drf.train_metric``: ends in the pull of the statistics."""
        with _mx.span("drf.train_metric", metric=metric_name):
            m = self._metrics_from_F(F, yn, wn, nrow, ntrees, K, classification)
        v = m._v.get(metric_name)
        if v is None:
            v = m._v.get("logloss" if classification else "rmse")
        return float(v)


class XRT(DRF):
    """Extremely-randomized-trees variant — H2O exposes XRT as DRF with
    ``histogram_type="Random"`` (random split points). Approximated here by
    stronger per-split column subsampling plus a distinct seed stream; true
    random-threshold selection is a planned histogram option."""

    algo = "xrt"
    _extra_random = True
