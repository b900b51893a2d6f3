"""GENERATED FILE — do not edit. Regenerate with tools/gen_bindings.py.

Explicit per-algorithm estimator classes rendered from the builder params
dataclasses (the codegen analog of upstream's h2o-bindings output).
"""

from h2o3_tpu.estimators import _EstimatorBase



class H2OGradientBoostingEstimator(_EstimatorBase):
    """GBM estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    ntrees: int (default 50)
    max_depth: int (default 5)
    min_rows: float (default 10.0)
    nbins: int (default 255)
    nbins_cats: int (default 1024)
    nbins_top_level: int (default 1024)
    min_split_improvement: float (default 1e-05)
    sample_rate: float (default 1.0)
    col_sample_rate_per_tree: float (default 1.0)
    score_tree_interval: int (default 5)
    grow_policy: str (default 'depthwise')
    max_leaves: int (default 0)
    calibrate_model: bool (default False)
    calibration_frame: Any (default None)
    calibration_method: str (default 'AUTO')
    learn_rate: float (default 0.1)
    learn_rate_annealing: float (default 1.0)
    distribution: str (default 'AUTO')
    col_sample_rate: float (default 1.0)
    max_abs_leafnode_pred: float (default float("inf"))
    quantile_alpha: float (default 0.5)
    tweedie_power: float (default 1.5)
    huber_alpha: float (default 0.9)
    monotone_constraints: Any (default None)
    """

    _BUILDER = "GBM"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        ntrees=50,
        max_depth=5,
        min_rows=10.0,
        nbins=255,
        nbins_cats=1024,
        nbins_top_level=1024,
        min_split_improvement=1e-05,
        sample_rate=1.0,
        col_sample_rate_per_tree=1.0,
        score_tree_interval=5,
        grow_policy='depthwise',
        max_leaves=0,
        calibrate_model=False,
        calibration_frame=None,
        calibration_method='AUTO',
        learn_rate=0.1,
        learn_rate_annealing=1.0,
        distribution='AUTO',
        col_sample_rate=1.0,
        max_abs_leafnode_pred=float("inf"),
        quantile_alpha=0.5,
        tweedie_power=1.5,
        huber_alpha=0.9,
        monotone_constraints=None,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            ntrees=ntrees,
            max_depth=max_depth,
            min_rows=min_rows,
            nbins=nbins,
            nbins_cats=nbins_cats,
            nbins_top_level=nbins_top_level,
            min_split_improvement=min_split_improvement,
            sample_rate=sample_rate,
            col_sample_rate_per_tree=col_sample_rate_per_tree,
            score_tree_interval=score_tree_interval,
            grow_policy=grow_policy,
            max_leaves=max_leaves,
            calibrate_model=calibrate_model,
            calibration_frame=calibration_frame,
            calibration_method=calibration_method,
            learn_rate=learn_rate,
            learn_rate_annealing=learn_rate_annealing,
            distribution=distribution,
            col_sample_rate=col_sample_rate,
            max_abs_leafnode_pred=max_abs_leafnode_pred,
            quantile_alpha=quantile_alpha,
            tweedie_power=tweedie_power,
            huber_alpha=huber_alpha,
            monotone_constraints=monotone_constraints,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'ntrees': 50,
            'max_depth': 5,
            'min_rows': 10.0,
            'nbins': 255,
            'nbins_cats': 1024,
            'nbins_top_level': 1024,
            'min_split_improvement': 1e-05,
            'sample_rate': 1.0,
            'col_sample_rate_per_tree': 1.0,
            'score_tree_interval': 5,
            'grow_policy': 'depthwise',
            'max_leaves': 0,
            'calibrate_model': False,
            'calibration_frame': None,
            'calibration_method': 'AUTO',
            'learn_rate': 0.1,
            'learn_rate_annealing': 1.0,
            'distribution': 'AUTO',
            'col_sample_rate': 1.0,
            'max_abs_leafnode_pred': float("inf"),
            'quantile_alpha': 0.5,
            'tweedie_power': 1.5,
            'huber_alpha': 0.9,
            'monotone_constraints': None,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OXGBoostEstimator(_EstimatorBase):
    """XGBoost estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    ntrees: int (default 50)
    max_depth: int (default 6)
    min_rows: float (default 1.0)
    nbins: int (default 255)
    nbins_cats: int (default 1024)
    nbins_top_level: int (default 1024)
    min_split_improvement: float (default 0.0)
    sample_rate: float (default 1.0)
    col_sample_rate_per_tree: float (default 1.0)
    score_tree_interval: int (default 5)
    grow_policy: str (default 'depthwise')
    max_leaves: int (default 0)
    calibrate_model: bool (default False)
    calibration_frame: Any (default None)
    calibration_method: str (default 'AUTO')
    learn_rate: float (default 0.3)
    learn_rate_annealing: float (default 1.0)
    distribution: str (default 'AUTO')
    col_sample_rate: float (default 1.0)
    max_abs_leafnode_pred: float (default float("inf"))
    quantile_alpha: float (default 0.5)
    tweedie_power: float (default 1.5)
    huber_alpha: float (default 0.9)
    monotone_constraints: Any (default None)
    reg_lambda: float (default 1.0)
    reg_alpha: float (default 0.0)
    tree_method: str (default 'auto')
    booster: str (default 'gbtree')
    scale_pos_weight: float (default 1.0)
    dmatrix_type: str (default 'auto')
    """

    _BUILDER = "XGBoost"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        ntrees=50,
        max_depth=6,
        min_rows=1.0,
        nbins=255,
        nbins_cats=1024,
        nbins_top_level=1024,
        min_split_improvement=0.0,
        sample_rate=1.0,
        col_sample_rate_per_tree=1.0,
        score_tree_interval=5,
        grow_policy='depthwise',
        max_leaves=0,
        calibrate_model=False,
        calibration_frame=None,
        calibration_method='AUTO',
        learn_rate=0.3,
        learn_rate_annealing=1.0,
        distribution='AUTO',
        col_sample_rate=1.0,
        max_abs_leafnode_pred=float("inf"),
        quantile_alpha=0.5,
        tweedie_power=1.5,
        huber_alpha=0.9,
        monotone_constraints=None,
        reg_lambda=1.0,
        reg_alpha=0.0,
        tree_method='auto',
        booster='gbtree',
        scale_pos_weight=1.0,
        dmatrix_type='auto',
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            ntrees=ntrees,
            max_depth=max_depth,
            min_rows=min_rows,
            nbins=nbins,
            nbins_cats=nbins_cats,
            nbins_top_level=nbins_top_level,
            min_split_improvement=min_split_improvement,
            sample_rate=sample_rate,
            col_sample_rate_per_tree=col_sample_rate_per_tree,
            score_tree_interval=score_tree_interval,
            grow_policy=grow_policy,
            max_leaves=max_leaves,
            calibrate_model=calibrate_model,
            calibration_frame=calibration_frame,
            calibration_method=calibration_method,
            learn_rate=learn_rate,
            learn_rate_annealing=learn_rate_annealing,
            distribution=distribution,
            col_sample_rate=col_sample_rate,
            max_abs_leafnode_pred=max_abs_leafnode_pred,
            quantile_alpha=quantile_alpha,
            tweedie_power=tweedie_power,
            huber_alpha=huber_alpha,
            monotone_constraints=monotone_constraints,
            reg_lambda=reg_lambda,
            reg_alpha=reg_alpha,
            tree_method=tree_method,
            booster=booster,
            scale_pos_weight=scale_pos_weight,
            dmatrix_type=dmatrix_type,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'ntrees': 50,
            'max_depth': 6,
            'min_rows': 1.0,
            'nbins': 255,
            'nbins_cats': 1024,
            'nbins_top_level': 1024,
            'min_split_improvement': 0.0,
            'sample_rate': 1.0,
            'col_sample_rate_per_tree': 1.0,
            'score_tree_interval': 5,
            'grow_policy': 'depthwise',
            'max_leaves': 0,
            'calibrate_model': False,
            'calibration_frame': None,
            'calibration_method': 'AUTO',
            'learn_rate': 0.3,
            'learn_rate_annealing': 1.0,
            'distribution': 'AUTO',
            'col_sample_rate': 1.0,
            'max_abs_leafnode_pred': float("inf"),
            'quantile_alpha': 0.5,
            'tweedie_power': 1.5,
            'huber_alpha': 0.9,
            'monotone_constraints': None,
            'reg_lambda': 1.0,
            'reg_alpha': 0.0,
            'tree_method': 'auto',
            'booster': 'gbtree',
            'scale_pos_weight': 1.0,
            'dmatrix_type': 'auto',
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2ORandomForestEstimator(_EstimatorBase):
    """DRF estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    ntrees: int (default 50)
    max_depth: int (default 20)
    min_rows: float (default 1.0)
    nbins: int (default 255)
    nbins_cats: int (default 1024)
    nbins_top_level: int (default 1024)
    min_split_improvement: float (default 1e-05)
    sample_rate: float (default 0.632)
    col_sample_rate_per_tree: float (default 1.0)
    score_tree_interval: int (default 5)
    grow_policy: str (default 'depthwise')
    max_leaves: int (default 0)
    calibrate_model: bool (default False)
    calibration_frame: Any (default None)
    calibration_method: str (default 'AUTO')
    mtries: int (default -1)
    binomial_double_trees: bool (default False)
    """

    _BUILDER = "DRF"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        ntrees=50,
        max_depth=20,
        min_rows=1.0,
        nbins=255,
        nbins_cats=1024,
        nbins_top_level=1024,
        min_split_improvement=1e-05,
        sample_rate=0.632,
        col_sample_rate_per_tree=1.0,
        score_tree_interval=5,
        grow_policy='depthwise',
        max_leaves=0,
        calibrate_model=False,
        calibration_frame=None,
        calibration_method='AUTO',
        mtries=-1,
        binomial_double_trees=False,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            ntrees=ntrees,
            max_depth=max_depth,
            min_rows=min_rows,
            nbins=nbins,
            nbins_cats=nbins_cats,
            nbins_top_level=nbins_top_level,
            min_split_improvement=min_split_improvement,
            sample_rate=sample_rate,
            col_sample_rate_per_tree=col_sample_rate_per_tree,
            score_tree_interval=score_tree_interval,
            grow_policy=grow_policy,
            max_leaves=max_leaves,
            calibrate_model=calibrate_model,
            calibration_frame=calibration_frame,
            calibration_method=calibration_method,
            mtries=mtries,
            binomial_double_trees=binomial_double_trees,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'ntrees': 50,
            'max_depth': 20,
            'min_rows': 1.0,
            'nbins': 255,
            'nbins_cats': 1024,
            'nbins_top_level': 1024,
            'min_split_improvement': 1e-05,
            'sample_rate': 0.632,
            'col_sample_rate_per_tree': 1.0,
            'score_tree_interval': 5,
            'grow_policy': 'depthwise',
            'max_leaves': 0,
            'calibrate_model': False,
            'calibration_frame': None,
            'calibration_method': 'AUTO',
            'mtries': -1,
            'binomial_double_trees': False,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OXRTEstimator(_EstimatorBase):
    """XRT estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    ntrees: int (default 50)
    max_depth: int (default 20)
    min_rows: float (default 1.0)
    nbins: int (default 255)
    nbins_cats: int (default 1024)
    nbins_top_level: int (default 1024)
    min_split_improvement: float (default 1e-05)
    sample_rate: float (default 0.632)
    col_sample_rate_per_tree: float (default 1.0)
    score_tree_interval: int (default 5)
    grow_policy: str (default 'depthwise')
    max_leaves: int (default 0)
    calibrate_model: bool (default False)
    calibration_frame: Any (default None)
    calibration_method: str (default 'AUTO')
    mtries: int (default -1)
    binomial_double_trees: bool (default False)
    """

    _BUILDER = "XRT"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        ntrees=50,
        max_depth=20,
        min_rows=1.0,
        nbins=255,
        nbins_cats=1024,
        nbins_top_level=1024,
        min_split_improvement=1e-05,
        sample_rate=0.632,
        col_sample_rate_per_tree=1.0,
        score_tree_interval=5,
        grow_policy='depthwise',
        max_leaves=0,
        calibrate_model=False,
        calibration_frame=None,
        calibration_method='AUTO',
        mtries=-1,
        binomial_double_trees=False,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            ntrees=ntrees,
            max_depth=max_depth,
            min_rows=min_rows,
            nbins=nbins,
            nbins_cats=nbins_cats,
            nbins_top_level=nbins_top_level,
            min_split_improvement=min_split_improvement,
            sample_rate=sample_rate,
            col_sample_rate_per_tree=col_sample_rate_per_tree,
            score_tree_interval=score_tree_interval,
            grow_policy=grow_policy,
            max_leaves=max_leaves,
            calibrate_model=calibrate_model,
            calibration_frame=calibration_frame,
            calibration_method=calibration_method,
            mtries=mtries,
            binomial_double_trees=binomial_double_trees,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'ntrees': 50,
            'max_depth': 20,
            'min_rows': 1.0,
            'nbins': 255,
            'nbins_cats': 1024,
            'nbins_top_level': 1024,
            'min_split_improvement': 1e-05,
            'sample_rate': 0.632,
            'col_sample_rate_per_tree': 1.0,
            'score_tree_interval': 5,
            'grow_policy': 'depthwise',
            'max_leaves': 0,
            'calibrate_model': False,
            'calibration_frame': None,
            'calibration_method': 'AUTO',
            'mtries': -1,
            'binomial_double_trees': False,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OGeneralizedLinearEstimator(_EstimatorBase):
    """GLM estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    family: str (default 'AUTO')
    link: str (default 'family_default')
    solver: str (default 'AUTO')
    alpha: float | None (default None)
    lambda_: Any (default None)
    lambda_search: bool (default False)
    nlambdas: int (default -1)
    lambda_min_ratio: float (default -1.0)
    standardize: bool (default True)
    intercept: bool (default True)
    max_iterations: int (default -1)
    beta_epsilon: float (default 0.0001)
    objective_epsilon: float (default 1e-06)
    tweedie_variance_power: float (default 0.0)
    tweedie_link_power: float (default 1.0)
    theta: float (default 1e-05)
    missing_values_handling: str (default 'mean_imputation')
    compute_p_values: bool (default False)
    non_negative: bool (default False)
    interactions: Any (default None)
    interaction_pairs: Any (default None)
    hash_buckets: Any (default None)
    """

    _BUILDER = "GLM"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        family='AUTO',
        link='family_default',
        solver='AUTO',
        alpha=None,
        lambda_=None,
        lambda_search=False,
        nlambdas=-1,
        lambda_min_ratio=-1.0,
        standardize=True,
        intercept=True,
        max_iterations=-1,
        beta_epsilon=0.0001,
        objective_epsilon=1e-06,
        tweedie_variance_power=0.0,
        tweedie_link_power=1.0,
        theta=1e-05,
        missing_values_handling='mean_imputation',
        compute_p_values=False,
        non_negative=False,
        interactions=None,
        interaction_pairs=None,
        hash_buckets=None,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            family=family,
            link=link,
            solver=solver,
            alpha=alpha,
            lambda_=lambda_,
            lambda_search=lambda_search,
            nlambdas=nlambdas,
            lambda_min_ratio=lambda_min_ratio,
            standardize=standardize,
            intercept=intercept,
            max_iterations=max_iterations,
            beta_epsilon=beta_epsilon,
            objective_epsilon=objective_epsilon,
            tweedie_variance_power=tweedie_variance_power,
            tweedie_link_power=tweedie_link_power,
            theta=theta,
            missing_values_handling=missing_values_handling,
            compute_p_values=compute_p_values,
            non_negative=non_negative,
            interactions=interactions,
            interaction_pairs=interaction_pairs,
            hash_buckets=hash_buckets,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'family': 'AUTO',
            'link': 'family_default',
            'solver': 'AUTO',
            'alpha': None,
            'lambda_': None,
            'lambda_search': False,
            'nlambdas': -1,
            'lambda_min_ratio': -1.0,
            'standardize': True,
            'intercept': True,
            'max_iterations': -1,
            'beta_epsilon': 0.0001,
            'objective_epsilon': 1e-06,
            'tweedie_variance_power': 0.0,
            'tweedie_link_power': 1.0,
            'theta': 1e-05,
            'missing_values_handling': 'mean_imputation',
            'compute_p_values': False,
            'non_negative': False,
            'interactions': None,
            'interaction_pairs': None,
            'hash_buckets': None,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2ODeepLearningEstimator(_EstimatorBase):
    """DeepLearning estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    hidden: Sequence[int] (default (200, 200))
    epochs: float (default 10.0)
    activation: str (default 'Rectifier')
    input_dropout_ratio: float (default 0.0)
    hidden_dropout_ratios: Sequence[float] | None (default None)
    l1: float (default 0.0)
    l2: float (default 0.0)
    adaptive_rate: bool (default True)
    rho: float (default 0.99)
    epsilon: float (default 1e-08)
    rate: float (default 0.005)
    rate_annealing: float (default 1e-06)
    rate_decay: float (default 1.0)
    momentum_start: float (default 0.0)
    momentum_ramp: float (default 1000000.0)
    momentum_stable: float (default 0.0)
    nesterov_accelerated_gradient: bool (default True)
    mini_batch_size: int (default 32)
    standardize: bool (default True)
    loss: str (default 'Automatic')
    reproducible: bool (default True)
    autoencoder: bool (default False)
    hash_buckets: int | None (default None)
    """

    _BUILDER = "DeepLearning"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        hidden=(200, 200),
        epochs=10.0,
        activation='Rectifier',
        input_dropout_ratio=0.0,
        hidden_dropout_ratios=None,
        l1=0.0,
        l2=0.0,
        adaptive_rate=True,
        rho=0.99,
        epsilon=1e-08,
        rate=0.005,
        rate_annealing=1e-06,
        rate_decay=1.0,
        momentum_start=0.0,
        momentum_ramp=1000000.0,
        momentum_stable=0.0,
        nesterov_accelerated_gradient=True,
        mini_batch_size=32,
        standardize=True,
        loss='Automatic',
        reproducible=True,
        autoencoder=False,
        hash_buckets=None,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            hidden=hidden,
            epochs=epochs,
            activation=activation,
            input_dropout_ratio=input_dropout_ratio,
            hidden_dropout_ratios=hidden_dropout_ratios,
            l1=l1,
            l2=l2,
            adaptive_rate=adaptive_rate,
            rho=rho,
            epsilon=epsilon,
            rate=rate,
            rate_annealing=rate_annealing,
            rate_decay=rate_decay,
            momentum_start=momentum_start,
            momentum_ramp=momentum_ramp,
            momentum_stable=momentum_stable,
            nesterov_accelerated_gradient=nesterov_accelerated_gradient,
            mini_batch_size=mini_batch_size,
            standardize=standardize,
            loss=loss,
            reproducible=reproducible,
            autoencoder=autoencoder,
            hash_buckets=hash_buckets,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'hidden': (200, 200),
            'epochs': 10.0,
            'activation': 'Rectifier',
            'input_dropout_ratio': 0.0,
            'hidden_dropout_ratios': None,
            'l1': 0.0,
            'l2': 0.0,
            'adaptive_rate': True,
            'rho': 0.99,
            'epsilon': 1e-08,
            'rate': 0.005,
            'rate_annealing': 1e-06,
            'rate_decay': 1.0,
            'momentum_start': 0.0,
            'momentum_ramp': 1000000.0,
            'momentum_stable': 0.0,
            'nesterov_accelerated_gradient': True,
            'mini_batch_size': 32,
            'standardize': True,
            'loss': 'Automatic',
            'reproducible': True,
            'autoencoder': False,
            'hash_buckets': None,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OKMeansEstimator(_EstimatorBase):
    """KMeans estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    k: int (default 2)
    max_iterations: int (default 10)
    init: str (default 'Furthest')
    standardize: bool (default True)
    estimate_k: bool (default False)
    """

    _BUILDER = "KMeans"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        k=2,
        max_iterations=10,
        init='Furthest',
        standardize=True,
        estimate_k=False,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            k=k,
            max_iterations=max_iterations,
            init=init,
            standardize=standardize,
            estimate_k=estimate_k,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'k': 2,
            'max_iterations': 10,
            'init': 'Furthest',
            'standardize': True,
            'estimate_k': False,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OPrincipalComponentAnalysisEstimator(_EstimatorBase):
    """PCA estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    k: int (default 1)
    transform: str (default 'STANDARDIZE')
    pca_method: str (default 'GramSVD')
    use_all_factor_levels: bool (default False)
    """

    _BUILDER = "PCA"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        k=1,
        transform='STANDARDIZE',
        pca_method='GramSVD',
        use_all_factor_levels=False,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            k=k,
            transform=transform,
            pca_method=pca_method,
            use_all_factor_levels=use_all_factor_levels,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'k': 1,
            'transform': 'STANDARDIZE',
            'pca_method': 'GramSVD',
            'use_all_factor_levels': False,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OSingularValueDecompositionEstimator(_EstimatorBase):
    """SVD estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    nv: int (default 1)
    transform: str (default 'NONE')
    svd_method: str (default 'Randomized')
    max_iterations: int (default 4)
    """

    _BUILDER = "SVD"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        nv=1,
        transform='NONE',
        svd_method='Randomized',
        max_iterations=4,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            nv=nv,
            transform=transform,
            svd_method=svd_method,
            max_iterations=max_iterations,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'nv': 1,
            'transform': 'NONE',
            'svd_method': 'Randomized',
            'max_iterations': 4,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2ONaiveBayesEstimator(_EstimatorBase):
    """NaiveBayes estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    laplace: float (default 0.0)
    min_sdev: float (default 0.001)
    eps_sdev: float (default 0.0)
    """

    _BUILDER = "NaiveBayes"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        laplace=0.0,
        min_sdev=0.001,
        eps_sdev=0.0,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            laplace=laplace,
            min_sdev=min_sdev,
            eps_sdev=eps_sdev,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'laplace': 0.0,
            'min_sdev': 0.001,
            'eps_sdev': 0.0,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OIsolationForestEstimator(_EstimatorBase):
    """IsolationForest estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    ntrees: int (default 50)
    sample_size: int (default 256)
    max_depth: int (default 8)
    mtries: int (default -1)
    """

    _BUILDER = "IsolationForest"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        ntrees=50,
        sample_size=256,
        max_depth=8,
        mtries=-1,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            ntrees=ntrees,
            sample_size=sample_size,
            max_depth=max_depth,
            mtries=mtries,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'ntrees': 50,
            'sample_size': 256,
            'max_depth': 8,
            'mtries': -1,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OExtendedIsolationForestEstimator(_EstimatorBase):
    """ExtendedIsolationForest estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    ntrees: int (default 100)
    sample_size: int (default 256)
    extension_level: int (default -1)
    """

    _BUILDER = "ExtendedIsolationForest"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        ntrees=100,
        sample_size=256,
        extension_level=-1,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            ntrees=ntrees,
            sample_size=sample_size,
            extension_level=extension_level,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'ntrees': 100,
            'sample_size': 256,
            'extension_level': -1,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OGeneralizedLowRankEstimator(_EstimatorBase):
    """GLRM estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    k: int (default 2)
    loss: str (default 'Quadratic')
    regularization_x: str (default 'None')
    regularization_y: str (default 'None')
    gamma_x: float (default 0.0)
    gamma_y: float (default 0.0)
    max_iterations: int (default 100)
    init_step_size: float (default 1.0)
    min_step_size: float (default 1e-06)
    tolerance_rel: float (default 1e-07)
    transform: str (default 'STANDARDIZE')
    init: str (default 'SVD')
    """

    _BUILDER = "GLRM"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        k=2,
        loss='Quadratic',
        regularization_x='None',
        regularization_y='None',
        gamma_x=0.0,
        gamma_y=0.0,
        max_iterations=100,
        init_step_size=1.0,
        min_step_size=1e-06,
        tolerance_rel=1e-07,
        transform='STANDARDIZE',
        init='SVD',
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            k=k,
            loss=loss,
            regularization_x=regularization_x,
            regularization_y=regularization_y,
            gamma_x=gamma_x,
            gamma_y=gamma_y,
            max_iterations=max_iterations,
            init_step_size=init_step_size,
            min_step_size=min_step_size,
            tolerance_rel=tolerance_rel,
            transform=transform,
            init=init,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'k': 2,
            'loss': 'Quadratic',
            'regularization_x': 'None',
            'regularization_y': 'None',
            'gamma_x': 0.0,
            'gamma_y': 0.0,
            'max_iterations': 100,
            'init_step_size': 1.0,
            'min_step_size': 1e-06,
            'tolerance_rel': 1e-07,
            'transform': 'STANDARDIZE',
            'init': 'SVD',
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OCoxProportionalHazardsEstimator(_EstimatorBase):
    """CoxPH estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    start_column: str | None (default None)
    stop_column: str | None (default None)
    ties: str (default 'efron')
    max_iterations: int (default 20)
    tolerance: float (default 1e-08)
    """

    _BUILDER = "CoxPH"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        start_column=None,
        stop_column=None,
        ties='efron',
        max_iterations=20,
        tolerance=1e-08,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            start_column=start_column,
            stop_column=stop_column,
            ties=ties,
            max_iterations=max_iterations,
            tolerance=tolerance,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'start_column': None,
            'stop_column': None,
            'ties': 'efron',
            'max_iterations': 20,
            'tolerance': 1e-08,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OIsotonicRegressionEstimator(_EstimatorBase):
    """IsotonicRegression estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    out_of_bounds: str (default 'clip')
    """

    _BUILDER = "IsotonicRegression"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        out_of_bounds='clip',
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            out_of_bounds=out_of_bounds,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'out_of_bounds': 'clip',
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OAdaBoostEstimator(_EstimatorBase):
    """AdaBoost estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    ntrees: int (default 50)
    max_depth: int (default 1)
    min_rows: float (default 10.0)
    nbins: int (default 255)
    nbins_cats: int (default 1024)
    nbins_top_level: int (default 1024)
    min_split_improvement: float (default 1e-05)
    sample_rate: float (default 1.0)
    col_sample_rate_per_tree: float (default 1.0)
    score_tree_interval: int (default 5)
    grow_policy: str (default 'depthwise')
    max_leaves: int (default 0)
    calibrate_model: bool (default False)
    calibration_frame: Any (default None)
    calibration_method: str (default 'AUTO')
    nlearners: int (default 50)
    weak_learner: str (default 'DT')
    learn_rate: float (default 0.5)
    """

    _BUILDER = "AdaBoost"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        ntrees=50,
        max_depth=1,
        min_rows=10.0,
        nbins=255,
        nbins_cats=1024,
        nbins_top_level=1024,
        min_split_improvement=1e-05,
        sample_rate=1.0,
        col_sample_rate_per_tree=1.0,
        score_tree_interval=5,
        grow_policy='depthwise',
        max_leaves=0,
        calibrate_model=False,
        calibration_frame=None,
        calibration_method='AUTO',
        nlearners=50,
        weak_learner='DT',
        learn_rate=0.5,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            ntrees=ntrees,
            max_depth=max_depth,
            min_rows=min_rows,
            nbins=nbins,
            nbins_cats=nbins_cats,
            nbins_top_level=nbins_top_level,
            min_split_improvement=min_split_improvement,
            sample_rate=sample_rate,
            col_sample_rate_per_tree=col_sample_rate_per_tree,
            score_tree_interval=score_tree_interval,
            grow_policy=grow_policy,
            max_leaves=max_leaves,
            calibrate_model=calibrate_model,
            calibration_frame=calibration_frame,
            calibration_method=calibration_method,
            nlearners=nlearners,
            weak_learner=weak_learner,
            learn_rate=learn_rate,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'ntrees': 50,
            'max_depth': 1,
            'min_rows': 10.0,
            'nbins': 255,
            'nbins_cats': 1024,
            'nbins_top_level': 1024,
            'min_split_improvement': 1e-05,
            'sample_rate': 1.0,
            'col_sample_rate_per_tree': 1.0,
            'score_tree_interval': 5,
            'grow_policy': 'depthwise',
            'max_leaves': 0,
            'calibrate_model': False,
            'calibration_frame': None,
            'calibration_method': 'AUTO',
            'nlearners': 50,
            'weak_learner': 'DT',
            'learn_rate': 0.5,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2ODecisionTreeEstimator(_EstimatorBase):
    """DT estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    ntrees: int (default 50)
    max_depth: int (default 10)
    min_rows: float (default 10.0)
    nbins: int (default 255)
    nbins_cats: int (default 1024)
    nbins_top_level: int (default 1024)
    min_split_improvement: float (default 1e-05)
    sample_rate: float (default 1.0)
    col_sample_rate_per_tree: float (default 1.0)
    score_tree_interval: int (default 5)
    grow_policy: str (default 'depthwise')
    max_leaves: int (default 0)
    calibrate_model: bool (default False)
    calibration_frame: Any (default None)
    calibration_method: str (default 'AUTO')
    """

    _BUILDER = "DT"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        ntrees=50,
        max_depth=10,
        min_rows=10.0,
        nbins=255,
        nbins_cats=1024,
        nbins_top_level=1024,
        min_split_improvement=1e-05,
        sample_rate=1.0,
        col_sample_rate_per_tree=1.0,
        score_tree_interval=5,
        grow_policy='depthwise',
        max_leaves=0,
        calibrate_model=False,
        calibration_frame=None,
        calibration_method='AUTO',
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            ntrees=ntrees,
            max_depth=max_depth,
            min_rows=min_rows,
            nbins=nbins,
            nbins_cats=nbins_cats,
            nbins_top_level=nbins_top_level,
            min_split_improvement=min_split_improvement,
            sample_rate=sample_rate,
            col_sample_rate_per_tree=col_sample_rate_per_tree,
            score_tree_interval=score_tree_interval,
            grow_policy=grow_policy,
            max_leaves=max_leaves,
            calibrate_model=calibrate_model,
            calibration_frame=calibration_frame,
            calibration_method=calibration_method,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'ntrees': 50,
            'max_depth': 10,
            'min_rows': 10.0,
            'nbins': 255,
            'nbins_cats': 1024,
            'nbins_top_level': 1024,
            'min_split_improvement': 1e-05,
            'sample_rate': 1.0,
            'col_sample_rate_per_tree': 1.0,
            'score_tree_interval': 5,
            'grow_policy': 'depthwise',
            'max_leaves': 0,
            'calibrate_model': False,
            'calibration_frame': None,
            'calibration_method': 'AUTO',
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OWord2vecEstimator(_EstimatorBase):
    """Word2Vec estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    vec_size: int (default 100)
    window_size: int (default 5)
    min_word_freq: int (default 5)
    epochs: int (default 5)
    learning_rate: float (default 0.025)
    negative_samples: int (default 5)
    sent_sample_rate: float (default 0.001)
    """

    _BUILDER = "Word2Vec"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        vec_size=100,
        window_size=5,
        min_word_freq=5,
        epochs=5,
        learning_rate=0.025,
        negative_samples=5,
        sent_sample_rate=0.001,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            vec_size=vec_size,
            window_size=window_size,
            min_word_freq=min_word_freq,
            epochs=epochs,
            learning_rate=learning_rate,
            negative_samples=negative_samples,
            sent_sample_rate=sent_sample_rate,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'vec_size': 100,
            'window_size': 5,
            'min_word_freq': 5,
            'epochs': 5,
            'learning_rate': 0.025,
            'negative_samples': 5,
            'sent_sample_rate': 0.001,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OStackedEnsembleEstimator(_EstimatorBase):
    """StackedEnsemble estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    base_models: Sequence[Any] (default ())
    metalearner_algorithm: str (default 'AUTO')
    metalearner_params: dict (default {})
    metalearner_nfolds: int (default 5)
    """

    _BUILDER = "StackedEnsemble"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        base_models=(),
        metalearner_algorithm='AUTO',
        metalearner_params={},
        metalearner_nfolds=5,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            base_models=base_models,
            metalearner_algorithm=metalearner_algorithm,
            metalearner_params=metalearner_params,
            metalearner_nfolds=metalearner_nfolds,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'base_models': (),
            'metalearner_algorithm': 'AUTO',
            'metalearner_params': {},
            'metalearner_nfolds': 5,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OTargetEncoderEstimator(_EstimatorBase):
    """TargetEncoder estimator (generated).

    Parameters
    ----------
    holdout_type: str (default 'none')
    blending: bool (default False)
    inflection_point: float (default 10.0)
    smoothing: float (default 20.0)
    noise: float (default 0.0)
    fold_column: str | None (default None)
    nfolds: int (default 5)
    seed: int (default -1)
    columns: Sequence[str] (default ())
    """

    _BUILDER = "TargetEncoder"

    def __init__(
        self,
        model_id=None,
        holdout_type='none',
        blending=False,
        inflection_point=10.0,
        smoothing=20.0,
        noise=0.0,
        fold_column=None,
        nfolds=5,
        seed=-1,
        columns=(),
    ):
        kw = dict(
            holdout_type=holdout_type,
            blending=blending,
            inflection_point=inflection_point,
            smoothing=smoothing,
            noise=noise,
            fold_column=fold_column,
            nfolds=nfolds,
            seed=seed,
            columns=columns,
        )
        defaults = {
            'holdout_type': 'none',
            'blending': False,
            'inflection_point': 10.0,
            'smoothing': 20.0,
            'noise': 0.0,
            'fold_column': None,
            'nfolds': 5,
            'seed': -1,
            'columns': (),
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2ORuleFitEstimator(_EstimatorBase):
    """RuleFit estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    algorithm: str (default 'AUTO')
    min_rule_length: int (default 3)
    max_rule_length: int (default 3)
    max_num_rules: int (default -1)
    model_type: str (default 'rules_and_linear')
    rule_generation_ntrees: int (default 50)
    distribution: str (default 'AUTO')
    lambda_: float | None (default None)
    remove_duplicates: bool (default True)
    """

    _BUILDER = "RuleFit"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        algorithm='AUTO',
        min_rule_length=3,
        max_rule_length=3,
        max_num_rules=-1,
        model_type='rules_and_linear',
        rule_generation_ntrees=50,
        distribution='AUTO',
        lambda_=None,
        remove_duplicates=True,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            algorithm=algorithm,
            min_rule_length=min_rule_length,
            max_rule_length=max_rule_length,
            max_num_rules=max_num_rules,
            model_type=model_type,
            rule_generation_ntrees=rule_generation_ntrees,
            distribution=distribution,
            lambda_=lambda_,
            remove_duplicates=remove_duplicates,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'algorithm': 'AUTO',
            'min_rule_length': 3,
            'max_rule_length': 3,
            'max_num_rules': -1,
            'model_type': 'rules_and_linear',
            'rule_generation_ntrees': 50,
            'distribution': 'AUTO',
            'lambda_': None,
            'remove_duplicates': True,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OUpliftRandomForestEstimator(_EstimatorBase):
    """UpliftDRF estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    nbins_cats: int (default 1024)
    treatment_column: str (default 'treatment')
    uplift_metric: str (default 'KL')
    ntrees: int (default 50)
    max_depth: int (default 10)
    min_rows: float (default 10.0)
    mtries: int (default -2)
    sample_rate: float (default 0.632)
    nbins: int (default 255)
    min_split_improvement: float (default 1e-05)
    score_tree_interval: int (default 10)
    """

    _BUILDER = "UpliftDRF"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        nbins_cats=1024,
        treatment_column='treatment',
        uplift_metric='KL',
        ntrees=50,
        max_depth=10,
        min_rows=10.0,
        mtries=-2,
        sample_rate=0.632,
        nbins=255,
        min_split_improvement=1e-05,
        score_tree_interval=10,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            nbins_cats=nbins_cats,
            treatment_column=treatment_column,
            uplift_metric=uplift_metric,
            ntrees=ntrees,
            max_depth=max_depth,
            min_rows=min_rows,
            mtries=mtries,
            sample_rate=sample_rate,
            nbins=nbins,
            min_split_improvement=min_split_improvement,
            score_tree_interval=score_tree_interval,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'nbins_cats': 1024,
            'treatment_column': 'treatment',
            'uplift_metric': 'KL',
            'ntrees': 50,
            'max_depth': 10,
            'min_rows': 10.0,
            'mtries': -2,
            'sample_rate': 0.632,
            'nbins': 255,
            'min_split_improvement': 1e-05,
            'score_tree_interval': 10,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OGeneralizedAdditiveEstimator(_EstimatorBase):
    """GAM estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    family: str (default 'AUTO')
    gam_columns: list (default [])
    num_knots: list (default [])
    scale: list (default [])
    bs: list (default [])
    lambda_: float (default 0.0)
    standardize: bool (default True)
    intercept: bool (default True)
    max_iterations: int (default 50)
    beta_epsilon: float (default 1e-06)
    keep_gam_cols: bool (default False)
    """

    _BUILDER = "GAM"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        family='AUTO',
        gam_columns=[],
        num_knots=[],
        scale=[],
        bs=[],
        lambda_=0.0,
        standardize=True,
        intercept=True,
        max_iterations=50,
        beta_epsilon=1e-06,
        keep_gam_cols=False,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            family=family,
            gam_columns=gam_columns,
            num_knots=num_knots,
            scale=scale,
            bs=bs,
            lambda_=lambda_,
            standardize=standardize,
            intercept=intercept,
            max_iterations=max_iterations,
            beta_epsilon=beta_epsilon,
            keep_gam_cols=keep_gam_cols,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'family': 'AUTO',
            'gam_columns': [],
            'num_knots': [],
            'scale': [],
            'bs': [],
            'lambda_': 0.0,
            'standardize': True,
            'intercept': True,
            'max_iterations': 50,
            'beta_epsilon': 1e-06,
            'keep_gam_cols': False,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OModelSelectionEstimator(_EstimatorBase):
    """ModelSelection estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    mode: str (default 'maxr')
    family: str (default 'AUTO')
    max_predictor_number: int (default 1)
    min_predictor_number: int (default 1)
    intercept: bool (default True)
    standardize: bool (default True)
    p_values_threshold: float (default 0.0)
    missing_values_handling: str (default 'mean_imputation')
    """

    _BUILDER = "ModelSelection"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        mode='maxr',
        family='AUTO',
        max_predictor_number=1,
        min_predictor_number=1,
        intercept=True,
        standardize=True,
        p_values_threshold=0.0,
        missing_values_handling='mean_imputation',
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            mode=mode,
            family=family,
            max_predictor_number=max_predictor_number,
            min_predictor_number=min_predictor_number,
            intercept=intercept,
            standardize=standardize,
            p_values_threshold=p_values_threshold,
            missing_values_handling=missing_values_handling,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'mode': 'maxr',
            'family': 'AUTO',
            'max_predictor_number': 1,
            'min_predictor_number': 1,
            'intercept': True,
            'standardize': True,
            'p_values_threshold': 0.0,
            'missing_values_handling': 'mean_imputation',
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OANOVAGLMEstimator(_EstimatorBase):
    """ANOVAGLM estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    family: str (default 'AUTO')
    highest_interaction_term: int (default 0)
    lambda_: float (default 0.0)
    standardize: bool (default True)
    """

    _BUILDER = "ANOVAGLM"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        family='AUTO',
        highest_interaction_term=0,
        lambda_=0.0,
        standardize=True,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            family=family,
            highest_interaction_term=highest_interaction_term,
            lambda_=lambda_,
            standardize=standardize,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'family': 'AUTO',
            'highest_interaction_term': 0,
            'lambda_': 0.0,
            'standardize': True,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OAggregatorEstimator(_EstimatorBase):
    """Aggregator estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    target_num_exemplars: int (default 5000)
    rel_tol_num_exemplars: float (default 0.5)
    transform: str (default 'NORMALIZE')
    categorical_encoding: str (default 'AUTO')
    """

    _BUILDER = "Aggregator"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        target_num_exemplars=5000,
        rel_tol_num_exemplars=0.5,
        transform='NORMALIZE',
        categorical_encoding='AUTO',
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            target_num_exemplars=target_num_exemplars,
            rel_tol_num_exemplars=rel_tol_num_exemplars,
            transform=transform,
            categorical_encoding=categorical_encoding,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'target_num_exemplars': 5000,
            'rel_tol_num_exemplars': 0.5,
            'transform': 'NORMALIZE',
            'categorical_encoding': 'AUTO',
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OInfogramEstimator(_EstimatorBase):
    """Infogram estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    protected_columns: list (default [])
    safety_index_threshold: float (default 0.1)
    relevance_index_threshold: float (default 0.1)
    total_information_threshold: float (default 0.1)
    net_information_threshold: float (default 0.1)
    ntrees: int (default 20)
    max_depth: int (default 5)
    top_n_features: int (default 50)
    """

    _BUILDER = "Infogram"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        protected_columns=[],
        safety_index_threshold=0.1,
        relevance_index_threshold=0.1,
        total_information_threshold=0.1,
        net_information_threshold=0.1,
        ntrees=20,
        max_depth=5,
        top_n_features=50,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            protected_columns=protected_columns,
            safety_index_threshold=safety_index_threshold,
            relevance_index_threshold=relevance_index_threshold,
            total_information_threshold=total_information_threshold,
            net_information_threshold=net_information_threshold,
            ntrees=ntrees,
            max_depth=max_depth,
            top_n_features=top_n_features,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'protected_columns': [],
            'safety_index_threshold': 0.1,
            'relevance_index_threshold': 0.1,
            'total_information_threshold': 0.1,
            'net_information_threshold': 0.1,
            'ntrees': 20,
            'max_depth': 5,
            'top_n_features': 50,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OSupportVectorMachineEstimator(_EstimatorBase):
    """PSVM estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    kernel_type: str (default 'gaussian')
    gamma: float (default -1.0)
    hyper_param: float (default 1.0)
    positive_weight: float (default 1.0)
    negative_weight: float (default 1.0)
    rank_ratio: float (default -1.0)
    max_iterations: int (default 200)
    convergence_tol: float (default 1e-06)
    """

    _BUILDER = "PSVM"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        kernel_type='gaussian',
        gamma=-1.0,
        hyper_param=1.0,
        positive_weight=1.0,
        negative_weight=1.0,
        rank_ratio=-1.0,
        max_iterations=200,
        convergence_tol=1e-06,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            kernel_type=kernel_type,
            gamma=gamma,
            hyper_param=hyper_param,
            positive_weight=positive_weight,
            negative_weight=negative_weight,
            rank_ratio=rank_ratio,
            max_iterations=max_iterations,
            convergence_tol=convergence_tol,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'kernel_type': 'gaussian',
            'gamma': -1.0,
            'hyper_param': 1.0,
            'positive_weight': 1.0,
            'negative_weight': 1.0,
            'rank_ratio': -1.0,
            'max_iterations': 200,
            'convergence_tol': 1e-06,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


class H2OHGLMEstimator(_EstimatorBase):
    """HGLM estimator (generated).

    Parameters
    ----------
    response_column: str | None (default None)
    ignored_columns: Sequence[str] (default ())
    weights_column: str | None (default None)
    offset_column: str | None (default None)
    nfolds: int (default 0)
    fold_assignment: str (default 'modulo')
    keep_cross_validation_predictions: bool (default False)
    seed: int (default -1)
    max_runtime_secs: float (default 0.0)
    stopping_rounds: int (default 0)
    stopping_metric: str (default 'AUTO')
    stopping_tolerance: float (default 0.001)
    checkpoint: Any (default None)
    export_checkpoints_dir: str | None (default None)
    random_columns: list (default [])
    method: str (default 'EM')
    max_iterations: int (default 100)
    em_epsilon: float (default 1e-06)
    standardize: bool (default False)
    intercept: bool (default True)
    """

    _BUILDER = "HGLM"

    def __init__(
        self,
        model_id=None,
        response_column=None,
        ignored_columns=(),
        weights_column=None,
        offset_column=None,
        nfolds=0,
        fold_assignment='modulo',
        keep_cross_validation_predictions=False,
        seed=-1,
        max_runtime_secs=0.0,
        stopping_rounds=0,
        stopping_metric='AUTO',
        stopping_tolerance=0.001,
        checkpoint=None,
        export_checkpoints_dir=None,
        random_columns=[],
        method='EM',
        max_iterations=100,
        em_epsilon=1e-06,
        standardize=False,
        intercept=True,
    ):
        kw = dict(
            response_column=response_column,
            ignored_columns=ignored_columns,
            weights_column=weights_column,
            offset_column=offset_column,
            nfolds=nfolds,
            fold_assignment=fold_assignment,
            keep_cross_validation_predictions=keep_cross_validation_predictions,
            seed=seed,
            max_runtime_secs=max_runtime_secs,
            stopping_rounds=stopping_rounds,
            stopping_metric=stopping_metric,
            stopping_tolerance=stopping_tolerance,
            checkpoint=checkpoint,
            export_checkpoints_dir=export_checkpoints_dir,
            random_columns=random_columns,
            method=method,
            max_iterations=max_iterations,
            em_epsilon=em_epsilon,
            standardize=standardize,
            intercept=intercept,
        )
        defaults = {
            'response_column': None,
            'ignored_columns': (),
            'weights_column': None,
            'offset_column': None,
            'nfolds': 0,
            'fold_assignment': 'modulo',
            'keep_cross_validation_predictions': False,
            'seed': -1,
            'max_runtime_secs': 0.0,
            'stopping_rounds': 0,
            'stopping_metric': 'AUTO',
            'stopping_tolerance': 0.001,
            'checkpoint': None,
            'export_checkpoints_dir': None,
            'random_columns': [],
            'method': 'EM',
            'max_iterations': 100,
            'em_epsilon': 1e-06,
            'standardize': False,
            'intercept': True,
        }
        kw = {k: v for k, v in kw.items() if v != defaults[k]}
        super().__init__(model_id=model_id, **kw)


__all__ = [
    'H2OGradientBoostingEstimator',
    'H2OXGBoostEstimator',
    'H2ORandomForestEstimator',
    'H2OXRTEstimator',
    'H2OGeneralizedLinearEstimator',
    'H2ODeepLearningEstimator',
    'H2OKMeansEstimator',
    'H2OPrincipalComponentAnalysisEstimator',
    'H2OSingularValueDecompositionEstimator',
    'H2ONaiveBayesEstimator',
    'H2OIsolationForestEstimator',
    'H2OExtendedIsolationForestEstimator',
    'H2OGeneralizedLowRankEstimator',
    'H2OCoxProportionalHazardsEstimator',
    'H2OIsotonicRegressionEstimator',
    'H2OAdaBoostEstimator',
    'H2ODecisionTreeEstimator',
    'H2OWord2vecEstimator',
    'H2OStackedEnsembleEstimator',
    'H2OTargetEncoderEstimator',
    'H2ORuleFitEstimator',
    'H2OUpliftRandomForestEstimator',
    'H2OGeneralizedAdditiveEstimator',
    'H2OModelSelectionEstimator',
    'H2OANOVAGLMEstimator',
    'H2OAggregatorEstimator',
    'H2OInfogramEstimator',
    'H2OSupportVectorMachineEstimator',
    'H2OHGLMEstimator',
]
