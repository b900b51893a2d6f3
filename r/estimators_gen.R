# GENERATED FILE — do not edit. Regenerate with tools/gen_bindings.py.
#
# Explicit per-algorithm h2o.* training functions with every parameter as a
# named argument with its default (the gen_R.py codegen analog, SURVEY.md
# §2.3 [UNVERIFIED upstream path h2o-bindings/bin/gen_R.py]). Requires
# h2o3tpu.R to be sourced first (.h2o.req / .h2o.train helpers). Only
# arguments the caller actually supplies are sent to the server (missing()
# check), so server-side defaults stay authoritative.

.h2o.train_params <- function(algo, y, x, training_frame, validation_frame,
                              params) {
  stopifnot(inherits(training_frame, "H2O3Frame"))
  # delegate to h2o3tpu.R's .h2o.train so job-wait / model-resolution
  # logic lives in exactly one place
  do.call(.h2o.train, c(
    list(algo, y = y, x = x, training_frame = training_frame,
         validation_frame = validation_frame),
    params))
}

h2o.gbm <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    ntrees = 50,
    max_depth = 5,
    min_rows = 10.0,
    nbins = 255,
    nbins_cats = 1024,
    nbins_top_level = 1024,
    min_split_improvement = 1e-05,
    sample_rate = 1.0,
    col_sample_rate_per_tree = 1.0,
    score_tree_interval = 5,
    grow_policy = "depthwise",
    max_leaves = 0,
    calibrate_model = FALSE,
    calibration_frame = NULL,
    calibration_method = "AUTO",
    learn_rate = 0.1,
    learn_rate_annealing = 1.0,
    distribution = "AUTO",
    col_sample_rate = 1.0,
    max_abs_leafnode_pred = Inf,
    quantile_alpha = 0.5,
    tweedie_power = 1.5,
    huber_alpha = 0.9,
    monotone_constraints = NULL
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(ntrees)) p$ntrees <- ntrees
  if (!missing(max_depth)) p$max_depth <- max_depth
  if (!missing(min_rows)) p$min_rows <- min_rows
  if (!missing(nbins)) p$nbins <- nbins
  if (!missing(nbins_cats)) p$nbins_cats <- nbins_cats
  if (!missing(nbins_top_level)) p$nbins_top_level <- nbins_top_level
  if (!missing(min_split_improvement)) p$min_split_improvement <- min_split_improvement
  if (!missing(sample_rate)) p$sample_rate <- sample_rate
  if (!missing(col_sample_rate_per_tree)) p$col_sample_rate_per_tree <- col_sample_rate_per_tree
  if (!missing(score_tree_interval)) p$score_tree_interval <- score_tree_interval
  if (!missing(grow_policy)) p$grow_policy <- grow_policy
  if (!missing(max_leaves)) p$max_leaves <- max_leaves
  if (!missing(calibrate_model)) p$calibrate_model <- calibrate_model
  if (!missing(calibration_frame)) p$calibration_frame <- calibration_frame
  if (!missing(calibration_method)) p$calibration_method <- calibration_method
  if (!missing(learn_rate)) p$learn_rate <- learn_rate
  if (!missing(learn_rate_annealing)) p$learn_rate_annealing <- learn_rate_annealing
  if (!missing(distribution)) p$distribution <- distribution
  if (!missing(col_sample_rate)) p$col_sample_rate <- col_sample_rate
  if (!missing(max_abs_leafnode_pred)) p$max_abs_leafnode_pred <- max_abs_leafnode_pred
  if (!missing(quantile_alpha)) p$quantile_alpha <- quantile_alpha
  if (!missing(tweedie_power)) p$tweedie_power <- tweedie_power
  if (!missing(huber_alpha)) p$huber_alpha <- huber_alpha
  if (!missing(monotone_constraints)) p$monotone_constraints <- monotone_constraints
  .h2o.train_params("gbm", y, x, training_frame, validation_frame, p)
}

h2o.xgboost <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    ntrees = 50,
    max_depth = 6,
    min_rows = 1.0,
    nbins = 255,
    nbins_cats = 1024,
    nbins_top_level = 1024,
    min_split_improvement = 0.0,
    sample_rate = 1.0,
    col_sample_rate_per_tree = 1.0,
    score_tree_interval = 5,
    grow_policy = "depthwise",
    max_leaves = 0,
    calibrate_model = FALSE,
    calibration_frame = NULL,
    calibration_method = "AUTO",
    learn_rate = 0.3,
    learn_rate_annealing = 1.0,
    distribution = "AUTO",
    col_sample_rate = 1.0,
    max_abs_leafnode_pred = Inf,
    quantile_alpha = 0.5,
    tweedie_power = 1.5,
    huber_alpha = 0.9,
    monotone_constraints = NULL,
    reg_lambda = 1.0,
    reg_alpha = 0.0,
    tree_method = "auto",
    booster = "gbtree",
    scale_pos_weight = 1.0,
    dmatrix_type = "auto"
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(ntrees)) p$ntrees <- ntrees
  if (!missing(max_depth)) p$max_depth <- max_depth
  if (!missing(min_rows)) p$min_rows <- min_rows
  if (!missing(nbins)) p$nbins <- nbins
  if (!missing(nbins_cats)) p$nbins_cats <- nbins_cats
  if (!missing(nbins_top_level)) p$nbins_top_level <- nbins_top_level
  if (!missing(min_split_improvement)) p$min_split_improvement <- min_split_improvement
  if (!missing(sample_rate)) p$sample_rate <- sample_rate
  if (!missing(col_sample_rate_per_tree)) p$col_sample_rate_per_tree <- col_sample_rate_per_tree
  if (!missing(score_tree_interval)) p$score_tree_interval <- score_tree_interval
  if (!missing(grow_policy)) p$grow_policy <- grow_policy
  if (!missing(max_leaves)) p$max_leaves <- max_leaves
  if (!missing(calibrate_model)) p$calibrate_model <- calibrate_model
  if (!missing(calibration_frame)) p$calibration_frame <- calibration_frame
  if (!missing(calibration_method)) p$calibration_method <- calibration_method
  if (!missing(learn_rate)) p$learn_rate <- learn_rate
  if (!missing(learn_rate_annealing)) p$learn_rate_annealing <- learn_rate_annealing
  if (!missing(distribution)) p$distribution <- distribution
  if (!missing(col_sample_rate)) p$col_sample_rate <- col_sample_rate
  if (!missing(max_abs_leafnode_pred)) p$max_abs_leafnode_pred <- max_abs_leafnode_pred
  if (!missing(quantile_alpha)) p$quantile_alpha <- quantile_alpha
  if (!missing(tweedie_power)) p$tweedie_power <- tweedie_power
  if (!missing(huber_alpha)) p$huber_alpha <- huber_alpha
  if (!missing(monotone_constraints)) p$monotone_constraints <- monotone_constraints
  if (!missing(reg_lambda)) p$reg_lambda <- reg_lambda
  if (!missing(reg_alpha)) p$reg_alpha <- reg_alpha
  if (!missing(tree_method)) p$tree_method <- tree_method
  if (!missing(booster)) p$booster <- booster
  if (!missing(scale_pos_weight)) p$scale_pos_weight <- scale_pos_weight
  if (!missing(dmatrix_type)) p$dmatrix_type <- dmatrix_type
  .h2o.train_params("xgboost", y, x, training_frame, validation_frame, p)
}

h2o.randomForest <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    ntrees = 50,
    max_depth = 20,
    min_rows = 1.0,
    nbins = 255,
    nbins_cats = 1024,
    nbins_top_level = 1024,
    min_split_improvement = 1e-05,
    sample_rate = 0.632,
    col_sample_rate_per_tree = 1.0,
    score_tree_interval = 5,
    grow_policy = "depthwise",
    max_leaves = 0,
    calibrate_model = FALSE,
    calibration_frame = NULL,
    calibration_method = "AUTO",
    mtries = -1,
    binomial_double_trees = FALSE
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(ntrees)) p$ntrees <- ntrees
  if (!missing(max_depth)) p$max_depth <- max_depth
  if (!missing(min_rows)) p$min_rows <- min_rows
  if (!missing(nbins)) p$nbins <- nbins
  if (!missing(nbins_cats)) p$nbins_cats <- nbins_cats
  if (!missing(nbins_top_level)) p$nbins_top_level <- nbins_top_level
  if (!missing(min_split_improvement)) p$min_split_improvement <- min_split_improvement
  if (!missing(sample_rate)) p$sample_rate <- sample_rate
  if (!missing(col_sample_rate_per_tree)) p$col_sample_rate_per_tree <- col_sample_rate_per_tree
  if (!missing(score_tree_interval)) p$score_tree_interval <- score_tree_interval
  if (!missing(grow_policy)) p$grow_policy <- grow_policy
  if (!missing(max_leaves)) p$max_leaves <- max_leaves
  if (!missing(calibrate_model)) p$calibrate_model <- calibrate_model
  if (!missing(calibration_frame)) p$calibration_frame <- calibration_frame
  if (!missing(calibration_method)) p$calibration_method <- calibration_method
  if (!missing(mtries)) p$mtries <- mtries
  if (!missing(binomial_double_trees)) p$binomial_double_trees <- binomial_double_trees
  .h2o.train_params("drf", y, x, training_frame, validation_frame, p)
}

h2o.xrt <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    ntrees = 50,
    max_depth = 20,
    min_rows = 1.0,
    nbins = 255,
    nbins_cats = 1024,
    nbins_top_level = 1024,
    min_split_improvement = 1e-05,
    sample_rate = 0.632,
    col_sample_rate_per_tree = 1.0,
    score_tree_interval = 5,
    grow_policy = "depthwise",
    max_leaves = 0,
    calibrate_model = FALSE,
    calibration_frame = NULL,
    calibration_method = "AUTO",
    mtries = -1,
    binomial_double_trees = FALSE
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(ntrees)) p$ntrees <- ntrees
  if (!missing(max_depth)) p$max_depth <- max_depth
  if (!missing(min_rows)) p$min_rows <- min_rows
  if (!missing(nbins)) p$nbins <- nbins
  if (!missing(nbins_cats)) p$nbins_cats <- nbins_cats
  if (!missing(nbins_top_level)) p$nbins_top_level <- nbins_top_level
  if (!missing(min_split_improvement)) p$min_split_improvement <- min_split_improvement
  if (!missing(sample_rate)) p$sample_rate <- sample_rate
  if (!missing(col_sample_rate_per_tree)) p$col_sample_rate_per_tree <- col_sample_rate_per_tree
  if (!missing(score_tree_interval)) p$score_tree_interval <- score_tree_interval
  if (!missing(grow_policy)) p$grow_policy <- grow_policy
  if (!missing(max_leaves)) p$max_leaves <- max_leaves
  if (!missing(calibrate_model)) p$calibrate_model <- calibrate_model
  if (!missing(calibration_frame)) p$calibration_frame <- calibration_frame
  if (!missing(calibration_method)) p$calibration_method <- calibration_method
  if (!missing(mtries)) p$mtries <- mtries
  if (!missing(binomial_double_trees)) p$binomial_double_trees <- binomial_double_trees
  .h2o.train_params("xrt", y, x, training_frame, validation_frame, p)
}

h2o.glm <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    family = "AUTO",
    link = "family_default",
    solver = "AUTO",
    alpha = NULL,
    lambda = NULL,
    lambda_search = FALSE,
    nlambdas = -1,
    lambda_min_ratio = -1.0,
    standardize = TRUE,
    intercept = TRUE,
    max_iterations = -1,
    beta_epsilon = 0.0001,
    objective_epsilon = 1e-06,
    tweedie_variance_power = 0.0,
    tweedie_link_power = 1.0,
    theta = 1e-05,
    missing_values_handling = "mean_imputation",
    compute_p_values = FALSE,
    non_negative = FALSE,
    interactions = NULL,
    interaction_pairs = NULL,
    hash_buckets = NULL
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(family)) p$family <- family
  if (!missing(link)) p$link <- link
  if (!missing(solver)) p$solver <- solver
  if (!missing(alpha)) p$alpha <- alpha
  if (!missing(lambda)) p$lambda <- lambda
  if (!missing(lambda_search)) p$lambda_search <- lambda_search
  if (!missing(nlambdas)) p$nlambdas <- nlambdas
  if (!missing(lambda_min_ratio)) p$lambda_min_ratio <- lambda_min_ratio
  if (!missing(standardize)) p$standardize <- standardize
  if (!missing(intercept)) p$intercept <- intercept
  if (!missing(max_iterations)) p$max_iterations <- max_iterations
  if (!missing(beta_epsilon)) p$beta_epsilon <- beta_epsilon
  if (!missing(objective_epsilon)) p$objective_epsilon <- objective_epsilon
  if (!missing(tweedie_variance_power)) p$tweedie_variance_power <- tweedie_variance_power
  if (!missing(tweedie_link_power)) p$tweedie_link_power <- tweedie_link_power
  if (!missing(theta)) p$theta <- theta
  if (!missing(missing_values_handling)) p$missing_values_handling <- missing_values_handling
  if (!missing(compute_p_values)) p$compute_p_values <- compute_p_values
  if (!missing(non_negative)) p$non_negative <- non_negative
  if (!missing(interactions)) p$interactions <- interactions
  if (!missing(interaction_pairs)) p$interaction_pairs <- interaction_pairs
  if (!missing(hash_buckets)) p$hash_buckets <- hash_buckets
  .h2o.train_params("glm", y, x, training_frame, validation_frame, p)
}

h2o.deeplearning <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    hidden = c(200, 200),
    epochs = 10.0,
    activation = "Rectifier",
    input_dropout_ratio = 0.0,
    hidden_dropout_ratios = NULL,
    l1 = 0.0,
    l2 = 0.0,
    adaptive_rate = TRUE,
    rho = 0.99,
    epsilon = 1e-08,
    rate = 0.005,
    rate_annealing = 1e-06,
    rate_decay = 1.0,
    momentum_start = 0.0,
    momentum_ramp = 1000000.0,
    momentum_stable = 0.0,
    nesterov_accelerated_gradient = TRUE,
    mini_batch_size = 32,
    standardize = TRUE,
    loss = "Automatic",
    reproducible = TRUE,
    autoencoder = FALSE,
    hash_buckets = NULL
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(hidden)) p$hidden <- hidden
  if (!missing(epochs)) p$epochs <- epochs
  if (!missing(activation)) p$activation <- activation
  if (!missing(input_dropout_ratio)) p$input_dropout_ratio <- input_dropout_ratio
  if (!missing(hidden_dropout_ratios)) p$hidden_dropout_ratios <- hidden_dropout_ratios
  if (!missing(l1)) p$l1 <- l1
  if (!missing(l2)) p$l2 <- l2
  if (!missing(adaptive_rate)) p$adaptive_rate <- adaptive_rate
  if (!missing(rho)) p$rho <- rho
  if (!missing(epsilon)) p$epsilon <- epsilon
  if (!missing(rate)) p$rate <- rate
  if (!missing(rate_annealing)) p$rate_annealing <- rate_annealing
  if (!missing(rate_decay)) p$rate_decay <- rate_decay
  if (!missing(momentum_start)) p$momentum_start <- momentum_start
  if (!missing(momentum_ramp)) p$momentum_ramp <- momentum_ramp
  if (!missing(momentum_stable)) p$momentum_stable <- momentum_stable
  if (!missing(nesterov_accelerated_gradient)) p$nesterov_accelerated_gradient <- nesterov_accelerated_gradient
  if (!missing(mini_batch_size)) p$mini_batch_size <- mini_batch_size
  if (!missing(standardize)) p$standardize <- standardize
  if (!missing(loss)) p$loss <- loss
  if (!missing(reproducible)) p$reproducible <- reproducible
  if (!missing(autoencoder)) p$autoencoder <- autoencoder
  if (!missing(hash_buckets)) p$hash_buckets <- hash_buckets
  .h2o.train_params("deeplearning", y, x, training_frame, validation_frame, p)
}

h2o.kmeans <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    k = 2,
    max_iterations = 10,
    init = "Furthest",
    standardize = TRUE,
    estimate_k = FALSE
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(k)) p$k <- k
  if (!missing(max_iterations)) p$max_iterations <- max_iterations
  if (!missing(init)) p$init <- init
  if (!missing(standardize)) p$standardize <- standardize
  if (!missing(estimate_k)) p$estimate_k <- estimate_k
  .h2o.train_params("kmeans", y, x, training_frame, validation_frame, p)
}

h2o.prcomp <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    k = 1,
    transform = "STANDARDIZE",
    pca_method = "GramSVD",
    use_all_factor_levels = FALSE
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(k)) p$k <- k
  if (!missing(transform)) p$transform <- transform
  if (!missing(pca_method)) p$pca_method <- pca_method
  if (!missing(use_all_factor_levels)) p$use_all_factor_levels <- use_all_factor_levels
  .h2o.train_params("pca", y, x, training_frame, validation_frame, p)
}

h2o.svd <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    nv = 1,
    transform = "NONE",
    svd_method = "Randomized",
    max_iterations = 4
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(nv)) p$nv <- nv
  if (!missing(transform)) p$transform <- transform
  if (!missing(svd_method)) p$svd_method <- svd_method
  if (!missing(max_iterations)) p$max_iterations <- max_iterations
  .h2o.train_params("svd", y, x, training_frame, validation_frame, p)
}

h2o.naiveBayes <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    laplace = 0.0,
    min_sdev = 0.001,
    eps_sdev = 0.0
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(laplace)) p$laplace <- laplace
  if (!missing(min_sdev)) p$min_sdev <- min_sdev
  if (!missing(eps_sdev)) p$eps_sdev <- eps_sdev
  .h2o.train_params("naivebayes", y, x, training_frame, validation_frame, p)
}

h2o.isolationForest <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    ntrees = 50,
    sample_size = 256,
    max_depth = 8,
    mtries = -1
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(ntrees)) p$ntrees <- ntrees
  if (!missing(sample_size)) p$sample_size <- sample_size
  if (!missing(max_depth)) p$max_depth <- max_depth
  if (!missing(mtries)) p$mtries <- mtries
  .h2o.train_params("isolationforest", y, x, training_frame, validation_frame, p)
}

h2o.extendedIsolationForest <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    ntrees = 100,
    sample_size = 256,
    extension_level = -1
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(ntrees)) p$ntrees <- ntrees
  if (!missing(sample_size)) p$sample_size <- sample_size
  if (!missing(extension_level)) p$extension_level <- extension_level
  .h2o.train_params("extendedisolationforest", y, x, training_frame, validation_frame, p)
}

h2o.glrm <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    k = 2,
    loss = "Quadratic",
    regularization_x = "None",
    regularization_y = "None",
    gamma_x = 0.0,
    gamma_y = 0.0,
    max_iterations = 100,
    init_step_size = 1.0,
    min_step_size = 1e-06,
    tolerance_rel = 1e-07,
    transform = "STANDARDIZE",
    init = "SVD"
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(k)) p$k <- k
  if (!missing(loss)) p$loss <- loss
  if (!missing(regularization_x)) p$regularization_x <- regularization_x
  if (!missing(regularization_y)) p$regularization_y <- regularization_y
  if (!missing(gamma_x)) p$gamma_x <- gamma_x
  if (!missing(gamma_y)) p$gamma_y <- gamma_y
  if (!missing(max_iterations)) p$max_iterations <- max_iterations
  if (!missing(init_step_size)) p$init_step_size <- init_step_size
  if (!missing(min_step_size)) p$min_step_size <- min_step_size
  if (!missing(tolerance_rel)) p$tolerance_rel <- tolerance_rel
  if (!missing(transform)) p$transform <- transform
  if (!missing(init)) p$init <- init
  .h2o.train_params("glrm", y, x, training_frame, validation_frame, p)
}

h2o.coxph <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    start_column = NULL,
    stop_column = NULL,
    ties = "efron",
    max_iterations = 20,
    tolerance = 1e-08
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(start_column)) p$start_column <- start_column
  if (!missing(stop_column)) p$stop_column <- stop_column
  if (!missing(ties)) p$ties <- ties
  if (!missing(max_iterations)) p$max_iterations <- max_iterations
  if (!missing(tolerance)) p$tolerance <- tolerance
  .h2o.train_params("coxph", y, x, training_frame, validation_frame, p)
}

h2o.isotonicregression <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    out_of_bounds = "clip"
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(out_of_bounds)) p$out_of_bounds <- out_of_bounds
  .h2o.train_params("isotonicregression", y, x, training_frame, validation_frame, p)
}

h2o.adaBoost <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    ntrees = 50,
    max_depth = 1,
    min_rows = 10.0,
    nbins = 255,
    nbins_cats = 1024,
    nbins_top_level = 1024,
    min_split_improvement = 1e-05,
    sample_rate = 1.0,
    col_sample_rate_per_tree = 1.0,
    score_tree_interval = 5,
    grow_policy = "depthwise",
    max_leaves = 0,
    calibrate_model = FALSE,
    calibration_frame = NULL,
    calibration_method = "AUTO",
    nlearners = 50,
    weak_learner = "DT",
    learn_rate = 0.5
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(ntrees)) p$ntrees <- ntrees
  if (!missing(max_depth)) p$max_depth <- max_depth
  if (!missing(min_rows)) p$min_rows <- min_rows
  if (!missing(nbins)) p$nbins <- nbins
  if (!missing(nbins_cats)) p$nbins_cats <- nbins_cats
  if (!missing(nbins_top_level)) p$nbins_top_level <- nbins_top_level
  if (!missing(min_split_improvement)) p$min_split_improvement <- min_split_improvement
  if (!missing(sample_rate)) p$sample_rate <- sample_rate
  if (!missing(col_sample_rate_per_tree)) p$col_sample_rate_per_tree <- col_sample_rate_per_tree
  if (!missing(score_tree_interval)) p$score_tree_interval <- score_tree_interval
  if (!missing(grow_policy)) p$grow_policy <- grow_policy
  if (!missing(max_leaves)) p$max_leaves <- max_leaves
  if (!missing(calibrate_model)) p$calibrate_model <- calibrate_model
  if (!missing(calibration_frame)) p$calibration_frame <- calibration_frame
  if (!missing(calibration_method)) p$calibration_method <- calibration_method
  if (!missing(nlearners)) p$nlearners <- nlearners
  if (!missing(weak_learner)) p$weak_learner <- weak_learner
  if (!missing(learn_rate)) p$learn_rate <- learn_rate
  .h2o.train_params("adaboost", y, x, training_frame, validation_frame, p)
}

h2o.decision_tree <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    ntrees = 50,
    max_depth = 10,
    min_rows = 10.0,
    nbins = 255,
    nbins_cats = 1024,
    nbins_top_level = 1024,
    min_split_improvement = 1e-05,
    sample_rate = 1.0,
    col_sample_rate_per_tree = 1.0,
    score_tree_interval = 5,
    grow_policy = "depthwise",
    max_leaves = 0,
    calibrate_model = FALSE,
    calibration_frame = NULL,
    calibration_method = "AUTO"
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(ntrees)) p$ntrees <- ntrees
  if (!missing(max_depth)) p$max_depth <- max_depth
  if (!missing(min_rows)) p$min_rows <- min_rows
  if (!missing(nbins)) p$nbins <- nbins
  if (!missing(nbins_cats)) p$nbins_cats <- nbins_cats
  if (!missing(nbins_top_level)) p$nbins_top_level <- nbins_top_level
  if (!missing(min_split_improvement)) p$min_split_improvement <- min_split_improvement
  if (!missing(sample_rate)) p$sample_rate <- sample_rate
  if (!missing(col_sample_rate_per_tree)) p$col_sample_rate_per_tree <- col_sample_rate_per_tree
  if (!missing(score_tree_interval)) p$score_tree_interval <- score_tree_interval
  if (!missing(grow_policy)) p$grow_policy <- grow_policy
  if (!missing(max_leaves)) p$max_leaves <- max_leaves
  if (!missing(calibrate_model)) p$calibrate_model <- calibrate_model
  if (!missing(calibration_frame)) p$calibration_frame <- calibration_frame
  if (!missing(calibration_method)) p$calibration_method <- calibration_method
  .h2o.train_params("decisiontree", y, x, training_frame, validation_frame, p)
}

h2o.word2vec <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    vec_size = 100,
    window_size = 5,
    min_word_freq = 5,
    epochs = 5,
    learning_rate = 0.025,
    negative_samples = 5,
    sent_sample_rate = 0.001
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(vec_size)) p$vec_size <- vec_size
  if (!missing(window_size)) p$window_size <- window_size
  if (!missing(min_word_freq)) p$min_word_freq <- min_word_freq
  if (!missing(epochs)) p$epochs <- epochs
  if (!missing(learning_rate)) p$learning_rate <- learning_rate
  if (!missing(negative_samples)) p$negative_samples <- negative_samples
  if (!missing(sent_sample_rate)) p$sent_sample_rate <- sent_sample_rate
  .h2o.train_params("word2vec", y, x, training_frame, validation_frame, p)
}

h2o.stackedEnsemble <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    base_models = c(),
    metalearner_algorithm = "AUTO",
    metalearner_params = list(),
    metalearner_nfolds = 5
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(base_models)) p$base_models <- base_models
  if (!missing(metalearner_algorithm)) p$metalearner_algorithm <- metalearner_algorithm
  if (!missing(metalearner_params)) p$metalearner_params <- metalearner_params
  if (!missing(metalearner_nfolds)) p$metalearner_nfolds <- metalearner_nfolds
  .h2o.train_params("stackedensemble", y, x, training_frame, validation_frame, p)
}

h2o.targetencoder <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    holdout_type = "none",
    blending = FALSE,
    inflection_point = 10.0,
    smoothing = 20.0,
    noise = 0.0,
    fold_column = NULL,
    nfolds = 5,
    seed = -1,
    columns = c()
) {
  p <- list()
  if (!missing(holdout_type)) p$holdout_type <- holdout_type
  if (!missing(blending)) p$blending <- blending
  if (!missing(inflection_point)) p$inflection_point <- inflection_point
  if (!missing(smoothing)) p$smoothing <- smoothing
  if (!missing(noise)) p$noise <- noise
  if (!missing(fold_column)) p$fold_column <- fold_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(seed)) p$seed <- seed
  if (!missing(columns)) p$columns <- columns
  .h2o.train_params("targetencoder", y, x, training_frame, validation_frame, p)
}

h2o.rulefit <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    algorithm = "AUTO",
    min_rule_length = 3,
    max_rule_length = 3,
    max_num_rules = -1,
    model_type = "rules_and_linear",
    rule_generation_ntrees = 50,
    distribution = "AUTO",
    lambda = NULL,
    remove_duplicates = TRUE
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(algorithm)) p$algorithm <- algorithm
  if (!missing(min_rule_length)) p$min_rule_length <- min_rule_length
  if (!missing(max_rule_length)) p$max_rule_length <- max_rule_length
  if (!missing(max_num_rules)) p$max_num_rules <- max_num_rules
  if (!missing(model_type)) p$model_type <- model_type
  if (!missing(rule_generation_ntrees)) p$rule_generation_ntrees <- rule_generation_ntrees
  if (!missing(distribution)) p$distribution <- distribution
  if (!missing(lambda)) p$lambda <- lambda
  if (!missing(remove_duplicates)) p$remove_duplicates <- remove_duplicates
  .h2o.train_params("rulefit", y, x, training_frame, validation_frame, p)
}

h2o.upliftRandomForest <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    nbins_cats = 1024,
    treatment_column = "treatment",
    uplift_metric = "KL",
    ntrees = 50,
    max_depth = 10,
    min_rows = 10.0,
    mtries = -2,
    sample_rate = 0.632,
    nbins = 255,
    min_split_improvement = 1e-05,
    score_tree_interval = 10
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(nbins_cats)) p$nbins_cats <- nbins_cats
  if (!missing(treatment_column)) p$treatment_column <- treatment_column
  if (!missing(uplift_metric)) p$uplift_metric <- uplift_metric
  if (!missing(ntrees)) p$ntrees <- ntrees
  if (!missing(max_depth)) p$max_depth <- max_depth
  if (!missing(min_rows)) p$min_rows <- min_rows
  if (!missing(mtries)) p$mtries <- mtries
  if (!missing(sample_rate)) p$sample_rate <- sample_rate
  if (!missing(nbins)) p$nbins <- nbins
  if (!missing(min_split_improvement)) p$min_split_improvement <- min_split_improvement
  if (!missing(score_tree_interval)) p$score_tree_interval <- score_tree_interval
  .h2o.train_params("upliftdrf", y, x, training_frame, validation_frame, p)
}

h2o.gam <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    family = "AUTO",
    gam_columns = c(),
    num_knots = c(),
    scale = c(),
    bs = c(),
    lambda = 0.0,
    standardize = TRUE,
    intercept = TRUE,
    max_iterations = 50,
    beta_epsilon = 1e-06,
    keep_gam_cols = FALSE
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(family)) p$family <- family
  if (!missing(gam_columns)) p$gam_columns <- gam_columns
  if (!missing(num_knots)) p$num_knots <- num_knots
  if (!missing(scale)) p$scale <- scale
  if (!missing(bs)) p$bs <- bs
  if (!missing(lambda)) p$lambda <- lambda
  if (!missing(standardize)) p$standardize <- standardize
  if (!missing(intercept)) p$intercept <- intercept
  if (!missing(max_iterations)) p$max_iterations <- max_iterations
  if (!missing(beta_epsilon)) p$beta_epsilon <- beta_epsilon
  if (!missing(keep_gam_cols)) p$keep_gam_cols <- keep_gam_cols
  .h2o.train_params("gam", y, x, training_frame, validation_frame, p)
}

h2o.modelSelection <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    mode = "maxr",
    family = "AUTO",
    max_predictor_number = 1,
    min_predictor_number = 1,
    intercept = TRUE,
    standardize = TRUE,
    p_values_threshold = 0.0,
    missing_values_handling = "mean_imputation"
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(mode)) p$mode <- mode
  if (!missing(family)) p$family <- family
  if (!missing(max_predictor_number)) p$max_predictor_number <- max_predictor_number
  if (!missing(min_predictor_number)) p$min_predictor_number <- min_predictor_number
  if (!missing(intercept)) p$intercept <- intercept
  if (!missing(standardize)) p$standardize <- standardize
  if (!missing(p_values_threshold)) p$p_values_threshold <- p_values_threshold
  if (!missing(missing_values_handling)) p$missing_values_handling <- missing_values_handling
  .h2o.train_params("modelselection", y, x, training_frame, validation_frame, p)
}

h2o.anovaglm <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    family = "AUTO",
    highest_interaction_term = 0,
    lambda = 0.0,
    standardize = TRUE
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(family)) p$family <- family
  if (!missing(highest_interaction_term)) p$highest_interaction_term <- highest_interaction_term
  if (!missing(lambda)) p$lambda <- lambda
  if (!missing(standardize)) p$standardize <- standardize
  .h2o.train_params("anovaglm", y, x, training_frame, validation_frame, p)
}

h2o.aggregator <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    target_num_exemplars = 5000,
    rel_tol_num_exemplars = 0.5,
    transform = "NORMALIZE",
    categorical_encoding = "AUTO"
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(target_num_exemplars)) p$target_num_exemplars <- target_num_exemplars
  if (!missing(rel_tol_num_exemplars)) p$rel_tol_num_exemplars <- rel_tol_num_exemplars
  if (!missing(transform)) p$transform <- transform
  if (!missing(categorical_encoding)) p$categorical_encoding <- categorical_encoding
  .h2o.train_params("aggregator", y, x, training_frame, validation_frame, p)
}

h2o.infogram <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    protected_columns = c(),
    safety_index_threshold = 0.1,
    relevance_index_threshold = 0.1,
    total_information_threshold = 0.1,
    net_information_threshold = 0.1,
    ntrees = 20,
    max_depth = 5,
    top_n_features = 50
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(protected_columns)) p$protected_columns <- protected_columns
  if (!missing(safety_index_threshold)) p$safety_index_threshold <- safety_index_threshold
  if (!missing(relevance_index_threshold)) p$relevance_index_threshold <- relevance_index_threshold
  if (!missing(total_information_threshold)) p$total_information_threshold <- total_information_threshold
  if (!missing(net_information_threshold)) p$net_information_threshold <- net_information_threshold
  if (!missing(ntrees)) p$ntrees <- ntrees
  if (!missing(max_depth)) p$max_depth <- max_depth
  if (!missing(top_n_features)) p$top_n_features <- top_n_features
  .h2o.train_params("infogram", y, x, training_frame, validation_frame, p)
}

h2o.psvm <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    kernel_type = "gaussian",
    gamma = -1.0,
    hyper_param = 1.0,
    positive_weight = 1.0,
    negative_weight = 1.0,
    rank_ratio = -1.0,
    max_iterations = 200,
    convergence_tol = 1e-06
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(kernel_type)) p$kernel_type <- kernel_type
  if (!missing(gamma)) p$gamma <- gamma
  if (!missing(hyper_param)) p$hyper_param <- hyper_param
  if (!missing(positive_weight)) p$positive_weight <- positive_weight
  if (!missing(negative_weight)) p$negative_weight <- negative_weight
  if (!missing(rank_ratio)) p$rank_ratio <- rank_ratio
  if (!missing(max_iterations)) p$max_iterations <- max_iterations
  if (!missing(convergence_tol)) p$convergence_tol <- convergence_tol
  .h2o.train_params("psvm", y, x, training_frame, validation_frame, p)
}

h2o.hglm <- function(
    y = NULL,
    x = NULL,
    training_frame,
    validation_frame = NULL,
    ignored_columns = c(),
    weights_column = NULL,
    offset_column = NULL,
    nfolds = 0,
    fold_assignment = "modulo",
    keep_cross_validation_predictions = FALSE,
    seed = -1,
    max_runtime_secs = 0.0,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    checkpoint = NULL,
    export_checkpoints_dir = NULL,
    random_columns = c(),
    method = "EM",
    max_iterations = 100,
    em_epsilon = 1e-06,
    standardize = FALSE,
    intercept = TRUE
) {
  p <- list()
  if (!missing(ignored_columns)) p$ignored_columns <- ignored_columns
  if (!missing(weights_column)) p$weights_column <- weights_column
  if (!missing(offset_column)) p$offset_column <- offset_column
  if (!missing(nfolds)) p$nfolds <- nfolds
  if (!missing(fold_assignment)) p$fold_assignment <- fold_assignment
  if (!missing(keep_cross_validation_predictions)) p$keep_cross_validation_predictions <- keep_cross_validation_predictions
  if (!missing(seed)) p$seed <- seed
  if (!missing(max_runtime_secs)) p$max_runtime_secs <- max_runtime_secs
  if (!missing(stopping_rounds)) p$stopping_rounds <- stopping_rounds
  if (!missing(stopping_metric)) p$stopping_metric <- stopping_metric
  if (!missing(stopping_tolerance)) p$stopping_tolerance <- stopping_tolerance
  if (!missing(checkpoint)) p$checkpoint <- checkpoint
  if (!missing(export_checkpoints_dir)) p$export_checkpoints_dir <- export_checkpoints_dir
  if (!missing(random_columns)) p$random_columns <- random_columns
  if (!missing(method)) p$method <- method
  if (!missing(max_iterations)) p$max_iterations <- max_iterations
  if (!missing(em_epsilon)) p$em_epsilon <- em_epsilon
  if (!missing(standardize)) p$standardize <- standardize
  if (!missing(intercept)) p$intercept <- intercept
  .h2o.train_params("hglm", y, x, training_frame, validation_frame, p)
}

