"""GLM — successor of ``hex.glm.GLM`` / ``GLMTask.GLMIterationTask`` /
``hex.glm.GLMModel`` / ``ComputationState`` [UNVERIFIED upstream paths,
SURVEY.md §2.2, §3.3].

Architecture (the BASELINE.json north-star GLM path):
- Per IRLS iteration ONE fused device program computes the working response,
  weights, weighted Gram XᵀWX and XᵀWz over the row-sharded design matrix —
  the ``GLMIterationTask.doAll`` successor, with XLA's psum replacing the
  MRTask log-tree reduce.
- The (p,p) solve is host-side float64: Cholesky when no L1, ADMM
  soft-thresholding for elastic net — mirroring H2O's single-node solve.
- Families: gaussian, binomial, quasibinomial, fractionalbinomial, poisson,
  gamma, tweedie, negativebinomial, multinomial (cycling per-class IRLS).
- Regularization: elastic net (alpha/lambda), full lambda search path with
  warm starts, strong-rule-free (dense Gram is cheap on MXU).
- Standardization, P-values for unpenalized fits, coefficient
  destandardization — matching ``GLMModel`` outputs.

Default lambda: like H2O, when ``lambda_`` is unset and ``lambda_search`` is
off we apply light shrinkage ``lambda_max/1000`` [UNVERIFIED exact upstream
default — H2O derives a small data-dependent default].
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.cluster.job import Job
from h2o3_tpu.cluster.registry import DKV
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.datainfo import (
    MEAN_IMPUTATION, SKIP, DataInfo, FactoredDesign,
)
from h2o3_tpu.models.glm_families import get_family
from h2o3_tpu.models.model_base import CommonParams, Model, ModelBuilder
from h2o3_tpu.ops.gram import (
    admm_elastic_net,
    admm_elastic_net_device,
    cat_gram_local,
    cho_solve_jitter_device,
    gram_collective_bytes,
    solve_cholesky,
    weighted_gram,
    weighted_gram_factored,
)
from h2o3_tpu.utils import faults
from h2o3_tpu.utils import metrics as _mx
from h2o3_tpu.utils.log import Log

_HI = jax.lax.Precision.HIGHEST

_IRLS_ITERS = _mx.counter(
    "glm_irls_iterations_total", "IRLS iterations executed")
_IRLS_SECONDS = _mx.histogram(
    "glm_irls_iteration_seconds",
    "per-IRLS-iteration wall time (Gram pass + solve; the hex.glm hot loop)")
_IRLS_SOLVE_SECONDS = _mx.histogram(
    "glm_irls_solve_seconds",
    "host-side (p,p) solve wall time per IRLS iteration (Cholesky/ADMM), "
    "split out of glm_irls_iteration_seconds so the fused-IRLS A/B can "
    "attribute its win; the fused lane solves on-device and reports only "
    "the iteration histogram")
# host dispatches issued by the IRLS loop (the fused-lane acceptance
# metric: O(iterations) unfused vs O(iterations/K) fused) and program-cache
# traffic for the fused chunk programs — the BUILD_STATS-style contract
# counters (always on, like the tree builders')
_GLM_DISPATCHES = _mx.counter(
    "glm_dispatches_total",
    "device-program launches issued by the GLM IRLS loop", always=True)
_GLM_COMPILED = _mx.counter(
    "glm_programs_compiled_total",
    "fused IRLS chunk program cache misses", always=True)
_GLM_HITS = _mx.counter(
    "glm_program_cache_hits_total",
    "fused IRLS chunk program cache hits (same shape bucket, no recompile)",
    always=True)
# the PR-5 collective byte family grows GLM phases (gram_reduce = the
# psum_scatter of G row blocks + b/sw psums, gram_gather = the one
# all_gather that reassembles G for the solve); same replication-volume
# model, tallied per executed iteration at dispatch time
_COLL_BYTES = _mx.counter(
    "tree_collective_bytes_total",
    "per-device collective payload bytes moved by tree builds (replication-"
    "volume model), by phase", always=True)

# Fallback observability (ISSUE 15): fits that WANT the fused while_loop
# lane (the knob says fuse) but drop to a slow lane for a structural
# reason — out-of-core streaming needs per-block host accumulation, a
# singular-in-f32 chunk drops its lambda to the host f64 tail, and a
# rejected fused-ordinal optimum falls back to the scipy driver.
# (compute_p_values rode the host-f64 trajectory until ISSUE 16; it now
# fuses — the covariance comes from the final device Gram at the
# converged beta, so the p_values reason only fires on a regression.)
_GLM_FALLBACKS = _mx.counter(
    "glm_fuse_fallbacks_total",
    "GLM fits (or lambda steps) that fell back from the fused while_loop "
    "lane while the fuse knob was on, by structural reason", always=True)

# the factored design (categoricals as codes, models/datainfo.py): the bytes
# of the design operands each GLM transform made (dense: 4 a design column a
# row; factored: 4 a categorical column plus 4 a dense column), and the
# Gram passes the categorical kernel (ops/gram.py::cat_gram_local) ran
_DESIGN_BYTES = _mx.counter(
    "glm_design_bytes_total",
    "device bytes of the design operands each GLM transform made",
    always=True)
_CAT_GRAM_CALLS = _mx.counter(
    "glm_cat_gram_calls_total",
    "Gram passes over a factored design (the cat_gram_pallas kernel)",
    always=True)

# fused IRLS chunk program cache: (shape bucket, family, solver branch,
# mesh, backend) -> compiled chunk. The shape-bucket ladder (rows ride the
# frame's bucketed npad; design columns pad to a multiple of 4 below) makes
# AutoML/grid rebuilds of near-identical frames reuse one program.
_GLM_PROGRAMS: dict = {}


def _glm_fuse_chunk(params) -> int:
    """Iterations per fused dispatch (K); 0 = the unfused per-iteration
    path. ``auto`` fuses with K=8 everywhere (the chunk program is plain
    XLA — while_loop + Cholesky — so the CPU proxy runs it too); an integer
    forces that K. compute_p_values fits fuse too (ISSUE 16): the
    covariance derives from the final device Gram at the converged beta
    (:meth:`GLM._p_values` re-runs one ``_irls_pass``), so nothing about
    the trajectory lane constrains it. With export_checkpoints_dir set
    the chunk clamps to 1 so PR-2's per-iteration irls_state snapshots land
    at the same loop positions."""
    from h2o3_tpu import config

    raw = config.get("H2O3_TPU_GLM_FUSE").strip().lower()
    if raw == "0":
        return 0
    k = int(raw) if raw.isdigit() else 8
    if getattr(params, "export_checkpoints_dir", None):
        return 1
    return max(k, 1)


def _mesh_shards() -> int:
    from h2o3_tpu.parallel.mesh import get_mesh

    return int(get_mesh().devices.size)


def _glm_pad_cols(p_real: int) -> int:
    """Design-matrix width for the fused lane: the PR-1 shape-bucket ladder
    (multiple of 4 under H2O3_TPU_SHAPE_BUCKETS) and then a multiple of the
    shard count so the Gram psum_scatter deals equal row blocks. Padded
    columns are all-zero with a unit solve diagonal — their coefficients
    are exactly zero, proven inert in tests/test_glm_dl_fuse.py."""
    from h2o3_tpu import config
    from h2o3_tpu.parallel.mesh import pad_cols_to_shards

    p = p_real
    if config.get_bool("H2O3_TPU_SHAPE_BUCKETS"):
        p = -(-p // 4) * 4
    return pad_cols_to_shards(p)


@dataclass
class GLMParams(CommonParams):
    family: str = "AUTO"
    link: str = "family_default"
    solver: str = "AUTO"  # -> IRLSM
    alpha: float | None = None
    lambda_: Any = None  # scalar, list, or None (auto)
    lambda_search: bool = False
    nlambdas: int = -1
    lambda_min_ratio: float = -1.0
    standardize: bool = True
    intercept: bool = True
    max_iterations: int = -1
    beta_epsilon: float = 1e-4
    objective_epsilon: float = 1e-6
    tweedie_variance_power: float = 0.0
    tweedie_link_power: float = 1.0
    theta: float = 1e-5
    missing_values_handling: str = MEAN_IMPUTATION
    compute_p_values: bool = False
    non_negative: bool = False
    # upstream `interactions` (all pairwise among the listed columns) and
    # `interaction_pairs` (explicit pairs); num x num and cat x num supported
    interactions: Any = None
    interaction_pairs: Any = None
    # feature hashing for Criteo-class cardinalities: cat columns wider than
    # this expand to a fixed hash-bucket indicator block (datainfo.py)
    hash_buckets: Any = None


# ---------------------------------------------------------------------------
# device programs (cached per family via partial+jit)


def _single_irls(family: str, p: "GLMParams") -> bool:
    """The single-vector IRLS lane with no p-values: the fits that may
    stream their design, and whose design may be factored."""
    return (family not in ("multinomial", "ordinal")
            and p.solver.upper().replace("-", "_") not in ("L_BFGS", "LBFGS")
            and not p.compute_p_values)


def _factored(family: str, p: "GLMParams", di: DataInfo) -> bool:
    """Whether a fit (or a model's scoring) takes the factored design: the
    single-vector IRLS lane on a DataInfo with a plain categorical column.
    Multinomial, ordinal, L-BFGS and p-values keep the dense one
    (docs/MIGRATION.md), as do interaction and hashed columns."""
    return _single_irls(family, p) and di.factorable()


def _glm_design(di: DataInfo, frame: Frame, factored: bool):
    """``(X, valid)``: the design, factored or dense, counted in
    ``glm_design_bytes_total``."""
    X, valid = (di.transform_factored(frame) if factored
                else di.transform(frame))
    _DESIGN_BYTES.inc(X.nbytes)
    return X, valid


def _eta(X, beta):
    """``X @ beta``: the einsum of a dense design; for a factored one a
    gather of ``beta`` for each categorical column (code -1 takes the 0
    appended past the end) plus the dense columns' product."""
    if not isinstance(X, FactoredDesign):
        return jnp.einsum("np,p->n", X, beta, precision=_HI)
    with jax.named_scope("ph_eta_cat"):
        ext = jnp.concatenate([beta, jnp.zeros((1,), beta.dtype)])
        eta = None
        for c in range(X.codes.shape[0]):
            code = X.codes[c]
            g = jnp.take(ext, jnp.where(code >= 0, code, beta.shape[0]))
            eta = g if eta is None else eta + g
        if X.dense_pos:
            eta = eta + jnp.einsum("qn,q->n", X.dense,
                                   beta[np.asarray(X.dense_pos)],
                                   precision=_HI)
        return eta


def _gram(X, W, z):
    """(G, b, sw) of :func:`weighted_gram`, from codes for a factored
    design."""
    if isinstance(X, FactoredDesign):
        return weighted_gram_factored(X.codes, X.dense, W, z, p=X.p,
                                      dense_pos=X.dense_pos)
    return weighted_gram(X, W, z)


def _irls_weights(fam, X, y, w, offset, beta):
    """The GLMIterationTask row math for the current beta: IRLS working
    weights W, working response z, and the deviance — shared op-for-op by
    the per-iteration pass and the fused while_loop body so the two lanes
    compute identical iterations."""
    with jax.named_scope("ph_gram"):  # the Gram pass's row math
        eta = _eta(X, beta) + offset
        mu = fam.link.inv(eta)
        d = fam.link.dinv(eta)
        d = jnp.where(d == 0, 1e-10, jnp.sign(d) * jnp.maximum(jnp.abs(d), 1e-10))
        var = fam.variance(mu)
        z = (eta - offset) + (y - mu) / d
        W = w * d * d / var
    with jax.named_scope("ph_dev"):
        dev = fam.deviance(y, mu, w)
    return W, z, dev


@partial(jax.jit, static_argnames=("family_key", "fam_args"))
def _irls_pass(X, y, w, offset, beta, family_key, fam_args):
    """One GLMIterationTask: Gram/XtWz for the current beta + deviance."""
    fam = get_family(family_key, *fam_args)
    W, z, dev = _irls_weights(fam, X, y, w, offset, beta)
    G, b, sw = _gram(X, W, z)
    return G, b, dev


def _fused_chunk_program(npad, p_pad, family_key, fam_args, l1_on,
                         non_negative, layout=None):
    """Build (or fetch) the compiled K-iterations-per-dispatch IRLS chunk.

    One ``lax.while_loop`` runs up to ``kmax`` IRLS iterations entirely on
    device: the Gram pass ends in a psum_scatter of contiguous G row blocks
    over the rows mesh axis (each device keeps p/P rows; one all_gather
    hands the full G to the replicated solve), and the Cholesky-with-jitter
    or ADMM solve runs in f32 on device. The loop exits early on
    convergence (``stop``) or a non-finite solve (``bad`` — the host f64
    lstsq fallback lane takes over). All regularization/convergence scalars
    are DYNAMIC arguments so one program serves the whole lambda path;
    ``beta`` is donated (the carry pipelines across chunk dispatches).
    ``layout`` is a factored design's dense positions (None: dense)."""
    from h2o3_tpu.parallel.mesh import get_mesh, mesh_key

    key = ("glm_irls_chunk", npad, p_pad, layout, family_key, fam_args,
           bool(l1_on), bool(non_negative), mesh_key(), jax.default_backend())
    fn = _GLM_PROGRAMS.get(key)
    if fn is not None:
        _GLM_HITS.inc()
        return fn
    _GLM_COMPILED.inc()

    from jax.sharding import PartitionSpec as Spec

    from h2o3_tpu.parallel.mesh import col_axis_name, row_axes, row_pspec

    fam = get_family(family_key, *fam_args)
    mesh = get_mesh()
    n_sh = int(mesh.devices.size)
    cax = col_axis_name(mesh)
    ar = jnp.arange(p_pad)

    def gram_dev_sharded(X, y, w, offset, beta):
        """One GLMIterationTask with the MRTask reduce made explicit and
        PACKED: the per-device row math (working weights, working response,
        local Gram/XtWz partials, local deviance) runs inside shard_map,
        the Gram reduction ends in a psum_scatter of contiguous G row
        blocks over the column-block axis (2-D meshes reduce the rows axis
        exactly first, inside the wrapper), b and the deviance ride ONE
        packed psum, and a single all_gather reassembles G for the solve —
        three collective rendezvous per iteration instead of five
        (collective count, not just volume, is what the CPU proxy pays
        for). A factored design's local Gram is the categorical kernel's."""
        factored = isinstance(X, FactoredDesign)

        def local(Xl, yl, wl, ol, beta):
            from h2o3_tpu.ops import collectives

            W, z, dev = _irls_weights(fam, Xl, yl, wl, ol, beta)
            if factored:
                G_l, b_l, _ = cat_gram_local(
                    Xl.codes, Xl.dense, W, z, p=Xl.p, dense_pos=Xl.dense_pos)
                # one exact reduce over every row axis at once (as
                # weighted_gram_factored's): G row blocks scattered over
                # all the devices and gathered back, so a fit gives the
                # same numbers on a 1-D and a 2-D mesh of the same devices
                axes = row_axes(mesh)
                rows = -(-p_pad // n_sh) * n_sh
                G_blk = jax.lax.psum_scatter(
                    jnp.pad(G_l, ((0, rows - p_pad), (0, 0))), axes,
                    scatter_dimension=0, tiled=True)
                vec = jax.lax.psum(jnp.concatenate([b_l, dev[None]]), axes)
                G = jax.lax.all_gather(G_blk, axes, axis=0, tiled=True)
                return G[:p_pad], vec[:p_pad], vec[p_pad]
            with jax.named_scope("ph_gram"):
                Xw = Xl * W[:, None]
                G_l = jnp.einsum("np,nq->pq", Xw, Xl, precision=_HI)
                b_l = jnp.einsum("np,n->p", Xw, z, precision=_HI)
            # the bulk G reduce rides the collective lane (quantized with a
            # residual-correction pass when on — the solve consumes G, so
            # it keeps ~14 effective mantissa bits); the small packed
            # b/deviance psum and the solve's G gather stay exact f32 so
            # convergence tests and the solve RHS are untouched
            G_blk = collectives.psum_scatter(
                G_l, n_dev=n_sh, passes=2, mesh=mesh)
            vec = collectives.exact_psum(
                jnp.concatenate([b_l, dev[None]]), mesh)
            G = jax.lax.all_gather(G_blk, cax, axis=0, tiled=True)
            return G, vec[:p_pad], vec[p_pad]

        from h2o3_tpu.parallel.mesh import shard_map

        rspec = row_pspec(mesh)
        if factored:
            lanes = row_pspec(mesh, ndim=2, axis=1)
            xspec = FactoredDesign(lanes, lanes, X.dense_pos, X.p)
        else:
            xspec = row_pspec(mesh, ndim=2)
        return shard_map(
            local, mesh,
            in_specs=(xspec, rspec, rspec, rspec, Spec()),
            out_specs=(Spec(), Spec(), Spec()),
            check_vma=False,
        )(X, y, w, offset, beta)

    def chunk(beta, dev_prev, X, y, w, offset, kmax, l1, l2,
              beta_eps, obj_eps, icpt, pad_diag, real_p):
        def cond(c):
            _, _, it, stop, bad = c
            return (it < kmax) & ~stop & ~bad

        def body(c):
            beta, dev_prev, it, stop, bad = c
            if n_sh > 1:
                G, b, dev = gram_dev_sharded(X, y, w, offset, beta)
            else:
                W, z, dev = _irls_weights(fam, X, y, w, offset, beta)
                G, b, _sw = _gram(X, W, z)
            with jax.named_scope("ph_solve"):
                if l1_on:
                    beta_new, ok = admm_elastic_net_device(
                        G, b, l1, l2, icpt, pad_diag, real_p,
                        non_negative=non_negative,
                    )
                else:
                    # Gp = G + l2*I with the intercept unpenalized (the host
                    # path's Gp[icpt, icpt] -= l2), plus the unit diagonal
                    # that keeps padded bucket columns invertible at exactly
                    # zero
                    extra = l2 * jnp.where(ar == icpt, 0.0, 1.0) + pad_diag
                    beta_new, ok = cho_solve_jitter_device(G, b, extra)
                    if non_negative:
                        beta_new = jnp.where(
                            (ar != icpt) & (beta_new < 0), 0.0, beta_new
                        )
            bad = ~ok | ~jnp.all(jnp.isfinite(beta_new))
            delta = jnp.max(jnp.abs(beta_new - beta))
            stop = ~bad & (
                (delta < beta_eps)
                | (jnp.abs(dev_prev - dev)
                   / jnp.maximum(jnp.abs(dev), 1e-10) < obj_eps)
            )
            beta = jnp.where(bad, beta, beta_new)
            dev_prev = jnp.where(stop | bad, dev_prev, dev)
            it = it + jnp.where(bad, 0, 1)
            return beta, dev_prev, it, stop, bad

        return jax.lax.while_loop(
            cond, body,
            (beta, dev_prev, jnp.int32(0), jnp.asarray(False),
             jnp.asarray(False)),
        )

    fn = jax.jit(chunk, donate_argnums=(0,))
    _GLM_PROGRAMS[key] = fn
    return fn


def _fused_multinomial_program(npad, p_pad, K, l1_on, non_negative):
    """Build (or fetch) the compiled fused multinomial cycling-IRLS chunk
    (ISSUE 15): ONE ``lax.while_loop`` runs up to ``kmax`` outer iterations
    per dispatch, each iteration a ``lax.scan`` over the K classes — class
    k's Gram pass sees the classes already updated this iteration, exactly
    the host loop's in-place cycling — with the sharded-Gram psum_scatter
    and the on-device Cholesky/ADMM solve per class reused from the
    single-response lane. The convergence exit replays the host rule
    (relative -2LL change from the LAST class's pass); any non-finite f32
    class solve sets ``bad``, discards that iteration's Beta wholesale and
    exits so the host float64 cycling tail takes over mid-trajectory."""
    from jax.sharding import PartitionSpec as Spec

    from h2o3_tpu.parallel.mesh import (
        col_axis_name, get_mesh, mesh_key, row_pspec, shard_map,
    )

    key = ("glm_multinom_chunk", npad, p_pad, K, bool(l1_on),
           bool(non_negative), mesh_key(), jax.default_backend())
    fn = _GLM_PROGRAMS.get(key)
    if fn is not None:
        _GLM_HITS.inc()
        return fn
    _GLM_COMPILED.inc()

    mesh = get_mesh()
    n_sh = int(mesh.devices.size)
    cax = col_axis_name(mesh)
    ar = jnp.arange(p_pad)

    def row_math(Xl, Yl, wl, Beta, k):
        """The _multinomial_pass row ops for class k — shared by the
        replicated and sharded bodies so both lanes compute the identical
        per-row floats."""
        Eta = jnp.einsum("np,pk->nk", Xl, Beta, precision=_HI)
        Eta = Eta - jax.scipy.special.logsumexp(Eta, axis=1, keepdims=True)
        Mu = jnp.exp(Eta)
        mu_k = jnp.clip(
            jax.lax.dynamic_index_in_dim(Mu, k, 1, keepdims=False),
            1e-10, 1 - 1e-10)
        wk = wl * mu_k * (1 - mu_k)
        beta_k = jax.lax.dynamic_index_in_dim(Beta, k, 1, keepdims=False)
        eta_k = jnp.einsum("np,p->n", Xl, beta_k, precision=_HI)
        yk = jax.lax.dynamic_index_in_dim(Yl, k, 1, keepdims=False)
        z = eta_k + (yk - mu_k) / jnp.maximum(
            wk / jnp.maximum(wl, 1e-10), 1e-10)
        Xw = Xl * wk[:, None]
        G_l = jnp.einsum("np,nq->pq", Xw, Xl, precision=_HI)
        b_l = jnp.einsum("np,n->p", Xw, z, precision=_HI)
        ll_l = jnp.sum(wl * jnp.sum(Yl * Eta, axis=1))
        return G_l, b_l, ll_l

    def class_pass(X, Y1h, w, Beta, k):
        if n_sh <= 1:
            G, b, ll = row_math(X, Y1h, w, Beta, k)
            return G, b, -2.0 * ll

        def local(Xl, Yl, wl, Beta, k):
            from h2o3_tpu.ops import collectives

            G_l, b_l, ll_l = row_math(Xl, Yl, wl, Beta, k)
            # same collective shape as the single-response fused lane:
            # bulk G through the (possibly quantized, residual-corrected)
            # scatter, packed exact psum for b/ll, one exact G gather
            G_blk = collectives.psum_scatter(
                G_l, n_dev=n_sh, passes=2, mesh=mesh)
            vec = collectives.exact_psum(
                jnp.concatenate([b_l, ll_l[None]]), mesh)
            G = jax.lax.all_gather(G_blk, cax, axis=0, tiled=True)
            return G, vec[:p_pad], -2.0 * vec[p_pad]

        rspec = row_pspec(mesh)
        return shard_map(
            local, mesh,
            in_specs=(row_pspec(mesh, ndim=2), row_pspec(mesh, ndim=2),
                      rspec, Spec(), Spec()),
            out_specs=(Spec(), Spec(), Spec()),
            check_vma=False,
        )(X, Y1h, w, Beta, k)

    def chunk(Beta, ll_prev, X, Y1h, w, kmax, l1, l2, obj_eps, icpt,
              pad_diag, real_p):
        def cond(c):
            _, _, it, stop, bad = c
            return (it < kmax) & ~stop & ~bad

        def body(c):
            Beta0, ll_prev, it, stop, bad = c

            def cstep(carry, k):
                Beta, bad_c = carry
                G, b, m2ll = class_pass(X, Y1h, w, Beta, k)
                if l1_on:
                    beta_k, ok = admm_elastic_net_device(
                        G, b, l1, l2, icpt, pad_diag, real_p,
                        non_negative=non_negative,
                    )
                else:
                    extra = l2 * jnp.where(ar == icpt, 0.0, 1.0) + pad_diag
                    beta_k, ok = cho_solve_jitter_device(G, b, extra)
                    if non_negative:
                        beta_k = jnp.where(
                            (ar != icpt) & (beta_k < 0), 0.0, beta_k)
                bad_k = ~ok | ~jnp.all(jnp.isfinite(beta_k))
                Beta = jnp.where(
                    bad_k, Beta,
                    jax.lax.dynamic_update_slice(
                        Beta, beta_k[:, None], (0, k)),
                )
                return (Beta, bad_c | bad_k), m2ll

            (Beta_new, bad_it), m2lls = jax.lax.scan(
                cstep, (Beta0, jnp.asarray(False)),
                jnp.arange(K, dtype=jnp.int32),
            )
            ll_now = m2lls[-1]  # the host rule: the LAST class's pass
            bad = bad_it
            stop = ~bad & (
                jnp.abs(ll_prev - ll_now)
                / jnp.maximum(jnp.abs(ll_now), 1e-10) < obj_eps
            )
            # a bad iteration is discarded WHOLE: the host f64 tail redoes
            # it from the pre-iteration Beta (the single-response rule)
            Beta = jnp.where(bad, Beta0, Beta_new)
            ll_prev = jnp.where(stop | bad, ll_prev, ll_now)
            it = it + jnp.where(bad, 0, 1)
            return Beta, ll_prev, it, stop, bad

        return jax.lax.while_loop(
            cond, body,
            (Beta, ll_prev, jnp.int32(0), jnp.asarray(False),
             jnp.asarray(False)),
        )

    fn = jax.jit(chunk, donate_argnums=(0,))
    _GLM_PROGRAMS[key] = fn
    return fn


@partial(jax.jit, static_argnames=("family_key", "fam_args"))
def _glm_dev_grad(X, y, w, offset, beta, family_key, fam_args):
    """Full-batch deviance + gradient in one fused pass (L-BFGS objective)."""
    fam = get_family(family_key, *fam_args)

    def dev(b):
        eta = jnp.einsum("np,p->n", X, b, precision=_HI) + offset
        mu = fam.link.inv(eta)
        return fam.deviance(y, mu, w)

    return jax.value_and_grad(dev)(beta)


@partial(jax.jit, static_argnames=("family_key", "fam_args"))
def _deviance_pass(X, y, w, offset, beta, family_key, fam_args):
    fam = get_family(family_key, *fam_args)
    with jax.named_scope("ph_dev"):
        eta = _eta(X, beta) + offset
        mu = fam.link.inv(eta)
        return fam.deviance(y, mu, w)


@partial(jax.jit, static_argnames=("K",))
def _multinomial_pass(X, Y1h, w, Beta, K, k):
    """Cycling-IRLS pass for class k of a multinomial model."""
    Eta = jnp.einsum("np,pk->nk", X, Beta, precision=_HI)
    Eta = Eta - jax.scipy.special.logsumexp(Eta, axis=1, keepdims=True)
    Mu = jnp.exp(Eta)
    mu_k = jnp.clip(Mu[:, k], 1e-10, 1 - 1e-10)
    wk = w * mu_k * (1 - mu_k)
    eta_k = jnp.einsum("np,p->n", X, Beta[:, k], precision=_HI)
    z = eta_k + (Y1h[:, k] - mu_k) / jnp.maximum(wk / jnp.maximum(w, 1e-10), 1e-10)
    G, b, sw = weighted_gram(X, wk, z)
    ll = jnp.sum(w * jnp.sum(Y1h * Eta, axis=1))
    return G, b, -2.0 * ll


def _fam_args(p) -> tuple:
    """``get_family``'s arguments after the family's name, from the params
    (hashable: the static key of the family's traced programs)."""
    return (
        p.link,
        float(p.tweedie_variance_power or 1.5),
        float(p.tweedie_link_power),
        float(p.theta),
    )


@partial(jax.jit, static_argnames=("family_key", "fam_args"))
@jax.named_scope("ph_score")  # a traced program: the scope names its operations
def _linear_mu(X, beta, offset, family_key, fam_args):
    """Linear predictor and inverse link of the single-vector families."""
    fam = get_family(family_key, *fam_args)
    eta = _eta(X, beta) + offset
    return fam.link.inv(eta)


@partial(jax.jit, static_argnames=())
@jax.named_scope("ph_score")
def _softmax_probs(X, Beta):
    Eta = jnp.einsum("np,pk->nk", X, Beta, precision=_HI)
    return jax.nn.softmax(Eta, axis=1)


# ---------------------------------------------------------------------------
# ordinal (proportional odds): P(y<=j) = sigmoid(theta_j - x.beta).
# One fused device program computes NLL + gradient; the (tiny) parameter
# vector is driven by host L-BFGS — the GLM "L_BFGS" solver reuses the same
# loss-plus-grad-on-device / optimize-on-host split.


@partial(jax.jit, static_argnames=("K",))
def _ordinal_nll_grad(X, y, w, beta, raw_cuts, K):
    """NLL and grad for proportional odds with ordered cuts.

    Cuts parameterized as theta_1 = raw_1, theta_j = theta_{j-1} +
    exp(raw_j) so ordering is unconstrained in raw space.
    """
    def nll(params):
        b = params[: X.shape[1]]
        raw = params[X.shape[1] :]
        theta = jnp.cumsum(
            jnp.concatenate([raw[:1], jnp.exp(raw[1:])])
        )  # (K-1,) ordered
        eta = jnp.einsum("np,p->n", X, b, precision=_HI)
        # P(y<=j) for j=0..K-2 ; clip for the log
        cum = jax.nn.sigmoid(theta[None, :] - eta[:, None])  # (n, K-1)
        lo = jnp.concatenate([jnp.zeros((X.shape[0], 1)), cum], axis=1)
        hi = jnp.concatenate([cum, jnp.ones((X.shape[0], 1))], axis=1)
        pk = jnp.clip(hi - lo, 1e-12, 1.0)  # (n, K)
        yi = jnp.clip(y.astype(jnp.int32), 0, K - 1)
        ll = jnp.take_along_axis(jnp.log(pk), yi[:, None], axis=1)[:, 0]
        return -jnp.sum(w * ll)

    val, g = jax.value_and_grad(nll)(jnp.concatenate([beta, raw_cuts]))
    return val, g


@partial(jax.jit, static_argnames=("K", "maxiter"))
def _ordinal_fused_fit(X, y, w, x0, K, maxiter):
    """Whole-program ordinal fit (ISSUE 15): the SAME proportional-odds NLL
    as :func:`_ordinal_nll_grad`, minimized entirely on device by
    ``jax.scipy.optimize.minimize(method='BFGS')`` — one dispatch instead
    of one per scipy line-search evaluation. The objective is convex in
    this parameterization, so BFGS and the host L-BFGS-B driver converge to
    the same optimum (pinned within the f32 envelope); a non-finite or
    unconverged result routes the caller back to the scipy path. Returns
    ``(x, nll, ok)``."""
    P = X.shape[1]

    def nll(params):
        b = params[:P]
        raw = params[P:]
        theta = jnp.cumsum(jnp.concatenate([raw[:1], jnp.exp(raw[1:])]))
        eta = jnp.einsum("np,p->n", X, b, precision=_HI)
        cum = jax.nn.sigmoid(theta[None, :] - eta[:, None])
        lo = jnp.concatenate([jnp.zeros((X.shape[0], 1)), cum], axis=1)
        hi = jnp.concatenate([cum, jnp.ones((X.shape[0], 1))], axis=1)
        pk = jnp.clip(hi - lo, 1e-12, 1.0)
        yi = jnp.clip(y.astype(jnp.int32), 0, K - 1)
        ll = jnp.take_along_axis(jnp.log(pk), yi[:, None], axis=1)[:, 0]
        return -jnp.sum(w * ll)

    import jax.scipy.optimize as _jsp_opt  # lazy submodule: import explicitly

    res = _jsp_opt.minimize(
        nll, x0, method="BFGS",
        options={"maxiter": maxiter, "gtol": 1e-6},
    )
    ok = jnp.all(jnp.isfinite(res.x)) & jnp.isfinite(res.fun)
    return res.x, res.fun, ok


# ---------------------------------------------------------------------------


def _lambda_sequence(p: "GLMParams", lambda_max: float, nobs: float, P: int):
    """The lambda schedule shared by every solver: explicit values, the
    lambda_search geometric path, or the light-shrinkage default — one
    definition so switching solver cannot silently change regularization."""
    if p.lambda_ is not None:
        return np.atleast_1d(np.asarray(p.lambda_, np.float64))
    if p.lambda_search:
        nl = p.nlambdas if p.nlambdas > 0 else 100
        ratio = p.lambda_min_ratio if p.lambda_min_ratio > 0 else (
            1e-4 if nobs > P else 1e-2
        )
        return np.geomspace(lambda_max, lambda_max * ratio, nl)
    return np.array([lambda_max / 1e3])


class GLMModel(Model):
    algo = "glm"

    def _predict_raw(self, frame: Frame) -> np.ndarray:
        if not self.output.get("ordinal"):
            return np.asarray(self._predict_raw_dev(frame))
        di: DataInfo = self.output["datainfo"]
        X, _ = di.transform(frame)
        beta = np.asarray(self.output["beta_std"], np.float64)
        theta = np.asarray(self.output["theta"], np.float64)
        eta = np.asarray(X, np.float64)[: frame.nrow] @ beta
        cum = 1.0 / (1.0 + np.exp(-(theta[None, :] - eta[:, None])))
        lo = np.concatenate([np.zeros((len(eta), 1)), cum], axis=1)
        hi = np.concatenate([cum, np.ones((len(eta), 1))], axis=1)
        return np.clip(hi - lo, 1e-12, 1.0)

    def _device_predictor(self):
        # ordinal scoring is float64 numpy code: it stays on the host path
        return None if self.output.get("ordinal") else self._predict_raw_dev

    def _predict_raw_dev(self, frame: Frame):
        """``_predict_raw`` of every family but ordinal, with no pull to the
        host: (n, 2) for binomial, ``mu`` for the regression families,
        (n, K) for multinomial."""
        di: DataInfo = self.output["datainfo"]
        X, _ = _glm_design(di, frame, _factored(
            self.output["family"], self.params, di))
        raw = self._raw_from_design(
            X, _offset_col(self.params, frame), frame.nrow)
        if self.is_classifier and raw.ndim == 1:
            return jnp.stack([1 - raw, raw], axis=1)
        return raw

    def _raw_from_design(self, X, offset, nrow: int):
        """The raw predictions from a design matrix of this model's
        ``DataInfo`` (the builder hands over the one it fitted on, so the
        training metrics transform nothing): (n, K) probabilities for
        multinomial, else ``mu`` — for binomial the second class's
        probability, which ``_make_metrics`` takes as it is."""
        if self.output.get("multinomial"):
            Beta = jnp.asarray(self.output["beta_multinomial_std"], jnp.float32)
            return _softmax_probs(X, Beta)[:nrow]
        beta = jnp.asarray(self.output["beta_std"], jnp.float32)
        return _linear_mu(
            X, beta, offset, self.output["family"], _fam_args(self.params)
        )[:nrow]

    @property
    def coef(self) -> dict:
        return dict(zip(self.output["coef_names"], self.output["beta_orig"]))

    def coef_norm(self) -> dict:
        return dict(zip(self.output["coef_names"], self.output["beta_std_report"]))

    def _distribution_for_metrics(self) -> str:
        fam = self.output["family"]
        return {"poisson": "poisson", "gamma": "gamma"}.get(fam, "gaussian")


@jax.jit
@jax.named_scope("ph_std")
def _response_lanes(ydata, valid, weights, offset):
    """The resident fit's row lanes from the frame's device columns: the
    response with 0 where it is missing (a categorical code < 0, a NaN),
    the weight ``valid * weights * (1 - missing response)``, the offset,
    and ``nobs`` — what the streamed path builds in numpy
    (``GLM._plan_streamed``), with nothing pulled or uploaded."""
    if jnp.issubdtype(ydata.dtype, jnp.floating):
        yna = jnp.isnan(ydata)
        y = jnp.nan_to_num(ydata.astype(jnp.float32), nan=0.0)
    else:
        yna = ydata < 0
        y = jnp.where(yna, 0, ydata).astype(jnp.float32)
    w = valid
    if weights is not None:
        w = w * jnp.nan_to_num(weights)
    w = w * (1.0 - yna.astype(jnp.float32))  # NA-response rows get weight 0
    offset = jnp.zeros_like(y) if offset is None else jnp.nan_to_num(offset)
    return y, w, offset, w.sum()


def _offset_col(params, frame: Frame):
    if params.offset_column:
        off = frame.vec(params.offset_column).data
        return jnp.nan_to_num(off)
    return jnp.zeros(frame.npad, jnp.float32)


class GLM(ModelBuilder):
    """``h2o.glm`` builder."""

    algo = "glm"
    PARAMS_CLS = GLMParams
    # upstream's REST/R param is "lambda" (a Python keyword, hence the
    # dataclass field lambda_); accept both over REST and the estimators
    PARAM_ALIASES = {"lambda": "lambda_"}

    def _build(self, job: Job, train: Frame, valid: Frame | None) -> Model:
        p: GLMParams = self.params
        yv = train.vec(p.response_column)

        family = p.family.lower()
        if family == "auto":
            if yv.is_categorical():
                family = "binomial" if yv.cardinality <= 2 else "multinomial"
            else:
                family = "gaussian"
        classification = (
            family in ("binomial", "multinomial", "ordinal")
            and yv.is_categorical()
        )

        pairs: list[tuple[str, str]] = []
        if p.interactions:
            import itertools as _it

            pairs += list(_it.combinations([str(c) for c in p.interactions], 2))
        if p.interaction_pairs:
            pairs += [(str(a), str(b)) for a, b in p.interaction_pairs]
        # DataInfo.fit, the design matrix, the response and weight lanes;
        # ends in the `nobs` pull, which waits for the transform
        with _mx.span("glm.datainfo"):
            di = DataInfo.fit(
                train,
                self._x,
                standardize=p.standardize,
                use_all_factor_levels=False,
                missing_handling=p.missing_values_handling,
                # ordinal: the K-1 ordered cuts ARE the intercepts
                add_intercept=p.intercept and family != "ordinal",
                interaction_pairs=pairs or None,
                hash_buckets=int(p.hash_buckets) if p.hash_buckets else None,
            )

            # out-of-core streaming (ISSUE 11, frame/chunkstore.py): a design
            # matrix past the HBM window streams as row-block chunks through
            # the per-iteration Gram accumulation (the IRLS Gram is a sum over
            # row blocks). Fallback matrix (docs/MIGRATION.md): multinomial /
            # ordinal / L-BFGS / compute_p_values stay resident.
            stream = None
            factored = _factored(family, p, di)
            if _single_irls(family, p):
                stream = self._plan_streamed(train, di, p, yv, factored)
            if stream is not None:
                X = stream
                w = stream.lane("w")
                y = stream.lane("y")
                offset = stream.lane("offset")
                nobs_dev = w.sum()
            else:
                # resident: nothing n-row-sized is made on the host
                X, valid_mask = _glm_design(di, train, factored)
                y, w, offset, nobs_dev = _response_lanes(
                    yv.data, valid_mask,
                    train.vec(p.weights_column).data
                    if p.weights_column else None,
                    train.vec(p.offset_column).data
                    if p.offset_column else None,
                )

            nobs = float(np.asarray(nobs_dev))
        job.update(0.05)

        from h2o3_tpu.models.model_base import (
            check_checkpoint_compat,
            resolve_checkpoint,
        )

        prior = resolve_checkpoint(p.checkpoint)
        response_domain = tuple(yv.domain) if classification else None
        if prior is not None:
            if family == "ordinal" or p.solver.upper().replace(
                "-", "_"
            ) in ("L_BFGS", "LBFGS"):
                raise ValueError(
                    "GLM checkpoint resume supports the IRLSM paths only"
                )
            check_checkpoint_compat(
                prior, self,
                ("family", "link", "solver", "alpha", "lambda_",
                 "lambda_search", "nlambdas", "lambda_min_ratio",
                 "standardize", "intercept", "missing_values_handling",
                 "max_iterations", "beta_epsilon", "objective_epsilon"),
            )
            st = prior.output.get("irls_state")
            if st is None:
                raise ValueError(
                    "GLM checkpoint resume needs an in-training snapshot "
                    "(a COMPLETED GLM fit has converged; there is nothing to "
                    "continue)"
                )
            if family == "multinomial":
                if not st.get("multinomial"):
                    raise ValueError(
                        "checkpoint is not a multinomial irls_state snapshot"
                    )
                if np.asarray(st["Beta"]).shape[0] != di.ncols_expanded:
                    raise ValueError("checkpoint design-matrix width differs")
            elif len(st["beta"]) != di.ncols_expanded:
                raise ValueError("checkpoint design-matrix width differs")

        # the solver: its device programs are the `dispatch:irls_chunk`
        # children, each ending in the pull of its iteration count
        with _mx.span("glm.fit", family=family):
            if family == "multinomial":
                out = self._fit_multinomial(job, X, y, w, di, yv, p, nobs,
                                            prior=prior)
            elif family == "ordinal":
                out = self._fit_ordinal(job, X, y, w, di, yv, p)
            elif p.solver.upper().replace("-", "_") in ("L_BFGS", "LBFGS"):
                out = self._fit_lbfgs(job, X, y, w, offset, di, p, family, nobs)
            else:
                out = self._fit_irls(job, X, y, w, offset, di, p, family, nobs,
                                     prior=prior,
                                     response_domain=response_domain)

        out["datainfo"] = di
        out["response_domain"] = tuple(yv.domain) if classification else None
        out["names"] = list(self._x)
        model = GLMModel(DKV.make_key("glm"), p, out)
        if stream is not None:
            # streamed scoring: never re-materialize the resident design
            model.training_metrics = self._streamed_metrics(model, stream, train)
            stream.close()
        elif family == "ordinal":  # float64 host scoring, from the frame
            model.training_metrics = model._score_metrics(train)
        else:
            # from the design matrix the fit ran on: `train` is not
            # transformed a second time. X lives no longer than this call
            model.training_metrics = model._score_metrics(
                train,
                raw=lambda: model._raw_from_design(X, offset, train.nrow),
            )
        if valid is not None:
            model.validation_metrics = model._score_metrics(valid)
        return model

    def _plan_streamed(self, train: Frame, di, p: GLMParams, yv,
                       factored: bool = False):
        """ChunkStore with the block-transformed design lanes, or None for
        the resident path. Whether the fit streams is decided by the bytes
        a row of the design the resident fit would hold (``factored``: 4 a
        categorical column and 4 a dense one; else 4 a design column); a
        store that is planned streams dense design blocks, so its geometry
        is sized by the dense bytes a row. The block transform reuses
        ``di.transform`` on host-block sub-frames — elementwise per row, so
        each lane equals the resident design matrix row-for-row — and the
        source feature columns then drop to compressed/host residency. The
        response lanes of a streamed fit are host lanes of the store, built
        here in numpy; the resident path makes its own on the device."""
        from h2o3_tpu.frame import chunkstore as cs

        P = di.ncols_expanded
        if factored:
            cats = [c.width for c in di.columns if c.kind == "cat"]
            width = P - sum(cats) + len(cats)
            if cs.ChunkStore.plan(train.npad, (width + 3) * 4) is None:
                return None
        store = cs.ChunkStore.plan(train.npad, (P + 3) * 4)
        if store is None:
            return None
        npad = train.npad
        y_np = yv.to_numpy()
        if yv.is_categorical():
            y_np = y_np.astype(np.float32)
            y_np[y_np < 0] = np.nan
        ybuf = np.zeros(npad, np.float32)
        ybuf[: train.nrow] = np.nan_to_num(y_np, nan=0.0)
        yna = np.zeros(npad, np.float32)
        yna[: train.nrow] = np.isnan(y_np)
        Log.info(
            f"GLM out-of-core streaming: {store.n_blocks} blocks x "
            f"{store.block_rows} rows, design width {P}"
        )
        Xlane = store.add_empty("X", (npad, P), np.float32)
        vmask = np.zeros(npad, np.float32)
        need: list[str] = []
        for c in di.columns:
            for nm in (c.pair if c.pair is not None else (c.name,)):
                if nm not in need:
                    need.append(nm)
        for bi in range(store.n_blocks):
            lo, hi = store.span(bi)
            bf = cs.host_block_frame(train, need, lo, hi)
            Xb, vb = di.transform(bf)
            Xlane[lo:hi] = np.asarray(jax.device_get(Xb))
            vmask[lo:hi] = np.asarray(jax.device_get(vb))
        cs.release_frame_features(train, need)
        w_np = vmask
        if p.weights_column:
            w_np = w_np * np.nan_to_num(
                train.vec(p.weights_column).host_values().astype(np.float32))
        w_np = (w_np * (1.0 - yna)).astype(np.float32)
        store.add("w", w_np)
        store.add("y", np.asarray(ybuf, np.float32))
        off = np.zeros(npad, np.float32)
        if p.offset_column:
            off = np.nan_to_num(
                train.vec(p.offset_column).host_values().astype(np.float32))
        store.add("offset", off)
        return store

    def _streamed_metrics(self, model: "GLMModel", store, frame: Frame):
        """Training metrics without re-materializing the resident design:
        per-block linear predictor + link inverse over the store's lanes,
        then the standard metric builder on the host-assembled raw."""
        from h2o3_tpu.models.model_base import _make_metrics

        with _mx.span("model.score_metrics", algo=self.algo):
            beta = jnp.asarray(model.output["beta_std"], jnp.float32)
            parts = []
            with _mx.span("model.predict_raw"):  # each block ends in a pull
                for bi, blk in store.stream(("X", "offset")):
                    parts.append(np.asarray(_linear_mu(
                        blk["X"], beta, blk["offset"],
                        model.output["family"], _fam_args(model.params))))
            mu = np.concatenate(parts)[: frame.nrow]
            raw = np.stack([1 - mu, mu], axis=1) if model.is_classifier else mu
            yh, wh = model._response_and_weights(frame)
            return _make_metrics(model, raw, yh, wh)

    # -- single-vector families ---------------------------------------------
    def _irls_snapshot(self, key, p: GLMParams, di, beta, family, fam,
                       response_domain, state: dict) -> GLMModel:
        """Interval-snapshot factory: a scoreable partial GLM carrying the
        exact IRLS loop position (``irls_state``) so ``checkpoint=`` resume
        re-enters the solver at the next iteration and reproduces the
        uninterrupted trajectory bit-for-bit."""
        out = self._coef_output(np.asarray(beta, np.float64), di, p)
        out.update(
            family=family,
            family_obj=fam,
            multinomial=False,
            datainfo=di,
            names=list(self._x),
            response_domain=response_domain,
            null_deviance=state["null_dev"],
            residual_deviance=(state["best"]["deviance"]
                               if state.get("best") else float("nan")),
            irls_state=state,
        )
        return GLMModel(key, p, out)

    def _fit_irls(self, job, X, y, w, offset, di, p: GLMParams, family, nobs,
                  prior=None, response_domain=None):
        fam_args = _fam_args(p)
        fam = get_family(family, *fam_args)
        P = di.ncols_expanded
        icpt = P - 1 if p.intercept else None
        alpha = 0.5 if p.alpha is None else float(p.alpha)
        max_iter = p.max_iterations if p.max_iterations > 0 else 50

        # out-of-core lane: X is a ChunkStore of row-block design lanes;
        # every full-batch pass becomes a block-accumulate loop around the
        # SAME _irls_pass program (the Gram is a sum over row blocks) and
        # the solve stays on the host float64 path (fallback matrix: the
        # fused while_loop needs the whole design resident per dispatch)
        from h2o3_tpu.frame.chunkstore import ChunkStore

        streaming = isinstance(X, ChunkStore)

        # fused whole-program lane (H2O3_TPU_GLM_FUSE): pad the design to
        # the shape-bucket/mesh width up front — padded columns are
        # all-zero, contribute exactly zero to every Gram/gradient below,
        # and every host-side vector stays REAL length (padding happens at
        # the dispatch boundary only)
        if streaming:
            from h2o3_tpu import config as _cfg

            if _cfg.get("H2O3_TPU_GLM_FUSE").strip().lower() != "0":
                _GLM_FALLBACKS.inc(reason="streamed")
            fuse_k = 0
        else:
            fuse_k = _glm_fuse_chunk(p)
        p_pad = _glm_pad_cols(P) if fuse_k else P
        factored = isinstance(X, FactoredDesign)
        if p_pad > P:
            X = (X.widened(p_pad) if factored
                 else jnp.pad(X, ((0, 0), (0, p_pad - P))))

        beta = np.zeros(P, np.float64)
        if p.intercept:
            mu0 = float(np.asarray(jnp.sum(w * y) / jnp.maximum(jnp.sum(w), 1e-10)))
            if family in ("binomial", "quasibinomial", "fractionalbinomial"):
                mu0 = min(max(mu0, 1e-4), 1 - 1e-4)
            beta[icpt] = float(np.asarray(fam.link.fwd(jnp.asarray(mu0))))

        def pad_beta(b64):
            return np.concatenate([b64, np.zeros(p_pad - P)]) if p_pad > P else b64

        def gram_pass(b64):
            """One GLMIterationTask over ALL rows for host-f64 consumers:
            resident = one _irls_pass dispatch; streamed = the same program
            per row block with the Gram/XtWz/deviance partials accumulated
            in float64 on host (the reduce the MRTask log-tree did).
            Returns (G (P,P) f64, b (P,) f64, dev float)."""
            b32 = jnp.asarray(pad_beta(b64), jnp.float32)
            if not streaming:
                if factored:
                    _CAT_GRAM_CALLS.inc()
                G, b, dev = _irls_pass(X, y, w, offset, b32, family, fam_args)
                return (np.asarray(G, np.float64)[:P, :P],
                        np.asarray(b, np.float64)[:P], float(dev))
            G = np.zeros((P, P), np.float64)
            bb = np.zeros(P, np.float64)
            dev = 0.0
            for _bi, blk in X.stream(("X", "y", "w", "offset")):
                _GLM_DISPATCHES.inc()
                Gb, bbb, db = _irls_pass(
                    blk["X"], blk["y"], blk["w"], blk["offset"], b32,
                    family, fam_args,
                )
                G += np.asarray(Gb, np.float64)
                bb += np.asarray(bbb, np.float64)
                dev += float(db)
            return G, bb, dev

        def dev_pass(b64):
            b32 = jnp.asarray(pad_beta(b64), jnp.float32)
            if not streaming:
                return float(
                    _deviance_pass(X, y, w, offset, b32, family, fam_args))
            return sum(
                float(_deviance_pass(
                    blk["X"], blk["y"], blk["w"], blk["offset"], b32,
                    family, fam_args))
                for _bi, blk in X.stream(("X", "y", "w", "offset"))
            )

        # lambda path
        G0, b0, dev0 = gram_pass(beta)
        g0 = b0 - G0 @ beta
        if icpt is not None:
            g0_pen = np.delete(g0, icpt)
        else:
            g0_pen = g0
        lambda_max = float(np.max(np.abs(g0_pen)) / max(alpha, 1e-3) / max(nobs, 1.0))

        lambdas = _lambda_sequence(p, lambda_max, nobs, P)

        best = None
        null_dev = float(dev0)
        path = []
        # checkpoint resume: the prologue above (beta init, lambda_max,
        # lambdas, null_dev) is a pure function of the data and params —
        # recomputed identically — so only the LOOP POSITION is restored
        li0, it0, iters0, dev_prev0 = 0, 0, 0, np.inf
        if prior is not None:
            st = prior.output["irls_state"]
            li0, it0 = int(st["li"]), int(st["it"])
            iters0 = int(st.get("iters", it0))
            dev_prev0 = float(st["dev_prev"])
            beta = np.asarray(st["beta"], np.float64).copy()
            best = ({k: (np.asarray(v).copy() if k == "beta" else v)
                     for k, v in st["best"].items()} if st.get("best") else None)
            path = [dict(e) for e in st.get("path", ())]
        tot_iters = 0  # this run's executed iterations (chaos abort site)
        fam_obj = fam

        def snapshot(li, it_pos, iters_done, dev_prev, beta):
            self._export_interval_checkpoint(
                job,
                lambda key: self._irls_snapshot(
                    key, p, di, beta, family, fam_obj, response_domain,
                    {"li": li, "it": it_pos, "iters": iters_done,
                     "dev_prev": dev_prev, "beta": beta.copy(),
                     "best": best, "path": [dict(e) for e in path],
                     "null_dev": null_dev},
                ),
            )

        def host_iteration(beta, l1, l2):
            """One per-iteration host-solve IRLS step (the pre-fused path,
            the fused lane's singular-tail fallback, and the out-of-core
            streamed lane): Gram on device — full batch or block-
            accumulated — float64 Cholesky/ADMM on host. Returns
            (beta, dev_now, delta)."""
            if not streaming:
                _GLM_DISPATCHES.inc()
            G, b, dev = gram_pass(beta)
            _solve_t0 = time.perf_counter()
            if l1 > 0:
                beta_new = admm_elastic_net(
                    G, b, l1, l2, icpt, non_negative=p.non_negative
                )
            else:
                Gp = G + l2 * np.eye(P)
                if icpt is not None:
                    Gp[icpt, icpt] -= l2
                beta_new = solve_cholesky(Gp, b)
                if p.non_negative:
                    mask = np.arange(P) != (icpt if icpt is not None else -1)
                    beta_new = np.where(mask & (beta_new < 0), 0.0, beta_new)
            _IRLS_SOLVE_SECONDS.observe(time.perf_counter() - _solve_t0)
            delta = np.max(np.abs(beta_new - beta))
            return beta_new, float(dev), delta

        coll_model = gram_collective_bytes(
            p_pad, _mesh_shards()) if fuse_k else None
        for li, lam in enumerate(lambdas):
            if li < li0:
                continue
            l1 = lam * alpha * nobs
            l2 = lam * (1 - alpha) * nobs
            dev_prev = dev_prev0 if li == li0 else np.inf
            # it_pos is the resume marker (max_iter once this lambda's
            # iterations finished); iters_done is the TRUE iteration count
            # reported in the regularization path
            it_pos = it0 if li == li0 else 0
            iters_done = iters0 if li == li0 else 0
            fused_ok = bool(fuse_k)  # a bad (singular-in-f32) chunk drops
            #                          this lambda to the host-f64 tail
            while it_pos < max_iter:
                if fused_ok:
                    prog = _fused_chunk_program(
                        X.shape[0], p_pad, family, fam_args, l1 > 0,
                        p.non_negative,
                        layout=X.dense_pos if factored else None,
                    )
                    kmax = min(fuse_k, max_iter - iters_done)
                    _it_t0 = time.perf_counter()
                    _GLM_DISPATCHES.inc()
                    from h2o3_tpu.utils import flightrec as _fr

                    with _fr.dispatch("irls_chunk", rows=int(X.shape[0]),
                                      cols=int(p_pad), k=int(kmax)):
                        beta_j, devp_j, ndone_j, stop_j, bad_j = prog(
                            jnp.asarray(pad_beta(beta), jnp.float32),
                            jnp.float32(dev_prev), X, y, w, offset,
                            jnp.int32(kmax), jnp.float32(l1), jnp.float32(l2),
                            jnp.float32(p.beta_epsilon),
                            jnp.float32(p.objective_epsilon),
                            jnp.int32(icpt if icpt is not None else -1),
                            jnp.asarray(
                                (np.arange(p_pad) >= P).astype(np.float32)),
                            jnp.float32(P),
                        )
                        n_done = int(ndone_j)
                    stop, bad = bool(stop_j), bool(bad_j)
                    _dt = time.perf_counter() - _it_t0
                    if n_done:
                        beta = np.asarray(beta_j, np.float64)[:P]
                        dev_prev = float(devp_j)
                        _IRLS_ITERS.inc(n_done)
                        if factored:
                            _CAT_GRAM_CALLS.inc(n_done)
                        for _ in range(n_done):
                            _IRLS_SECONDS.observe(_dt / n_done)
                        for ph, lanes in coll_model.items():
                            for lane, nb in lanes.items():
                                if nb:
                                    _COLL_BYTES.inc(nb * n_done, phase=ph)
                                    _COLL_BYTES.inc(
                                        nb * n_done, phase=ph, lane=lane)
                    iters_done += n_done
                    it_pos = max_iter if stop else iters_done
                    snapshot(li, it_pos, iters_done, dev_prev, beta)
                    first = tot_iters + 1
                    tot_iters += n_done
                    faults.die_check("glm")  # chaos: worker death at boundary
                    for i in range(first, tot_iters + 1):
                        faults.abort_check("glm", i)
                    if bad:
                        Log.warn(
                            "GLM fused IRLS chunk hit a non-finite f32 "
                            "solve; falling back to the host float64 lane "
                            f"for lambda index {li}"
                        )
                        _GLM_FALLBACKS.inc(reason="singular")
                        fused_ok = False
                    if stop:
                        break
                    continue
                _it_t0 = time.perf_counter()
                beta_new, dev_now, delta = host_iteration(beta, l1, l2)
                beta = beta_new
                iters_done += 1
                it_pos = iters_done
                tot_iters += 1
                # the np.asarray(G) in host_iteration forced the device
                # sync, so this is the true Gram+solve iteration time
                # (checkpoint IO excluded; persist_write_seconds covers it)
                _IRLS_ITERS.inc()
                _IRLS_SECONDS.observe(time.perf_counter() - _it_t0)
                stop = delta < p.beta_epsilon or abs(dev_prev - dev_now) / max(
                    abs(dev_now), 1e-10
                ) < p.objective_epsilon
                if stop:
                    it_pos = max_iter
                else:
                    dev_prev = dev_now
                # snapshot AFTER the stop decision: the recorded (li, it)
                # is exactly where a resumed run re-enters the loop (it ==
                # max_iter marks "this lambda's iterations are finished")
                snapshot(li, it_pos, iters_done, dev_prev, beta)
                faults.die_check("glm")  # chaos: worker death at boundary
                faults.abort_check("glm", tot_iters)
                if stop:
                    break
            dev_final = dev_pass(beta)
            expl = 1 - dev_final / max(null_dev, 1e-30)
            path.append({"lambda": float(lam), "deviance": dev_final, "dev_ratio": expl, "iters": iters_done})
            if best is None or dev_final <= best["deviance"]:
                best = {"lambda": float(lam), "beta": beta.copy(), "deviance": dev_final}
            job.update(0.05 + 0.8 * (li + 1) / len(lambdas))
            if p.lambda_search and expl > 0.999:
                break

        beta = best["beta"]
        out = self._coef_output(beta, di, p)
        out.update(
            family=family,
            family_obj=fam,
            null_deviance=null_dev,
            residual_deviance=best["deviance"],
            lambda_best=best["lambda"],
            lambda_max=lambda_max,
            alpha=alpha,
            regularization_path=path,
            multinomial=False,
        )
        if p.compute_p_values:
            out.update(self._p_values(X, y, w, offset, beta, family, fam_args, di, p, nobs))
        return out

    def _coef_output(self, beta_std, di: DataInfo, p: GLMParams,
                     has_intercept: bool | None = None) -> dict:
        """Destandardize coefficients back to the original scale.

        ``has_intercept`` overrides ``p.intercept`` for fits whose design has
        no intercept column regardless of the param (ordinal: the cuts are
        the intercepts) — otherwise the shift correction would clobber the
        LAST feature's coefficient. The accumulated shift is returned so
        such fits can fold it into their own intercept-like parameters.
        """
        if has_intercept is None:
            has_intercept = p.intercept
        with _mx.span("glm.coef_output"):
            names = di.coef_names()
            beta_std = np.asarray(beta_std, np.float64)
            beta_orig = beta_std.copy()
            shift = 0.0
            if p.standardize:
                for c in di.columns:
                    if c.kind == "num":
                        beta_orig[c.offset] = beta_std[c.offset] / c.sigma
                        shift += beta_std[c.offset] * c.mean / c.sigma
                if has_intercept:
                    beta_orig[-1] = beta_std[-1] - shift
        return {
            "coef_names": names,
            "beta_std": beta_std,
            "beta_std_report": beta_std,
            "beta_orig": beta_orig,
            "destandardize_shift": shift,
        }

    def _p_values(self, X, y, w, offset, beta, family, fam_args, di, p, nobs) -> dict:
        P = int(np.shape(beta)[0])
        b32 = jnp.asarray(beta, jnp.float32)
        if X.shape[1] > P:
            # fused lane: the design was padded to the shape-bucket width up
            # front; the padded columns are all-zero so slicing the Gram back
            # to the real width reproduces the unpadded pass exactly
            b32 = jnp.pad(b32, (0, X.shape[1] - P))
        G, b, dev = _irls_pass(X, y, w, offset, b32, family, fam_args)
        G = np.asarray(G, np.float64)[:P, :P]
        fam = get_family(family, *fam_args)
        try:
            inv = np.linalg.inv(G)
        except np.linalg.LinAlgError:
            inv = np.linalg.pinv(G)
        dispersion = 1.0
        if not fam.dispersion_fixed:
            dispersion = float(dev) / max(nobs - P, 1.0)
        se = np.sqrt(np.maximum(np.diag(inv) * dispersion, 0.0))
        z = np.asarray(beta, np.float64) / np.maximum(se, 1e-30)
        from scipy import stats as sps

        if fam.dispersion_fixed:
            pv = 2 * sps.norm.sf(np.abs(z))
        else:
            pv = 2 * sps.t.sf(np.abs(z), df=max(nobs - P, 1.0))
        return {"std_errs": se, "z_values": z, "p_values": pv, "dispersion": dispersion}

    # -- ordinal (proportional odds) ----------------------------------------
    def _fit_ordinal(self, job, X, y, w, di, yv, p: GLMParams):
        from scipy import optimize as spo

        if p.offset_column:
            raise ValueError("ordinal does not support offset_column")
        if p.compute_p_values:
            raise ValueError("compute_p_values requires solver=IRLSM")
        if p.lambda_search:
            raise ValueError("lambda_search is not supported for ordinal")
        if p.lambda_ is not None and float(np.atleast_1d(np.asarray(p.lambda_))[0]) > 0:
            Log.warn("ordinal fits unpenalized; lambda_ is ignored")
        K = yv.cardinality
        if K < 2:
            raise ValueError("ordinal needs a categorical response with >=2 levels")
        P = di.ncols_expanded
        # init: zero betas; first cut below zero, the rest unit-spaced
        # (the exp parameterization keeps them ordered during optimization)
        raw0 = np.zeros(K - 1)
        raw0[0] = -1.0
        x0 = np.concatenate([np.zeros(P), raw0])
        maxiter = p.max_iterations if p.max_iterations > 0 else 200

        # fused lane (ISSUE 15): the whole BFGS optimization of the SAME
        # convex proportional-odds NLL runs as one device program — one
        # dispatch instead of one per scipy line-search evaluation; a
        # non-finite result falls back to the host scipy driver below
        x_fit = None
        fun_val = None
        if _glm_fuse_chunk(p):
            _GLM_DISPATCHES.inc()
            x_j, f_j, ok_j = _ordinal_fused_fit(
                X, y, w, jnp.asarray(x0, jnp.float32), K, maxiter
            )
            if bool(ok_j):
                x_fit = np.asarray(x_j, np.float64)
                fun_val = float(f_j)
            else:
                Log.warn(
                    "GLM fused ordinal BFGS returned a non-finite optimum; "
                    "falling back to the host L-BFGS-B driver"
                )
                _GLM_FALLBACKS.inc(reason="ordinal_opt")

        if x_fit is None:
            def fun(params):
                val, g = _ordinal_nll_grad(
                    X, y, w, jnp.asarray(params[:P], jnp.float32),
                    jnp.asarray(params[P:], jnp.float32), K,
                )
                return float(val), np.asarray(g, np.float64)

            res = spo.minimize(
                fun, x0, jac=True, method="L-BFGS-B",
                options={"maxiter": maxiter},
            )
            x_fit = res.x
            fun_val = float(res.fun)
        beta = x_fit[:P]
        raw = x_fit[P:]
        theta = np.cumsum(np.concatenate([raw[:1], np.exp(raw[1:])]))
        out = self._coef_output(beta, di, p, has_intercept=False)
        out.update(
            family="ordinal",
            family_obj=get_family("binomial"),
            ordinal=True,
            theta=theta,  # standardized scale — what _predict_raw consumes
            # original-scale cuts: eta_std = eta_orig_lin - shift, so the
            # same cumulative probabilities come from theta + shift
            theta_orig=theta + out["destandardize_shift"],
            residual_deviance=2.0 * fun_val,
            null_deviance=float("nan"),
            multinomial=False,
        )
        job.update(0.9)
        return out

    # -- L-BFGS solver (hex/optimization/L_BFGS successor): the device
    # computes the full-batch objective+gradient in one fused pass; the
    # low-memory quasi-Newton direction update runs host-side in scipy.
    def _fit_lbfgs(self, job, X, y, w, offset, di, p: GLMParams, family, nobs):
        from scipy import optimize as spo

        fam_args = _fam_args(p)
        if p.compute_p_values:
            raise ValueError("compute_p_values requires solver=IRLSM")
        fam = get_family(family, *fam_args)
        P = di.ncols_expanded
        icpt = P - 1 if p.intercept else None
        alpha = 0.5 if p.alpha is None else float(p.alpha)

        # null model: intercept (or zero) coefficients; its deviance INCLUDES
        # the offset (IRLSM uses dev0 from the same pass — a constant-mu null
        # would inflate dev_ratio and fire the path early-stop at lambda_max
        # whenever an offset explains most of the response)
        beta0 = np.zeros(P, np.float64)
        if p.intercept:
            mu0 = float(np.asarray(jnp.sum(w * y) / jnp.maximum(jnp.sum(w), 1e-10)))
            if family in ("binomial", "quasibinomial", "fractionalbinomial"):
                mu0 = min(max(mu0, 1e-4), 1 - 1e-4)
            beta0[icpt] = float(np.asarray(fam.link.fwd(jnp.asarray(mu0))))
        nd_v, g_v = _glm_dev_grad(
            X, y, w, offset, jnp.asarray(beta0, jnp.float32), family, fam_args
        )
        null_dev = float(nd_v)
        g_dev0 = np.asarray(g_v, np.float64)
        # lambda_max from the null gradient on the HALF-deviance scale
        # (the IRLSM derivation, without paying its O(N P^2) Gram pass)
        g_half = g_dev0 / 2.0
        g_pen = np.delete(g_half, icpt) if icpt is not None else g_half
        lambda_max = float(np.max(np.abs(g_pen)) / max(alpha, 1e-3) / max(nobs, 1.0))
        lambdas = _lambda_sequence(p, lambda_max, nobs, P)

        maxiter = p.max_iterations if p.max_iterations > 0 else 200
        l1_mask = np.ones(P)
        if icpt is not None:
            l1_mask[icpt] = 0.0

        def smooth(b, l2):
            """Deviance + L2 part (value, gradient) — device pass."""
            val, g = _glm_dev_grad(
                X, y, w, offset, jnp.asarray(b, jnp.float32), family, fam_args
            )
            b64 = np.asarray(b, np.float64)
            g64 = np.asarray(g, np.float64)
            pen = b64 * l1_mask
            return float(val) + l2 * float(pen @ pen), g64 + 2.0 * l2 * pen

        def solve_one(lam, beta_init):
            """One elastic-net L-BFGS solve, warm-started at beta_init.

            Objective scale: h2o minimizes (1/N)(deviance/2) + lam*P_alpha
            with P_alpha = alpha*||b||_1 + (1-alpha)/2*||b||^2. On the
            DEVIANCE scale (x 2N): l2 = lam*(1-alpha)*N on ||b||^2 and
            l1 = 2*lam*alpha*N on ||b||_1 — the factor 2 mirrors ADMM's
            penalties living on the half-deviance (Gram) scale.
            """
            l2 = lam * (1 - alpha) * nobs
            l1 = 2.0 * lam * alpha * nobs
            if l1 > 0:
                # exact L1 via the bound-constrained split beta = b+ - b-,
                # b± >= 0 with penalty l1*Σ(b+ + b-): a smooth box problem
                # L-BFGS-B solves natively (the OWL-QN alternative without
                # a custom solver)
                l1_vec = l1 * l1_mask

                def fun2(z):
                    bp, bn = z[:P], z[P:]
                    val, g = smooth(bp - bn, l2)
                    val += float(l1_vec @ (bp + bn))
                    return val, np.concatenate([g + l1_vec, -g + l1_vec])

                z0 = np.concatenate([np.maximum(beta_init, 0.0),
                                     np.maximum(-beta_init, 0.0)])
                res = spo.minimize(
                    fun2, z0, jac=True, method="L-BFGS-B",
                    bounds=[(0.0, None)] * (2 * P),
                    options={"maxiter": maxiter},
                )
                b = res.x[:P] - res.x[P:]
                # the split leaves tiny +/- residue where the true coef is 0
                b[np.abs(b) < 1e-10] = 0.0
                return b
            res = spo.minimize(
                lambda bb: smooth(bb, l2), beta_init, jac=True,
                method="L-BFGS-B", options={"maxiter": maxiter},
            )
            return res.x

        best = None
        path = []
        beta = beta0.copy()
        for li, lam_i in enumerate(lambdas):
            beta = solve_one(float(lam_i), beta)  # warm start down the path
            dev_i = float(
                _deviance_pass(
                    X, y, w, offset, jnp.asarray(beta, jnp.float32), family,
                    fam_args,
                )
            )
            expl = 1 - dev_i / max(null_dev, 1e-30)
            path.append({"lambda": float(lam_i), "deviance": dev_i,
                         "dev_ratio": expl})
            if best is None or dev_i <= best["deviance"]:
                best = {"lambda": float(lam_i), "beta": beta.copy(),
                        "deviance": dev_i}
            job.update(0.05 + 0.8 * (li + 1) / len(lambdas))
            if p.lambda_search and expl > 0.999:
                break

        beta = best["beta"]
        out = self._coef_output(beta, di, p)
        out.update(
            family=family, family_obj=fam,
            null_deviance=null_dev, residual_deviance=best["deviance"],
            lambda_best=best["lambda"], lambda_max=lambda_max, alpha=alpha,
            regularization_path=path, multinomial=False, solver="L_BFGS",
        )
        job.update(0.9)
        return out

    # -- multinomial ---------------------------------------------------------
    def _multinomial_output(self, di, Beta) -> dict:
        names = di.coef_names()
        return {
            "coef_names": names,
            "beta_multinomial_std": Beta,
            "beta_std": Beta[:, -1],
            "beta_orig": Beta[:, -1],
            "beta_std_report": Beta[:, -1],
            "family": "multinomial",
            "family_obj": get_family("binomial"),
            "multinomial": True,
        }

    def _multinomial_snapshot(self, key, p: GLMParams, di, Beta,
                              response_domain, state: dict) -> GLMModel:
        """Interval-snapshot factory for the cycling IRLS: a scoreable
        partial multinomial GLM carrying the outer-iteration position
        (``irls_state``: it / ll_prev / Beta) so ``checkpoint=`` resume
        re-enters the cycle at the next iteration and reproduces the
        uninterrupted trajectory bit-for-bit (the fused lane clamps its
        chunk to one iteration whenever export_checkpoints_dir is set)."""
        out = self._multinomial_output(di, np.asarray(Beta, np.float64))
        out.update(
            datainfo=di,
            names=list(self._x),
            response_domain=response_domain,
            residual_deviance=state["ll_prev"],
            irls_state=state,
        )
        return GLMModel(key, p, out)

    def _fit_multinomial(self, job, X, y, w, di, yv, p: GLMParams, nobs,
                         prior=None):
        K = yv.cardinality
        P = di.ncols_expanded
        icpt = P - 1 if p.intercept else None
        alpha = 0.5 if p.alpha is None else float(p.alpha)
        lam = 0.0
        if p.lambda_ is not None:
            lam = float(np.atleast_1d(np.asarray(p.lambda_))[0])
        max_iter = p.max_iterations if p.max_iterations > 0 else 30
        l1 = lam * alpha * nobs
        l2 = lam * (1 - alpha) * nobs
        response_domain = tuple(yv.domain)

        Y1h = (y[:, None] == jnp.arange(K)[None, :]).astype(jnp.float32) * (
            w[:, None] > 0
        )
        # fused whole-program lane (ISSUE 15): the K-class cycling IRLS was
        # per-class-per-iteration host-dispatched — exactly the
        # many-dispatch regime the single-response fusion pays off in. The
        # fused chunk runs up to K_chunk outer iterations as one program
        # (lax.scan over classes inside one while_loop); the host f64
        # cycling tail below stays as the non-finite escape hatch.
        fuse_k = _glm_fuse_chunk(p)
        p_pad = _glm_pad_cols(P) if fuse_k else P
        Xf = jnp.pad(X, ((0, 0), (0, p_pad - P))) if p_pad > P else X

        Beta = np.zeros((P, K), np.float64)
        ll_prev = np.inf
        it = 0
        if prior is not None:
            st = prior.output["irls_state"]
            it = int(st["it"])
            ll_prev = float(st["ll_prev"])
            Beta = np.asarray(st["Beta"], np.float64).copy()

        def snapshot(it_pos, ll_prev_v, Beta_v):
            self._export_interval_checkpoint(
                job,
                lambda key: self._multinomial_snapshot(
                    key, p, di, Beta_v, response_domain,
                    {"multinomial": True, "it": it_pos,
                     "ll_prev": ll_prev_v, "Beta": Beta_v.copy()},
                ),
            )

        def pad_Beta(B64):
            if p_pad > P:
                return np.concatenate(
                    [B64, np.zeros((p_pad - P, K))], axis=0)
            return B64

        fused_ok = bool(fuse_k)
        stop = False
        while it < max_iter and not stop:
            if fused_ok:
                prog = _fused_multinomial_program(
                    Xf.shape[0], p_pad, K, l1 > 0, p.non_negative
                )
                kmax = min(fuse_k, max_iter - it)
                _GLM_DISPATCHES.inc()
                from h2o3_tpu.utils import flightrec as _fr

                with _fr.dispatch("irls_chunk", rows=int(Xf.shape[0]),
                                  cols=int(p_pad), k=int(kmax), classes=K):
                    Beta_j, llp_j, ndone_j, stop_j, bad_j = prog(
                        jnp.asarray(pad_Beta(Beta), jnp.float32),
                        jnp.float32(ll_prev), Xf, Y1h, w,
                        jnp.int32(kmax), jnp.float32(l1), jnp.float32(l2),
                        jnp.float32(p.objective_epsilon),
                        jnp.int32(icpt if icpt is not None else -1),
                        jnp.asarray(
                            (np.arange(p_pad) >= P).astype(np.float32)),
                        jnp.float32(P),
                    )
                    n_done = int(ndone_j)
                stop, bad = bool(stop_j), bool(bad_j)
                if n_done:
                    Beta = np.asarray(Beta_j, np.float64)[:P]
                    ll_prev = float(llp_j)
                first = it + 1
                it += n_done
                snapshot(it, ll_prev, Beta)
                faults.die_check("glm")  # chaos: worker death at boundary
                for i in range(first, it + 1):
                    faults.abort_check("glm", i)
                if bad:
                    Log.warn(
                        "GLM fused multinomial chunk hit a non-finite f32 "
                        "class solve; falling back to the host float64 "
                        "cycling lane"
                    )
                    _GLM_FALLBACKS.inc(reason="singular")
                    fused_ok = False
                job.update(0.05 + 0.8 * min(it + 1, max_iter) / max_iter)
                continue
            # host float64 cycling lane (the pre-fusion path and the
            # singular-tail fallback): one dispatch per (iteration, class)
            for k in range(K):
                _GLM_DISPATCHES.inc()
                G, b, m2ll = _multinomial_pass(
                    X, Y1h, w, jnp.asarray(Beta, jnp.float32), K, k
                )
                G = np.asarray(G, np.float64)
                b = np.asarray(b, np.float64)
                if l1 > 0:
                    Beta[:, k] = admm_elastic_net(G, b, l1, l2, icpt)
                else:
                    Gp = G + l2 * np.eye(P)
                    if icpt is not None:
                        Gp[icpt, icpt] -= l2
                    Beta[:, k] = solve_cholesky(Gp, b)
            ll_now = float(m2ll)
            it += 1
            stop = (
                abs(ll_prev - ll_now) / max(abs(ll_now), 1e-10)
                < p.objective_epsilon
            )
            if not stop:
                ll_prev = ll_now
            snapshot(it, ll_prev, Beta)
            faults.die_check("glm")  # chaos: worker death at boundary
            faults.abort_check("glm", it)
            job.update(0.05 + 0.8 * it / max_iter)

        out = self._multinomial_output(di, Beta)
        out["residual_deviance"] = ll_prev
        return out
