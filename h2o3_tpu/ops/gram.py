"""Weighted Gram accumulation — successor of ``hex.gram.Gram`` [UNVERIFIED
upstream path, SURVEY.md §2.2].

H2O accumulates X'WX with a per-chunk outer-product MRTask and a pairwise
reduce, then Cholesky-solves on one node. Here the accumulation is a single
einsum over the row-sharded design matrix: XLA tiles it onto the MXU and
inserts the cross-chip ``psum`` automatically (the MRTask reduce). float32
with HIGHEST precision keeps the normal equations accurate; the (p,p) solve
happens host-side in float64 — same split as H2O (distributed accumulate,
local solve).

The fused whole-program IRLS lane (H2O3_TPU_GLM_FUSE, models/glm.py) uses
the explicit variants below instead: :func:`weighted_gram_sharded` ends in a
``psum_scatter`` of contiguous G row blocks over the rows mesh axis (each
device keeps p/P rows; the solve gathers them once — the hierarchical-
reduction placement of arXiv:2110.10548 at one mesh level), and
:func:`cho_solve_jitter_device` / :func:`admm_elastic_net_device` move the
per-iteration solve on-device (float32) so a K-iteration chunk runs with
zero host round-trips. The host float64 functions stay as the singular-tail
fallback lane.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import scipy.linalg

_P = jax.lax.Precision.HIGHEST


@jax.jit
def weighted_gram(X, w, z):
    """Return (G, b) = (XᵀWX, XᵀWz) for diagonal W, plus the weight sum."""
    with jax.named_scope("ph_gram"):
        Xw = X * w[:, None]
        G = jnp.einsum("np,nq->pq", Xw, X, precision=_P)
        b = jnp.einsum("np,n->p", Xw, z, precision=_P)
        return G, b, w.sum(dtype=jnp.float32)


def weighted_gram_sharded(X, w, z, mesh=None):
    """:func:`weighted_gram` with the MRTask reduce made explicit: each
    device contracts its local row block, the Gram reduction ends in a
    ``psum_scatter`` of contiguous (p/P, p) row blocks over the rows mesh
    axis, and one ``all_gather`` reassembles G for the (replicated) solve.

    Traceable inside a larger jitted program (the fused IRLS while_loop).
    Requires ``X.shape[1]`` divisible by the shard count (the caller pads —
    models/glm.py pads the design matrix columns to the shape-bucket ladder
    and then to the mesh). Row blocks are contiguous, so device d's slice
    is exactly rows [d·p/P, (d+1)·p/P) of the replicated-einsum G.
    """
    from h2o3_tpu.parallel.mesh import (
        col_axis_name, get_mesh, n_col_shards, row_pspec, shard_map,
    )
    from jax.sharding import PartitionSpec as Spec

    mesh = mesh or get_mesh()
    n_sh = int(mesh.devices.size)
    if n_sh <= 1:
        return weighted_gram(X, w, z)
    n_blk = n_col_shards(mesh)
    cax = col_axis_name(mesh)
    p = X.shape[1]
    assert p % n_blk == 0, f"gram width {p} not divisible by {n_blk} blocks"

    from h2o3_tpu.ops import collectives

    @jax.named_scope("ph_gram")
    def local(Xl, wl, zl):
        Xw = Xl * wl[:, None]
        G_l = jnp.einsum("np,nq->pq", Xw, Xl, precision=_P)
        b_l = jnp.einsum("np,n->p", Xw, zl, precision=_P)
        # contiguous row blocks: col-block d keeps G rows [d*p/B, (d+1)*p/B)
        # (on a 2-D mesh an exact rows-axis psum runs first inside the
        # wrapper and the scatter deals blocks over the cols axis only).
        # The reduce runs through the collective lane (stock psum_scatter
        # when quant is off); passes=2 adds the residual-correction pass —
        # G feeds the solve directly, so it gets ~14 effective mantissa
        # bits instead of bare int8
        G_blk = collectives.psum_scatter(G_l, n_dev=n_sh, passes=2, mesh=mesh)
        # the solve needs the full (p, p) matrix exactly once per iteration
        # — and exactly as reduced: the gather stays f32 (exact lane)
        G = jax.lax.all_gather(G_blk, cax, axis=0, tiled=True)
        b = collectives.exact_psum(b_l, mesh)
        sw = collectives.exact_psum(wl.sum(dtype=jnp.float32), mesh)
        return G, b, sw

    rspec = row_pspec(mesh)
    return shard_map(
        local, mesh,
        in_specs=(row_pspec(mesh, ndim=2), rspec, rspec),
        out_specs=(Spec(), Spec(), Spec()),
        check_vma=False,
    )(X, w, z)


def gram_collective_bytes(p_pad: int, n_shards: int) -> dict:
    """Per-lane replication-volume model (the PR-5 accounting) of ONE
    sharded Gram pass: ``gram_reduce`` = the G psum_scatter (through the
    quantized lane when on — ``lane=quant`` wire bytes, with its
    residual-correction pass) + the exact b/sw (or packed b/deviance)
    psums, ``gram_gather`` = the one exact all_gather that reassembles G
    for the solve. Shape: {phase: {lane: bytes}}; empty lanes on a
    1-device mesh (nothing moves)."""
    from h2o3_tpu.ops.collectives import modeled_reduce_bytes

    if n_shards <= 1:
        return {"gram_reduce": {}, "gram_gather": {}}
    reduce_lanes = dict(modeled_reduce_bytes(
        p_pad * p_pad, n_shards, passes=2))
    reduce_lanes["exact"] = reduce_lanes.get("exact", 0.0) + (p_pad + 1) * 4.0
    return {
        "gram_reduce": reduce_lanes,
        "gram_gather": {"exact": p_pad * p_pad * 4.0},
    }


# jitter ladder mirroring solve_cholesky's host escalation: first try is
# bare, then max(1e-10, 10x) per retry — six attempts before the caller's
# lstsq fallback
_JITTERS = (0.0, 1e-10, 1e-9, 1e-8, 1e-7, 1e-6)


def cho_solve_jitter_device(G, b, extra_diag=None):
    """On-device SPD solve with jitter escalation — the traceable f32
    analog of :func:`solve_cholesky`. ``jax.scipy`` Cholesky reports
    non-SPD as NaNs instead of raising, so every rung of the ladder is
    factored and the first finite solution wins. Returns ``(x, ok)``;
    ``ok=False`` (no rung produced a finite solution) routes the caller to
    the host float64 lstsq fallback lane. ``extra_diag`` is a per-column
    additive diagonal (ridge wiring + the unit diagonal that keeps padded
    bucket columns invertible without touching real coefficients)."""
    p = G.shape[0]
    eye = jnp.eye(p, dtype=G.dtype)
    if extra_diag is not None:
        G = G + jnp.diag(extra_diag)
    x = jnp.zeros_like(b)
    ok = jnp.asarray(False)
    for j in _JITTERS:
        c, low = jax.scipy.linalg.cho_factor(G + j * eye, lower=True)
        xj = jax.scipy.linalg.cho_solve((c, low), b)
        okj = jnp.all(jnp.isfinite(xj))
        take = (~ok) & okj
        x = jnp.where(take, xj, x)
        ok = ok | okj
    return x, ok


@partial(jax.jit, static_argnames=("iters", "non_negative"))
def admm_elastic_net_device(
    G, b, l1, l2, icpt, pad_diag, real_p,
    rho=None, iters=500, tol=1e-6, non_negative=False,
):
    """Traceable f32 ADMM elastic net mirroring :func:`admm_elastic_net`
    op-for-op (same rho heuristic, same soft-threshold loop, same stopping
    rule) with a while_loop early exit. ``icpt`` is a DYNAMIC index (-1 for
    no intercept) so one compiled program serves every design width in a
    shape bucket; ``pad_diag`` adds a unit diagonal on padded bucket columns
    (their b entries are zero, so their coefficients stay exactly zero) and
    ``real_p`` is the true column count for the rho diagonal mean. Returns
    ``(z, ok)`` like the Cholesky lane."""
    p = G.shape[0]
    ar = jnp.arange(p)
    diag = jnp.diagonal(G)
    if rho is None:
        rho = jnp.maximum(
            1e-3, jnp.sum(diag * (1.0 - pad_diag)) / jnp.maximum(real_p, 1.0)
        )
    A = G + jnp.diag(pad_diag) + (l2 + rho) * jnp.eye(p, dtype=G.dtype)
    c, low = jax.scipy.linalg.cho_factor(A, lower=True)
    thr = jnp.where(ar == icpt, 0.0, l1 / rho)
    neg_mask = ar != icpt

    def body(carry):
        x, z, u, z_old, i, done = carry
        x = jax.scipy.linalg.cho_solve((c, low), b + rho * (z - u))
        v = x + u
        z_new = jnp.sign(v) * jnp.maximum(jnp.abs(v) - thr, 0.0)
        if non_negative:
            z_new = jnp.where(neg_mask & (z_new < 0), 0.0, z_new)
        done = (jnp.max(jnp.abs(z_new - z)) < tol) & (
            jnp.max(jnp.abs(x - z_new)) < tol
        )
        return x, z_new, u + x - z_new, z, i + 1, done

    def cond(carry):
        _, _, _, _, i, done = carry
        return (i < iters) & ~done

    z0 = jnp.zeros_like(b)
    x, z, u, _, _, _ = jax.lax.while_loop(
        cond, body, (z0, z0, z0, z0, jnp.int32(0), jnp.asarray(False))
    )
    ok = jnp.all(jnp.isfinite(z)) & jnp.all(jnp.isfinite(c))
    return z, ok


def solve_cholesky(G: np.ndarray, b: np.ndarray, ridge: float = 0.0) -> np.ndarray:
    """Host-side SPD solve with jitter escalation (Gram.Cholesky successor)."""
    G = np.asarray(G, np.float64)
    b = np.asarray(b, np.float64)
    p = G.shape[0]
    jitter = 0.0
    for _ in range(6):
        try:
            c, low = scipy.linalg.cho_factor(
                G + (ridge + jitter) * np.eye(p), lower=True
            )
            return scipy.linalg.cho_solve((c, low), b)
        except np.linalg.LinAlgError:
            jitter = max(1e-10, jitter * 10 or 1e-10)
    return np.linalg.lstsq(G + ridge * np.eye(p), b, rcond=None)[0]


def admm_elastic_net(
    G: np.ndarray,
    b: np.ndarray,
    l1: float,
    l2: float,
    intercept_idx: int | None,
    rho: float | None = None,
    iters: int = 500,
    tol: float = 1e-6,
    non_negative: bool = False,
) -> np.ndarray:
    """ADMM LASSO/elastic-net on the Gram — successor of
    ``hex.optimization.ADMM`` [UNVERIFIED]: minimize ½βᵀGβ − bᵀβ + l2/2‖β‖² +
    l1‖β‖₁ (intercept unpenalized)."""
    G = np.asarray(G, np.float64)
    b = np.asarray(b, np.float64)
    p = G.shape[0]
    if rho is None:
        rho = max(1e-3, np.mean(np.diag(G)))
    A = G + (l2 + rho) * np.eye(p)
    c, low = scipy.linalg.cho_factor(A, lower=True)
    x = np.zeros(p)
    z = np.zeros(p)
    u = np.zeros(p)
    thr = np.full(p, l1 / rho)
    if intercept_idx is not None:
        thr[intercept_idx] = 0.0
    for _ in range(iters):
        x = scipy.linalg.cho_solve((c, low), b + rho * (z - u))
        z_old = z
        v = x + u
        z = np.sign(v) * np.maximum(np.abs(v) - thr, 0.0)
        if non_negative:
            neg = np.arange(p) != (intercept_idx if intercept_idx is not None else -1)
            z = np.where(neg & (z < 0), 0.0, z)
        u = u + x - z
        if np.max(np.abs(z - z_old)) < tol and np.max(np.abs(x - z)) < tol:
            break
    return z
