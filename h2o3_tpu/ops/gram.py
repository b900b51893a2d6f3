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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_P = jax.lax.Precision.HIGHEST

CAT_ROW_TILE = 512  # rows a grid step of the categorical Gram kernel
CAT_VMEM_LIMIT = 64 << 20


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


# ---------------------------------------------------------------------------
# the Gram of a factored design (categoricals as codes)


def _split3(a):
    """``a`` (float32) as three bfloat16 terms whose sum is ``a`` to
    float32's 24 bits: each term holds the next 8 bits of the mantissa."""
    a1 = a.astype(jnp.bfloat16)
    r = a - a1.astype(jnp.float32)
    a2 = r.astype(jnp.bfloat16)
    a3 = (r - a2.astype(jnp.float32)).astype(jnp.bfloat16)
    return a1, a2, a3


def _nt(a, b, **kw):
    """``a @ b.T``, float32 results: both operands carry the rows on their
    lanes, so the contraction runs over the lanes of both."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32, **kw)


def _cat_gram_kernel(codes_ref, dense_ref, w_ref, z_ref,
                     gcc_ref, cross_ref, dd_ref, *comp, k: int, q8: int):
    """One row tile, accumulated into the three resident output blocks.
    ``v`` is ``[x; 1; z; 0...]`` (the q dense columns, a row of ones, the
    working response, zero rows to a multiple of 8), ``w`` the weights:

    - ``gcc`` (K, K): the indicator rows' weighted products, every
      categorical block of G at once (a level's diagonal entry is its weight
      sum; two columns' block is their weighted contingency table);
    - ``cross`` (K, 3·q8): the indicators against ``w·v`` (the
      categorical-by-numeric blocks and ``b``'s categorical entries), as
      three bfloat16 terms stacked on the lanes;
    - ``dd`` (q8, q8): ``v`` against ``w·v`` at float32 (the numeric block,
      ``b``'s numeric entries and the weight sum).

    An indicator entry is 0 or 1, exact in bfloat16, so each MXU product
    is exact when the other operand is split into three bfloat16 terms
    (:func:`_split3`: 24 bits, as ``Precision.HIGHEST``); the MXU
    accumulates a tile in float32. The codes are design column indices, -1
    where a column's indicator row is zero, so the compare needs no offsets.

    The tiles' sums are added to the outputs with Kahan's compensation
    (``comp``, one VMEM block per output, holding what the last addition
    lost): a plain float32 running sum over 100,000 tiles would lose about
    sqrt(tiles) roundings of its own size, and the IRLS solution is only as
    good as ``b - G beta``, a small difference of two such sums."""
    i, last = pl.program_id(0), pl.num_programs(0) - 1
    outs = (gcc_ref, cross_ref, dd_ref)

    @pl.when(i == 0)
    def _():
        for ref in outs + comp:
            ref[...] = jnp.zeros_like(ref)

    codes = codes_ref[...]  # (C, R) int32
    r = codes.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (k, r), 0)
    hit = codes[0:1, :] == col
    for c in range(1, codes.shape[0]):
        hit = hit | (codes[c:c + 1, :] == col)
    w = w_ref[...]  # (1, R)
    d = dense_ref[...]  # (q, R)
    q = d.shape[0]
    v = jnp.concatenate(
        [d, jnp.ones((1, r), jnp.float32), z_ref[...],
         jnp.zeros((q8 - q - 2, r), jnp.float32)], axis=0)  # (q8, R)
    zero = jnp.zeros((k, r), jnp.float32)
    onehot = jnp.where(hit, 1.0, zero).astype(jnp.bfloat16)
    # the three weight terms stacked on the contraction: one MXU pass
    lhs = jnp.concatenate(
        [jnp.where(hit, wi.astype(jnp.float32), zero).astype(jnp.bfloat16)
         for wi in _split3(w)], axis=1)
    wv = v * w
    tiles = (_nt(lhs, jnp.concatenate([onehot] * 3, axis=1)),
             _nt(onehot, jnp.concatenate(_split3(wv), axis=0)),
             _nt(v, wv, precision=_P))
    for ref, lost, x in zip(outs, comp, tiles):
        y = x - lost[...]
        s = ref[...]
        t = s + y
        lost[...] = (t - s) - y
        ref[...] = t

    @pl.when(i == last)
    def _():
        for ref, lost in zip(outs, comp):
            ref[...] = ref[...] - lost[...]


def _cat_rows(n: int) -> tuple[int, int]:
    """(rows a grid step, padded rows): the largest of 512/256/128 that
    divides ``n``, else ``n`` padded to a multiple of 128 (below 512 rows)
    or of 512."""
    for t in (CAT_ROW_TILE, 256, 128):
        if n % t == 0:
            return t, n
    t = 128 if n < CAT_ROW_TILE else CAT_ROW_TILE
    return t, -(-n // t) * t


@partial(jax.jit, static_argnames=("p", "dense_pos"))
def cat_gram_local(codes, dense, w, z, *, p: int, dense_pos: tuple):
    """``(G, b, sw)`` of :func:`weighted_gram` on the design a
    ``FactoredDesign`` stands for (``codes`` (C, n), ``dense`` (q, n) at
    design columns ``dense_pos``, width ``p``), over the rows this device
    holds: the kernel ``cat_gram_pallas`` (:func:`_cat_gram_kernel`), then
    its blocks placed at their design columns.

    Precision: the indicator entries are exact in bfloat16 and every
    other operand of an MXU product (``w``, ``w·x``, ``w·z``) is split
    into three bfloat16 terms, so each product is exact and the sums are
    float32 — at least as exact as the ``HIGHEST`` einsum of
    :func:`weighted_gram`. The numeric-by-numeric block is a float32
    product at ``HIGHEST``. Rows whose weight is 0 contribute nothing."""
    n = codes.shape[1]
    q = len(dense_pos)
    if q == 0:  # no numeric and no intercept: one zero row
        dense = jnp.zeros((1, n), jnp.float32)
    q8 = -(-(dense.shape[0] + 2) // 8) * 8
    w2, z2 = w.reshape(1, n), z.reshape(1, n)
    tr, npad = _cat_rows(n)
    if npad != n:
        codes = jnp.pad(codes, ((0, 0), (0, npad - n)), constant_values=-1)
        dense, w2, z2 = (jnp.pad(a, ((0, 0), (0, npad - n)))
                         for a in (dense, w2, z2))
    k = -(-p // 128) * 128
    c = codes.shape[0]
    lanes = lambda rows: pl.BlockSpec(  # noqa: E731
        (rows, tr), lambda i: (0, i), memory_space=pltpu.VMEM)
    whole = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i: (0, 0), memory_space=pltpu.VMEM)
    cost = pl.CostEstimate(
        flops=int(2 * npad * (3 * k * k + 3 * k * q8 + q8 * q8)),
        bytes_accessed=int(npad * 4 * (c + dense.shape[0] + 2)
                           + 4 * (k * k + 3 * k * q8 + q8 * q8)),
        transcendentals=0)
    with jax.named_scope("ph_gram_cat"):
        gcc, cross, dd = pl.pallas_call(
            partial(_cat_gram_kernel, k=k, q8=q8),
            grid=(npad // tr,),
            in_specs=[lanes(c), lanes(dense.shape[0]), lanes(1), lanes(1)],
            out_specs=[whole((k, k)), whole((k, 3 * q8)), whole((q8, q8))],
            out_shape=[jax.ShapeDtypeStruct((k, k), jnp.float32),
                       jax.ShapeDtypeStruct((k, 3 * q8), jnp.float32),
                       jax.ShapeDtypeStruct((q8, q8), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((k, k), jnp.float32),
                            pltpu.VMEM((k, 3 * q8), jnp.float32),
                            pltpu.VMEM((q8, q8), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=CAT_VMEM_LIMIT),
            cost_estimate=cost,
            interpret=jax.default_backend() == "cpu",
            name="cat_gram_pallas",  # the prefix is the trace readers' key
        )(codes, dense, w2, z2)
        cross = cross[:, :q8] + cross[:, q8:2 * q8] + cross[:, 2 * q8:]
        # a numeric column's indicator rows are zero: its cross entries
        # are added at its design column, its own block from dd
        pos = jnp.asarray(dense_pos, jnp.int32)
        cd = cross[:p, :q]
        G = gcc[:p, :p].at[:, pos].add(cd).at[pos, :].add(cd.T)
        G = G.at[pos[:, None], pos[None, :]].add(dd[:q, :q])
        qd = dense.shape[0]  # v's rows: [x (qd); 1; z; 0...]
        b = cross[:p, qd + 1].at[pos].add(dd[:q, qd + 1])
        return G, b, dd[qd, qd]


def weighted_gram_factored(codes, dense, w, z, *, p: int, dense_pos: tuple,
                           mesh=None):
    """:func:`weighted_gram` of a factored design: :func:`cat_gram_local`
    on each device's rows and one ``psum`` of the partials over every row
    axis at once (the MRTask reduce), as XLA reduces the einsum of
    :func:`weighted_gram`: the sum's order is the same on a 1-D and a 2-D
    mesh. Traceable."""
    from h2o3_tpu.parallel.mesh import get_mesh, row_axes, row_pspec, shard_map
    from jax.sharding import PartitionSpec as Spec

    mesh = mesh or get_mesh()
    local = partial(cat_gram_local, p=p, dense_pos=dense_pos)
    if int(mesh.devices.size) <= 1:
        return local(codes, dense, w, z)
    axes = row_axes(mesh)

    def part(cl, dl, wl, zl):
        return jax.lax.psum(local(cl, dl, wl, zl), axes)

    lanes, rspec = row_pspec(mesh, ndim=2, axis=1), row_pspec(mesh)
    return shard_map(
        part, mesh, in_specs=(lanes, lanes, rspec, rspec),
        out_specs=(Spec(), Spec(), Spec()), check_vma=False,
    )(codes, dense, w, z)


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
    # the intercept is unpenalised: neither l1 nor l2 (the Cholesky lane's
    # rule, and hex.gram.Gram.addDiag's)
    ridge = jnp.where(ar == icpt, 0.0, l2)
    A = G + jnp.diag(pad_diag + ridge) + rho * jnp.eye(p, dtype=G.dtype)
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
    l1‖β‖₁ (the intercept unpenalized, by l2 as by l1)."""
    G = np.asarray(G, np.float64)
    b = np.asarray(b, np.float64)
    p = G.shape[0]
    if rho is None:
        rho = max(1e-3, np.mean(np.diag(G)))
    ridge = np.full(p, float(l2))
    thr = np.full(p, l1 / rho)
    if intercept_idx is not None:  # the intercept is unpenalised
        ridge[intercept_idx] = 0.0
        thr[intercept_idx] = 0.0
    A = G + np.diag(ridge) + rho * np.eye(p)
    c, low = scipy.linalg.cho_factor(A, lower=True)
    x = np.zeros(p)
    z = np.zeros(p)
    u = np.zeros(p)
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
