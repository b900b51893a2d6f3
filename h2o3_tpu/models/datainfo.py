"""Design-matrix view — successor of ``hex.DataInfo`` [UNVERIFIED upstream
path, SURVEY.md §2.2].

H2O's DataInfo gives GLM/DL/KMeans/PCA a canonical numeric view of a Frame:
categoricals expanded to indicator blocks, numerics standardized, missing
values imputed or skipped. Here the view is materialized as one row-sharded
``(npad, p)`` float32 device matrix — dense one-hot is MXU-friendly and XLA
fuses the expansion into downstream matmuls. Train-time statistics (means,
sigmas, domains) are captured so the identical transform applies to
validation/test frames (the ``adaptTestForTrain`` contract).

:meth:`DataInfo.transform_factored` is the second view, for the GLM's IRLS:
each plain categorical column stays its codes (``hex.DataInfo.Row``'s
``binIds``) and only the numerics and the intercept are dense — a
:class:`FactoredDesign`, a few bytes a row where the indicator blocks take
four bytes a level.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.frame.frame import CAT, Frame, Vec
from h2o3_tpu.parallel.mesh import get_mesh, mesh_key, row_pspec, row_sharding
from h2o3_tpu.utils import flightrec as _fr

MEAN_IMPUTATION = "mean_imputation"
SKIP = "skip"


@dataclass
class ColumnSpec:
    name: str
    kind: str  # "num" | "cat" | "hash"
    mean: float = 0.0
    sigma: float = 1.0
    domain: tuple[str, ...] = ()
    offset: int = 0  # first column index in the expanded matrix
    width: int = 1
    # interaction column (upstream `interactions`/`interaction_pairs`):
    # ("a", "b") source pair; kind "num" = numeric product (standardized like
    # any numeric), kind "cat" = onehot(cat) * raw numeric per level.
    # pair_means = TRAINING means of the numeric sources (NA imputation must
    # not depend on the scoring batch)
    pair: tuple[str, str] | None = None
    pair_means: tuple[float, float] | None = None
    # cat x cat combined-factor interaction (upstream enum-by-enum): the
    # TRAINING domains of both sources, kept so scoring frames remap each
    # source before forming combined code a*|domain_b| + b
    pair_domains: tuple[tuple[str, ...], tuple[str, ...]] | None = None


@jax.tree_util.register_pytree_node_class
class FactoredDesign:
    """The design matrix with its categorical blocks as codes (what
    :meth:`DataInfo.transform_factored` returns): an ``(npad, p)`` matrix
    that is never materialised.

    - ``codes`` ``(C, npad)`` int32, one row a plain categorical column: the
      DESIGN column index of the row's level (the block's offset plus the
      level, shifted for the dropped reference level), or -1 where the
      indicator row is all zeros (NA, the reference level, an invalid row);
    - ``dense`` ``(q, npad)`` float32: the numerics (imputed, standardised)
      and the intercept, zero on invalid rows, at design columns
      ``dense_pos``;
    - ``p``: the design's width (padded columns past ``ncols_expanded`` are
      zero columns).

    Both arrays carry the rows on their LAST axis: an ``(npad, 6)`` operand
    would be tiled to 128 lanes a row in the TPU's HBM. A pytree: the arrays
    are its leaves, ``dense_pos`` and ``p`` its static structure."""

    def __init__(self, codes, dense, dense_pos: tuple, p: int):
        self.codes = codes
        self.dense = dense
        self.dense_pos = tuple(dense_pos)
        self.p = int(p)

    def tree_flatten(self):
        return (self.codes, self.dense), (self.dense_pos, self.p)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)

    @property
    def shape(self) -> tuple[int, int]:
        return (self.codes.shape[1], self.p)

    @property
    def nbytes(self) -> int:
        return int(self.codes.nbytes + self.dense.nbytes)

    def widened(self, p: int) -> "FactoredDesign":
        """The same design with zero columns up to width ``p``."""
        return FactoredDesign(self.codes, self.dense, self.dense_pos, p)


@dataclass
class DataInfo:
    """Fitted design-matrix spec. Build with :meth:`fit`, apply with
    :meth:`transform`."""

    columns: list[ColumnSpec] = field(default_factory=list)
    standardize: bool = True
    use_all_factor_levels: bool = True
    missing_handling: str = MEAN_IMPUTATION
    add_intercept: bool = False
    ncols_expanded: int = 0
    # feature hashing (the sparse-chunk / sparse-DMatrix successor for
    # Criteo-class cardinalities): cat columns wider than hash_buckets
    # levels expand to a FIXED hash_buckets-wide indicator block instead of
    # one column per level, bounding the design matrix at any cardinality.
    # Buckets come from a stable string hash of (column, level), so train
    # and scoring frames agree without any domain remap. Values <= 0 mean
    # "no hashing" (fit coerces them to None). Like the exact cat path,
    # use_all_factor_levels=False drops bucket 0 as the reference level —
    # otherwise the block sums to the intercept and the unregularized Gram
    # goes singular.
    hash_buckets: int | None = None
    # per-COLUMN device LUT cache (most-recent domain only): rebuilding
    # costs one crc32 per LEVEL (≈1M Python calls at Criteo cardinality)
    # and must not be paid again on every scoring call. Keyed by column
    # name alone — a long-lived scoring server cycling through frames with
    # distinct domain objects would otherwise pin every domain tuple +
    # device LUT it ever saw. Values hold the domain tuple so a hit can be
    # validated by identity and a stale entry is simply replaced.
    _hash_luts: dict = field(default_factory=dict, repr=False, compare=False)

    @staticmethod
    def fit(
        frame: Frame,
        x: list[str],
        standardize: bool = True,
        use_all_factor_levels: bool = True,
        missing_handling: str = MEAN_IMPUTATION,
        add_intercept: bool = False,
        interaction_pairs: list[tuple[str, str]] | None = None,
        hash_buckets: int | None = None,
    ) -> "DataInfo":
        hash_buckets = (
            int(hash_buckets) if hash_buckets and int(hash_buckets) > 0 else None
        )
        di = DataInfo(
            standardize=standardize,
            use_all_factor_levels=use_all_factor_levels,
            missing_handling=missing_handling,
            add_intercept=add_intercept,
            hash_buckets=hash_buckets,
        )
        off = 0
        # H2O orders the expanded matrix categoricals-first, then numerics
        # [UNVERIFIED]; we keep the user's column order for readability of
        # coefficient names — the math is order-invariant.
        for name in x:
            v = frame.vec(name)
            if v.is_categorical():
                k = v.cardinality
                if hash_buckets is not None and k > hash_buckets:
                    hw = (
                        hash_buckets
                        if use_all_factor_levels
                        else max(1, hash_buckets - 1)
                    )
                    di.columns.append(
                        ColumnSpec(name, "hash", offset=off, width=hw)
                    )
                    off += hw
                    continue
                width = k if use_all_factor_levels else max(1, k - 1)
                di.columns.append(
                    ColumnSpec(name, "cat", domain=v.domain or (), offset=off, width=width)
                )
                off += width
            else:
                s = v.stats()
                sigma = s["sigma"] if standardize else 1.0
                if not np.isfinite(sigma) or sigma == 0.0:
                    sigma = 1.0
                di.columns.append(
                    ColumnSpec(
                        name,
                        "num",
                        mean=s["mean"] if np.isfinite(s["mean"]) else 0.0,
                        sigma=sigma,
                        offset=off,
                    )
                )
                off += 1
        for a, b in interaction_pairs or ():
            va, vb = frame.vec(a), frame.vec(b)
            if va.is_categorical() and vb.is_categorical():
                # combined-factor column (upstream enum-by-enum interaction):
                # one level per (level_a, level_b) cross pair
                da = tuple(va.domain or ())
                db = tuple(vb.domain or ())
                dom = tuple(f"{x}_{y}" for x in da for y in db)
                k = len(dom)
                width = k if use_all_factor_levels else max(1, k - 1)
                di.columns.append(
                    ColumnSpec(f"{a}:{b}", "cat", domain=dom, offset=off,
                               width=width, pair=(a, b),
                               pair_domains=(da, db))
                )
                off += width
                continue
            if va.is_categorical() or vb.is_categorical():
                cv, nv = (va, vb) if va.is_categorical() else (vb, va)
                k = cv.cardinality
                width = k if use_all_factor_levels else max(1, k - 1)
                di.columns.append(
                    ColumnSpec(f"{cv.name}:{nv.name}", "cat",
                               domain=cv.domain or (), offset=off,
                               width=width, pair=(cv.name, nv.name),
                               pair_means=(0.0, float(nv.mean())))
                )
                off += width
            else:
                # product stats on device (one tiny reduction) so the
                # interaction standardizes like any other numeric column
                ma, mb = float(va.mean()), float(vb.mean())
                xa = jnp.nan_to_num(va.data, nan=ma)
                xb = jnp.nan_to_num(vb.data, nan=mb)
                prod = xa * xb
                mask = frame.row_mask()
                sw = jnp.maximum(mask.sum(), 1.0)
                mean = float(jnp.sum(prod * mask) / sw)
                sigma = float(
                    jnp.sqrt(jnp.sum(mask * (prod - mean) ** 2) / sw)
                ) if standardize else 1.0
                if not np.isfinite(sigma) or sigma == 0.0:
                    sigma = 1.0
                di.columns.append(
                    ColumnSpec(f"{a}:{b}", "num",
                               mean=mean if standardize else 0.0, sigma=sigma,
                               offset=off, pair=(a, b), pair_means=(ma, mb))
                )
                off += 1
        di.ncols_expanded = off + (1 if add_intercept else 0)
        return di

    # -- expanded-column names (for coefficient tables) ----------------------
    def coef_names(self) -> list[str]:
        names = []
        for c in self.columns:
            if c.kind == "hash":
                names += [f"{c.name}.hash{i}" for i in range(c.width)]
            elif c.kind == "cat":
                lo = 0 if self.use_all_factor_levels else 1
                if c.pair_domains is not None:  # cat x cat combined factor
                    names += [f"{c.name}.{d}" for d in c.domain[lo : lo + c.width]]
                elif c.pair is not None:  # cat x num interaction block
                    names += [
                        f"{c.pair[0]}.{d}:{c.pair[1]}"
                        for d in c.domain[lo : lo + c.width]
                    ]
                else:
                    names += [f"{c.name}.{d}" for d in c.domain[lo : lo + c.width]]
            else:
                names.append(c.name)
        if self.add_intercept:
            names.append("Intercept")
        return names

    def transform(self, frame: Frame):
        """Build the (npad, p) float32 design matrix on device, plus a row
        validity mask folding in padding and (if skip-handling) NA rows.

        One dispatch (``dispatch:design``): the whole expansion is the traced
        program :func:`_design`, keyed by the columns' structure. The column
        arrays, the training statistics and the code LUTs are its operands,
        so a second frame of the same structure compiles nothing."""
        cols, operands = [], []
        stats = np.zeros((max(1, len(self.columns)), 4), np.float32)
        for i, c in enumerate(self.columns):
            stats[i, :2] = c.mean, c.sigma
            if c.pair_means is not None:
                stats[i, 2:] = c.pair_means
            if c.pair_domains is not None:  # cat x cat combined factor
                da, db = c.pair_domains
                cols.append(("cat_cat", len(c.domain), c.width, len(db)))
                operands.append((
                    _codes_operands(frame.vec(c.pair[0]), da),
                    _codes_operands(frame.vec(c.pair[1]), db),
                ))
            elif c.pair is not None and c.kind == "num":
                cols.append(("num_num",))
                operands.append(
                    (frame.vec(c.pair[0]).data, frame.vec(c.pair[1]).data))
            elif c.pair is not None:  # onehot(cat) * numeric
                cols.append(("cat_num", len(c.domain), c.width))
                operands.append((
                    _codes_operands(frame.vec(c.pair[0]), c.domain),
                    frame.vec(c.pair[1]).data,
                ))
            elif c.kind == "hash":
                v = frame.vec(c.name)
                cols.append(("cat", self.hash_buckets, c.width))
                operands.append((v.data, self._hash_lut_for(v, c)))
            elif c.kind == "cat":
                cols.append(("cat", len(c.domain), c.width))
                operands.append(_codes_operands(frame.vec(c.name), c.domain))
            else:
                cols.append(("num",))
                operands.append((frame.vec(c.name).data,))
        plan = (
            tuple(cols), self.standardize, self.missing_handling == SKIP,
            self.use_all_factor_levels, self.add_intercept, frame.npad,
            mesh_key(),
        )
        with _fr.dispatch("design", rows=frame.npad, cols=self.ncols_expanded):
            return _design(plan, np.int32(frame.nrow), stats, operands)

    def factorable(self) -> bool:
        """Whether :meth:`transform_factored` applies: a plain categorical
        column and no interaction or hashed one (those keep the dense
        design, docs/MIGRATION.md)."""
        plain = [c.kind == "cat" and c.pair is None for c in self.columns]
        return any(plain) and all(
            ok or (c.kind == "num" and c.pair is None)
            for ok, c in zip(plain, self.columns))

    def transform_factored(self, frame: Frame):
        """The design of :meth:`transform` as a :class:`FactoredDesign`, plus
        the same row validity mask. One dispatch (``dispatch:design``), the
        traced program :func:`_design_factored`; the numerics are computed
        op for op as :func:`_design` computes them."""
        assert self.factorable(), "interaction or hashed columns: use transform"
        cols, operands, dense_pos = [], [], []
        stats = np.zeros((max(1, len(self.columns)), 2), np.float32)
        for i, c in enumerate(self.columns):
            stats[i] = c.mean, c.sigma
            if c.kind == "cat":
                cols.append(("cat", c.offset))
                operands.append(_codes_operands(frame.vec(c.name), c.domain))
            else:
                cols.append(("num",))
                operands.append(frame.vec(c.name).data)
                dense_pos.append(c.offset)
        if self.add_intercept:
            dense_pos.append(self.ncols_expanded - 1)
        plan = (
            tuple(cols), self.standardize, self.missing_handling == SKIP,
            self.use_all_factor_levels, self.add_intercept, frame.npad,
            mesh_key(),
        )
        with _fr.dispatch("design", rows=frame.npad, cols=self.ncols_expanded):
            codes, dense, valid = _design_factored(
                plan, np.int32(frame.nrow), stats, operands)
        return FactoredDesign(codes, dense, dense_pos, self.ncols_expanded), valid

    def _hash_lut_for(self, v: Vec, c: ColumnSpec):
        """Device LUT (level code -> bucket) of a hashed column, cached per
        column (most-recent domain) so steady-state scoring never re-pays the
        O(cardinality) host hash loop and the cache stays bounded by the
        model's column count."""
        hit = self._hash_luts.get(c.name)
        if hit is not None and hit[0] is v.domain:
            return hit[1]
        lut_dev = _hash_lut(v.domain or (), c.name, self.hash_buckets)
        self._hash_luts[c.name] = (v.domain, lut_dev)
        return lut_dev


@partial(jax.jit, static_argnums=0)
@jax.named_scope("ph_std")  # a traced program: the scope names its operations
def _design(plan, nrow, stats, operands):
    """The traced body of :meth:`DataInfo.transform`. ``plan`` is static:
    each column's kind, cardinality and width, then the DataInfo's flags,
    ``npad`` and the mesh. ``stats`` holds a row (mean, sigma, pair means)
    for each column; ``operands`` holds each column's arrays.

    NA imputation uses the TRAINING means, never the scoring batch's, and
    missing_handling=SKIP invalidates rows with a missing source, in the
    interaction blocks exactly like the base columns."""
    col_plans, standardize, skip, use_all, add_intercept, npad, _mesh = plan
    valid = (jnp.arange(npad) < nrow).astype(jnp.float32)
    present = jnp.ones(npad, bool)  # rows with no missing source
    cols = []
    for i, (cp, ops) in enumerate(zip(col_plans, operands)):
        mean, sigma = stats[i, 0], stats[i, 1]
        if cp[0] == "num":
            x, isna = _num_column(ops[0], mean, sigma, standardize, skip)
            present &= ~isna
            cols.append(x[:, None])
        elif cp[0] == "num_num":
            xa, xb = ops
            present &= ~(jnp.isnan(xa) | jnp.isnan(xb))
            x = jnp.nan_to_num(xa, nan=stats[i, 2]) * jnp.nan_to_num(
                xb, nan=stats[i, 3])
            if standardize:
                x = (x - mean) / sigma
            cols.append(x[:, None])
        elif cp[0] == "cat_cat":
            # int32 BEFORE the product: enum codes may be stored int8/int16
            # (narrowest-dtype compression) and ca*len(db)+cb overflows there
            ca = _codes(ops[0]).astype(jnp.int32)
            cb = _codes(ops[1]).astype(jnp.int32)
            codes = jnp.where((ca >= 0) & (cb >= 0), ca * cp[3] + cb, -1)
            present &= codes >= 0
            cols.append(_expand_cat(codes, cp[1], cp[2], use_all))
        elif cp[0] == "cat_num":
            codes, x = _codes(ops[0]), ops[1]
            present &= (codes >= 0) & ~jnp.isnan(x)
            oh = _expand_cat(codes, cp[1], cp[2], use_all)
            cols.append(oh * jnp.nan_to_num(x, nan=stats[i, 3])[:, None])
        else:
            # "cat"; a hashed column is one whose LUT maps levels to buckets.
            # use_all_factor_levels=False drops level (bucket) 0, the
            # reference — see the hash_buckets field doc
            codes = _codes(ops)
            present &= codes >= 0
            cols.append(_expand_cat(codes, cp[1], cp[2], use_all))
    if skip:
        valid = valid * present.astype(jnp.float32)
    if add_intercept:
        cols.append(jnp.ones((npad, 1), jnp.float32))
    X = jnp.concatenate(cols, axis=1)
    X = jax.lax.with_sharding_constraint(X, row_sharding())
    # zero out invalid rows so they contribute nothing to reductions
    X = X * valid[:, None]
    return X, jax.lax.with_sharding_constraint(valid, row_sharding())


def _num_column(data, mean, sigma, standardize: bool, skip: bool):
    """A numeric column of the design and its NA mask: NA imputed with the
    TRAINING mean, then standardised (or, unstandardised under SKIP, NA as
    0: the row is invalid anyway)."""
    isna = jnp.isnan(data)
    x = jnp.where(isna, mean, data)
    if standardize:
        x = (x - mean) / sigma
    elif skip:
        x = jnp.where(isna, 0.0, x)
    return x, isna


@partial(jax.jit, static_argnums=0)
@jax.named_scope("ph_std")
def _design_factored(plan, nrow, stats, operands):
    """The traced body of :meth:`DataInfo.transform_factored`: ``(codes,
    dense, valid)``. ``plan`` holds each column's kind and, for a
    categorical, its block's offset; the rest is :func:`_design`'s."""
    col_plans, standardize, skip, use_all, add_intercept, npad, _mesh = plan
    valid = (jnp.arange(npad) < nrow).astype(jnp.float32)
    present = jnp.ones(npad, bool)
    base = 0 if use_all else 1  # use_all_factor_levels=False drops level 0
    codes, dense = [], []
    for i, (cp, ops) in enumerate(zip(col_plans, operands)):
        if cp[0] == "cat":
            c = _codes(ops).astype(jnp.int32)
            present &= c >= 0
            codes.append(jnp.where(c >= base, c - base + cp[1], -1))
        else:
            x, isna = _num_column(ops, stats[i, 0], stats[i, 1], standardize, skip)
            present &= ~isna
            dense.append(x)
    if skip:
        valid = valid * present.astype(jnp.float32)
    if add_intercept:
        dense.append(jnp.ones(npad, jnp.float32))
    lanes = jax.sharding.NamedSharding(
        get_mesh(), row_pspec(ndim=2, axis=1))
    codes = jnp.where(valid > 0, jnp.stack(codes), -1)
    dense = (jnp.stack(dense) if dense else jnp.zeros((0, npad), jnp.float32))
    dense = dense * valid[None, :]
    return (jax.lax.with_sharding_constraint(codes, lanes),
            jax.lax.with_sharding_constraint(dense, lanes),
            jax.lax.with_sharding_constraint(valid, row_sharding()))


def _hash_lut(domain: tuple[str, ...], col_name: str, n_buckets: int):
    """Device LUT: level code -> hash bucket.

    The bucket of a level is ``crc32(col_name \\0 level) % n_buckets`` — a
    STABLE string hash (Python's ``hash()`` is process-salted), seeded by the
    column name so two hashed columns decorrelate. Because the hash sees the
    level STRING, train and scoring frames land in identical buckets with no
    domain adaptation, at any cardinality. One crc32 per LEVEL, so callers
    must cache per domain (``DataInfo._hash_lut_for`` does); NA codes (< 0)
    stay NA (-1) → all-zero indicator row.
    """
    import zlib

    prefix = col_name.encode() + b"\x00"
    lut = np.fromiter(
        (zlib.crc32(prefix + d.encode()) % n_buckets for d in domain),
        dtype=np.int32,
        count=len(domain),
    )
    return jnp.asarray(np.append(lut, -1))  # slot keeps the gather in-bounds
                                            # for an empty domain


def _hash_codes(v: Vec, col_name: str, n_buckets: int):
    """Uncached convenience wrapper (tests / one-off use)."""
    return _codes((v.data, _hash_lut(v.domain or (), col_name, n_buckets)))


def _codes_operands(v: Vec, train_domain: tuple[str, ...]) -> tuple:
    """A categorical Vec's codes on the training domain, as operands of a
    traced program: ``(codes,)`` where the domains agree, else ``(codes,
    remap)`` with the host-built LUT from the Vec's levels to the training
    ones — the ``CategoricalWrappedVec`` / ``adaptTestForTrain`` successor.
    Unseen levels map to NA (-1), matching H2O's default warning path."""
    if v.domain == train_domain:
        return (v.data,)
    lut = {d: i for i, d in enumerate(train_domain)}
    remap = np.full(len(v.domain or ()) + 1, -1, dtype=np.int32)
    for j, d in enumerate(v.domain or ()):
        remap[j] = lut.get(d, -1)
    return (v.data, remap)


def _codes(ops):
    """The codes of :func:`_codes_operands` (or of a hashed column's
    ``(codes, bucket LUT)``); traceable. NA (< 0) stays NA (-1)."""
    if len(ops) == 1:
        return ops[0]
    data, lut = ops
    return jnp.where(data >= 0, jnp.asarray(lut)[jnp.clip(data, 0)], -1)


def _adapt_codes(v: Vec, train_domain: tuple[str, ...]):
    """Remap a categorical Vec's codes onto the training domain."""
    return _codes(_codes_operands(v, train_domain))


def _expand_cat(codes, card: int, width: int, use_all: bool):
    """Dense indicator block; NA (-1) rows get all-zeros (mode-free encoding,
    mirroring H2O's missing-as-zero-row for expanded categoricals)."""
    base = 0 if use_all else 1
    shifted = codes - base
    onehot = (shifted[:, None] == jnp.arange(width)[None, :]).astype(jnp.float32)
    return onehot
