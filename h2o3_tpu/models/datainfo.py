"""Design-matrix view — successor of ``hex.DataInfo`` [UNVERIFIED upstream
path, SURVEY.md §2.2].

H2O's DataInfo gives GLM/DL/KMeans/PCA a canonical numeric view of a Frame:
categoricals expanded to indicator blocks, numerics standardized, missing
values imputed or skipped. Here the view is materialized as one row-sharded
``(npad, p)`` float32 device matrix — dense one-hot is MXU-friendly and XLA
fuses the expansion into downstream matmuls. Train-time statistics (means,
sigmas, domains) are captured so the identical transform applies to
validation/test frames (the ``adaptTestForTrain`` contract).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.frame.frame import CAT, Frame, Vec
from h2o3_tpu.parallel.mesh import row_sharding

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

    @jax.named_scope("ph_std")  # names the operations where a caller traces it
    def transform(self, frame: Frame):
        """Build the (npad, p) float32 design matrix on device, plus a row
        validity mask folding in padding and (if skip-handling) NA rows."""
        cols = []
        valid = frame.row_mask()
        for c in self.columns:
            if c.pair is not None:
                col, valid = self._transform_interaction(frame, c, valid)
                cols.append(col)
                continue
            v = frame.vec(c.name)
            if c.kind == "hash":
                buckets = self._hashed_codes(v, c)
                if self.missing_handling == SKIP:
                    valid = valid * (buckets >= 0).astype(jnp.float32)
                # use_all_factor_levels=False drops bucket 0 (reference),
                # exactly like the cat path — see the hash_buckets field doc
                cols.append(
                    _expand_cat(
                        buckets, self.hash_buckets, c.width,
                        self.use_all_factor_levels,
                    )
                )
            elif c.kind == "cat":
                codes = _adapt_codes(v, c.domain)
                if self.missing_handling == SKIP:
                    valid = valid * (codes >= 0).astype(jnp.float32)
                cols.append(_expand_cat(codes, len(c.domain), c.width, self.use_all_factor_levels))
            else:
                data = v.data
                isna = jnp.isnan(data)
                if self.missing_handling == SKIP:
                    valid = valid * (~isna).astype(jnp.float32)
                x = jnp.where(isna, c.mean, data)
                if self.standardize:
                    x = (x - c.mean) / c.sigma
                elif self.missing_handling == SKIP:
                    x = jnp.where(isna, 0.0, x)
                cols.append(x[:, None])
        if self.add_intercept:
            cols.append(jnp.ones((frame.npad, 1), jnp.float32))
        X = jnp.concatenate(cols, axis=1)
        X = jax.device_put(X, row_sharding())
        # zero out invalid rows so they contribute nothing to reductions
        X = X * valid[:, None]
        return X, valid

    def _hashed_codes(self, v: Vec, c: ColumnSpec):
        """Device bucket codes for a hashed column, LUT-cached per column
        (most-recent domain) so steady-state scoring never re-pays the
        O(cardinality) host hash loop and the cache stays bounded by the
        model's column count."""
        hit = self._hash_luts.get(c.name)
        if hit is not None and hit[0] is v.domain:
            lut_dev = hit[1]
        else:
            lut_dev = _hash_lut(v.domain or (), c.name, self.hash_buckets)
            self._hash_luts[c.name] = (v.domain, lut_dev)
        return jnp.where(v.data >= 0, lut_dev[jnp.clip(v.data, 0)], -1)

    def _transform_interaction(self, frame: Frame, c: ColumnSpec, valid):
        """Interaction block: numeric product or onehot(cat) * numeric.

        NA imputation uses the TRAINING means (c.pair_means) — never the
        scoring batch's — and missing_handling=SKIP invalidates rows with
        missing sources exactly like the base columns do.
        """
        if c.pair_domains is not None:  # cat x cat combined factor
            va, vb = frame.vec(c.pair[0]), frame.vec(c.pair[1])
            da, db = c.pair_domains
            # int32 BEFORE the product: enum codes may be stored int8/int16
            # (narrowest-dtype compression) and ca*len(db)+cb overflows there
            ca = _adapt_codes(va, da).astype(jnp.int32)
            cb = _adapt_codes(vb, db).astype(jnp.int32)
            codes = jnp.where((ca >= 0) & (cb >= 0), ca * len(db) + cb, -1)
            if self.missing_handling == SKIP:
                valid = valid * (codes >= 0).astype(jnp.float32)
            oh = _expand_cat(
                codes, len(c.domain), c.width, self.use_all_factor_levels
            )
            return oh, valid
        if c.kind == "num":
            va, vb = frame.vec(c.pair[0]), frame.vec(c.pair[1])
            ma, mb = c.pair_means or (0.0, 0.0)
            na = jnp.isnan(va.data) | jnp.isnan(vb.data)
            if self.missing_handling == SKIP:
                valid = valid * (~na).astype(jnp.float32)
            xa = jnp.nan_to_num(va.data, nan=ma)
            xb = jnp.nan_to_num(vb.data, nan=mb)
            x = xa * xb
            if self.standardize:
                x = (x - c.mean) / c.sigma
            return x[:, None], valid
        cv, nv = frame.vec(c.pair[0]), frame.vec(c.pair[1])
        codes = _adapt_codes(cv, c.domain)
        if self.missing_handling == SKIP:
            valid = valid * (codes >= 0).astype(jnp.float32)
            valid = valid * (~jnp.isnan(nv.data)).astype(jnp.float32)
        oh = _expand_cat(codes, len(c.domain), c.width, self.use_all_factor_levels)
        x = jnp.nan_to_num(nv.data, nan=(c.pair_means or (0.0, 0.0))[1])
        return oh * x[:, None], valid


def _hash_lut(domain: tuple[str, ...], col_name: str, n_buckets: int):
    """Device LUT: level code -> hash bucket.

    The bucket of a level is ``crc32(col_name \\0 level) % n_buckets`` — a
    STABLE string hash (Python's ``hash()`` is process-salted), seeded by the
    column name so two hashed columns decorrelate. Because the hash sees the
    level STRING, train and scoring frames land in identical buckets with no
    domain adaptation, at any cardinality. One crc32 per LEVEL, so callers
    must cache per domain (``DataInfo._hashed_codes`` does); NA codes (< 0)
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
    lut_dev = _hash_lut(v.domain or (), col_name, n_buckets)
    return jnp.where(v.data >= 0, lut_dev[jnp.clip(v.data, 0)], -1)


def _adapt_codes(v: Vec, train_domain: tuple[str, ...]):
    """Remap a categorical Vec's codes onto the training domain — the
    ``CategoricalWrappedVec`` / ``adaptTestForTrain`` successor. Unseen
    levels map to NA (-1), matching H2O's default warning path."""
    if v.domain == train_domain:
        return v.data
    lut = {d: i for i, d in enumerate(train_domain)}
    remap = np.full(len(v.domain or ()) + 1, -1, dtype=np.int32)
    for j, d in enumerate(v.domain or ()):
        remap[j] = lut.get(d, -1)
    remap_dev = jnp.asarray(remap)
    return jnp.where(v.data >= 0, remap_dev[jnp.clip(v.data, 0)], -1)


def _expand_cat(codes, card: int, width: int, use_all: bool):
    """Dense indicator block; NA (-1) rows get all-zeros (mode-free encoding,
    mirroring H2O's missing-as-zero-row for expanded categoricals)."""
    base = 0 if use_all else 1
    shifted = codes - base
    onehot = (shifted[:, None] == jnp.arange(width)[None, :]).astype(jnp.float32)
    return onehot
