"""Ingest — successor of ``water.parser.ParseDataset`` / ``ParseSetup`` /
``CsvParser`` [UNVERIFIED upstream paths, SURVEY.md §0].

H2O's distributed parse maps ``CsvParser.parseChunk`` over file-block chunks
and unifies categorical domains in a second cluster pass (SURVEY.md §3.2).
The TPU-native shape of that work (SURVEY.md §7 step 3) is host-side columnar
ingest — pandas/pyarrow do vectorized tokenization — followed by type
inference, global categorical interning (single-process: one pass), and
``device_put`` of each column's padded buffer with the row sharding. The
three-call REST surface (ImportFiles → ParseSetup → Parse) is preserved by
:func:`parse_setup` + :func:`parse` for API parity.

Formats: CSV (+gz), Parquet, ORC, Feather/Arrow, SVMLight; XLS via pandas
when openpyxl is present.
"""

from __future__ import annotations

import os
from typing import Mapping, Sequence

import numpy as np
import pandas as pd

from h2o3_tpu.frame.frame import CAT, INT, NUM, STR, TIME, Frame, Vec
from h2o3_tpu.utils.log import Log

# H2O parses low-cardinality strings as enums and high-cardinality ones as
# strings; this mirrors that heuristic (upstream constant lives in the parser
# setup logic [UNVERIFIED]).
_MAX_CAT_FRACTION = 0.95
_MAX_CAT_LEVELS = 10_000_000


def _read_any(
    path: str,
    sep: str | None = None,
    header: int | None = 0,
    nrows: int | None = None,
) -> pd.DataFrame:
    ext = os.path.splitext(path.removesuffix(".gz"))[1].lower()
    if ext in (".parquet", ".pq"):
        return pd.read_parquet(path)
    if ext == ".orc":
        return pd.read_orc(path)
    if ext in (".feather", ".arrow"):
        return pd.read_feather(path)
    if ext in (".xls", ".xlsx"):
        return pd.read_excel(path, nrows=nrows)
    if ext == ".svm" or ext == ".svmlight":
        from sklearn.datasets import load_svmlight_file

        X, y = load_svmlight_file(path)
        df = pd.DataFrame(X.toarray(), columns=[f"C{i + 1}" for i in range(X.shape[1])])
        df.insert(0, "target", y)
        return df
    # CSV / TSV / txt (+ .gz transparently via pandas)
    sep = sep or _sniff_sep(path)
    if header == 0 and nrows is None:
        from h2o3_tpu import config

        if config.get_bool("H2O3_TPU_NATIVE_PARSE"):
            df = _try_native_csv(path, sep)
            if df is not None:
                return df
    return pd.read_csv(path, sep=sep, header=header, engine="c", nrows=nrows)


def _try_native_csv(path: str, sep: str) -> pd.DataFrame | None:
    """Native chunked-parse fast path (native/fastcsv.cpp via native_csv.py)
    — the ParseDataset tokenizer analog. Returns None whenever the file is
    outside the strict fast path, and the caller uses pandas: eligibility
    is decided from a 2000-row pandas sample so both paths agree on types.

    Known value-semantics deviation (documented): a column whose sampled
    rows are integers narrows to int64 iff the FULL column is NA-free and
    integral-valued — a decimal-formatted integral value ("2.0") past the
    sample keeps it int where pandas would flip the dtype to float. H2O
    types by value, so this is the upstream-faithful choice.
    """
    import gzip
    import io

    from h2o3_tpu import native_csv

    if not native_csv.available():
        return None
    opener = (lambda: gzip.open(path, "rb")) if path.endswith(".gz") else (
        lambda: open(path, "rb")
    )
    try:
        # eligibility from a BOUNDED prefix first — an ineligible multi-GB
        # file must not be slurped (and then re-read by pandas anyway)
        with opener() as f:
            prefix = f.read(4 << 20)
        if len(prefix) == (4 << 20):
            # likely truncated mid-line: drop the partial last line so it
            # cannot poison the dtype sniff
            cut = prefix.rfind(b"\n")
            if cut < 0:
                return None
            prefix = prefix[: cut + 1]
        sample = pd.read_csv(io.BytesIO(prefix), sep=sep, nrows=2000, engine="c")
    except Exception:  # noqa: BLE001 — any sniff trouble means pandas decides
        return None
    names = [str(c) for c in sample.columns]
    if len(set(names)) != len(names):
        return None  # duplicate headers: pandas mangles, we won't guess
    kinds: list[int] = []
    int_named = []
    for c in sample.columns:
        s = sample[c]
        if pd.api.types.is_bool_dtype(s):
            return None  # pandas bool semantics
        if pd.api.types.is_integer_dtype(s):
            kinds.append(0)
            int_named.append(str(c))
        elif pd.api.types.is_float_dtype(s):
            kinds.append(0)
        elif (
            pd.api.types.is_object_dtype(s) or pd.api.types.is_string_dtype(s)
        ) and infer_kind(s) == CAT:
            # string-ish AND sniffed as enum (pandas ≥2 infers 'str' dtype,
            # not object, for string columns)
            kinds.append(1)
        else:
            # datetime / TIME-ish / STR / mixed: pandas semantics
            return None
    try:
        with opener() as f:
            data = f.read()
        df = native_csv.parse_csv_native(data, names, kinds, sep=sep)
    except Exception:  # noqa: BLE001 — ANY native trouble means pandas decides
        return None
    if df is None:
        return None
    for c in int_named:
        v = df[c].to_numpy()
        if np.any(np.abs(v) >= 2**53):
            # f64 already rounded these — only pandas' int64 path is exact
            return None
        if not np.isnan(v).any() and np.all(v == np.floor(v)):
            df[c] = v.astype(np.int64)
    return df


def _sniff_sep(path: str) -> str:
    """Separator guessing on the first lines — ParseSetup's sep sniffing."""
    import gzip

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt", errors="replace") as f:
        head = [line for _, line in zip(range(5), f)]
    if not head:
        return ","
    best, best_score = ",", -1
    for cand in (",", "\t", ";", "|"):
        counts = [line.count(cand) for line in head]
        score = min(counts) if min(counts) == max(counts) else 0
        if score > best_score:
            best, best_score = cand, score
    return best


def infer_kind(s: pd.Series) -> str:
    """Column type inference — ParseSetup's type-sniffing successor."""
    if pd.api.types.is_bool_dtype(s):
        return CAT
    if pd.api.types.is_datetime64_any_dtype(s):
        return TIME
    if isinstance(s.dtype, pd.CategoricalDtype):
        return CAT
    if pd.api.types.is_integer_dtype(s):
        return INT
    if pd.api.types.is_float_dtype(s):
        return NUM
    # object/string column: enum unless near-unique
    nz = s.dropna()
    if len(nz) == 0:
        return NUM
    # numeric-looking strings parse as numeric (CsvParser type coercion)
    coerced = pd.to_numeric(nz, errors="coerce")
    if coerced.notna().all():
        return NUM
    # date/time-looking strings parse as TIME (ParseSetup sniffs date formats)
    sample = nz.iloc[: 1000].astype(str)
    if sample.str.match(r"^\d{4}-\d{2}-\d{2}([ T].*)?$").all():
        try:
            pd.to_datetime(sample, format="ISO8601")
            return TIME
        except (ValueError, TypeError):
            pass
    nuniq = nz.nunique()
    if nuniq > _MAX_CAT_LEVELS or (len(nz) > 100 and nuniq > _MAX_CAT_FRACTION * len(nz)):
        return STR
    return CAT


def _series_to_host(s: pd.Series, kind: str, name: str):
    """Column → host-side (kind, values, domain, exact_time_copy) WITHOUT
    device placement, so :func:`dataframe_to_vecs` can batch all columns of
    one dtype into a single host→device transfer (28 per-column puts of a
    10M-row frame were upload-bound)."""
    if kind == STR:
        vals = s.astype(object).where(s.notna(), None).to_numpy()
        return STR, vals, None, None
    if kind == CAT:
        if isinstance(s.dtype, pd.CategoricalDtype):
            cat = s.cat
            domain = [str(c) for c in cat.categories]
            codes = cat.codes.to_numpy().astype(np.int32)
        else:
            astr = s.astype(object).where(s.notna(), None)
            # H2O interns categorical levels in sorted order [UNVERIFIED]
            levels = sorted({str(v) for v in astr.dropna()})
            lut = {v: i for i, v in enumerate(levels)}
            codes = np.array(
                [lut[str(v)] if v is not None else -1 for v in astr], dtype=np.int32
            )
            domain = levels
        return CAT, codes, domain, None
    if kind == TIME:
        # epoch milliseconds UTC (H2O's time encoding); robust to the series'
        # datetime64 resolution (ns in classic pandas, us/s possible in 2.x)
        # and to timezone-aware inputs
        # errors="coerce": values the sniff sample missed (mixed formats, stray
        # strings past the first 1000 rows) become NA instead of crashing
        if pd.api.types.is_datetime64_any_dtype(s):
            dt = pd.to_datetime(s)
        elif pd.api.types.is_numeric_dtype(s):
            dt = pd.to_datetime(s, unit="ms", errors="coerce")  # epoch-ms input
        else:
            dt = pd.to_datetime(s, errors="coerce", format="ISO8601")
        if getattr(dt.dtype, "tz", None) is not None:
            dt = dt.dt.tz_convert("UTC").dt.tz_localize(None)
        vals = dt.astype("datetime64[ms]").astype("int64").to_numpy().astype(np.float64)
        vals = np.where(dt.isna().to_numpy(), np.nan, vals)
        return TIME, vals, None, np.asarray(vals, dtype=np.float64)
    vals = pd.to_numeric(s, errors="coerce").to_numpy(dtype=np.float64)
    return (INT if kind == INT else NUM), vals, None, None


def dataframe_to_vecs(df: pd.DataFrame, column_types: Mapping[str, str]) -> list[Vec]:
    """Columns → Vecs with BATCHED device placement: all columns of one
    device dtype ride a single host→device transfer and are sliced apart on
    device. Per-column ``device_put`` fragments a 10M×28 upload into one
    transfer per column; one (rows, k) matrix per dtype amortizes it to ≤3
    transfers total."""
    from h2o3_tpu.parallel.mesh import pad_to_shards, shard_rows

    specs = []
    for name in df.columns:
        kind = column_types.get(str(name)) or infer_kind(df[name])
        if kind in ("numeric", "float", "double"):
            kind = NUM
        if kind in ("factor", "categorical"):
            kind = CAT
        specs.append((str(name), *_series_to_host(df[name], kind, str(name))))

    n = len(df)
    npad = pad_to_shards(n)
    vecs: list[Vec | None] = [None] * len(specs)
    groups: dict = {}  # device dtype -> [spec index]
    for i, (name, kind, arr, domain, exact) in enumerate(specs):
        if kind == STR:
            vecs[i] = Vec(arr, STR, name=name)
        else:
            dt, fill = Vec.device_dtype(kind, domain)
            groups.setdefault(dt.name, (dt, fill, []))[2].append(i)

    from h2o3_tpu.frame import chunkstore as _cs

    seed_mirror = _cs.streaming_enabled()
    for dt, fill, idxs in groups.values():
        mat = np.full((npad, len(idxs)), fill, dtype=dt)
        for j, i in enumerate(idxs):
            mat[:n, j] = specs[i][2].astype(dt, copy=False)
        dmat = shard_rows(mat)  # ONE transfer for the whole dtype group
        # the staging matrix is live device memory no Vec owns yet: claim
        # it in the devmem ledger under 'parse' until the per-column
        # slices (each its own device array) take over as frame_resident
        from h2o3_tpu.utils import devmem as _dm

        _dm.adjust("parse", dmat.nbytes)
        try:
            for j, i in enumerate(idxs):
                name, kind, _arr, domain, exact = specs[i]
                vecs[i] = Vec(dmat[:, j], kind, name=name, domain=domain,
                              nrow=n, host_exact=exact)
                if seed_mirror:
                    # an HBM window is configured: the ingest buffer already
                    # holds the padded column, so seed the spill-tier mirror
                    # now — a streaming build's host_values() then costs
                    # nothing instead of a device pull per column
                    vecs[i]._seed_host_mirror(mat[:, j])
        finally:
            _dm.adjust("parse", -dmat.nbytes)
    return vecs


def parse_setup(path: str, sep: str | None = None) -> dict:
    """Sniff a file — the ``POST /3/ParseSetup`` successor. Returns an
    editable setup dict accepted by :func:`parse`."""
    ext = os.path.splitext(path.removesuffix(".gz"))[1].lower()
    if sep is None and ext not in (".parquet", ".pq", ".orc", ".feather", ".arrow", ".xls", ".xlsx", ".svm", ".svmlight"):
        sep = _sniff_sep(path)
    head = _read_any(path, sep=sep, nrows=10_000)
    return {
        "source_frames": [path],
        "separator": sep or ",",
        "column_names": [str(c) for c in head.columns],
        "column_types": {str(c): infer_kind(head[c]) for c in head.columns},
        "rows_sniffed": len(head),
    }


_STREAM_CHUNK_ROWS = 1_000_000  # size threshold lives in config (H2O3_TPU_STREAM_BYTES)


def _is_csv_like(path: str) -> bool:
    ext = os.path.splitext(path.removesuffix(".gz"))[1].lower()
    return ext not in (
        ".parquet", ".pq", ".orc", ".feather", ".arrow", ".xls", ".xlsx",
        ".svm", ".svmlight",
    )


def parse_stream(
    paths: Sequence[str],
    column_types: Mapping[str, str],
    sep: str | None = None,
    destination_frame: str | None = None,
    chunk_rows: int = _STREAM_CHUNK_ROWS,
) -> Frame:
    """Chunked CSV ingest — the distributed-parse successor for files that
    should not be tokenized in one piece (upstream maps ``parseChunk`` over
    file blocks and unifies categorical domains in a second pass; here the
    chunked reader bounds tokenizer memory, categorical levels intern
    incrementally per chunk, and the cross-chunk code remap at the end is the
    single-process image of that second pass).
    """
    col_order: list[str] | None = None
    kinds: dict[str, str] = {}
    num_parts: dict[str, list[np.ndarray]] = {}
    cat_parts: dict[str, list[np.ndarray]] = {}
    str_parts: dict[str, list[np.ndarray]] = {}
    domains: dict[str, dict[str, int]] = {}
    # column types are fixed by the setup sniff (or the first chunk) — count
    # values later chunks silently coerce to NA so the drift is at least loud
    coerce_losses: dict[str, int] = {}

    for path in paths:
        reader = pd.read_csv(
            path, sep=sep or _sniff_sep(path), engine="c", chunksize=chunk_rows
        )
        for chunk in reader:
            if col_order is None:
                col_order = [str(c) for c in chunk.columns]
                for c in col_order:
                    k = column_types.get(c) or infer_kind(chunk[c])
                    if k in ("numeric", "float", "double"):
                        k = NUM
                    if k in ("factor", "categorical"):
                        k = CAT
                    kinds[c] = k
            for c in col_order:
                s = chunk[c]
                k = kinds[c]
                if k == CAT:
                    # C-speed interning: factorize the chunk, then remap the
                    # (small) chunk-local domain into the global LUT
                    local_codes, local_levels = pd.factorize(
                        s.astype(str).where(s.notna(), None)
                    )
                    lut = domains.setdefault(c, {})
                    remap = np.empty(len(local_levels) + 1, np.int32)
                    for li, lv in enumerate(local_levels):
                        remap[li] = lut.setdefault(str(lv), len(lut))
                    remap[-1] = -1  # factorize encodes NA as -1
                    cat_parts.setdefault(c, []).append(
                        remap[local_codes.astype(np.int64)]
                    )
                elif k == STR:
                    str_parts.setdefault(c, []).append(
                        s.astype(object).where(s.notna(), None).to_numpy()
                    )
                elif k == TIME:
                    dt = pd.to_datetime(s, errors="coerce", format="mixed", utc=True)
                    dt = dt.dt.tz_localize(None)
                    vals = (
                        dt.astype("datetime64[ms]").astype("int64").to_numpy()
                        .astype(np.float64)
                    )
                    vals = np.where(dt.isna().to_numpy(), np.nan, vals)
                    num_parts.setdefault(c, []).append(vals)
                else:
                    vals = pd.to_numeric(s, errors="coerce").to_numpy(np.float64)
                    lost = int((np.isnan(vals) & s.notna().to_numpy()).sum())
                    if lost:
                        coerce_losses[c] = coerce_losses.get(c, 0) + lost
                    num_parts.setdefault(c, []).append(vals)

    assert col_order is not None, "empty parse input"
    for c, lost in coerce_losses.items():
        Log.warn(
            f"stream parse: column {c!r} (typed {kinds[c]} from the sniff) had "
            f"{lost} non-numeric value(s) in later chunks coerced to NA — "
            "pass column_types to override the sniffed type"
        )
    vecs: list[Vec] = []
    for c in col_order:
        k = kinds[c]
        if k == CAT:
            codes = np.concatenate(cat_parts[c])
            # H2O interns levels in sorted order; remap insertion-order codes
            levels_ins = list(domains[c])
            order = sorted(range(len(levels_ins)), key=lambda i: levels_ins[i])
            remap = np.empty(len(levels_ins) + 1, np.int32)
            for new_i, old_i in enumerate(order):
                remap[old_i] = new_i
            remap[-1] = -1  # NA slot
            codes = remap[codes]  # -1 indexes the NA slot
            vecs.append(
                Vec.from_numpy(codes, CAT, name=c,
                               domain=[levels_ins[i] for i in order])
            )
        elif k == STR:
            vecs.append(Vec(np.concatenate(str_parts[c]), STR, name=c))
        else:
            vals = np.concatenate(num_parts[c])
            vecs.append(Vec.from_numpy(vals, INT if k == INT else NUM, name=c))
    fr = Frame(vecs, col_order, key=destination_frame, register=True)
    Log.info(f"Stream-parsed {fr.nrow} rows x {fr.ncol} cols into {fr.key}")
    return fr


def _data_line_offsets(path: str, wanted: set[int]) -> dict[int, int]:
    """Byte offsets where the requested 0-based DATA rows start (header is
    file-line 0). One streaming block scan, O(1) memory."""
    out: dict[int, int] = {}
    if not wanted:
        return out
    remaining = set(wanted)
    line = 0  # completed newlines so far == file-line index about to start
    pos = 0
    with open(path, "rb") as f:
        while remaining:
            block = f.read(1 << 22)
            if not block:
                break
            idx = 0
            while remaining:
                j = block.find(b"\n", idx)
                if j < 0:
                    break
                # data row (line) starts right after file-line `line` ends
                if line in remaining:
                    out[line] = pos + j + 1
                    remaining.discard(line)
                line += 1
                idx = j + 1
            pos += len(block)
    return out


def _read_rank_rows(path, sep, col_order, kinds, lo: int, hi: int, n: int):
    """This rank's data rows [lo, hi) as a DataFrame.

    Fast path: byte-range + native chunk parse. Locating the range is a
    streaming byte scan of the prefix (cheap: no tokenizing, ~GB/s); only
    the rank's own slice is TOKENIZED — the expensive part. The pandas
    ``skiprows`` fallback instead re-tokenizes the whole prefix on every
    rank; it remains the behavior-defining fallback for anything outside
    the native dialect. The caller (parse_sharded) has already rejected
    quoted files, so raw-newline row addressing == record addressing here.
    """
    from h2o3_tpu import config, native_csv

    if (
        hi > lo
        and config.get_bool("H2O3_TPU_NATIVE_PARSE")
        and native_csv.available()
    ):
        try:
            offs = _data_line_offsets(path, ({lo, hi} if hi < n else {lo}))
            start = offs.get(lo)
            end = offs.get(hi, os.path.getsize(path))
            if start is not None:
                with open(path, "rb") as f:
                    f.seek(start)
                    data = f.read(end - start)
                nat_kinds = [1 if kinds[c] == CAT else 0 for c in col_order]
                got = native_csv.parse_csv_native(
                    data, col_order, nat_kinds, sep=sep, has_header=False
                )
                if got is not None and len(got) == hi - lo:
                    return got
        except Exception:  # noqa: BLE001 — ANY native trouble (truncated
            # file mid-flight, decode, ...) must degrade to pandas, not
            # crash one rank and deadlock the others at the allgather
            pass
    return pd.read_csv(
        path, sep=sep,
        skiprows=range(1, lo + 1), nrows=max(hi - lo, 0),
        header=0, names=col_order,
    )


def parse_sharded(
    setup: dict, destination_frame: str | None = None
) -> Frame:
    """Distributed ingest — the ``MultiFileParseTask`` successor proper
    (``water/parser/ParseDataset.java`` [UNVERIFIED], SURVEY §2.1): on a
    multi-process cloud EVERY process parses only ITS OWN row range of the
    source and contributes its local device shards, so no single host ever
    materializes the whole table (Higgs-1B cannot pass through one host's
    pandas). Categorical domains are interned per-rank and unified in a
    second pass (an allgather of the small per-rank level sets), mirroring
    upstream's two-pass domain unification.

    v1 scope: one plain CSV path; numeric / enum / int columns (strings are
    host-resident and would defeat the point; TIME needs exact f64 host
    copies). Runs fine on a single process too (degenerate 1-range case).
    Must execute on every rank (spmd command or replicated section).
    """
    import pickle

    import jax

    from h2o3_tpu.parallel.mesh import get_mesh, pad_to_shards, row_sharding

    paths = setup["source_frames"]
    if len(paths) != 1 or not str(paths[0]).endswith(".csv"):
        raise ValueError("sharded parse v1 handles exactly one plain .csv")
    path = str(paths[0])
    P = jax.process_count()
    r = jax.process_index()

    # row count: one streaming newline scan (O(1) memory, every rank).
    # The SAME pass detects double quotes: a quoted field could hide an
    # embedded newline, which would make this raw-newline row count (and
    # any byte-offset row addressing) disagree with pandas' record
    # semantics — silently, and potentially DIFFERENTLY per rank. v1 scope
    # is plain CSV, so refuse deterministically on every rank instead.
    newlines = 0
    quotes = 0
    last = b"\n"
    with open(path, "rb") as f:
        while True:
            block = f.read(1 << 22)
            if not block:
                break
            newlines += block.count(b"\n")
            quotes += block.count(b'"')
            last = block[-1:]
    if quotes:
        raise ValueError(
            "sharded parse v1 requires unquoted CSV (a quoted field could "
            "embed a newline, breaking row addressing); re-export without "
            "quotes or use the single-host parse"
        )
    total_lines = newlines + (0 if last == b"\n" else 1)
    n = max(total_lines - 1, 0)  # minus header

    # identical sniff on every rank (deterministic kinds)
    sep = setup.get("separator") or _sniff_sep(path)
    sample = pd.read_csv(path, sep=sep, nrows=1000)
    col_order = [str(c) for c in sample.columns]
    ctypes = setup.get("column_types") or {}
    kinds = {}
    for c in col_order:
        k = ctypes.get(c) or infer_kind(sample[c])
        k = {"numeric": NUM, "float": NUM, "double": NUM,
             "factor": CAT, "categorical": CAT}.get(k, k)
        if k in (STR, TIME):
            raise ValueError(
                f"sharded parse v1 does not support {k} column {c!r} "
                "(host-resident / needs exact f64)"
            )
        kinds[c] = k

    npad = pad_to_shards(n)
    from h2o3_tpu.parallel.mesh import get_mesh as _gm

    mesh0 = _gm()
    flat = list(mesh0.devices.flat)
    rows_per_dev = npad // len(flat)
    positions = [i for i, d in enumerate(flat) if d.process_index == r]
    assert positions == list(range(positions[0], positions[-1] + 1)), (
        "sharded parse requires process-contiguous mesh devices"
    )
    per = len(positions) * rows_per_dev  # this rank's row block
    lo = min(positions[0] * rows_per_dev, n)
    hi = min(positions[0] * rows_per_dev + per, n)
    from h2o3_tpu import config as _cfg

    k_ranges = max(_cfg.get_int("H2O3_TPU_INGEST_SHARDS"), 0)
    if P == 1 and k_ranges > 1 and hi > lo:
        # coordinator-free single-process sharded lane (the pod ingest's
        # test/A-B form): split THIS range into k byte ranges, parse each
        # independently through the same byte-range reader a pod rank uses,
        # and concatenate — pinned byte-equal to the one-range parse
        bounds = [lo + (hi - lo) * j // k_ranges for j in range(k_ranges + 1)]
        parts = [
            _read_rank_rows(path, sep, col_order, kinds, a, b, n)
            for a, b in zip(bounds, bounds[1:]) if b > a
        ]
        local = pd.concat(parts, ignore_index=True)
    else:
        local = _read_rank_rows(path, sep, col_order, kinds, lo, hi, n)

    # per-rank categorical interning, then the global union pass
    local_domains: dict[str, list] = {}
    local_codes: dict[str, np.ndarray] = {}
    for c in col_order:
        if kinds[c] == CAT:
            codes, levels = pd.factorize(
                local[c].astype(str).where(local[c].notna(), None)
            )
            local_domains[c] = [str(v) for v in levels]
            local_codes[c] = codes.astype(np.int32)

    if P > 1:
        from jax.experimental import multihost_utils as mh

        raw = pickle.dumps(local_domains)
        cap = 1 << 20
        if len(raw) > cap:
            raise ValueError("sharded parse: categorical domains exceed 1MB")
        buf = np.zeros(cap + 4, np.uint8)
        buf[:4] = np.frombuffer(np.int32(len(raw)).tobytes(), np.uint8)
        buf[4 : 4 + len(raw)] = np.frombuffer(raw, np.uint8)
        gathered = np.asarray(mh.process_allgather(buf))
        all_domains = []
        for row in gathered:
            ln = int(np.frombuffer(row[:4].tobytes(), np.int32)[0])
            all_domains.append(pickle.loads(row[4 : 4 + ln].tobytes()))
    else:
        all_domains = [local_domains]

    union: dict[str, list] = {}
    for doms in all_domains:  # rank order → deterministic union on all ranks
        for c, levels in doms.items():
            seen = union.setdefault(c, [])
            have = set(seen)
            seen.extend(lv for lv in levels if lv not in have)
    for c in union:
        union[c] = sorted(union[c])  # H2O interns levels sorted

    mesh = mesh0
    sh = row_sharding(mesh)
    local_devs = [flat[i] for i in positions]
    dev_rows = rows_per_dev

    def _global_from_local(block: np.ndarray, dtype):
        block = np.asarray(block, dtype)
        parts = [
            jax.device_put(block[i * dev_rows : (i + 1) * dev_rows], d)
            for i, d in enumerate(local_devs)
        ]
        return jax.make_array_from_single_device_arrays((npad,), sh, parts)

    from h2o3_tpu.frame import chunkstore as _cs

    # ChunkStore lane: on a single process the local block IS the whole
    # padded column, so an out-of-core config (HBM window set) adopts it as
    # the spill-tier host mirror — a streaming build's host_values() then
    # costs nothing instead of a device pull per column. Multi-process
    # ranks hold only their slice; mirrors stay lazy there (documented).
    seed_mirror = P == 1 and _cs.streaming_enabled()
    vecs: list[Vec] = []
    for c in col_order:
        k = kinds[c]
        if k == CAT:
            lut = {lv: i for i, lv in enumerate(union[c])}
            # same narrowest-dtype rule as Vec.from_numpy so single- and
            # multi-process clouds store identical dtypes for the same data
            card = len(union[c])
            dt = np.int8 if card <= 127 else np.int16 if card <= 32767 else np.int32
            remap = np.array([lut[lv] for lv in local_domains[c]] or [0], dt)
            codes = np.full(per, -1, dt)
            lc = local_codes[c]
            codes[: len(lc)] = np.where(lc >= 0, remap[np.clip(lc, 0, None)], -1)
            data = _global_from_local(codes, dt)
            v = Vec(data, CAT, name=c, domain=tuple(union[c]), nrow=n)
            if seed_mirror:
                v._seed_host_mirror(codes)
            vecs.append(v)
        else:
            vals = np.full(per, np.nan, np.float32)
            got = pd.to_numeric(local[c], errors="coerce").to_numpy(np.float32)
            vals[: len(got)] = got
            data = _global_from_local(vals, np.float32)
            v = Vec(data, INT if k == INT else NUM, name=c, nrow=n)
            if seed_mirror:
                v._seed_host_mirror(vals)
            vecs.append(v)

    fr = Frame(vecs, col_order, key=destination_frame, register=True)
    Log.info(
        f"Shard-parsed {fr.nrow} rows x {fr.ncol} cols into {fr.key} "
        f"(rank {r}/{P} read rows [{lo}, {hi}))"
    )
    return fr


def parse(setup: dict, destination_frame: str | None = None) -> Frame:
    """Materialize a frame from a setup dict — the ``POST /3/Parse`` successor.

    Large CSV sources (or ``setup["stream"]=True``) take the chunked
    streaming path; everything else reads eagerly.
    """
    paths = setup["source_frames"]
    want_stream = bool(setup.get("stream"))
    if not want_stream and all(_is_csv_like(p) for p in paths):
        from h2o3_tpu import config

        try:
            total = sum(os.path.getsize(p) for p in paths)
            want_stream = total > config.get_int("H2O3_TPU_STREAM_BYTES")
        except OSError:
            pass
    if want_stream and all(_is_csv_like(p) for p in paths):
        return parse_stream(
            paths, setup.get("column_types") or {},
            sep=setup.get("separator"), destination_frame=destination_frame,
        )
    dfs = [_read_any(p, sep=setup.get("separator")) for p in paths]
    df = pd.concat(dfs, ignore_index=True) if len(dfs) > 1 else dfs[0]
    fr = Frame.from_pandas(
        df,
        destination_frame=destination_frame,
        column_types=setup.get("column_types"),
        register=True,
    )
    Log.info(f"Parsed {fr.nrow} rows x {fr.ncol} cols into {fr.key}")
    return fr


def import_file(
    path: str,
    destination_frame: str | None = None,
    col_types: Mapping[str, str] | None = None,
    sep: str | None = None,
    lazy: bool = False,
) -> Frame:
    """``h2o.import_file`` successor: sniff + parse in one call.

    ``lazy=True`` defers each column's device materialization to first
    touch (the FileVec successor — see frame/lazy.py).
    """
    if lazy:
        from h2o3_tpu.frame.lazy import import_file_lazy

        return import_file_lazy(
            path, destination_frame=destination_frame, col_types=col_types,
            sep=sep,
        )
    setup = parse_setup(path, sep=sep)
    if col_types:
        setup["column_types"].update(col_types)
    return parse(setup, destination_frame=destination_frame)


def upload_file(
    data: "str | pd.DataFrame | Mapping[str, Sequence]",
    destination_frame: str | None = None,
    col_types: Mapping[str, str] | None = None,
) -> Frame:
    """``h2o.upload_file`` successor; also accepts in-memory tabular data
    (the ``h2o.H2OFrame(python_obj)`` path)."""
    if isinstance(data, str):
        return import_file(data, destination_frame, col_types)
    df = data if isinstance(data, pd.DataFrame) else pd.DataFrame(data)
    return Frame.from_pandas(df, destination_frame, col_types or {}, register=True)
