"""Pallas split-scan kernel — the second half of the fused histogram→split
tree pipeline (``H2O3_TPU_SPLIT_FUSE``).

STATUS: interpret-only. Mosaic refuses this kernel body (jax 0.9.0 / libtpu
0.0.34, TPU v5e: ``Unimplemented primitive in Pallas TPU lowering for
KernelType.TC: cumsum``, then ``Only 2D gather is supported``), so no
default selects it (``shared_tree._split_fuse_on``) and it runs only under
``H2O3_TPU_SPLIT_FUSE=1`` in the Pallas interpreter — the CPU parity lane.
Compiling it needs 2-D lane/sublane operations throughout (ROADMAP A3b).

The unfused pipeline materializes the full (C, N·B, S) histogram in HBM
(via two unscramble transpose passes over the Pallas kernel's scrambled
output), then the XLA split scan streams the whole tensor back. The r5
trace puts ~66% of device time in the histogram phase and ~18% in the split
scan — most of it HBM bandwidth, not math. This kernel closes the loop:

- input is the histogram kernel's NATIVE blocked layout
  (``hist_pallas.HistLayout``): grid step (i_ct, i_nt) reads exactly the
  (NT·S, CT·Bpad) tile the histogram kernel emitted for that (column tile,
  node tile) — one VMEM-resident pass, no relayout in HBM;
- per (node, col) it runs DTree.findBestSplitPoint's numeric branch —
  bin prefix sums, NA-direction both ways, min_rows feasibility, gain vs
  the caller-passed GLOBAL node totals — and reduces over bins in VMEM;
- only the per-(node, col) winner candidates (gain, bin, NA dir, folded
  child stats) ever reach HBM: O(N·C) scalars instead of O(N·C·B·S).

The arithmetic mirrors ``shared_tree._split_scan``'s numeric branch
operation-for-operation (same ``fit``, same gain/feasibility masks, same
lowest-index argmax), so on the adversarial tie suites — where every sum is
exact in f32 — the fused pipeline's split decisions are bit-identical to
the unfused scan's (pinned by tests/test_split_pallas.py); elsewhere they
agree to the f64 accuracy bound of the histogram kernel.

Categorical columns keep the mean-sorted XLA branch (argsorts are not a
Pallas-friendly shape): :func:`fused_split_scan` gathers ONLY the
categorical columns' tiles into a small dense (N, Cc, B, S) tensor and runs
the existing formulas there — per-column routing, numeric stays on the
kernel. Monotone constraints (ISSUE 15) thread INTO the kernel grid step:
the per-bin feasibility mask — bound-clamped child Newton values must not
violate the column's direction — is mirrored op-for-op from
``_split_scan``'s ``mono`` branch (a per-column ``mono`` lane and per-node
``node_lo``/``node_hi`` bounds are extra kernel inputs), and the winner's
``mid``/``mono_col`` bound-propagation outputs are derived from the folded
child stats exactly as the unfused scan derives them. The unconstrained
kernel trace is untouched (the mono variant is a separate kernel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from h2o3_tpu.ops.hist_pallas import (
    HistLayout,
    blocked_cols_dense,
    blocked_node_totals,
)

_NEG = -1e30  # must match shared_tree._NEG (same sentinel, same compares)


def _fit(s):
    """SE with the cancelling wy² term dropped — byte-for-byte the formula
    of ``shared_tree._split_scan``'s ``fit`` (parity depends on it)."""
    w = s[..., 0]
    return -jnp.where(w > 0, s[..., 1] ** 2 / jnp.maximum(w, 1e-30), 0.0)


def _split_kernel(
    blk_ref, tot_ref, mr_ref, gain_ref, t_ref, nal_ref, lst_ref, rst_ref,
    *, nt, ct, bpad, ns, n_bins, mono_ref=None, lo_ref=None, hi_ref=None,
):
    # one histogram tile, exactly as hist_pallas emitted it:
    # rows = node·S + stat, lanes = bin·CT + col
    h = blk_ref[0].reshape(nt, ns, bpad, ct)
    hh = jnp.transpose(h, (0, 3, 2, 1))  # (nt, ct, bpad, ns)
    na = hh[:, :, 0, :]  # (nt, ct, ns)
    data = hh[:, :, 1:, :]  # (nt, ct, bpad-1, ns)
    tot = tot_ref[...]  # (nt, ns) — GLOBAL column-0 node totals
    mr = mr_ref[0, 0]

    parent_fit = _fit(tot)  # (nt,)

    def gain_with_na(L, R):
        gl = _fit(L)
        gr = _fit(R)
        ok = (L[..., 0] >= mr) & (R[..., 0] >= mr)
        g = parent_fit[:, None, None] - gl - gr
        return jnp.where(ok, g, _NEG)

    cum = jnp.cumsum(data, axis=2)  # (nt, ct, bpad-1, ns)
    tot_nonna = cum[:, :, -1:, :]
    left = cum[:, :, :-1, :]  # split after data-bin t: left = bins 1..t+1
    right = tot_nonna - left

    g_nal = gain_with_na(left + na[:, :, None, :], right)
    g_nar = gain_with_na(left, right + na[:, :, None, :])
    if mono_ref is not None:
        # monotone feasibility, the same ops as _split_scan's mono branch:
        # bound-clamped child Newton values must not violate the direction
        mono = mono_ref[0].astype(jnp.int32)  # (ct,) this tile's columns
        lo = lo_ref[:, 0]  # (nt,) this tile's node bounds
        hi = hi_ref[:, 0]

        def child_val(s):  # wy/wh clamped to the node's [lo, hi]
            v = jnp.where(
                s[..., 2] > 0, s[..., 1] / jnp.maximum(s[..., 2], 1e-30), 0.0
            )
            return jnp.clip(v, lo[:, None, None], hi[:, None, None])

        m = mono[None, :, None]
        na_b = na[:, :, None, :]
        ok_nl = (m == 0) | (
            m * (child_val(right) - child_val(left + na_b)) >= 0)
        ok_nr = (m == 0) | (
            m * (child_val(right + na_b) - child_val(left)) >= 0)
        g_nal = jnp.where(ok_nl, g_nal, _NEG)
        g_nar = jnp.where(ok_nr, g_nar, _NEG)
    # candidates past the REAL bin range (bpad tile padding) must not exist:
    # with min_rows == 0 an all-left "split" on a pad slot would otherwise
    # become feasible, which the dense scan never even enumerates
    valid_t = (
        jax.lax.broadcasted_iota(jnp.int32, g_nal.shape, 2) < n_bins - 2
    )
    g_nal = jnp.where(valid_t, g_nal, _NEG)
    g_nar = jnp.where(valid_t, g_nar, _NEG)

    g = jnp.maximum(g_nal, g_nar)
    tbest = jnp.argmax(g, axis=2)  # (nt, ct) — lowest index on ties
    take = lambda a: jnp.take_along_axis(a, tbest[:, :, None], 2).squeeze(2)
    best_gain = take(g)
    nal = take(g_nal) >= take(g_nar)
    take3 = lambda a: jnp.take_along_axis(
        a, tbest[:, :, None, None], 2
    ).squeeze(2)  # (nt, ct, ns)
    Lraw, Rraw = take3(left), take3(right)
    Lst = Lraw + jnp.where(nal[:, :, None], na, 0.0)
    Rst = Rraw + jnp.where(~nal[:, :, None], na, 0.0)

    gain_ref[0] = best_gain
    t_ref[0] = tbest.astype(jnp.int32)
    nal_ref[0] = nal.astype(jnp.int32)
    # child stats ship in the layout's row convention: rows = node·S + stat
    lst_ref[0] = jnp.transpose(Lst, (0, 2, 1)).reshape(nt * ns, ct)
    rst_ref[0] = jnp.transpose(Rst, (0, 2, 1)).reshape(nt * ns, ct)


def _split_kernel_mono(
    blk_ref, tot_ref, mr_ref, mono_ref, lo_ref, hi_ref,
    gain_ref, t_ref, nal_ref, lst_ref, rst_ref,
    *, nt, ct, bpad, ns, n_bins,
):
    """Monotone-constrained grid step: the same kernel with the per-column
    direction lane and per-node bound inputs threaded through (the
    unconstrained trace above stays byte-identical — separate kernel)."""
    _split_kernel(
        blk_ref, tot_ref, mr_ref, gain_ref, t_ref, nal_ref, lst_ref, rst_ref,
        nt=nt, ct=ct, bpad=bpad, ns=ns, n_bins=n_bins,
        mono_ref=mono_ref, lo_ref=lo_ref, hi_ref=hi_ref,
    )


@functools.partial(
    jax.jit, static_argnames=("layout", "interpret")
)
def split_candidates(
    blk, node_totals, min_rows, layout: HistLayout, interpret: bool = False,
    mono=None, node_lo=None, node_hi=None,
):
    """Per-(node, col) numeric split candidates from a blocked histogram.

    Returns ``(gain, tbest, na_left, Lst, Rst)`` with shapes
    (N, cpad), (N, cpad) i32, (N, cpad) bool, (N, cpad, S), (N, cpad, S) —
    tiny next to the histogram. ``node_totals`` is (n_nodes, S): the GLOBAL
    column-0 totals every block's gains are computed against (the sharded
    merge's bit-exactness contract, see shared_tree._split_scan_sharded).

    ``mono`` ((cpad,) int {-1,0,1}) + ``node_lo``/``node_hi`` ((n_nodes,))
    select the monotone-constrained kernel variant: infeasible candidates
    are masked to ``_NEG`` inside the grid step, exactly as the unfused
    scan masks them before its argmax.
    """
    L = layout
    nt, ct, bpad, ns = L.nt, L.ct, L.bpad, L.ns
    tot = node_totals.astype(jnp.float32)
    if L.nn > L.n_nodes:  # pad nodes: zero totals, zero hists — never win
        tot = jnp.pad(tot, ((0, L.nn - L.n_nodes), (0, 0)))
    mr = jnp.asarray(min_rows, jnp.float32).reshape(1, 1)

    specs = [
        pl.BlockSpec(
            (1, nt * ns, ct * bpad),
            lambda ct_, nt_: (ct_, nt_, 0),
            memory_space=pltpu.VMEM,
        ),
        pl.BlockSpec(
            (nt, ns), lambda ct_, nt_: (nt_, 0), memory_space=pltpu.VMEM
        ),
        pl.BlockSpec(
            (1, 1), lambda ct_, nt_: (0, 0), memory_space=pltpu.VMEM
        ),
    ]
    args = [blk, tot, mr]
    if mono is not None:
        kernel = functools.partial(
            _split_kernel_mono, nt=nt, ct=ct, bpad=bpad, ns=ns,
            n_bins=L.n_bins,
        )
        mono_t = mono.astype(jnp.int32).reshape(L.n_ct, ct)
        # pad-node bounds are inert: their histograms are all zero, so no
        # candidate there is ever feasible regardless of the bound values
        lo = node_lo.astype(jnp.float32)
        hi = node_hi.astype(jnp.float32)
        if L.nn > L.n_nodes:
            lo = jnp.pad(lo, (0, L.nn - L.n_nodes),
                         constant_values=-jnp.inf)
            hi = jnp.pad(hi, (0, L.nn - L.n_nodes), constant_values=jnp.inf)
        specs += [
            pl.BlockSpec(
                (1, ct), lambda ct_, nt_: (ct_, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (nt, 1), lambda ct_, nt_: (nt_, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (nt, 1), lambda ct_, nt_: (nt_, 0), memory_space=pltpu.VMEM
            ),
        ]
        args += [mono_t, lo.reshape(L.nn, 1), hi.reshape(L.nn, 1)]
    else:
        kernel = functools.partial(
            _split_kernel, nt=nt, ct=ct, bpad=bpad, ns=ns, n_bins=L.n_bins
        )
    scalar_spec = lambda: pl.BlockSpec(
        (1, nt, ct), lambda ct_, nt_: (ct_, nt_, 0), memory_space=pltpu.VMEM
    )
    stat_spec = lambda: pl.BlockSpec(
        (1, nt * ns, ct), lambda ct_, nt_: (ct_, nt_, 0),
        memory_space=pltpu.VMEM,
    )
    gain, tbest, nal, lst, rst = pl.pallas_call(
        kernel,
        grid=(L.n_ct, L.n_nt),
        in_specs=specs,
        out_specs=[
            scalar_spec(), scalar_spec(), scalar_spec(),
            stat_spec(), stat_spec(),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L.n_ct, L.nn, ct), jnp.float32),
            jax.ShapeDtypeStruct((L.n_ct, L.nn, ct), jnp.int32),
            jax.ShapeDtypeStruct((L.n_ct, L.nn, ct), jnp.int32),
            jax.ShapeDtypeStruct((L.n_ct, L.nn * ns, ct), jnp.float32),
            jax.ShapeDtypeStruct((L.n_ct, L.nn * ns, ct), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            # the whole blocked histogram streams through VMEM exactly once;
            # outputs are O(N·C) and negligible next to it
            flops=int(10 * L.nn * L.cpad * bpad * ns),
            bytes_accessed=int(4 * L.n_ct * L.nn * ns * ct * bpad),
            transcendentals=0,
        ),
        interpret=interpret,
    )(*args)

    N, Cp = L.n_nodes, L.cpad
    to_nc = lambda a: jnp.transpose(a, (1, 0, 2)).reshape(L.nn, Cp)[:N]
    lst = jnp.transpose(
        lst.reshape(L.n_ct, L.nn, ns, ct), (1, 0, 3, 2)
    ).reshape(L.nn, Cp, ns)[:N]
    rst = jnp.transpose(
        rst.reshape(L.n_ct, L.nn, ns, ct), (1, 0, 3, 2)
    ).reshape(L.nn, Cp, ns)[:N]
    return (
        to_nc(gain), to_nc(tbest), to_nc(nal).astype(bool), lst, rst
    )


def fused_split_scan(
    blk, layout: HistLayout, is_cat, col_mask, min_rows,
    min_split_improvement, cat_cols=(), node_totals=None,
    interpret: bool | None = None, mono=None, node_lo=None, node_hi=None,
):
    """Best split per node from a BLOCKED histogram — the drop-in fused
    replacement for ``shared_tree._split_scan`` (same return dict, same
    tie-breaking, no dense histogram ever assembled for numeric columns).

    ``is_cat``/``col_mask`` arrive at the REAL column count and are padded
    to the layout's ``cpad`` here (pad columns mask to gain ``_NEG``, so
    the column argmax resolves exactly as the dense scan's over C columns).
    ``cat_cols`` (static GLOBAL column indices) routes those columns to the
    mean-sorted fallback branch on a small dense gather; ``node_totals``
    overrides the column-0 totals exactly as in ``_split_scan``.

    ``mono`` ((C,) int {-1,0,1}) activates the monotone-constrained kernel
    variant with per-node ``node_lo``/``node_hi`` bounds; the result then
    carries ``mid``/``mono_col`` for child-bound propagation, mirroring the
    unfused scan (categorical winners carry ``mono_col`` 0 — the cat branch
    is unconstrained there too).
    """
    L = layout
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    N, Cp, B = L.n_nodes, L.cpad, L.n_bins
    C = is_cat.shape[0]
    if node_totals is None:
        node_totals = blocked_node_totals(blk, L)
    if Cp > C:
        is_cat = jnp.pad(is_cat, (0, Cp - C))
        col_mask = jnp.pad(col_mask, ((0, 0), (0, Cp - C)))
        if mono is not None:  # pad columns are unconstrained (and masked)
            mono = jnp.pad(mono, (0, Cp - C))

    num_best_gain, num_best_t, num_na_left, Lst_n, Rst_n = split_candidates(
        blk, node_totals, min_rows, layout=L, interpret=interpret,
        mono=mono, node_lo=node_lo, node_hi=node_hi,
    )

    if cat_cols:
        # ---- categorical fallback: mean-sorted prefix split on the cat
        # column subset only, gathered dense (O(N·Cc·B·S)) — formulas are
        # the same lines as _split_scan's categorical branch ----
        hist_c = blocked_cols_dense(blk, L, tuple(cat_cols))  # (N, Cc, B, S)
        cat_idx = jnp.asarray(np.asarray(cat_cols, np.int32))
        Cc = len(cat_cols)
        na_c = hist_c[:, :, 0, :]
        data_c = hist_c[:, :, 1:, :]
        parent_fit = _fit(node_totals[:, None, :]).squeeze(1)

        def gain_with_na(Lh, Rh):
            gl = _fit(Lh)
            gr = _fit(Rh)
            ok = (Lh[..., 0] >= min_rows) & (Rh[..., 0] >= min_rows)
            g = parent_fit[:, None, None] - gl - gr
            return jnp.where(ok, g, _NEG)

        w_bins = data_c[..., 0]
        mean = jnp.where(
            w_bins > 0, data_c[..., 1] / jnp.maximum(w_bins, 1e-30), jnp.inf
        )
        order = jnp.argsort(mean, axis=2)  # (N, Cc, B-1) empty (inf) last
        sdata = jnp.take_along_axis(data_c, order[..., None], axis=2)
        scum = jnp.cumsum(sdata, axis=2)
        s_tot = scum[:, :, -1:, :]
        s_left = scum[:, :, :-1, :]
        s_right = s_tot - s_left
        gc_naleft = gain_with_na(s_left + na_c[:, :, None, :], s_right)
        gc_naright = gain_with_na(s_left, s_right + na_c[:, :, None, :])
        g_cat = jnp.maximum(gc_naleft, gc_naright)
        cat_best_k = jnp.argmax(g_cat, axis=2)  # (N, Cc)
        cat_best_gain_c = jnp.take_along_axis(
            g_cat, cat_best_k[:, :, None], 2
        ).squeeze(2)
        cat_na_left_c = (
            jnp.take_along_axis(gc_naleft, cat_best_k[:, :, None], 2).squeeze(2)
            >= jnp.take_along_axis(gc_naright, cat_best_k[:, :, None], 2).squeeze(2)
        )
        cat_best_gain = jnp.full((N, Cp), _NEG, jnp.float32).at[
            :, cat_idx
        ].set(cat_best_gain_c)
        col_gain = jnp.where(is_cat[None, :], cat_best_gain, num_best_gain)
    else:
        col_gain = num_best_gain

    # ---- choose best column per node (identical argmax to _split_scan:
    # pad columns are col_mask 0 → _NEG; the all-_NEG argmax is 0 in both
    # the C-wide and the Cp-wide matrix) ----
    col_gain = jnp.where(col_mask > 0, col_gain, _NEG)
    best_col = jnp.argmax(col_gain, axis=1)  # (N,)
    best_gain = jnp.take_along_axis(col_gain, best_col[:, None], 1).squeeze(1)

    take = lambda a: jnp.take_along_axis(a, best_col[:, None], 1).squeeze(1)
    bc_t = take(num_best_t)
    split_bin = bc_t + 1

    take_s = lambda a: jnp.take_along_axis(
        a, best_col[:, None, None], 1
    ).squeeze(1)  # (N, S)
    Lst = take_s(Lst_n)
    Rst = take_s(Rst_n)

    if cat_cols:
        pos_of_col = np.zeros(Cp, np.int32)
        pos_of_col[list(cat_cols)] = np.arange(Cc, dtype=np.int32)
        bc_is_cat = is_cat[best_col]
        best_pos = jnp.asarray(pos_of_col)[best_col]  # (N,)
        take_c = lambda a: jnp.take_along_axis(a, best_pos[:, None], 1).squeeze(1)
        bc_k = take_c(cat_best_k)
        bc_na_left = jnp.where(
            bc_is_cat, take_c(cat_na_left_c), take(num_na_left)
        )
        ranks = jnp.argsort(order, axis=2)  # (N, Cc, B-1)
        idx = jnp.broadcast_to(best_pos[:, None, None], (N, 1, ranks.shape[2]))
        best_ranks = jnp.take_along_axis(ranks, idx, axis=1).squeeze(1)
        cat_left = best_ranks <= bc_k[:, None]
        cat_mask = jnp.concatenate([bc_na_left[:, None], cat_left], axis=1)
        cat_mask = jnp.where(bc_is_cat[:, None], cat_mask, False)
        gidx_c = best_pos[:, None, None, None]
        gcat = lambda arr: jnp.take_along_axis(
            jnp.take_along_axis(arr, gidx_c, 1).squeeze(1),
            bc_k[:, None, None], 1,
        ).squeeze(1)
        na_best = jnp.take_along_axis(na_c, best_pos[:, None, None], 1).squeeze(1)
        nl = bc_na_left[:, None]
        Lst_c = gcat(s_left) + jnp.where(nl, na_best, 0.0)
        Rst_c = gcat(s_right) + jnp.where(~nl, na_best, 0.0)
        Lst = jnp.where(bc_is_cat[:, None], Lst_c, Lst)
        Rst = jnp.where(bc_is_cat[:, None], Rst_c, Rst)
    else:
        bc_is_cat = jnp.zeros(N, bool)
        bc_na_left = take(num_na_left)
        cat_mask = jnp.zeros((N, B), bool)

    out = {
        "Lst": Lst,
        "Rst": Rst,
        "gain": best_gain,
        "ok": best_gain >= min_split_improvement,
        "col": best_col,
        "is_cat": bc_is_cat,
        "split_bin": split_bin,
        "na_left": bc_na_left,
        "cat_mask": cat_mask,
        "node_w": node_totals[:, 0],
        "node_wy": node_totals[:, 1],
        "node_wh": node_totals[:, 2],
    }
    if mono is not None:
        # chosen split's clamped child values -> mid for bound propagation;
        # same formulas as _split_scan's tail (categorical winners carry
        # mono_col 0, so their mid is never consumed)
        vL = jnp.clip(
            jnp.where(Lst[:, 2] > 0,
                      Lst[:, 1] / jnp.maximum(Lst[:, 2], 1e-30), 0.0),
            node_lo, node_hi,
        )
        vR = jnp.clip(
            jnp.where(Rst[:, 2] > 0,
                      Rst[:, 1] / jnp.maximum(Rst[:, 2], 1e-30), 0.0),
            node_lo, node_hi,
        )
        out["mid"] = 0.5 * (vL + vR)
        out["mono_col"] = jnp.where(bc_is_cat, 0, mono[best_col])
    return out
