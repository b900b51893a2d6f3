"""DeepLearning — successor of ``hex.deeplearning.DeepLearning`` /
``DeepLearningModel`` / ``Neurons`` [UNVERIFIED upstream paths, SURVEY.md
§2.2].

H2O trains a fully-connected MLP with **Hogwild!** lock-free async SGD
within a node plus periodic cross-node model averaging. The north star
(BASELINE.json) explicitly licenses replacing that with synchronous
data-parallel SGD: here each epoch is ONE compiled ``lax.scan`` over
minibatches of the row-sharded design matrix — flax MLP forward/backward on
the MXU in float32 (HIGHEST), ADADELTA (h2o's adaptive_rate default) from
optax or H2O's manual-rate SGD (``_h2o_sgd``: rate annealing, per-layer rate
decay, a momentum ramp, Nesterov); on one TPU chip a classifier's
manual-rate step with no dropout is one Pallas kernel
(``ops/dl_step_pallas.py``, :func:`_fused_schedule`). Parameter parity:
hidden/activation (+dropout variants), input_dropout, l1/l2, adaptive-rate rho/epsilon,
rate/rate_annealing/rate_decay, momentum_start/ramp/stable,
nesterov_accelerated_gradient, standardize, early stopping. Scoring is a
blocked forward in one program (``_pred_program``). Deviation noted:
``mini_batch_size`` defaults to 32 (h2o's online default of 1 serializes the
MXU for no accuracy gain).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

from h2o3_tpu.cluster.job import Job
from h2o3_tpu.cluster.registry import DKV
from h2o3_tpu.frame.frame import Frame, Vec
from h2o3_tpu.models.datainfo import DataInfo
from h2o3_tpu.models.model_base import (
    CommonParams,
    Model,
    ModelBuilder,
    ScoreKeeper,
)
from h2o3_tpu.utils import faults
from h2o3_tpu.utils import metrics as _mx
from h2o3_tpu.utils.log import Log

_DL_EPOCHS = _mx.counter("dl_epochs_total", "DeepLearning epochs executed")
_DL_STEPS = _mx.counter(
    "dl_minibatch_steps_total",
    "DeepLearning minibatch updates executed (counted at dispatch: "
    "minibatches an epoch x the dispatch's epochs)")
_DL_FUSED_STEPS = _mx.counter(
    "dl_fused_steps_total",
    "DeepLearning minibatch updates dispatched through the one-kernel step "
    "(ops/dl_step_pallas.py), counted at dispatch as dl_minibatch_steps_total "
    "is")
_DL_EPOCH_SECONDS = _mx.histogram(
    "dl_epoch_seconds", "per-epoch wall time of the sync-SGD driver")
# host dispatches issued by the epoch driver (the epoch-chunk acceptance
# metric: O(epochs) per-epoch vs O(epochs/K) chunked) and program-cache
# traffic for the chunk programs — BUILD_STATS-style contract counters
_DL_DISPATCHES = _mx.counter(
    "dl_dispatches_total",
    "device-program launches issued by the DeepLearning epoch driver",
    always=True)
_DL_COMPILED = _mx.counter(
    "dl_programs_compiled_total",
    "DeepLearning epoch-chunk program cache misses", always=True)
_DL_HITS = _mx.counter(
    "dl_program_cache_hits_total",
    "DeepLearning epoch-chunk program cache hits (same shape bucket, no "
    "recompile)", always=True)
# the PR-5 collective byte family grows DL phases (dl_grad_reduce = the
# per-minibatch gradient psum_scatter — or the replicated allreduce volume
# on the unsharded lane — dl_param_gather = the all_gather of updated
# parameter shards); replication-volume model, tallied per dispatch
_COLL_BYTES = _mx.counter(
    "tree_collective_bytes_total",
    "per-device collective payload bytes moved by tree builds (replication-"
    "volume model), by phase", always=True)

# Fallback observability (ISSUE 15): trainings that WANT the sharded
# gradient lane (knob on, >1-device mesh) but drop to the replicated
# reduce for a structural reason. Dropout is no longer one — the dropout
# key folds the shard index per device (see _dl_chunk_program) — leaving
# batch divisibility and non-elementwise optimizer state.
_DL_SHARD_FALLBACKS = _mx.counter(
    "dl_shard_fallbacks_total",
    "DeepLearning trainings that fell back from the sharded-gradient lane "
    "while the knob was on and the mesh had >1 device, by structural "
    "reason", always=True)

# epoch-chunk program cache: (shape bucket, net/optimizer descriptor,
# lanes, mesh, backend) -> compiled chunk
_DL_PROGRAMS: dict = {}
# minibatch steps each probe of DeepLearningModel.sgd_probe replays: the
# training's first steps and the last ones of its last epoch
PROBE_STEPS = 256
# rows a block of the device predictor's forward (_pred_program)
PRED_BLOCK = 1 << 16


def _dl_epoch_chunk(p) -> int:
    """Epochs folded into one compiled dispatch (H2O3_TPU_DL_EPOCH_CHUNK).

    Clamped to 1 whenever per-epoch boundaries are load-bearing: interval
    checkpoints (export_checkpoints_dir — PR-2 snapshots must land at every
    epoch), epoch-loss early stopping (stopping_rounds), or armed fault
    injection (the chaos suite aborts at exact epoch counts)."""
    from h2o3_tpu import config

    raw = config.get("H2O3_TPU_DL_EPOCH_CHUNK").strip().lower()
    k = int(raw) if raw.isdigit() else 8
    if (getattr(p, "export_checkpoints_dir", None)
            or (p.stopping_rounds or 0) > 0 or faults.armed()):
        return 1
    return max(k, 1)


def _flat_state_ok(opt_state, params) -> bool:
    """True iff every optimizer-state field is parameter-shaped (one array
    per param leaf, elementwise semantics) — the eligibility gate for the
    sharded-gradient lane, which runs the optimizer on 1/P slices of the
    FLATTENED parameter vector. Adadelta qualifies; a schedule's scalar
    step counter does not (its update is not elementwise in the flat
    view)."""
    pl = jax.tree.leaves(params)
    sl = jax.tree.leaves(opt_state)
    n = len(pl)
    if n == 0 or len(sl) % n != 0:
        return False
    return all(s.shape == pl[i % n].shape for i, s in enumerate(sl))


def _dl_grad_shard(p, dropout, input_dropout, batch: int, opt_ok: bool) -> bool:
    """Sharded minibatch gradient reduction (H2O3_TPU_DL_GRAD_SHARD):
    psum_scatter the flat gradient, update only the local parameter shard,
    all_gather the updated params — instead of the replicated
    allreduce+full-update. Eligible when the mesh has >1 device, the batch
    splits evenly over it and the optimizer state is elementwise. Dropout
    composes since ISSUE 15: each device folds its shard index into the
    minibatch dropout key (``H2O3_TPU_DL_GRAD_SHARD=ctl`` is the matching
    replicated parity-control lane — see :func:`_dl_dropout_ctl`).
    Structural ineligibility tallies ``dl_shard_fallbacks_total``."""
    from h2o3_tpu import config
    from h2o3_tpu.parallel.mesh import n_shards

    raw = config.get("H2O3_TPU_DL_GRAD_SHARD").strip().lower()
    if raw in ("0", "ctl"):
        return False
    n_sh = n_shards()
    if n_sh <= 1:
        return False
    ok = batch % n_sh == 0 and opt_ok
    if not ok:
        _DL_SHARD_FALLBACKS.inc(
            reason="batch_indivisible" if batch % n_sh else "opt_state")
    return ok


def _dl_dropout_ctl(p, dropout, input_dropout) -> int:
    """Shard count for the ``H2O3_TPU_DL_GRAD_SHARD=ctl`` parity-control
    lane: the REPLICATED trainer draws its dropout masks in n_shards
    contiguous batch chunks with the sharded lane's exact per-chunk key
    folds, so a ctl run is the trajectory-parity control for the sharded
    dropout run (same masks, replicated math). 0 = not the ctl lane or no
    dropout to control for."""
    from h2o3_tpu import config
    from h2o3_tpu.parallel.mesh import n_shards

    raw = config.get("H2O3_TPU_DL_GRAD_SHARD").strip().lower()
    if raw != "ctl":
        return 0
    if float(input_dropout) == 0.0 and all(float(d) == 0.0 for d in dropout):
        return 0  # no masks to align — plain replicated lane
    return n_shards()


def _state_to_flat(opt_state, params, tx, fpad: int):
    """Standard (params-structured) optimizer state -> the state of the
    same optimizer over the zero-padded FLAT parameter vector. Field order
    follows ``jax.tree.leaves``; padded tail entries are zero and stay zero
    (zero gradients under an elementwise transform). Inverse of
    :func:`_state_from_flat`; only called when :func:`_flat_state_ok`."""
    pl = jax.tree.leaves(params)
    n = len(pl)
    sl = jax.tree.leaves(opt_state)
    fields = []
    for i in range(0, len(sl), n):
        flat = jnp.concatenate([jnp.ravel(a) for a in sl[i:i + n]])
        fields.append(jnp.pad(flat, (0, fpad - flat.size)))
    ref = jax.tree.structure(tx.init(jnp.zeros(fpad, jnp.float32)))
    return jax.tree.unflatten(ref, fields)


def _state_from_flat(flat_state, unravel, n_real: int):
    """Flat optimizer state back to the standard params-structured form
    (what checkpoints serialize and the unsharded lane consumes)."""
    return jax.tree.map(lambda leaf: unravel(leaf[:n_real]), flat_state)


@dataclass
class DeepLearningParams(CommonParams):
    hidden: Sequence[int] = field(default_factory=lambda: (200, 200))
    epochs: float = 10.0
    activation: str = "Rectifier"
    input_dropout_ratio: float = 0.0
    hidden_dropout_ratios: Sequence[float] | None = None
    l1: float = 0.0
    l2: float = 0.0
    adaptive_rate: bool = True
    rho: float = 0.99
    epsilon: float = 1e-8
    rate: float = 0.005
    rate_annealing: float = 1e-6
    rate_decay: float = 1.0
    momentum_start: float = 0.0
    momentum_ramp: float = 1e6
    momentum_stable: float = 0.0
    nesterov_accelerated_gradient: bool = True
    mini_batch_size: int = 32
    standardize: bool = True
    loss: str = "Automatic"
    reproducible: bool = True  # sync SGD is deterministic by construction
    autoencoder: bool = False  # reconstruct inputs; y is ignored
    # feature hashing for Criteo-class cardinalities (datainfo.py)
    hash_buckets: int | None = None


class _MLP(nn.Module):
    hidden: tuple
    n_out: int
    activation: str
    dropout: tuple
    input_dropout: float

    @nn.compact
    def __call__(self, x, train: bool = False):
        act = {
            "rectifier": nn.relu,
            "rectifierwithdropout": nn.relu,
            "tanh": nn.tanh,
            "tanhwithdropout": nn.tanh,
            "maxout": nn.relu,  # maxout approximated [deviation noted]
        }[self.activation.lower()]
        if self.input_dropout > 0:
            x = nn.Dropout(self.input_dropout, deterministic=not train)(x)
        # float32 matmuls, as H2O computes: HIGHEST holds the forward and,
        # through the transpose rules, the backward products to it (the
        # TPU's default is one bfloat16 pass)
        hi = jax.lax.Precision.HIGHEST
        for i, h in enumerate(self.hidden):
            x = nn.Dense(h, precision=hi)(x)
            x = act(x)
            if self.dropout[i] > 0:
                x = nn.Dropout(self.dropout[i], deterministic=not train)(x)
        return nn.Dense(self.n_out, precision=hi)(x)




def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _fused_schedule(p, mlp, kind: str, shard_on: bool, ctl_shards: int,
                    n_in: int, batch: int):
    """H2O's SGD schedule for the one-kernel step (``ops/dl_step_pallas``),
    or None where the step stays XLA's: the kernel takes the softmax
    cross-entropy of a dense tanh or rectifier network with no dropout,
    trained by manual-rate SGD (``_h2o_sgd``) on one replicated lane, on one
    TPU chip (a Mosaic kernel is not partitioned over a mesh), whose
    parameters, momentum and activations fit its VMEM budget."""
    from h2o3_tpu.ops import dl_step_pallas as dsp
    from h2o3_tpu.parallel.mesh import n_shards

    widths = [int(n_in), *(int(h) for h in mlp.hidden), int(mlp.n_out)]
    if (kind != "ce" or p.adaptive_rate or shard_on or ctl_shards
            or mlp.activation.lower() not in ("tanh", "rectifier")
            or float(mlp.input_dropout) > 0
            or any(float(d) > 0 for d in mlp.dropout)
            or not _on_tpu() or n_shards() != 1
            or not dsp.fits(widths, batch)):
        return None
    return dsp.Schedule(
        float(p.rate), float(p.rate_annealing), float(p.rate_decay),
        float(p.momentum_start or 0.0), float(p.momentum_ramp),
        float(p.momentum_stable or 0.0),
        bool(p.nesterov_accelerated_gradient))


def _fused_slots(prm) -> list:
    """The leaf positions of each Dense layer's ``(kernel, bias)`` in
    ``jax.tree.leaves(prm)`` order, by layer."""
    slots = {}
    for j, (path, _) in enumerate(jax.tree_util.tree_flatten_with_path(prm)[0]):
        name = str(getattr(path[-1], "key", getattr(path[-1], "name", "")))
        slots[(_dense_index(path), name)] = j
    return [(slots[(i, "kernel")], slots[(i, "bias")])
            for i in range(len(slots) // 2)]


def _dl_chunk_program(desc, mlp, tx, kind: str, batch: int, npad: int,
                      n_chunk: int, shard_on: bool, unravel=None,
                      n_real: int = 0, fpad: int = 0, ctl_shards: int = 0,
                      sgd=None):
    """Build (or fetch) the compiled K-epochs-per-dispatch training chunk.

    One program runs ``n_chunk`` whole epochs: an outer fori over the
    host-precomputed shuffle permutations (stacked ``(K, npad)`` — the
    permutation RNG stays host-side so trajectories are bit-identical to
    the per-epoch path), an inner fori over minibatches with a DYNAMIC trip
    count (row-count variation inside a shape bucket never recompiles), the
    dropout RNG threading through the carry exactly as the per-epoch path
    split it. ``params``/``opt_state`` are donated — chunk d+1 reuses chunk
    d's buffers with no copies.

    On the sharded lane (``shard_on``) params/opt_state are flat
    ``(fpad,)`` vectors: each device grads its local batch rows, the flat
    gradient ends in a ``psum_scatter`` (each device keeps 1/P), the
    elementwise optimizer updates only that shard, and one ``all_gather``
    republishes the updated parameters for the next forward. With dropout
    active (ISSUE 15), each device folds its flat shard index into the
    minibatch dropout key before the forward — batch rows are contiguous
    per shard, so a replicated trainer drawing its masks in the same
    per-chunk folds reproduces the identical masks: that is the
    ``ctl_shards`` lane (``H2O3_TPU_DL_GRAD_SHARD=ctl``), the
    trajectory-parity control for the sharded dropout run.

    With ``sgd`` (H2O's schedule, from :func:`_fused_schedule`) a step is
    one launch of ``ops/dl_step_pallas``'s kernel: forward, loss, backward
    and update in VMEM, the parameters and momentum carried in the kernel's
    layout through each segment of the step loop (compiled, or interpreted
    off the TPU). It shares no step logic with the XLA step, which serves
    dropout, ADADELTA, the autoencoder and the sharded lanes.
    """
    import jax.tree_util as jtu

    from h2o3_tpu.parallel.mesh import (
        col_axis_name, get_mesh, mesh_key, n_col_shards, row_axes, row_pspec,
        shard_map,
    )
    from jax.sharding import PartitionSpec as Spec

    key = ("dl_chunk", desc, batch, npad, n_chunk, bool(shard_on),
           int(ctl_shards), sgd, PROBE_STEPS, mesh_key(), jax.default_backend())
    fn = _DL_PROGRAMS.get(key)
    if fn is not None:
        _DL_HITS.inc()
        return fn
    _DL_COMPILED.inc()

    def row_loss(prm, xb, yb, kb):
        out = mlp.apply(prm, xb, train=True, rngs={"dropout": kb})
        if kind == "ce":
            return optax.softmax_cross_entropy_with_integer_labels(
                out, yb.astype(jnp.int32)
            )
        if kind == "mse":
            return (out[:, 0] - yb) ** 2
        return jnp.mean((out - xb) ** 2, axis=1)  # recon: the input IS the target

    def penalties(prm, l1, l2):
        # written unconditionally with dynamic scalars: +0.0 when a knob is
        # zero, which leaves loss AND gradient bits identical to the old
        # `if l2:` closures while letting one program serve every (l1, l2)
        pen = l2 * 0.5 * sum(jnp.sum(q**2) for q in jax.tree.leaves(prm))
        return pen + l1 * sum(jnp.sum(jnp.abs(q)) for q in jax.tree.leaves(prm))

    def row_loss_ctl(prm, xb, yb, kb):
        """The ctl parity lane's row loss: the SAME masks as the sharded
        lane — the batch in ``ctl_shards`` contiguous chunks, chunk d's
        dropout drawn from fold_in(kb, d), vmapped (identical bits to
        per-chunk applies)."""
        D = xb.shape[1]
        xbr = xb.reshape(ctl_shards, batch // ctl_shards, D)
        ybr = yb.reshape(ctl_shards, batch // ctl_shards)
        keys = jax.vmap(lambda i: jax.random.fold_in(kb, i))(
            jnp.arange(ctl_shards, dtype=jnp.int32))
        ll = jax.vmap(row_loss, in_axes=(None, 0, 0, 0))(prm, xbr, ybr, keys)
        return ll.reshape(batch)

    def loss_fn(prm, xb, yb, wb, kb, l1, l2):
        rl = row_loss_ctl if ctl_shards > 1 else row_loss
        ll = rl(prm, xb, yb, kb)
        loss = jnp.sum(wb * ll) / jnp.maximum(jnp.sum(wb), 1e-9)
        return loss + penalties(prm, l1, l2)

    has_drop = float(mlp.input_dropout) > 0 or any(
        float(d) > 0 for d in mlp.dropout)

    if shard_on:
        mesh = get_mesh()
        n_sh = int(mesh.devices.size)
        cax = col_axis_name(mesh)
        raxes = row_axes(mesh)
        fb = fpad // n_col_shards(mesh)

        def shard_step(prm_flat, ost, xb, yb, wb, bk, l1, l2):
            def local(prm_flat, ost_l, xb_l, yb_l, wb_l, bk, l1, l2):
                # dropout composes with sharding (ISSUE 15): fold the FLAT
                # row-shard index into the minibatch key so each device
                # draws its own rows' masks — shard-major order matches
                # row_axes, so the ctl lane's per-chunk folds reproduce
                # the identical mask sequence. No-dropout nets skip the
                # fold: their traced program stays byte-identical
                if has_drop:
                    sidx = jax.lax.axis_index(raxes[0])
                    for a in raxes[1:]:
                        sidx = sidx * mesh.shape[a] + jax.lax.axis_index(a)
                    bk = jax.random.fold_in(bk, sidx)

                def wsum_loss(pf):
                    prm = unravel(pf[:n_real])
                    return jnp.sum(wb_l * row_loss(prm, xb_l, yb_l, bk))

                lsum, g = jax.value_and_grad(wsum_loss)(prm_flat)
                # the flat-gradient reduce rides the collective lane
                # (ops/collectives.py): block-quantized with a residual-
                # correction pass when on — the optimizer consumes the
                # shard directly — stock psum_scatter bit-for-bit when off.
                # On a 2-D mesh the wrapper reduces the rows axis exactly
                # first and param shards live on the COLS axis (replicated
                # across rows groups — identical updates by construction)
                from h2o3_tpu.ops import collectives

                gs = collectives.psum_scatter(
                    g, n_dev=n_sh, passes=2, mesh=mesh)
                wsum = collectives.exact_psum(jnp.sum(wb_l), mesh)
                d = jax.lax.axis_index(cax)
                my = jax.lax.dynamic_slice(prm_flat, (d * fb,), (fb,))
                gshard = (gs / jnp.maximum(wsum, 1e-9)
                          + l2 * my + l1 * jnp.sign(my))
                upd, ost_l = tx.update(gshard, ost_l, my)
                my = optax.apply_updates(my, upd)
                prm_new = jax.lax.all_gather(
                    my, cax, axis=0, tiled=True)
                loss = (collectives.exact_psum(lsum, mesh)
                        / jnp.maximum(wsum, 1e-9)
                        + penalties(prm_flat[:n_real], l1, l2))
                return loss, prm_new, ost_l

            rspec = row_pspec(mesh)
            ost_spec = jtu.tree_map(lambda _: Spec(cax), ost)
            return shard_map(
                local, mesh,
                in_specs=(Spec(), ost_spec, row_pspec(mesh, ndim=2),
                          rspec, rspec, Spec(), Spec(),
                          Spec()),
                out_specs=(Spec(), Spec(), ost_spec),
                check_vma=False,
            )(prm_flat, ost, xb, yb, wb, bk, l1, l2)

    def fused_steps(lo, hi, sc, Xp, yp, wp, l1, l2):
        """Steps ``lo..hi`` of the epoch, one kernel launch each, the
        parameters and momentum in the kernel's layout between them."""
        from h2o3_tpu.ops import dl_step_pallas as dsp

        prm, ost, k, loss_sum = sc
        leaves, treedef = jax.tree.flatten(prm)
        mom = treedef.flatten_up_to(ost.momentum)
        slots = _fused_slots(prm)
        carry = dsp.pack(ost.count, loss_sum,
                         *([src[s[c]] for s in slots]
                           for src in (leaves, mom) for c in (0, 1)))
        pen = jnp.stack([l1, l2]).reshape(1, 2)
        act = mlp.activation.lower()
        D = Xp.shape[1]

        def fstep(i, c):
            with jax.named_scope("ph_dl_step"):
                start = i * batch
                xt = jax.lax.dynamic_slice(Xp, (start, 0), (batch, D)).T
                yb = jax.lax.dynamic_slice(yp, (start,), (batch,))
                wb = jax.lax.dynamic_slice(wp, (start,), (batch,))
                return dsp.step(c, xt, yb, wb, pen, sched=sgd, act=act,
                                interpret=jax.default_backend() != "tpu")

        count, loss_sum, ws, bs, vs, vbs = dsp.unpack(
            jax.lax.fori_loop(lo, hi, fstep, carry))
        new, new_m = list(leaves), list(mom)
        for (kw, kb), w_, b_, v_, vb_ in zip(slots, ws, bs, vs, vbs):
            new[kw], new[kb], new_m[kw], new_m[kb] = w_, b_, v_, vb_
        return (treedef.unflatten(new),
                H2OSGDState(count, treedef.unflatten(new_m)), k, loss_sum)

    def chunk(params, opt_state, X, y, w, perms, key, nbatch, l1, l2,
              slot_mask, first):
        D = X.shape[1]

        def pick(cond, new, old):
            return jax.tree.map(lambda a, b: jnp.where(cond, a, b), new, old)

        def epoch_body(e, c):
            prm, ost, key, losses, early, late = c
            perm = perms[e]
            with jax.named_scope("ph_dl_perm"):
                Xp, yp, wp = X[perm], y[perm], w[perm] * slot_mask
            key, dkey = jax.random.split(key)

            def step(i, sc):
                prm, ost, k, loss_sum = sc
                with jax.named_scope("ph_dl_step"):
                    k, bk = jax.random.split(k)
                    start = i * batch
                    xb = jax.lax.dynamic_slice(Xp, (start, 0), (batch, D))
                    yb = jax.lax.dynamic_slice(yp, (start,), (batch,))
                    wb = jax.lax.dynamic_slice(wp, (start,), (batch,))
                    if shard_on:
                        loss, prm, ost = shard_step(
                            prm, ost, xb, yb, wb, bk, l1, l2)
                    else:
                        loss, g = jax.value_and_grad(loss_fn)(
                            prm, xb, yb, wb, bk, l1, l2)
                        upd, ost = tx.update(g, ost, prm)
                        prm = optax.apply_updates(prm, upd)
                return (prm, ost, k, loss_sum + loss)

            def steps(lo, hi, sc):
                if sgd is None:
                    return jax.lax.fori_loop(lo, hi, step, sc)
                return fused_steps(lo, hi, sc, Xp, yp, wp, l1, l2)

            # the probes' records (DeepLearningModel.sgd_probe): the loop is
            # cut, with no work added to a step, after the training's first
            # PROBE_STEPS steps and before the chunk's last epoch's last ones
            head = first & (e == 0)
            a = jnp.where(head, jnp.minimum(PROBE_STEPS, nbatch), 0)
            cuts = jnp.stack([0, a, jnp.maximum(nbatch - PROBE_STEPS, a), nbatch])

            def segment(j, sg):
                sc, early, late = sg
                sc = steps(cuts[j], cuts[j + 1], sc)
                early = pick(head & (j == 0), sc[0], early)
                late = pick((e == n_chunk - 1) & (j == 1), sc[:2], late)
                return sc, early, late

            sc, early, late = jax.lax.fori_loop(
                0, 3, segment,
                ((prm, ost, dkey, jnp.float32(0.0)), early, late))
            prm, ost, _, loss_sum = sc
            losses = losses.at[e].set(loss_sum / nbatch)
            return (prm, ost, key, losses, early, late)

        params, opt_state, key, losses, early, late = jax.lax.fori_loop(
            0, n_chunk, epoch_body,
            (params, opt_state, key, jnp.zeros(n_chunk, jnp.float32),
             params, (params, opt_state)))
        return params, opt_state, key, losses, early, late

    fn = jax.jit(chunk, donate_argnums=(0, 1))
    _DL_PROGRAMS[key] = fn
    return fn


def _run_sync_sgd(job, p, mlp, kind, tx, params, opt_state, X, y, w,
                  nrow: int, npad: int, key, start_epochs: int = 0,
                  on_epoch=None):
    """The shared sync-SGD epoch driver for both supervised and autoencoder
    training: permutation shuffling, epoch-chunk compiled loops
    (H2O3_TPU_DL_EPOCH_CHUNK) with donated (params, opt_state) buffers,
    epoch-loss early stopping, checkpoint RNG alignment. ``kind`` selects
    the per-row objective ('ce' | 'mse' | 'recon'). ``on_epoch(params,
    opt_state, epochs_done, history)`` fires at every chunk boundary — with
    checkpoints/faults/early-stopping active the chunk clamps to one epoch,
    so that IS every epoch boundary. Returns (params, opt_state, history,
    epochs_done, probe): ``probe`` is what ``DeepLearningModel.sgd_probe``
    reads (None where the training streamed)."""
    import time as _time

    from h2o3_tpu.frame.chunkstore import ChunkStore
    from h2o3_tpu.parallel.mesh import n_shards, pad_flat_to_shards

    if isinstance(X, ChunkStore):
        return _run_sync_sgd_streamed(
            job, p, mlp, kind, tx, params, opt_state, X, nrow, key,
            start_epochs, on_epoch,
        )

    batch = min(int(p.mini_batch_size), npad)
    nbatch = max(1, nrow // batch)
    # padded permutation slots alias row 0 — a SLOT mask zeroes their weight
    # so a final partial batch cannot over-count real rows (nrow < batch)
    slot_mask = (jnp.arange(npad) < nrow).astype(jnp.float32)
    l1, l2 = jnp.float32(p.l1), jnp.float32(p.l2)

    chunk_k = _dl_epoch_chunk(p)
    dropout = _resolved_dropout(p, len(p.hidden))
    shard_on = _dl_grad_shard(
        p, dropout, p.input_dropout_ratio, batch, _flat_state_ok(opt_state, params)
    )
    ctl = _dl_dropout_ctl(p, dropout, p.input_dropout_ratio)
    if ctl and batch % ctl:
        ctl = 0  # the sharded lane it controls for would be ineligible too
    sgd = _fused_schedule(p, mlp, kind, shard_on, ctl, X.shape[1], batch)
    n_sh = n_shards()
    # the FULL network + optimizer identity: n_out matters even at equal
    # hidden/width (a cached program's closed-over mlp bakes the output
    # head), and every optimizer hyper is baked into tx's update closure
    desc = (tuple(int(h) for h in mlp.hidden), mlp.activation.lower(),
            tuple(mlp.dropout), float(mlp.input_dropout), int(mlp.n_out),
            kind, X.shape[1], *_sgd_desc(p))

    unravel = None
    n_real = fpad = 0
    # the probes' starting point: the weights before the first step
    initial = jax.tree.map(jnp.copy, params) if start_epochs == 0 else None
    if shard_on:
        from jax.flatten_util import ravel_pytree

        flat, unravel = ravel_pytree(params)
        n_real = int(flat.size)
        fpad = pad_flat_to_shards(n_real)
        params = jnp.pad(flat, (0, fpad - n_real))
        opt_state = _state_to_flat(opt_state, unravel(flat), tx, fpad)

    # epoch-level stopping tracks the (always smaller-is-better) training
    # loss; the resolved stopping_metric drives final scoring only
    keeper = ScoreKeeper(p.stopping_rounds, p.stopping_tolerance, False)
    seed = abs(p.seed) if p.seed and p.seed > 0 else 99
    rng = np.random.default_rng(seed)
    history = []
    n_epochs = max(1, int(np.ceil(p.epochs)))
    for _ in range(start_epochs):  # continuation: keep the epoch RNG
        rng.permutation(nrow)  # stream aligned with an
        key, _ = jax.random.split(key)  # uninterrupted run
    epochs_done = start_epochs

    # modeled per-batch collective volume, per lane: sharded = the 1/P
    # gradient scatter (through the quantized collective lane when on —
    # wire bytes + residual pass — exact f32 otherwise) + the exact wsum
    # psum + the exact full param gather; unsharded = the full replicated
    # gradient reduce (XLA-inserted — the lane cannot intercept it, exact
    # by construction). Zero on a 1-device mesh.
    coll = {}
    if n_sh > 1:
        from h2o3_tpu.ops.collectives import modeled_reduce_bytes

        n_param = n_real if shard_on else sum(
            int(np.prod(q.shape)) for q in jax.tree.leaves(params))
        if shard_on:
            reduce_lanes = dict(modeled_reduce_bytes(fpad, n_sh, passes=2))
            reduce_lanes["exact"] = reduce_lanes.get("exact", 0.0) + 4.0
            coll = {"dl_grad_reduce": reduce_lanes,
                    "dl_param_gather": {"exact": fpad * 4.0}}
        else:
            coll = {"dl_grad_reduce": {"exact": n_param * 4.0}}

    e = start_epochs
    stopped = False
    early = late = None
    while e < n_epochs and not stopped:
        k_i = min(chunk_k, n_epochs - e)
        _ep_t0 = _time.perf_counter()
        perms = np.zeros((k_i, npad), np.int64)
        for j in range(k_i):
            perms[j, :nrow] = rng.permutation(nrow)
        prog = _dl_chunk_program(
            desc, mlp, tx, kind, batch, npad, k_i, shard_on,
            unravel=unravel, n_real=n_real, fpad=fpad, ctl_shards=ctl,
            sgd=sgd,
        )
        perms_dev = jnp.asarray(perms)
        first = e == 0
        _DL_DISPATCHES.inc()
        _DL_STEPS.inc(nbatch * k_i)
        if sgd is not None:
            _DL_FUSED_STEPS.inc(nbatch * k_i)
        from h2o3_tpu.utils import flightrec as _fr

        with _fr.dispatch("dl_chunk", epochs=int(k_i), rows=int(npad)):
            params, opt_state, key, losses, snap, last = prog(
                params, opt_state, X, y, w, perms_dev, key,
                jnp.int32(nbatch), l1, l2, slot_mask, jnp.bool_(first),
            )
            losses = np.asarray(losses, np.float64)  # syncs the chunk's work
        if first:
            a = min(PROBE_STEPS, nbatch)
            early = {"steps": a, "rows": perms[0, :a * batch].copy(),
                     "after": snap}
        b = max(nbatch - PROBE_STEPS, a if first and k_i == 1 else 0)
        late = {"steps": nbatch - b,
                "rows": perms[-1, b * batch:nbatch * batch].copy(),
                "before": last[0], "state": last[1]}
        _dt = _time.perf_counter() - _ep_t0
        for j in range(k_i):
            epochs_done = e + j + 1
            history.append({"epoch": epochs_done, "loss": float(losses[j])})
            _DL_EPOCHS.inc()
            _DL_EPOCH_SECONDS.observe(_dt / k_i)
            keeper.record(float(losses[j]))
        for ph, lanes in coll.items():
            for lane, nb in lanes.items():
                if nb:
                    _COLL_BYTES.inc(nb * k_i * nbatch, phase=ph)
                    _COLL_BYTES.inc(nb * k_i * nbatch, phase=ph, lane=lane)
        if on_epoch is not None:
            if shard_on:
                on_epoch(unravel(params[:n_real]),
                         _state_from_flat(opt_state, unravel, n_real),
                         epochs_done, history)
            else:
                on_epoch(params, opt_state, epochs_done, history)
        job.update(0.05 + 0.9 * epochs_done / n_epochs)
        e += k_i
        if keeper.should_stop() or job.stop_requested:
            Log.info(f"DeepLearning early stop at epoch {epochs_done}")
            stopped = True
    if shard_on:
        params = unravel(params[:n_real])
        opt_state = _state_from_flat(opt_state, unravel, n_real)
        if late is not None:
            late["before"] = unravel(late["before"][:n_real])
            late["state"] = _state_from_flat(late["state"], unravel, n_real)
        if early is not None:
            early["after"] = unravel(early["after"][:n_real])
    probe = None
    if initial is not None and early is not None:
        probe = {"batch": batch, "initial": initial, "early": early,
                 "late": late}
    return params, opt_state, history, epochs_done, probe


def _run_sync_sgd_streamed(job, p, mlp, kind, tx, params, opt_state, store,
                           nrow: int, key, start_epochs: int = 0,
                           on_epoch=None):
    """Out-of-core epoch driver (ISSUE 11): one epoch = one pass over the
    ChunkStore's row blocks, each block running the EXISTING compiled
    chunk program (one-epoch form) on its streamed (X, y, w) lanes while
    the next block's transfer rides behind it. Shuffling is within-block —
    the documented deviation from the resident global shuffle (frames that
    fit the window never reach this driver, so the bit-parity pins hold on
    the resident path). params/opt_state stay donated across block
    dispatches; epoch-loss early stopping and checkpoint cadence match the
    resident driver's."""
    import time as _time

    from h2o3_tpu.parallel.mesh import n_shards, pad_flat_to_shards

    blk_rows = store.block_rows
    batch = min(int(p.mini_batch_size), blk_rows)
    l1, l2 = jnp.float32(p.l1), jnp.float32(p.l2)
    dropout = _resolved_dropout(p, len(p.hidden))
    shard_on = _dl_grad_shard(
        p, dropout, p.input_dropout_ratio, batch,
        _flat_state_ok(opt_state, params),
    )
    ctl = _dl_dropout_ctl(p, dropout, p.input_dropout_ratio)
    if ctl and batch % ctl:
        ctl = 0
    n_sh = n_shards()
    D = store.lane("X").shape[1]
    sgd = _fused_schedule(p, mlp, kind, shard_on, ctl, D, batch)
    desc = (tuple(int(h) for h in mlp.hidden), mlp.activation.lower(),
            tuple(mlp.dropout), float(mlp.input_dropout), int(mlp.n_out),
            kind, D, *_sgd_desc(p))

    unravel = None
    n_real = fpad = 0
    if shard_on:
        from jax.flatten_util import ravel_pytree

        flat, unravel = ravel_pytree(params)
        n_real = int(flat.size)
        fpad = pad_flat_to_shards(n_real)
        params = jnp.pad(flat, (0, fpad - n_real))
        opt_state = _state_to_flat(opt_state, unravel(flat), tx, fpad)

    keeper = ScoreKeeper(p.stopping_rounds, p.stopping_tolerance, False)
    seed = abs(p.seed) if p.seed and p.seed > 0 else 99
    rng = np.random.default_rng(seed)
    history = []
    n_epochs = max(1, int(np.ceil(p.epochs)))
    real = [max(min(store.span(bi)[1], nrow) - store.span(bi)[0], 0)
            for bi in range(store.n_blocks)]
    for _ in range(start_epochs):  # continuation: keep the RNG streams
        for bi in range(store.n_blocks):  # aligned with an uninterrupted
            if real[bi]:  # streamed run
                rng.permutation(real[bi])
        key, _ = jax.random.split(key)
    epochs_done = start_epochs

    coll = {}
    if n_sh > 1:
        from h2o3_tpu.ops.collectives import modeled_reduce_bytes

        n_param = n_real if shard_on else sum(
            int(np.prod(q.shape)) for q in jax.tree.leaves(params))
        if shard_on:
            reduce_lanes = dict(modeled_reduce_bytes(fpad, n_sh, passes=2))
            reduce_lanes["exact"] = reduce_lanes.get("exact", 0.0) + 4.0
            coll = {"dl_grad_reduce": reduce_lanes,
                    "dl_param_gather": {"exact": fpad * 4.0}}
        else:
            coll = {"dl_grad_reduce": {"exact": n_param * 4.0}}

    e = start_epochs
    stopped = False
    while e < n_epochs and not stopped:
        _ep_t0 = _time.perf_counter()
        key, ekey = jax.random.split(key)
        loss_sum, nb_sum = 0.0, 0
        for bi, blk in store.stream(("X", "y", "w")):
            if real[bi] == 0:
                continue  # all-padding tail block
            nbatch = max(1, real[bi] // batch)
            perm = np.zeros((1, blk_rows), np.int64)
            perm[0, : real[bi]] = rng.permutation(real[bi])
            slot = jnp.asarray(
                (np.arange(blk_rows) < real[bi]).astype(np.float32))
            prog = _dl_chunk_program(
                desc, mlp, tx, kind, batch, blk_rows, 1, shard_on,
                unravel=unravel, n_real=n_real, fpad=fpad, ctl_shards=ctl,
                sgd=sgd,
            )
            _DL_DISPATCHES.inc()
            _DL_STEPS.inc(nbatch)
            if sgd is not None:
                _DL_FUSED_STEPS.inc(nbatch)
            from h2o3_tpu.utils import flightrec as _fr

            with _fr.dispatch("dl_chunk", block=int(bi),
                              rows=int(blk_rows)):
                params, opt_state, _k, losses, _, _ = prog(
                    params, opt_state, blk["X"], blk["y"], blk["w"],
                    jnp.asarray(perm), jax.random.fold_in(ekey, bi),
                    jnp.int32(nbatch), l1, l2, slot, jnp.bool_(False),
                )
                loss_sum += float(np.asarray(losses)[0]) * nbatch
            nb_sum += nbatch
        epochs_done = e + 1
        loss = loss_sum / max(nb_sum, 1)
        history.append({"epoch": epochs_done, "loss": loss})
        _DL_EPOCHS.inc()
        _DL_EPOCH_SECONDS.observe(_time.perf_counter() - _ep_t0)
        keeper.record(loss)
        for ph, lanes in coll.items():
            for lane, nb in lanes.items():
                if nb:
                    _COLL_BYTES.inc(nb * nb_sum, phase=ph)
                    _COLL_BYTES.inc(nb * nb_sum, phase=ph, lane=lane)
        if on_epoch is not None:
            if shard_on:
                on_epoch(unravel(params[:n_real]),
                         _state_from_flat(opt_state, unravel, n_real),
                         epochs_done, history)
            else:
                on_epoch(params, opt_state, epochs_done, history)
        job.update(0.05 + 0.9 * epochs_done / n_epochs)
        e += 1
        if keeper.should_stop() or job.stop_requested:
            Log.info(f"DeepLearning early stop at epoch {epochs_done}")
            stopped = True
    if shard_on:
        params = unravel(params[:n_real])
        opt_state = _state_from_flat(opt_state, unravel, n_real)
    return params, opt_state, history, epochs_done, None


class H2OSGDState(NamedTuple):
    """Manual-rate SGD's state: the minibatch steps taken (int32; the
    samples seen are ``count * batch``, so a checkpoint continues the
    schedule where it stopped) and the momentum buffer, parameter-shaped.
    The scalar ``count`` also keeps the state off the sharded-gradient lane
    (``_flat_state_ok``: ``dl_shard_fallbacks_total{reason=opt_state}``)."""

    count: jax.Array
    momentum: object


def _sgd_desc(p) -> tuple:
    """The optimizer's hyperparameters, for the chunk programs' cache key
    (``tx``'s update closure bakes every one in)."""
    return (bool(p.adaptive_rate), float(p.rho), float(p.epsilon),
            float(p.rate), float(p.rate_annealing), float(p.rate_decay),
            float(p.momentum_start or 0), float(p.momentum_ramp),
            float(p.momentum_stable or 0),
            bool(p.nesterov_accelerated_gradient))


def _sgd_schedule(p, batch: int, count):
    """(rate, momentum) at the step taken after ``count`` steps: H2O's
    ``rate / (1 + rate_annealing * n)`` and its momentum, ``momentum_start``
    rising (or falling) linearly to ``momentum_stable`` over
    ``momentum_ramp`` samples and held there, for ``n = count * batch``
    samples seen."""
    n = count.astype(jnp.float32) * jnp.float32(batch)
    rate = p.rate / (1.0 + p.rate_annealing * n)
    start, stable = float(p.momentum_start or 0.0), float(p.momentum_stable or 0.0)
    mom = jnp.float32(start)
    if p.momentum_ramp > 0:
        mom = jnp.where(n >= p.momentum_ramp, jnp.float32(stable),
                        start + (stable - start) * (n / p.momentum_ramp))
    return rate, mom


def _dense_index(path) -> int:
    """The ``i`` of the ``Dense_<i>`` module a parameter leaf belongs to."""
    for k in path:
        name = str(getattr(k, "key", getattr(k, "name", "")))
        if name.startswith("Dense_"):
            return int(name.split("_")[1])
    raise ValueError(f"parameter {path} is in no Dense layer")


def _h2o_sgd(p, batch: int) -> optax.GradientTransformation:
    """H2O's manual-rate SGD (``adaptive_rate=False``). For the step after
    ``n`` samples, with ``g`` the gradient of the batch's mean loss plus the
    l1/l2 penalties, and ``l`` the index of a parameter's layer (0 for the
    weights into the first hidden layer)::

        rate_n = rate / (1 + rate_annealing * n)
        mu_n   = momentum_start + (momentum_stable - momentum_start)
                 * min(n / momentum_ramp, 1)          (momentum_ramp > 0)
        d      = -rate_n * rate_decay**l * (1 - mu_n) * g
        v      = mu_n * v + d
        w      = w + v                   (classical momentum)
        w      = w + mu_n * v + d        (nesterov_accelerated_gradient)

    ``rate_decay`` is the factor between layers (the N-th layer learns at
    ``rate * rate_decay**(N-1)``), the rate is scaled by ``1 - mu_n``, and
    Nesterov's step is the look-ahead form ``w += (1 + mu) v - mu v_old``.
    These follow H2O's documented parameters; upstream's ``Neurons.java``
    was not at hand to check them against [UNVERIFIED: the ``1 - mu``
    scaling, the Nesterov form, the penalties on biases (here every
    parameter is penalised, as the ADADELTA path does)]."""
    nesterov = bool(p.nesterov_accelerated_gradient)
    decay = float(p.rate_decay)

    def init(params):
        return H2OSGDState(jnp.zeros((), jnp.int32),
                           jax.tree.map(jnp.zeros_like, params))

    def update(grads, state, params=None):
        del params
        rate, mom = _sgd_schedule(p, batch, state.count)
        step = rate * (1.0 - mom)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(grads)
        vel = treedef.flatten_up_to(state.momentum)
        upd, new_v = [], []
        for (path, g), v in zip(leaves, vel):
            d = -(step * decay ** _dense_index(path)) * g
            v = mom * v + d
            upd.append(mom * v + d if nesterov else v)
            new_v.append(v)
        return (treedef.unflatten(upd),
                H2OSGDState(state.count + 1, treedef.unflatten(new_v)))

    return optax.GradientTransformation(init, update)


def _make_optimizer(p, batch: int):
    """ADADELTA (H2O's default ``adaptive_rate``) or H2O's manual-rate SGD
    over minibatches of ``batch`` rows."""
    if p.adaptive_rate:
        return optax.adadelta(learning_rate=1.0, rho=p.rho, eps=p.epsilon)
    return _h2o_sgd(p, batch)


def _dl_pad_cols(d: int) -> int:
    """Bucketed input width for the supervised DL program keys: columns to
    a multiple of 4 (the PR-1 ladder) so AutoML/grid steps over
    near-identical frames share one compiled chunk program. Padded input
    columns are all-zero; the first Dense kernel's extra rows start at zero
    and receive zero gradients forever, so a bucketed build's trajectory is
    bit-identical to the exact-shape one."""
    from h2o3_tpu import config

    if not config.get_bool("H2O3_TPU_SHAPE_BUCKETS"):
        return d
    return -(-d // 4) * 4


def _repad_input_kernel(params, d_real: int, d_pad: int):
    """Zero-pad (or re-pad, on checkpoint resume across bucket settings)
    the first Dense kernel's input rows to ``d_pad``. Rows past ``d_real``
    are exactly zero by construction, so slicing them off is lossless."""
    import flax.core

    frozen = isinstance(params, flax.core.FrozenDict)
    prm = flax.core.unfreeze(params) if frozen else jax.tree.map(
        lambda x: x, params)
    k = prm["params"]["Dense_0"]["kernel"]
    if int(k.shape[0]) != d_pad:
        k = k[:d_real]
        k = jnp.pad(k, ((0, d_pad - d_real), (0, 0)))
        prm["params"]["Dense_0"]["kernel"] = k
    return flax.core.freeze(prm) if frozen else prm


def _resolved_dropout(p, n_hidden: int) -> tuple:
    """THE dropout-default rule (WithDropout activations default to 0.5) —
    single source for the network build and the model_summary table."""
    return tuple(
        p.hidden_dropout_ratios
        or ((0.5,) * n_hidden if "dropout" in p.activation.lower()
            else (0.0,) * n_hidden)
    )


def _make_mlp(p, n_out: int) -> _MLP:
    return _MLP(hidden=tuple(int(h) for h in p.hidden), n_out=n_out,
                activation=p.activation,
                dropout=_resolved_dropout(p, len(p.hidden)),
                input_dropout=p.input_dropout_ratio)


def _pred_program(mlp: _MLP, softmax: bool, block: int):
    """The device predictor's forward, in row blocks inside one program: a
    ``fori_loop`` over ``block``-row slices of the design (the last block
    starts ``block`` rows before the end, so a ragged tail recomputes rows
    it shares with the block before, to the same values), the softmax head
    on each block, and the outputs written as ``(n_out, rows)``: an
    ``(n, 2)`` float32 result would be tiled to 128 lanes a row. No
    activation larger than ``(block, width)`` is ever alive."""
    key = ("dl_pred", tuple(int(h) for h in mlp.hidden), mlp.activation.lower(),
           int(mlp.n_out), bool(softmax), int(block), jax.default_backend())
    fn = _DL_PROGRAMS.get(key)
    if fn is not None:
        return fn

    @jax.jit
    @jax.named_scope("ph_dl_pred")
    def fn(prm, X):
        n = X.shape[0]
        B = min(block, n)

        def body(i, out):
            start = jnp.minimum(i * B, n - B)
            o = mlp.apply(prm, jax.lax.dynamic_slice_in_dim(X, start, B, 0),
                          train=False)
            if softmax:
                o = jax.nn.softmax(o, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(out, o.T, start, 1)

        return jax.lax.fori_loop(0, -(-n // B), body,
                                 jnp.zeros((mlp.n_out, n), jnp.float32))

    _DL_PROGRAMS[key] = fn
    return fn


def _layers(prm, d_real: int | None = None) -> list:
    """A parameter tree as ``[(W, b), ...]`` in layer order, numpy float32;
    ``d_real`` cuts the first kernel's zero bucket-pad rows."""
    inner = prm["params"] if "params" in prm else prm
    names = sorted(inner, key=lambda k: int(k.split("_")[-1]))
    out = [(np.asarray(inner[k]["kernel"]), np.asarray(inner[k]["bias"]))
           for k in names]
    if d_real is not None:
        out[0] = (out[0][0][:d_real], out[0][1])
    return out


class DeepLearningModel(Model):
    algo = "deeplearning"

    def _mlp(self) -> _MLP:
        """The network, rebuilt from the parameters (its output width is the
        last layer's bias)."""
        n_out = int(_layers(self.output["params"])[-1][1].shape[0])
        return _make_mlp(self.params, n_out)

    def _design(self, frame: Frame):
        X, _ = self.output["datainfo"].transform(frame)
        pad = int(self.output.get("input_pad") or 0)
        if pad:  # bucketed input width: scoring pads with the same zeros
            X = jnp.pad(X, ((0, 0), (0, pad)))
        return X

    def _forward(self, X):
        """``(n_out, rows)`` float32 on the device: the blocked forward of
        ``_pred_program``, class probabilities for a classifier."""
        softmax = self.is_classifier and not self.output.get("autoencoder")
        return _pred_program(self._mlp(), softmax, PRED_BLOCK)(
            self.output["params"], X)

    def _predict_raw(self, frame: Frame) -> np.ndarray:
        out = np.asarray(self._forward(self._design(frame)))[:, : frame.nrow]
        if self.output.get("autoencoder") or self.is_classifier:
            return np.ascontiguousarray(out.T)  # (n, classes) / (n, expanded)
        return out[0]

    def _device_predictor(self):
        # the autoencoder's metrics are its reconstruction error
        return None if self.output.get("autoencoder") else self._predict_raw_dev

    def _predict_raw_dev(self, frame: Frame):
        """``_predict_raw`` with no pull to the host: P(class 1) for a
        binomial model, ``(n, K)`` probabilities for multinomial, the
        prediction for regression."""
        return self._raw_from_design(self._design(frame), frame.nrow)

    def _raw_from_design(self, X, nrow: int):
        """The device predictions from a design matrix of this model's
        ``DataInfo`` (the builder hands over the one it trained on, so the
        training metrics transform nothing)."""
        out = self._forward(X)
        if self.is_classifier:
            return out[1, :nrow] if out.shape[0] == 2 else out[:, :nrow].T
        return out[0, :nrow]

    def sgd_probe(self) -> dict | None:
        """The records a plain reference replays the training's updates from
        (None after a checkpoint continuation, a streamed training or a
        load: they are not persisted), in numpy, each parameter set as
        ``[(W, b), ...]`` by layer:

        - ``batch``: rows a minibatch;
        - ``initial``: the weights before the first step;
        - ``early``: ``steps`` (the first ``PROBE_STEPS``), ``rows`` (the
          training's row indices for them, in the order they were taken) and
          ``after`` (the weights the program held after them);
        - ``late``: the last ``steps`` of the training's last epoch:
          ``rows``, the weights ``before`` them, and where the optimizer is
          manual-rate SGD its ``count`` (steps taken) and ``momentum``
          then; ``after`` is the trained model's weights."""
        pr = self.output.get("probe")
        if pr is None:
            return None
        d = self.output["datainfo"].ncols_expanded
        late = pr["late"]
        rec = {"batch": int(pr["batch"]),
               "initial": _layers(pr["initial"], d),
               "early": {"steps": int(pr["early"]["steps"]),
                         "rows": np.asarray(pr["early"]["rows"]),
                         "after": _layers(pr["early"]["after"], d)},
               "late": {"steps": int(late["steps"]),
                        "rows": np.asarray(late["rows"]),
                        "before": _layers(late["before"], d),
                        "after": _layers(self.output["params"], d)}}
        if isinstance(late["state"], H2OSGDState):
            rec["late"]["count"] = int(late["state"].count)
            rec["late"]["momentum"] = _layers(late["state"].momentum, d)
        return rec

    def predict(self, frame: Frame) -> Frame:
        if not self.output.get("autoencoder"):
            return super().predict(frame)
        # upstream autoencoder predict: one reconstr_* column per expanded
        # input feature (the standardized design-matrix space)
        recon = self._predict_raw(frame)
        names = [f"reconstr_{n}" for n in self.output["expanded_names"]]
        return Frame(
            [Vec.from_numpy(recon[:, j], "real") for j in range(recon.shape[1])],
            names,
        )

    def _recon_row_mse(self, frame: Frame, X=None, wmask=None):
        """Per-row reconstruction MSE in the standardized feature space —
        the ONE formula behind anomaly() and the AutoEncoder metrics.
        Pass (X, wmask) to reuse an existing design-matrix transform."""
        di: DataInfo = self.output["datainfo"]
        if X is None:
            X, wmask = di.transform(frame)
        recon = self.output["apply_fn"](self.output["params"], X)
        row_mse = np.asarray(jnp.mean((recon - X) ** 2, axis=1))[: frame.nrow]
        return row_mse, np.asarray(wmask)[: frame.nrow] > 0

    def _autoencoder_metrics(self, frame: Frame, X=None, wmask=None):
        """ModelMetricsAutoEncoder analog: reconstruction MSE on the
        standardized design matrix."""
        from h2o3_tpu.models.metrics import ModelMetrics

        row_mse, mask = self._recon_row_mse(frame, X, wmask)
        mse = float(row_mse[mask].mean()) if mask.any() else float("nan")
        return ModelMetrics("AutoEncoder", {"mse": mse, "rmse": float(np.sqrt(mse))})

    def model_performance(self, frame: Frame | None = None):
        if self.output.get("autoencoder"):
            return (self._autoencoder_metrics(frame) if frame is not None
                    else self.training_metrics)
        return super().model_performance(frame)

    def model_summary(self) -> list[dict]:
        """Upstream DL model_summary: the layer table."""
        p = self.params
        di: DataInfo = self.output["datainfo"]
        hidden = list(self.output.get("hidden") or p.hidden)
        n_out = (di.ncols_expanded if self.output.get("autoencoder")
                 else (self.nclasses if self.is_classifier else 1))
        dropout = list(_resolved_dropout(p, len(hidden)))
        rows = [{"layer": 1, "units": di.ncols_expanded, "type": "Input",
                 "dropout": p.input_dropout_ratio}]
        for i, h in enumerate(hidden):
            rows.append({"layer": i + 2, "units": int(h),
                         "type": p.activation, "dropout": dropout[i],
                         "l1": p.l1, "l2": p.l2})
        rows.append({"layer": len(hidden) + 2, "units": int(n_out),
                     "type": ("Linear" if (self.output.get("autoencoder")
                              or not self.is_classifier) else "Softmax")})
        return rows

    def anomaly(self, frame: Frame) -> Frame:
        """Per-row reconstruction MSE (``h2o.anomaly`` successor): the
        anomaly score in the standardized feature space."""
        if not self.output.get("autoencoder"):
            raise ValueError("anomaly() requires an autoencoder model")
        mse, _ = self._recon_row_mse(frame)
        return Frame([Vec.from_numpy(mse, "real")], ["Reconstruction.MSE"])


class DeepLearning(ModelBuilder):
    algo = "deeplearning"
    PARAMS_CLS = DeepLearningParams

    def _plan_streamed(self, train: Frame, di, p, d_pad: int, yv,
                       classification: bool):
        """ChunkStore of block design lanes for out-of-core epochs, or
        None for the resident path (autoencoder is excluded — its
        reconstruction target is the whole design; docs/MIGRATION.md
        fallback matrix). The streamed lanes are built on the host."""
        from h2o3_tpu.frame import chunkstore as cs

        if p.autoencoder:
            return None
        store = cs.ChunkStore.plan(train.npad, (d_pad + 2) * 4 + 8)
        if store is None:
            return None
        npad = train.npad
        Log.info(
            f"DeepLearning out-of-core streaming: {store.n_blocks} blocks "
            f"x {store.block_rows} rows, input width {d_pad}"
        )
        Xlane = store.add_empty("X", (npad, d_pad), np.float32)
        vmask = np.zeros(npad, np.float32)
        need = [c.name for c in di.columns if c.pair is None]
        for c in di.columns:
            if c.pair is not None:
                need += [nm for nm in c.pair if nm not in need]
        for bi in range(store.n_blocks):
            lo, hi = store.span(bi)
            bf = cs.host_block_frame(train, need, lo, hi)
            Xb, vb = di.transform(bf)
            Xlane[lo:hi, : di.ncols_expanded] = np.asarray(jax.device_get(Xb))
            vmask[lo:hi] = np.asarray(jax.device_get(vb))
        cs.release_frame_features(train, need)
        y_np = yv.to_numpy().astype(np.float64)
        ybuf = np.zeros(npad, np.float32)
        ybuf[: train.nrow] = np.nan_to_num(y_np, nan=0.0)
        okresp = np.ones(npad, np.float32)
        okresp[: train.nrow] = (
            (y_np >= 0) if classification else ~np.isnan(y_np)
        ).astype(np.float32)
        w_np = vmask
        if p.weights_column:
            w_np = w_np * np.nan_to_num(
                train.vec(p.weights_column).host_values().astype(np.float32))
        store.add("w", (w_np * okresp).astype(np.float32))
        store.add("y", np.asarray(ybuf, np.float32))
        return store

    def _streamed_metrics(self, model: "DeepLearningModel", store,
                          frame: Frame):
        """Training metrics from per-block forward passes over the store's
        design lanes — the resident design is never re-materialized."""
        from h2o3_tpu.models.model_base import _make_metrics

        parts = []
        for bi, blk in store.stream(("X",)):
            logits = model.output["apply_fn"](model.output["params"],
                                              blk["X"])
            if model.is_classifier:
                parts.append(np.asarray(jax.nn.softmax(logits, axis=1)))
            else:
                parts.append(np.asarray(logits[:, 0]))
        raw = np.concatenate(parts)[: frame.nrow]
        yh, wh = model._response_and_weights(frame)
        return _make_metrics(model, raw, yh, wh)

    def _epoch_snapshot(self, key, di, prm, ost, done, hist, domain,
                        autoencoder=False, expanded=None) -> DeepLearningModel:
        """Interval-snapshot factory: params + optimizer accumulators +
        epoch count — everything the existing checkpoint-resume path reads
        (``apply_fn`` is rebuilt on load by persist._rebuild_deeplearning)."""
        p = self.params
        out = {
            "datainfo": di, "params": prm, "names": list(self._x),
            "hidden": list(p.hidden), "epochs_trained": done,
            "opt_state": ost, "response_domain": domain,
        }
        if autoencoder:
            out["autoencoder"] = True
            out["expanded_names"] = expanded
        else:
            k0 = prm["params"]["Dense_0"]["kernel"]
            out["input_pad"] = int(k0.shape[0]) - di.ncols_expanded
        m = DeepLearningModel(key, p, out)
        m.scoring_history = list(hist)
        return m

    def _build_autoencoder(self, job: Job, train: Frame, valid: Frame | None) -> Model:
        """Autoencoder mode (upstream ``autoencoder=true`` /
        H2OAutoEncoderEstimator): reconstruct the standardized design
        matrix; no response. Same sync-SGD driver as the supervised path."""
        p: DeepLearningParams = self.params
        with _mx.span("dl.datainfo"):
            di = DataInfo.fit(train, self._x, standardize=p.standardize,
                              hash_buckets=p.hash_buckets)
            X, wmask = di.transform(train)
            w = wmask
            if p.weights_column:
                w = w * jnp.nan_to_num(train.vec(p.weights_column).data)
            w = jnp.asarray(np.asarray(w))

        D = di.ncols_expanded
        mlp = _make_mlp(p, n_out=D)
        seed = abs(p.seed) if p.seed and p.seed > 0 else 99
        key = jax.random.PRNGKey(seed)
        key, init_key = jax.random.split(key)
        params = mlp.init(init_key, jnp.zeros((1, D)), train=False)

        from h2o3_tpu.models.model_base import check_checkpoint_compat, resolve_checkpoint

        prior = resolve_checkpoint(p.checkpoint)
        start_epochs = 0
        if prior is not None:
            check_checkpoint_compat(
                prior, self,
                ("hidden", "activation", "standardize", "adaptive_rate",
                 "autoencoder"),
            )
            if prior.output["datainfo"].ncols_expanded != D:
                raise ValueError("checkpoint design-matrix width differs")
            start_epochs = int(prior.output.get("epochs_trained", 0))
            if p.epochs <= start_epochs:
                raise ValueError(
                    f"checkpoint continuation needs epochs > {start_epochs}"
                )
            params = prior.output["params"]

        tx = _make_optimizer(p, min(int(p.mini_batch_size), train.npad))
        opt_state = tx.init(params)
        if prior is not None and prior.output.get("opt_state") is not None:
            opt_state = prior.output["opt_state"]

        def on_epoch(prm, ost, done, hist):
            self._export_interval_checkpoint(
                job, lambda key: self._epoch_snapshot(
                    key, di, prm, ost, done, hist, None,
                    autoencoder=True, expanded=di.coef_names(),
                )
            )
            faults.die_check(self.algo)  # chaos: worker death at boundary
            faults.abort_check(self.algo, done)

        # autoencoder inputs are NOT shape-bucketed: the reconstruction
        # target is the input itself, so padded columns would enter the
        # per-row MSE mean (docs/MIGRATION.md fallback matrix)
        with _mx.span("dl.epochs"):
            params, opt_state, history, epochs_done, _ = _run_sync_sgd(
                job, p, mlp, "recon", tx, params, opt_state,
                X, jnp.zeros(train.npad, jnp.float32), w,
                train.nrow, train.npad, key, start_epochs, on_epoch=on_epoch,
            )

        apply_fn = jax.jit(lambda prm, xx: mlp.apply(prm, xx, train=False))
        out = {
            "datainfo": di, "params": params, "apply_fn": apply_fn,
            "names": list(self._x), "hidden": list(p.hidden),
            "epochs_trained": epochs_done, "opt_state": opt_state,
            "response_domain": None, "autoencoder": True,
            "expanded_names": di.coef_names(),
        }
        model = DeepLearningModel(DKV.make_key("dl"), p, out)
        model.scoring_history = history
        model.training_metrics = model._autoencoder_metrics(train, X, wmask)
        if valid is not None:
            model.validation_metrics = model._autoencoder_metrics(valid)
        return model

    def _validate(self, train: Frame, valid: Frame | None) -> None:
        p: DeepLearningParams = self.params
        if p.autoencoder:
            if p.nfolds and p.nfolds > 1:
                raise ValueError("autoencoder does not support cross-validation")
            return  # unsupervised: no response checks
        super()._validate(train, valid)

    def _build(self, job: Job, train: Frame, valid: Frame | None) -> Model:
        p: DeepLearningParams = self.params
        if p.autoencoder:
            return self._build_autoencoder(job, train, valid)
        yv = train.vec(p.response_column)
        classification = yv.is_categorical()
        K = yv.cardinality if classification else 1
        n_out = max(K, 1) if classification else 1

        # DataInfo.fit, the design matrix and the response and weight
        # lanes; ends in a wait for the design program, so that its device
        # time is not the epochs'
        with _mx.span("dl.datainfo"):
            di = DataInfo.fit(train, self._x, standardize=p.standardize,
                              hash_buckets=p.hash_buckets)
            # shape-bucket ladder on the input width (zero columns, proven
            # bit-inert via the zero-padded first kernel — _dl_pad_cols)
            D = di.ncols_expanded
            d_pad = _dl_pad_cols(D)

            # out-of-core streaming (frame/chunkstore.py): a design
            # matrix past the HBM window trains as row-block epochs — DL
            # already minibatches, so each block runs the existing chunk
            # program; shuffling is within-block (documented deviation).
            stream = self._plan_streamed(train, di, p, d_pad, yv,
                                         classification)
            if stream is not None:
                X = stream
                w = jnp.asarray(stream.lane("w"))
                y = jnp.asarray(stream.lane("y"))
            else:
                from h2o3_tpu.models.glm import _response_lanes

                # resident: nothing n-row-sized is made on the host
                X, wmask = di.transform(train)
                if d_pad > D:
                    X = jnp.pad(X, ((0, 0), (0, d_pad - D)))
                y, w, _, _ = _response_lanes(
                    yv.data, wmask,
                    train.vec(p.weights_column).data
                    if p.weights_column else None, None)
                X.block_until_ready()

        mlp = _make_mlp(p, n_out=n_out)
        seed = abs(p.seed) if p.seed and p.seed > 0 else 99
        key = jax.random.PRNGKey(seed)
        key, init_key = jax.random.split(key)
        # init at the EXACT width (initializer fan-in must not see padding)
        # then zero-pad the first kernel's rows to the bucketed width
        params = mlp.init(init_key, jnp.zeros((1, di.ncols_expanded)), train=False)
        if d_pad > D:
            params = _repad_input_kernel(params, D, d_pad)

        from h2o3_tpu.models.model_base import check_checkpoint_compat, resolve_checkpoint

        prior = resolve_checkpoint(p.checkpoint)
        start_epochs = 0
        if prior is not None:
            check_checkpoint_compat(
                prior, self, ("hidden", "activation", "standardize", "adaptive_rate")
            )
            if prior.output["datainfo"].ncols_expanded != di.ncols_expanded:
                raise ValueError("checkpoint design-matrix width differs")
            start_epochs = int(prior.output.get("epochs_trained", 0))
            if p.epochs <= start_epochs:
                raise ValueError(
                    f"checkpoint continuation needs epochs > {start_epochs}"
                )
            params = _repad_input_kernel(prior.output["params"], D, d_pad)

        tx = _make_optimizer(p, min(int(p.mini_batch_size),
                                    stream.block_rows if stream is not None
                                    else train.npad))
        opt_state = tx.init(params)
        if prior is not None and prior.output.get("opt_state") is not None:
            # carry the optimizer accumulators (adadelta rho-averages /
            # momentum + schedule counter) so continuation matches an
            # uninterrupted run, like GBM carries F and the split chain
            prior_ost = prior.output["opt_state"]
            shapes_ok = jax.tree.structure(prior_ost) == jax.tree.structure(
                opt_state
            ) and all(
                a.shape == b.shape
                for a, b in zip(jax.tree.leaves(prior_ost),
                                jax.tree.leaves(opt_state))
            )
            if shapes_ok:
                opt_state = prior_ost
            else:  # bucket-width change between runs: accumulators reset
                Log.warn(
                    "DeepLearning checkpoint optimizer state has a "
                    "different shape bucket; accumulators re-initialized"
                )

        domain = tuple(yv.domain) if classification else None

        def on_epoch(prm, ost, done, hist):
            self._export_interval_checkpoint(
                job, lambda key: self._epoch_snapshot(
                    key, di, prm, ost, done, hist, domain,
                )
            )
            faults.die_check(self.algo)  # chaos: worker death at boundary
            faults.abort_check(self.algo, done)

        # the epochs: host permutations, then each chunk dispatched and
        # synced in its `dispatch:dl_chunk` child
        with _mx.span("dl.epochs"):
            params, opt_state, history, epochs_done, probe = _run_sync_sgd(
                job, p, mlp, "ce" if classification else "mse", tx, params,
                opt_state, X, y, w,
                train.nrow, train.npad, key, start_epochs, on_epoch=on_epoch,
            )
        apply_fn = jax.jit(lambda prm, xx: mlp.apply(prm, xx, train=False))

        out = {
            "datainfo": di,
            "params": params,
            "apply_fn": apply_fn,
            "names": list(self._x),
            "hidden": list(p.hidden),
            "epochs_trained": epochs_done,
            "opt_state": opt_state,
            "input_pad": d_pad - D,
            "response_domain": tuple(yv.domain) if classification else None,
        }
        model = DeepLearningModel(DKV.make_key("dl"), p, out)
        model.scoring_history = history
        if stream is not None:
            # streamed scoring: never re-materialize the resident design
            model.training_metrics = self._streamed_metrics(model, stream, train)
            stream.close()
        else:
            # from the design matrix the epochs ran on, in row blocks: `train`
            # is not transformed a second time
            model.training_metrics = model._score_metrics(
                train, raw=lambda: model._raw_from_design(X, train.nrow))
        if valid is not None:
            model.validation_metrics = model._score_metrics(valid)
        # on the host once the device's work is done: no HBM held for them
        # (and not persisted: persist._STRIP)
        model.output["probe"] = jax.device_get(probe)
        return model
