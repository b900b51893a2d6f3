"""One DeepLearning minibatch step as one Pallas kernel: the forward, the
softmax cross-entropy, the backward and H2O's manual-rate SGD update of a
dense network, in VMEM, in a single launch.

Why: the XLA step of a 28 -> 5 x 300 -> 2 network at a batch of 100 is 33
fusions inside the step loop's body, 17 of them with results of at most 300
elements. Each costs a fixed ~1.2 us (its operands in from HBM, its results
back out) and one of the loop's gaps: the step is launch-bound, not
FLOP-bound. Here one launch does it all.

The layout (:func:`pack` / :func:`unpack`): per layer ``l`` the kernel
``W (fan_in, fan_out)``, its momentum ``V``, and the bias and its momentum
as ``(1, fan_out)`` rows; H2O's step count and the running loss sum as
``(1, 1)`` SMEM scalars. Every one of them is aliased from input to output,
so the caller's ``fori_loop`` carries them in place with no copy.

Memory traffic: each ``W`` and ``V`` stays in HBM (``pl.ANY``) and is copied
into VMEM scratch by the kernel itself. Every ``W`` copy starts first, in
layer order, and each is waited for just before its forward product, so a
layer's copy overlaps the products of the layers before it; the ``V`` copies
follow in the order the backward takes them. A layer's new ``W`` and ``V``
are copied back out as soon as its update is done, behind the next backward
product.

The arithmetic follows the XLA step (``deeplearning._dl_chunk_program``'s
``loss_fn`` under ``jax.value_and_grad``, then ``_h2o_sgd``), all in
float32 and every product at ``Precision.HIGHEST``:

1. the schedule from the count: ``rate_n = rate / (1 + rate_annealing n)``
   for ``n = count * batch`` samples, the momentum ramp, ``rate_decay ** l``;
2. the forward, tanh or rectifier;
3. the loss, against a one-hot compare of the labels (no gather);
4. the backward, with every parameter's penalty gradient
   ``l2 w + l1 sign(w)`` (``sign(0)`` is 1, as in jax's derivative of
   ``|w|``), and the penalties' value added to the loss;
5. the update: ``d = -rate_n decay**l (1 - mu) g``, ``v = mu v + d``,
   ``w += v`` (classical) or ``w += mu v + d`` (Nesterov).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM the kernel may use, and the share of it the estimate of
# :func:`fits` may fill (the compiler's own temporaries take the rest)
VMEM_LIMIT = 64 << 20
VMEM_BUDGET = 32 << 20

_HI = jax.lax.Precision.HIGHEST


class Schedule(NamedTuple):
    """H2O's manual-rate SGD hyperparameters (``_h2o_sgd``)."""

    rate: float
    rate_annealing: float
    rate_decay: float
    momentum_start: float
    momentum_ramp: float
    momentum_stable: float
    nesterov: bool


def _tile_bytes(rows: int, cols: int) -> int:
    """float32 bytes of a ``(rows, cols)`` array laid out in (8, 128) tiles."""
    return 4 * (-(-rows // 8) * 8) * (-(-cols // 128) * 128)


def vmem_bytes(widths, batch: int) -> int:
    """VMEM the kernel needs for a network of ``widths`` (input, hidden...,
    output) at ``batch`` rows: every ``W`` and ``V`` in scratch, the biases
    and their momentum in and out, four activations of the batch a layer
    (the forward's, its derivative, the backward's two), the largest
    gradient, and the batch's inputs (its rows transposed, its labels and
    weights)."""
    pairs = list(zip(widths[:-1], widths[1:]))
    params = sum(2 * _tile_bytes(i, o) + 4 * _tile_bytes(1, o) for i, o in pairs)
    acts = sum(4 * _tile_bytes(batch, o) for _, o in pairs)
    grad = max(_tile_bytes(i, o) for i, o in pairs)
    return (params + acts + grad + _tile_bytes(widths[0], batch)
            + 2 * _tile_bytes(1, batch))


def fits(widths, batch: int) -> bool:
    return vmem_bytes(widths, batch) <= VMEM_BUDGET


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _kernel(cnt_ref, lsum_ref, pen_ref, xt_ref, y_ref, wt_ref, *refs,
            n_layers: int, act: str, sched: Schedule, batch: int):
    L = n_layers
    ins, outs = refs[:4 * L], refs[4 * L:8 * L + 2]
    w_vm, v_vm, sem_in, sem_out = refs[8 * L + 2:]
    cnt_out, lsum_out, outs = outs[0], outs[1], outs[2:]
    w_in, b_in, v_in, vb_in = (ins[k::4] for k in range(4))
    w_out, b_out, v_out, vb_out = (outs[k::4] for k in range(4))

    # every W first, in the forward's order, then every V in the backward's
    load_w = [pltpu.make_async_copy(w_in[l], w_vm[l], sem_in.at[l])
              for l in range(L)]
    load_v = [pltpu.make_async_copy(v_in[l], v_vm[l], sem_in.at[L + l])
              for l in range(L)]
    for c in load_w:
        c.start()
    for c in reversed(load_v):
        c.start()

    # the schedule (_sgd_schedule), as (1, 1) vectors
    count = cnt_ref[0, 0]
    n = jnp.full((1, 1), count, jnp.int32).astype(jnp.float32) * jnp.float32(batch)
    rate = sched.rate / (1.0 + sched.rate_annealing * n)
    mom = jnp.full((1, 1), sched.momentum_start, jnp.float32)
    if sched.momentum_ramp > 0:
        start, stable = sched.momentum_start, sched.momentum_stable
        mom = jnp.where(n >= sched.momentum_ramp, jnp.float32(stable),
                        start + (stable - start) * (n / sched.momentum_ramp))
    step = rate * (1.0 - mom)
    l1 = jnp.full((1, 1), pen_ref[0, 0], jnp.float32)
    l2 = jnp.full((1, 1), pen_ref[0, 1], jnp.float32)

    # forward; the batch comes transposed, (D, B): the first layer's
    # products contract its first dimension
    a = xt_ref[...]
    acts = [a]
    for l in range(L):
        load_w[l].wait()
        z = _dot(a, w_vm[l][...], ((0 if l == 0 else 1,), (0,))) + b_in[l][...]
        if l < L - 1:
            a = jnp.tanh(z) if act == "tanh" else jnp.maximum(z, 0.0)
            acts.append(a)

    # the loss: softmax cross-entropy against the labels' one-hot
    yw = jnp.concatenate([y_ref[...].reshape(1, -1),
                          wt_ref[...].reshape(1, -1)]).T
    y, wt = yw[:, 0:1], yw[:, 1:2]
    k = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1).astype(jnp.float32)
    onehot = (k == y).astype(jnp.float32)
    s = z - jnp.max(z, axis=1, keepdims=True)
    e = jnp.exp(s)
    se = jnp.sum(e, axis=1, keepdims=True)
    ll = jnp.log(se) - jnp.sum(s * onehot, axis=1, keepdims=True)
    wn = jnp.maximum(jnp.sum(wt, axis=0, keepdims=True), 1e-9)
    loss = jnp.sum(wt * ll, axis=0, keepdims=True) / wn
    dz = (e / se - onehot) * (wt / wn)

    # backward and update, last layer first
    pen = jnp.zeros((1, 1), jnp.float32)
    for l in reversed(range(L)):
        a_prev = acts[l]
        dw = _dot(a_prev, dz, ((1 if l == 0 else 0,), (0,)))
        db = jnp.sum(dz, axis=0, keepdims=True)
        if l > 0:
            da = _dot(dz, w_vm[l][...], ((1,), (1,)))
            if act == "tanh":  # jax's tanh derivative: (g + g a)(1 - a)
                dz = (da + da * a_prev) * (1.0 - a_prev)
            else:
                dz = jnp.where(a_prev > 0, da, 0.0)
        step_l = step * (sched.rate_decay ** l)
        load_v[l].wait()
        # W and V are updated in their scratch (copied out below); a bias
        # and its momentum are read from their input blocks and written to
        # their output blocks
        for p_in, v_in_, p_out, v_out_, g in (
                (w_vm[l], v_vm[l], w_vm[l], v_vm[l], dw),
                (b_in[l], vb_in[l], b_out[l], vb_out[l], db)):
            p = p_in[...]
            pen = pen + (l2 * 0.5 * jnp.sum(p * p, keepdims=True)
                         + l1 * jnp.sum(jnp.abs(p), keepdims=True))
            d = -step_l * (g + l2 * p + l1 * jnp.where(p >= 0, 1.0, -1.0))
            v = mom * v_in_[...] + d
            p_out[...] = p + (mom * v + d if sched.nesterov else v)
            v_out_[...] = v
        pltpu.make_async_copy(w_vm[l], w_out[l], sem_out.at[l]).start()
        pltpu.make_async_copy(v_vm[l], v_out[l], sem_out.at[L + l]).start()

    cnt_out[0, 0] = count + 1
    lsum_out[0, 0] = lsum_ref[0, 0] + jnp.sum(loss + pen)
    for l in range(L):
        pltpu.make_async_copy(w_vm[l], w_out[l], sem_out.at[l]).wait()
        pltpu.make_async_copy(v_vm[l], v_out[l], sem_out.at[L + l]).wait()


def step(carry, xt, y, w, pen, *, sched: Schedule, act: str,
         interpret: bool = False):
    """One minibatch step: ``carry`` is ``(count, loss_sum, layers)`` as
    :func:`pack` makes it, ``xt`` the batch transposed ``(D, B)``, ``y`` and
    ``w`` its labels (class indices, as float32) and row weights ``(B,)``,
    ``pen`` ``(1, 2)`` = ``[[l1, l2]]``. Returns the carry after the step;
    the loss sum has grown by the step's loss (the batch's weighted mean
    cross-entropy plus the penalties).

    The batch comes transposed because an epoch's permuted copy of an
    ``(n, D)`` design keeps its rows on the lanes: the ``(D, B)`` slice of
    it is a free relayout, where a row-major ``(B, D)`` operand would make
    XLA lay the whole copy out row-major, ``D`` padded to 128 lanes."""
    count, lsum, layers = carry
    L = len(layers)
    flat = [a for lay in layers for a in lay]
    D, B = xt.shape
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    lay_specs = [hbm, vmem, hbm, vmem] * L
    widths = [D] + [int(lay[0].shape[1]) for lay in layers]
    flops = sum(6 * B * i * o for i, o in zip(widths[:-1], widths[1:]))
    pbytes = sum(4 * a.size for a in flat)
    cost = pl.CostEstimate(
        flops=int(flops), transcendentals=int(B * sum(widths[1:])),
        bytes_accessed=int(2 * pbytes + 4 * (xt.size + 2 * B)))
    out = pl.pallas_call(
        functools.partial(_kernel, n_layers=L, act=act, sched=sched, batch=B),
        in_specs=[smem, smem, smem, vmem, vmem, vmem] + lay_specs,
        out_specs=[smem, smem] + lay_specs,
        out_shape=[jax.ShapeDtypeStruct(count.shape, count.dtype),
                   jax.ShapeDtypeStruct(lsum.shape, lsum.dtype)]
        + [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in flat],
        scratch_shapes=[
            [pltpu.VMEM(lay[0].shape, jnp.float32) for lay in layers],
            [pltpu.VMEM(lay[2].shape, jnp.float32) for lay in layers],
            pltpu.SemaphoreType.DMA((2 * L,)),
            pltpu.SemaphoreType.DMA((2 * L,)),
        ],
        input_output_aliases={0: 0, 1: 1,
                              **{6 + j: 2 + j for j in range(4 * L)}},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=cost,
        interpret=interpret,
        name="dl_step_pallas",
    )(count, lsum, pen, xt, y, w, *flat)
    return (out[0], out[1],
            tuple(tuple(out[2 + 4 * l:6 + 4 * l]) for l in range(L)))


def pack(count, loss_sum, kernels, biases, m_kernels, m_biases):
    """The kernel's carry from the network's arrays in layer order: the
    step count and loss sum as ``(1, 1)``, each bias and its momentum as a
    ``(1, fan_out)`` row."""
    layers = tuple(
        (w, b.reshape(1, -1), v, vb.reshape(1, -1))
        for w, b, v, vb in zip(kernels, biases, m_kernels, m_biases))
    return (jnp.reshape(count, (1, 1)), jnp.reshape(loss_sum, (1, 1)), layers)


def unpack(carry):
    """:func:`pack`'s inverse: ``(count, loss_sum, kernels, biases,
    m_kernels, m_biases)``."""
    count, lsum, layers = carry
    return (count[0, 0], lsum[0, 0], [lay[0] for lay in layers],
            [lay[1][0] for lay in layers], [lay[2] for lay in layers],
            [lay[3][0] for lay in layers])

