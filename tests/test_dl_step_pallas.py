"""The DeepLearning minibatch step as one Pallas kernel
(``ops/dl_step_pallas.py``) against the XLA step it replaces, on the CPU
with the kernel in the Pallas interpreter: a dense 28 -> 3 x 40 -> 2
network at a batch of 16, l1 and l2 both on, a ``rate_decay`` of 0.8, row
weights with zeros, tanh and rectifier, classical momentum and Nesterov.

Tolerance: float32. The two paths compute the same products in another
order, so a weight or a momentum entry may differ by a few units in the
last place of a step's change; over 64 steps the largest difference seen
is 6e-8. The checks hold them to ``ATOL`` = 1e-6 absolute (weights of
order 0.1-1, momentum of order 1e-3), the loss record to ``RTOL`` = 1e-5
relative, and the step count, the probes' steps and rows exactly.

Then which fits take the kernel: a qualifying network on a one-device mesh
(the backend check patched, the kernel interpreted), resident or streamed,
counts every step in ``dl_fused_steps_total``; dropout, ADADELTA, the autoencoder, the sharded
and ctl lanes, a mesh of several devices and a CPU backend keep the XLA
step and leave the counter where it was."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest
from jax.sharding import Mesh

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models import deeplearning as dlm
from h2o3_tpu.models.deeplearning import DeepLearning
from h2o3_tpu.ops import dl_step_pallas as dsp
from h2o3_tpu.parallel import mesh as pm
from h2o3_tpu.utils import metrics as mx

ATOL, RTOL = 1e-6, 1e-5
B, D, HIDDEN = 16, 28, [40, 40, 40]


def _params(activation="Tanh", nesterov=False, **kw):
    args = dict(hidden=HIDDEN, activation=activation, adaptive_rate=False,
                rate=0.05, rate_annealing=1e-3, rate_decay=0.8,
                momentum_start=0.5, momentum_ramp=500, momentum_stable=0.9,
                nesterov_accelerated_gradient=nesterov, l1=1e-4, l2=1e-3,
                mini_batch_size=B)
    args.update(kw)
    return dlm.DeepLearningParams(**args)


def _schedule(p):
    return dsp.Schedule(p.rate, p.rate_annealing, p.rate_decay,
                        p.momentum_start, p.momentum_ramp, p.momentum_stable,
                        p.nesterov_accelerated_gradient)


def _chunk(p, steps, sgd, classes=2):
    """One epoch of ``steps`` minibatches through ``_dl_chunk_program``,
    from the same start and data on either path."""
    mlp = dlm._make_mlp(p, classes)
    params = mlp.init(jax.random.PRNGKey(0), jnp.zeros((1, D)))
    tx = dlm._make_optimizer(p, B)
    rng = np.random.default_rng(1)
    n = steps * B
    X = jnp.asarray(rng.normal(size=(n, D)), jnp.float32)
    y = jnp.asarray(rng.integers(0, classes, n), jnp.float32)
    w = jnp.asarray(rng.integers(0, 3, n) * (rng.random(n) < 0.8), jnp.float32)
    perms = jnp.asarray(rng.permutation(n)[None])
    desc = ("parity", p.activation, p.nesterov_accelerated_gradient, classes)
    prog = dlm._dl_chunk_program(desc, mlp, tx, "ce", B, n, 1, False, sgd=sgd)
    return prog(params, tx.init(params), X, y, w, perms, jax.random.PRNGKey(2),
                jnp.int32(steps), jnp.float32(p.l1), jnp.float32(p.l2),
                jnp.ones(n, jnp.float32), jnp.bool_(True))


def _close(a, b):
    for x, z in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_allclose(np.asarray(x), np.asarray(z), rtol=0, atol=ATOL)


@pytest.mark.parametrize("steps", [1, 64])
@pytest.mark.parametrize("nesterov", [False, True])
@pytest.mark.parametrize("activation", ["Tanh", "Rectifier"])
def test_kernel_step_matches_the_xla_step(activation, nesterov, steps):
    p = _params(activation, nesterov)
    prm_x, ost_x, _, loss_x, early_x, late_x = _chunk(p, steps, None)
    prm_k, ost_k, _, loss_k, early_k, late_k = _chunk(p, steps, _schedule(p))
    assert int(ost_k.count) == int(ost_x.count) == steps
    _close(prm_k, prm_x)
    _close(ost_k.momentum, ost_x.momentum)
    np.testing.assert_allclose(np.asarray(loss_k), np.asarray(loss_x), rtol=RTOL)
    # the probes' snapshots: after the first steps, and before the last ones
    _close(early_k, early_x)
    _close(late_k[0], late_x[0])
    assert int(late_k[1].count) == int(late_x[1].count)
    _close(late_k[1].momentum, late_x[1].momentum)
    # the weights moved: the comparison is not of two untouched starts
    moved = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree.leaves(prm_x), jax.tree.leaves(dlm._make_mlp(p, 2).init(
            jax.random.PRNGKey(0), jnp.zeros((1, D))))))
    assert moved > 100 * ATOL


def test_kernel_step_matches_the_xla_step_for_three_classes():
    p = _params()
    prm_x, ost_x, _, loss_x, _, _ = _chunk(p, 64, None, classes=3)
    prm_k, ost_k, _, loss_k, _, _ = _chunk(p, 64, _schedule(p), classes=3)
    _close(prm_k, prm_x)
    _close(ost_k.momentum, ost_x.momentum)
    np.testing.assert_allclose(np.asarray(loss_k), np.asarray(loss_x), rtol=RTOL)


def test_penalty_gradient_at_zero_is_jaxs():
    """A zero weight's l1 gradient is +l1 in the XLA step (jax's derivative
    of ``|w|`` at 0): the biases start at zero, so one step tells."""
    p = _params(l1=1e-2, l2=0.0)
    prm_x = _chunk(p, 1, None)[0]
    prm_k = _chunk(p, 1, _schedule(p))[0]
    _close(prm_k, prm_x)


# ---- which fits take the kernel ----


@contextlib.contextmanager
def _one_device():
    old = pm._mesh
    pm.set_mesh(Mesh(np.array(jax.devices("cpu")[:1]), (pm.ROWS_AXIS,)))
    try:
        yield
    finally:
        pm.set_mesh(old)


def _frame(n=640, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 6)).astype(np.float32)
    eta = X[:, 0] - 0.5 * X[:, 1] + 0.25 * X[:, 2] * X[:, 3]
    df = pd.DataFrame(X, columns=[f"x{i}" for i in range(6)])
    df["y"] = np.where(rng.random(n) < 1 / (1 + np.exp(-eta)), "a", "b")
    return Frame.from_pandas(df)


FIT = dict(hidden=[8, 8], activation="Tanh", adaptive_rate=False, rate=0.05,
           momentum_start=0.5, momentum_stable=0.9, momentum_ramp=1000,
           l1=1e-4, l2=1e-3, mini_batch_size=32, epochs=2, seed=5)


def _fit(fr, **kw):
    args = dict(FIT)
    args.update(kw)
    steps0 = mx.counter_value("dl_minibatch_steps_total")
    fused0 = mx.counter_value("dl_fused_steps_total")
    if args.get("autoencoder"):
        model = DeepLearning(**args).train(x=[f"x{i}" for i in range(6)],
                                           training_frame=fr)
    else:
        model = DeepLearning(**args).train(y="y", training_frame=fr)
    return (model, mx.counter_value("dl_minibatch_steps_total") - steps0,
            mx.counter_value("dl_fused_steps_total") - fused0)


def test_qualifying_fit_takes_the_kernel_and_keeps_its_records(monkeypatch):
    """Every step of a qualifying fit goes through the kernel (nbatch x
    epochs), and the fit's records agree with the XLA step's: the probes'
    steps and rows exactly, the weights, the step count and the loss
    history within float32."""
    with _one_device():
        fr = _frame()
        ref, steps_x, fused_x = _fit(fr)
        monkeypatch.setattr(dlm, "_on_tpu", lambda: True)
        model, steps_k, fused_k = _fit(fr)
    nbatch = 640 // 32
    assert steps_x == steps_k == nbatch * FIT["epochs"]
    assert fused_x == 0 and fused_k == nbatch * FIT["epochs"]
    a, b = ref.sgd_probe(), model.sgd_probe()
    for probe in ("early", "late"):
        assert a[probe]["steps"] == b[probe]["steps"]
        np.testing.assert_array_equal(a[probe]["rows"], b[probe]["rows"])
    assert a["late"]["count"] == b["late"]["count"] == nbatch * (FIT["epochs"] - 1)
    _close(b["early"]["after"], a["early"]["after"])
    _close(b["late"]["before"], a["late"]["before"])
    _close(b["late"]["momentum"], a["late"]["momentum"])
    _close(model.output["params"], ref.output["params"])
    hx = [h["loss"] for h in ref.scoring_history]
    assert len(hx) == FIT["epochs"]
    np.testing.assert_allclose([h["loss"] for h in model.scoring_history], hx,
                               rtol=RTOL)


def test_streamed_fit_counts_its_kernel_steps(monkeypatch):
    """The out-of-core epoch loop is the second dispatch site: its block
    programs take the kernel too, and count it step for step, with the
    weights the XLA step's block programs leave."""
    from h2o3_tpu.frame import chunkstore as cs

    monkeypatch.setenv("H2O3_TPU_HBM_WINDOW_BYTES", str(96 * 1024))
    with _one_device():
        fr = _frame(n=4000)
        ref, steps_x, fused_x = _fit(fr, epochs=1, mini_batch_size=64)
        monkeypatch.setattr(dlm, "_on_tpu", lambda: True)
        model, steps_k, fused_k = _fit(fr, epochs=1, mini_batch_size=64)
    assert cs.LAST_STORE_STATS["n_blocks"] > 1
    assert fused_x == 0 and fused_k == steps_k == steps_x > 0
    _close(model.output["params"], ref.output["params"])


@pytest.mark.parametrize("case", [
    "dropout", "adadelta", "autoencoder", "mesh", "sharded", "ctl", "cpu"])
def test_other_fits_keep_the_xla_step(monkeypatch, case):
    """``sharded``: ADADELTA on the 8-device mesh, where the sharded
    gradient lane runs; ``ctl``: that lane's replicated parity control
    (``H2O3_TPU_DL_GRAD_SHARD=ctl``, dropout on)."""
    kw = {"dropout": dict(input_dropout_ratio=0.1),
          "adadelta": dict(adaptive_rate=True),
          "autoencoder": dict(autoencoder=True),
          "sharded": dict(adaptive_rate=True),
          "ctl": dict(input_dropout_ratio=0.1),
          "mesh": {}, "cpu": {}}[case]
    if case != "cpu":
        monkeypatch.setattr(dlm, "_on_tpu", lambda: True)
    if case == "ctl":
        monkeypatch.setenv("H2O3_TPU_DL_GRAD_SHARD", "ctl")
    if case in ("mesh", "sharded", "ctl"):
        assert pm.n_shards() > 1, "8-device conftest pin did not land"
        shard0 = mx.counter_value("tree_collective_bytes_total",
                                  phase="dl_param_gather")
        _, steps, fused = _fit(_frame(n=320), **kw)
        gathered = mx.counter_value("tree_collective_bytes_total",
                                    phase="dl_param_gather") - shard0
        assert (gathered > 0) == (case == "sharded")
    else:
        with _one_device():
            _, steps, fused = _fit(_frame(n=320), **kw)
    assert steps == (320 // 32) * FIT["epochs"]
    assert fused == 0


@pytest.mark.parametrize("lane", ["sharded", "ctl", "mse", "maxout", "wide"])
def test_schedule_refuses_what_the_kernel_does_not_compute(monkeypatch, lane):
    """The sharded and ctl gradient lanes, another loss, another activation
    and a network past the VMEM budget: no schedule, so the XLA step. The
    same network with none of these gets one."""
    monkeypatch.setattr(dlm, "_on_tpu", lambda: True)

    def schedule(activation="Tanh", hidden=HIDDEN, kind="ce", shard_on=False,
                 ctl=0):
        p = _params(activation, hidden=hidden)
        return dlm._fused_schedule(p, dlm._make_mlp(p, 2), kind, shard_on,
                                   ctl, D, B)

    change = {"sharded": dict(shard_on=True), "ctl": dict(ctl=2),
              "mse": dict(kind="mse"), "maxout": dict(activation="Maxout"),
              "wide": dict(hidden=[4096, 4096])}[lane]
    with _one_device():
        assert schedule() == _schedule(_params())
        assert schedule(**change) is None
    assert not dsp.fits([D, 4096, 4096, 2], B) and dsp.fits([D, *HIDDEN, 2], B)
