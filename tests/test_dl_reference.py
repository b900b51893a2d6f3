"""DeepLearning against a plain float32 reference, on the CPU at a small
size: 2,048 x 28 rows, five tanh layers of 16, minibatches of 100.

The reference is ``jax.numpy`` under ``jax.default_matmul_precision
("highest")`` with no kernels, batching or program cache: the forward, the
binomial loss with the l1/l2 penalties, its gradients, and H2O's manual-rate
update (``rate_annealing``, per-layer ``rate_decay``, the momentum ramp,
Nesterov). It replays the system's own records (``sgd_probe``: the initial
weights, the rows in the order they were taken, the optimizer's state) and
is compared with the weights the system held, and with its predictions."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models import deeplearning as dlm
from h2o3_tpu.models.deeplearning import DeepLearning
from h2o3_tpu.utils import flightrec
from h2o3_tpu.utils import metrics as mx

ROWS, COLS, BATCH, EPOCHS = 2048, 28, 100, 3
NBATCH = ROWS // BATCH
SGD = dict(hidden=[16] * 5, activation="Tanh", adaptive_rate=False, rate=0.05,
           rate_annealing=1e-4, momentum_start=0.5, momentum_ramp=3000,
           momentum_stable=0.9, l2=1e-3, mini_batch_size=BATCH, epochs=EPOCHS,
           seed=3)


def _data(seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(ROWS, COLS)).astype(np.float32)
    eta = 1.5 * X[:, 0] - X[:, 1] + 0.8 * X[:, 2] * X[:, 3] + np.sin(2 * X[:, 4])
    y = rng.random(ROWS) < 1 / (1 + np.exp(-eta))
    df = pd.DataFrame(X, columns=[f"f{i}" for i in range(COLS)])
    df["label"] = np.where(y, "s", "b")  # class 1 is "s"
    return Frame.from_pandas(df), X, y.astype(np.int32)


@pytest.fixture(scope="module")
def data():
    return _data()


# ---- the plain reference ----


def standardize(X):
    mean = X.astype(np.float64).mean(axis=0)
    sd = X.astype(np.float64).std(axis=0, ddof=1)
    return jnp.asarray((X - mean.astype(np.float32)) / sd.astype(np.float32))


def forward(layers, X):
    with jax.default_matmul_precision("highest"):
        h = X
        for W, b in layers[:-1]:
            h = jnp.tanh(h @ W + b)
        W, b = layers[-1]
        return h @ W + b


def loss(layers, X, y, l1, l2):
    logits = forward(layers, X)
    ll = jax.nn.logsumexp(logits, axis=1) - jnp.take_along_axis(
        logits, y[:, None], axis=1)[:, 0]
    leaves = jax.tree.leaves(layers)
    return (ll.mean() + l2 * 0.5 * sum(jnp.sum(q * q) for q in leaves)
            + l1 * sum(jnp.sum(jnp.abs(q)) for q in leaves))


grad = jax.jit(jax.grad(loss))


def replay(hp, layers, vel, count, Xs, y, rows, steps):
    """H2O's update, step by step: the rate and momentum from the samples
    seen, the rate scaled by rate_decay**layer and (1 - momentum)."""
    layers = [(jnp.asarray(W), jnp.asarray(b)) for W, b in layers]
    vel = [(jnp.asarray(W), jnp.asarray(b)) for W, b in vel]
    start, stable, ramp = hp["momentum_start"], hp["momentum_stable"], hp["momentum_ramp"]
    for k in range(steps):
        idx = rows[k * BATCH:(k + 1) * BATCH]
        g = grad(layers, Xs[idx], jnp.asarray(y[idx]), hp.get("l1", 0.0), hp["l2"])
        n = np.float32(count * BATCH)
        rate = hp["rate"] / (1 + hp["rate_annealing"] * n)
        mu = start + (stable - start) * min(n / ramp, 1.0)
        new_l, new_v = [], []
        for li, ((W, b), (vW, vb), (gW, gb)) in enumerate(zip(layers, vel, g)):
            r = rate * hp.get("rate_decay", 1.0) ** li * (1 - mu)
            pair_w, pair_v = [], []
            for w_, v_, g_ in ((W, vW, gW), (b, vb, gb)):
                d = -r * g_
                v_ = mu * v_ + d
                w_ = w_ + (mu * v_ + d if hp.get("nesterov_accelerated_gradient", True) else v_)
                pair_w.append(w_)
                pair_v.append(v_)
            new_l.append(tuple(pair_w))
            new_v.append(tuple(pair_v))
        layers, vel, count = new_l, new_v, count + 1
    return layers


def gap(a, b):
    """The largest parameter difference, against the largest parameter."""
    fa, fb = (np.concatenate([np.ravel(q) for q in jax.tree.leaves(x)]) for x in (a, b))
    return float(np.max(np.abs(fa - fb)) / np.max(np.abs(fb)))


# ---- the system against it ----


@pytest.mark.parametrize("hp", [
    {},
    {"nesterov_accelerated_gradient": False},
    {"rate_decay": 0.7},
    {"rate_decay": 0.7, "l1": 1e-4, "nesterov_accelerated_gradient": False},
], ids=["nesterov", "classical", "rate_decay", "decay_l1_classical"])
def test_sgd_weights_and_logits_match_the_reference(data, hp, monkeypatch):
    """The first k steps, the last k of the last epoch, the whole training
    (its row order redrawn from the seed as `_run_sync_sgd` draws it) and the
    trained model's probabilities, each against the reference."""
    monkeypatch.setattr(dlm, "PROBE_STEPS", 12)
    fr, X, y = data
    hp = {**SGD, **hp}
    m = DeepLearning(**hp).train(y="label", training_frame=fr)
    rec = m.sgd_probe()
    Xs = standardize(X)
    zero = [(np.zeros_like(W), np.zeros_like(b)) for W, b in rec["initial"]]

    early = rec["early"]
    assert early["steps"] == 12 and len(early["rows"]) == 12 * BATCH
    got = replay(hp, rec["initial"], zero, 0, Xs, y, early["rows"], 12)
    assert gap(early["after"], got) < 2e-6

    late = rec["late"]
    assert late["steps"] == 12 and late["count"] == EPOCHS * NBATCH - 12
    got = replay(hp, late["before"], late["momentum"], late["count"], Xs, y,
                 late["rows"], 12)
    assert gap(late["after"], got) < 2e-6

    rng = np.random.default_rng(hp["seed"])
    rows = np.concatenate([rng.permutation(ROWS)[:NBATCH * BATCH] for _ in range(EPOCHS)])
    got = replay(hp, rec["initial"], zero, 0, Xs, y, rows, EPOCHS * NBATCH)
    assert gap(late["after"], got) < 2e-5

    want = jax.nn.softmax(forward(got, Xs), axis=1)
    np.testing.assert_allclose(m._predict_raw(fr), want, atol=2e-5)


@pytest.mark.parametrize("block", [300, 1024, 2048, 5000])
@pytest.mark.parametrize("response", ["binomial", "regression"])
def test_blocked_predictor_matches_the_whole_forward(data, block, response, monkeypatch):
    """The device predictor's row blocks (a ragged last block at 300 rows
    of 2,048; one block at 2,048 and 5,000) give what the reference's one
    forward gives, and what the host predictor gives."""
    monkeypatch.setattr(dlm, "PRED_BLOCK", block)
    fr, X, y = data
    if response == "regression":
        df = pd.DataFrame(X, columns=[f"f{i}" for i in range(COLS)])
        df["label"] = X[:, 0] * 2 + y
        fr = Frame.from_pandas(df)
    m = DeepLearning(hidden=[16] * 5, activation="Tanh", epochs=1, seed=5,
                     mini_batch_size=BATCH).train(y="label", training_frame=fr)
    layers = [(W, b) for W, b in dlm._layers(m.output["params"], COLS)]
    out = forward(layers, standardize(X))
    dev = np.asarray(m._predict_raw_dev(fr))
    host = m._predict_raw(fr)
    if response == "binomial":
        want = np.asarray(jax.nn.softmax(out, axis=1))
        np.testing.assert_allclose(host, want, atol=2e-6)
        np.testing.assert_array_equal(dev, host[:, 1])
    else:
        np.testing.assert_allclose(host, np.asarray(out[:, 0]), rtol=2e-6, atol=2e-6)
        np.testing.assert_array_equal(dev, host)


@pytest.mark.parametrize("chunk", ["1", "8"])
def test_checkpoint_continuation_mid_ramp_matches_an_uninterrupted_run(
        data, chunk, monkeypatch):
    """The step count lives in the optimizer state, so a continuation from
    a checkpoint taken half-way up the momentum ramp (and the annealing)
    takes the same steps as a run that never stopped."""
    monkeypatch.setenv("H2O3_TPU_DL_EPOCH_CHUNK", chunk)
    fr, _, _ = data
    kw = {**SGD, "momentum_ramp": 6000}  # the ramp ends in the third epoch
    full = DeepLearning(**{**kw, "epochs": 4}).train(y="label", training_frame=fr)
    part = DeepLearning(**{**kw, "epochs": 2}).train(y="label", training_frame=fr)
    assert int(part.output["opt_state"].count) == 2 * NBATCH
    resumed = DeepLearning(**{**kw, "epochs": 4}, checkpoint=part.key).train(
        y="label", training_frame=fr)
    assert int(resumed.output["opt_state"].count) == 4 * NBATCH
    assert resumed.sgd_probe() is None  # a continuation records no probe
    for a, b in zip(jax.tree.leaves(full.output["params"]),
                    jax.tree.leaves(resumed.output["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("nesterov", [True, False])
def test_rate_decay_is_the_factor_between_layers(data, nesterov):
    """rate_decay 0: the first layer learns at ``rate``, every later one at
    ``rate * 0**n`` and keeps its initial weights; with no momentum the
    classical and Nesterov steps are the same."""
    fr, _, _ = data
    kw = dict(hidden=[16] * 3, activation="Tanh", adaptive_rate=False,
              rate=0.05, rate_decay=0.0, epochs=1, seed=2, mini_batch_size=BATCH,
              nesterov_accelerated_gradient=nesterov)
    m = DeepLearning(**kw).train(y="label", training_frame=fr)
    rec = m.sgd_probe()
    assert not np.array_equal(rec["initial"][0][0], rec["late"]["after"][0][0])
    for (W0, b0), (W1, b1) in zip(rec["initial"][1:], rec["late"]["after"][1:]):
        np.testing.assert_array_equal(W0, W1)
        np.testing.assert_array_equal(b0, b1)
    other = DeepLearning(**{**kw, "nesterov_accelerated_gradient": not nesterov}).train(
        y="label", training_frame=fr)
    for a, b in zip(jax.tree.leaves(m.output["params"]),
                    jax.tree.leaves(other.output["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_probe_records_are_on_the_host_and_not_saved(data, tmp_path):
    """The records ``sgd_probe`` reads hold no device memory, and a saved
    model leaves them out: the loaded one predicts the same and has none."""
    from h2o3_tpu.persist import load_model, save_model

    fr, _, _ = data
    m = DeepLearning(**{**SGD, "epochs": 1}).train(y="label", training_frame=fr)
    leaves = jax.tree.leaves(m.output["probe"])
    assert leaves and not any(isinstance(v, jax.Array) for v in leaves)
    assert m.sgd_probe()["late"]["count"] == NBATCH - m.sgd_probe()["late"]["steps"]
    back = load_model(save_model(m, str(tmp_path / "dl_model")))
    assert "probe" not in back.output and back.sgd_probe() is None
    np.testing.assert_array_equal(back._predict_raw(fr), m._predict_raw(fr))


def test_estimator_takes_h2o_sgd_parameters(data):
    from h2o3_tpu.estimators import H2ODeepLearningEstimator

    fr, _, _ = data
    est = H2ODeepLearningEstimator(
        hidden=[8, 8], activation="Tanh", adaptive_rate=False, rate=0.01,
        rate_annealing=1e-5, rate_decay=0.9, momentum_start=0.2,
        momentum_ramp=500, momentum_stable=0.8,
        nesterov_accelerated_gradient=False, epochs=1, seed=1)
    est.train(y="label", training_frame=fr)
    p = est.model.params
    assert (p.rate_annealing, p.rate_decay, p.momentum_start, p.momentum_ramp,
            p.momentum_stable, p.nesterov_accelerated_gradient) == (
        1e-5, 0.9, 0.2, 500, 0.8, False)
    assert isinstance(est.model.output["opt_state"], dlm.H2OSGDState)


def test_spans_and_the_step_counter(data):
    """``dl.datainfo``, ``dl.epochs`` (parent of every ``dispatch:dl_chunk``)
    and ``model.score_metrics`` under the build, and
    ``dl_minibatch_steps_total`` counted at dispatch."""
    from h2o3_tpu import estimators as E

    fr, _, _ = data
    flightrec.reset()
    before = set(mx._TRACES)
    steps0 = mx.counter_value("dl_minibatch_steps_total")
    E.H2ODeepLearningEstimator(hidden=[8], epochs=3, mini_batch_size=BATCH,
                               seed=1).train(y="label", training_frame=fr)
    assert mx.counter_value("dl_minibatch_steps_total") - steps0 == 3 * NBATCH
    (tid,) = set(mx._TRACES) - before
    evs = mx.trace_events(tid)
    by_id = {e["id"]: e for e in evs}
    parent = {e["name"]: by_id[e["parent"]]["name"] for e in evs if e["parent"] in by_id}
    for name in ("dl.datainfo", "dl.epochs", "model.score_metrics"):
        assert parent[name] == "deeplearning.build", (name, parent.get(name))
    disp = [e for e in flightrec.events(kind="dispatch_end")
            if e["site"] == "dl_chunk" and e["trace"] == tid]
    assert disp and all(by_id[d["parent"]]["name"] == "dl.epochs" for d in disp)


def _scopes(lowered):
    return set(re.findall(r"\bph_[a-z_]+[a-z]", lowered.as_text(debug_info=True)))


@pytest.mark.parametrize("adaptive", [True, False])
def test_phase_scopes_are_in_the_programs(adaptive):
    """``ph_dl_perm`` and ``ph_dl_step`` in the epoch program, ``ph_dl_pred``
    in the blocked forward: what ``telemetry.summarize`` splits a capture by."""
    S = jax.ShapeDtypeStruct
    f32 = jnp.float32
    p = dlm.DeepLearningParams(hidden=[8, 8], adaptive_rate=adaptive)
    mlp = dlm._make_mlp(p, 2)
    params = mlp.init(jax.random.PRNGKey(0), jnp.zeros((1, 4)))
    tx = dlm._make_optimizer(p, 16)
    ost = tx.init(params)
    prog = dlm._dl_chunk_program(("scopes", adaptive), mlp, tx, "ce", 16, 64, 2, False)
    low = prog.lower(params, ost, S((64, 4), f32), S((64,), f32), S((64,), f32),
                     S((2, 64), jnp.int32), jax.random.PRNGKey(1), S((), jnp.int32),
                     S((), f32), S((), f32), S((64,), f32), S((), jnp.bool_))
    assert {"ph_dl_perm", "ph_dl_step"} <= _scopes(low)
    pred = dlm._pred_program(mlp, True, 16).lower(params, S((64, 4), f32))
    assert "ph_dl_pred" in _scopes(pred)
