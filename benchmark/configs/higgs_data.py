"""Higgs-shaped data, made on the device from the seed (a copy of
``bench._make_data_device``'s generator, column by column so that set-up has
no ``(n, 28)`` transient, which the TPU would tile to 128 lanes).

28 standard-normal f32 features and a Bernoulli label from the six-term
logit. Data takes the place of weights: the same seed gives the same frame.
The arrays are the benchmark's; the program sees them wrapped in its own
``Frame``/``Vec`` and the plain references see them pulled to the host.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Data:
    frame: object  # the program's Frame, registered in its DKV
    columns: list  # device arrays, (npad,) f32 each, NaN in the pad rows
    label: object  # device array, (npad,) int8, -1 in the pad rows
    rows: int

    def host(self) -> tuple[np.ndarray, np.ndarray]:
        """(X (rows, C) f32, y (rows,) f64), pulled for the plain reference."""
        X = np.stack([np.asarray(c)[: self.rows] for c in self.columns], axis=1)
        return X, np.asarray(self.label)[: self.rows].astype(np.float64)

    def rewrap(self) -> None:
        """The same device columns under a new frame of the program's, as a
        client that loads its data anew has: the old frame, and whatever the
        program cached on it, is dropped."""
        from h2o3_tpu.cluster.registry import DKV

        DKV.remove(self.frame.key)
        self.frame = wrap(self.columns, self.label, self.rows)

    def drop(self) -> None:
        """Free the device copy (the reference runs after this)."""
        from h2o3_tpu.cluster.registry import DKV

        DKV.remove(self.frame.key)
        self.frame, self.columns, self.label = None, [], None


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def make_frame(rows: int, cols: int, seed: int, first_rows: int | None = None) -> Data:
    """The frame of ``rows`` x ``cols`` features + ``label``, in one jitted
    call. ``first_rows`` (rehearsals of the half-batch fault only) registers
    a frame that holds the first rows of the same data."""
    import functools

    import jax
    import jax.numpy as jnp

    from h2o3_tpu.parallel.mesh import pad_to_shards, row_sharding

    n = int(first_rows or rows)
    npad = pad_to_shards(n)

    @functools.partial(jax.jit, out_shardings=row_sharding())
    def gen(key):
        keys = jax.random.split(key, cols + 1)
        # each column is drawn at the FULL row count so that a frame of the
        # first rows holds the same values as the whole frame's head
        xs = [jax.random.normal(keys[i], (rows,), jnp.float32)[:n]
              for i in range(cols)]
        eta = (1.5 * xs[0] - xs[1] + 0.8 * xs[2] * xs[3]
               + jnp.sin(2 * xs[4]) + 0.5 * xs[5] ** 2 - 1.0)
        u = jax.random.uniform(keys[cols], (rows,))[:n]
        y = (u < jax.nn.sigmoid(eta)).astype(jnp.int8)
        padw = (0, npad - n)
        xs = [jnp.pad(x, padw, constant_values=jnp.nan) for x in xs]
        return xs, jnp.pad(y, padw, constant_values=-1)

    xs, y = gen(seed_key(seed))
    return Data(wrap(xs, y, n), list(xs), y, n)


def wrap(xs, y, n: int):
    """The program's ``Frame`` over the device columns, registered in its DKV."""
    from h2o3_tpu.frame.frame import CAT, NUM, Frame, Vec

    vecs = [Vec(x, NUM, name=f"f{i}", nrow=n) for i, x in enumerate(xs)]
    vecs.append(Vec(y, CAT, name="label", nrow=n, domain=("b", "s")))
    return Frame(vecs, register=True)


# ---- what every configuration on this frame shares ----


def frame_for(cfg: dict, seed: int) -> Data:
    """A configuration's ``make_frame``: its ``rows`` x ``cols`` from the seed."""
    return make_frame(cfg["rows"], cfg["cols"], seed)


def train(est, data: Data):
    """The timed call: the program's public ``train()`` on the resident frame."""
    return est.train(y="label", training_frame=data.frame)


def release(est) -> None:
    """Drop the finished model from the program's DKV (it pins its frame)."""
    from h2o3_tpu.cluster.registry import DKV

    DKV.remove(est.model.key)
