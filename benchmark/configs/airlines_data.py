"""Airline-on-time-shaped data, made on the device from the seed.

The predictors of szilard/benchm-ml's logistic regressions on the ASA Data
Expo 2009 flights: six categorical columns (Month, DayofMonth, DayOfWeek,
UniqueCarrier, Origin, Dest) and two numeric ones (DepTime as hhmm, Distance
in miles); the label ``dep_delayed_15min`` (N/Y). The cardinalities and
distributions are the configuration's (``glm_airlines.json``: ``data``):
the calendar columns uniform, the carrier and the two airports Zipf-skewed,
DepTime's hour from a day-shaped normal and its minute uniform, Distance
log-normal. A column's codes are a seeded permutation of its levels'
popularity ranks: the first level, which the design drops as the reference,
is drawn from the seed and is mostly a small one, as H2O's lexical order
makes a small airport (ABE) or carrier (9E) the reference in the source. The label is Bernoulli from a logit of per-level effects drawn
from the seed plus the two numerics. Data takes the place of weights: the
same seed gives the same frame.

``make_frame`` first imports the factored design's public name from the
program: a program without it fails here, in seconds, instead of fitting on
a dense one-hot design that the chip cannot hold.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .higgs_data import release, seed_key  # noqa: F401

CATS = ("Month", "DayofMonth", "DayOfWeek", "UniqueCarrier", "Origin", "Dest")
NUMS = ("DepTime", "Distance")
LABEL = ("N", "Y")


def domain(name: str, levels: int) -> tuple[str, ...]:
    """Level names whose sorted order is their code order."""
    return tuple(f"{name[:3].lower()}{i:03d}" for i in range(levels))


def level_probs(spec: dict) -> np.ndarray:
    """A column's level probabilities: uniform, or Zipf ``k ** -s`` by rank."""
    k = np.arange(1, int(spec["levels"]) + 1, dtype=np.float64)
    p = k ** -float(spec.get("zipf", 0.0))
    return p / p.sum()


@dataclasses.dataclass
class Data:
    frame: object  # the program's Frame, registered in its DKV
    codes: list  # device arrays, (npad,) int8/int16 each, -1 in the pad rows
    nums: list  # device arrays, (npad,) f32 each, NaN in the pad rows
    label: object  # device array, (npad,) int8, -1 in the pad rows
    rows: int
    cfg: dict

    @property
    def columns(self) -> list:
        return self.codes + self.nums

    def host(self) -> tuple[dict, np.ndarray]:
        """({"codes": (rows, 6) int16, "num": (rows, 2) f64}, y (rows,) f64),
        pulled for the plain reference."""
        codes = np.stack([np.asarray(c)[: self.rows] for c in self.codes],
                         axis=1).astype(np.int16)
        num = np.stack([np.asarray(x)[: self.rows] for x in self.nums],
                       axis=1).astype(np.float64)
        y = np.asarray(self.label)[: self.rows].astype(np.float64)
        return {"codes": codes, "num": num}, y

    def rewrap(self) -> None:
        from h2o3_tpu.cluster.registry import DKV

        DKV.remove(self.frame.key)
        self.frame = wrap(self.cfg, self.codes, self.nums, self.label, self.rows)

    def drop(self) -> None:
        """Free the device copy (the reference runs after this)."""
        from h2o3_tpu.cluster.registry import DKV

        DKV.remove(self.frame.key)
        self.frame, self.codes, self.nums, self.label = None, [], [], None


def make_frame(cfg: dict, seed: int, first_rows: int | None = None) -> Data:
    """The configuration's frame from the seed, in one jitted call.
    ``first_rows`` (the half-batch fault only) registers a frame that holds
    the first rows of the same data: every column is drawn at the full row
    count and cut."""
    import functools

    import jax
    import jax.numpy as jnp

    from h2o3_tpu.models.datainfo import FactoredDesign  # noqa: F401  (fail fast)
    from h2o3_tpu.parallel.mesh import pad_to_shards, row_sharding

    spec = cfg["data"]
    rows = int(cfg["rows"])
    n = int(first_rows or rows)
    npad = pad_to_shards(n)
    cdfs = [jnp.asarray(np.cumsum(level_probs(spec["columns"][c]))[:-1],
                        jnp.float32) for c in CATS]
    sds = [float(spec["columns"][c]["effect_sd"]) for c in CATS]
    lab = spec["label"]

    @functools.partial(jax.jit, out_shardings=row_sharding())
    def gen(key):
        keys = jax.random.split(key, 3 * len(CATS) + 4)
        eta = jnp.full((rows,), float(lab["base_logit"]), jnp.float32)
        codes = []
        for i, cdf in enumerate(cdfs):
            u = jax.random.uniform(keys[i], (rows,))
            rank = jnp.searchsorted(cdf, u, side="right")
            code_of_rank = jax.random.permutation(
                keys[2 * len(CATS) + i], cdf.shape[0] + 1).astype(jnp.int32)
            c = code_of_rank[rank]
            effect = sds[i] * jax.random.normal(keys[len(CATS) + i],
                                                (cdf.shape[0] + 1,))
            eta = eta + effect[c]
            codes.append(c)
        k = 3 * len(CATS)
        dt = spec["DepTime"]
        hour = jnp.clip(jnp.floor(dt["hour_mean"] + dt["hour_sd"]
                                  * jax.random.normal(keys[k], (rows,))), 0, 23)
        minute = jnp.floor(60.0 * jax.random.uniform(keys[k + 1], (rows,)))
        deptime = hour * 100.0 + minute
        ds = spec["Distance"]
        dist = jnp.clip(jnp.round(ds["median"] * jnp.exp(
            ds["log_sd"] * jax.random.normal(keys[k + 2], (rows,)))),
            ds["min"], ds["max"])
        eta = (eta + lab["per_hour"] * (deptime / 100.0 - dt["hour_mean"])
               + lab["per_log_distance"] * jnp.log(dist / ds["median"]))
        y = (jax.random.uniform(keys[k + 3], (rows,)) < jax.nn.sigmoid(eta))
        padw = (0, npad - n)
        codes = [jnp.pad(c[:n].astype(jnp.int8 if len(cdf) < 127 else jnp.int16),
                         padw, constant_values=-1)
                 for c, cdf in zip(codes, cdfs)]
        nums = [jnp.pad(x[:n].astype(jnp.float32), padw, constant_values=jnp.nan)
                for x in (deptime, dist)]
        return codes, nums, jnp.pad(y[:n].astype(jnp.int8), padw, constant_values=-1)

    codes, nums, y = gen(seed_key(seed))
    return Data(wrap(cfg, codes, nums, y, n), list(codes), list(nums), y, n, cfg)


def wrap(cfg: dict, codes, nums, y, n: int):
    """The program's ``Frame`` over the device columns, registered in its DKV."""
    from h2o3_tpu.frame.frame import CAT, NUM, Frame, Vec

    cols = cfg["data"]["columns"]
    vecs = [Vec(c, CAT, name=name, nrow=n,
                domain=domain(name, int(cols[name]["levels"])))
            for name, c in zip(CATS, codes)]
    vecs += [Vec(x, NUM, name=name, nrow=n) for name, x in zip(NUMS, nums)]
    vecs.append(Vec(y, CAT, name="dep_delayed_15min", nrow=n, domain=LABEL))
    return Frame(vecs, register=True)


def train(est, data: Data):
    """The timed call: the program's public ``train()`` on the resident frame."""
    return est.train(x=list(CATS + NUMS), y="dep_delayed_15min",
                     training_frame=data.frame)
