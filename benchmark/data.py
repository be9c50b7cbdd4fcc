"""Seeded GLMix data for the benchmark's cells.

ONE data set per configuration, MIRRORED by the run's seed. ``scale.data_seed``
draws every value (features, coefficients, labels; user of rank r owns a fixed
quota of rows, Zipf, at least one), and ``--seed`` draws one sign for every
feature column but the intercepts (:class:`Mirror`): the run trains on the data
reflected in those coordinates. IEEE arithmetic is symmetric under negation, so
every product, sum, norm and comparison of the solvers is the same number for
every seed -- the same margins, losses, iteration counts, trust-region and
line-search decisions, bit for bit -- and the coefficients come out reflected.
No seed does more work than another, and every seed finds the same programs.

Why so little moves with the seed (PERF.md section 6, PR 24): the solvers stop
on thresholds that f32 rounding decides. When the seed drew the values, TRON
took an iteration more or fewer with the seed and ``fit_s`` moved 10% (the
driver's first check); when it only re-ordered the rows of one data set, the
other summation order did the same (``fit_s`` 1.14 to 1.37 s over six orders,
my chip runs). A reflection is the one change of the inputs that leaves the
arithmetic alone.

The dense fixed-effect matrix never exists on the host: it is generated on the
device in row chunks, one jitted call, together with the fixed-effect margin
X @ w. Per-user features, ids and labels are host numpy
(they feed ``build_random_effect_dataset`` the way ``cli train`` feeds it).
Generator copied in spirit from ``bench.py`` ``build_data`` (f32, intercept as
the LAST of d columns so d stays a multiple of 128 and the fused kernels
engage); what changed is the fixed quotas and the device-made matrix.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


def user_quotas(n_rows: int, n_users: int, zipf: float) -> np.ndarray:
    """Rows owned by the user of rank r (0 = most active): floor(n * p_r)
    under p_r ~ (r+1)^-zipf, at least 1, the remainder handed out one row at a
    time by rank (taken back from the largest owners when the floor of 1
    overshoots). Sums to ``n_rows`` exactly; depends on no seed."""
    if n_users > n_rows:
        raise ValueError(f"{n_users} users cannot each own one of {n_rows} rows")
    p = 1.0 / np.arange(1, n_users + 1, dtype=np.float64) ** zipf
    p /= p.sum()
    q = np.maximum(np.floor(n_rows * p).astype(np.int64), 1)
    diff = int(n_rows - q.sum())
    while diff != 0:
        if diff > 0:
            take = min(diff, n_users)
            q[:take] += 1
            diff -= take
        else:
            can = np.flatnonzero(q > 1)[: -diff]
            q[can] -= 1
            diff += len(can)
    return q


@dataclasses.dataclass
class Mirror:
    """The run's seed as data: +1 or -1 for every feature column, +1 for the
    intercepts (the last column of each bag)."""

    fixed: np.ndarray  # f32[d]
    user: np.ndarray  # f32[d_re]


def draw_mirror(seed: int, d: int, d_re: int) -> Mirror:
    rng = np.random.default_rng(seed)  # takes any whole number, past 2**31 too

    def signs(width: int) -> np.ndarray:
        s = (2 * rng.integers(0, 2, size=width) - 1).astype(np.float32)
        s[-1] = 1.0
        return s

    return Mirror(fixed=signs(d), user=signs(d_re))


@dataclasses.dataclass
class HostData:
    """Everything of one data set that lives on the host."""

    user_of_row: np.ndarray  # i64[n]
    user_features: np.ndarray  # f32[n, d_re], last column = 1 (intercept)
    labels: np.ndarray  # f32[n]


@dataclasses.dataclass
class Truth:
    """The generating model (only the generator and the tests look at it)."""

    w_fixed: np.ndarray  # f32[d]
    w_user: np.ndarray  # f32[n_users, d_re]


def draw_truth(rng: np.random.Generator, d: int, n_users: int, d_re: int) -> Truth:
    return Truth(
        w_fixed=(rng.standard_normal(d) / np.sqrt(d)).astype(np.float32),
        w_user=(rng.standard_normal((n_users, d_re)) / np.sqrt(d_re)).astype(np.float32),
    )


def host_rows(
    rng: np.random.Generator,
    user_of_row: np.ndarray,
    fixed_margin: np.ndarray,
    truth: Truth,
    signs: Optional[np.ndarray] = None,
) -> HostData:
    """Per-user features and Bernoulli labels for rows whose fixed-effect
    margin is already known (it comes back from the device). Labels are drawn
    from the data as it is; ``signs`` (f32[d_re]) then mirrors the features."""
    n = len(user_of_row)
    d_re = truth.w_user.shape[1]
    ex = rng.standard_normal((n, d_re), dtype=np.float32)
    ex[:, -1] = 1.0
    z = fixed_margin.astype(np.float32) + np.einsum(
        "nd,nd->n", ex, truth.w_user[user_of_row]
    )
    labels = (rng.random(n, dtype=np.float32) < 1.0 / (1.0 + np.exp(-z))).astype(np.float32)
    if signs is not None:
        ex *= signs
    return HostData(user_of_row=user_of_row, user_features=ex, labels=labels)


def train_users(rng: np.random.Generator, quotas: np.ndarray) -> np.ndarray:
    """User of each training row: the fixed quotas, shuffled."""
    owners = np.repeat(np.arange(len(quotas), dtype=np.int64), quotas)
    return owners[rng.permutation(len(owners))]


def validation_users(rng: np.random.Generator, quotas: np.ndarray, n: int) -> np.ndarray:
    """Validation rows draw their user by training activity (all seen)."""
    p = quotas / quotas.sum()
    return rng.choice(len(quotas), size=n, p=p).astype(np.int64)


def device_features(seed: int, n_rows: int, d: int, chunk_rows: int, w_fixed, mesh=None,
                    stream: int = 0, signs: Optional[np.ndarray] = None):
    """(X f32[n, d], X @ w f32[n]) made on the device: the data set of ``seed``
    (a configuration's ``data_seed``), its columns mirrored by ``signs``.

    Rows are drawn chunk by chunk (``chunk_rows`` rows, key = fold_in(seed key,
    global chunk index)) into one buffer, so nothing but the result and one
    chunk is ever resident; the last column is the intercept. With a mesh the
    rows are sharded over its ``data`` axis and every chip draws its own
    chunks: the matrix is the same function of (seed, n, chunk) on any number
    of chips. ``stream`` separates the training matrix from the validation one.
    The margin is taken before the mirror, so it is the same number under any
    signs; the signs are an argument of the program, so every run's seed finds
    the same program in the cache.
    """
    import jax
    import jax.numpy as jnp

    n_shards = 1 if mesh is None else mesh.shape["data"]
    if n_rows % (n_shards * chunk_rows):
        raise ValueError(
            f"rows {n_rows} must be a multiple of chips x chunk = {n_shards} x {chunk_rows}"
        )
    chunks_per_shard = n_rows // n_shards // chunk_rows
    # seeds reach past 2**31: fold the two halves in, never truncate
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(int(seed) & 0xFFFFFFFF), int(seed) >> 32), stream
    )

    def shard_rows(key, w, signs, shard_index):
        def body(i, carry):
            x, z = carry
            k = jax.random.fold_in(key, shard_index * chunks_per_shard + i)
            xc = jax.random.normal(k, (chunk_rows, d), jnp.float32).at[:, -1].set(1.0)
            zc = jnp.dot(xc, w, precision=jax.lax.Precision.HIGHEST)
            x = jax.lax.dynamic_update_slice(x, xc * signs, (i * chunk_rows, 0))
            z = jax.lax.dynamic_update_slice(z, zc, (i * chunk_rows,))
            return x, z

        rows = chunks_per_shard * chunk_rows
        init = (jnp.zeros((rows, d), jnp.float32), jnp.zeros((rows,), jnp.float32))
        return jax.lax.fori_loop(0, chunks_per_shard, body, init)

    w = jnp.asarray(w_fixed, jnp.float32)
    signs = jnp.ones(d, jnp.float32) if signs is None else jnp.asarray(signs, jnp.float32)
    if mesh is None:
        return jax.jit(lambda k, w, s: shard_rows(k, w, s, 0))(key, w, signs)

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def per_shard(k, w, s):
        return shard_rows(k, w, s, jax.lax.axis_index("data"))

    return jax.jit(
        shard_map(
            per_shard, mesh=mesh, in_specs=(P(), P(), P()),
            out_specs=(P("data", None), P("data")), check_vma=False,
        )
    )(key, w, signs)


def dense_coo(x: np.ndarray):
    """Dense [n, d] -> the (rows, cols, vals f64) triplets RawDataset holds."""
    n, d = x.shape
    return (
        np.repeat(np.arange(n, dtype=np.int64), d),
        np.tile(np.arange(d, dtype=np.int64), n),
        x.reshape(-1).astype(np.float64),
    )
