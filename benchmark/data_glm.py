"""Seeded count-response data for the Poisson + elastic-net cells.

ONE data set per configuration (``scale.data_seed`` draws every value),
MIRRORED by the run's seed exactly as benchmark/data.py mirrors the GLMix
cells': a sign for every feature column but the intercept. Column means flip
with their columns, variances and ``||w||_1`` do not change, and IEEE
arithmetic is symmetric under negation, so every seed does the same sums,
comparisons, line-search trials and orthant decisions, bit for bit.

The law (the configuration's ``assumed``): column j of the raw matrix is
s_j * (mu_j + sigma_j * eps_ij), eps iid N(0,1), sigma_j log-uniform on
[0.1, 10], mu_j = sigma_j * N(0,1), the intercept last (all ones), s the
mirror. Columns have means and scales of their own so that a wrong shift or
factor in the standardised solve shows (with N(0,1) columns the shift is ~0).
The truth lives in the standardised space: ``support`` of the feature columns
carry a coefficient, scaled so that the margin's standard deviation is
``margin_std``; the intercept sets the mean count. Labels are
y ~ Poisson(exp(margin)), drawn on the host from the margin the device hands
back (taken before the mirror and the scaling: the same number under any seed).

The dense matrix never exists on the host: it is drawn on the device in row
chunks by one jitted call, as benchmark/data.py draws the GLMix cells'.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Law:
    """Everything the generator needs besides the noise (host, float32)."""

    mu: np.ndarray  # f32[d], 0 for the intercept
    sigma: np.ndarray  # f32[d], 1 for the intercept (whose column is the constant 1)
    beta: np.ndarray  # f32[d] truth on the standardised columns; beta[-1] is the intercept


def draw_law(data_seed: int, d: int, support: int, margin_std: float, mean_count: float) -> Law:
    rng = np.random.default_rng(data_seed)
    sigma = (10.0 ** rng.uniform(-1.0, 1.0, size=d)).astype(np.float32)
    mu = (sigma * rng.standard_normal(d)).astype(np.float32)
    sigma[-1], mu[-1] = 1.0, 0.0
    beta = np.zeros(d, np.float64)
    on = rng.choice(d - 1, size=support, replace=False)
    beta[on] = rng.standard_normal(support)
    beta *= margin_std / np.linalg.norm(beta)
    # E exp(b + N(0, s^2)) = exp(b + s^2 / 2)
    beta[-1] = np.log(mean_count) - 0.5 * margin_std**2
    return Law(mu=mu, sigma=sigma, beta=beta.astype(np.float32))


def device_features(seed: int, n_rows: int, chunk_rows: int, law: Law, signs: np.ndarray,
                    stream: int = 0):
    """(X f32[n, d], margin f32[n]) made on ONE device: the data set of
    ``seed`` (a configuration's ``data_seed``) under ``law``, its columns
    mirrored by ``signs``. Key = fold_in(seed key, chunk index), one chunk of
    noise resident at a time; ``stream`` separates training from validation.
    Law and signs are arguments of the program: every run finds it cached."""
    import jax
    import jax.numpy as jnp

    if n_rows % chunk_rows:
        raise ValueError(f"rows {n_rows} must be a multiple of the chunk {chunk_rows}")
    d = len(law.mu)
    chunks = n_rows // chunk_rows
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(int(seed) & 0xFFFFFFFF), int(seed) >> 32), stream
    )

    def draw(key, mu, sigma, beta, signs):
        def body(i, carry):
            x, z = carry
            eps = jax.random.normal(jax.random.fold_in(key, i), (chunk_rows, d), jnp.float32)
            eps = eps.at[:, -1].set(1.0)  # the intercept: mu 0, sigma 1
            zc = jnp.dot(eps, beta, precision=jax.lax.Precision.HIGHEST)
            xc = signs * (mu + sigma * eps)
            x = jax.lax.dynamic_update_slice(x, xc, (i * chunk_rows, 0))
            z = jax.lax.dynamic_update_slice(z, zc, (i * chunk_rows,))
            return x, z

        init = (jnp.zeros((n_rows, d), jnp.float32), jnp.zeros((n_rows,), jnp.float32))
        return jax.lax.fori_loop(0, chunks, body, init)

    as32 = lambda a: jnp.asarray(a, jnp.float32)
    return jax.jit(draw)(key, as32(law.mu), as32(law.sigma), as32(law.beta), as32(signs))


def draw_counts(data_seed: int, margin: np.ndarray, stream: int = 0) -> np.ndarray:
    """y ~ Poisson(exp(margin)) on the host, f32, by inversion of the CDF with
    ONE uniform a row: a margin that differs in its last bit between two
    backends can move its own row's count at most (numpy's ``poisson`` draws
    a rate-dependent number of uniforms a row, so one such bit re-deals every
    later row). A generator of its own per stream."""
    rng = np.random.default_rng([int(data_seed), 7919, int(stream)])
    u = rng.random(len(margin))
    rate = np.exp(margin.astype(np.float64))
    term = np.exp(-rate)  # P(y = 0)
    cdf = term.copy()
    counts = np.zeros(len(margin), np.float32)
    for k in range(1, 1000):
        above = u > cdf
        if not above.any():
            break
        counts += above
        term = term * rate / k
        cdf += term
    return counts
