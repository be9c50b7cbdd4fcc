"""Per-feature summary statistics.

Reference: photon-lib .../stat/FeatureDataStatistics.scala:44-139 (mean, var,
min, max, numNonZeros per feature) written by
ModelProcessingUtils.writeBasicStatistics as FeatureSummarizationResultAvro
records (GameTrainingDriver.scala:581-612). Also feeds NormalizationContext
construction.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from ..io.avro import write_avro_file
from ..io.data import RawDataset
from ..io.index_map import IndexMap, split_feature_key
from ..io.schemas import FEATURE_SUMMARIZATION_RESULT_AVRO
from ..robust.retry import io_call


# rows of a dense batch summarized by one step of the device pass
_DEVICE_CHUNK_ROWS = 65536


def compute_feature_statistics(data, shard: Optional[str] = None) -> Dict[str, np.ndarray]:
    """Per-feature statistics of what it is given: a ``RawDataset`` shard
    (``shard`` names it; the host walks its COO) or a prepared dense
    ``LabeledBatch`` (no ``shard``; one pass over X on the device, for a batch
    that never existed as a COO). Both return the same keys, in float64."""
    if isinstance(data, RawDataset):
        if shard is None:
            raise TypeError("a RawDataset's statistics need the shard's name")
        return _coo_statistics(data, shard)
    features = getattr(data, "features", None)
    if shard is not None or features is None or not features.is_dense:
        raise TypeError(
            "feature statistics take a RawDataset and a shard name, or a "
            f"dense LabeledBatch alone; got {type(data).__name__}"
        )
    return _dense_statistics(features.dense)


def _finish(n, s1, m2, nnz, fmin, fmax) -> Dict[str, np.ndarray]:
    max_mag = np.maximum(np.abs(fmin), np.abs(fmax))
    return {
        "mean": s1 / max(n, 1),
        "variance": np.maximum(m2 / max(n, 1), 0.0),
        "min": fmin,
        "max": fmax,
        "num_nonzeros": nnz,
        "max_magnitude": max_mag,
        "count": np.full(len(s1), float(n)),
    }


def _dense_statistics(x) -> Dict[str, np.ndarray]:
    """Column moments of a dense device matrix ``x`` [n, d], every row
    counted. One jitted pass in chunks of rows: a chunk gives its column sums,
    its squared deviations about ITS OWN mean (two sweeps of a chunk that is
    already on the chip; E[x^2] - E[x]^2 would cancel in f32), min, max and
    non-zeros; the [chunks, d] partials are fetched once and combined in
    float64 on the host (Chan et al.'s pairwise update). Set-up work: d-sized
    fetches, never inside a fit."""
    import jax
    import jax.numpy as jnp

    n, d = x.shape
    rows = min(n, _DEVICE_CHUNK_ROWS)
    whole = n // rows

    def moments(xc):
        s1 = jnp.sum(xc, axis=0)
        dev = xc - s1 / xc.shape[0]
        return (s1, jnp.sum(dev * dev, axis=0), jnp.sum(xc != 0, axis=0, dtype=jnp.int32),
                jnp.min(xc, axis=0), jnp.max(xc, axis=0))

    @jax.jit
    def chunked(x):
        return jax.lax.map(
            lambda i: moments(jax.lax.dynamic_slice(x, (i * rows, 0), (rows, d))),
            jnp.arange(whole),
        )

    parts = [(rows, p) for p in zip(*(np.asarray(a) for a in jax.device_get(chunked(x))))]
    if whole * rows < n:
        tail = jax.device_get(jax.jit(moments)(x[whole * rows:]))
        parts.append((n - whole * rows, tuple(np.asarray(a) for a in tail)))

    count = 0
    s1 = np.zeros(d)
    m2 = np.zeros(d)
    nnz = np.zeros(d)
    fmin = np.full(d, np.inf)
    fmax = np.full(d, -np.inf)
    for rows_c, (s1_c, m2_c, nnz_c, min_c, max_c) in parts:
        s1_c = s1_c.astype(np.float64)
        if count:
            delta = s1_c / rows_c - s1 / count
            m2 += delta * delta * (count * rows_c / (count + rows_c))
        m2 += m2_c
        s1 += s1_c
        count += rows_c
        nnz += nnz_c
        fmin = np.minimum(fmin, min_c)
        fmax = np.maximum(fmax, max_c)
    return _finish(count, s1, m2, nnz, fmin, fmax)


def _coo_statistics(raw: RawDataset, shard: str) -> Dict[str, np.ndarray]:
    """Weighted-count statistics over a shard's COO features (zeros included
    in mean/variance via implicit zero entries, matching a dense summary).

    Multi-process: each host computes moment sums over ITS row slice and the
    d-sized sums are allgathered and combined, so every host returns the
    GLOBAL statistics (the reference computes summaries over the full
    DataFrame, GameTrainingDriver.scala:555-612 — here the cross-host reduce
    is the d-vector exchange, not a row shuffle)."""
    rows, cols, vals = raw.shard_coo[shard]
    d = raw.shard_dims[shard]
    # padded rows (multi-process equal-share) carry no features and must not
    # inflate the count denominator
    n = raw.true_rows if raw.true_rows is not None else raw.n_rows
    s1 = np.zeros(d)
    s2 = np.zeros(d)
    np.add.at(s1, cols, vals)
    np.add.at(s2, cols, vals * vals)
    nnz = np.bincount(cols, minlength=d).astype(np.float64)
    fmin = np.full(d, np.inf)
    fmax = np.full(d, -np.inf)
    np.minimum.at(fmin, cols, vals)
    np.maximum.at(fmax, cols, vals)

    import jax

    if jax.process_count() > 1:
        from ..parallel import multihost

        parts = multihost.allgather_object((s1, s2, nnz, fmin, fmax, n))
        s1 = np.sum([p[0] for p in parts], axis=0)
        s2 = np.sum([p[1] for p in parts], axis=0)
        nnz = np.sum([p[2] for p in parts], axis=0)
        fmin = np.min([p[3] for p in parts], axis=0)
        fmax = np.max([p[4] for p in parts], axis=0)
        n = sum(p[5] for p in parts)

    # a column with fewer entries than rows holds implicit zeros
    sparse = nnz < n
    fmin = np.where(sparse, np.minimum(fmin, 0.0), fmin)
    fmax = np.where(sparse, np.maximum(fmax, 0.0), fmax)
    mean = s1 / max(n, 1)
    return _finish(n, s1, np.maximum(s2 - n * mean**2, 0.0), nnz, fmin, fmax)


def save_feature_statistics(path: str, stats: Dict[str, np.ndarray], index_map: IndexMap):
    """Write FeatureSummarizationResultAvro records (one per feature)."""
    d = len(index_map)

    def records():
        for i in range(d):
            key = index_map.get_feature_name(i)
            if key is None:
                continue
            name, term = split_feature_key(key)
            yield {
                "featureName": name,
                "featureTerm": term,
                "metrics": {
                    "mean": float(stats["mean"][i]),
                    "variance": float(stats["variance"][i]),
                    "min": float(stats["min"][i]),
                    "max": float(stats["max"][i]),
                    "numNonzeros": float(stats["num_nonzeros"][i]),
                },
            }

    # atomic via write_avro_file; transient failures retry (Spark task-retry
    # parity — a stats write must not kill a run that just finished training)
    io_call(
        write_avro_file, path, FEATURE_SUMMARIZATION_RESULT_AVRO, list(records()),
        site="io.stats_save",
    )
