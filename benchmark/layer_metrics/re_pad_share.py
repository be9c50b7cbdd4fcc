"""Padded share of the random-effect solve's rows: 1 - real rows / sum over the (K, S) buckets of
E_b * K_b, from the built blocks' shapes (the buckets are the program's: _size_buckets)."""

UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "random-effect solve"
MOVES = "fit_s"


def read(obs):
    from photon_ml_tpu.game.coordinate import _entity_shard_align, _size_buckets

    ds = obs.job.datasets.get(obs.job.config["random_effect"]["name"])
    if ds is None:
        return None
    e, k, _ = ds.blocks.features.shape
    segments = _size_buckets(ds, align=_entity_shard_align(ds.blocks)) or [(0, e, k, 0)]
    padded = sum((end - start) * kb for start, end, kb, _ in segments)
    return 100.0 * (1.0 - float(ds.entity_counts.sum()) / padded)
