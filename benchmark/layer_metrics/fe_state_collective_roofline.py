"""The state's collectives against the chip's interconnect: the bytes a chip moves in them a fit (the
program's ``photon_fe_collective_bytes_total``, both kinds, reckoned from shapes by the solve's own
pass counts) over their device seconds (``fe_state_collective_s``), in percent of the v5e's 1,600 Gbps
a chip (benchmark/shapes_sharded.py ICI_BYTES_PER_S). A 2x2 host uses about half a chip's links."""

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "collectives"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans, shapes_sharded, sharded_ops

    seconds = sharded_ops.collective_seconds(obs)
    moved = fit_spans.counter_per_fit(
        obs, "photon_fe_collective_bytes_total", coordinate=obs.job.config["fixed_effect"]["name"]
    )
    if not seconds or not moved:
        return None
    return shapes_sharded.ici_share(moved, seconds)
