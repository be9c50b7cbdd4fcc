"""GB the random-effect coordinates' entity blocks hold as stored, bucket by bucket (gauge
photon_re_block_store_bytes, summed over coordinates; a [E, K, S] plane would hold E * K * S * 4).
None on a program without the gauge."""

UNIT = "GB"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "random-effect solve"
MOVES = "setup_s"


def read(obs):
    from benchmark import fit_spans

    total = fit_spans.counter_total(obs, "photon_re_block_store_bytes")
    return None if total is None else total / 1e9
