"""Padded share of the feature slots the fixed-effect solver was handed, from the program's own
photon_fe_slots_total{kind=real|padded}: what the layout makes every pass touch beyond the shard's
stored entries (ELL rows shorter than the widest; 0 where every row fills its slots)."""

UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "fixed-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    name = obs.job.config["fixed_effect"]["name"]
    real = fit_spans.counter_total(obs, "photon_fe_slots_total", coordinate=name, kind="real")
    padded = fit_spans.counter_total(obs, "photon_fe_slots_total", coordinate=name, kind="padded")
    if real is None or padded is None or real + padded == 0:
        return None
    return 100.0 * padded / (real + padded)
