"""Median cd.coordinate span of the fixed-effect coordinate."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "fixed-effect solve"
MOVES = "fit_s"


def read(obs):
    return obs.median_span_s("cd.coordinate", coordinate=obs.job.config["fixed_effect"]["name"])
