"""Median cd.coordinate span of the random-effect coordinate."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "random-effect solve"
MOVES = "fit_s"


def read(obs):
    return obs.median_span_s("cd.coordinate", coordinate=obs.job.config["random_effect"]["name"])
