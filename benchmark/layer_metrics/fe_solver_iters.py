"""TRON iterations per fixed-effect update, mean over the traced part (photon_cd_iterations)."""

UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "fixed-effect solve"
MOVES = "fit_s"


def read(obs):
    return obs.summary_mean("photon_cd_iterations", coordinate=obs.job.config["fixed_effect"]["name"])
