"""L-BFGS iterations per entity of the random effect, mean over the traced part (photon_cd_iterations)."""

UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "random-effect solve"
MOVES = "fit_s"


def read(obs):
    if not obs.n_fits:
        return None
    return obs.summary_mean("photon_cd_iterations", coordinate=obs.job.config["random_effect"]["name"])
