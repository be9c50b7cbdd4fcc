"""photon_fe_line_search_evals_total of the fixed effect, per fit: the value-and-gradient passes its
OWL-QN solves issued (the first one and every line-search trial), one sweep of X each."""

UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "fixed-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    return fit_spans.counter_per_fit(
        obs, "photon_fe_line_search_evals_total", coordinate=obs.job.config["fixed_effect"]["name"]
    )
