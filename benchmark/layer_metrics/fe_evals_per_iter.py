"""Value-and-gradient passes per OWL-QN iteration of the fixed effect, over the traced part: the
counter photon_fe_line_search_evals_total over the sum of photon_cd_iterations. 1 is a search that
takes its first step every time and evaluates nothing it throws away."""

UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "fixed-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    name = obs.job.config["fixed_effect"]["name"]
    evals = fit_spans.counter_total(obs, "photon_fe_line_search_evals_total", coordinate=name)
    found = fit_spans._series(obs, "photon_cd_iterations", coordinate=name)
    iters = sum(m["sum"] for m in found)
    return evals / iters if evals is not None and iters else None
