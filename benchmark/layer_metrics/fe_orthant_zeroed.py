"""photon_fe_orthant_zeroed_total of the fixed effect, per fit: coefficients that OWL-QN's orthant
projection set to zero, summed over the accepted steps (the orthant's churn)."""

UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "fixed-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    return fit_spans.counter_per_fit(
        obs, "photon_fe_orthant_zeroed_total", coordinate=obs.job.config["fixed_effect"]["name"]
    )
