"""photon_fe_nonzero_coefficients of the fixed effect: the support, in the solver's space, of the last
OWL-QN solve of the traced part (the smallest weight of a falling path: the largest support)."""

UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "fixed-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    found = fit_spans._series(
        obs, "photon_fe_nonzero_coefficients", coordinate=obs.job.config["fixed_effect"]["name"]
    )
    return float(found[0].get("value", 0.0)) if found else None
