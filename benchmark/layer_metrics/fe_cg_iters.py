"""photon_cd_cg_iterations of the fixed effect, total per fit: TRON's inner CG iterations, one
Hessian-vector product each. Tells a faster Hv kernel from one CG step fewer."""

UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "fixed-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    return fit_spans.summary_sum_per_fit(
        obs, "photon_cd_cg_iterations", coordinate=obs.job.config["fixed_effect"]["name"]
    )
