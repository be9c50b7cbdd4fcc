"""1 - useful / issued of photon_re_lane_iterations_total: iterations a lockstep bucket ran for lanes
that had already stopped (a bucket runs until its slowest lane does)."""

UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "random-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    useful = fit_spans.counter_total(obs, "photon_re_lane_iterations_total", kind="useful")
    issued = fit_spans.counter_total(obs, "photon_re_lane_iterations_total", kind="issued")
    if useful is None or not issued:
        return None
    return 100.0 * (1.0 - useful / issued)
