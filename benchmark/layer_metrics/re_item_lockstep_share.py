"""1 - useful / issued of the per-item coordinate's photon_re_lane_iterations_total: iterations a
lockstep bucket ran for item lanes that had already stopped."""

UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "random-effect solve"
MOVES = "fit_s"
COORDINATE = "per-item"


def read(obs):
    from benchmark import fit_spans

    name = "photon_re_lane_iterations_total"
    useful = fit_spans.counter_total(obs, name, coordinate=COORDINATE, kind="useful")
    issued = fit_spans.counter_total(obs, name, coordinate=COORDINATE, kind="issued")
    if useful is None or not issued:
        return None
    return 100.0 * (1.0 - useful / issued)
