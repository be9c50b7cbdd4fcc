"""The device's busy seconds inside the re.bucket intervals (mean over chips), summed per fit, median over
the traced fits: what of re_solve_s the chip worked; the rest is the host's."""

UNIT = "s"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "random-effect solve"
MOVES = "fit_s"


def read(obs):
    from benchmark import wait_spans

    return wait_spans.device_s(obs, "re.bucket")
