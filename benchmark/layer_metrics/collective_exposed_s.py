"""All-reduce / all-gather device time during which no other operation runs on that chip, per fit,
averaged over chips."""

UNIT = "s"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "collectives"
MOVES = "fit_s"


def read(obs):
    if obs.trace is None or not obs.trace.chips or not obs.fit_windows or obs.chips < 2:
        return None
    from benchmark import trace

    return trace.collective_exposed_seconds(obs.trace, obs.traced_window) / obs.n_fits
