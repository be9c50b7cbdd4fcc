"""1 - union of device op intervals over the traced fits, averaged over chips."""

UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "fit_s"


def read(obs):
    if obs.trace is None or not obs.trace.chips or not obs.fit_windows:
        return None
    from benchmark import trace

    return 100.0 * trace.idle_share(obs.trace, obs.traced_window)
