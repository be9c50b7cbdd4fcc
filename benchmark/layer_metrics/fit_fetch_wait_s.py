"""photon_device_fetch_seconds_total over the traced part, all sites, per fit: host seconds inside blocking
device fetches. Untraced, with no fence before them, these are the program's only waits for the chip."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "CD loop"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    return fit_spans.counter_per_fit(obs, "photon_device_fetch_seconds_total")
