"""memory_stats()["peak_bytes_in_use"] after the window, max over the cell's chips."""

UNIT = "GB"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "device"
MOVES = "fit_s"


def read(obs):
    return obs.memory_peak_bytes / 1e9 if obs.memory_peak_bytes else None
