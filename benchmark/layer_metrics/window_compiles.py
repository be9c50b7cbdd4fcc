"""Backend compiles inside the window that no persistent-cache hit answered: must read 0."""

UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "entry point"
MOVES = "fit_s"


def read(obs):
    return float(obs.listener.compiles("window"))
