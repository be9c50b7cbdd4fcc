"""Backend compile seconds during set-up, cache retrieval included (jax times the backend event
around the cache lookup)."""

UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "set-up"
MOVES = "setup_s"


def read(obs):
    return obs.listener.backend_seconds("setup") + obs.listener.backend_seconds("warm")
