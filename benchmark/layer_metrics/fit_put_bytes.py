"""photon_device_put_bytes_total over the traced part, all sites, per fit: host-to-device bytes the
program counts at its instrumented sites (the validation context's uploads)."""

UNIT = "bytes"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "entry point"
MOVES = "fit_s"


def read(obs):
    from benchmark import fit_spans

    return fit_spans.counter_per_fit(obs, "photon_device_put_bytes_total")
