"""photon_device_fetch_bytes_total over the traced part, all sites, per sweep. Read with the
metrics sink attached, which adds the trackers' own fetches (tracker_metrics, tracker_aggregates)."""

UNIT = "bytes"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "CD loop"
MOVES = "fit_s"


def read(obs):
    sweeps = len(obs.spans_named("cd.sweep"))
    return obs.counter_total("photon_device_fetch_bytes_total") / sweeps if sweeps else None
