"""One chip's sparse pass of a solve whose state is split over the chips: least time at the HBM peak
(the chip's pass bytes STREAMED at 819 GB/s, benchmark/shapes_sharded.py) over the device time of its
gather and scatter-add (``fe_shard_pass_s``). A gathered coefficient or a scattered update moves 4
useful bytes of a whole HBM transaction, so single digits are the layout's distance from streaming."""

from benchmark import shapes, shapes_sharded

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "GLM kernels"
MOVES = "fit_s"


def read(obs):
    from benchmark import sharded_ops

    seconds = sharded_ops.pass_seconds(obs)
    shape = obs.job.pass_shape
    if seconds is None or shape["layout"] != "ell":
        return None
    n_chip, k = shape["rows"] // obs.chips, shape["width"]
    width = shapes_sharded.solve_width(shape["dim"], obs.chips)
    return shapes.roofline_share(
        shapes_sharded.chip_pass_bytes(n_chip, k, width), shapes_sharded.chip_pass_flops(n_chip, k),
        seconds, obs.peak,
    )["share"]
