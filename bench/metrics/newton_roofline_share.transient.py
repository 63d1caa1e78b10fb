"""The fused Newton lattice program's share of its roofline: least time
over measured time. v5e publishes no float64 peak, so the least time is
bytes alone: the bytes a characterization must move whatever engine
computes it (`bench.lib.work.transient_point_bytes`, per real point,
from the shapes of each lattice-program call) over the chip's HBM
bandwidth (`bench.lib.peaks`). Measured time: the program module's
device time in the trace."""
from bench.lib import layers
from bench.lib.work import transient_point_bytes

SPANS = (layers.CHARACTERIZE, layers.RUN_LATTICE)
MODULE = "jit_run"


def read(run):
    calls = run.spans.named(layers.RUN_LATTICE["span"])
    lanes = layers.lanes(run)
    if run.trace is None or run.peaks is None or not lanes:
        return None
    t = run.trace.module_time(MODULE)
    if t <= 0:
        return None
    real_share = layers.real_points(run) / lanes
    total = 0.0
    for s in calls:
        self_, wt, wv, t_end, n_steps = s.info["args"]
        over = s.info["kwargs"]["over_batches"]
        B, n = over["G"][0], over["G"][1]
        dev = [k for k in over if k not in ("G", "C")]
        total += B * real_share * transient_point_bytes(
            n=n, n_waves=wt[1], knots=wt[2], n_dev=over[dev[0]][1],
            n_dev_params=len(dev), n_steps=n_steps)
    least = total / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / t
