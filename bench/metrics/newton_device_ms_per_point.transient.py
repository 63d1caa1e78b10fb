"""Device time of the fused Newton lattice program per real point: the
summed durations of its XLA module's executions in the trace (module
`jit_run`, the jitted `run` of `Transient._fused_fn`) over the points
characterized in the window."""
from bench.lib import layers

SPANS = (layers.CHARACTERIZE, layers.RUN_LATTICE)
MODULE = "jit_run"


def read(run):
    n = layers.real_points(run)
    if run.trace is None or not n:
        return None
    t = run.trace.module_time(MODULE)
    return t / n * 1e3 if t > 0 else None
