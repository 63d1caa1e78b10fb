"""Share of the traced window in which no operation ran on the device,
in the latent-attention cell: 1 - (union of the device's operation
intervals) / window, from the profiler trace (`bench.lib.trace`)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
