"""Host time of the transient characterization per real point: the
`char_batch.characterize` spans minus the lattice-program spans inside
them (which block on the program's result), over the points
characterized. Host clock, traced run."""
from bench.lib import layers
from bench.lib.spans import self_time

SPANS = (layers.CHARACTERIZE, layers.RUN_LATTICE)


def read(run):
    n = layers.real_points(run)
    if not n:
        return None
    host = self_time(run.spans, layers.CHARACTERIZE["span"],
                     [layers.RUN_LATTICE["span"]])
    return host / n * 1e3
