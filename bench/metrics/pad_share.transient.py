"""Share of the lattice program's lanes that are padding: 1 - real
points / lanes computed, from the arguments of `characterize` and of the
lattice program's calls (roadmap S6)."""
from bench.lib import layers

SPANS = (layers.CHARACTERIZE, layers.RUN_LATTICE)


def read(run):
    lanes = layers.lanes(run)
    if not lanes:
        return None
    return 100.0 * (1.0 - layers.real_points(run) / lanes)
