"""Host time per `dse_batch._group_constants` call, the scalar
electrical constants of one (topology group, vdd rung) on the CPU
device. Host clock, traced run."""
from bench.lib import layers

SPANS = (layers.GROUP_CONSTANTS,)


def read(run):
    s = run.spans.named(layers.GROUP_CONSTANTS["span"])
    return sum(x.dur for x in s) / len(s) * 1e3 if s else None
