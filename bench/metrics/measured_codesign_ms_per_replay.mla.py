"""Host time of the measured co-design per replay: the benchmark's spans
around `Session.codesign_measured` (the replay's telemetry window turned
into a measured profile, then a co-design of the whole lattice at the
drawn rungs) over their count. Host clock, traced run."""
from bench.lib import layers

SPANS = (layers.CODESIGN_MEASURED, layers.SERVE_RUN)


def read(run):
    s = run.spans.named(layers.CODESIGN_MEASURED["span"])
    return sum(x.dur for x in s) / len(s) * 1e3 if s else None
