"""Host time of the api layer (plan, executor, compose) per query: the
`Session.run` spans minus the `dse_batch` evaluation spans inside them.
Host clock, traced run."""
from bench.lib import layers
from bench.lib.spans import self_time

SPANS = (layers.SESSION_RUN,) + layers.DSE_BATCH


def read(run):
    calls = run.spans.named(layers.SESSION_RUN["span"])
    if not calls:
        return None
    host = self_time(run.spans, layers.SESSION_RUN["span"],
                     [s["span"] for s in layers.DSE_BATCH])
    return host / len(calls) * 1e3
