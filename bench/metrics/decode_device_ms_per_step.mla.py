"""Device time of one decode step in the latent-attention cell: the
summed executions of the engine's decode-chunk program in the trace
(module `jit__chunk`) over the model steps the traced replays decoded
(the engine's telemetry window of each replay: a chunk counts its every
step)."""
from bench.lib import layers

SPANS = (layers.SERVE_RUN,)


def read(run):
    steps = sum(r.result["decode_steps"] for r in run.traced if r.ok)
    if run.trace is None or not steps:
        return None
    t = run.trace.module_time(layers.CHUNK_MODULE)
    return t / steps * 1e3 if t > 0 else None
