"""The decode-chunk program's share of its roofline in the latent-
attention cell: least time over its device time in the trace (module
`jit__chunk`). Least time of a traced replay: the larger of its decode
FLOPs over the bf16 peak and the bytes its decode steps must move over
HBM bandwidth (`bench.lib.work_mla`: every weight once a step, of the
held experts those the step's tokens reach in expectation, and each
live request's resident latent rows once a step); summed over the
replays."""
from bench.lib import layers
from bench.lib.work_mla import MLAWork

SPANS = (layers.SERVE_RUN,)


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    w = MLAWork(run.config["model"])
    t = run.trace.module_time(layers.CHUNK_MODULE)
    least = 0.0
    for r in run.traced:
        steps = r.result["decode_steps"] if r.ok else 0
        if not steps:
            continue
        po = [(p, o) for _, p, o in r.request["prompts"]]
        batch = sum(o - 1 for _, o in po) / steps
        flops = sum(w.decode_flops(p, o) for p, o in po)
        moved = steps * w.decode_weight_bytes(batch) + sum(
            w.decode_kv_bytes(p, o) for p, o in po)
        least += max(flops / run.peaks["bf16_flops_per_s"],
                     moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t if least > 0 and t > 0 else None
