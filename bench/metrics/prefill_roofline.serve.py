"""The admission program's share of its roofline: least time over its
device time in the trace (module `jit__admit_kernel`: batched prefill,
first-token sampling and cache-row insertion). Least time of a traced
replay: the larger of its prompts' causal prefill FLOPs
(`bench.lib.work`) over the bf16 peak and the bytes it must move (every
weight once, each prompt's cache rows written once) over HBM bandwidth;
summed over the replays. Bucket padding counts as time, not work."""
from bench.lib import layers
from bench.lib.work import DecoderWork

SPANS = (layers.SERVE_RUN,)


def read(run):
    w = DecoderWork(run.config["model"])
    if run.trace is None or run.peaks is None:
        return None
    t = run.trace.module_time(layers.ADMIT_MODULE)
    least = 0.0
    for r in run.traced:
        if not r.ok:
            continue
        ps = [p for _, p, _ in r.request["prompts"]]
        flops = sum(w.prefill_flops(p) for p in ps)
        moved = w.decode_weight_bytes(1) + sum(
            p * w.L * w.row_bytes() for p in ps)
        least += max(flops / run.peaks["bf16_flops_per_s"],
                     moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t if least > 0 and t > 0 else None
