"""The admission program's share of its roofline in the latent-
attention cell: least time over its device time in the trace (module
`jit__admit_kernel`: batched prefill, first-token sampling and latent
row insertion). Least time of a traced replay: the larger of its
prompts' causal prefill FLOPs (`bench.lib.work_mla`, unabsorbed
attention, held experts in expectation) over the bf16 peak and the
bytes it must move (every weight once, each prompt's latent rows
written once) over HBM bandwidth; summed over the replays. Bucket
padding counts as time, not work."""
from bench.lib import layers
from bench.lib.work_mla import MLAWork

SPANS = (layers.SERVE_RUN,)


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    w = MLAWork(run.config["model"])
    t = run.trace.module_time(layers.ADMIT_MODULE)
    least = 0.0
    for r in run.traced:
        if not r.ok:
            continue
        ps = [p for _, p, _ in r.request["prompts"]]
        flops = sum(w.prefill_flops(p) for p in ps)
        moved = w.decode_weight_bytes(sum(ps)) + sum(
            p * w.L * w.row_bytes() for p in ps)
        least += max(flops / run.peaks["bf16_flops_per_s"],
                     moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t if least > 0 and t > 0 else None
