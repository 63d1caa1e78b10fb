"""The whole serving step's share of the chip's peak in the latent-
attention cell: the model FLOPs of the traced replays' prefills and
decode steps (`bench.lib.work_mla`: 2 per weight a token passes through,
the held experts in expectation, attention's term per key attended,
unabsorbed in prefill and absorbed in decode, the unembedding where
logits are taken) over the traced window times the bf16 peak
(`bench.lib.peaks`). Padding, recomputation, held experts computed for
tokens not routed to them and the co-design's host time count as time
and not as work."""
from bench.lib import layers
from bench.lib.work_mla import MLAWork

SPANS = (layers.SERVE_RUN, layers.CODESIGN_MEASURED)


def read(run):
    served = layers.served(run)
    if run.trace is None or run.peaks is None or not served:
        return None
    w = MLAWork(run.config["model"])
    flops = sum(w.prefill_flops(p) + w.decode_flops(p, o)
                for _, p, o in served)
    return 100.0 * flops / (run.trace.window_s
                            * run.peaks["bf16_flops_per_s"])
