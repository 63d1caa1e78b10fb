"""Tokens each held routed expert takes in a MoE layer's decode step:
the engine's counter `moe.held_assignments` (live tokens' top-k
assignments that land on the experts held here, summed over the MoE
layers, frozen slots left out) over the experts held, the MoE layers
and the decode steps of the traced replays (their telemetry windows),
from the program's recording. 16 live slots read 16 x 6 / 64 = 1.5 in
expectation. None where the program has no such counter."""


def read(run):
    rec = run.recording
    steps = sum(r.result["decode_steps"] for r in run.traced if r.ok)
    if rec is None or not steps:
        return None
    n = rec.counters.get("moe.held_assignments")
    if n is None:
        return None
    model = run.config["model"]
    held = model.get("experts_held") or model["n_experts"]
    layers = model["n_layers"] - model.get("first_k_dense", 0)
    return n / held / layers / steps
