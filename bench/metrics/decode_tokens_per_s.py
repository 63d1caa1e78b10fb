"""Answer tokens of the replays that completed in the window (each engine
request's whole budget, its prefill token included), over the window's
length (first send to last completion). Host clock."""


def read(run):
    n = sum(r.units.get("decode_tokens", 0) for r in run.done)
    return n / run.window_s if n else None
