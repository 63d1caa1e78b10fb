"""Gain-cell points characterized at transient fidelity by the requests
that completed in the window, over the window's length (first send to
last completion). Host clock."""


def read(run):
    pts = sum(r.units.get("transient_points", 0) for r in run.done)
    return pts / run.window_s if pts else None
