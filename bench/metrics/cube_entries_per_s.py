"""(vdd rung x lattice point x demand) co-design entries of the reports
that completed in the window, over the window's length (first send to
last completion). Host clock."""


def read(run):
    n = sum(r.units.get("cube_entries", 0) for r in run.done)
    return n / run.window_s if n else None
