"""Device time per co-design cube: the device's busy time in the traced
window (in this cell only `core/dse_batch` runs on the device: the
nested-vmap lattice programs and the feasibility, bank and energy grids)
over the cubes completed in the traced window."""


def read(run):
    if run.trace is None or not run.traced:
        return None
    return run.trace.busy_s / len(run.traced) * 1e3
