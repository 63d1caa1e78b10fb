"""Work a computation must do whatever engine computes it, from shapes.

`transient_point_bytes`: the bytes one transient read characterization
moves at the least: its inputs (the (n, n) conductance and capacitance
matrices, the piecewise-linear stimulus, the stop time and every device
parameter) read once, and the (n_steps, n) node-voltage trace written
once, all at 8 bytes (float64). It counts no iteration, no padding lane
and no intermediate, so dividing by HBM bandwidth gives a floor on the
time of any engine.
"""
from __future__ import annotations

F64 = 8


def transient_point_bytes(n: int, n_waves: int, knots: int, n_dev: int,
                          n_dev_params: int, n_steps: int) -> int:
    inputs = (2 * n * n                 # G and C
              + 2 * n_waves * knots     # stimulus times and values
              + 1                       # stop time
              + n_dev_params * n_dev)   # device parameters
    outputs = n_steps * n               # node-voltage trace
    return F64 * (inputs + outputs)
