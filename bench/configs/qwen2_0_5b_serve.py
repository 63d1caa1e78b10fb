"""Reference check and control of `qwen2_0_5b_serve`.

`check` (`bench.lib.serve_check`): `greedy_margin`, the widest gap by
which a served greedy token's logit lies below the plain reference's
best (`bench.reference.dense_lm`, float32 at the highest matmul
precision), over that position's spread of logits, in the requests
drawn from the seed with the longest among them; `profile_rel_err`, one
drawn replay's measured profile against the one worked out from its
lengths, the model's widths and the window's measured steps;
`value_rel_err`, that replay's co-design report against the co-design
cell's scalar reference on the profile worked out.

`control`: the same run with the program's own lower-precision path,
the int8 key-value cache (`kv_dtype: "int8"`, per-token-per-head
scales), below the bfloat16 cache the configuration states.
"""
from __future__ import annotations

import contextlib
import copy

from bench.lib import serve_check
from bench.reference import dense_lm

# limits set between the lower reading (sound runs) and the upper one
# (the int8-cache control); see PERF.md
MARGIN_LIMIT = 0.4
PROFILE_LIMIT = 1e-9
GAP_LIMIT = 1e-9


def readings(records, config, seed) -> dict:
    """`check`'s numbers, and each sampled request's own margin."""
    m = serve_check.margins(records, config, seed, dense_lm.logits)
    return {"greedy_margin": {"value": max(m, default=float("inf")),
                              "limit": MARGIN_LIMIT},
            "margins": m,
            "profile_rel_err": {"value": serve_check.profile_gap(
                records, config, seed), "limit": PROFILE_LIMIT},
            "value_rel_err": {"value": serve_check.codesign_gap(
                records, config, seed), "limit": GAP_LIMIT}}


def check(records, config, seed) -> dict:
    out = readings(records, config, seed)
    del out["margins"]
    return out


class Control(contextlib.AbstractContextManager):
    def __init__(self, config):
        self.config = copy.deepcopy(config)
        self.config["model"]["kv_dtype"] = "int8"

    def __exit__(self, *exc):
        return None


def control(config) -> Control:
    return Control(config)
