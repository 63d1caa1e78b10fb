"""Work a computation must do whatever engine computes it, from shapes.

`DecoderWork`: the FLOPs and bytes of serving a decoder language model,
from its configuration's dimensions (see the class).

`transient_point_bytes`: the bytes one transient read characterization
moves at the least: its inputs (the (n, n) conductance and capacitance
matrices, the piecewise-linear stimulus, the stop time and every device
parameter) read once, and the (n_steps, n) node-voltage trace written
once, all at 8 bytes (float64). It counts no iteration, no padding lane
and no intermediate, so dividing by HBM bandwidth gives a floor on the
time of any engine.
"""
from __future__ import annotations

import numpy as np

F64 = 8


def transient_point_bytes(n: int, n_waves: int, knots: int, n_dev: int,
                          n_dev_params: int, n_steps: int) -> int:
    inputs = (2 * n * n                 # G and C
              + 2 * n_waves * knots     # stimulus times and values
              + 1                       # stop time
              + n_dev_params * n_dev)   # device parameters
    outputs = n_steps * n               # node-voltage trace
    return F64 * (inputs + outputs)


class DecoderWork:
    """The least work of serving a decoder language model, from the
    dimensions in a configuration's `model` dict (the `ModelConfig`
    fields): grouped-query attention over a full or sliding window, a
    gated feed-forward, dense or with `top_k` of `n_experts` experts.

    FLOPs are 2 per multiply-add of every weight a token passes through
    (its own experts only), plus attention's 4 x heads x head_dim per key
    attended (scores and values), plus the unembedding where logits are
    taken (the last prompt position and every decode step). A prompt of
    P tokens attends causally (position i sees i keys); the k-th decode
    step of an answer sees P + k. Bytes a decode step must move: every
    weight once (the experts the step's tokens reach, in expectation),
    and each live request's resident cache rows once. Nothing counts
    padding, recomputation or a frozen slot.
    """

    def __init__(self, model: dict):
        if model["family"] not in ("dense", "moe"):
            raise ValueError(f"no work count for family {model['family']!r}")
        self.m = model
        self.d, self.H, self.K = (model["d_model"], model["n_heads"],
                                  model["n_kv_heads"])
        self.hd = model.get("head_dim") or self.d // self.H
        self.L, self.V = model["n_layers"], model["vocab_size"]
        self.window = model.get("sliding_window", 0)
        self.E, self.top_k = model.get("n_experts", 0), model.get("top_k", 0)
        self.itemsize = 2 if model.get("dtype", "bfloat16") in (
            "bfloat16", "float16") else 4

    def _keys(self, ctx):
        """Keys attended at context `ctx` (array)."""
        return np.minimum(ctx, self.window) if self.window else ctx

    def _attn_weights(self) -> int:
        d, H, K, hd = self.d, self.H, self.K, self.hd
        return d * H * hd + 2 * d * K * hd + H * hd * d

    def _expert(self) -> int:
        return 3 * self.d * self.m["d_ff"]

    def _token_weights(self) -> int:
        """Weights one token passes through in one layer."""
        mlp = self._expert() * (self.top_k if self.E else 1)
        router = self.d * self.E
        dense = 3 * self.d * self.m.get("moe_dense_ff", 0)
        return self._attn_weights() + mlp + router + dense

    def params(self) -> int:
        """Parameters the model holds, of its routed experts only the
        `top_k` a token reaches: embedding (and unembedding unless
        tied), norm gains (and biases under layernorm), projections,
        query/key/value biases where the model has them, router."""
        m, d, L = self.m, self.d, self.L
        norm = d * (2 if m.get("norm") == "layernorm" else 1)
        bias = (self.H + 2 * self.K) * self.hd if m.get("qkv_bias") else 0
        per_layer = 2 * norm + bias + self._token_weights()
        embed = self.V * d * (1 if m.get("tie_embeddings") else 2)
        return int(embed + norm + L * per_layer)

    def _tokens(self, ctx) -> float:
        """FLOPs of tokens at contexts `ctx`, unembedding left out."""
        ctx = np.asarray(ctx, np.float64)
        return float(self.L * (2 * self._token_weights() * ctx.size
                               + 4 * self.H * self.hd
                               * self._keys(ctx).sum()))

    def prefill_flops(self, p: int) -> float:
        return self._tokens(np.arange(1, p + 1)) + 2 * self.d * self.V

    def decode_flops(self, p: int, o: int) -> float:
        """The o - 1 decode steps of an answer of `o` tokens (its first
        comes from prefill)."""
        return self._tokens(p + np.arange(1, o)) + \
            2 * self.d * self.V * (o - 1)

    def row_bytes(self) -> float:
        """One layer's cache row (keys and values of one position)."""
        if self.m.get("kv_dtype") == "int8":
            return 2 * self.K * (self.hd + 2)       # int8 + bf16 scale
        return 2 * self.K * self.hd * self.itemsize

    def decode_kv_bytes(self, p: int, o: int) -> float:
        rows = self._keys(p + np.arange(1, o, dtype=np.float64)).sum()
        return float(self.L * rows * self.row_bytes())

    def decode_weight_bytes(self, batch: float) -> float:
        """Weights a decode step of `batch` live tokens reads once."""
        experts = self.E * (1 - (1 - self.top_k / self.E) ** batch) \
            if self.E else 1
        per_layer = self._attn_weights() + experts * self._expert() + \
            self.d * self.E + 3 * self.d * self.m.get("moe_dense_ff", 0)
        return float(self.itemsize * (self.L * per_layer + self.d * self.V))

