"""Work of serving a DeepSeek-V2 decoder (latent attention, routed and
shared experts, leading dense layers) on the chip that holds a share of
its routed experts, from the dimensions in a configuration's `model`
dict (the `ModelConfig` fields).

`MLAWork` has `DecoderWork`'s interface (`bench.lib.work`): FLOPs are 2
per multiply-add of every weight a token passes through, the held
routed experts counted in expectation (top_k x experts_held / n_experts
of them a token), the shared experts, the dense layers' MLP and the
router whole; plus attention's FLOPs per key attended, the unembedding
where logits are taken (the last prompt position and every decode
step). Prefill attends unabsorbed, each head's 192-wide key and 128-wide
value per key: 2 x heads x (qk_nope + qk_rope + v_head) a key. Decode
attends absorbed, over latent rows: 2 x heads x (2 x kv_lora_rank +
qk_rope) a key; its absorption products pass Wk_b and Wv_b once a
token, as prefill's expansion of each key does. A prompt of P tokens
attends causally; the k-th decode step of an answer sees P + k keys.
Bytes a decode step must move: every weight once (of the held experts,
those the step's tokens reach, in expectation), and each live request's
resident latent rows once. Nothing counts padding, recomputation, a
frozen slot, or an expert's computation for a token not routed to it.
"""
from __future__ import annotations

import numpy as np


class MLAWork:
    def __init__(self, model: dict):
        if model.get("attn_kind") != "mla":
            raise ValueError("MLAWork counts latent-attention models")
        self.m = model
        self.d, self.H, self.V = (model["d_model"], model["n_heads"],
                                  model["vocab_size"])
        self.R, self.dn = model["kv_lora_rank"], model["qk_nope_dim"]
        self.dr, self.dv = model["qk_rope_dim"], model["v_head_dim"]
        self.L, self.k0 = model["n_layers"], model.get("first_k_dense", 0)
        self.E, self.top_k = model["n_experts"], model["top_k"]
        self.held = model.get("experts_held") or self.E
        self.f = model.get("moe_d_ff") or model["d_ff"]
        self.itemsize = 2 if model.get("dtype", "bfloat16") in (
            "bfloat16", "float16") else 4

    def _attn_weights(self) -> int:
        d, H, R = self.d, self.H, self.R
        return (d * H * (self.dn + self.dr) + d * (R + self.dr)
                + R * H * (self.dn + self.dv) + H * self.dv * d)

    def _expert(self) -> int:
        return 3 * self.d * self.f

    def _shared(self) -> int:
        return self.m.get("n_shared_experts", 0) * self._expert()

    def _token_weights(self) -> float:
        """Weights one token passes through, summed over the layers."""
        dense = self._attn_weights() + 3 * self.d * self.m["d_ff"]
        routed = self.top_k * self.held / self.E * self._expert()
        moe = self._attn_weights() + self.d * self.E + self._shared() \
            + routed
        return self.k0 * dense + (self.L - self.k0) * moe

    def params(self) -> int:
        """Parameters held here, of the routed experts the share a token
        reaches (top_k / n_experts of each): embedding and unembedding,
        norm gains (the latent's too), projections, router, shared and
        dense MLPs."""
        d, n_moe = self.d, self.L - self.k0
        attn = self._attn_weights() + self.R + 2 * d
        dense = attn + 3 * d * self.m["d_ff"]
        moe = attn + d * self.E + self._shared()
        reached = n_moe * self.held * self._expert() * self.top_k // self.E
        return int(2 * self.V * d + d + self.k0 * dense + n_moe * moe
                   + reached)

    def _tokens(self, ctx, per_key: int) -> float:
        """FLOPs of tokens at contexts `ctx`, unembedding left out."""
        ctx = np.asarray(ctx, np.float64)
        return float(2 * self._token_weights() * ctx.size
                     + per_key * self.L * ctx.sum())

    def prefill_flops(self, p: int) -> float:
        per_key = 2 * self.H * (self.dn + self.dr + self.dv)
        return self._tokens(np.arange(1, p + 1), per_key) + \
            2 * self.d * self.V

    def decode_flops(self, p: int, o: int) -> float:
        """The o - 1 decode steps of an answer of `o` tokens (its first
        comes from prefill)."""
        per_key = 2 * self.H * (2 * self.R + self.dr)
        return self._tokens(p + np.arange(1, o), per_key) + \
            2 * self.d * self.V * (o - 1)

    def row_bytes(self) -> float:
        """One layer's latent cache row: c_kv and k_pe in the cache's
        type (`kv_dtype`; int8 with a bfloat16 scale each)."""
        kv = self.m.get("kv_dtype", "bfloat16")
        if kv == "int8":
            return self.R + self.dr + 2 * 2
        return (self.R + self.dr) * (4 if kv == "float32" else 2)

    def decode_kv_bytes(self, p: int, o: int) -> float:
        rows = (p + np.arange(1, o, dtype=np.float64)).sum()
        return float(self.L * rows * self.row_bytes())

    def decode_weight_bytes(self, batch: float) -> float:
        """Weights a step of `batch` tokens reads once."""
        reached = self.held * (1 - (1 - self.top_k / self.E) ** batch)
        per_moe = self._attn_weights() + self.d * self.E + self._shared() \
            + reached * self._expert()
        dense = self._attn_weights() + 3 * self.d * self.m["d_ff"]
        return float(self.itemsize * (self.k0 * dense
                                      + (self.L - self.k0) * per_moe
                                      + self.d * self.V))
