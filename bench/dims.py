"""The sizes of a configuration file, read once: the model's keys as its
source publishes them, and the deployment the cell serves it in."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    norm_eps: float
    rope_theta: float
    max_position: int
    window: int = 0             # sliding-window width; 0 = global attention

    @classmethod
    def of(cls, name: str, conf: dict) -> "Dims":
        if conf["hidden_act"] != "silu":
            raise ValueError(f"{name}: only silu MLPs are covered")
        heads = conf["num_attention_heads"]
        return cls(
            name=name, n_layers=conf["num_hidden_layers"],
            d_model=conf["hidden_size"], n_heads=heads,
            n_kv_heads=conf["num_key_value_heads"],
            head_dim=conf.get("head_dim") or conf["hidden_size"] // heads,
            d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
            tied=conf["tie_word_embeddings"], norm_eps=conf["rms_norm_eps"],
            rope_theta=conf["rope_theta"],
            max_position=conf["max_position_embeddings"],
            window=conf.get("sliding_window") or 0)

    def attended(self, ctx: int) -> int:
        """Positions a query at position ``ctx - 1`` attends."""
        return min(ctx, self.window) if self.window else ctx

    def attended_prefill(self, S: int) -> int:
        """Query-key pairs a causal prefill of ``S`` positions scores."""
        w = self.window if self.window and self.window < S else S
        return w * (w + 1) // 2 + (S - w) * w

    # parameter counts (the yardstick's own, from the shapes)
    @property
    def layer_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * hd * (2 * self.n_heads + 2 * self.n_kv_heads)
        return attn + 3 * d * self.d_ff

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def weight_bytes_per_step(self) -> int:
        """Bytes of bfloat16 weights one decode step reads: every layer's
        matrices and norms, the final norm and the output head. The
        embedding table is gathered a row per token, not read whole."""
        per_layer = self.layer_matmul_params + 2 * self.d_model
        return 2 * (self.n_layers * per_layer + self.d_model
                    + self.head_params)

    @property
    def kv_bytes_per_position(self) -> int:
        """bfloat16 K and V plus the int32 position tag, all layers."""
        return self.n_layers * (2 * 2 * self.n_kv_heads * self.head_dim + 4)
