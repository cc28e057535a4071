"""Operations and bytes from shapes: the codebook kernel's least time and
the model FLOPs of the tokens a window served.

Both count the algorithm, not an implementation: an index plane is
counted at ceil(log2 |W|) bits per weight (10 at |W| = 1000) whatever
width a program stores it in, so a later change of storage or kernel is
measured against the same floor.
"""

from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class Shape:
    """The model sizes the counts need (from a configuration file)."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    n_weights: int
    act_bytes: int = 2    # bf16 activations into the kernel
    out_bytes: int = 4    # f32 kernel output

    @classmethod
    def of(cls, config: dict) -> "Shape":
        hf, a = config["hf"], config["assumed"]
        heads = hf["num_attention_heads"]
        return cls(layers=hf["num_hidden_layers"], d=hf["hidden_size"],
                   heads=heads, kv_heads=hf["num_key_value_heads"],
                   head_dim=hf.get("head_dim",
                                   hf["hidden_size"] // heads),
                   ffn=hf["intermediate_size"], vocab=hf["vocab_size"],
                   n_weights=a["n_weights"])

    def projections(self) -> list:
        """(name, K, N) of every matrix of one layer."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return [("wq", self.d, q), ("wk", self.d, kv), ("wv", self.d, kv),
                ("wo", q, self.d), ("w1", self.d, self.ffn),
                ("w3", self.d, self.ffn), ("w2", self.ffn, self.d)]

    def layer_weights(self) -> int:
        return sum(k * n for _, k, n in self.projections())


def index_bits(n_weights: int) -> int:
    return max(1, math.ceil(math.log2(n_weights)))


def codebook_call(shape: Shape, m: int, k: int, n: int) -> tuple:
    """(FLOPs, bytes) of one x(m, k) @ codebook[w_idx(k, n)] call."""
    flops = 2 * m * k * n
    nbytes = (k * n * index_bits(shape.n_weights) / 8 + 4 * shape.n_weights
              + m * k * shape.act_bytes + m * n * shape.out_bytes)
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peaks) -> float:
    return max(flops / peaks.flops, nbytes / peaks.hbm_bw)


def model_flops(shape: Shape, tokens: int, ctx_sum: int,
                heads: int = 0) -> float:
    """Model FLOPs of ``tokens`` tokens whose attention contexts sum to
    ``ctx_sum`` keys, with ``heads`` lm-head rows: 2 per matrix weight per
    token, 4 * heads * head_dim per key per layer for QK and PV, and
    2 * d * vocab per head row."""
    return (2 * shape.layer_weights() * shape.layers * tokens
            + 4 * shape.heads * shape.head_dim * shape.layers * ctx_sum
            + 2 * shape.d * shape.vocab * heads)


def prefill_ctx_sum(prompt_len: int) -> int:
    """Keys attended by a causal prompt: token p attends p + 1."""
    return prompt_len * (prompt_len + 1) // 2
