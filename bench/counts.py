"""Operations and bytes of the benchmark's programs, from shapes alone.

These are the work the algorithm needs, counted the same way for every
PR, never read from the compiler's cost analysis: a later kernel or fusion
change is measured against the same work.
"""
from __future__ import annotations

from dataclasses import dataclass

BF16 = 2


# ------------------------------------------------------- dense transformer
@dataclass(frozen=True)
class Dense:
    """The shapes of a dense GQA transformer with a SwiGLU feed-forward."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def of(cls, cfg) -> "Dense":
        return cls(cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                   cfg.resolved_head_dim, cfg.d_ff, cfg.vocab_size)

    @property
    def layer_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * (self.heads + 2 * self.kv_heads) * hd + self.heads * hd * d
        return attn + 3 * d * self.d_ff

    @property
    def kv_bytes_per_position(self) -> int:
        """Keys and values of one position over every layer, bf16."""
        return self.layers * 2 * self.kv_heads * self.head_dim * BF16

    def weight_bytes(self) -> float:
        """Every layer and the (tied) embedding table, bf16."""
        return (self.layers * self.layer_params
                + self.vocab * self.d_model) * BF16

    def _attn_flops(self, q_tokens_ctx: float) -> float:
        # q.k and p.v: 2 products of head_dim per (query, key) per head
        return 4.0 * self.layers * self.heads * self.head_dim * q_tokens_ctx

    def prefill_flops(self, s: int) -> float:
        """A prompt of ``s`` tokens: every layer on each token, causal
        attention over the positions before it, logits of the last one."""
        return (2.0 * self.layers * self.layer_params * s
                + self._attn_flops(s * (s + 1) / 2)
                + 2.0 * self.d_model * self.vocab)

    def decode_flops(self, contexts) -> float:
        """One token for each lane, lane i attending over ``contexts[i]``
        positions (itself included)."""
        n = len(contexts)
        return (2.0 * (self.layers * self.layer_params
                       + self.d_model * self.vocab) * n
                + self._attn_flops(float(sum(contexts))))

    def decode_bytes(self, contexts) -> float:
        """Weights read once, keys and values of the occupied positions
        read, the new position written."""
        return (self.weight_bytes()
                + self.kv_bytes_per_position * float(sum(contexts)))


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
