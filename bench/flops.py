"""Operations and bytes of the model's work, counted from shapes.

The counts are the algorithm's, whatever implements it: a decode step
reads every weight once and the keys and values of each row's context;
a token costs two operations per weight of the matrix products it runs
through, plus its attention at the number of keys it attends to. The
probe (two small float32 layers) is counted with the weights; the
elementwise work (norms, rotary positions, softmax) is not counted.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Shapes:
    """The sizes of a dense decoder that the counts need."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    param_bytes: int
    kv_bytes: int
    probe_hidden: int = 512
    probe_bins: int = 10

    @classmethod
    def of(cls, config: dict) -> "Shapes":
        """The shapes of a configuration file's ``program`` section."""
        p = config["program"]
        probe = p.get("probe", {})
        return cls(p["num_layers"], p["d_model"], p["num_heads"],
                   p["num_kv_heads"], p["head_dim"], p["d_ff"],
                   p["vocab_size"], jnp.dtype(p["param_dtype"]).itemsize,
                   jnp.dtype(p["dtype"]).itemsize,
                   probe.get("hidden", 512), probe.get("num_bins", 10))

    @property
    def layer_params(self) -> int:
        """Weights of one block's matrix products."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return 2 * self.d_model * q + 2 * self.d_model * kv + 3 * self.d_model * self.d_ff

    @property
    def weight_bytes(self) -> int:
        """Bytes a decode step reads once: blocks, norms, tied head, probe."""
        blocks = self.layers * (self.layer_params + 2 * self.d_model)
        head = self.vocab * self.d_model + self.d_model
        probe = 4 * (self.d_model * self.probe_hidden
                     + self.probe_hidden * self.probe_bins)
        return (blocks + head) * self.param_bytes + probe

    def kv_token_bytes(self) -> int:
        """Bytes of one token's keys and values over all layers."""
        return self.layers * 2 * self.kv_heads * self.head_dim * self.kv_bytes

    def token_flops(self, keys: int) -> float:
        """Operations of one token through the blocks, attending ``keys``."""
        attn = 4 * self.layers * self.heads * self.head_dim * keys
        return 2.0 * self.layers * self.layer_params + attn

    def head_flops(self) -> float:
        """Operations of one row of logits."""
        return 2.0 * self.vocab * self.d_model


def decode_work(s: Shapes, calls: int, k: int, rows) -> tuple[float, float]:
    """(operations, bytes) of ``calls`` decode calls of ``k`` steps each.

    ``rows`` lists (context before the call, tokens emitted) for every
    row of every call: the token emitted at step ``j`` attends to
    ``context + j`` keys and writes one token's keys and values.
    """
    flops = 0.0
    nbytes = float(calls * k * s.weight_bytes)
    kv = s.kv_token_bytes()
    for ctx, n in rows:
        for j in range(n):
            flops += s.token_flops(ctx + j) + s.head_flops()
            nbytes += (ctx + j + 1) * kv
    return flops, nbytes


def prefill_flops(s: Shapes, spans, prompts_done: int) -> float:
    """Operations of prefilling ``spans`` of (first position, tokens).

    The token at position ``p`` attends to ``p + 1`` keys; each prompt
    whose prefill ended adds one row of logits.
    """
    total = 0.0
    for first, n in spans:
        keys = n * first + n * (n + 1) // 2
        total += 2.0 * s.layers * s.layer_params * n
        total += 4.0 * s.layers * s.heads * s.head_dim * keys
    return total + prompts_done * s.head_flops()
