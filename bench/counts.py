"""Operations and bytes that the serving programs need, from shapes.

A matmul (m, k) x (k, n) is 2mkn operations.  "Needed" means the work of the
tokens that were really there: padded rows of a prefill group do not count,
a MoE token counts its top-k experts and not all of them, a causal query at
position p attends to p + 1 keys, and the LM head counts once per prompt
(where its logits seed the first output) and once per decoded token.

Bytes count what has to cross HBM at least once: each weight once per call
(for MoE, only the experts that some token of the call routes to, in
expectation under uniform routing), the keys and values the call reads and
writes, and nothing for activations.
"""
from __future__ import annotations

import dataclasses

WEIGHT_BYTES = 2  # bf16 weights, as served
KV_BYTES = 2  # bf16 keys and values


def _moe(m: dict) -> bool:
    return m["num_local_experts"] > 0


def attn_params(m: dict) -> int:
    d, hd = m["hidden_size"], m["head_dim"]
    h, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    return d * (h + 2 * hkv) * hd + h * hd * d


def expert_params(m: dict) -> int:
    """One GLU expert (or the dense MLP)."""
    return 3 * m["hidden_size"] * m["intermediate_size"]


def mlp_active_params(m: dict) -> int:
    """MLP parameters one token multiplies by (routed experts + router)."""
    if _moe(m):
        return (m["num_experts_per_tok"] * expert_params(m)
                + m["hidden_size"] * m["num_local_experts"])
    return expert_params(m)


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def token_flops(m: dict, n_keys: float) -> float:
    """One token through every layer, attending to ``n_keys`` keys; no head."""
    per_layer = (2 * (attn_params(m) + mlp_active_params(m))
                 + 4 * m["num_attention_heads"] * m["head_dim"] * n_keys)
    return m["num_hidden_layers"] * per_layer


def expected_experts(m: dict, tokens: int) -> float:
    """Distinct experts that ``tokens`` tokens route to, in expectation, when
    each picks top-k of E uniformly."""
    e, k = m["num_local_experts"], m["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** tokens)


def layer_weight_bytes(m: dict, tokens: int) -> float:
    """Weights of all layers that a call over ``tokens`` tokens reads."""
    mlp = (expected_experts(m, tokens) * expert_params(m)
           + m["hidden_size"] * m["num_local_experts"]) if _moe(m) \
        else expert_params(m)
    return m["num_hidden_layers"] * (attn_params(m) + mlp) * WEIGHT_BYTES


def kv_bytes_per_token(m: dict) -> int:
    return (m["num_hidden_layers"] * 2 * m["num_key_value_heads"]
            * m["head_dim"] * KV_BYTES)


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def min_seconds(self, peak_flops: float, peak_bytes: float) -> float:
        return max(self.flops / peak_flops, self.bytes / peak_bytes)


ZERO = Work(0.0, 0.0)


def prefill_round(m: dict, members: list[tuple[int, int, bool]]) -> Work:
    """One prefill call: ``members`` are (start, chunk, finishes_prompt)."""
    if not members:
        return ZERO
    h, hd, d = m["num_attention_heads"], m["head_dim"], m["hidden_size"]
    flops = 0.0
    kv = 0.0
    tokens = 0
    heads = 0
    for start, c, finishes in members:
        # chunk tokens at positions start .. start + c - 1
        keys = c * start + c * (c + 1) / 2
        flops += m["num_hidden_layers"] * (
            2 * c * (attn_params(m) + mlp_active_params(m))
            + 4 * h * hd * keys)
        kv += (start + c) * kv_bytes_per_token(m)  # read held, write chunk
        tokens += c
        heads += finishes
    flops += heads * 2 * head_params(m)
    byts = (layer_weight_bytes(m, tokens) + kv + tokens * d * WEIGHT_BYTES
            + (head_params(m) * WEIGHT_BYTES if heads else 0))
    return Work(flops, byts)


def decode_tick(m: dict, positions: list[int]) -> Work:
    """One decode tick of the slots at ``positions`` (each token attends to
    its position + 1 keys; the keys held are read once)."""
    if not positions:
        return ZERO
    n = len(positions)
    flops = sum(token_flops(m, p + 1) for p in positions) \
        + n * 2 * head_params(m)
    byts = (layer_weight_bytes(m, n) + head_params(m) * WEIGHT_BYTES
            + sum(p + 1 for p in positions) * kv_bytes_per_token(m)
            + n * m["hidden_size"] * WEIGHT_BYTES)
    return Work(flops, byts)


def decode_block(m: dict, block: list[tuple[int, int]]) -> list[Work]:
    """The ticks of one decode block; ``block`` holds (position at the
    block's first tick, ticks it emits) for each active slot."""
    ticks = max((n for _, n in block), default=0)
    return [decode_tick(m, [p + j for p, n in block if j < n])
            for j in range(ticks)]
