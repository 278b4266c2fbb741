"""Operations and bytes that the serving programs need, from shapes.

Each architecture (``bench/arch/<name>.py``) counts one prefill round and
one decode tick by these rules.  A matmul (m, k) x (k, n) is 2mkn
operations.  "Needed" means the work of the tokens that were really there:
padded rows of a prefill group do not count, a MoE token counts its top-k
experts and not all of them, a causal query at position p attends to p + 1
keys, and the LM head counts once per prompt (where its logits seed the
first output) and once per decoded token.

Bytes count what has to cross HBM at least once: each weight once per call
(for MoE, only the experts that some token of the call routes to, in
expectation under uniform routing), the keys and values the call reads and
writes, and nothing for activations.
"""
from __future__ import annotations

import dataclasses

WEIGHT_BYTES = 2  # bf16 weights, as served
KV_BYTES = 2  # bf16 keys and values


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def min_seconds(self, peak_flops: float, peak_bytes: float) -> float:
        return max(self.flops / peak_flops, self.bytes / peak_bytes)


ZERO = Work(0.0, 0.0)


def decode_block(arch, m: dict, block: list[tuple[int, int]]) -> list[Work]:
    """The ticks of one decode block, counted by architecture module
    ``arch``; ``block`` holds (position at the block's first tick, ticks it
    emits) for each active slot."""
    ticks = max((n for _, n in block), default=0)
    return [arch.decode_tick(m, [p + j for p, n in block if j < n])
            for j in range(ticks)]
