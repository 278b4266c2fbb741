"""A Llama-style decoder: pre-norm RMSNorm blocks, rotary positions over the
whole head, grouped-query attention, and a SiLU GLU MLP or a softmax top-k
mixture of GLU experts; an LM head, tied or not.

The configuration's ``model`` holds Hugging Face key names (``hidden_size``,
``num_local_experts``, ...); ``num_local_experts`` 0 means a dense MLP.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import arch
from bench import weights as W
from bench.counts import KV_BYTES, WEIGHT_BYTES, ZERO, Work
from bench.reference import llama as reference

# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

# Hugging Face key in a configuration file -> ModelConfig field
_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "num_local_experts": "n_experts",
    "num_experts_per_tok": "top_k", "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "hidden_act": "act",
}
# the block that bench/reference/llama.py computes; a program config that
# says otherwise is another architecture
_BLOCK = {"layer_pattern": ("attn",), "mlp_type": "glu", "pos_type": "rope",
          "rope_fraction": 1.0, "use_mla": False, "n_shared_experts": 0,
          "first_dense_layers": 0, "norm_type": "rmsnorm",
          "gemma_norm": False, "emb_scale": False, "enc_dec": False,
          "embed_norm": False}


def program_config(cj: dict):
    """The program's ``ModelConfig`` for a configuration file.  A size that
    differs from the program's registered config must be in ``reduced``."""
    fields = dict(_FIELDS)
    fields["intermediate_size"] = "d_ff_expert" \
        if cj["model"]["num_local_experts"] else "d_ff"
    cfg = arch.scaled_config(cj, fields)
    for field, want in _BLOCK.items():
        if getattr(cfg, field) != want:
            raise ValueError(f"{cj['name']}: {field} = {getattr(cfg, field)}; "
                             f"the Llama reference computes {want}")
    return cfg


def program_tree(model, m: dict, key, served):
    """The program's parameter tree, filled from ``bench.weights``."""
    shapes = layer_shapes(m)
    layers = jax.vmap(lambda i: W.layer_weights(shapes, key, i, served,
                                                served))(
        jnp.arange(m["num_hidden_layers"]))
    g = W.global_weights(global_shapes(m), key, served, served)
    d, h, hkv, hd = (m["hidden_size"], m["num_attention_heads"],
                     m["num_key_value_heads"], m["head_dim"])
    tree = {"embed": g["embed"], "final_norm": {"scale": g["final_norm"]}}
    if not m["tie_word_embeddings"]:
        tree["out"] = g["lm_head"]
    first = 0
    for i, seg in enumerate(model.dec_segments):
        if len(seg.kinds) != 1:
            raise ValueError(f"segment {i} mixes layer kinds: {seg.kinds}")
        n = seg.n_layers
        lw = jax.tree.map(lambda t: t[first:first + n], layers)
        first += n
        sub = {"norm1": {"scale": lw["attn_norm"]},
               "norm2": {"scale": lw["mlp_norm"]},
               "core": {"w_q": lw["wq"].reshape(n, d, h, hd),
                        "w_k": lw["wk"].reshape(n, d, hkv, hd),
                        "w_v": lw["wv"].reshape(n, d, hkv, hd),
                        "w_o": lw["wo"].reshape(n, h, hd, d)}}
        if is_moe(m):
            sub["moe"] = {"router": lw["router"], "w_gate": lw["e_gate"],
                          "w_up": lw["e_up"], "w_down": lw["e_down"]}
        else:
            sub["mlp"] = {"w_gate": lw["w_gate"], "w_up": lw["w_up"],
                          "w_down": lw["w_down"]}
        if not seg.scanned:
            sub = jax.tree.map(lambda t: t[0], sub)
        tree[f"seg{i}"] = {"sub0": sub}
    return tree


# ---------------------------------------------------------------------------
# weights and the reference
# ---------------------------------------------------------------------------


def is_moe(m: dict) -> bool:
    return m["num_local_experts"] > 0


def layer_shapes(m: dict) -> dict[str, tuple[tuple[int, ...], float]]:
    """(shape, std) of each tensor of one layer; std 0 means ones (norms)."""
    d, hd = m["hidden_size"], m["head_dim"]
    h, hkv, ff = m["num_attention_heads"], m["num_key_value_heads"], \
        m["intermediate_size"]
    s = {
        "attn_norm": ((d,), 0.0),
        "wq": ((d, h * hd), d ** -0.5),
        "wk": ((d, hkv * hd), d ** -0.5),
        "wv": ((d, hkv * hd), d ** -0.5),
        "wo": ((h * hd, d), (h * hd) ** -0.5),
        "mlp_norm": ((d,), 0.0),
    }
    if is_moe(m):
        e = m["num_local_experts"]
        s.update({"router": ((d, e), d ** -0.5),
                  "e_gate": ((e, d, ff), d ** -0.5),
                  "e_up": ((e, d, ff), d ** -0.5),
                  "e_down": ((e, ff, d), ff ** -0.5)})
    else:
        s.update({"w_gate": ((d, ff), d ** -0.5),
                  "w_up": ((d, ff), d ** -0.5),
                  "w_down": ((ff, d), ff ** -0.5)})
    return s


def global_shapes(m: dict) -> dict[str, tuple[tuple[int, ...], float]]:
    d, v = m["hidden_size"], m["vocab_size"]
    s = {"embed": ((v, d), W.EMBED_STD), "final_norm": ((d,), 0.0)}
    if not m["tie_word_embeddings"]:
        s["lm_head"] = ((d, v), W.EMBED_STD)
    return s


class Reference(reference.Reference):
    """``bench/reference/llama.py`` with this architecture's tensors."""

    def __init__(self, m: dict, seed: int, served_dtype: str):
        super().__init__(m, seed, served_dtype, layer_shapes(m),
                         global_shapes(m))


# ---------------------------------------------------------------------------
# operations and bytes (conventions: bench/counts.py)
# ---------------------------------------------------------------------------


def attn_params(m: dict) -> int:
    d, hd = m["hidden_size"], m["head_dim"]
    h, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    return d * (h + 2 * hkv) * hd + h * hd * d


def expert_params(m: dict) -> int:
    """One GLU expert (or the dense MLP)."""
    return 3 * m["hidden_size"] * m["intermediate_size"]


def mlp_active_params(m: dict) -> int:
    """MLP parameters one token multiplies by (routed experts + router)."""
    if is_moe(m):
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
    """Weights of all layers that a call over ``tokens`` tokens reads (for
    MoE, only the experts that some token routes to, in expectation under
    uniform routing)."""
    mlp = (expected_experts(m, tokens) * expert_params(m)
           + m["hidden_size"] * m["num_local_experts"]) if is_moe(m) \
        else expert_params(m)
    return m["num_hidden_layers"] * (attn_params(m) + mlp) * WEIGHT_BYTES


def kv_bytes_per_token(m: dict) -> int:
    return (m["num_hidden_layers"] * 2 * m["num_key_value_heads"]
            * m["head_dim"] * KV_BYTES)


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

