"""DeepSeek-V2: multi-head latent attention under YaRN, a leading dense
layer, then mixtures of 160 routed experts (group-limited greedy top-6,
gates scaled by 16, not renormalised) beside 2 shared experts; an untied
LM head.

The configuration's ``model`` holds Hugging Face key names
(``kv_lora_rank``, ``n_routed_experts``, ``rope_scaling``, ...) with two
keys of the cut: ``n_routed_experts`` counts the experts held on this chip,
``first_held_expert`` is the first of them, and ``router_experts`` is the
router's width over all of them (the published ``n_routed_experts``).

The checkpoint's rotary columns (of ``q_b``'s rope part and of the shared
rope key in ``kv_a``) are interleaved pairs, which the model de-interleaves
before rotate-half; the program rotates split halves, so ``program_tree``
permutes those columns, as a checkpoint converter does.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch
from bench import weights as W
from bench.counts import KV_BYTES, WEIGHT_BYTES, ZERO, Work
from bench.reference import deepseek_v2 as reference

# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

# Hugging Face key in a configuration file -> ModelConfig field
_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "moe_intermediate_size": "d_ff_expert",
    "num_experts_per_tok": "top_k", "n_shared_experts": "n_shared_experts",
    "first_k_dense_replace": "first_dense_layers", "n_group": "n_group",
    "topk_group": "topk_group", "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "vocab_size": "vocab_size", "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "hidden_act": "act",
}
# rope_scaling key -> ModelConfig field
_YARN = {"factor": "yarn_factor",
         "original_max_position_embeddings": "yarn_original_max_pos",
         "beta_fast": "yarn_beta_fast", "beta_slow": "yarn_beta_slow",
         "mscale": "yarn_mscale", "mscale_all_dim": "yarn_mscale_all_dim"}
# the block that bench/reference/deepseek_v2.py computes; a program config
# that says otherwise is another architecture
_BLOCK = {"layer_pattern": ("attn",), "mlp_type": "glu", "pos_type": "rope",
          "use_mla": True, "norm_type": "rmsnorm",
          "gemma_norm": False, "emb_scale": False, "enc_dec": False,
          "embed_norm": False}
# the model's own settings that the reference and the program both assume
_MODEL = {"model_type": "deepseek_v2", "scoring_func": "softmax",
          "topk_method": "group_limited_greedy", "moe_layer_freq": 1,
          "attention_bias": False, "hidden_act": "silu",
          "tie_word_embeddings": False}


def program_config(cj: dict):
    """The program's ``ModelConfig`` for a configuration file: the sizes,
    the router's width and YaRN mapped onto the registered config (where
    one differs, its key must be in ``reduced``), and the held experts."""
    m = cj["model"]
    for key, want in _MODEL.items():
        if m[key] != want:
            raise ValueError(f"{cj['name']}: {key} = {m[key]!r}; the "
                             f"DeepSeek-V2 reference computes {want!r}")
    if m["rope_scaling"]["type"] != "yarn":
        raise ValueError(f"{cj['name']}: rope_scaling is not YaRN")
    cfg = arch.scaled_config(cj, {**_FIELDS, "router_experts": "n_experts"})
    published = cj.get("published", {}).get("n_routed_experts", cfg.n_experts)
    if published != cfg.n_experts:
        raise ValueError(f"{cj['name']}: the router spans {cfg.n_experts} "
                         f"experts, the published model {published}")
    first, held = m["first_held_expert"], m["n_routed_experts"]
    if held != cfg.n_experts and "n_routed_experts" not in cj["reduced"]:
        raise ValueError(f"{cj['name']}: {held} of {cfg.n_experts} experts "
                         f"held, and n_routed_experts is not in 'reduced'")
    if not 0 <= first < first + held <= cfg.n_experts:
        raise ValueError(f"{cj['name']}: experts {first}..{first + held - 1} "
                         f"of {cfg.n_experts}")
    yarn = {field: m["rope_scaling"][key] for key, field in _YARN.items()}
    if "rope_scaling" not in cj["reduced"] and any(
            getattr(cfg, f) != v for f, v in yarn.items()):
        raise ValueError(f"{cj['name']}: rope_scaling differs from the "
                         f"program's, and is not in 'reduced'")
    cfg = cfg.scaled(held_experts=(first, held), **yarn)
    for field, want in _BLOCK.items():
        if getattr(cfg, field) != want:
            raise ValueError(f"{cj['name']}: {field} = {getattr(cfg, field)}; "
                             f"the DeepSeek-V2 reference computes {want}")
    if cfg.n_kv_heads != cfg.n_heads:
        raise ValueError(f"{cj['name']}: MLA gives every head its own key")
    return cfg


def _rope_order(n: int) -> np.ndarray:
    """The checkpoint's interleaved rotary columns in split-half order."""
    return np.concatenate([np.arange(0, n, 2), np.arange(1, n, 2)])


def _attn_tree(m: dict, w: dict) -> dict:
    """One layer's MLA weights (leading axes, if any, kept) in the
    program's shapes."""
    h, nope, rope, vd, kl = (m["num_attention_heads"], m["qk_nope_head_dim"],
                             m["qk_rope_head_dim"], m["v_head_dim"],
                             m["kv_lora_rank"])
    order = _rope_order(rope)
    lead = w["q_a"].shape[:-2]
    q_b = w["q_b"].reshape(*lead, m["q_lora_rank"], h, nope + rope)
    q_b = jnp.concatenate([q_b[..., :nope], q_b[..., nope:][..., order]], -1)
    kv_b = w["kv_b"].reshape(*lead, kl, h, nope + vd)
    return {"w_dq": w["q_a"], "q_norm": {"scale": w["q_a_norm"]},
            "w_uq": q_b, "w_dkv": w["kv_a"][..., :kl],
            "kv_norm": {"scale": w["kv_a_norm"]},
            "w_kr": w["kv_a"][..., kl:][..., order],
            "w_uk": kv_b[..., :nope], "w_uv": kv_b[..., nope:],
            "w_o": w["o"].reshape(*lead, h, vd, m["hidden_size"])}


def _layer_tree(m: dict, w: dict, dense: bool) -> dict:
    sub = {"norm1": {"scale": w["attn_norm"]},
           "norm2": {"scale": w["mlp_norm"]},
           "core": _attn_tree(m, w)}
    if dense:
        sub["mlp"] = {"w_gate": w["w_gate"], "w_up": w["w_up"],
                      "w_down": w["w_down"]}
    else:
        sub["moe"] = {"router": w["router"], "w_gate": w["e_gate"],
                      "w_up": w["e_up"], "w_down": w["e_down"]}
        sub["shared"] = {"w_gate": w["s_gate"], "w_up": w["s_up"],
                         "w_down": w["s_down"]}
    return sub


def program_tree(model, m: dict, key, served):
    """The program's parameter tree, filled from ``bench.weights``: layer i
    from ``layer_shapes(m, dense=i < first_k_dense_replace)``."""
    g = W.global_weights(global_shapes(m), key, served, served)
    tree = {"embed": g["embed"], "final_norm": {"scale": g["final_norm"]},
            "out": g["lm_head"]}
    n_dense = m["first_k_dense_replace"]
    first = 0
    for i, seg in enumerate(model.dec_segments):
        if len(seg.kinds) != 1:
            raise ValueError(f"segment {i} mixes layer kinds: {seg.kinds}")
        dense = first < n_dense
        n = seg.n_layers
        if dense != (first + n <= n_dense):
            raise ValueError(f"segment {i} mixes dense and MoE layers")
        shapes = layer_shapes(m, dense)
        layers = jax.vmap(lambda j: W.layer_weights(shapes, key, j, served,
                                                    served))(
            jnp.arange(first, first + n))
        first += n
        sub = _layer_tree(m, layers, dense)
        if not seg.scanned:
            sub = jax.tree.map(lambda t: t[0], sub)
        tree[f"seg{i}"] = {"sub0": sub}
    return tree


# ---------------------------------------------------------------------------
# weights and the reference
# ---------------------------------------------------------------------------


def layer_shapes(m: dict, dense: bool = False
                 ) -> dict[str, tuple[tuple[int, ...], float]]:
    """(shape, std) of each tensor of one layer, in the checkpoint's layout;
    std 0 means ones (norms).  ``dense``: a leading dense layer; else a
    mixture layer, with the router over all experts and the held experts."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    ql, kl = m["q_lora_rank"], m["kv_lora_rank"]
    s = {
        "attn_norm": ((d,), 0.0),
        "q_a": ((d, ql), d ** -0.5),
        "q_a_norm": ((ql,), 0.0),
        "q_b": ((ql, h * (nope + rope)), ql ** -0.5),
        "kv_a": ((d, kl + rope), d ** -0.5),
        "kv_a_norm": ((kl,), 0.0),
        "kv_b": ((kl, h * (nope + vd)), kl ** -0.5),
        "o": ((h * vd, d), (h * vd) ** -0.5),
        "mlp_norm": ((d,), 0.0),
    }
    if dense:
        ff = m["intermediate_size"]
        s.update({"w_gate": ((d, ff), d ** -0.5), "w_up": ((d, ff), d ** -0.5),
                  "w_down": ((ff, d), ff ** -0.5)})
        return s
    e, ff = m["n_routed_experts"], m["moe_intermediate_size"]
    sf = m["n_shared_experts"] * ff
    s.update({"router": ((d, m["router_experts"]), d ** -0.5),
              "e_gate": ((e, d, ff), d ** -0.5),
              "e_up": ((e, d, ff), d ** -0.5),
              "e_down": ((e, ff, d), ff ** -0.5),
              "s_gate": ((d, sf), d ** -0.5), "s_up": ((d, sf), d ** -0.5),
              "s_down": ((sf, d), sf ** -0.5)})
    return s


def global_shapes(m: dict) -> dict[str, tuple[tuple[int, ...], float]]:
    d, v = m["hidden_size"], m["vocab_size"]
    return {"embed": ((v, d), W.EMBED_STD), "final_norm": ((d,), 0.0),
            "lm_head": ((d, v), W.EMBED_STD)}


class Reference(reference.Reference):
    """``bench/reference/deepseek_v2.py`` with this architecture's
    tensors."""

    def __init__(self, m: dict, seed: int, served_dtype: str):
        super().__init__(m, seed, served_dtype, layer_shapes,
                         global_shapes(m))


# ---------------------------------------------------------------------------
# operations and bytes (conventions: bench/counts.py)
# ---------------------------------------------------------------------------


def attn_params(m: dict) -> int:
    d, h = m["hidden_size"], m["num_attention_heads"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    ql, kl = m["q_lora_rank"], m["kv_lora_rank"]
    return (d * ql + ql * h * (nope + rope) + d * (kl + rope)
            + kl * h * (nope + vd) + h * vd * d)


def latent_row(m: dict) -> int:
    """Cache entries one token holds in one layer: its latent and its
    rotary key."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def key_flops(m: dict) -> int:
    """One query against one latent row in one layer of absorbed MLA:
    scores over the latent and the rotary key, and the weighted latent."""
    h, kl = m["num_attention_heads"], m["kv_lora_rank"]
    return 2 * h * (2 * kl + m["qk_rope_head_dim"])


def attn_flops(m: dict, n_keys: float) -> float:
    """One token through one layer's absorbed MLA (the serving path: the
    query absorbs ``w_uk``, scores and outputs stay in the latent space,
    ``w_uv`` is applied after) over ``n_keys`` latent rows."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    ql, kl = m["q_lora_rank"], m["kv_lora_rank"]
    return 2 * (d * ql + ql * h * (nope + rope)  # query
                + d * (kl + rope)  # latent and rotary key
                + h * nope * kl  # absorb w_uk into the query
                + h * kl * vd  # absorb w_uv
                + h * vd * d  # output projection
                ) + n_keys * key_flops(m)


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def held_share(m: dict) -> float:
    """Of a token's top-k, the share that lands on the experts held here
    (uniform routing)."""
    return m["n_routed_experts"] / m["router_experts"]


def n_moe_layers(m: dict) -> int:
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def mlp_flops(m: dict) -> float:
    """One token through every layer's MLP: the dense layers' GLU, and in
    each mixture layer the router over all experts, the held share of its
    top-k and the shared experts."""
    d = m["hidden_size"]
    dense = 2 * 3 * d * m["intermediate_size"]
    moe = 2 * (d * m["router_experts"]
               + m["num_experts_per_tok"] * held_share(m) * expert_params(m)
               + m["n_shared_experts"] * expert_params(m))
    return m["first_k_dense_replace"] * dense + n_moe_layers(m) * moe


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def expected_experts(m: dict, tokens: int) -> float:
    """Distinct held experts that ``tokens`` tokens route to, in
    expectation, when each picks top-k of all experts uniformly."""
    k, e = m["num_experts_per_tok"], m["router_experts"]
    return m["n_routed_experts"] * (1.0 - (1.0 - k / e) ** tokens)


def layer_weight_bytes(m: dict, tokens: int) -> float:
    """Weights of all layers that a call over ``tokens`` tokens reads: for
    the mixtures, the router, the shared experts and the held experts that
    some token routes to."""
    d = m["hidden_size"]
    dense = 3 * d * m["intermediate_size"]
    moe = (d * m["router_experts"] + m["n_shared_experts"] * expert_params(m)
           + expected_experts(m, tokens) * expert_params(m))
    return (m["num_hidden_layers"] * attn_params(m)
            + m["first_k_dense_replace"] * dense
            + n_moe_layers(m) * moe) * WEIGHT_BYTES


def latent_bytes_per_token(m: dict) -> int:
    return m["num_hidden_layers"] * latent_row(m) * KV_BYTES


def prefill_round(m: dict, members: list[tuple[int, int, bool]]) -> Work:
    """One prefill call: ``members`` are (start, chunk, finishes_prompt)."""
    if not members:
        return ZERO
    layers = m["num_hidden_layers"]
    flops = 0.0
    cache = 0.0
    tokens = 0
    heads = 0
    for start, c, finishes in members:
        # chunk tokens at positions start .. start + c - 1
        keys = c * start + c * (c + 1) / 2
        flops += (layers * (c * attn_flops(m, 0) + keys * key_flops(m))
                  + c * mlp_flops(m))
        cache += (start + c) * latent_bytes_per_token(m)  # read held, write
        tokens += c
        heads += finishes
    flops += heads * 2 * head_params(m)
    byts = (layer_weight_bytes(m, tokens) + cache
            + tokens * m["hidden_size"] * WEIGHT_BYTES
            + (head_params(m) * WEIGHT_BYTES if heads else 0))
    return Work(flops, byts)


def decode_tick(m: dict, positions: list[int]) -> Work:
    """One decode tick of the slots at ``positions`` (each token attends to
    its position + 1 latent rows; the rows held are read once)."""
    if not positions:
        return ZERO
    n = len(positions)
    layers = m["num_hidden_layers"]
    flops = sum(layers * attn_flops(m, p + 1) for p in positions) \
        + n * (mlp_flops(m) + 2 * head_params(m))
    byts = (layer_weight_bytes(m, n) + head_params(m) * WEIGHT_BYTES
            + sum(p + 1 for p in positions) * latent_bytes_per_token(m)
            + n * m["hidden_size"] * WEIGHT_BYTES)
    return Work(flops, byts)
