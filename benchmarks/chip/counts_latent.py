"""Operations and bytes the algorithm needs for the latent-attention MoE
configuration (DeepSeek-V2-Lite on one chip's expert share), from its
widths alone. This is the benchmark's yardstick: the program may change,
these functions do not follow it.

Counts are of weights held on the chip (the block of ``experts_held``
routed experts, everything else whole) and of the work a token needs:
the non-expert weights multiply every token once, a routed expert's
weights only the rows routed to it, which the program counts
(``moe.routed_rows``, ``moe.experts_touched``).
"""
from __future__ import annotations

#: bytes a weight or a cached value takes in the served dtype (bfloat16)
BF16 = 2


def attn_params(m: dict) -> int:
    """One latent-attention block: W_q, W_dkv, the latent's norm, W_ukv,
    W_o and the block's input norm."""
    d, H, R = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return (d * H * (dn + dr) + d * (R + dr) + R + R * H * (dn + dv)
            + H * dv * d + d)


def expert_params(m: dict) -> int:
    """One routed expert's SwiGLU: gate, up and down."""
    return 3 * m["d_model"] * m["moe_d_ff"]


def layer_params(m: dict, moe: bool) -> int:
    """One whole layer as held here: attention, the second norm, and the
    dense MLP or the router, the held experts and the shared experts."""
    d = m["d_model"]
    n = attn_params(m) + d
    if not moe:
        return n + 3 * d * m["d_ff"]
    held = m["experts_held"] or m["n_experts"]
    return (n + d * m["n_experts"] + held * expert_params(m)
            + m["n_shared_experts"] * expert_params(m))


def n_moe_layers(m: dict) -> int:
    return m["n_layers"] - m["first_dense_layers"]


def held_params(m: dict) -> int:
    """Every parameter on the chip, the embedding table included."""
    L0 = m["first_dense_layers"]
    return (L0 * layer_params(m, False) + n_moe_layers(m) * layer_params(m, True)
            + 2 * m["vocab"] * m["d_model"] + m["d_model"])


def non_expert_params(m: dict) -> int:
    """Weights every token multiplies: all held weights but the routed
    experts and the embedding table (a gather, not a product)."""
    held = m["experts_held"] or m["n_experts"]
    return (held_params(m) - m["vocab"] * m["d_model"]
            - n_moe_layers(m) * held * expert_params(m))


def latent_bytes_per_position(m: dict) -> int:
    """The cached latent and rotary key of one position, all layers."""
    return (m["n_layers"] * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            * BF16)


def decode_bytes(m: dict, steps: int, experts_touched: int,
                 live_positions: int, active_slots: int) -> int:
    """Least HBM traffic of ``steps`` batched decode steps: every
    non-expert weight once a step; each held expert's weights once in
    every layer and step that routed it a row (``experts_touched``,
    summed over layers and steps); the cached latents of each active
    slot's live positions, up to and including the new token, and each
    active slot's new row written once. ``live_positions`` and
    ``active_slots`` are summed over the steps."""
    return (steps * non_expert_params(m) * BF16
            + experts_touched * expert_params(m) * BF16
            + latent_bytes_per_position(m) * (live_positions + active_slots))


def causal_pairs(prompt_len: int) -> int:
    """(query, key) pairs of one causal prompt."""
    return prompt_len * (prompt_len + 1) // 2


def prefill_flops(m: dict, prompt_tokens: int, prompts: int,
                  routed_rows: int, pairs: int) -> float:
    """One forward pass over ``prompts`` prompts of ``prompt_tokens``
    tokens in all: every non-expert weight but the head once per token,
    the head once per prompt (only the last position's logits are
    needed), each routed row through its expert, and causal attention's
    quadratic part, scores (dn + dr wide) and the weighted sum of values
    (dv wide), over ``pairs`` (query, key) pairs in every layer."""
    head = m["d_model"] * m["vocab"]
    per_pair = m["n_heads"] * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
                               + m["v_head_dim"])
    return 2.0 * ((non_expert_params(m) - head) * prompt_tokens
                  + head * prompts + routed_rows * expert_params(m)
                  + m["n_layers"] * per_pair * pairs)
