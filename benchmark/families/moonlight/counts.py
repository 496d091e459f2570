"""Operations of the Moonlight token-window Q-network's train step, from
shapes alone (the benchmark's own count; nothing here imports the
program).

Multiply-adds count 2. One grad step runs θ forward, θ⁻ forward and θ's
backward (twice a forward) on ``batch_size`` windows of
``sequence_length + 1`` tokens: 4 forwards' worth. Recomputation does not
count. Only (query, key) pairs inside the causal mask are counted for
attention, at the PUBLISHED widths (scores over ``qk_nope_head_dim +
qk_rope_head_dim``, values of ``v_head_dim``): the count does not move with
padding, with an absorbed form or with block sizes. Only the experts HELD
are counted for the expert layers; the shared expert is counted whole.
"""

from __future__ import annotations

from benchmark.families.tokenq.counts import (
    FORWARDS, causal_pairs, tokens_per_window)


def tokens(hp: dict) -> float:
    return float(hp["batch_size"] * tokens_per_window(hp))


def expert_layers(hp: dict) -> int:
    return hp["num_hidden_layers"] - hp["num_dense_layers"]


def mla_core_flops(hp: dict) -> float:
    """QKᵀ over the score width and PV over the value width of every
    layer, pairs inside the mask: ``2 · (dn + dr + dv)`` a pair a head."""
    per_pair = 2.0 * (hp["qk_nope_head_dim"] + hp["qk_rope_head_dim"]
                      + hp["v_head_dim"])
    per_window = (hp["num_attention_heads"] * per_pair
                  * causal_pairs(tokens_per_window(hp)))
    return FORWARDS * hp["batch_size"] * hp["num_hidden_layers"] * per_window


def mla_projection_flops(hp: dict) -> float:
    """``W_q``, ``W_kva``, ``W_kvb`` (keys and values expanded a head: the
    form that is not absorbed) and ``W_o``, every layer. ALL FOUR: the
    metric ``mla_proj_ms_per_step`` times the first three only (``W_o``,
    30.5 % of this count at the published widths, runs outside
    ``ddq.mla_down`` / ``ddq.mla_up``), so this is not its count."""
    h, hq, r = hp["hidden_size"], hp["num_attention_heads"], hp["kv_lora_rank"]
    dn, dr, dv = (hp["qk_nope_head_dim"], hp["qk_rope_head_dim"],
                  hp["v_head_dim"])
    per_token = 2.0 * (h * hq * (dn + dr) + h * (r + dr)
                       + r * hq * (dn + dv) + hq * dv * h)
    return FORWARDS * tokens(hp) * hp["num_hidden_layers"] * per_token


def dense_ffn_flops(hp: dict) -> float:
    per_token = 6.0 * hp["hidden_size"] * hp["intermediate_size"]
    return FORWARDS * tokens(hp) * hp["num_dense_layers"] * per_token


def shared_expert_flops(hp: dict) -> float:
    """The shared expert of every expert layer: one SwiGLU of width
    ``n_shared_experts · moe_intermediate_size`` over every token."""
    per_token = (6.0 * hp["hidden_size"] * hp["n_shared_experts"]
                 * hp["moe_intermediate_size"])
    return FORWARDS * tokens(hp) * expert_layers(hp) * per_token


def expected_held_slots(hp: dict) -> float:
    """Token-slots an expert layer routes to the experts held here in one
    grad step under even routing: tokens x top-k x held / all."""
    return (tokens(hp) * hp["num_experts_per_tok"] * hp["experts_held"]
            / hp["router_experts"])


def expected_slots_held_share(hp: dict) -> float:
    """Per cent of an expert layer's token-slots that come to the experts
    held here under even routing (100 x held / router width): what
    ``expert_ffn_roofline`` divides the measured share by."""
    return 100.0 * hp["experts_held"] / hp["router_experts"]


def expert_ffn_flops(hp: dict) -> float:
    """The grouped products of the experts held, one grad step, all
    expert layers: gate, up and down of ``moe_intermediate_size`` a slot
    (even routing; ``expert_ffn_roofline`` scales it by the
    share the layers' counter read)."""
    per_slot = 6.0 * hp["hidden_size"] * hp["moe_intermediate_size"]
    return FORWARDS * expert_layers(hp) * per_slot * expected_held_slots(hp)


def router_flops(hp: dict) -> float:
    return (FORWARDS * tokens(hp) * expert_layers(hp)
            * 2.0 * hp["hidden_size"] * hp["router_experts"])


def head_flops(hp: dict) -> float:
    return FORWARDS * tokens(hp) * 2.0 * hp["hidden_size"] * hp["vocab_size"]


PARTS = {"mla_core": mla_core_flops, "mla_projections": mla_projection_flops,
         "dense_ffn": dense_ffn_flops, "shared_expert": shared_expert_flops,
         "experts_held": expert_ffn_flops, "router": router_flops,
         "head": head_flops}


def train_flops_per_step(hp: dict) -> float:
    """What one grad step requires of the chip."""
    return sum(f(hp) for f in PARTS.values())


def train_flop_shares(hp: dict) -> dict:
    total = train_flops_per_step(hp)
    return {k: f(hp) / total for k, f in PARTS.items()}
