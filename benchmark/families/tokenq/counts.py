"""Operations of the token-window Q-network's train step, from shapes alone
(the benchmark's own count; nothing here imports the program).

Multiply-adds count 2. One grad step runs θ forward, θ⁻ forward and θ's
backward (twice a forward: weight and input gradients) on ``batch_size``
windows of ``sequence_length + 1`` tokens: 4 forwards' worth. Recomputation
(the per-layer and per-block rematerialisation, the flash kernels' second
look at the scores) does not count. Only what the mask lets through is
counted for attention, and only the experts HELD for the expert layer.
"""

from __future__ import annotations

FORWARDS = 4.0      # θ forward + θ⁻ forward + θ backward (2)


def tokens_per_window(hp: dict) -> int:
    return hp["sequence_length"] + 1


def causal_pairs(t: int, window: int = 0) -> float:
    """(query, key) pairs inside the mask: key s <= query t, and with a
    window t - window < s."""
    if not window or window >= t:
        return t * (t + 1) / 2.0
    return window * (window + 1) / 2.0 + (t - window) * float(window)


def layer_windows(hp: dict) -> list[int]:
    return [hp["sliding_window_size"] if hp["sliding_window_layout"][i]
            else 0 for i in range(hp["num_hidden_layers"])]


def attention_forward_flops(hp: dict, window: int) -> float:
    """QKᵀ and PV of one layer over one window, pairs inside the mask."""
    return (4.0 * hp["num_attention_heads"] * hp["head_dim"]
            * causal_pairs(tokens_per_window(hp), window))


def attention_flops(hp: dict, kinds=(True, False)) -> float:
    """The attention kernel's required FLOPs in one grad step over the
    layers whose kind (windowed?) is in ``kinds``."""
    return FORWARDS * hp["batch_size"] * sum(
        attention_forward_flops(hp, w) for w in layer_windows(hp)
        if bool(w) in kinds)


def window_attention_flops(hp: dict) -> float:
    """All calls of the one blockwise kernel in a grad step: the full
    layers and the window layers (``window_attention_roofline``)."""
    return attention_flops(hp)


def expected_held_slots(hp: dict) -> float:
    """Token-slots a layer routes to the experts held here in one grad
    step under even routing: tokens x top-k x held / all."""
    return (hp["batch_size"] * tokens_per_window(hp)
            * hp["moe_num_active_primary_experts"]
            * hp["moe_experts_held"] / hp["moe_router_experts"])


def expected_slots_held_share(hp: dict) -> float:
    """Per cent of an expert layer's token-slots that come to the experts
    held here under even routing (100 x held / router width): what
    ``expert_ffn_roofline`` divides the measured share by."""
    return 100.0 * hp["moe_experts_held"] / hp["moe_router_experts"]


def expert_ffn_flops(hp: dict) -> float:
    """The grouped products of the experts held, one grad step, all
    layers: gate, up and down of width ``moe_ffn_hidden_size`` a slot."""
    per_slot = 6.0 * hp["hidden_size"] * hp["moe_ffn_hidden_size"]
    return (FORWARDS * hp["num_hidden_layers"] * per_slot
            * expected_held_slots(hp))


def dense_forward_flops_per_token(hp: dict) -> float:
    """Projections and router of one layer, one token."""
    h, d = hp["hidden_size"], hp["head_dim"]
    hq, hkv = hp["num_attention_heads"], hp["num_key_value_heads"]
    return (2.0 * h * (hq + 2 * hkv) * d + 2.0 * hq * d * h
            + 2.0 * h * hp["moe_router_experts"])


def head_flops(hp: dict) -> float:
    return (FORWARDS * hp["batch_size"] * tokens_per_window(hp)
            * 2.0 * hp["hidden_size"] * hp["vocab_size"])


def train_flops_per_step(hp: dict) -> float:
    """What one grad step requires of the chip."""
    tokens = hp["batch_size"] * tokens_per_window(hp)
    dense = (FORWARDS * tokens * hp["num_hidden_layers"]
             * dense_forward_flops_per_token(hp))
    return dense + attention_flops(hp) + expert_ffn_flops(hp) \
        + head_flops(hp)


def train_flop_shares(hp: dict) -> dict:
    total = train_flops_per_step(hp)
    return {"attention": attention_flops(hp) / total,
            "experts_held": expert_ffn_flops(hp) / total,
            "head": head_flops(hp) / total,
            "projections_router": 1.0 - (attention_flops(hp)
                                         + expert_ffn_flops(hp)
                                         + head_flops(hp)) / total}
