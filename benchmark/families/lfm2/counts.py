"""Operations and bytes of the LFM2 token-window Q-network's train step,
from shapes alone (the benchmark's own count; nothing here imports the
program).

Multiply-adds count 2. One grad step runs θ forward, θ⁻ forward and θ's
backward (twice a forward) on ``batch_size`` windows of
``sequence_length + 1`` tokens: 4 forwards' worth. Recomputation does not
count. Only (query, key) pairs inside the causal mask are counted for
attention, and only the experts HELD for the expert layers.
"""

from __future__ import annotations

from benchmark.families.tokenq.counts import (
    FORWARDS, causal_pairs, tokens_per_window)

F32 = 4.0


def tokens(hp: dict) -> float:
    return float(hp["batch_size"] * tokens_per_window(hp))


def conv_layers(hp: dict) -> int:
    return sum(k == "conv" for k in hp["layer_types"])


def attention_layers(hp: dict) -> int:
    return hp["num_hidden_layers"] - conv_layers(hp)


def expert_layers(hp: dict) -> int:
    return hp["num_hidden_layers"] - hp["num_dense_layers"]


def attention_flops(hp: dict) -> float:
    """QKᵀ and PV of the attention layers, pairs inside the mask."""
    per_window = (4.0 * hp["num_attention_heads"] * hp["head_dim"]
                  * causal_pairs(tokens_per_window(hp)))
    return FORWARDS * hp["batch_size"] * attention_layers(hp) * per_window


def attention_projection_flops(hp: dict) -> float:
    h, d = hp["hidden_size"], hp["head_dim"]
    hq, hkv = hp["num_attention_heads"], hp["num_key_value_heads"]
    per_token = 2.0 * h * (hq + 2 * hkv) * d + 2.0 * hq * d * h
    return FORWARDS * tokens(hp) * attention_layers(hp) * per_token


def short_conv_flops(hp: dict) -> float:
    """The conv operators: ``W_in`` (h x 3h) and ``W_out`` (h x h) a
    token, and the mix between them (two gates, ``L`` taps)."""
    h = hp["hidden_size"]
    per_token = 8.0 * h * h + (2.0 + 2.0 * hp["conv_L_cache"]) * h
    return FORWARDS * tokens(hp) * conv_layers(hp) * per_token


def short_conv_mix_bytes(hp: dict) -> float:
    """What the gates and the convolution must read and write in one grad
    step, whatever implements them, in float32: a forward reads ``[B, C,
    z]`` (3h a token) and writes h (θ and θ⁻: twice); the backward reads
    the cotangent (h) and ``[B, C, z]`` again and writes their cotangent
    (3h). The taps and their gradient are nothing beside it."""
    per_token = (2 * (3 + 1) + (1 + 3 + 3)) * hp["hidden_size"] * F32
    return tokens(hp) * conv_layers(hp) * per_token


def dense_ffn_flops(hp: dict) -> float:
    per_token = 6.0 * hp["hidden_size"] * hp["intermediate_size"]
    return FORWARDS * tokens(hp) * hp["num_dense_layers"] * per_token


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
    (even routing; ``expert_ffn_roofline`` scales it by the share
    the layer's counter read)."""
    per_slot = 6.0 * hp["hidden_size"] * hp["moe_intermediate_size"]
    return FORWARDS * expert_layers(hp) * per_slot * expected_held_slots(hp)


def router_flops(hp: dict) -> float:
    return (FORWARDS * tokens(hp) * expert_layers(hp)
            * 2.0 * hp["hidden_size"] * hp["router_experts"])


def head_flops(hp: dict) -> float:
    return FORWARDS * tokens(hp) * 2.0 * hp["hidden_size"] * hp["vocab_size"]


PARTS = {"short_conv": short_conv_flops, "dense_ffn": dense_ffn_flops,
         "attention_kernel": attention_flops,
         "attention_projections": attention_projection_flops,
         "experts_held": expert_ffn_flops, "router": router_flops,
         "head": head_flops}


def train_flops_per_step(hp: dict) -> float:
    """What one grad step requires of the chip."""
    return sum(f(hp) for f in PARTS.values())


def train_flop_shares(hp: dict) -> dict:
    total = train_flops_per_step(hp)
    return {k: f(hp) / total for k, f in PARTS.items()}
