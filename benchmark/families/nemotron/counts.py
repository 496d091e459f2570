"""Operations and bytes of the Nemotron token-window Q-network's train
step, from shapes alone (the benchmark's own count; nothing here imports
the program).

Multiply-adds count 2. One grad step runs θ forward, θ⁻ forward and θ's
backward (twice a forward) on ``batch_size`` windows of
``sequence_length + 1`` tokens: 4 forwards' worth. Recomputation does not
count. A layer is ONE part alone (``pattern``: "M" a Mamba-2 mixer, "*"
attention, "E" the expert layer). Only (query, key) pairs inside the
causal mask are counted for attention, and for the scan's two products
inside a chunk only the pairs ``j <= i``; only the experts HELD for the
expert layers, at their PUBLISHED width (two products of
``moe_intermediate_size``: what the grouped matmul's tiles round it up to
is counted nowhere); the shared expert whole.
"""

from __future__ import annotations

from benchmark.families.tokenq.counts import (
    FORWARDS, causal_pairs, tokens_per_window)

F32 = 4


def tokens(hp: dict) -> float:
    return float(hp["batch_size"] * tokens_per_window(hp))


def layers(hp: dict, letter: str) -> int:
    return hp["pattern"][:hp["num_hidden_layers"]].count(letter)


def d_inner(hp: dict) -> int:
    return hp["mamba_num_heads"] * hp["mamba_head_dim"]


def conv_channels(hp: dict) -> int:
    return d_inner(hp) + 2 * hp["n_groups"] * hp["ssm_state_size"]


def ssm_projection_flops(hp: dict) -> float:
    """``W_in`` (h x (z | xBC | dt)) and ``W_out`` (d_inner x h) of every
    Mamba layer."""
    h, di = hp["hidden_size"], d_inner(hp)
    per_token = 2.0 * h * (di + conv_channels(hp) + hp["mamba_num_heads"]) \
        + 2.0 * di * h
    return FORWARDS * tokens(hp) * layers(hp, "M") * per_token


def ssm_conv_flops(hp: dict) -> float:
    """The depthwise taps and the bias over x, B and C."""
    per_token = (2.0 * hp["conv_kernel"] + 1.0) * conv_channels(hp)
    return FORWARDS * tokens(hp) * layers(hp, "M") * per_token


def ssm_scan_flops(hp: dict) -> float:
    """The scan's four products in chunks of ``chunk_size`` Q, a token a
    Mamba layer a forward: the pairs ``j <= i`` of its chunk ((Q + 1) / 2
    a token) at 2 N a group (C·B) and 2 P a head (weights x values), and
    the chunk state in and out at 2 P N a head each — 2.76 MFLOP at the
    published sizes, against 2 x 38.7M parameters of projections."""
    q, n, p = hp["chunk_size"], hp["ssm_state_size"], hp["mamba_head_dim"]
    nh, g = hp["mamba_num_heads"], hp["n_groups"]
    per_token = (q + 1) / 2.0 * (2.0 * n * g + 2.0 * p * nh) \
        + 4.0 * p * n * nh
    return FORWARDS * tokens(hp) * layers(hp, "M") * per_token


def ssm_scan_bytes(hp: dict) -> float:
    """What the scan must read and write in one grad step, whatever
    implements it, in float32: a forward reads x, B, C and Δ (conv
    channels + heads a token) and writes y (d_inner), for θ and θ⁻: twice;
    the backward reads them and y's cotangent again and writes theirs. The
    chunk states (``[P, N]`` a head a CHUNK: 1/128 of a token's share) and
    ``A``, ``D`` are nothing beside it."""
    ins = conv_channels(hp) + hp["mamba_num_heads"]
    per_token = (2 * (ins + d_inner(hp)) + (ins + d_inner(hp) + ins)) * F32
    return tokens(hp) * layers(hp, "M") * per_token


def attention_flops(hp: dict) -> float:
    """QKᵀ and PV of the attention layers, pairs inside the mask."""
    per_window = (4.0 * hp["num_attention_heads"] * hp["head_dim"]
                  * causal_pairs(tokens_per_window(hp)))
    return FORWARDS * hp["batch_size"] * layers(hp, "*") * per_window


def attention_projection_flops(hp: dict) -> float:
    h, d = hp["hidden_size"], hp["head_dim"]
    hq, hkv = hp["num_attention_heads"], hp["num_key_value_heads"]
    per_token = 2.0 * h * (hq + 2 * hkv) * d + 2.0 * hq * d * h
    return FORWARDS * tokens(hp) * layers(hp, "*") * per_token


def shared_expert_flops(hp: dict) -> float:
    """The shared expert of every expert layer: two products of
    ``moe_shared_expert_intermediate_size`` over every token."""
    per_token = (4.0 * hp["hidden_size"]
                 * hp["moe_shared_expert_intermediate_size"])
    return FORWARDS * tokens(hp) * layers(hp, "E") * per_token


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
    expert layers: TWO products (up, down) of ``moe_intermediate_size`` a
    slot — the published 1 856, never the 1 920 the tiles cover (even
    routing; ``expert_ffn_roofline`` scales it by the share the layers'
    counter read)."""
    per_slot = 4.0 * hp["hidden_size"] * hp["moe_intermediate_size"]
    return FORWARDS * layers(hp, "E") * per_slot * expected_held_slots(hp)


def router_flops(hp: dict) -> float:
    return (FORWARDS * tokens(hp) * layers(hp, "E")
            * 2.0 * hp["hidden_size"] * hp["router_experts"])


def head_flops(hp: dict) -> float:
    return FORWARDS * tokens(hp) * 2.0 * hp["hidden_size"] * hp["vocab_size"]


PARTS = {"ssm_projections": ssm_projection_flops, "ssm_conv": ssm_conv_flops,
         "ssm_scan": ssm_scan_flops, "attention_kernel": attention_flops,
         "attention_projections": attention_projection_flops,
         "shared_expert": shared_expert_flops,
         "experts_held": expert_ffn_flops, "router": router_flops,
         "head": head_flops}


def train_flops_per_step(hp: dict) -> float:
    """What one grad step requires of the chip."""
    return sum(f(hp) for f in PARTS.values())


def train_flop_shares(hp: dict) -> dict:
    total = train_flops_per_step(hp)
    return {k: f(hp) / total for k, f in PARTS.items()}
