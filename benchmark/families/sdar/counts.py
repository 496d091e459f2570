"""Operations of the SDAR token-window Q-network's train step, from shapes
alone (the benchmark's own count; nothing here imports the program).

Multiply-adds count 2. One grad step runs θ forward, θ⁻ forward and θ's
backward (twice a forward) on ``batch_size`` windows, each PACKED to
``sequence_length + 1`` clean rows and G·B noised ones (``packed_rows``):
4 forwards' worth. Recomputation does not count. Attention counts the
(query, key) pairs the FOUR RULES allow (``allowed_pairs``: counted from
the rules, not from what the kernel runs — the kernel's blocks and
padding move nothing here). Only the experts HELD are counted for the
expert layers; the head runs on the decision rows alone, one a block.
"""

from __future__ import annotations

FORWARDS = 4.0      # θ forward + θ⁻ forward + θ backward (2)


def blocks(hp: dict) -> int:
    return -(-hp["sequence_length"] // hp["block_length"])


def packed_rows(hp: dict) -> int:
    """Rows of one packed window: T + 1 clean, G·B noised."""
    return hp["sequence_length"] + 1 + blocks(hp) * hp["block_length"]


def tokens(hp: dict) -> int:
    """Rows a layer's dense parts run on in one forward of the batch."""
    return hp["batch_size"] * packed_rows(hp)


def allowed_pairs(hp: dict) -> float:
    """(query, key) pairs of one packed window inside the three-part mask:
    a clean row at position p sees the clean rows up to its block's end; a
    noised row the clean rows before its block and its own noised
    block."""
    t, bl = hp["sequence_length"], hp["block_length"]
    clean = 1.0                                         # position 0: itself
    for p in range(1, t + 1):
        clean += min(((p - 1) // bl + 1) * bl, t) + 1
    noised = 0.0
    for p in range(1, blocks(hp) * bl + 1):
        noised += ((p - 1) // bl) * bl + 1 + bl
    return clean + noised


def allowed_share(hp: dict) -> float:
    """The allowed pairs over the packed rows' square, percent."""
    return 100.0 * allowed_pairs(hp) / packed_rows(hp) ** 2


def bd_core_flops(hp: dict) -> float:
    """QKᵀ and PV under the block mask, every layer: 4 · ``head_dim`` a
    pair a head."""
    return (FORWARDS * hp["batch_size"] * hp["num_hidden_layers"] * 4.0
            * hp["num_attention_heads"] * hp["head_dim"]
            * allowed_pairs(hp))


def attention_projection_flops(hp: dict) -> float:
    """``W_q``, ``W_k``, ``W_v``, ``W_o`` over the packed rows."""
    h, d = hp["hidden_size"], hp["head_dim"]
    hq, hkv = hp["num_attention_heads"], hp["num_key_value_heads"]
    per_token = 2.0 * h * (2 * hq + 2 * hkv) * d
    return FORWARDS * tokens(hp) * hp["num_hidden_layers"] * per_token


def expected_held_slots(hp: dict) -> float:
    """Token-slots a layer routes to the experts held here in one forward
    under even routing: rows x top-k x held / all."""
    return (tokens(hp) * hp["num_experts_per_tok"] * hp["experts_held"]
            / hp["router_experts"])


def expected_slots_held_share(hp: dict) -> float:
    """Per cent of an expert layer's token-slots that come to the experts
    held here under even routing (100 x held / router width): what
    ``expert_ffn_roofline`` divides the measured share by."""
    return 100.0 * hp["experts_held"] / hp["router_experts"]


def expert_ffn_flops(hp: dict) -> float:
    """The grouped products of the experts held, one grad step, all
    layers: gate, up and down of width ``moe_intermediate_size`` a
    slot."""
    per_slot = 6.0 * hp["hidden_size"] * hp["moe_intermediate_size"]
    return (FORWARDS * hp["num_hidden_layers"] * per_slot
            * expected_held_slots(hp))


def router_flops(hp: dict) -> float:
    return (FORWARDS * tokens(hp) * hp["num_hidden_layers"] * 2.0
            * hp["hidden_size"] * hp["router_experts"])


def head_flops(hp: dict) -> float:
    """The Q head on the decision rows alone: one a block a window."""
    return (FORWARDS * hp["batch_size"] * blocks(hp) * 2.0
            * hp["hidden_size"] * hp["vocab_size"])


PARTS = {"bd_core": bd_core_flops,
         "attention_projections": attention_projection_flops,
         "experts_held": expert_ffn_flops, "router": router_flops,
         "head": head_flops}


def train_flops_per_step(hp: dict) -> float:
    """What one grad step requires of the chip."""
    return sum(f(hp) for f in PARTS.values())


def train_flop_shares(hp: dict) -> dict:
    total = train_flops_per_step(hp)
    return {k: f(hp) / total for k, f in PARTS.items()}
