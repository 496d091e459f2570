"""Operations of the Laguna token-window Q-network's train step, from
shapes alone (the benchmark's own count; nothing here imports the
program).

Multiply-adds count 2. One grad step runs θ forward, θ⁻ forward and θ's
backward (twice a forward) on ``batch_size`` windows of
``sequence_length + 1`` tokens: 4 forwards' worth. Recomputation does not
count. Only (query, key) pairs INSIDE the mask are counted for attention —
the causal triangle on a full layer, the band of ``sliding_window`` keys
on a sliding one — at the heads of THAT kind of layer: the counts do not
move with the kernel's blocks or with padding. Only the experts HELD are
counted for the expert layers; the shared expert is counted whole. The
dense layer, the held experts (even routing; ``expert_ffn_roofline``
scales it by the share the layers' counter read), router and head are
counted under the keys the ``lfm2`` family counts them by, so by import.
"""

from __future__ import annotations

from benchmark.families.lfm2.counts import (  # noqa: F401 — same keys
    dense_ffn_flops, expected_held_slots, expected_slots_held_share,
    expert_ffn_flops, expert_layers, head_flops, router_flops, tokens)
from benchmark.families.tokenq.counts import (
    FORWARDS, causal_pairs, tokens_per_window)

SLIDING = "sliding_attention"


def core_flops(hp: dict, sliding: bool) -> float:
    """QKᵀ and PV of the layers of one kind, pairs inside the mask: 4 ·
    ``head_dim`` a pair a head."""
    pairs = causal_pairs(tokens_per_window(hp),
                         hp["sliding_window"] if sliding else 0)
    heads = sum(h for h, k in zip(hp["num_attention_heads_per_layer"],
                                  hp["layer_types"])
                if (k == SLIDING) == sliding)
    return FORWARDS * hp["batch_size"] * 4.0 * heads * hp["head_dim"] * pairs


def full_core_flops(hp: dict) -> float:
    return core_flops(hp, False)


def window_core_flops(hp: dict) -> float:
    return core_flops(hp, True)


def attention_projection_flops(hp: dict) -> float:
    """``W_q``, ``W_k``, ``W_v``, ``W_o`` and the gate's ``W_g`` at each
    layer's own head count."""
    h, d, hkv = hp["hidden_size"], hp["head_dim"], hp["num_key_value_heads"]
    per_token = sum(2.0 * h * (2 * hq + 2 * hkv) * d + 2.0 * h * hq
                    for hq in hp["num_attention_heads_per_layer"])
    return FORWARDS * tokens(hp) * per_token


def shared_expert_flops(hp: dict) -> float:
    """The shared expert of every expert layer: one SwiGLU of width
    ``shared_expert_intermediate_size`` over every token."""
    per_token = (6.0 * hp["hidden_size"]
                 * hp["shared_expert_intermediate_size"])
    return FORWARDS * tokens(hp) * expert_layers(hp) * per_token


PARTS = {"full_core": full_core_flops, "window_core": window_core_flops,
         "attention_projections": attention_projection_flops,
         "dense_ffn": dense_ffn_flops, "shared_expert": shared_expert_flops,
         "experts_held": expert_ffn_flops, "router": router_flops,
         "head": head_flops}


def train_flops_per_step(hp: dict) -> float:
    """What one grad step requires of the chip."""
    return sum(f(hp) for f in PARTS.values())


def train_flop_shares(hp: dict) -> dict:
    total = train_flops_per_step(hp)
    return {k: f(hp) / total for k, f in PARTS.items()}
