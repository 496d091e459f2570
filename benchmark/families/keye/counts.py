"""Operations of the Keye-VL-2.0 token-window Q-network's train step, from
shapes alone (the benchmark's own count; nothing here imports the
program).

Multiply-adds count 2. One grad step runs θ forward, θ⁻ forward and θ's
backward (twice a forward) on ``batch_size`` windows of ``sequence_length
+ 1`` tokens: 4 forwards' worth. Recomputation does not count. USEFUL work
only: attention over the SELECTED (query, key) pairs (a query keeps
``topk`` of its earlier keys, all of them while it has no more) whatever
the kernel multiplies; the indexer scores every causal pair forward (it
must) and only the selected pairs backward (the loss reads no other); only
the experts HELD count for the expert layers.
"""

from __future__ import annotations

from benchmark.families.tokenq.counts import (
    FORWARDS, causal_pairs, tokens_per_window)


def tokens(hp: dict) -> float:
    return float(hp["batch_size"] * tokens_per_window(hp))


def pairs_causal(hp: dict) -> float:
    """(query, key) pairs with key <= query in one window."""
    return causal_pairs(tokens_per_window(hp))


def pairs_selected(hp: dict) -> float:
    """Pairs the selection keeps in one window: ``Σ_t min(t + 1, topk)``
    (a window of ``topk`` keys keeps as many as the indexer does)."""
    return causal_pairs(tokens_per_window(hp), hp["topk"])


def pairs_selected_share(hp: dict) -> float:
    """The selection's share of the causal pairs, in percent: exact, it
    does not depend on the scores."""
    return 100.0 * pairs_selected(hp) / pairs_causal(hp)


def sparse_core_flops(hp: dict) -> float:
    """QKᵀ and PV over the selected pairs, forward and backward, θ and θ⁻,
    every layer (``sparse_core_roofline``)."""
    per_window = (4.0 * hp["num_attention_heads"] * hp["head_dim"]
                  * pairs_selected(hp))
    return (FORWARDS * hp["batch_size"] * hp["num_hidden_layers"]
            * per_window)


def indexer_scores_flops(hp: dict) -> float:
    """The indexer's score product (``indexer_num_heads`` dot products of
    ``indexer_head_dim`` and their relu-weighted sum a pair): every causal
    pair forward for θ and θ⁻, the selected pairs twice for θ's backward
    (``indexer_scores_roofline``)."""
    per_pair = (2.0 * hp["indexer_head_dim"] + 2.0) * hp["indexer_num_heads"]
    pairs = 2.0 * pairs_causal(hp) + 2.0 * pairs_selected(hp)
    return hp["batch_size"] * hp["num_hidden_layers"] * per_pair * pairs


def attention_projection_flops(hp: dict) -> float:
    h, d = hp["hidden_size"], hp["head_dim"]
    hq, hkv = hp["num_attention_heads"], hp["num_key_value_heads"]
    per_token = 2.0 * h * (hq + 2 * hkv) * d + 2.0 * hq * d * h
    return FORWARDS * tokens(hp) * hp["num_hidden_layers"] * per_token


def indexer_projection_flops(hp: dict) -> float:
    """``W_iq``, ``W_ik``, ``W_iw``: θ forward, θ⁻ forward and θ's weight
    gradients (their input carries no gradient): 3 forwards' worth."""
    hi, di = hp["indexer_num_heads"], hp["indexer_head_dim"]
    per_token = 2.0 * hp["hidden_size"] * (hi * di + di + hi)
    return 3.0 * tokens(hp) * hp["num_hidden_layers"] * per_token


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
    layers: gate, up and down of ``moe_intermediate_size`` a slot (even
    routing; ``expert_ffn_roofline`` scales it by the share the
    layers' counter read)."""
    per_slot = 6.0 * hp["hidden_size"] * hp["moe_intermediate_size"]
    return (FORWARDS * hp["num_hidden_layers"] * per_slot
            * expected_held_slots(hp))


def router_flops(hp: dict) -> float:
    return (FORWARDS * tokens(hp) * hp["num_hidden_layers"]
            * 2.0 * hp["hidden_size"] * hp["router_experts"])


def head_flops(hp: dict) -> float:
    return FORWARDS * tokens(hp) * 2.0 * hp["hidden_size"] * hp["vocab_size"]


PARTS = {"sparse_core": sparse_core_flops,
         "indexer_scores": indexer_scores_flops,
         "attention_projections": attention_projection_flops,
         "indexer_projections": indexer_projection_flops,
         "experts_held": expert_ffn_flops, "router": router_flops,
         "head": head_flops}


def train_flops_per_step(hp: dict) -> float:
    """What one grad step requires of the chip."""
    return sum(f(hp) for f in PARTS.values())


def train_flop_shares(hp: dict) -> dict:
    total = train_flops_per_step(hp)
    return {k: f(hp) / total for k, f in PARTS.items()}
