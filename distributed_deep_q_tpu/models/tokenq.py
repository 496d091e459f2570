"""Token-window Q-network: a decoder-only backbone whose head row ``a``
is Q(token prefix, next token ``a``) — ``net.kind = "tokenq"``.

The state at position t is the prefix ``tok[0..t]``, the action is the
next token, and ONE causal forward over a window gives Q at every
position. Every size comes from ``config.TokenQConfig`` (the published
``config.json`` keys of the architecture being run) and ``net.num_actions``
(the vocabulary rows held); nothing is hard-coded here.

ONE backbone whose layers are data (``layer_plan``): each is a token
mixer and a feed-forward in pre-norm residual form — or, where
``hybrid_override_pattern`` says so, ONE of the two alone under its one
norm (``norm_1`` a mixer's, ``norm_2`` a feed-forward's) —, input x
``[B, T, h]`` (float32 residual stream), ``u = rmsnorm_1(x)``:

- mixer, ``x' = x + m``:
  - attention (grouped-query, causal; ``sliding_window_layout[l]`` = 1
    limits it to the last ``sliding_window_size`` keys,
    ``rope_layout[l]`` = 1 rotates q/k in the rotate-half convention,
    theta ``rope_theta`` — or, where ``rope_parameters`` states the
    layer's KIND (full / sliding), the first ``partial_rotary_factor ·
    head_dim`` columns at that kind's base, blended and scaled under YaRN
    (``rotary_table``, ``rotary_by_table``), the other columns unturned;
    the rotation and the cast to the compute dtype are ONE pass over q
    and over k each way where a head fills the 128 lanes
    (``rotary_cast`` → ``ops/rotary.turn``), the plain form otherwise;
    with ``qk_norm`` an RMSNorm of each head of q and k before the
    rotation; ``num_attention_heads_per_layer[l]`` query heads where the
    configuration counts them a layer; ``ops/attention.causal_attention``
    serves every kind): ``m = attn(u) · W_o``, and with ``gating``
    ``m = (sigmoid(u W_g) ⊙_head attn(u)) · W_o``, one float32 gate a
    head a token;
  - ``layer_types[l] == "conv"``: the gated short convolution,
    ``m = short_conv_mix(u · W_in, w_conv) · W_out``
    (``ops/short_conv.py``: ``CONV_TAPS`` causal taps between two
    gates);
  - ``layer_types[l] == "sparse_attention"``: the same heads over the
    ``indexer_topk`` keys a learned INDEXER selects for each query
    (``ops/sparse_attention.py``): ``qI = ū W_iq`` [``indexer_num_heads``
    x ``indexer_head_dim``], ``kI = rmsnorm(ū W_ik)`` (one key head),
    ``wI = ū W_iw``, ``ū = stop_gradient(u)``, the layer's rotary on
    both; index scores, an exact top-k, attention over the kept keys;
    the indexer's own loss comes back with the layer's counters and the
    learner adds it to the TD loss. The whole indexer is float32; the
    window is padded to whole blocks of ``indexer_q_chunk`` inside the
    mixer;
  - ``layer_types[l] == "latent_attention"``: ``num_attention_heads``
    query heads ``q = u W_q`` of ``qk_nope_head_dim`` +
    ``qk_rope_head_dim``; ``[c | k_r] = u W_kva`` (``kv_lora_rank`` |
    ``qk_rope_head_dim``), ``[k_n | v] = rmsnorm(c; kv_norm) W_kvb`` a
    head (``qk_nope_head_dim`` | ``v_head_dim``); the rotary embedding on
    each head's ``q_r`` and on the ONE ``k_r`` every head shares, over
    the interleaved pairs (2i, 2i+1) as ``deepseek_v3`` turns them (a
    fact of this mixer, no option); causal scores over
    ``[q_n | q_r] · [k_n | k_r]`` at their width to the -1/2, values
    ``v_head_dim`` wide: ``m = attn · W_o``. Keys and values are expanded
    a head (the form that is not absorbed);
  - a pattern's "M" (``hybrid_override_pattern`` alone names it: a layer
    with this mixer has no feed-forward): the Mamba-2
    state-space mixer (``mamba_mixer``): ``[z | xBC | dt] = u W_in``, a
    causal depthwise convolution of ``conv_kernel`` taps with a bias and
    SiLU over x, B and C, the selective scan with a state ``[head_dim,
    ssm_state_size]`` a head in chunks of ``chunk_size``
    (``ops/ssd.py``), a gated RMSNorm a group, ``W_out``; the window a
    segment of ``ssm_segment`` rows at a time, state and convolution tail
    carried, each segment rematerialised on its own;
- feed-forward over ``w = rmsnorm_2(x')``, ``y = x' + f``:
  - ``l < num_dense_layers``: ``f = (act(w W_gate) * (w W_up)) W_down`` of
    width ``intermediate_size``, blockwise over tokens;
  - else ``f = Σ_{e in top k, held here} p_e · (act(w W_gate,e) * (w
    W_up,e)) W_down,e`` (``ops/moe.held_experts_ffn``: this process's
    share of an expert-parallel layer), ``act`` = ``hidden_act``: relu
    (ReGLU) or silu (SwiGLU) — with ``ffn_gated`` false every
    feed-forward is TWO matrices, ``act(w W_up) W_down`` (``relu2``:
    relu(x)²); with ``n_shared_experts`` > 0 plus ``S(w)``,
    ONE ungated feed-forward of width ``n_shared_experts ·
    moe_ffn_hidden_size`` that every token takes, whole on every member
    of the group (``dense_ffn``, blockwise). The ROUTER
    (``ops/moe.route``) reads what ``router_input`` says: ``w``
    ("ffn_norm"), or ``u`` — the layer's normed input, BEFORE the mixer
    ("pre_mixer"); its renormalised gates are multiplied by
    ``routed_scaling_factor``.

With ``block_length`` > 0 (generation by diffusion over blocks) the
window is cut into blocks after position 0 and attention is no longer
causal: on the training path the window stands in the rows TWICE
(``bd_pack``: a clean copy, then a copy in which each block shows its
first ``reveal`` tokens and the mask token at the rest), both copies at
the same position ids, under the three-part block mask
(``ops/attention.block_diffusion_attention``: a clean row sees the clean
rows of its own and earlier blocks, a noised row the clean rows of
earlier blocks and the noised rows of its own); one row a block — its
first masked one (``bd_decision_rows``) — is a decision, and the learner
gathers those before the head. On the acting path ONE copy runs under the
block-causal part of that mask, the prefix followed by the mask token
(``q_at``). Every other product (norms, projections, router, experts)
runs on the packed rows as on any window.

Every branch here is on a mechanism a ``TokenQConfig`` field names, never
on which model is being run.

SmallThinker's settings: attention on every layer (window + rope, or full
with no positional encoding), ReGLU experts everywhere, a softmax router
before attention. LFM2's: convolutions among full attention with q/k
norms, a leading dense layer, SwiGLU, a sigmoid router with a selection
bias reading the second norm. Keye-VL-2.0's: sparse attention with q/k
norms and rope on every layer, SwiGLU experts behind a softmax router
reading the second norm. Moonlight's (``deepseek_v3``): latent attention
on every layer, a leading dense layer, SwiGLU experts behind LFM2's
router with scaled gates, and a shared expert beside them. Laguna's:
window and full attention at a head count and a rotary embedding of
their own (YaRN over half of each head on the full layers), a gate a head
on every attention output, a leading dense layer, SwiGLU experts behind a
sigmoid router with no bias and scaled gates, a shared expert.
Nemotron-3-Nano's (``nemotron_h``): the pattern ``MEMEM*E`` — Mamba-2
mixers, expert layers and attention with no positional embedding, each
alone under one norm; two-matrix relu² experts behind LFM2's router with
scaled gates, a shared expert. SDAR's
(``sdar_moe``): Keye's numbers without the indexer — plain attention with
q/k norms and rope on every layer, SwiGLU experts behind a softmax router
reading the second norm — in blocks of 4 under the block mask, the last
vocabulary row held the mask token.

Then the final RMSNorm; the untied head ``[h, V]`` is applied by the
learner, blockwise over tokens, together with the TD loss
(``parallel/sequence_learner.py``). Matmuls run in ``net.compute_dtype``
with float32 accumulation; norms, router, gates and convolution, rotary
and the residual stream are float32. Each layer is rematerialised in the
backward pass (a state-space layer a segment at a time, inside its mixer);
a sparse layer's two halves apart (``mixer``,
``feed_forward``: what the mixer's backward needs does not stand beside
the expert layer's buffers), and its mixer keeps its selection (bits, no
gradient) and its indexer's loss with that loss's gradients across it, so
the top-k search and the loss run once a forward pass — θ's index scores
three times a step in all: the selection's (which also hands the loss
each row's normaliser), the loss's one product a chunk of keys, and that
product's pull-back; θ⁻ and the acting path score once and take no loss.

Parameters are a plain nested dict; a leaf's name is its path
(``layer_02/w_gate``), which is what weight IO uses.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from distributed_deep_q_tpu.config import (
    NetConfig, RopeParameters, TokenQConfig)
from distributed_deep_q_tpu.ops import moe, sparse_attention, ssd
from distributed_deep_q_tpu.ops import rotary as rotary_pass
from distributed_deep_q_tpu.ops.attention import (
    bd_rows, block_diffusion_attention, causal_attention)
from distributed_deep_q_tpu.ops.short_conv import short_conv_mix

INIT_STD = 0.02
BIAS_STD = 0.01     # the expert bias: seeded, and no gradient reaches it
CONV_TAPS = 3       # a conv layer's taps (LFM2's ``conv_L_cache``)
ACTS = {"relu": jax.nn.relu, "silu": jax.nn.silu,       # ``hidden_act``
        "relu2": lambda x: jnp.square(jax.nn.relu(x))}
ROUTER_INPUTS = ("pre_mixer", "ffn_norm")
MIXERS = ("conv", "full_attention", "sparse_attention",     # ``layer_types``
          "latent_attention")
# ``hybrid_override_pattern``: a letter a layer -> (its mixer, its
# feed-forward), ONE of the two and "none" for the other
PATTERN = {"M": ("mamba", "none"), "*": ("full_attention", "none"),
           "E": ("none", "experts")}
# a Mamba-2 mixer's seeded Δ: log-uniform between the ``nemotron_h``
# family's ``time_step_min`` and ``time_step_max`` (keys of the init alone)
DT_RANGE = (1e-3, 1e-1)
ROPE_TYPES = ("default", "yarn")


def layer_name(i: int) -> str:
    return f"layer_{i:02d}"


def layer_plan(tq: TokenQConfig) -> list[dict[str, Any]]:
    """Per layer ``{windowed, rope, conv, sparse, latent, mamba, dense}``
    (bools): the mixer from ``layer_types`` (absent: attention) and the
    two layouts, the feed-forward from ``num_dense_layers``; ``mixer`` /
    ``ffn``: whether the layer has that half at all (both, unless
    ``hybrid_override_pattern`` gives every layer ONE part alone: then the
    pattern's letters say the mixer and the feed-forward, ``PATTERN``);
    ``heads``, the layer's query heads (``num_attention_heads_per_layer``;
    absent: ``num_attention_heads``); ``rope_params``, the
    ``RopeParameters`` of the layer's kind (``None``: ``rope_theta`` over
    all of ``head_dim``)."""
    n = tq.num_hidden_layers
    pattern = tq.hybrid_override_pattern[:n]
    if pattern:
        if len(pattern) < n or set(pattern) - set(PATTERN):
            raise ValueError(
                f"hybrid_override_pattern must name {n} layers by "
                f"{' | '.join(PATTERN)}: {tq.hybrid_override_pattern!r}")
        if tq.layer_types or tq.num_dense_layers:
            raise ValueError("hybrid_override_pattern states every layer's "
                             "mixer and feed-forward: layer_types and "
                             "num_dense_layers must be empty / 0")
    if len(tq.sliding_window_layout) < n or len(tq.rope_layout) < n:
        raise ValueError(
            f"sliding_window_layout/rope_layout must cover "
            f"{n} layers: {tq.sliding_window_layout} {tq.rope_layout}")
    if tq.layer_types and (len(tq.layer_types) < n or set(
            tq.layer_types[:n]) - set(MIXERS)):
        raise ValueError(f"layer_types must name {n} layers as "
                         f"{' | '.join(MIXERS)}: {tq.layer_types}")
    if tq.hidden_act not in ACTS:
        raise ValueError(f"hidden_act must be one of {sorted(ACTS)}: "
                         f"{tq.hidden_act!r}")
    if tq.router_input not in ROUTER_INPUTS:
        raise ValueError(f"router_input must be one of {ROUTER_INPUTS}: "
                         f"{tq.router_input!r}")
    kinds = (tuple(PATTERN[c][0] for c in pattern) or tq.layer_types[:n]
             or ("full_attention",) * n)
    ffns = tuple(PATTERN[c][1] for c in pattern) or tuple(
        "dense" if i < tq.num_dense_layers else "experts" for i in range(n))
    per_layer = tuple(int(x) for x in tq.num_attention_heads_per_layer)
    if per_layer and len(per_layer) < n:
        raise ValueError(f"num_attention_heads_per_layer must cover {n} "
                         f"layers: {per_layer}")
    plan = [{"windowed": bool(tq.sliding_window_layout[i]),
             "rope": bool(tq.rope_layout[i]),
             "conv": kinds[i] == "conv",
             "sparse": kinds[i] == "sparse_attention",
             "latent": kinds[i] == "latent_attention",
             "mamba": kinds[i] == "mamba",
             "mixer": kinds[i] != "none", "ffn": ffns[i] != "none",
             "dense": ffns[i] == "dense",
             "heads": per_layer[i] if per_layer
             else tq.num_attention_heads,
             "rope_params": _rope_params(
                 tq, bool(tq.sliding_window_layout[i]))}
            for i in range(n)]
    plain = [kinds[i] == "full_attention" for i in range(n)]
    if "mamba" in kinds and tq.mamba_num_heads % tq.n_groups:
        raise ValueError(f"{tq.n_groups} groups do not divide "
                         f"{tq.mamba_num_heads} state-space heads")
    if pattern and tq.router_input != "ffn_norm":
        raise ValueError("an expert layer alone has one norm: "
                         "router_input must be 'ffn_norm'")
    if any(k["heads"] % tq.num_key_value_heads
           for k, p in zip(plan, plain) if p):
        raise ValueError(
            f"{tq.num_key_value_heads} key/value heads do not divide the "
            f"query heads of every layer: {[k['heads'] for k in plan]}")
    if (per_layer or any(k["rope_params"] for k in plan)) and any(
            k["sparse"] or k["latent"] for k in plan):
        raise ValueError("a head count a layer and rotary parameters a "
                         "kind of layer are the plain attention mixer's: "
                         f"{kinds}")
    if tq.gating and not all(plain):
        raise ValueError(f"gating is the plain attention mixer's gate: "
                         f"{kinds}")
    if tq.gating and tq.qk_norm:
        raise ValueError("gating together with qk_norm is held to no "
                         "reference")
    if any((k["sparse"] or k["latent"]) and k["windowed"] for k in plan):
        raise ValueError("a sparse_attention or latent_attention layer "
                         f"takes no sliding window: "
                         f"{tq.sliding_window_layout}")
    if tq.qk_norm and any(k["latent"] for k in plan):
        raise ValueError("a latent_attention layer takes no qk_norm (its "
                         "latent has a norm of its own: kv_norm)")
    if tq.block_length and (
            not all(plain) or tq.gating or per_layer or any(
                k["windowed"] or k["rope_params"] for k in plan)):
        raise ValueError(
            "block_length > 0 (generation by diffusion over blocks) is the "
            "plain full attention mixer's, with one head count and one "
            "rope_theta: with a window, a conv, sparse or latent mixer, "
            "gating or rotary parameters a kind it is held to no reference")
    return plan


def mask_token(cfg: NetConfig) -> int:
    """The mask token of generation by diffusion over blocks: the LAST row
    held, which is no action (-1 with ``block_length`` 0: none)."""
    return cfg.num_actions - 1 if cfg.tokenq.block_length else -1


def _rope_params(tq: TokenQConfig, windowed: bool) -> RopeParameters | None:
    """The rotary parameters of a layer's kind where the configuration
    states them, checked; ``None`` where it states none."""
    rp = (tq.rope_parameters.sliding_attention if windowed
          else tq.rope_parameters.full_attention)
    if not rp.rope_type:
        return None
    if rp.rope_type not in ROPE_TYPES:
        raise ValueError(f"rope_type must be one of {ROPE_TYPES}: "
                         f"{rp.rope_type!r}")
    width = rp.partial_rotary_factor * tq.head_dim
    if width != int(width) or int(width) % 2 or not 0 < width <= tq.head_dim:
        raise ValueError(
            f"partial_rotary_factor {rp.partial_rotary_factor} of head_dim "
            f"{tq.head_dim} is no even number of columns")
    if rp.rope_type == "yarn" and not (
            rp.factor >= 1 and rp.original_max_position_embeddings > 0):
        raise ValueError(f"yarn needs a factor >= 1 and the original "
                         f"positions: {rp}")
    return rp


def rotary_table(rp: RopeParameters, head_dim: int):
    """A kind's rotary embedding as constants of the program → (inverse
    frequencies [r / 2] float32, the factor on cos and sin); r =
    ``partial_rotary_factor · head_dim`` columns turn. "default":
    ``rope_theta^(-2i/r)``, factor 1. "yarn" (as ``transformers`` computes
    it): pair i's frequency is ``e_i = rope_theta^(-2i/r)`` below ``low``,
    ``e_i / factor`` above ``high`` and a linear blend between, ``low`` /
    ``high`` the pairs that turn ``beta_fast`` / ``beta_slow`` times
    inside the original positions; the factor is ``attention_factor``.
    Computed with numpy in float64 and rounded once."""
    r = int(rp.partial_rotary_factor * head_dim)
    i = np.arange(r // 2, dtype=np.float64)
    extra = float(rp.rope_theta) ** (-2.0 * i / r)
    if rp.rope_type != "yarn":
        return extra.astype(np.float32), 1.0

    def pair(turns: float) -> float:    # the pair that turns ``turns`` times
        return (r * np.log(rp.original_max_position_embeddings
                           / (turns * 2.0 * np.pi))
                / (2.0 * np.log(rp.rope_theta)))
    low = max(int(np.floor(pair(rp.beta_fast))), 0)
    high = min(int(np.ceil(pair(rp.beta_slow))), r - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = extra / rp.factor * ramp + extra * (1.0 - ramp)
    return inv.astype(np.float32), float(rp.attention_factor)


def param_shapes(cfg: NetConfig) -> dict[str, Any]:
    tq, v = cfg.tokenq, cfg.num_actions
    h, d = tq.hidden_size, tq.head_dim
    hq, hkv = tq.num_attention_heads, tq.num_key_value_heads
    e, f = tq.experts_held, tq.moe_ffn_hidden_size
    if not 0 <= tq.expert_offset <= tq.moe_num_primary_experts - e:
        raise ValueError(
            f"experts [{tq.expert_offset}, {tq.expert_offset + e}) are not "
            f"among {tq.moe_num_primary_experts}")
    def attention(heads: int) -> dict[str, tuple]:
        out = {"w_q": (h, heads * d), "w_k": (h, hkv * d),
               "w_v": (h, hkv * d), "w_o": (heads * d, h)}
        if tq.qk_norm:
            out.update({"q_norm": (d,), "k_norm": (d,)})
        if tq.gating:
            out["w_g"] = (h, heads)
        return out
    dn, dr, dv, r = (tq.qk_nope_head_dim, tq.qk_rope_head_dim,
                     tq.v_head_dim, tq.kv_lora_rank)
    latent = {"w_q": (h, hq * (dn + dr)), "w_kva": (h, r + dr),
              "kv_norm": (r,), "w_kvb": (r, hq * (dn + dv)),
              "w_o": (hq * dv, h)}
    conv = {"w_in": (h, 3 * h), "w_conv": (h, CONV_TAPS),
            "w_out": (h, h)}
    nh = tq.mamba_num_heads
    di = nh * tq.mamba_head_dim                     # the mixer's channels
    xbc = di + 2 * tq.n_groups * tq.ssm_state_size  # x, B, C: convolved
    mamba = {"w_in": (h, di + xbc + nh), "ssm_conv_w": (xbc, tq.conv_kernel),
             "ssm_conv_b": (xbc,), "a_log": (nh,), "d_skip": (nh,),
             "dt_bias": (nh,), "gate_norm": (di,), "w_out": (di, h)}
    hi, di = tq.indexer_num_heads, tq.indexer_head_dim
    indexer = {"w_iq": (h, hi * di), "w_ik": (h, di), "w_iw": (h, hi),
               "ik_norm": (di,)}
    experts = {"w_router": (h, tq.moe_num_primary_experts),
               "w_gate": (e, h, f), "w_up": (e, h, f), "w_down": (e, f, h)}
    if tq.use_expert_bias:
        experts["expert_bias"] = (tq.moe_num_primary_experts,)
    if tq.n_shared_experts:
        fs = shared_width(tq)
        experts.update({"shared_gate": (h, fs), "shared_up": (h, fs),
                        "shared_down": (fs, h)})
    fi = tq.intermediate_size
    dense = {"w_gate": (h, fi), "w_up": (h, fi), "w_down": (fi, h)}
    if not tq.ffn_gated:        # two matrices: no leaf for a gate
        del experts["w_gate"], dense["w_gate"]
        experts.pop("shared_gate", None)
    shapes: dict[str, Any] = {"embed": (v, h), "final_norm": (h,),
                              "head": (h, v)}
    for i, kind in enumerate(layer_plan(tq)):
        mixer = {"norm_1": (h,),
                 **(conv if kind["conv"] else latent if kind["latent"]
                    else mamba if kind["mamba"]
                    else attention(kind["heads"])),
                 **(indexer if kind["sparse"] else {})}
        ffn = {"norm_2": (h,), **(dense if kind["dense"] else experts)}
        # a layer that is one part alone has that part's norm alone
        shapes[layer_name(i)] = {**(mixer if kind["mixer"] else {}),
                                 **(ffn if kind["ffn"] else {})}
    return shapes


def shared_width(tq: TokenQConfig) -> int:
    """The shared expert's width: stated, or ``n_shared_experts`` experts'
    widths side by side."""
    return (tq.moe_shared_expert_intermediate_size
            or tq.n_shared_experts * tq.moe_ffn_hidden_size)


def init_params(cfg: NetConfig, seed: int) -> dict[str, Any]:
    """Normal(0, 0.02) matrices, unit norms, a Normal(0, 0.01) expert
    bias (it stays as seeded: no gradient reaches it), float32. A Mamba-2
    mixer's own: taps and their bias uniform within ``±taps^-½`` (the
    source framework's default for a depthwise convolution), ``A``
    uniform in [1, 16) (``a_log`` its log), ``dt_bias`` the inverse
    softplus of a Δ log-uniform in ``DT_RANGE``, ``d_skip`` 1."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))

    def leaf(key, path, shape):
        name = path[-1].key
        if name in ("ssm_conv_w", "ssm_conv_b"):
            bound = cfg.tokenq.conv_kernel ** -0.5
            return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        if name == "a_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        if name == "dt_bias":
            lo, hi = np.log(DT_RANGE)
            dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
            return dt + jnp.log(-jnp.expm1(-dt))
        if len(shape) > 1:
            return INIT_STD * jax.random.normal(key, shape, jnp.float32)
        if name == "expert_bias":
            return BIAS_STD * jax.random.normal(key, shape, jnp.float32)
        return jnp.ones(shape, jnp.float32)

    return jax.tree_util.tree_unflatten(
        treedef, [leaf(k, path, s) for k, (path, s) in zip(keys, leaves)])


def named_leaves(params: dict[str, Any]) -> dict[str, jax.Array]:
    """``{"layer_00/w_q": leaf, ...}``: the names weight IO goes by."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return {"/".join(str(k.key) for k in path): leaf for path, leaf in flat}


def from_named(params: dict[str, Any], named: dict[str, Any]):
    """A tree shaped like ``params`` with every leaf taken from ``named``
    by its path; a missing or extra name is an error."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    names = ["/".join(str(k.key) for k in path) for path, _ in flat]
    if set(names) != set(named):
        raise KeyError(f"leaf names differ: {set(names) ^ set(named)}")
    return jax.tree_util.tree_unflatten(treedef, [named[n] for n in names])


def rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _positions(t: int, positions) -> jax.Array:
    """Row i's position id, float32 [T]: ``positions`` where the caller
    states them (two rows of a packed window may share one), else the row
    index."""
    if positions is None:
        return jnp.arange(t, dtype=jnp.float32)
    return jnp.asarray(positions, jnp.float32)


def rotary_by_table(x: jax.Array, inv, factor: float,
                    positions=None) -> jax.Array:
    """Rotary embedding over ``[B, H, T, D]`` from a kind's table
    (``rotary_table``: ``inv`` [r / 2]), float32, row i at position
    ``positions[i]`` (``None``: i, the row index — every window that holds
    each position once): only the FIRST r columns of each head turn,
    rotate-half among themselves (i with i + r/2), pair i by ``position ·
    inv[i]``, cos and sin multiplied by ``factor``; the other D - r pass
    through unturned and unscaled."""
    d, t = x.shape[-1], x.shape[-2]
    r = 2 * inv.shape[0]
    ang = _positions(t, positions)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1) * factor
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1) * factor
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    turned = x[..., :r] * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([turned, x[..., r:]], -1) if r < d else turned


def rope_inv(theta: float, d: int) -> jax.Array:
    """Pair i's inverse frequency at ONE base over all of a head,
    ``theta^(-2i/d)`` [d / 2] float32."""
    return theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)


def rotary_cast(q: jax.Array, k: jax.Array, inv, factor: float, positions,
                dtype, interpret: bool):
    """``rotary_by_table(x, inv, factor, positions).astype(dtype)`` of
    ``q`` and of ``k`` [B, H, T, D] float32, from ONE pair of tables and in
    one pass over each, each way (``ops/rotary.turn``), where a head fills
    the lanes; the plain form where it does not (decided from D alone)."""
    d, t = q.shape[-1], q.shape[-2]
    if not rotary_pass.fills_lanes(d):
        return tuple(rotary_by_table(x, inv, factor, positions).astype(dtype)
                     for x in (q, k))
    table = rotary_pass.tables(inv, factor, _positions(t, positions), d)
    return tuple(rotary_pass.turn(x, *table, 2 * inv.shape[0], dtype,
                                  interpret) for x in (q, k))


def rotary_fused(tq: TokenQConfig) -> int | None:
    """1 where every rotate-half layer of the plan turns q and k by the
    fused pass, 0 where they keep the plain form (``rotary_cast``'s own
    rule); ``None`` where no layer turns rotate-half (conv and latent
    mixers do not, nor does a state-space mixer or a layer without one)."""
    turning = any(k["rope"] and k["mixer"]
                  and not (k["conv"] or k["latent"] or k["mamba"])
                  for k in layer_plan(tq))
    return int(rotary_pass.fills_lanes(tq.head_dim)) if turning else None


def rotary(x: jax.Array, theta: float, interleave: bool = False,
           positions=None) -> jax.Array:
    """Rotary embedding over ``[B, H, T, D]``, float32, row i at position
    ``positions[i]`` (``None``: the row index; the packed rows of a
    block-diffusion window state theirs, which the clean and the noised
    copy share), ONE base over all of ``D`` and no scaling (a kind of
    layer with rotary parameters of its own turns by ``rotary_by_table``):
    pair i turns by ``position · theta^(-2i/D)``. Rotate-half pairs element
    i with i + D/2; ``interleave`` pairs 2i with 2i + 1."""
    d, t = x.shape[-1], x.shape[-2]
    inv = rope_inv(theta, d)
    if not interleave:
        return rotary_by_table(x, inv, 1.0, positions)
    ang = _positions(t, positions)[:, None] * inv[None, :]
    cos, sin = (jnp.repeat(f(ang), 2, -1) for f in (jnp.cos, jnp.sin))
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    rot = jnp.stack([-pairs[..., 1], pairs[..., 0]], -1).reshape(x.shape)
    return x * cos + rot * sin


def _mm(a: jax.Array, w: jax.Array, dtype) -> jax.Array:
    return jnp.dot(a.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


def _route(w: jax.Array, p: dict[str, jax.Array], tq: TokenQConfig):
    with jax.named_scope("ddq.router"):
        return moe.route(
            w.reshape(-1, w.shape[-1]), p["w_router"],
            tq.moe_num_active_primary_experts,
            softmax=tq.moe_primary_router_apply_softmax,
            bias=p.get("expert_bias"), scale=tq.routed_scaling_factor)


def dense_ffn(w: jax.Array, w_gate: jax.Array | None, w_up: jax.Array,
              w_down: jax.Array, *, act, block: int, dtype) -> jax.Array:
    """``(act(w W_gate) * (w W_up)) W_down`` — with ``w_gate`` ``None``
    the two-matrix ``act(w W_up) W_down`` — over ``w`` [N, h], a block of
    ``block`` tokens at a time, each recomputed in the backward pass: the
    gate and up activations of a leading dense layer (LFM2: 5.75 x the
    hidden size) never exist for the whole batch."""
    n, h = w.shape
    block = min(block, n)
    nb = -(-n // block)
    gated = w_gate is not None
    w_gu = (jnp.concatenate([w_gate, w_up], axis=-1) if gated
            else w_up).astype(dtype)
    w_down = w_down.astype(dtype)
    f = w_up.shape[1]

    @jax.checkpoint
    def one(wb):
        gu = jnp.dot(wb.astype(dtype), w_gu,
                     preferred_element_type=jnp.float32)
        return _mm(act(gu[:, :f]) * gu[:, f:] if gated else act(gu),
                   w_down, dtype)

    blocks = jnp.pad(w, ((0, nb * block - n), (0, 0))).reshape(nb, block, h)
    return jax.lax.map(one, blocks).reshape(nb * block, h)[:n]


def _indexer_inputs(u: jax.Array, p: dict[str, jax.Array],
                    tq: TokenQConfig, rope: bool):
    """The indexer's query heads, head weights and one key head from the
    layer's normed input, float32: ``(qI [B, T, Hi, Di], wI [B, T, Hi],
    kI [B, T, Di])``. No gradient reaches ``u`` from here."""
    b, t, _ = u.shape
    hi, di = tq.indexer_num_heads, tq.indexer_head_dim
    u = jax.lax.stop_gradient(u)

    def proj(w):
        return jnp.dot(u, w, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    q_i = proj(p["w_iq"]).reshape(b, t, hi, di).transpose(0, 2, 1, 3)
    k_i = rmsnorm(proj(p["w_ik"]), p["ik_norm"], tq.rms_norm_eps)[:, None]
    if rope:
        q_i, k_i = rotary(q_i, tq.rope_theta), rotary(k_i, tq.rope_theta)
    return q_i.transpose(0, 2, 1, 3), proj(p["w_iw"]), k_i[:, 0]


def latent_attention(u: jax.Array, p: dict[str, jax.Array],
                     cfg: NetConfig, rope: bool, interpret: bool):
    """Latent attention over the layer's normed input ``u`` [B, T, h] →
    ``attn · W_o`` [B, T, h]: keys and values of every head expanded from
    one latent a token, one rotary key head shared by all heads (the form
    that is not absorbed). Matmuls and the kernel in ``compute_dtype``
    with float32 accumulation; the latent's norm and the rotations
    float32."""
    tq = cfg.tokenq
    dtype = jnp.dtype(cfg.compute_dtype)
    b, t, _ = u.shape
    hq, r = tq.num_attention_heads, tq.kv_lora_rank
    dn, dr, dv = tq.qk_nope_head_dim, tq.qk_rope_head_dim, tq.v_head_dim

    def heads(x, d):
        return x.reshape(b, t, hq, d).transpose(0, 2, 1, 3)
    with jax.named_scope("ddq.mla_down"):
        q = heads(_mm(u, p["w_q"], dtype), dn + dr)
        q_n, q_r = q[..., :dn], q[..., dn:]
        ckr = _mm(u, p["w_kva"], dtype)
        c = rmsnorm(ckr[..., :r], p["kv_norm"], tq.rms_norm_eps)
        k_r = ckr[:, None, :, r:]               # ONE head: [B, 1, T, dr]
        if rope:
            q_r = rotary(q_r, tq.rope_theta, interleave=True)
            k_r = rotary(k_r, tq.rope_theta, interleave=True)
    with jax.named_scope("ddq.mla_up"):
        kv = heads(_mm(c, p["w_kvb"], dtype), dn + dv)
        q = jnp.concatenate([q_n, q_r], -1).astype(dtype)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (b, hq, t, dr))],
            -1).astype(dtype)
        v = kv[..., dn:].astype(dtype)
    with jax.named_scope("ddq.mla_core"):
        a = causal_attention(q, k, v, block=tq.attn_block,
                             compute_block=tq.attn_compute_block,
                             interpret=interpret)
    return _mm(a.transpose(0, 2, 1, 3).reshape(b, t, hq * dv), p["w_o"],
               dtype)


def mamba_mixer(x: jax.Array, p: dict[str, jax.Array], cfg: NetConfig):
    """A Mamba-2 layer whole, ``x`` [B, T, h] float32 → (``x + g · W_out``,
    the mean Δ). ``u = rmsnorm_1(x)``; ``[z | xBC | dt] = u W_in``; ``xBC
    ← silu(conv(xBC) + b)`` (causal, depthwise); x a head, B and C a
    group; ``Δ = softplus(dt + dt_bias)``, ``A = -exp(a_log)``; the scan
    (``ops/ssd.ssd_scan``: the state zero before the window); ``g = y ·
    silu(z)`` under an RMSNorm over each GROUP's channels, times
    ``gate_norm``. The two projections and the scan's four products in
    ``compute_dtype`` with float32 accumulation; convolution, Δ, decays,
    state, skip and norm float32.

    The window runs a SEGMENT of ``ssm_segment`` rows at a time (0: whole),
    the convolution's last rows and the state carried from one to the
    next, each segment rematerialised on its own in the backward pass:
    this is the layer's ONE ``jax.checkpoint`` (the caller adds none), and
    a segment's intermediates — ``[z | xBC | dt]`` alone is 10 304 float32
    a token at the published widths — never stand for the whole window. A
    window no segment divides is padded with rows after its last, which
    nothing before them reads."""
    tq = cfg.tokenq
    dtype = jnp.dtype(cfg.compute_dtype)
    b, t, h = x.shape
    nh, hd, g, n = (tq.mamba_num_heads, tq.mamba_head_dim, tq.n_groups,
                    tq.ssm_state_size)
    di, gn = nh * hd, g * n
    seg = min(tq.ssm_segment or t, t)
    ns = -(-t // seg)

    @jax.checkpoint
    def segment(carry, rows, real, p):
        tail, state = carry
        u = rmsnorm(rows, p["norm_1"], tq.rms_norm_eps)
        zxbcdt = _mm(u, p["w_in"], dtype)
        z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * gn],
                      zxbcdt[..., 2 * di + 2 * gn:])
        with jax.named_scope("ddq.ssm_conv"):
            xbc, tail = ssd.causal_conv(xbc, p["ssm_conv_w"],
                                        p["ssm_conv_b"], tail)
        with jax.named_scope("ddq.ssm_scan"):
            delta = jax.nn.softplus(dt + p["dt_bias"])
            y, state = ssd.ssd_scan(
                xbc[..., :di].reshape(b, seg, nh, hd), delta,
                -jnp.exp(p["a_log"]),
                xbc[..., di:di + gn].reshape(b, seg, g, n),
                xbc[..., di + gn:].reshape(b, seg, g, n), p["d_skip"],
                state, chunk=tq.chunk_size, dtype=dtype)
        gated = (y.reshape(b, seg, di) * jax.nn.silu(z)).reshape(
            b, seg, g, di // g)
        var = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
        normed = (gated * jax.lax.rsqrt(var + tq.rms_norm_eps)).reshape(
            b, seg, di) * p["gate_norm"]
        return (tail, state), (rows + _mm(normed, p["w_out"], dtype),
                               jnp.sum(delta * real[:, None]))

    xs = jnp.pad(x, ((0, 0), (0, ns * seg - t), (0, 0))).reshape(
        b, ns, seg, h).transpose(1, 0, 2, 3)
    real = (jnp.arange(ns * seg) < t).astype(jnp.float32).reshape(ns, seg)
    init = (jnp.zeros((b, tq.conv_kernel - 1, di + 2 * gn), jnp.float32),
            jnp.zeros((b, g, nh // g, hd, n), jnp.float32))
    _, (ys, dts) = jax.lax.scan(
        lambda carry, xr: segment(carry, *xr, p), init, (xs, real))
    return (ys.transpose(1, 0, 2, 3).reshape(b, ns * seg, h)[:, :t],
            jnp.sum(dts) / (b * t * nh))


def mixer(x: jax.Array, p: dict[str, jax.Array], cfg: NetConfig,
          windowed: bool, rope: bool, interpret: bool, *,
          conv: bool = False, dense: bool = False, sparse: bool = False,
          latent: bool = False, mamba: bool = False,
          index_loss: bool = True, heads: int = 0,
          rope_params: RopeParameters | None = None, bd_steps: int = 0):
    """A block's first half, ``x' = x + m``; ``x`` [B, T, h] float32 →
    (x', the routing where the router reads the mixer's input — else
    ``None`` —, the mixer's counters: a sparse mixer's, under ``gating``
    the gate's mean over tokens and heads, a state-space mixer's
    ``ssm_dt_mean`` — else ``None``). The mixer is
    attention (``windowed``, ``rope``; ``heads`` query heads, 0:
    ``num_attention_heads``; ``rope_params``: the rotary parameters of
    the layer's kind), with ``conv`` the gated short convolution, with
    ``sparse`` attention over the keys its indexer selects
    (``index_loss``: with the indexer's loss), with ``latent`` latent
    attention, with ``mamba`` the Mamba-2 state-space mixer
    (``mamba_mixer``). ``bd_steps`` > 0: ``x`` holds the packed rows of windows of
    that many steps in blocks of ``block_length`` (``ops/attention.
    bd_rows``: one copy or two) — the rotary embedding turns each row at
    its position there and attention runs under the block mask."""
    tq = cfg.tokenq
    router_first = tq.router_input == "pre_mixer"
    dtype = jnp.dtype(cfg.compute_dtype)
    b, t, _ = x.shape
    hq, hkv, d = (heads or tq.num_attention_heads, tq.num_key_value_heads,
                  tq.head_dim)
    if mamba:   # its norm, its residual and its checkpoint are inside
        with jax.named_scope("ddq.ssm"):
            x, dt_mean = mamba_mixer(x, p, cfg)
        return x, None, {"ssm_dt_mean": dt_mean}
    u = rmsnorm(x, p["norm_1"], tq.rms_norm_eps)
    route = _route(u, p, tq) if router_first and not dense else None
    counters = None
    if conv:
        with jax.named_scope("ddq.short_conv"):
            bcz = _mm(u, p["w_in"], dtype)
            with jax.named_scope("ddq.short_conv_mix"):
                mixed = short_conv_mix(bcz, p["w_conv"])
            x = x + _mm(mixed, p["w_out"], dtype)
    elif latent:
        with jax.named_scope("ddq.attn_latent"):
            x = x + latent_attention(u, p, cfg, rope, interpret)
    else:
        positions = bd_rows(bd_steps, tq.block_length,
                            1 + (t > bd_steps + 1))[1] if bd_steps else None
        with jax.named_scope(
                "ddq.attn_bd" if bd_steps else
                "ddq.attn_sparse" if sparse else
                "ddq.attn_window" if windowed else "ddq.attn_full"):
            # the sparse core takes whole query blocks: the window is
            # padded here, once (padded keys lie in every real query's
            # future), so every projection comes out padded
            tm = t + (-t % tq.indexer_q_chunk if sparse else 0)
            um = jnp.pad(u, ((0, 0), (0, tm - t), (0, 0))) if tm > t else u

            def heads(w, n):
                return _mm(um, w, dtype).reshape(b, tm, n, d).transpose(
                    0, 2, 1, 3)
            q, k, v = heads(p["w_q"], hq), heads(p["w_k"], hkv), heads(
                p["w_v"], hkv)
            if tq.qk_norm:
                q = rmsnorm(q, p["q_norm"], tq.rms_norm_eps)
                k = rmsnorm(k, p["k_norm"], tq.rms_norm_eps)
            if rope and rope_params is not None:
                with jax.named_scope("ddq.rotary"):
                    q, k = rotary_cast(q, k, *rotary_table(rope_params, d),
                                       None, dtype, interpret)
            elif rope:
                q, k = rotary_cast(q, k, rope_inv(tq.rope_theta, d), 1.0,
                                   positions, dtype, interpret)
            if bd_steps:
                # the kernel calls alone stand under ddq.attn_bd_core
                a = block_diffusion_attention(
                    q.astype(dtype), k.astype(dtype), v.astype(dtype),
                    t=bd_steps, block_length=tq.block_length,
                    block=tq.attn_block,
                    compute_block=tq.attn_compute_block,
                    fused_bwd=tq.attn_fused_bwd, interpret=interpret)
                a = a.transpose(0, 2, 1, 3).reshape(b, t, hq * d)
            elif sparse:
                with jax.named_scope("ddq.indexer"):
                    indexer = _indexer_inputs(um, p, tq, rope)
                a, counters = sparse_attention.sparse_attention(
                    q.astype(dtype), k.astype(dtype), v.astype(dtype),
                    *indexer, topk=tq.indexer_topk,
                    block=tq.indexer_q_chunk, t_real=t,
                    with_loss=index_loss, interpret=interpret)
                a = a[:, :t]
            else:
                block, compute = (
                    (tq.sliding_attn_block, 0)
                    if windowed and tq.sliding_attn_block
                    else (tq.attn_block, tq.attn_compute_block))
                a = causal_attention(
                    q.astype(dtype), k.astype(dtype), v.astype(dtype),
                    window=tq.sliding_window_size if windowed else 0,
                    block=block, compute_block=compute,
                    fused_bwd=tq.attn_fused_bwd, interpret=interpret)
                a = a.transpose(0, 2, 1, 3)
                if tq.gating:
                    with jax.named_scope("ddq.attn_gate"):
                        gate = jax.nn.sigmoid(jnp.dot(
                            u, p["w_g"], precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32))
                        a = a * gate[..., None]
                        counters = {"attn_gate_mean": jnp.mean(gate)}
                a = a.reshape(b, t, hq * d)
            x = x + _mm(a, p["w_o"], dtype)
    return x, route, counters


def feed_forward(x: jax.Array, p: dict[str, jax.Array], cfg: NetConfig,
                 interpret: bool, *, dense: bool = False, route=None):
    """A block's second half, ``y = x' + f``: the held experts (``route``:
    the routing, where the mixer's half made it) and the shared expert
    where the configuration has one, or with ``dense`` the dense
    feed-forward → (y, the expert layer's counters; ``None`` from a dense
    one)."""
    tq = cfg.tokenq
    act = ACTS[tq.hidden_act]
    dtype = jnp.dtype(cfg.compute_dtype)
    b, t, h = x.shape
    if dense:
        with jax.named_scope("ddq.dense_ffn"):
            v2 = rmsnorm(x, p["norm_2"], tq.rms_norm_eps)
            y = dense_ffn(v2.reshape(b * t, h), p.get("w_gate"), p["w_up"],
                          p["w_down"], act=act,
                          block=tq.head_block, dtype=dtype)
        return x + y.reshape(b, t, h), None
    with jax.named_scope("ddq.experts"):
        v2 = rmsnorm(x, p["norm_2"], tq.rms_norm_eps)
        # the router in its own scope, nested: the innermost
        idx, prob = route if route is not None else _route(v2, p, tq)
        k = tq.moe_num_active_primary_experts
        # the whole batch's token-slots, sorted once: a held expert's rows
        # lie together across the sequences. The bound is the worst case
        # (every token with min(k, held) slots here), so nothing can
        # overflow; the layer walks it in blocks and runs those that hold
        # a slot, so no buffer of that size ever stands
        y, counters = moe.held_experts_ffn(
            v2, idx, prob, p.get("w_gate"), p["w_up"], p["w_down"],
            offset=tq.expert_offset,
            rows=moe.buffer_rows(b * t, k, tq.experts_held, tq.moe_tile),
            tile=tq.moe_tile, compute_dtype=dtype, interpret=interpret,
            act=act)
        if tq.n_shared_experts:
            # what every token takes, ungated and whole on every member
            # of the group: blockwise, so its activations (2 x 1 408 wide
            # for Moonlight) never stand for the whole batch
            with jax.named_scope("ddq.shared_expert"):
                y = y + dense_ffn(
                    v2.reshape(b * t, h), p.get("shared_gate"),
                    p["shared_up"], p["shared_down"], act=act,
                    block=tq.head_block,
                    dtype=dtype).reshape(b, t, h)
    return x + y, counters


def layer(x: jax.Array, p: dict[str, jax.Array], cfg: NetConfig,
          windowed: bool, rope: bool, interpret: bool, *,
          conv: bool = False, dense: bool = False, sparse: bool = False,
          latent: bool = False, mamba: bool = False,
          has_mixer: bool = True, has_ffn: bool = True,
          index_loss: bool = True, heads: int = 0,
          rope_params: RopeParameters | None = None, bd_steps: int = 0):
    """One block, ``mixer`` then ``feed_forward`` — or the ONE of the two
    the layer has (``has_mixer``, ``has_ffn``); ``x`` [B, T, h] float32
    → (x, the layer's counters: the expert layer's, under ``"dsa"`` a
    sparse mixer's, a gated mixer's ``attn_gate_mean`` and a state-space
    mixer's ``ssm_dt_mean``; ``None`` where it has none)."""
    route = mixed = counters = None
    if has_mixer:
        x, route, mixed = mixer(
            x, p, cfg, windowed, rope, interpret, conv=conv, dense=dense,
            sparse=sparse, latent=latent, mamba=mamba,
            index_loss=index_loss, heads=heads, rope_params=rope_params,
            bd_steps=bd_steps)
    if has_ffn:
        x, counters = feed_forward(x, p, cfg, interpret, dense=dense,
                                   route=route)
    if sparse:
        counters = {**(counters or {}), "dsa": mixed}
    elif mixed is not None:
        counters = {**(counters or {}), **mixed}
    return x, counters


# a sparse layer's selection outlives the layer's rematerialisation
KEEP_SELECTION = jax.checkpoint_policies.save_only_these_names(
    sparse_attention.SELECTION_NAME, sparse_attention.LOSS_NAME)


def bd_pack(tokens: jax.Array, reveal: jax.Array,
            cfg: NetConfig) -> jax.Array:
    """The packed rows of block-diffusion windows: ``tokens`` [B, T+1]
    int32 and ``reveal`` [B, G] int32 (tokens of each block already
    revealed, 0..block_length-1, left to right) → [B, T + 1 + G·B] token
    ids, the clean copy then the noised one (``ops/attention.bd_rows``):
    noised position p holds ``tokens[p]`` where its offset in its block is
    under the block's ``reveal``, else the mask token (and past ``T``,
    where a last block is not filled, the mask token)."""
    t, bl = tokens.shape[1] - 1, cfg.tokenq.block_length
    _, pos, blk = bd_rows(t, bl)
    pos, blk = pos[t + 1:], blk[t + 1:]                 # the noised rows
    shown = ((pos - 1) % bl < reveal[:, blk]) & (pos <= t)
    noised = jnp.where(shown, tokens[:, np.minimum(pos, t)],
                       jnp.asarray(mask_token(cfg), tokens.dtype))
    return jnp.concatenate([tokens, noised], axis=1)


def bd_decision_rows(reveal: jax.Array, t: int, block_length: int):
    """Block b's decision → (its packed row [B, G]: the first masked row
    of the noised block, ``T + 1 + bB + reveal``; its step ``p_b = bB +
    reveal`` [B, G]: the state is the prefix ``tok[0..p_b]``, the action
    ``tok[p_b + 1]``)."""
    step = jnp.arange(reveal.shape[1]) * block_length + reveal
    return t + 1 + step, step


def backbone(params: dict[str, Any], tokens: jax.Array, cfg: NetConfig,
             interpret: bool = False, *, index_loss: bool = True,
             reveal: jax.Array | None = None):
    """``tokens`` [B, T] int32 → (final-normed hidden [B, T, h] float32,
    counters: the expert layers' stacked over the EXPERT layers and,
    where there are sparse layers, theirs stacked over those as
    ``dsa_selected`` / ``dsa_causal`` (pairs), ``dsa_index_loss`` (0
    without ``index_loss``: θ⁻ and the acting path) and ``dsa_bits`` (the
    selected pairs as bits: a caller that does not read them drops them,
    and the compiler with it); under ``gating`` ``attn_gate_mean``, the
    gate's mean over tokens and heads stacked over the layers; with
    state-space layers ``ssm_dt_mean``, their mean Δ stacked over them.
    Only the attention kernels pad the window
    (to their blocks); every other product runs on T.

    With ``block_length`` > 0 the window runs under the block mask. With
    ``reveal`` [B, G] (the training path) it is packed first (``bd_pack``:
    clean copy, then the noised copy with ``reveal[b]`` tokens of block b
    shown) and the hidden states are those of the T + 1 + G·B packed
    rows; without, ``tokens`` is ONE copy that holds the mask token where
    the caller put it (the acting path: block-causal)."""
    tq = cfg.tokenq
    bd_steps = tokens.shape[1] - 1 if tq.block_length else 0
    if bd_steps and reveal is not None:
        with jax.named_scope("ddq.bd_pack"):
            x = params["embed"][bd_pack(tokens, reveal, cfg)]
    else:
        x = params["embed"][tokens]
    counters, dsa, gates, dts = [], [], [], []
    for i, kind in enumerate(layer_plan(tq)):
        p = params[layer_name(i)]
        kw = dict(conv=kind["conv"], dense=kind["dense"],
                  sparse=kind["sparse"], latent=kind["latent"],
                  mamba=kind["mamba"], index_loss=index_loss,
                  heads=kind["heads"],
                  rope_params=kind["rope_params"], bd_steps=bd_steps)
        if kind["sparse"] or kind["latent"]:
            # the two halves rematerialised apart: what the mixer's
            # backward needs (q, k, v, the indexer's inputs, o: 2.5 GB at
            # 2 x 16 896 tokens; a latent mixer's queries, expanded keys
            # and values: 1.3 GB at 2 x 8 192) need not stand beside the
            # expert layer's buffers, at the price of one more [B, T, h]
            # a layer
            x, route, dsa_i = jax.checkpoint(
                lambda x, p, kind=kind, kw=kw: mixer(
                    x, p, cfg, kind["windowed"], kind["rope"], interpret,
                    **kw), policy=KEEP_SELECTION)(x, p)
            x, c = jax.checkpoint(
                lambda x, p, route, kind=kind: feed_forward(
                    x, p, cfg, interpret, dense=kind["dense"],
                    route=route))(x, p, route)
            if kind["sparse"]:
                dsa.append(dsa_i)
        elif kind["mamba"]:
            # rematerialised inside, a segment of the window at a time
            x, _, c = mixer(x, p, cfg, False, False, interpret, **kw)
        else:
            x, c = jax.checkpoint(
                lambda x, p, kind=kind, kw=kw: layer(
                    x, p, cfg, kind["windowed"], kind["rope"], interpret,
                    has_mixer=kind["mixer"], has_ffn=kind["ffn"],
                    **kw))(x, p)
        if c is not None and "attn_gate_mean" in c:
            # a dense layer has a gate too: stacked over the GATED layers
            gates.append(c.pop("attn_gate_mean"))
        if c is not None and "ssm_dt_mean" in c:
            dts.append(c.pop("ssm_dt_mean"))
        if c:
            counters.append(c)
    x = rmsnorm(x, params["final_norm"], tq.rms_norm_eps)
    out = jax.tree.map(lambda *a: jnp.stack(a), *counters)
    if gates:
        out["attn_gate_mean"] = jnp.stack(gates)
    if dts:
        out["ssm_dt_mean"] = jnp.stack(dts)
    if dsa:
        out.update({f"dsa_{k}": jnp.stack([d[k] for d in dsa])
                    for k in dsa[0]})
    return x, out


def q_at(params: dict[str, Any], tokens: jax.Array, pos: jax.Array,
         cfg: NetConfig, interpret: bool = False) -> jax.Array:
    """Q(prefix ``tokens[:, :pos + 1]``, ·) of each row: ``[B, V]`` — the
    acting path (no cache: the whole window is run). Under the causal mask
    it is read at position ``pos``, which nothing after it can reach.
    With ``block_length`` > 0 attention is bidirectional inside a block,
    so what follows the prefix is part of the state: every position after
    ``pos`` is set to the mask token here (the prefix's block as the
    sampler holds it after revealing it left to right; what lies past the
    block's end is invisible under the block-causal mask), and Q is read
    at the FIRST masked position, ``pos + 1`` — the row that predicts the
    token that belongs there. The mask token's own column is no action:
    callers skip it."""
    tq = cfg.tokenq
    if tq.block_length:
        tokens = jnp.where(jnp.arange(tokens.shape[1]) > pos,
                           jnp.asarray(mask_token(cfg), tokens.dtype),
                           tokens)
        pos = pos + 1
    hid, _ = backbone(params, tokens, cfg, interpret, index_loss=False)
    return _mm(hid[:, pos], params["head"], jnp.dtype(cfg.compute_dtype))
