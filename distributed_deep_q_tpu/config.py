"""Single dataclass-tree config system.

The reference splits configuration between argparse flags (``--backend``,
worker counts, hyperparameters) and Caffe ``.prototxt`` net/solver files
(SURVEY.md §5.6 [M][R]). Here everything lives in one typed tree; network
topology is code (Flax modules selected by ``NetConfig.kind``), not config
files. The top-level ``--backend={tpu,cpu}`` switch is preserved verbatim —
the north star measures the rebuild "behind the existing Solver/--backend
switch" (BASELINE.json [M]).
"""

from __future__ import annotations

import argparse
import dataclasses
from dataclasses import dataclass, field
from typing import Any


@dataclass
class RopeParameters:
    """The rotary embedding of ONE kind of attention layer, under the
    published keys of ``rope_parameters`` (laguna). ``rope_type`` "" =
    none stated: the layer turns all of ``head_dim`` at
    ``TokenQConfig.rope_theta``, as every layer did before a kind had
    parameters of its own. "default": the first ``partial_rotary_factor ·
    head_dim`` columns of each head turn at ``rope_theta``, the others
    pass through. "yarn": the same columns at frequencies blended between
    ``rope_theta``'s and those divided by ``factor`` (the ramp between the
    pairs that turn ``beta_fast`` and ``beta_slow`` times inside
    ``original_max_position_embeddings``), cos and sin multiplied by
    ``attention_factor`` (``models/tokenq.rotary_table``)."""

    rope_type: str = ""     # "" | default | yarn
    rope_theta: float = 10_000.0
    partial_rotary_factor: float = 1.0
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclass
class RopeKinds:
    """``rope_parameters``: one ``RopeParameters`` a kind of attention
    layer, named as ``layer_types`` names them in the source (a layer with
    a sliding window is ``sliding_attention``, one without
    ``full_attention``)."""

    full_attention: RopeParameters = field(default_factory=RopeParameters)
    sliding_attention: RopeParameters = field(
        default_factory=RopeParameters)


@dataclass
class TokenQConfig:
    """Token-window Q-network backbone (``net.kind = "tokenq"``,
    ``models/tokenq.py``): a decoder-only backbone whose head row ``a``
    is Q(token prefix, next token ``a``). The keys are the published
    ``config.json`` keys of the architectures it runs (SmallThinker's
    names where two architectures name one thing differently), and three
    mechanisms that a source's modelling code fixes and no key of its
    publishes (``qk_norm``, ``hidden_act``, ``router_input``: a
    configuration states them under ``assumed``); the defaults are a toy
    in SmallThinker's settings. Each layer is a token
    mixer (attention, full or windowed, with ``num_attention_heads`` query
    heads or with ``num_attention_heads_per_layer[l]`` of them, a rotary
    embedding whose parameters may belong to the KIND of layer, and with
    ``gating`` a sigmoid gate a head on its output; a gated short
    convolution; attention over the keys a learned indexer selects;
    latent attention, keys and values expanded from one low-rank latent;
    or a Mamba-2 state-space mixer, a scan with a carried state)
    and a
    feed-forward (dense, or the experts held here, with
    ``n_shared_experts`` beside a shared expert every token takes; three
    matrices with a gate, or two without) — or, under
    ``hybrid_override_pattern``, ONE of the two alone —, read
    off these keys by
    ``models/tokenq.layer_plan``. ``experts_held`` / ``expert_offset``
    and ``net.num_actions`` (the vocabulary rows held) say which SHARE of
    an expert-parallel deployment this process computes: the router stays
    ``moe_num_primary_experts`` wide and what the absent experts would
    add is left out."""

    hidden_size: int = 64
    num_hidden_layers: int = 4
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    rms_norm_eps: float = 1e-6
    # per layer: 1 = sliding window of ``sliding_window_size`` tokens
    # (query t sees keys s, t - window < s <= t), 0 = full causal; and
    # 1 = rotary embedding on q/k, 0 = no positional encoding. Layer l
    # takes entry l (a longer published layout is cut to the depth)
    sliding_window_layout: tuple[int, ...] = (0, 1, 1, 1)
    rope_layout: tuple[int, ...] = (0, 1, 1, 1)
    sliding_window_size: int = 8
    rope_theta: float = 10_000.0
    # a head count a LAYER (laguna): layer l's attention has entry l query
    # heads of ``head_dim`` over the same ``num_key_value_heads`` (a longer
    # published list is cut to the depth). Empty: ``num_attention_heads``
    # on every layer
    num_attention_heads_per_layer: tuple[int, ...] = ()
    # the rotary embedding's parameters by KIND of attention layer
    # (``RopeParameters``; a kind whose ``rope_type`` is "" turns all of
    # ``head_dim`` at ``rope_theta`` above). ``rope_layout`` still says
    # which layers turn at all
    rope_parameters: RopeKinds = field(default_factory=RopeKinds)
    # a sigmoid gate a HEAD a token on the attention output before W_o,
    # read from the layer's normed input: ``o[h, t] *= sigmoid(u W_g)[t,
    # h]``, ``W_g`` [hidden, heads of that layer], float32 (laguna's
    # ``gating``)
    gating: bool = False
    # token mixer per layer (LFM2's ``layer_types``): "conv" = the gated
    # short convolution (``ops/short_conv.py``; 3 taps, LFM2's
    # ``conv_L_cache``: ``models/tokenq.CONV_TAPS``), "full_attention" =
    # attention as the two layouts above say, "sparse_attention" = the
    # same heads over the ``indexer_topk`` keys a query's INDEXER scores
    # highest (``ops/sparse_attention.py``; Keye-VL-2.0's ``sa_config``),
    # "latent_attention" = the latent heads below. Empty (SmallThinker
    # publishes no such key): attention on every layer
    layer_types: tuple[str, ...] = ()
    # a "latent_attention" layer (``deepseek_v3``'s keys; a full-rank
    # query, no ``q_lora_rank``): every query head is ``qk_nope_head_dim``
    # + ``qk_rope_head_dim`` wide; keys and values come from ONE latent of
    # ``kv_lora_rank`` a token (RMSNorm with a learned gain, then expanded
    # to ``qk_nope_head_dim`` + ``v_head_dim`` a head) and ONE rotary key
    # head of ``qk_rope_head_dim`` that all query heads share. Scores run
    # over the two parts together (scale: their width to the -1/2), values
    # are ``v_head_dim`` wide; ``num_key_value_heads`` and ``head_dim``
    # are not read. Its rotary embedding turns the interleaved pairs
    # (2i, 2i+1), not rotate-half's (i, i + d/2): a fact of the mixer
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    # the indexer of a "sparse_attention" layer (``sa_config``'s
    # ``indexer_num_heads`` / ``indexer_head_dim`` / ``topk`` /
    # ``q_chunk_size``; ONE key head, ``indexer_num_kv_heads`` = 1):
    # query heads of ``indexer_head_dim`` against one key head score every
    # earlier key, the ``indexer_topk`` highest are attended (every key
    # while a query has no more). It learns from its own loss, which the
    # step adds to the TD loss; ``indexer_q_chunk`` (a multiple of 32; on
    # the chip of 256) is the query and key block of the selection, of
    # that loss and of the attention kernels, which pad the window to it
    indexer_num_heads: int = 4
    indexer_head_dim: int = 8
    indexer_topk: int = 8
    indexer_q_chunk: int = 16
    # per-head RMSNorm of q and k before the rotary embedding (LFM2)
    qk_norm: bool = False
    # the first ``num_dense_layers`` layers carry a dense gated
    # feed-forward of width ``intermediate_size`` and no router (LFM2);
    # 0: every layer is an expert layer (SmallThinker)
    num_dense_layers: int = 0
    intermediate_size: int = 0
    # the gate of every feed-forward, dense or expert: "relu" (ReGLU,
    # SmallThinker) | "silu" (SwiGLU, LFM2) | "relu2" (relu(x)², the
    # ``nemotron_h`` family's ``mlp_hidden_act``)
    hidden_act: str = "relu"
    # false: every feed-forward (dense, expert, shared) is TWO matrices,
    # ``act(x W_up) W_down`` — no gate matrix and no leaf for one
    # (``nemotron_h``'s MLP)
    ffn_gated: bool = True
    # experts of width moe_ffn_hidden_size. Router over ALL
    # moe_num_primary_experts, top moe_num_active_primary_experts kept and
    # renormalised to sum 1: a softmax
    # (``moe_primary_router_apply_softmax``, SmallThinker's) or a sigmoid
    # whose SELECTION adds a per-expert bias the weights leave out
    # (``use_expert_bias``, LFM2's). ``router_input``: what the router
    # reads — "pre_mixer", the layer's normed INPUT, before attention
    # (SmallThinker) | "ffn_norm", the second norm's output, what the
    # experts read (LFM2)
    moe_primary_router_apply_softmax: bool = True
    use_expert_bias: bool = False
    router_input: str = "pre_mixer"
    moe_ffn_hidden_size: int = 32
    moe_num_primary_experts: int = 8
    moe_num_active_primary_experts: int = 2
    experts_held: int = 8
    expert_offset: int = 0
    # the renormalised gates of the chosen experts are multiplied by
    # ``routed_scaling_factor`` (1.0: they sum to 1); with
    # ``n_shared_experts`` > 0 every expert layer adds ONE ungated
    # feed-forward of width ``n_shared_experts * moe_ffn_hidden_size``
    # that every token takes, whole on every member of the group
    routed_scaling_factor: float = 1.0
    n_shared_experts: int = 0
    # the shared expert's width where the source states it on its own
    # (0: ``n_shared_experts * moe_ffn_hidden_size``)
    moe_shared_expert_intermediate_size: int = 0
    # the ``nemotron_h`` family's layer pattern, one letter a layer, each
    # layer ONE part alone under one norm: "M" a Mamba-2 state-space mixer
    # (the keys below), "*" attention (as the two layouts say), "E" the
    # expert layer (the source's "-", a dense feed-forward alone, is in no
    # published pattern here and is refused). Empty: every layer is a
    # mixer AND a feed-forward (``layer_types``, ``num_dense_layers``). A
    # longer published pattern is cut to the depth
    hybrid_override_pattern: str = ""
    # a Mamba-2 mixer (``ops/ssd.py``): ``mamba_num_heads`` heads of
    # ``mamba_head_dim`` channels, each carrying a state of
    # ``ssm_state_size`` a channel; B and C shared by the heads of one of
    # ``n_groups`` groups; a causal depthwise convolution of
    # ``conv_kernel`` taps with a bias over x, B and C; the scan in chunks
    # of ``chunk_size`` positions; the gated RMSNorm over each group's
    # channels
    mamba_num_heads: int = 4
    mamba_head_dim: int = 16
    ssm_state_size: int = 16
    n_groups: int = 2
    conv_kernel: int = 4
    chunk_size: int = 8
    # generation by diffusion over blocks (0: off, one token a position
    # under the causal mask): the window is cut into blocks of
    # ``block_length`` positions after position 0 (a prompt block of its
    # own); the train step runs it TWICE in one pass — a clean copy and a
    # copy in which each block keeps its first ``reveal`` tokens and holds
    # the mask token at the others, the two sharing position ids — under
    # the three-part block mask (``ops/attention.block_diffusion_attention``)
    # and reads ONE decision a block, at its first masked row
    # (``parallel/sequence_learner.py``). The mask token is the LAST row
    # held (``models/tokenq.mask_token``: ``num_actions - 1``, one more
    # than the env's tokens — ``train.token_rows``): a row of the embedding
    # and of the head that is no action; the token ring never holds it and
    # every argmax skips its column
    block_length: int = 0
    # kernel blocks: attention q/kv block (the window is padded to a
    # multiple) and the kv columns of one inner step (a divisor of it),
    # tokens per block of the Q head + TD loss (and of the dense
    # feed-forward), gmm m-tile (the expert layer walks the batch's sorted
    # held slots in blocks of 16 of them and runs those that hold a slot:
    # ``ops/moe.block_rows``)
    attn_block: int = 128
    attn_compute_block: int = 128
    # the block of the layers with a sliding window, compute block and
    # all (0: the two above): a window far under the block leaves most of
    # a block's pairs outside the band
    sliding_attn_block: int = 0
    # the attention backward as ONE kernel, which keeps a partial dq for
    # every kv block, or as two (``ops/attention.py``)
    attn_fused_bwd: bool = True
    head_block: int = 128
    moe_tile: int = 128
    # rows of a state-space layer run at a time, the state carried from
    # one segment to the next (0: the whole window; a multiple of
    # ``chunk_size``): what bounds the layer's intermediates — a tile field
    # like the three above (at the Nemotron cell's sizes 2 048 rows read a
    # 10 % shorter step than 4 096 on the chip: PERF.md section 6, PR 48)
    ssm_segment: int = 0


@dataclass
class NetConfig:
    """Q-network topology. Replaces the reference's ``models/*.prototxt``."""

    kind: str = "mlp"  # mlp | nature_cnn | r2d2 | tokenq
    num_actions: int = 2
    # mlp
    hidden: tuple[int, ...] = (64, 64)
    # nature_cnn / r2d2 torso input: (H, W, stack)
    frame_shape: tuple[int, int] = (84, 84)
    stack: int = 4
    dueling: bool = False
    # r2d2
    lstm_size: int = 512
    torso: str = "nature_cnn"  # r2d2 feature torso: nature_cnn | mlp
    # compute dtype for the torso ("bfloat16" on TPU keeps the MXU fed;
    # params stay float32)
    compute_dtype: str = "float32"
    # tokenq backbone (kind = "tokenq")
    tokenq: TokenQConfig = field(default_factory=TokenQConfig)


@dataclass
class ReplayConfig:
    capacity: int = 100_000
    batch_size: int = 64
    prioritized: bool = False
    priority_alpha: float = 0.6
    priority_beta0: float = 0.4
    priority_beta_steps: int = 1_000_000
    priority_eps: float = 1e-6
    # PER write-back runs this many grad steps behind the learner so the
    # per-sample |TD| D2H fetch (async-copied at dispatch) never blocks the
    # step — see replay.prioritized.DelayedPriorityWriteback
    priority_writeback_delay: int = 8
    # the recurrent SEQUENCE loops' switch (train_recurrent,
    # _train_distributed_recurrent): with device_resident + prioritized,
    # true runs the fused sequence step (sampling and priority update on
    # the device), false the host-sampled device sequence ring. The
    # transition loops do not read it — a pixel device run builds the
    # fused ring and nothing else (replay/device_per.pixel_device_ring);
    # the field goes when the sequence side has one path too (ROADMAP D3a)
    device_per: bool = False
    # grad steps chained per fused-PER dispatch (lax.scan inside the two
    # XLA programs): dispatch + host bookkeeping amortize over the chunk;
    # sampling within a chunk sees chunk-start priorities (staleness ≤
    # fused_chain steps — same bound as priority_writeback_delay on the
    # host path). Applies where grad steps run back-to-back (the
    # decoupled distributed learner, benches); the in-process loop chains
    # at most grad_steps_per_train to keep its env/learn cadence
    fused_chain: int = 8
    n_step: int = 1
    # minimum fill before learning starts
    learn_start: int = 1_000
    # pixel envs: keep the frame ring in device HBM and gather stacks inside
    # the jitted step (replay/device_ring.py) instead of shipping pixel
    # minibatches host→device every step
    device_resident: bool = True
    # frames staged per shard per HBM write (device-resident mode)
    write_chunk: int = 64
    # sequence replay (R2D2)
    sequence_length: int = 80
    burn_in: int = 40
    use_native: bool = True  # use the C++ replay core when available
    # columnar ingest staging (ISSUE 8): staged rows land in per-shard
    # per-column preallocated buffers (one memcpy per column per staged
    # segment — replay/columnar.py) instead of the legacy per-flush FIFO
    # of array tuples. False selects the legacy reference path, kept
    # bit-identical for the staged≡legacy equivalence tests
    staging_columnar: bool = True
    # initial per-shard staging-buffer depth in rows (grows by doubling;
    # occupancy is bounded in practice by staged_high_watermark)
    staging_depth: int = 4096
    # background staging→device drain thread (replay.start_drain): the
    # replay server attaches it so writers never pay the device dispatch;
    # it dispatches a batched flush once write_chunk rows are staged.
    # Ignored on multi-host meshes (flushes are lockstep collectives)
    ingest_drain: bool = True
    # optional replay persistence (SURVEY §5.4): when set, the buffer's
    # complete sampling state (rings, cursors, trees, RNG) is dumped to
    # this .npz alongside learner checkpoints and restored on
    # train.resume. Default empty = warm-refill, matching the reference
    persist_path: str = ""
    # overload plane (rpc/flowcontrol.py): staged-but-unflushed rows the
    # server tolerates before flushes are shed / the watchdog trips
    # degraded mode. Watermark rows are replay rows, not bytes
    staged_high_watermark: int = 8192
    # which flushes the admission controller sheds under overload:
    # "fair" sheds actors over their fair share of the fleet ingest rate
    # first, "all" sheds every flush while over the watermark, "none"
    # disables shedding (credits still throttle)
    shed_policy: str = "fair"
    # learner-process RSS bound for the flowcontrol watchdog (0 = RSS
    # tripwire disabled; staged-depth tripwire is always on)
    rss_high_watermark_mb: int = 0


@dataclass
class TrainConfig:
    lr: float = 1e-4
    optimizer: str = "adam"  # adam | rmsprop (reference PS used RMSProp/AdaGrad [P])
    adam_eps: float = 1.5e-4  # DQN-Atari convention; 1e-8 for classic control
    gamma: float = 0.99
    target_update_period: int = 500  # "every C pulls: θ⁻ ← θ" (SURVEY §3.1 [M])
    # Polyak soft target updates: θ⁻ ← τθ + (1−τ)θ⁻ every step when τ > 0
    # (overrides the hard period copy; the stable choice for small nets)
    target_tau: float = 0.0
    double_dqn: bool = False
    huber_delta: float = 1.0
    # R2D2 sequence path: invertible value rescaling h(x) on targets, and
    # the η mixing of max/mean |TD| for per-sequence priorities
    value_rescale: bool = True
    priority_eta: float = 0.9
    grad_clip_norm: float = 10.0
    total_steps: int = 50_000
    # env steps between learn phases when running single-process, and grad
    # steps per learn phase — the reference worker's "actor phase: k steps /
    # learn phase: j minibatches" cadence (SURVEY §3.1 [M])
    train_every: int = 4
    grad_steps_per_train: int = 1
    eval_every: int = 0  # 0 = no periodic eval
    eval_episodes: int = 5
    # with periodic eval on: keep the best-eval params and restore them at
    # the end of training if the final params score worse (EvalCallback-
    # style model selection; DQN end-of-run policies oscillate)
    keep_best_eval: bool = False
    seed: int = 0
    # use the fused Pallas TD-loss kernel on TPU
    use_pallas_loss: bool = False
    # stack θ and θ⁻ on a leading axis and run ALL the step's Q-forwards
    # (θ(s), θ(s') for Double-DQN, θ⁻(s')) as ONE vmapped application —
    # the conv/dense chain count collapses to a single forward's worth
    # (PERF.md §3: the small-batch step is op-count-bound). "auto" turns
    # it on when the per-shard batch is ≤ 128 — at large batch the step
    # is HBM/flop-bound and the extra θ⁻(s) quarter stops being free.
    stack_forwards: str = "auto"  # auto | on | off
    # store Adam's first moment in bfloat16 (optax mu_dtype): trims
    # optimizer-state HBM traffic on the HBM-bound small-batch step
    adam_mu_dtype: str = "float32"  # float32 | bfloat16
    # learning-dynamics plane (ISSUE 16, learning.py): accumulate loss /
    # TD-histogram / grad-norm / Q / PER-sampling statistics INSIDE the
    # fused-chain and Anakin scan bodies, returned as one flat plane per
    # dispatch. Static trace-time gate: False compiles the exact pre-PR
    # programs (bitwise math, unchanged op budgets); True pays the small
    # documented budget delta (PERF.md §16) and still zero host-comm ops
    learn_metrics: bool = False
    checkpoint_dir: str = ""
    checkpoint_every: int = 0  # grad steps between Orbax snapshots
    resume: bool = False       # restore newest snapshot before training
    # learner-restart survival (distributed topology): when set, the
    # ReplayFeed server binds actors.port (stable across restarts),
    # snapshots replay + counters + the θ frame here (at checkpoint
    # cadence and on exit), and warm-boots from it — a restarted learner
    # resumes with its replay intact while actors simply reconnect
    server_snapshot_path: str = ""
    # generational snapshot retention: server_snapshot_path holds the
    # newest N checksummed generations; restore walks newest→oldest past
    # any torn/corrupt one (quarantined, not fatal)
    snapshot_keep: int = 3
    # profiling (SURVEY §5.1): jax.profiler trace of a step window, and an
    # optional live profiler server port (0 = off)
    profile_dir: str = ""
    profile_start_step: int = 100
    profile_num_steps: int = 20
    profile_port: int = 0


@dataclass
class EnvConfig:
    id: str = "CartPole-v1"
    kind: str = "gym"  # gym | atari | fake_atari | signal_atari | token
    # multi-game fleets (config 4 "Atari-57 8-game subset"): when non-empty,
    # actor i plays games[i % len(games)] (env_for_actor) and eval reports
    # per-game returns. All games must expose the same action count — for
    # ALE use full_action_space=True (the 18-action set) as Ape-X does.
    games: tuple[str, ...] = ()
    full_action_space: bool = False
    frame_skip: int = 4
    frame_shape: tuple[int, int] = (84, 84)
    stack: int = 4
    reward_clip: float = 1.0  # 0 disables; Atari clips to ±1 [P]
    terminal_on_life_loss: bool = True
    max_episode_steps: int = 27_000  # 108k frames / skip 4, standard Atari cap
    # kind = "token": the seeded token env's vocabulary (= its action
    # count: the action IS the next token)
    token_vocab: int = 64
    noop_max: int = 30


@dataclass
class ActorConfig:
    num_actors: int = 1
    # multi-host fleets (config 5 full shape): each learner process runs
    # its own supervisor over a slice of the fleet. Local actor ids stay
    # 0..k-1 (they double as per-host replay stream ids); the offset and
    # global fleet size give every actor its GLOBAL identity for the ε
    # ladder and env seeding, so host slices cover different ladder
    # segments instead of repeating the same one
    actor_id_offset: int = 0
    fleet_size: int = 0  # 0 = num_actors (single-host)
    # actor→host placement for multi-host fleets (actors/assignment.py):
    # "contiguous" slices the gid range per process (the historical
    # layout); "hash" walks a bounded-load consistent-hash ring, so a
    # restarting actor keeps its host, host join/leave remaps only
    # ~fleet/hosts actors, and a host address change is just a reconnect
    assignment: str = "contiguous"
    # Sebulba-style vectorized acting (actors/vector.py): >1 makes each
    # actor PROCESS drive this many stacked env copies behind one
    # batched step — V global actor identities (ε ladder slots, env
    # seeds, replay streams) per process, one infer RPC per wall tick.
    # 0/1 = the historical one-env-per-process loop. Replay stream ids
    # become process_id*V + row, so device replays must be built with
    # num_streams = num_actors * V (train_distributed does this).
    vector_envs: int = 0
    # explicit local→global actor id map, filled in by the supervisor's
    # fleet split under assignment="hash" (local slot i plays global
    # actor actor_gids[i]). Empty = derive gid as actor_id + offset
    actor_gids: tuple[int, ...] = ()
    # Anakin mode (parallel/anakin.py): >0 runs acting INSIDE the jitted
    # learner program — this many jax envs (ops/jax_envs.py, must divide
    # over the dp mesh; 0 = mode off) co-resident with training, one
    # device sub-ring per env, zero steady-state host transfers. An
    # explicit opt-in, not inferred: only the signal_atari family has a
    # JAX-expressible step
    anakin_envs: int = 0
    # env ticks per Anakin superstep (must stay ≤ the ring's slot_cap so
    # one insert never wraps a sub-ring — the same single-flush-chunk
    # invariant the host write path keeps)
    anakin_ticks: int = 16
    # Ape-X ε ladder: actor i uses ε = base ** (1 + i/(N-1) * alpha) [T]
    eps_base: float = 0.4
    eps_alpha: float = 7.0
    # single-actor annealed schedule (Nature-DQN style)
    eps_start: float = 1.0
    eps_end: float = 0.05
    eps_decay_steps: int = 10_000
    eval_eps: float = 0.05
    # pull fresh θ from the learner every this many env steps (SURVEY §5.8);
    # each actor offsets its pull schedule by a stable random phase so a
    # 256-actor fleet doesn't stampede the learner host in lockstep
    param_sync_period: int = 400
    # wall-clock seconds between explicit liveness heartbeats (0 disables).
    # Liveness must not be inferred from data traffic alone: a healthy
    # actor in a slow env can legitimately go > heartbeat_timeout without
    # filling a send_batch (VERDICT r3 weak #5)
    heartbeat_period: float = 5.0
    # the beat stops once the env loop has made no progress for this many
    # wall-clock seconds, so a PERMANENTLY wedged env still trips the
    # supervisor's heartbeat_timeout and gets respawned — this budget is
    # the line between "slow step, keep alive" and "hung, replace"
    env_stall_budget: float = 300.0
    # transitions per RPC AddTransitions message
    send_batch: int = 64
    # RPC fault tolerance (rpc/resilience.py): exponential backoff between
    # retried calls, capped per attempt, giving up after the deadline.
    # Flushes are idempotent (flush_seq dedup on the server), so a retry
    # after an ambiguous failure can never double-insert into replay
    rpc_retry_base: float = 0.05
    rpc_retry_max: float = 2.0
    rpc_retry_deadline: float = 120.0
    # per-call socket timeout on the actor-side stub: a stalled server
    # surfaces as a retryable TimeoutError instead of hanging the actor
    rpc_call_timeout: float = 30.0
    # staleness guard: an actor whose pulled θ version trails the
    # published version by more than this many publishes blocks on a
    # fresh pull before acting (0 disables). The published version rides
    # back on every add_transitions reply, so the check is free
    max_param_lag: int = 10
    # credit-based backpressure floor: the server never grants an actor
    # fewer than this many rows/second while healthy, so a throttled
    # fleet keeps trickling instead of livelocking
    flush_credit_floor: int = 64
    # chaos injection spec for the whole fleet (rpc/faultinject.py), e.g.
    # "drop=0.02,delay=0.05:40,corrupt=0.01,seed=7"; propagated to actor
    # processes via the DDQ_CHAOS env var. Empty = no faults
    chaos: str = ""
    # replay-feed service address
    host: str = "127.0.0.1"
    port: int = 6379


@dataclass
class MeshConfig:
    """Device-mesh / backend selection — the rebuilt ``--backend`` switch.

    ``backend='tpu'`` runs on the TPU devices JAX finds and REFUSES to
    start on any other platform (``parallel.mesh.mesh_devices``);
    ``backend='cpu'`` forces the host platform with ``num_fake_devices``
    virtual devices — the test/dummy backend (SURVEY §4: the reference's
    own fake-backend pattern, rebuilt).
    """

    backend: str = "tpu"  # tpu | cpu
    num_fake_devices: int = 8  # only for backend=cpu; GLOBAL count when multi-host
    dp: int = 0  # 0 = all available devices on the dp axis
    model: int = 1  # model-parallel axis (hooks only; SURVEY §2.2: TP not needed)
    # multi-host learner (SURVEY §5.8 third leg, BASELINE config 5):
    # when num_processes > 1 the mesh spans processes —
    # ``parallel.multihost.initialize_multihost`` must run before any JAX
    # backend init. On TPU pods the three fields are usually auto-detected
    # (leave coordinator empty); on the CPU test backend they are explicit.
    coordinator: str = ""       # e.g. "10.0.0.1:8476"
    num_processes: int = 1
    process_id: int = 0


@dataclass
class TraceConfig:
    """Distributed tracing plane (``distributed_deep_q_tpu/tracing.py``).

    Off by default; when off the tracer costs a single module-flag branch
    per instrumented site. Context piggybacks on existing wire frames as
    ``tr_*`` keys (no wire version bump), so a traced learner and
    untraced actors — or the reverse — interoperate freely.
    """

    enabled: bool = False
    # fraction of per-env-step hot-path cycles that record a span
    # (counter-based, deterministic: every round(1/rate)-th step)
    sample_rate: float = 0.01
    # fraction of flushes carrying per-row lineage birth stamps — the
    # input to the learner's time_to_learn histogram
    lineage_rate: float = 0.05
    # per-thread span ring capacity (drop-oldest beyond this)
    buffer_spans: int = 8192
    # shard export directory; each process writes trace-<pid>.json here
    dir: str = "traces"


@dataclass
class HealthConfig:
    """Health plane (``distributed_deep_q_tpu/health.py``).

    Off by default; when off every monitor entry point is a single
    module-flag branch returning preallocated constants. When on, each
    server samples its own telemetry into fixed-capacity rings and the
    supervisor aggregates every member's ``health`` RPC verdict into
    one fleet ``HealthVerdict`` logged as ``health/verdict``.
    """

    enabled: bool = False
    # fixed capacity of each per-key time-series ring (drop-oldest)
    ring_capacity: int = 512
    # multi-window burn-rate alerting: a rule fires only when BOTH
    # windows have burned their budget; it clears (hysteresis) when the
    # fast window cools below clear_ratio. Per-rule overrides win.
    fast_window_s: float = 30.0
    slow_window_s: float = 300.0
    clear_ratio: float = 0.5
    # supervisor fleet-scrape cadence (log ticks between scrapes; the
    # scrape itself is one in-process call + one RPC per remote member)
    scrape_every: int = 1


@dataclass
class AutoscaleConfig:
    """Health-driven autoscaler (``actors/autoscaler.py``).

    Off by default (and inert unless the health plane is on — its only
    input is the fleet ``HealthVerdict``). When enabled, the supervisor
    folds each scraped verdict through the autoscaler on the health
    tick; decisions land in the run JSONL under ``autoscale/decision``
    with the triggering rule and burn numbers, and the targets are
    exported as ``autoscale/target_*`` gauges. With ``execute`` on, a
    supervisor-side ``ScaleExecutor`` (``actors/executor.py``) closes
    the loop: actor-dimension decisions actually start/stop actor
    processes — rate-limited, dry-run-able, rolled back when a spawned
    actor misses its grace window — and every applied action lands in
    the JSONL under ``autoscale/applied`` with the decision's rule for
    lineage (``telemetry_report --strict`` audits applied vs target).
    """

    enabled: bool = False
    # actor-capacity band; max_actors=0 = the boot fleet size
    min_actors: int = 1
    max_actors: int = 0
    # inference-capacity band (replicas of the batched-inference plane)
    min_inference: int = 0
    max_inference: int = 0
    # capacity change per decision
    step: int = 1
    # per-dimension cooldown between decisions (anti-flap damper)
    cooldown_s: float = 30.0
    # consecutive ok verdicts required before growing back (hysteresis)
    recover_ticks: int = 3
    # executor (ISSUE 20): act on actor-dimension decisions. dry_run
    # logs what WOULD happen without touching processes
    execute: bool = False
    dry_run: bool = False
    # floor between applied actions (on top of the decision cooldown)
    rate_limit_s: float = 5.0
    # graceful retirement: wait this long for the actor's in-flight
    # flush to drain before terminating it
    drain_s: float = 5.0
    # a grown actor must heartbeat within this window or the grow is
    # rolled back (the process reaped, the slot released)
    spawn_grace_s: float = 20.0


@dataclass
class InferenceConfig:
    """Batched inference plane (``rpc/inference_server.py``).

    When enabled, the learner hosts an ``InferenceServer`` next to the
    replay feed and actors ship OBSERVATIONS instead of pulling θ: the
    server queues per-actor requests, cuts microbatches under the
    deadline-aware SLO below, and answers with argmax actions + Q-values
    from ONE device-resident forward. ε-greedy stays client-side
    (seeded, per-actor ε) so exploration is bitwise reproducible either
    way. Param pulls drop to zero in steady state and actor staleness
    is eliminated by construction — the forward always uses the θ the
    learner last pushed.
    """

    enabled: bool = False
    # service address; port 0 = ephemeral (the supervisor rewrites the
    # pickled cfg with the bound port before spawning actors). Snapshot
    # runs that need a stable address set it explicitly
    host: str = "127.0.0.1"
    port: int = 0
    # microbatch SLO: close a batch at max_batch rows OR cutoff_us after
    # its first request, whichever comes first — the deadline bounds the
    # tail latency a lone actor pays for batching
    max_batch: int = 256
    cutoff_us: int = 2000
    # compiled batch buckets: each forward pads to the smallest bucket
    # that fits, so XLA compiles at most len(buckets) programs (≤ 4 per
    # the acceptance bound) instead of one per observed batch size
    buckets: tuple = (8, 32, 128, 256)
    # admission (reuses rpc/flowcontrol.py): queued rows beyond this shed
    # new requests with an explicit retry_after_ms reply
    queue_high_watermark: int = 4096
    # multi-tenant serving (ISSUE 20): extra tenant tags registered at
    # boot ("ab:<name>" arms join the actor-hash split once θ installs;
    # "shadow:<name>" tenants mirror primary traffic, replies never
    # reach actors). The primary always exists and needs no entry
    tenants: tuple = ()
    # degrade ladder: tenant classes shed in strict order (shadow → A/B
    # → primary) when queue occupancy SUSTAINS above these fractions of
    # queue_high_watermark for ladder_burn_s; the primary only ever
    # sheds through its own controller at the full watermark
    shed_shadow_frac: float = 0.5
    shed_ab_frac: float = 0.75
    ladder_burn_s: float = 1.0


@dataclass
class Config:
    net: NetConfig = field(default_factory=NetConfig)
    replay: ReplayConfig = field(default_factory=ReplayConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    env: EnvConfig = field(default_factory=EnvConfig)
    actors: ActorConfig = field(default_factory=ActorConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    trace: TraceConfig = field(default_factory=TraceConfig)
    inference: InferenceConfig = field(default_factory=InferenceConfig)
    health: HealthConfig = field(default_factory=HealthConfig)
    autoscale: AutoscaleConfig = field(default_factory=AutoscaleConfig)

    def replace(self, **kv: Any) -> "Config":
        return dataclasses.replace(self, **kv)


# ---------------------------------------------------------------------------
# Presets mirroring BASELINE.json ``configs`` [M]
# ---------------------------------------------------------------------------


def cartpole_config() -> Config:
    """Config 1: CartPole-v1, MLP Q-net, single worker, uniform replay.

    Recipe selected empirically (scripts/diag_cartpole.py sweeps): Double
    DQN + dueling + 3-step returns + Polyak targets (τ=0.005) converges
    monotonically to 500/500 within 30k steps. Atari-style settings (hard
    target copies, 1-step) plateau at ~120 from max-bias overestimation —
    not a numerics bug (scripts/diag_mdp.py recovers analytic Q* exactly,
    and a faithful torch replica of the published community recipe plateaus
    identically in this environment). ``keep_best_eval`` guards the tail
    against late policy oscillation; eval is greedy.
    """
    c = Config()
    c.net = NetConfig(kind="mlp", num_actions=2, hidden=(128, 128),
                      dueling=True)
    c.replay = ReplayConfig(capacity=100_000, batch_size=128,
                            learn_start=1_000, n_step=3)
    c.train = TrainConfig(
        lr=5e-4, adam_eps=1e-8, gamma=0.99, target_tau=0.005,
        double_dqn=True, total_steps=30_000, train_every=1,
        grad_clip_norm=10.0, eval_every=2_500, keep_best_eval=True,
    )
    c.env = EnvConfig(id="CartPole-v1", kind="gym", stack=1, reward_clip=0.0)
    c.actors = ActorConfig(num_actors=1, eps_decay_steps=8_000, eps_end=0.04,
                           eval_eps=0.0)
    return c


def pong_config() -> Config:
    """Config 2: Atari Pong, Nature-DQN CNN, 4 actors + 1 learner, uniform.

    Uniform sampling runs through the FUSED device sampler with α=0:
    constant priorities make the inverse-CDF draw uniform within each
    shard, and the stratified-IS weights stay within a few percent of 1
    (exactly 1 once shard fills equalize — they correct for unequal
    per-shard sampleable mass, which plain weight=1 uniform ignores).
    Sampling/composition stay on device (no per-step host sum-tree/index
    work).
    """
    c = Config()
    c.net = NetConfig(kind="nature_cnn", num_actions=6, compute_dtype="bfloat16")
    c.replay = ReplayConfig(capacity=1_000_000, batch_size=512,
                            learn_start=20_000, prioritized=True,
                            priority_alpha=0.0, device_per=True)
    c.train = TrainConfig(lr=6.25e-5, target_update_period=2_500, total_steps=2_000_000)
    c.env = EnvConfig(id="PongNoFrameskip-v4", kind="atari")
    c.actors = ActorConfig(num_actors=4)
    return c


def breakout_config() -> Config:
    """Config 3: Atari Breakout, Double-DQN + prioritized replay, 16 actors."""
    c = pong_config()
    c.net = dataclasses.replace(c.net, num_actions=4)
    c.replay = dataclasses.replace(
        c.replay, prioritized=True, n_step=3, batch_size=512,
        # real PER here: pong's α=0 (fused-uniform) must not leak through
        priority_alpha=0.6,
        # fused device-PER is the production prioritized path on TPU
        # (replay/device_per.py); host sum-tree remains the fallback
        device_per=True,
        # β anneals per sample() (= per grad step): reach β=1 by end of
        # training (total_steps env steps / train_every)
        priority_beta_steps=c.train.total_steps // c.train.train_every)
    c.train = dataclasses.replace(c.train, double_dqn=True)
    c.env = dataclasses.replace(c.env, id="BreakoutNoFrameskip-v4")
    c.actors = dataclasses.replace(c.actors, num_actors=16)
    return c


def apex_config() -> Config:
    """Config 4: Ape-X style — 256 CPU actors, prioritized n-step, dueling,
    8-game Atari-57 subset round-robined across the fleet (full 18-action
    space so one Q-head serves every game)."""
    c = breakout_config()
    c.net = dataclasses.replace(c.net, dueling=True, num_actions=18)
    c.actors = dataclasses.replace(c.actors, num_actors=256)
    c.env = dataclasses.replace(
        c.env, full_action_space=True,
        games=("BreakoutNoFrameskip-v4", "PongNoFrameskip-v4",
               "BeamRiderNoFrameskip-v4", "EnduroNoFrameskip-v4",
               "QbertNoFrameskip-v4", "SeaquestNoFrameskip-v4",
               "SpaceInvadersNoFrameskip-v4", "AsterixNoFrameskip-v4"))
    return c


def r2d2_config() -> Config:
    """Config 5 (stretch): R2D2 recurrent Q-net, sequence replay.
    Single-game (drops apex's multi-game round-robin): the config-5 bar is
    the recurrent pipeline at scale, not Atari-57 coverage."""
    c = apex_config()
    c.net = dataclasses.replace(c.net, kind="r2d2", lstm_size=512)
    c.replay = dataclasses.replace(
        c.replay, sequence_length=80, burn_in=40, batch_size=64,
        # selects the HOST-sampled device sequence ring (per-slot sum-trees
        # on the host, pixels gathered in HBM:
        # ``SequenceLearner._build_ring_step``), not the fused sequence
        # step ``train_recurrent`` also has (ROADMAP D3a)
        device_per=False)
    c.env = dataclasses.replace(c.env, games=(), full_action_space=False)
    return c


def tokenq_config() -> Config:
    """Token-window Q-network at a toy size: a 4-layer transformer (full
    + window attention, a held-experts MoE layer) doing token-level
    Double-DQN over windows of ``replay.sequence_length`` + 1 tokens on
    the seeded token env, through ``SequenceSolver`` and the fused
    sequence step. The recipe is the Ape-X learner's."""
    c = Config()
    c.net = NetConfig(kind="tokenq", num_actions=64)
    c.replay = ReplayConfig(
        capacity=256 * 24, batch_size=8, sequence_length=24, burn_in=0,
        prioritized=True, device_per=True, fused_chain=4,
        learn_start=32 * 24, priority_beta_steps=10_000)
    c.train = TrainConfig(lr=6.25e-5, adam_eps=1.5e-4, double_dqn=True,
                          target_update_period=2_500, total_steps=2_000,
                          train_every=24)
    c.env = EnvConfig(id="token", kind="token", stack=1, reward_clip=0.0,
                      max_episode_steps=96)
    c.actors = ActorConfig(num_actors=1, eps_decay_steps=1_000)
    return c


def smallthinker_tokenq_config() -> Config:
    """SmallThinker-21BA3B-Instruct (PowerInfer, config.json) as a
    token-window Q-network, one chip's share of an 8-chip expert-parallel
    deployment: every width as published (hidden 2560, 28/4 heads of 128,
    experts of width 768, router 64 wide, top 6, window 4096, rope theta
    1.5e6); one period of 4 layers (1 full/NoPE + 3 window/RoPE), 8 of
    the 64 experts and 18 992 of the 151 936 vocabulary rows held here.
    Windows of 8 192 steps (+1 token), batch 4, chain 4."""
    c = tokenq_config()
    c.net = NetConfig(
        kind="tokenq", num_actions=18_992, compute_dtype="bfloat16",
        tokenq=TokenQConfig(
            hidden_size=2560, num_hidden_layers=4, num_attention_heads=28,
            num_key_value_heads=4, head_dim=128, rms_norm_eps=1e-6,
            sliding_window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1),
            sliding_window_size=4096, rope_theta=1_500_000.0,
            moe_ffn_hidden_size=768, moe_num_primary_experts=64,
            moe_num_active_primary_experts=6, experts_held=8,
            expert_offset=0, attn_block=1024, attn_compute_block=512,
            head_block=1024, moe_tile=256))
    c.replay = dataclasses.replace(
        c.replay, capacity=16_384 * 8_192, batch_size=4,
        sequence_length=8_192, learn_start=64 * 8_192,
        priority_beta_steps=1_000_000)
    c.train = dataclasses.replace(c.train, total_steps=1_000_000,
                                  train_every=8_192)
    c.env = dataclasses.replace(c.env, max_episode_steps=4_096,
                                token_vocab=18_992)
    return c


def lfm2_tokenq_config() -> Config:
    """LFM2-24B-A2B (LiquidAI, config.json, ``model_type`` lfm2_moe) as a
    token-window Q-network, one chip's share of an 8-chip expert-parallel
    deployment: every width as published (hidden 2048, 32/8 heads of 64
    with q/k norms, rope theta 1e6, convolutions of 3 taps, dense width
    11 776, SwiGLU experts of width 1 536, sigmoid router 64 wide with a
    selection bias, top 4); 5 layers = the published layers 0, 2, 3, 4, 5
    (one leading dense layer, then one period: full attention + three
    gated short convolutions), 8 of the 64 experts and 8 192 of the
    65 536 vocabulary rows held here. Windows of 8 192 steps (+1 token),
    chain 4, batch 2: the chip's compiler puts the batch-4 train program
    at 16.4 GB before the 1.2 GB ring, over one v5e chip's 16 GB."""
    c = smallthinker_tokenq_config()
    c.net = NetConfig(
        kind="tokenq", num_actions=8_192, compute_dtype="bfloat16",
        tokenq=TokenQConfig(
            hidden_size=2048, num_hidden_layers=5, num_attention_heads=32,
            num_key_value_heads=8, head_dim=64, rms_norm_eps=1e-5,
            layer_types=("conv", "full_attention", "conv", "conv", "conv"),
            sliding_window_layout=(0,) * 5, rope_layout=(1,) * 5,
            rope_theta=1_000_000.0, qk_norm=True, num_dense_layers=1,
            intermediate_size=11_776, hidden_act="silu",
            moe_primary_router_apply_softmax=False, use_expert_bias=True,
            router_input="ffn_norm",
            moe_ffn_hidden_size=1536, moe_num_primary_experts=64,
            moe_num_active_primary_experts=4, experts_held=8,
            expert_offset=0, attn_block=1024, attn_compute_block=512,
            head_block=1024, moe_tile=256))
    c.replay = dataclasses.replace(c.replay, batch_size=2)
    c.env = dataclasses.replace(c.env, token_vocab=8_192)
    return c


def keye_tokenq_config() -> Config:
    """Keye-VL-2.0-30B-A3B's language model (Kwai-Keye, config.json,
    ``model_type`` KeyeVL2) as a token-window Q-network, one chip's share
    of a 16-chip expert-parallel deployment: every width as published
    (hidden 2048, 32/4 heads of 128 with q/k norms, rope theta 1e7, the
    ``sa_config`` indexer of 16 heads of 64 with one key head, top 2 048
    keys a query, SwiGLU experts of width 768, softmax router 128 wide,
    top 8); 4 of the 48 layers (the period is one layer), 8 of the 128
    experts and 18 992 of the 151 936 vocabulary rows held here. Windows
    of 16 384 steps (+1 token: a query keeps 2 048 of 8 192 earlier keys
    on average), batch 2, chain 4, a ring of 8 192 windows."""
    c = smallthinker_tokenq_config()
    c.net = NetConfig(
        kind="tokenq", num_actions=18_992, compute_dtype="bfloat16",
        tokenq=TokenQConfig(
            hidden_size=2048, num_hidden_layers=4, num_attention_heads=32,
            num_key_value_heads=4, head_dim=128, rms_norm_eps=1e-6,
            layer_types=("sparse_attention",) * 4,
            sliding_window_layout=(0,) * 4, rope_layout=(1,) * 4,
            rope_theta=10_000_000.0, qk_norm=True,
            indexer_num_heads=16, indexer_head_dim=64, indexer_topk=2048,
            indexer_q_chunk=512, hidden_act="silu",
            router_input="ffn_norm",
            moe_ffn_hidden_size=768, moe_num_primary_experts=128,
            moe_num_active_primary_experts=8, experts_held=8,
            expert_offset=0, head_block=1024, moe_tile=256))
    c.replay = dataclasses.replace(
        c.replay, capacity=8_192 * 16_384, batch_size=2,
        sequence_length=16_384, learn_start=64 * 16_384)
    c.train = dataclasses.replace(c.train, train_every=16_384)
    return c


def moonlight_tokenq_config() -> Config:
    """Moonlight-16B-A3B (moonshotai, config.json, ``model_type``
    deepseek_v3) as a token-window Q-network, one chip's share of an
    8-chip expert-parallel deployment: every width as published (hidden
    2048, 16 heads of latent attention: scores over 128 + 64, values of
    128, from a latent of rank 512 and one shared rotary key head, rope
    theta 5e4 on interleaved pairs; dense width 11 264; SwiGLU experts of
    width 1 408, sigmoid router 64 wide with a selection bias, top 6,
    gates x 2.446, and a shared expert of width 2 x 1 408 beside them);
    5 layers = the published layers 0-4 (one leading dense layer, four
    expert layers), 8 of the 64 experts and 20 480 of the 163 840
    vocabulary rows held here. Windows of 8 191 steps (+1 token = the
    published 8 192 positions, whole attention blocks), chain 4,
    batch 2."""
    c = smallthinker_tokenq_config()
    c.net = NetConfig(
        kind="tokenq", num_actions=20_480, compute_dtype="bfloat16",
        tokenq=TokenQConfig(
            hidden_size=2048, num_hidden_layers=5, num_attention_heads=16,
            num_key_value_heads=16, rms_norm_eps=1e-5,
            layer_types=("latent_attention",) * 5,
            sliding_window_layout=(0,) * 5, rope_layout=(1,) * 5,
            rope_theta=50_000.0, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            num_dense_layers=1, intermediate_size=11_264,
            hidden_act="silu", moe_primary_router_apply_softmax=False,
            use_expert_bias=True, router_input="ffn_norm",
            moe_ffn_hidden_size=1408, moe_num_primary_experts=64,
            moe_num_active_primary_experts=6, experts_held=8,
            expert_offset=0, routed_scaling_factor=2.446,
            n_shared_experts=2, attn_block=1024, attn_compute_block=512,
            head_block=1024, moe_tile=256))
    c.replay = dataclasses.replace(
        c.replay, capacity=16_384 * 8_191, batch_size=2,
        sequence_length=8_191, learn_start=64 * 8_191)
    c.train = dataclasses.replace(c.train, train_every=8_191)
    c.env = dataclasses.replace(c.env, token_vocab=20_480)
    return c


def laguna_tokenq_config() -> Config:
    """Laguna-XS.2 (poolside, config.json, ``model_type`` laguna) as a
    token-window Q-network, one chip's share of a 16-chip expert-parallel
    deployment: every width as published (hidden 2048, heads of 128 over 8
    key/value heads: 48 on the full layers, 64 on the layers with a
    sliding window of 512; the full layers turn half of each head under
    YaRN at base 5e5, the sliding ones all of it at 1e4; a sigmoid gate a
    head on the attention output; dense width 8 192; SwiGLU experts of
    width 512, sigmoid router 256 wide, top 8, gates x 2.5, and a shared
    expert of width 512 beside them); 5 layers = the published layers 0-4
    (the leading dense layer, full; three sliding; one full: a whole
    period), 16 of the 256 experts and 12 544 of the 100 352 vocabulary
    rows held here. Windows of 16 384 steps (+1 token), chain 4, batch 1,
    a ring of 8 192 windows."""
    c = smallthinker_tokenq_config()
    c.net = NetConfig(
        kind="tokenq", num_actions=12_544, compute_dtype="bfloat16",
        tokenq=TokenQConfig(
            hidden_size=2048, num_hidden_layers=5, num_attention_heads=48,
            num_attention_heads_per_layer=(48, 64, 64, 64, 48),
            num_key_value_heads=8, head_dim=128, rms_norm_eps=1e-6,
            sliding_window_layout=(0, 1, 1, 1, 0), rope_layout=(1,) * 5,
            sliding_window_size=512,
            rope_parameters=RopeKinds(
                full_attention=RopeParameters(
                    rope_type="yarn", rope_theta=500_000.0,
                    partial_rotary_factor=0.5, factor=64.0,
                    original_max_position_embeddings=4096,
                    beta_fast=64.0, beta_slow=1.0,
                    attention_factor=1.4158883083359672),
                sliding_attention=RopeParameters(
                    rope_type="default", rope_theta=10_000.0,
                    partial_rotary_factor=1.0)),
            gating=True, num_dense_layers=1, intermediate_size=8192,
            hidden_act="silu", moe_primary_router_apply_softmax=False,
            router_input="ffn_norm", moe_ffn_hidden_size=512,
            moe_num_primary_experts=256,
            moe_num_active_primary_experts=8, experts_held=16,
            expert_offset=0, routed_scaling_factor=2.5, n_shared_experts=1,
            attn_block=1024, attn_compute_block=512,
            sliding_attn_block=512, attn_fused_bwd=False,
            head_block=1024, moe_tile=256))
    c.replay = dataclasses.replace(
        c.replay, capacity=8_192 * 16_384, batch_size=1,
        sequence_length=16_384, learn_start=64 * 16_384)
    c.train = dataclasses.replace(c.train, train_every=16_384)
    c.env = dataclasses.replace(c.env, token_vocab=12_544)
    return c


def nemotron_tokenq_config() -> Config:
    """NVIDIA-Nemotron-3-Nano-30B-A3B (nvidia, config.json, ``model_type``
    nemotron_h) as a token-window Q-network, one chip's share of a 16-chip
    expert-parallel deployment: every width as published (hidden 2688;
    Mamba-2 mixers of 64 heads of 64, state 128, 8 groups, a convolution
    of 4 taps, chunks of 128; attention of 32 / 2 heads of 128 with no
    positional embedding; two-matrix relu² experts of width 1 856 behind a
    sigmoid router 128 wide with a selection bias, top 6, gates x 2.5, and
    a shared expert 3 712 wide); 7 layers = the published layers 0-6, the
    unit ``MEMEM*E`` the pattern repeats (each layer ONE part alone under
    one norm), 8 of the 128 experts and 16 384 of the 131 072 vocabulary
    rows held here. Windows of 8 191 steps (+1 token = 64 whole chunks),
    chain 4, batch 2."""
    c = smallthinker_tokenq_config()
    c.net = NetConfig(
        kind="tokenq", num_actions=16_384, compute_dtype="bfloat16",
        tokenq=TokenQConfig(
            hidden_size=2688, num_hidden_layers=7,
            hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*E"
                                    "MEMEMEM*EMEMEMEME",
            num_attention_heads=32, num_key_value_heads=2, head_dim=128,
            rms_norm_eps=1e-5, sliding_window_layout=(0,) * 7,
            rope_layout=(0,) * 7, mamba_num_heads=64, mamba_head_dim=64,
            ssm_state_size=128, n_groups=8, conv_kernel=4, chunk_size=128,
            hidden_act="relu2", ffn_gated=False,
            moe_primary_router_apply_softmax=False, use_expert_bias=True,
            router_input="ffn_norm", moe_ffn_hidden_size=1856,
            moe_num_primary_experts=128, moe_num_active_primary_experts=6,
            experts_held=8, expert_offset=0, routed_scaling_factor=2.5,
            n_shared_experts=1, moe_shared_expert_intermediate_size=3712,
            attn_block=1024, attn_compute_block=512, head_block=1024,
            moe_tile=256, ssm_segment=2048))
    c.replay = dataclasses.replace(
        c.replay, capacity=16_384 * 8_191, batch_size=2,
        sequence_length=8_191, learn_start=64 * 8_191)
    c.train = dataclasses.replace(c.train, train_every=8_191)
    c.env = dataclasses.replace(c.env, token_vocab=16_384)
    return c


def sdar_tokenq_config() -> Config:
    """SDAR-30B-A3B-Chat (JetLM, config.json, ``model_type`` sdar_moe) as
    a token-window Q-network, one chip's share of a 16-chip expert-parallel
    deployment: every width as published (hidden 2048, 32/4 heads of 128
    with q/k norms, rope theta 1e6, SwiGLU experts of width 768, softmax
    router 128 wide, top 8); 4 of the 48 layers (the period is one layer),
    8 of the 128 experts and 18 992 of the 151 936 vocabulary rows held
    here, the last of them ``[MASK]``. Generation by diffusion over blocks
    of 4: a window of 16 384 steps (+1 token) goes through the backbone
    twice in one pass (32 769 rows) under the three-part block mask and
    gives 4 096 decisions; batch 1, chain 4, a ring of 8 192 windows."""
    c = smallthinker_tokenq_config()
    c.net = NetConfig(
        kind="tokenq", num_actions=18_992, compute_dtype="bfloat16",
        tokenq=TokenQConfig(
            hidden_size=2048, num_hidden_layers=4, num_attention_heads=32,
            num_key_value_heads=4, head_dim=128, rms_norm_eps=1e-6,
            sliding_window_layout=(0,) * 4, rope_layout=(1,) * 4,
            rope_theta=1_000_000.0, qk_norm=True, hidden_act="silu",
            router_input="ffn_norm",
            moe_ffn_hidden_size=768, moe_num_primary_experts=128,
            moe_num_active_primary_experts=8, experts_held=8,
            expert_offset=0, block_length=4,
            attn_block=1024, attn_compute_block=512, attn_fused_bwd=False,
            head_block=1024, moe_tile=256))
    c.replay = dataclasses.replace(
        c.replay, capacity=8_192 * 16_384, batch_size=1,
        sequence_length=16_384, learn_start=64 * 16_384)
    c.train = dataclasses.replace(c.train, train_every=16_384)
    c.env = dataclasses.replace(c.env, token_vocab=18_991)
    return c


def env_for_actor(env: EnvConfig, actor_id: int) -> EnvConfig:
    """Per-actor game assignment (config 4 multi-game fleets): actor i
    plays ``games[i % len(games)]``; single-game configs pass through."""
    if not env.games:
        return env
    return dataclasses.replace(env,
                               id=env.games[actor_id % len(env.games)])


PRESETS = {
    "cartpole": cartpole_config,
    "pong": pong_config,
    "breakout": breakout_config,
    "apex": apex_config,
    "r2d2": r2d2_config,
    "tokenq": tokenq_config,
    "smallthinker_tokenq": smallthinker_tokenq_config,
    "lfm2_tokenq": lfm2_tokenq_config,
    "keye_tokenq": keye_tokenq_config,
    "moonlight_tokenq": moonlight_tokenq_config,
    "laguna_tokenq": laguna_tokenq_config,
    "sdar_tokenq": sdar_tokenq_config,
    "nemotron_tokenq": nemotron_tokenq_config,
}


# ---------------------------------------------------------------------------
# argparse bridge
# ---------------------------------------------------------------------------


def add_config_flags(parser: argparse.ArgumentParser) -> None:
    parser.add_argument("--preset", default="cartpole", choices=sorted(PRESETS))
    parser.add_argument(
        "--backend", default="tpu", choices=["tpu", "cpu"],
        help="Compute backend behind the Solver (north-star mandated switch).")
    parser.add_argument("--set", nargs="*", default=[], metavar="PATH=VALUE",
                        help="Override any config field, e.g. train.lr=3e-4")


def _coerce(old: Any, s: str) -> Any:
    if isinstance(old, bool):
        return s.lower() in ("1", "true", "yes")
    if isinstance(old, int):
        return int(s)
    if isinstance(old, float):
        return float(s)
    if isinstance(old, tuple):     # an empty default holds strings
        kind = type(old[0]) if old else str
        return tuple(kind(v) for v in s.split(",")) if s else ()
    return s


def apply_overrides(cfg: Config, overrides: list[str]) -> Config:
    for item in overrides:
        path, _, val = item.partition("=")
        *parents, leaf = path.split(".")
        node = cfg
        for p in parents:
            node = getattr(node, p)
        setattr(node, leaf, _coerce(getattr(node, leaf), val))
    return cfg


def config_from_args(args: argparse.Namespace) -> Config:
    cfg = PRESETS[args.preset]()
    cfg.mesh.backend = args.backend
    apply_overrides(cfg, args.set)
    return cfg
