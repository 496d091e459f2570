"""The fused rotary pass (``ops/rotary.turn`` behind
``models/tokenq.rotary_cast``) against the plain form it replaces in the
token train programs — ``tokenq.rotary`` / ``tokenq.rotary_by_table``
followed by ``.astype`` — on the CPU in interpret mode at toy sizes: the
forward to the last bit, the gradient of a random projection to 1e-6; then
the pass inside the backbone (both q and k turned, a packed window's own
position ids), and the gauge that says whether a program took it.
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_deep_q_tpu.config import PRESETS, apply_overrides
from distributed_deep_q_tpu.models import tokenq
from distributed_deep_q_tpu.ops import rotary as rotary_pass

F32, BF16 = jnp.float32, jnp.bfloat16
YARN_FULL = PRESETS["laguna_tokenq"]().net.tokenq.rope_parameters.full_attention
SHARED = np.concatenate([np.arange(21), np.arange(1, 20)])  # 19 positions twice

# id: (q heads, rows, head, (inv, factor) from the head's width, positions,
# the dtype written)
CASES = {
    "all_columns_turn": (4, 24, 128, lambda d: (tokenq.rope_inv(1e4, d), 1.0),
                         None, BF16),
    "half_the_columns_under_yarn": (
        3, 24, 128, lambda d: tokenq.rotary_table(YARN_FULL, d), None, BF16),
    "two_rows_share_a_position": (
        4, len(SHARED), 128, lambda d: (tokenq.rope_inv(1e6, d), 1.0),
        SHARED, BF16),
    "rows_off_the_tile_and_the_block": (
        2, rotary_pass.ROW_BLOCK + 101, 128,
        lambda d: tokenq.rotary_table(YARN_FULL, d), None, BF16),
    "a_head_of_64_keeps_the_plain_form": (
        4, 24, 64, lambda d: (tokenq.rope_inv(1e6, d), 1.0), None, BF16),
    "float32_out": (4, 24, 128, lambda d: tokenq.rotary_table(YARN_FULL, d),
                    None, F32),
}


def _plain_cast(q, k, inv, factor, positions, dtype, interpret):
    """What ``rotary_cast`` replaces, under its signature."""
    return tuple(tokenq.rotary_by_table(x, inv, factor, positions)
                 .astype(dtype) for x in (q, k))


@pytest.mark.parametrize("case", CASES)
def test_the_pass_is_the_plain_form_then_the_cast(case):
    hq, t, d, table, positions, dtype = CASES[case]
    inv, factor = table(d)
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(keys[0], (2, hq, t, d), F32)
    k = jax.random.normal(keys[1], (2, 2, t, d), F32)
    wq, wk = (jax.random.normal(key, x.shape, F32)
              for key, x in zip(keys[2:], (q, k)))
    if case == "half_the_columns_under_yarn":
        assert (2 * inv.shape[0], factor != 1.0) == (d // 2, True)

    def plain(q, k):
        return _plain_cast(q, k, inv, factor, positions, dtype, True)

    def fused(q, k):
        return tokenq.rotary_cast(q, k, inv, factor, positions, dtype, True)

    def projected(form):
        return jax.jit(jax.grad(lambda q, k: sum(
            jnp.sum(y.astype(F32) * w) for y, w in zip(form(q, k), (wq, wk))),
            argnums=(0, 1)))

    kernels = str(jax.make_jaxpr(fused)(q, k)).count("pallas_call")
    assert kernels == (2 if rotary_pass.fills_lanes(d) else 0)
    for got, want in zip(jax.jit(fused)(q, k), jax.jit(plain)(q, k)):
        assert got.dtype == want.dtype == dtype
        np.testing.assert_array_equal(np.asarray(got.astype(F32)),
                                      np.asarray(want.astype(F32)))
    for got, want in zip(projected(fused)(q, k), projected(plain)(q, k)):
        assert got.dtype == F32
        np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
    if case == "all_columns_turn":      # ``rotary`` is the same table
        np.testing.assert_array_equal(
            tokenq.rotary(q, 1e4).astype(dtype), jax.jit(fused)(q, k)[0])


LAGUNA_LAYERS = [
    "net.tokenq.num_attention_heads_per_layer=2,4,4,4,2",
    "net.tokenq.sliding_window_size=8", "net.tokenq.intermediate_size=96",
    "net.tokenq.sliding_attn_block=128", "net.tokenq.head_block=32"]
BACKBONES = {
    "laguna_kinds": ("laguna_tokenq", LAGUNA_LAYERS, None),
    "packed_block_diffusion": ("sdar_tokenq", [], "reveal"),
    "sparse_padded_window": ("keye_tokenq", [
        "net.tokenq.indexer_topk=8", "net.tokenq.indexer_q_chunk=32",
        "net.tokenq.indexer_num_heads=2", "net.tokenq.indexer_head_dim=8"],
        None),
}


@pytest.mark.parametrize("family", BACKBONES)
def test_a_backbone_with_the_pass_is_the_backbone_without(family,
                                                          monkeypatch):
    """The presets' own mixers at toy widths but heads of 128, bfloat16
    products: hidden states and the parameters' gradients with the pass
    against the same program with the plain form in its place."""
    preset, more, reveal = BACKBONES[family]
    cfg = apply_overrides(PRESETS[preset](), [
        "net.num_actions=64", "net.tokenq.hidden_size=64",
        "net.tokenq.num_attention_heads=4",
        "net.tokenq.num_key_value_heads=2", "net.tokenq.head_dim=128",
        "net.tokenq.moe_ffn_hidden_size=32",
        "net.tokenq.moe_num_primary_experts=8",
        "net.tokenq.moe_num_active_primary_experts=2",
        "net.tokenq.experts_held=8", "net.tokenq.expert_offset=0",
        "net.tokenq.attn_block=128", "net.tokenq.attn_compute_block=128",
        "net.tokenq.moe_tile=8", *more])
    tq, steps = cfg.net.tokenq, 24
    assert tokenq.rotary_fused(tq) == 1 and cfg.net.compute_dtype == \
        "bfloat16"
    params = tokenq.init_params(cfg.net, 5)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, steps + 1), 0, 60)
    kw = {}
    if reveal:
        kw["reveal"] = jax.random.randint(
            jax.random.PRNGKey(2), (2, -(-steps // tq.block_length)), 0,
            tq.block_length)

    def run(compute_dtype):
        net = dataclasses.replace(cfg.net, compute_dtype=compute_dtype)

        def loss(params):
            hid, _ = tokenq.backbone(params, tokens, net, True, **kw)
            return jnp.mean(jnp.square(hid)), hid
        (_, hid), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(
            params)
        return hid, tokenq.named_leaves(grads)

    with_pass = {dt: run(dt) for dt in ("bfloat16", "float32")}
    monkeypatch.setattr(tokenq, "rotary_cast", _plain_cast)
    # the kernels read the same bfloat16 q and k: the same hidden states
    np.testing.assert_array_equal(with_pass["bfloat16"][0],
                                  run("bfloat16")[0])
    # no product's rounding can flip in float32: the gradients agree as
    # the two backward passes do
    hid, grads = run("float32")
    np.testing.assert_allclose(with_pass["float32"][0], hid, atol=1e-5)
    for name, want in grads.items():
        scale = float(jnp.max(jnp.abs(want))) + 1e-12
        assert float(jnp.max(jnp.abs(with_pass["float32"][1][name] - want))
                     ) <= 1e-4 * scale, name


@pytest.mark.parametrize("preset,fused", [
    ("laguna_tokenq", 1), ("sdar_tokenq", 1), ("keye_tokenq", 1),
    ("smallthinker_tokenq", 1), ("lfm2_tokenq", 0),
    ("moonlight_tokenq", None), ("tokenq", 0)])
def test_which_presets_take_the_pass(preset, fused):
    """Decided from the head's width alone: LFM2's 64 columns half-fill
    the lanes, Moonlight's latent layers turn interleaved pairs."""
    assert tokenq.rotary_fused(PRESETS[preset]().net.tokenq) == fused


def test_train_tokenq_says_whether_its_program_took_the_pass(tmp_path):
    from distributed_deep_q_tpu.metrics import Metrics
    from distributed_deep_q_tpu.train import train_tokenq

    out = tmp_path / "m.jsonl"
    cfg = apply_overrides(PRESETS["tokenq"](), [
        "mesh.num_fake_devices=1", "train.total_steps=150",
        "net.tokenq.head_dim=128", "net.tokenq.num_hidden_layers=2",
        "replay.learn_start=96", "train.train_every=48",
        "replay.batch_size=2", "replay.fused_chain=2",
        "env.max_episode_steps=30", "train.eval_episodes=1"])
    cfg.mesh.backend = "cpu"
    summary = train_tokenq(cfg, Metrics(str(out)), log_every=1)
    assert summary["grad_steps"] >= 2 and np.isfinite(summary["loss"])
    assert summary["train_rotary_fused"] == 1
    assert summary["solver"].fused_gauges() == {"train/rotary_fused": 1}
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    assert [r["train/rotary_fused"] for r in rows if "loss" in r] == [1] * (
        summary["grad_steps"])
