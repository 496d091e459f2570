#!/usr/bin/env python
"""The rotary embedding's two forms INSIDE one layer's mixer, on the chip:
device time by operation.

    python scripts/rotary_microbench.py \
        [--case PRESET:KIND ...] [--set PATH=VALUE ...] \
        [--blocks HEADSxROWS ...] [--reps N] [--out DIR]

A case is a token preset and a kind of its attention layers (``full`` /
``sliding``), at the preset's own sizes (``--set`` changes any Config
field, as ``main``'s does: the window's rows are
``replay.sequence_length`` + 1, the heads ``net.tokenq.*``). The defaults
are Laguna's two kinds (48 heads, YaRN over 64 of 128 columns with its
factor; 64 heads, all 128 columns) and SDAR's packed window (32 769 rows
with their own position ids). For each, ONE layer's ``models/tokenq.mixer``
under ``jax.checkpoint`` and ``jax.grad`` — forward, recomputed forward
and backward, as one layer of θ runs in the train program — with the
rotation and the cast

- ``plain``: ``rotary_by_table(...).astype(dtype)``, left to XLA (what the
  train programs ran up to PR 45);
- ``fused``: ``ops/rotary.turn`` (``--blocks``: at each of these
  ``HEAD_BLOCK x ROW_BLOCK`` in place of the module's own).

Alone the compiler fuses the plain form better than it does inside the
program, so a bare function would flatter it: the mixer is the unit. Both
forms run under one more ``ddq.rotary`` scope here, SDAR's too, so the
table can name the operations: each row is an HLO instruction of the
compiled program under that scope with its device ms an execution; the
summary gives the scope's ms and the whole mixer's (a cast that moves out
of the scope moves into the rest, so the whole is what decides).

Device time comes from a ``jax.profiler`` trace and exists only where the
program ran on a TPU: anywhere else the script says so and prints no time.
The JSON goes to ``--out`` (default ``chiprun_out/rotary_microbench``).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_CASES = ("laguna_tokenq:full", "laguna_tokenq:sliding",
                 "sdar_tokenq:full")
SCOPE = "ddq.rotary"
OPS_SHOWN = 8


def build_case(case: str, overrides: list[str]):
    """(cfg, the layer's index and plan entry, packed rows, bd_steps)."""
    from distributed_deep_q_tpu.config import PRESETS, apply_overrides
    from distributed_deep_q_tpu.models import tokenq
    from distributed_deep_q_tpu.ops.attention import bd_rows

    preset, _, kind = case.partition(":")
    cfg = apply_overrides(PRESETS[preset](), overrides)
    tq = cfg.net.tokenq
    i, entry = next(
        (i, k) for i, k in enumerate(tokenq.layer_plan(tq))
        if k["rope"] and not (k["conv"] or k["latent"])
        and k["windowed"] == (kind == "sliding"))
    steps = cfg.replay.sequence_length
    bd_steps = steps if tq.block_length else 0
    rows = len(bd_rows(steps, tq.block_length)[1]) if bd_steps else steps + 1
    return cfg, i, entry, rows, bd_steps


def mixer_step(cfg, entry, bd_steps: int, form, interpret: bool):
    """``(x, p) -> (loss, grads)`` of one rematerialised mixer whose
    rotation and cast are ``form``."""
    import jax
    import jax.numpy as jnp

    from distributed_deep_q_tpu.models import tokenq

    def scoped(*args):
        with jax.named_scope(SCOPE):
            return form(*args)

    def loss(x, p):
        tokenq.rotary_cast, was = scoped, tokenq.rotary_cast
        try:
            y, _, _ = jax.checkpoint(lambda x, p: tokenq.mixer(
                x, p, cfg.net, entry["windowed"], entry["rope"], interpret,
                sparse=entry["sparse"], index_loss=False,
                heads=entry["heads"], rope_params=entry["rope_params"],
                bd_steps=bd_steps))(x, p)
        finally:
            tokenq.rotary_cast = was
        return jnp.sum(y * y)

    return jax.value_and_grad(loss, argnums=(0, 1))


def plain_form(q, k, inv, factor, positions, dtype, interpret):
    from distributed_deep_q_tpu.models.tokenq import rotary_by_table

    return tuple(rotary_by_table(x, inv, factor, positions).astype(dtype)
                 for x in (q, k))


def device_ops(trace_dir: str, module: str):
    """{HLO instruction: device ns} summed over the executions of
    ``module`` in the newest trace under ``trace_dir``, and how many
    executions; ``None`` where the trace has no TPU plane."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    for plane in ProfileData.from_file(path).planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        runs = [(e.start_ns, e.start_ns + e.duration_ns)
                for e in lines["XLA Modules"].events
                if e.name.startswith(module)]
        ops: dict[str, float] = {}
        for e in lines["XLA Ops"].events:
            m = re.match(r"^%(\S+) = ", e.name)
            if m and not re.match(r"^%(while|conditional|call)[.\d]* = ",
                                  e.name) and any(
                    s <= e.start_ns and e.start_ns + e.duration_ns <= t
                    for s, t in runs):
                ops[m.group(1)] = ops.get(m.group(1), 0.0) + e.duration_ns
        return ops, len(runs)
    return None


def measure(name: str, step, args, reps: int, out: str) -> dict:
    """Compile ``step``, run it ``reps`` times under the profiler and
    split its device time by the scope table of its own compiled text."""
    import jax

    from distributed_deep_q_tpu.profiling import scope_table

    step.__name__ = name
    compiled = jax.jit(step).lower(*args).compile()
    text = compiled.as_text()
    scopes = scope_table(text)["scopes"]
    shapes = dict(re.findall(r"^\s*(?:ROOT )?%(\S+) = (\S+) ", text, re.M))
    jax.block_until_ready(compiled(*args))
    trace_dir = os.path.join(out, name)
    with jax.profiler.trace(trace_dir):
        for _ in range(reps):
            jax.block_until_ready(compiled(*args))
    got = device_ops(trace_dir, f"jit_{name}")
    row = {"name": name, "instructions_under_scope": sum(
        SCOPE in st for st in scopes.values())}
    if got is None:
        row["device"] = "no TPU plane in the trace: no device time"
        return row
    ops, runs = got
    under = {k: v for k, v in ops.items() if SCOPE in scopes.get(k, ())}
    row.update(
        executions=runs,
        mixer_ms=sum(ops.values()) / runs / 1e6,
        rotary_ms=sum(under.values()) / runs / 1e6,
        rotary_ops=[[k, shapes.get(k, "?"), v / runs / 1e6] for k, v in
                    sorted(under.items(), key=lambda kv: -kv[1])])
    return row


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--case", nargs="*", default=list(DEFAULT_CASES))
    ap.add_argument("--set", nargs="*", default=[], metavar="PATH=VALUE")
    ap.add_argument("--blocks", nargs="*", default=[],
                    metavar="HEADSxROWS")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default=os.path.join(
        "chiprun_out", "rotary_microbench"))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from distributed_deep_q_tpu.models import tokenq
    from distributed_deep_q_tpu.ops import rotary as rotary_pass

    device = jax.devices()[0]
    interpret = device.platform != "tpu"
    os.makedirs(args.out, exist_ok=True)
    own = f"{rotary_pass.HEAD_BLOCK}x{rotary_pass.ROW_BLOCK}"
    rows = []
    for case in args.case:
        cfg, i, entry, t, bd_steps = build_case(case, args.set)
        tq = cfg.net.tokenq
        shapes = tokenq.param_shapes(cfg.net)[tokenq.layer_name(i)]
        keys = iter(jax.random.split(jax.random.PRNGKey(0), len(shapes) + 1))
        p = {k: tokenq.INIT_STD * jax.random.normal(next(keys), s)
             if len(s) > 1 else jnp.ones(s) for k, s in shapes.items()}
        x = jax.random.normal(
            next(keys), (cfg.replay.batch_size, t, tq.hidden_size))
        forms = [("plain", plain_form, own)] + [
            ("fused", tokenq.rotary_cast, b) for b in (args.blocks or [own])]
        for form, fn, blocks in forms:
            heads, block_rows = (int(n) for n in blocks.split("x"))
            rotary_pass.HEAD_BLOCK, rotary_pass.ROW_BLOCK = heads, block_rows
            name = re.sub(r"\W", "_", f"{case}_{form}_{blocks}")
            row = measure(name, mixer_step(cfg, entry, bd_steps, fn,
                                           interpret), (x, p), args.reps,
                          args.out)
            row.update(case=case, form=form, blocks=blocks, rows=t,
                       heads=entry["heads"],
                       kv_heads=tq.num_key_value_heads, head_dim=tq.head_dim,
                       device=row.get("device", device.device_kind))
            rows.append(row)
            print(json.dumps({k: v for k, v in row.items()
                              if k != "rotary_ops"}), flush=True)
            for op in row.get("rotary_ops", [])[:OPS_SHOWN]:
                print("   ", json.dumps(op), flush=True)
    with open(os.path.join(args.out, "rotary_microbench.json"), "w") as fh:
        json.dump(rows, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
