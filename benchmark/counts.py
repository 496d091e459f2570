"""Operations and bytes of the fused learner's pieces, from shapes alone
(the benchmark's own count; the program's XLA census is only printed).

``train_flops_per_step``: the head width and the number of forwards are
taken from the configuration, not assumed. Multiply-adds count 2. The
backward pass of the online net on s costs twice its forward;
recomputation does not count.
"""

from __future__ import annotations


def conv_out(n: int, k: int, s: int) -> int:
    return (n - k) // s + 1


def layer_flops(hp: dict) -> list[float]:
    """Forward FLOPs of each layer of the Nature CNN, one observation."""
    h, w = hp["frame_shape"]
    out, cin = [], hp["stack"]
    for k, s, cout in ((8, 4, 32), (4, 2, 64), (3, 1, 64)):
        h, w = conv_out(h, k, s), conv_out(w, k, s)
        out.append(2.0 * h * w * cout * k * k * cin)
        cin = cout
    out.append(2.0 * h * w * cin * 512)
    out.append(2.0 * 512 * hp["num_actions"])
    return out


def train_flops_per_step(hp: dict) -> float:
    """What one grad step requires: the online net forward on s and its
    backward (weight and input gradients: twice the forward, less the
    first layer's input gradient, which nothing needs), the target net on
    s', and with Double-DQN the online net on s' — times the batch."""
    layers = layer_flops(hp)
    fwd = sum(layers)
    forwards = 1 + 1 + (1 if hp["double_dqn"] else 0)
    return (forwards * fwd + 2.0 * fwd - layers[0]) * hp["batch_size"]


def padded_row_bytes(hp: dict) -> int:
    """Frame row padded to the 4 KiB 1-D tile the row DMA moves."""
    row = hp["frame_shape"][0] * hp["frame_shape"][1]
    return -(-row // 4096) * 4096


def gather_windows_bytes_per_chunk(hp: dict) -> float:
    """What the window DMA of one chunk must READ from the ring in HBM:
    chain x batch windows of (stack + n_step) padded rows. The write is
    left out: the compiler may place the destination outside HBM (at batch
    32 it does — the output carries memory space S(1) — and counting the
    write there read 175 %). Where the destination is HBM too, as at batch
    512, the traffic is twice this and the kernel's ceiling is 50 %."""
    window = hp["stack"] + hp["n_step"]
    return float(hp["fused_chain"] * hp["batch_size"] * window
                 * padded_row_bytes(hp))
