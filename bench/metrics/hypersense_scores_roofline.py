"""Share of its roofline the scoring kernel reaches, in percent.

The least time its calls could take is the larger of the work's FLOPs
over the chip's peak FLOP/s and its least bytes over the peak bandwidth
(``bench/work.py``; at the paper's point the FLOPs bound it). It is
divided by the kernel's device time: the summed durations of the
``hypersense_scores`` events in the traced window."""

from bench import work


def read(ctx):
    tr = ctx["trace"]
    if not tr["kernel_calls"] or tr["kernel_s"] <= 0:
        return None
    g, p = ctx["gate"], ctx["peaks"]
    frames = tr["kernel_calls"] * ctx["frames_per_kernel_call"]
    least = max(frames * work.gate_frame_flops(g) / p.bf16_flops,
                frames * work.gate_frame_bytes(g) / p.hbm_bytes)
    return least / tr["kernel_s"] * 100.0
