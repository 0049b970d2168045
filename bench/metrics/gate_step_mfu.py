"""Model FLOP/s utilization of the gate, in percent: gate frames whose
decisions reached the host in the traced window, times the projection's
FLOPs per frame (``bench/work.py``), over the window, the chips and the
bfloat16 peak."""

from bench import work


def read(ctx):
    frames = ctx["counts"]["gate_frames"]
    if not frames:
        return None
    flops = frames * work.gate_frame_flops(ctx["gate"])
    return flops / (ctx["trace"]["window_s"] * ctx["chips"]
                    * ctx["peaks"].bf16_flops) * 100.0
