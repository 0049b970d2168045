"""Model FLOP/s utilization of the detector backbone, in percent: real
frames whose logits reached the host in the traced window (pad rows never
count), times the detector's forward FLOPs per frame (``frame_flops`` of
the reference module its configuration names, ``bench/reference``), over
the window, the chips and the bfloat16 peak."""

from bench import reference


def read(ctx):
    frames = ctx["counts"]["backbone_frames"]
    if not frames or ctx["detector"] is None:
        return None
    flops = frames * reference.detector(ctx["config"]).frame_flops(
        ctx["gate"], ctx["detector"])
    return flops / (ctx["trace"]["window_s"] * ctx["chips"]
                    * ctx["peaks"].bf16_flops) * 100.0
