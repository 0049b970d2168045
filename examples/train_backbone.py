"""Train a ~100M-param backbone for a few hundred steps (deliverable b).

Uses internlm2-1.8b's family at reduced width (~100M params) with the
production train loop (checkpointing, resume, preemption handler).

Run:  PYTHONPATH=src python examples/train_backbone.py [--steps 200]
"""

import argparse

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import common, lm
from repro.train import loop as train_loop


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_100m_ckpt")
    args = ap.parse_args()

    # ~100M-param dense config (internlm2 family, narrowed)
    cfg = configs.get_config("internlm2-1.8b").replace(
        n_layers=8, d_model=768, n_heads=12, kv_heads=6, d_ff=2048,
        vocab=32000, compute_dtype="float32", remat="none")
    model = lm.build(cfg)
    n = common.spec_param_count(model.spec())
    print(f"params: {n/1e6:.1f}M")

    tc = train_loop.TrainConfig(
        steps=args.steps, ckpt_every=50, log_every=10,
        ckpt_dir=args.ckpt_dir, lr=3e-4, warmup=20)
    data = train_loop.synthetic_lm_data(cfg, args.batch, args.seq)
    result = train_loop.train(model, data, tc)
    h = result["history"]
    print(f"loss: first {h[0]:.3f} -> last {h[-1]:.3f} "
          f"({'DECREASED' if h[-1] < h[0] else 'did not decrease'})")


if __name__ == "__main__":
    main()
