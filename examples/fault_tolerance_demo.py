"""Fault-tolerance demo: train, 'crash', resume; elastic re-shard restore.

Simulates the production contract (DESIGN.md §5):
  1. train 6 steps with async checkpointing every 3
  2. "node failure" — a fresh process state (new model object)
  3. relaunch resumes from the latest valid checkpoint, continuing the
     exactly-once data stream
  4. elastic restore: the same checkpoint re-shards onto a different mesh

Run:  PYTHONPATH=src python examples/fault_tolerance_demo.py
"""

import shutil
import tempfile

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.ckpt import checkpoint as ckpt
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.train import loop as train_loop


def main() -> None:
    enable_compile_cache()
    ckpt_dir = tempfile.mkdtemp(prefix="repro_ft_")
    cfg = configs.get_smoke("internlm2-1.8b")
    model = lm.build(cfg)

    # --- phase 1: train + checkpoint ---
    tc = train_loop.TrainConfig(steps=6, ckpt_every=3, log_every=3,
                                ckpt_dir=ckpt_dir, lr=1e-3)
    data = train_loop.synthetic_lm_data(cfg, batch=2, seq=16)
    train_loop.train(model, data, tc)
    print(f"[demo] latest checkpoint: step {ckpt.latest_step(ckpt_dir)}")

    # --- phase 2: 'crash' + relaunch with more steps ---
    print("[demo] simulating node failure + relaunch ...")
    model2 = lm.build(cfg)                      # fresh process state
    tc2 = train_loop.TrainConfig(steps=10, ckpt_every=3, log_every=2,
                                 ckpt_dir=ckpt_dir, lr=1e-3)
    data2 = train_loop.synthetic_lm_data(cfg, batch=2, seq=16,
                                         start_step=6)
    result = train_loop.train(model2, data2, tc2)
    assert result["step"] == 10
    print("[demo] resumed and finished at step 10")

    # --- phase 3: elastic restore onto a different mesh ---
    from repro.train import optim

    mesh = make_mesh((1, 1), ("data", "model"))
    params = model.init(jax.random.PRNGKey(0))
    opt = optim.AdamW(lr=1e-3, weight_decay=0.1)
    like = (params, opt.init(params))
    shardings = jax.tree.map(lambda x: NamedSharding(mesh, P()), like)
    try:
        (p2, _), extra = ckpt.restore(ckpt_dir, like, shardings=shardings)
        assert next(iter(jax.tree.leaves(p2))).sharding == \
            NamedSharding(mesh, P())
        print(f"[demo] elastic restore ok (step {extra['step']}); "
              "same checkpoint loads on any mesh")
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
