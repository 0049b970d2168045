"""Serve a small model with batched requests (deliverable b).

Batched greedy decoding with KV cache through the production decode path.

Run:  PYTHONPATH=src python examples/serve_backbone.py
"""

import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.decode import greedy_decode
from repro.models import lm


def main() -> None:
    enable_compile_cache()
    cfg = configs.get_smoke("internlm2-1.8b").replace(
        n_layers=4, d_model=128, n_heads=4, kv_heads=2, d_ff=512)
    model = lm.build(cfg)
    params = model.init(jax.random.PRNGKey(0))

    batch, prompt_len, gen = 4, 8, 24
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (batch, prompt_len), 0, cfg.vocab)
    t0 = time.time()
    toks = greedy_decode(model, params, prompts, gen,
                         max_seq=prompt_len + gen)
    dt = time.time() - t0
    print(f"served {batch} requests, {gen} new tokens each, in {dt:.1f}s")
    print("first request tokens:", toks[0].tolist())

    # determinism check: same prompts -> same generation
    toks2 = greedy_decode(model, params, prompts, gen,
                          max_seq=prompt_len + gen)
    assert (toks == toks2).all(), "decode must be deterministic"
    print("determinism check passed")


if __name__ == "__main__":
    main()
