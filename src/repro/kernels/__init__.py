"""Pallas TPU kernels for HyperSense's compute hot-spots (paper §IV).

* :mod:`repro.kernels.hdc_encode`     — fused RFF encoding matmul
* :mod:`repro.kernels.sliding_scores` — computation-reuse frame scoring
  (the paper's FPGA accelerator, TPU-adapted; DESIGN.md §3)
* :mod:`repro.kernels.similarity`     — fused cosine classifier
* :mod:`repro.kernels.ops`            — jit'd public wrappers
* :mod:`repro.kernels.ref`            — pure-jnp oracles for all of the above
"""
