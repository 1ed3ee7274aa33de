"""Pallas TPU kernels for the paper's compute hot-spots.

* :mod:`repro.kernels.lowrank_matmul` — fused ``(x@W0)@W1`` (the SVD pair
  of paper Eq. 3) keeping the rank-bottleneck intermediate in VMEM.
* :mod:`repro.kernels.branched_matmul` — block-diagonal grouped matmul
  (the paper's branched Tucker, Fig. 4, adapted to the MXU).
* :mod:`repro.kernels.lowrank_matmul_q` — weight-only quantized variant:
  int8/fp8 factor tiles dequantized in VMEM (see repro/quant/).
* :mod:`repro.kernels.ops` — jit'd wrappers with padding + dispatch.
* :mod:`repro.kernels.ref` — pure-jnp oracles for the allclose tests.
* :mod:`repro.kernels.tpu` — the compiler settings every kernel shares
  (the one VMEM budget) and the interpret-mode switch.

Run in interpret mode on the CPU backend (the parity tests) and compiled
everywhere else; ``tests/test_tpu_compile.py`` compiles the main path's
kernels for a described v5e.
"""
