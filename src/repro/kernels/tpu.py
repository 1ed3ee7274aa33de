"""What every Pallas kernel tells the TPU compiler.

``VMEM_LIMIT_BYTES`` is the repo's one VMEM number: each kernel passes
it to the compiler as ``vmem_limit_bytes``, and
:func:`repro.kernels.ops.kernel_fits` checks each kernel's
``vmem_bytes`` against it.  The ``vmem_bytes`` formulas count every
blocked operand ``BUFFERS`` times (Pallas double-buffers them to
overlap the next block's DMA with this block's compute), so a geometry
that fits is a geometry the compiler accepts.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

#: scoped VMEM a kernel may use (a v5e core has 128 MiB)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024

#: buffers Pallas keeps per blocked operand (double buffering)
BUFFERS = 2


def compiler_params(*dimension_semantics: str) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def interpret() -> bool:
    """Kernels run in Pallas interpret mode on the CPU backend only;
    every other backend compiles them."""
    return jax.default_backend() == "cpu"
