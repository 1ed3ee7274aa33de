"""Per-chip peaks, keyed by the ``device_kind`` JAX reports.

Single source of truth: the rank-selection cost model (repro.core.cost_model)
and the roofline report (repro.analysis.roofline) both read these, so the
paper's Algorithm-1 adaptation and the perf analysis agree on the hardware.
Code that measures on the device present looks its peaks up with
:func:`for_device`; a chip that is not in :data:`SPECS` is an error, never
a default.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops_bf16: float      # FLOP/s per chip
    hbm_bandwidth: float        # B/s per chip
    hbm_bytes: float            # HBM capacity per chip
    ici_link_bandwidth: float   # B/s per ICI link
    mxu_dim: int                # systolic array tile (lanes)
    sublanes: int               # VREG sublane granularity
    source: str                 # where the peaks come from
    int8_mxu_mult: float = 2.0  # int8 x int8 issue rate vs bf16/f32

    def peak_flops(self, operand_bytes: int = 2) -> float:
        """MXU FLOP/s at the *widest* operand width feeding the dot.

        int8 x int8 (both operands 1 byte) issues at ``int8_mxu_mult``
        times the bf16 rate; anything wider — including int8 weights
        dequantized in VMEM against full-width activations — runs at
        the base rate.
        """
        if operand_bytes <= 1:
            return self.peak_flops_bf16 * self.int8_mxu_mult
        return self.peak_flops_bf16


TPU_V5E = HardwareSpec(
    name="tpu_v5e",
    peak_flops_bf16=197e12,         # 393 TOP/s int8 = 2x (int8_mxu_mult)
    hbm_bandwidth=819e9,
    hbm_bytes=16 * 1024**3,
    ici_link_bandwidth=50e9,        # 1,600 Gbit/s over 4 links
    mxu_dim=128,
    sublanes=8,
    source='Google Cloud documentation, "TPU v5e" (system architecture)',
)

#: peaks by ``jax.Device.device_kind``
SPECS: dict[str, HardwareSpec] = {"TPU v5 lite": TPU_V5E}

#: the chip the cost model and the dry-run plan for (the chip this
#: system is built for) — not a stand-in for the device present
DEFAULT = TPU_V5E


def for_device(device=None) -> HardwareSpec:
    """Peaks of ``device`` (default: the first JAX device), by its
    ``device_kind``.  Raises ``KeyError`` for a kind not in SPECS."""
    if device is None:
        import jax
        device = jax.devices()[0]
    kind = device.device_kind
    if kind not in SPECS:
        raise KeyError(f"no peaks for device_kind {kind!r}: add the chip "
                       "to hw_specs.SPECS with its source")
    return SPECS[kind]


def mxu_padded(dim: int, spec: HardwareSpec = DEFAULT) -> int:
    """Dim as the MXU sees it: zero-padded up to a multiple of 128 lanes."""
    t = spec.mxu_dim
    return ((dim + t - 1) // t) * t


def sublane_padded(dim: int, spec: HardwareSpec = DEFAULT) -> int:
    t = spec.sublanes
    return ((dim + t - 1) // t) * t
