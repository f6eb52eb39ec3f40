"""Per-device hardware constants, keyed by ``jax.Device.device_kind``.

The planner's analytic cost model (peak FLOP/s, HBM and interconnect
bandwidth) and the emitter's VMEM limit read this one table.  A TPU whose
kind is not listed is an error, never a silent default: its numbers would be
guessed.  The CPU entry exists so plans can be made (and the XLA executor
run) off-chip; its numbers rank plans only relative to each other.
"""
from __future__ import annotations

import dataclasses

import jax


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    peak_flops_bf16: float  # FLOP/s
    peak_flops_f32: float  # FLOP/s
    hbm_bw: float  # bytes/s
    vmem_bytes: int  # on-chip vector memory per core (0: no VMEM)
    scoped_vmem_bytes: int  # the compiler's default per-kernel VMEM limit
    ici_bw: float  # bytes/s per device, chip-to-chip
    source: str


# TPU v5e: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM
# at 819 GB/s).  Not from the documentation, and not measured: the f32 peak
# (the bf16 peak halved) and the 45 GB/s per-device all_to_all rate, both
# the planner's earlier assumptions.  VMEM is the limit the Mosaic compiler
# reports for this chip; 16 MiB is its default per-kernel scoped limit.
_V5E = DeviceSpec(
    peak_flops_bf16=197e12,
    peak_flops_f32=98.5e12,
    hbm_bw=819e9,
    vmem_bytes=128 * 1024 * 1024,
    scoped_vmem_bytes=16 * 1024 * 1024,
    ici_bw=45e9,
    source="Google Cloud TPU v5e documentation; VMEM from the Mosaic limit",
)

# Host CPU: only relative plan ranking happens here (no kernel runs compiled).
_CPU = DeviceSpec(
    peak_flops_bf16=1e12,
    peak_flops_f32=1e12,
    hbm_bw=50e9,
    vmem_bytes=0,
    scoped_vmem_bytes=0,
    ici_bw=10e9,
    source="nominal host numbers for off-chip plan ranking, not a device peak",
)

DEVICE_SPECS: dict[str, DeviceSpec] = {
    "TPU v5 lite": _V5E,  # how JAX names a v5e
    "cpu": _CPU,
}

# The chip the Pallas kernels are compiled for when no TPU is attached (the
# ahead-of-time compiles against a described topology).
DEFAULT_TPU_KIND = "TPU v5 lite"


def spec(kind: str | None = None) -> DeviceSpec:
    """The table entry for ``kind`` (default: this process's first device)."""
    kind = jax.devices()[0].device_kind if kind is None else kind
    try:
        return DEVICE_SPECS[kind]
    except KeyError:
        raise KeyError(
            f"no hardware entry for device_kind {kind!r}; add its published "
            f"peaks to repro.kernels.hardware.DEVICE_SPECS"
        ) from None


def tpu_spec() -> DeviceSpec:
    """The TPU the kernels target: the attached one, else the default chip."""
    if jax.default_backend() == "tpu":
        return spec()
    return spec(DEFAULT_TPU_KIND)


__all__ = ["DeviceSpec", "DEVICE_SPECS", "DEFAULT_TPU_KIND", "spec", "tpu_spec"]
