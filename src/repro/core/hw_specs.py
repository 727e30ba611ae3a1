"""Hardware constants for the roofline model and both cost models.

Two targets coexist:
  * ``TPUSpec``  — the runtime target of the framework, one entry per
    chip in ``TPU_SPECS`` keyed by the ``device_kind`` JAX reports.
    ``target_spec()`` is the one lookup: the attached chip's entry (an
    unknown kind raises), or ``TPU_V5E`` — the explicit planning target —
    when JAX runs on another backend, so CPU planning stays
    deterministic.
  * ``FPGASpec`` — the paper's target (AMD/Xilinx Virtex UltraScale+
    xcvu37p-fsvh2892-3-e), used only by the analytical resource model that
    reproduces the paper's Tables I/II.
"""

from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class TPUSpec:
    """Per-chip numbers for the roofline terms and the kernel tiling.

    Defaults are TPU v5e.  Peaks and HBM: Google Cloud documentation,
    "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s,
    1,600 Gbit/s ICI = 4 links x 50 GB/s).  ``vmem_bytes`` is what the
    XLA:TPU compiler lets one Pallas kernel use on v5e: 128 MiB compiles,
    130 MiB is refused ("Used 130.00M of 128.00M vmem").  It is the one
    VMEM number: every ``pallas_call`` asks for it as
    ``vmem_limit_bytes`` (the default scoped limit is 16 MiB), and the
    tile rule (core.tpu_tiles) budgets working sets against a fraction
    of it.
    """

    name: str = "tpu-v5e"
    peak_bf16_flops: float = 197e12     # FLOP/s per chip
    peak_int8_ops: float = 393e12       # int8 OP/s per chip
    hbm_bytes: int = 16 * 1024**3       # 16 GiB HBM per chip
    hbm_bw: float = 819e9               # bytes/s per chip
    ici_bw_per_link: float = 50e9       # bytes/s per ICI link (one direction)
    ici_links: int = 4                  # 2D torus: +/-x, +/-y
    vmem_bytes: int = 128 * 1024**2     # compiler-allowed VMEM per kernel
    mxu_dim: int = 128                  # systolic array edge
    sublanes: int = 8
    lanes: int = 128


@dataclasses.dataclass(frozen=True)
class FPGASpec:
    """xcvu37p resources (paper's device) + mapping constants.

    The mapping constants are calibrated once against the paper's own
    tables (see benchmarks/table*.py) and documented here:

    * ``dsp_pack``   — int8 multiplications packed per DSP48E2 when a
      shared operand allows it (classic 2x packing for 8-bit).
    * ``dw_mult_in_lut`` — depthwise multipliers are small and numerous;
      the paper's DSP counts are only consistent with dw mults in LUTs.
    * ``lut_per_8x8_mult`` — soft-logic int8 multiplier cost.
    * ``compressor_alpha`` — LUTs per partial-product bit in a
      compressor tree [13]; ``binary_alpha`` for naive binary adder trees
      (the [11] baseline uses smaller trees, less compressor-friendly).
    """

    name: str = "xcvu37p-fsvh2892-3-e"
    luts: int = 1_303_680
    ffs: int = 2_607_360
    bram36: int = 2_016
    uram: int = 960
    dsps: int = 9_024
    bram36_kbits: int = 36
    bram_width: int = 72                # SDP max width
    bram_depth: int = 512               # at width 72
    # calibration constants (fit once, never per-experiment):
    dsp_pack: int = 2
    dw_mult_in_lut: bool = True
    lut_per_8x8_mult: float = 58.0
    compressor_alpha: float = 0.62      # LUT / operand-bit, compressor tree
    binary_alpha: float = 1.0           # LUT / operand-bit, binary adder tree
    acc_bits: int = 16                  # partial-product width entering trees
    ctrl_lut_per_unit: float = 34.0     # mux/counter/padding control per unit
    ctrl_lut_invalid_filter: float = 55.0  # [11]-style invalid-data filtering
    ff_per_mult: float = 26.0           # pipeline regs around each multiplier
    ff_per_unit: float = 120.0          # config counters, select lines
    ff_input_buffer_per_tap: float = 9.0  # non-transposed KPU input delay regs


TPU_V5E = TPUSpec()
XCVU37P = FPGASpec()

# device_kind (as ``jax.devices()[0].device_kind`` reports it) -> spec.
TPU_SPECS: Dict[str, TPUSpec] = {"TPU v5 lite": TPU_V5E}


def tpu_spec(device_kind: str) -> TPUSpec:
    """The spec of one TPU kind; a kind without an entry is an error,
    never a default."""
    try:
        return TPU_SPECS[device_kind]
    except KeyError:
        raise KeyError(
            f"no TPUSpec for device kind {device_kind!r} "
            f"(known: {sorted(TPU_SPECS)}) — add its entry to "
            f"core.hw_specs.TPU_SPECS"
        ) from None


def target_spec() -> TPUSpec:
    """The chip kernels are planned and compiled for.

    On a TPU backend, the attached chip's entry (unknown kinds raise).
    Elsewhere (CPU planning, interpret-mode kernels, compiles for a
    described but unattached chip) ``TPU_V5E``, the explicit target.
    """
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return TPU_V5E
    return tpu_spec(dev.device_kind)
