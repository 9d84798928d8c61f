"""Three-term roofline from a compiled dry-run artifact.

Targets TPU v5e (assignment constants):
    197 TFLOP/s bf16 MXU per chip | 819 GB/s HBM | ~50 GB/s/link ICI.
The VPU estimate (~1 TOP/s, 8x128 lanes x ~940 MHz x 2 ops) prices the
faithful min-plus kernel, which cannot use the MXU (DESIGN.md §2).

cost_analysis() on the compiled module is PER-DEVICE (the SPMD-partitioned
module — verified empirically), so terms are flops_dev/peak etc. with no
chip division.  Collective bytes come from HLO parsing (repro.roofline.hlo);
the collective term uses modeled wire traffic / one ICI link (conservative:
a 2D torus ring uses one link per direction per axis).
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.roofline.hlo import analyze_hlo  # noqa: F401 (re-exported)


@dataclass(frozen=True)
class Hardware:
    name: str
    peak_flops: float  # bf16 FLOP/s per chip (MXU)
    vpu_ops: float  # elementwise op/s per chip (VPU estimate)
    hbm_bw: float  # bytes/s per chip
    link_bw: float  # bytes/s per ICI link


HW_V5E = Hardware(
    name="tpu_v5e", peak_flops=197e12, vpu_ops=1.0e12, hbm_bw=819e9, link_bw=50e9
)


def analyze_compiled(compiled, n_devices: int, hw: Hardware = HW_V5E,
                     vpu_fraction: float = 0.0) -> dict:
    """Roofline terms (seconds per step, per chip) from a compiled artifact.

    vpu_fraction: fraction of the FLOPs that are min-plus (VPU-priced) —
    1.0 for the faithful comet kernels, 0.0 for matmul workloads.
    """
    from repro.roofline.hlo import analyze_hlo

    ca = compiled.cost_analysis()
    text = compiled.as_text()
    hc = analyze_hlo(text, n_devices)
    # loop-aware HLO cost model (while bodies x trip count); XLA's own
    # cost_analysis counts loop bodies once and is kept for reference
    flops = float(hc.flops)
    bytes_accessed = float(hc.bytes)
    try:
        ma = compiled.memory_analysis()
        memory = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_bytes_est": int(
                ma.argument_size_in_bytes
                + ma.output_size_in_bytes
                + ma.temp_size_in_bytes
                - ma.alias_size_in_bytes
            ),
        }
    except Exception:  # pragma: no cover - backend without memory_analysis
        memory = {}

    mxu_flops = flops * (1 - vpu_fraction)
    vpu_flops = flops * vpu_fraction
    t_compute = mxu_flops / hw.peak_flops + vpu_flops / hw.vpu_ops
    t_memory = bytes_accessed / hw.hbm_bw
    t_collective = hc.total_wire_bytes / hw.link_bw
    t_collective_operand = hc.total_operand_bytes / hw.link_bw

    terms = {
        "hw": hw.name,
        "n_devices": n_devices,
        "flops_per_device": flops,
        "bytes_per_device": bytes_accessed,
        "bytes_upper_per_device": float(hc.bytes_upper),
        "xla_flops_once": float(ca.get("flops", 0.0)),
        "xla_bytes_once": float(ca.get("bytes accessed", 0.0)),
        "vpu_fraction": vpu_fraction,
        "t_compute": t_compute,
        "t_memory": t_memory,
        "t_collective": t_collective,
        "t_collective_operand_spec": t_collective_operand,
        "collectives": hc.collectives_dict(),
        "memory": memory,
    }
    terms["bottleneck"] = max(
        ("compute", t_compute), ("memory", t_memory), ("collective", t_collective),
        key=lambda kv: kv[1],
    )[0]
    t_bound = max(t_compute, t_memory, t_collective)
    terms["roofline_fraction"] = (t_compute / t_bound) if t_bound > 0 else 0.0
    return terms


def model_flops(arch_params: int, tokens: int, kind: str,
                active_fraction: float = 1.0) -> float:
    """MODEL_FLOPS: 6*N*D train (N_active for MoE), 2*N*D forward-only."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * arch_params * active_fraction * tokens
