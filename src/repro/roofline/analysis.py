"""Roofline-term derivation from compiled dry-run artifacts (§Roofline).

Terms per (arch x shape x mesh) — all derived from the per-device SPMD
module, so no "chips x" factor is needed (the brief's global-bytes form and
this per-device form are algebraically identical):

    compute    = HLO_FLOPs(per-device) / PEAK_FLOPS_BF16
    memory     = HLO_bytes(per-device) / HBM_BW
    collective = ICI_traffic(per-device) / ICI_BW

``cost_analysis()`` supplies FLOPs and bytes-accessed; ICI traffic is parsed
from the compiled HLO text (hlo_parse.py).  MODEL_FLOPS is the analytic
6*N*D (train) / 2*N*D (inference) with N the *active* parameter count for
MoE — the "useful compute" yardstick that exposes remat/dispatch waste.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Optional

from repro.configs.base import ModelConfig
from repro.configs.shapes import InputShape
from repro.roofline import constants as C
from repro.roofline import hlo_parse


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    flops_per_device: float
    hbm_bytes_per_device: float
    ici_traffic_per_device: float
    peak_memory_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops_global: float
    useful_fraction: float  # MODEL_FLOPS / (HLO_FLOPs * devices)
    collective_detail: dict
    bound_s: float = 0.0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def model_flops(cfg: ModelConfig, shape: InputShape, local_steps: int = 1) -> float:
    """Analytic 'useful' FLOPs for the whole step, global across chips."""
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return 6.0 * n_active * tokens * local_steps
    if shape.kind == "prefill":
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # decode: one token per sequence


def analyze(
    cfg: ModelConfig,
    shape: InputShape,
    mesh_name: str,
    n_devices: int,
    cost: dict,
    hlo_text: str,
    memory_stats: Optional[dict] = None,
    local_steps: int = 1,
) -> RooflineReport:
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    colls = hlo_parse.collective_summary(hlo_text)
    ici = float(colls["total_traffic_bytes"])

    compute_s = flops / C.PEAK_FLOPS_BF16
    memory_s = hbm / C.HBM_BW
    collective_s = ici / C.ICI_BW
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)

    mf = model_flops(cfg, shape, local_steps)
    useful = mf / (flops * n_devices) if flops > 0 else 0.0

    return RooflineReport(
        arch=cfg.name,
        shape=shape.name,
        mesh=mesh_name,
        n_devices=n_devices,
        flops_per_device=flops,
        hbm_bytes_per_device=hbm,
        ici_traffic_per_device=ici,
        peak_memory_per_device=float((memory_stats or {}).get("peak_bytes", 0.0)),
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        model_flops_global=mf,
        useful_fraction=useful,
        collective_detail=colls,
        bound_s=max(terms.values()),
    )


def compress_traffic(k: int, P: int, bits: int = 20,
                     density: float = 1.0) -> dict:
    """HBM-traffic model of delta-to-wire compression on a (k, P) cohort —
    the bandwidth argument behind ``kernels/compress.py``.

    Staged path (ClipStage -> QuantizeStage -> MaskStage, each a separate
    XLA/Pallas dispatch over the full block):

        clip      read f32 rows + write f32 rows          2·k·P·4
        quantize  read f32 rows + write u32 rows          2·k·P·4
        mask      read u32 rows + read u32 pads + write   3·k·P·4

    Fused path: read f32 rows for the norms, then read f32 rows + read u32
    pads + write u32 ciphertext in the P-tiled kernel = ``4·k·P·4``.  Both
    paths are far under the compute roof (a handful of FLOPs per byte), so
    the traffic ratio *is* the predicted speedup on a memory-bound part.

    ``bits``/``density`` also price the resulting wire payload per client
    (bit-packed ring values; top-k keeps ``density·P`` (index, value)
    pairs), matching ``repro.api.pipeline.upload_bytes_per_client``.
    """
    if k < 1 or P < 1:
        raise ValueError(f"need k, P >= 1, got k={k}, P={P}")
    if not (0.0 < density <= 1.0):
        raise ValueError(f"density must be in (0, 1], got {density}")
    block = k * P * 4.0
    staged = 7.0 * block
    fused = 4.0 * block
    kept = max(1, int(round(density * P)))
    wire = kept * bits / 8.0 + (kept * 4.0 if density < 1.0 else 0.0)
    return {
        "k": k, "P": P, "bits": bits, "density": density,
        "staged_hbm_bytes": staged,
        "fused_hbm_bytes": fused,
        "traffic_ratio": staged / fused,
        "predicted_speedup": staged / fused,  # memory-bound: ratio == speedup
        "staged_s": staged / C.HBM_BW,
        "fused_s": fused / C.HBM_BW,
        "wire_bytes_per_client": wire,
        "wire_vs_float32": wire / (P * 4.0),
    }


def save_report(report: RooflineReport, path: str) -> None:
    with open(path, "w") as f:
        json.dump(report.to_dict(), f, indent=1)


def format_table(reports: list[RooflineReport]) -> str:
    hdr = (
        f"{'arch':<16}{'shape':<13}{'mesh':<10}{'compute_s':>11}{'memory_s':>11}"
        f"{'collect_s':>11}{'bound':<11}{'useful%':>8}{'peakHBM':>10}"
    )
    lines = [hdr, "-" * len(hdr)]
    for r in reports:
        lines.append(
            f"{r.arch:<16}{r.shape:<13}{r.mesh:<10}"
            f"{r.compute_s:>11.3e}{r.memory_s:>11.3e}{r.collective_s:>11.3e}"
            f" {r.dominant:<10}{100*r.useful_fraction:>7.1f}%"
            f"{r.peak_memory_per_device/2**30:>9.2f}G"
        )
    return "\n".join(lines)
