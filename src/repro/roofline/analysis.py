"""Three-term roofline from a compiled dry-run artifact.

    compute    = HLO_FLOPs      / (chips * peak_FLOP/s)
    memory     = HLO_bytes      / (chips * HBM_bw)
    collective = collective_B   / (chips * link_bw)

``compiled.cost_analysis()`` on an SPMD module reports the *per-device*
program (one partition's flops/bytes), so per-chip terms divide by the
chip rate only; we normalize both conventions explicitly and record
which was used.  MODEL_FLOPS is the analytic useful work (6·N·D train,
2·N·D inference, N_active for MoE); its ratio against HLO_FLOPs exposes
remat recompute and dispatch overhead.
"""

from __future__ import annotations

from typing import Dict, Optional

from .constants import ChipPeaks
from .hlo import parse_collectives


def model_flops(n_params: int, n_active: int, tokens: int,
                kind: str) -> float:
    n = n_active or n_params
    if kind == "train":
        return 6.0 * n * tokens
    return 2.0 * n * tokens          # prefill / decode forward-only


def roofline_terms(*, hlo_flops_per_chip: float, hlo_bytes_per_chip: float,
                   collective_bytes_per_chip: float,
                   chip: ChipPeaks) -> Dict[str, float]:
    compute = hlo_flops_per_chip / chip.peak_flops
    memory = hlo_bytes_per_chip / chip.hbm_bw
    collective = collective_bytes_per_chip / chip.ici_bw
    terms = {"compute_s": compute, "memory_s": memory,
             "collective_s": collective}
    dominant = max(terms, key=terms.get)
    bound = max(compute, memory, collective)
    total = max(bound, 1e-30)
    return {
        **terms,
        "dominant": dominant.replace("_s", ""),
        "bound_s": bound,
        "compute_fraction_of_roofline": compute / total,
    }


def analyze_compiled(compiled, desc: dict, n_chips: int, chip: ChipPeaks,
                     hlo_text: Optional[str] = None) -> dict:
    """Extract the full §Roofline row for one compiled cell on ``chip``.

    Primary accounting is the trip-count-aware HLO cost model
    (roofline/hlo_cost.py); the backend's ``cost_analysis()`` is kept in
    the artifact for reference but is known to count ``while`` bodies
    once on CPU (validated in tests/test_roofline.py).
    """
    backend_cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        backend_cost = {k: float(v) for k, v in dict(ca or {}).items()
                        if isinstance(v, (int, float))}
    except Exception as e:             # pragma: no cover
        backend_cost = {"error": str(e)}

    text = hlo_text if hlo_text is not None else compiled.as_text()
    from .hlo_cost import hlo_cost
    model_cost = hlo_cost(text)
    flops = float(model_cost["flops"])
    nbytes = float(model_cost["bytes"])
    coll = {
        "total_bytes": float(model_cost["collective_bytes"]),
        "per_kind_bytes": model_cost["per_kind_bytes"],
        "flat_parse": parse_collectives(text),    # no loop multipliers
    }
    bytes_by_op = model_cost.get("bytes_by_op", {})

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                mem[k] = int(v)
    except Exception as e:             # pragma: no cover
        mem = {"error": str(e)}

    # cost_analysis on an SPMD module is per-device; collective bytes
    # parsed from the per-device HLO likewise.
    terms = roofline_terms(
        hlo_flops_per_chip=flops,
        hlo_bytes_per_chip=nbytes,
        collective_bytes_per_chip=coll["total_bytes"],
        chip=chip,
    )
    mf = model_flops(desc["n_params"], desc.get("n_active_params", 0),
                     desc["tokens"], desc["kind"])
    mf_per_chip = mf / n_chips
    return {
        **desc,
        "n_chips": n_chips,
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": nbytes,
        "bytes_by_op": bytes_by_op,
        "backend_cost_analysis": backend_cost,
        "collectives": coll,
        "memory_analysis": mem,
        "roofline": terms,
        "model_flops_total": mf,
        "model_flops_per_chip": mf_per_chip,
        "useful_flops_ratio": (mf_per_chip / flops) if flops else 0.0,
        "step_time_bound_s": terms["bound_s"],
        "model_flops_utilization_bound": (
            mf_per_chip / chip.peak_flops / terms["bound_s"]
            if terms["bound_s"] > 0 else 0.0),
    }


def format_row(r: dict) -> str:
    t = r["roofline"]
    return (f"| {r['arch']} | {r['shape']} | {t['compute_s']:.4f} | "
            f"{t['memory_s']:.4f} | {t['collective_s']:.4f} | "
            f"{t['dominant']} | {r['useful_flops_ratio']:.2f} | "
            f"{r['model_flops_utilization_bound']:.3f} |")
