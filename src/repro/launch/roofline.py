"""Three-term roofline analysis from a compiled dry-run artifact.

  compute term    = HLO_FLOPs_per_device / peak_FLOP/s
  memory term     = HLO_bytes_per_device / HBM_bw
  collective term = collective_traffic_per_device / link_bw

Sources: ``compiled.cost_analysis()`` under-counts while-loop bodies (it
counts each body once — verified in tests/launch/test_hlo_analysis.py),
so FLOPs / bytes / collectives all come from the loop-trip-weighted HLO
analysis in hlo_analysis.py; the raw cost_analysis numbers are kept in
the artifact for reference.

Ring-model traffic per collective (g = replica-group size):

  all-gather         out_bytes x (g-1)/g
  reduce-scatter     out_bytes x (g-1)        (input = out x g)
  all-reduce         2 x bytes x (g-1)/g      (RS + AG)
  all-to-all         bytes x (g-1)/g
  collective-permute bytes

Traffic whose replica groups span pods (member ids differing by >= the
pod size, or iota groups laid across the pod axis) is charged to DCN
bandwidth; everything else to ICI.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from .mesh import peaks_for


def collective_traffic(
    collectives: List[Dict], *, n_devices: int, pod_size: Optional[int] = None
) -> Dict[str, Any]:
    """Aggregate ring-model traffic per device from hlo_analysis output."""
    ici = 0.0
    dcn = 0.0
    by_op: Dict[str, float] = {}
    for c in collectives:
        g = c["group_size"] or n_devices
        if g <= 1:
            continue
        rb = c["result_bytes"]
        op = c["op"]
        if op == "all-gather":
            t = rb * (g - 1) / g
        elif op == "reduce-scatter":
            t = rb * (g - 1)
        elif op == "all-reduce":
            t = 2 * rb * (g - 1) / g
        elif op == "all-to-all":
            t = rb * (g - 1) / g
        else:  # collective-permute
            t = rb
        t *= c.get("count", 1.0)
        is_dcn = False
        if pod_size:
            groups = c.get("explicit_groups")
            if groups:
                is_dcn = any(len({m // pod_size for m in g_}) > 1 for g_ in groups)
            elif g == n_devices // pod_size and n_devices > pod_size:
                # iota groups of exactly the pod count = the 'pod' axis
                is_dcn = True
        if is_dcn:
            dcn += t
        else:
            ici += t
        by_op[op] = by_op.get(op, 0.0) + t
    return {"ici": ici, "dcn": dcn, "by_op": by_op, "n": len(collectives)}


def roofline_terms(
    *,
    flops_per_device: float,
    bytes_per_device: float,
    traffic: Dict[str, Any],
    device_kind: str,
) -> Dict[str, Any]:
    """Roofline terms against the peaks of ``device_kind``, the chip the
    program was compiled for."""
    peaks = peaks_for(device_kind)
    t_compute = flops_per_device / peaks.bf16_flops
    t_memory = bytes_per_device / peaks.hbm_bw
    t_coll = traffic["ici"] / peaks.ici_bw + traffic["dcn"] / peaks.dcn_bw
    terms = {"compute_s": t_compute, "memory_s": t_memory, "collective_s": t_coll}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    return {
        **terms,
        "device_kind": device_kind,
        "dominant": dominant,
        "roofline_fraction": (t_compute / bound) if bound > 0 else 1.0,
        "collective_bytes_ici": traffic["ici"],
        "collective_bytes_dcn": traffic["dcn"],
        "collective_by_op": traffic["by_op"],
        "n_collectives": traffic["n"],
    }


def summarize_artifact(art: Dict[str, Any]) -> str:
    if art.get("skipped"):
        return f"{art['arch']:24s} {art['shape']:12s} {art['mesh']:7s} SKIP ({art['skipped'][:60]})"
    r = art["roofline"]
    return (
        f"{art['arch']:24s} {art['shape']:12s} {art['mesh']:7s} "
        f"C={r['compute_s']*1e3:9.2f}ms M={r['memory_s']*1e3:9.2f}ms "
        f"N={r['collective_s']*1e3:9.2f}ms -> {r['dominant'][:-2]:10s} "
        f"frac={r['roofline_fraction']:.3f} "
        f"useful={art.get('useful_flops_ratio', 0):.2f}"
    )
