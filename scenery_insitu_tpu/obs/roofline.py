"""Roofline verdicts — per-phase achieved-vs-peak fractions and a bound
classification over a phase attribution (obs/profiler.py) joined with
the compiled step's device-cost snapshot (obs/device.py).

This module is deliberately **JAX-free** (benchmarks/divergence.py reads
artifacts without a backend), and the peak tables here are the ONE copy
the whole repo reads (bench.py re-exports them for its MFU/HBM report
fields).

The verdict model, stated so the artifact can carry its own assumptions:

- **Peaks** come from public per-device-kind numbers
  (``PEAK_TFLOPS`` / ``PEAK_HBM_GBPS`` by device-kind substring); link
  peaks default to the modeled-projection assumptions
  (``ICI_GBPS_DEFAULT`` / ``DCN_GBPS_DEFAULT`` — the same 45 / 3.125
  GB/s effective figures modeled_projection_r14.json uses). Non-TPU
  platforms get a stated ``cpu_nominal`` peak so CPU CI captures still
  produce *relative* verdicts — the artifact marks them indicative.
- **Apportionment**: XLA's cost analysis reports whole-step bytes/flops,
  not per-phase, so compute phases split the step totals proportionally
  to their measured ms share (communication and host phases excluded
  from the split). That is an assumption, written into the artifact.
- **Bound classification** per phase: ``host`` for the host-gap phase
  or any compute phase whose best achieved fraction sits under
  ``host_floor`` (nothing on the device explains the time), ``ici-dcn``
  for the exchange/DCN-hop phases, else the larger of the achieved HBM
  and MXU fractions (``hbm`` / ``mxu``).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

# TPU bf16 matmul peak FLOP/s by device-kind substring (public numbers).
PEAK_TFLOPS = (
    ("v6", 918.0), ("trillium", 918.0),
    ("v5p", 459.0),
    ("v5e", 197.0), ("v5 lite", 197.0), ("v5litepod", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
)

# HBM bandwidth GB/s by device-kind substring (public numbers).
PEAK_HBM_GBPS = (
    ("v6", 1640.0), ("trillium", 1640.0),
    ("v5p", 2765.0),
    ("v5e", 819.0), ("v5 lite", 819.0), ("v5litepod", 819.0),
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
)

# Effective link peaks — the SAME figures the modeled projection assumes
# (benchmarks/results/modeled_projection_r14.json "assumptions"), so a
# divergence between modeled and measured exchange time is never an
# artifact of two different link models.
ICI_GBPS_DEFAULT = 45.0
DCN_GBPS_DEFAULT = 3.125

# Stated nominal peaks for non-TPU captures (one modern core's FMA rate
# and a laptop-class memory bus): absolute fractions are meaningless on
# the CPU fallback, but the RELATIVE ordering of phases still is — the
# artifact's peaks_source says which regime produced the verdicts.
CPU_NOMINAL_TFLOPS = 0.1
CPU_NOMINAL_HBM_GBPS = 20.0

# Phases whose time is a link transfer, not compute: classified ici-dcn
# against the matching link peak instead of the HBM/MXU roofline.
COMM_PHASES = {"exchange": "ici", "dcn_hop": "dcn"}


def kind_lookup(table, device_kind: str, platform: str):
    """Device-kind substring lookup of a peak table; None off-TPU (the
    caller decides its non-TPU story). A TPU kind the table does not
    know is an error — a roofline share against a guessed peak is not a
    measurement."""
    if platform != "tpu":
        return None
    kind = (device_kind or "").lower()
    for sub, val in table:
        if sub in kind:
            return val
    raise ValueError(
        f"TPU device kind {device_kind!r} is not in the peak tables "
        f"(obs/roofline.py); add its published peaks there")


def peaks_for(device_kind: str, platform: str,
              ici_gbps: float = ICI_GBPS_DEFAULT,
              dcn_gbps: float = DCN_GBPS_DEFAULT) -> Dict[str, Any]:
    """The peak-assumption block of one capture: HBM + MXU peaks for the
    device kind (stated nominal figures off-TPU), link peaks from the
    modeled-projection assumptions. Every verdict artifact embeds this
    verbatim so the numbers can be re-judged when assumptions move."""
    tflops = kind_lookup(PEAK_TFLOPS, device_kind, platform)
    hbm = kind_lookup(PEAK_HBM_GBPS, device_kind, platform)
    if tflops is None or hbm is None:
        return {"tflops": CPU_NOMINAL_TFLOPS,
                "hbm_gbps": CPU_NOMINAL_HBM_GBPS,
                "ici_gbps": ici_gbps, "dcn_gbps": dcn_gbps,
                "device_kind": device_kind, "platform": platform,
                "peaks_source": "cpu_nominal (relative verdicts only)"}
    return {"tflops": tflops, "hbm_gbps": hbm,
            "ici_gbps": ici_gbps, "dcn_gbps": dcn_gbps,
            "device_kind": device_kind, "platform": platform,
            "peaks_source": "public device-kind table"}


def roofline_verdicts(attribution: Dict[str, Any],
                      cost: Optional[Dict[str, Any]] = None,
                      peaks: Optional[Dict[str, Any]] = None,
                      modeled: Optional[Dict[str, Any]] = None,
                      host_floor: float = 0.05) -> Dict[str, Any]:
    """Join a ``phase_attribution`` record (obs/profiler.py) with the
    step's cost snapshot into one verdict per phase: achieved-vs-peak
    HBM and MXU fractions and a bound classification (hbm / mxu /
    ici-dcn / host).

    ``modeled`` optionally supplies per-frame link bytes for the
    communication phases (``{"ici_bytes_per_frame": ...,
    "dcn_bytes_per_frame": ...}`` — e.g. from the modeled exchange
    traffic the step build minted) so the ici-dcn verdicts carry an
    achieved-GB/s figure too."""
    peaks = peaks or peaks_for("", "cpu")
    cost = cost if isinstance(cost, dict) else {}
    modeled = modeled or {}
    phases = attribution.get("phases") or {}
    wall = float(attribution.get("wall_ms_per_frame") or 0.0)
    step_bytes = float(cost.get("bytes_accessed") or 0.0)
    step_flops = float(cost.get("flops") or 0.0)
    compute_ms = sum(
        float(p.get("ms") or 0.0) for name, p in phases.items()
        if name not in COMM_PHASES and name != "host")
    verdicts: Dict[str, Any] = {}
    for name, p in phases.items():
        ms = float(p.get("ms") or 0.0)
        v: Dict[str, Any] = {
            "ms": round(ms, 4),
            "frac_of_wall": round(ms / wall, 4) if wall > 0 else None}
        if name == "host":
            v["bound"] = "host"
        elif name in COMM_PHASES:
            link = COMM_PHASES[name]
            peak_gbps = peaks.get(f"{link}_gbps")
            link_bytes = modeled.get(f"{link}_bytes_per_frame")
            if link_bytes and ms > 0:
                ach = float(link_bytes) / (ms / 1e3) / 1e9
                v["achieved_gbps"] = round(ach, 3)
                if peak_gbps:
                    v["link_frac_peak"] = round(ach / peak_gbps, 4)
            v["bound"] = "ici-dcn"
        else:
            share = ms / compute_ms if compute_ms > 0 else 0.0
            b_est = step_bytes * share
            f_est = step_flops * share
            hbm_frac = mxu_frac = None
            if ms > 0:
                if peaks.get("hbm_gbps"):
                    hbm_frac = (b_est / (ms / 1e3) / 1e9
                                ) / peaks["hbm_gbps"]
                if peaks.get("tflops"):
                    mxu_frac = (f_est / (ms / 1e3) / 1e12
                                ) / peaks["tflops"]
            v["bytes_est"] = round(b_est)
            v["flops_est"] = round(f_est)
            v["hbm_frac_peak"] = (round(hbm_frac, 4)
                                  if hbm_frac is not None else None)
            v["mxu_frac_peak"] = (round(mxu_frac, 4)
                                  if mxu_frac is not None else None)
            best = max(hbm_frac or 0.0, mxu_frac or 0.0)
            if best < host_floor:
                v["bound"] = "host"
            else:
                v["bound"] = ("hbm" if (hbm_frac or 0.0)
                              >= (mxu_frac or 0.0) else "mxu")
        verdicts[name] = v
    return {
        "type": "roofline_verdicts",
        "assumptions": {
            **peaks,
            "host_floor_frac": host_floor,
            "apportionment": (
                "whole-step cost-analysis bytes/flops split across "
                "compute phases proportionally to measured ms "
                "(communication + host phases excluded)"),
        },
        "step": {"bytes_accessed": step_bytes or None,
                 "flops": step_flops or None,
                 "wall_ms_per_frame": wall or None,
                 "cost_source": cost.get("source")},
        "verdicts": verdicts,
    }
