"""The model-vs-measured divergence engine (ROADMAP item 1: "where
measurement and model disagree, the delta IS the next perf PR").

Joins a fresh ``phase_attribution`` capture (obs/profiler.py) against
the committed ``modeled_projection_*.json`` lever stack
(benchmarks/model_projection.py) and emits a per-lever delta report:
measured/modeled ratio, which side of the roofline the error sits on
(from the attribution's roofline verdicts when present), and a ranked
"next perf PR" list.

Scale honesty: the modeled stack is minted for its OWN assumptions
(e.g. 8 ranks at 512^3 on v5e-class HBM/ICI) while a capture may be a
1-chip CPU 128^3 run — raw ms ratios are then scale-polluted, so the
ranking key is the **share delta**: each lever's fraction of its own
frame total, modeled vs measured. A lever whose share grew is eating
more of the frame than the model promised, whatever the absolute
clock; the report states both scales so a reader can judge.

JAX-free on purpose: runs in CI over committed artifacts.

Usage:
    python benchmarks/divergence.py --attribution FILE [--modeled FILE]
                                    [--out FILE]
    python benchmarks/divergence.py --self-check
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))

RESULTS_DIR = os.path.join(_HERE, "results")

# Measured phase names (obs/profiler.py PHASES + synthetic) → the
# modeled stack's per-lever "ms" keys (benchmarks/model_projection.py).
# Since PR 19 the host-delivery path (tile slicing, compression, CRC,
# sinks — measured through ProfileCapture's host_time_fn hook) is a
# modeled lever (bytes × codec throughput, overlap factor from
# pipeline_depth), so "host" joins the lever table; only "unattributed"
# stays in the unmodeled bucket — device time the sitpu_* scopes could
# not explain, the model's remaining stated blind spot.
LEVER_PHASES: Dict[str, tuple] = {
    "sim": ("sim_step",),
    "march": ("march", "halo", "wave"),
    "composite_stream": ("merge", "resegment", "wire_encode"),
    "exchange_exposed": ("exchange",),
    "dcn_exchange": ("dcn_hop",),
    "host_delivery": ("host",),
}
UNMODELED = ("unattributed",)


def latest_modeled(results_dir: str = RESULTS_DIR) -> Optional[str]:
    """Newest committed modeled projection (lexicographic == revision
    order for modeled_projection_r*.json)."""
    paths = sorted(glob.glob(os.path.join(results_dir,
                                          "modeled_projection_*.json")))
    return paths[-1] if paths else None


def extract_attribution(doc: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Accept either a bare ``phase_attribution`` record or a bench
    artifact embedding one."""
    if doc.get("type") == "phase_attribution":
        return doc
    emb = doc.get("phase_attribution")
    if isinstance(emb, dict) and emb.get("phases"):
        return emb
    return None


def _config_score(row_cfg: Dict[str, Any],
                  measured_cfg: Dict[str, Any]) -> int:
    """How many of the lever-defining knobs a stack row shares with the
    measured run. Ties resolve to the LAST matching row — deeper in the
    stack, i.e. the most-levered row consistent with the measurement."""
    score = 0
    for key in ("exchange", "wire", "schedule", "sim_fused",
                "render_dtype", "temporal_reuse", "num_hosts"):
        if key in row_cfg and key in measured_cfg \
                and row_cfg[key] == measured_cfg[key]:
            score += 1
    return score


def select_row(stack: List[Dict[str, Any]],
               measured_cfg: Optional[Dict[str, Any]]
               ) -> Dict[str, Any]:
    """The modeled row to confront the measurement with: best config
    match, else the baseline (first) row."""
    if not stack:
        raise ValueError("modeled projection has an empty stack")
    if not measured_cfg:
        return stack[0]
    best, best_score = stack[0], -1
    for row in stack:
        s = _config_score(row.get("config") or {}, measured_cfg)
        if s >= best_score:
            best, best_score = row, s
    return best


def divergence_report(attribution: Dict[str, Any],
                      modeled_doc: Dict[str, Any],
                      roofline: Optional[Dict[str, Any]] = None,
                      measured_config: Optional[Dict[str, Any]] = None,
                      modeled_path: Optional[str] = None
                      ) -> Dict[str, Any]:
    """Per-lever model-vs-measured delta over one attribution capture.

    Each lever row carries: modeled/measured ms, the raw ratio, both
    shares of their respective frame totals, the share delta (ranking
    key) and — when roofline verdicts ride along — the bound class the
    measured time predominantly sits on (so the reader knows WHICH side
    of the roofline to attack)."""
    phases = attribution.get("phases") or {}
    row = select_row(modeled_doc.get("stack") or [], measured_config)
    modeled_ms: Dict[str, float] = dict(row.get("ms") or {})
    measured_by_lever: Dict[str, float] = {}
    covered = set()
    for lever, names in LEVER_PHASES.items():
        ms = sum(float((phases.get(p) or {}).get("ms") or 0.0)
                 for p in names)
        covered.update(names)
        if lever in modeled_ms or ms > 0:
            measured_by_lever[lever] = ms
    unmodeled_ms = sum(
        float(p.get("ms") or 0.0) for name, p in phases.items()
        if name in UNMODELED or name not in covered)

    modeled_total = sum(modeled_ms.values()) or None
    measured_total = (sum(measured_by_lever.values()) + unmodeled_ms) \
        or None

    def bound_of(names) -> Optional[str]:
        if not roofline:
            return None
        verdicts = roofline.get("verdicts") or {}
        best, best_ms = None, 0.0
        for p in names:
            v = verdicts.get(p)
            if v and float(v.get("ms") or 0.0) >= best_ms:
                best, best_ms = v.get("bound"), float(v.get("ms") or 0.0)
        return best

    levers = {}
    for lever, measured in measured_by_lever.items():
        modeled = modeled_ms.get(lever)
        m_share = (measured / measured_total) if measured_total else None
        p_share = (modeled / modeled_total) \
            if (modeled is not None and modeled_total) else None
        entry = {
            "modeled_ms": modeled,
            "measured_ms": round(measured, 4),
            "ratio": (round(measured / modeled, 3)
                      if modeled else None),
            "modeled_share": (round(p_share, 4)
                              if p_share is not None else None),
            "measured_share": (round(m_share, 4)
                               if m_share is not None else None),
            "share_delta": (round(m_share - p_share, 4)
                            if None not in (m_share, p_share) else None),
            "bound": bound_of(LEVER_PHASES[lever]),
        }
        levers[lever] = entry

    # ranked next-perf-PR list: biggest absolute share divergence first;
    # levers the model doesn't even carry rank by raw measured share
    def rank_key(item):
        e = item[1]
        if e["share_delta"] is not None:
            return abs(e["share_delta"])
        return e["measured_share"] or 0.0

    ranked = []
    for lever, e in sorted(levers.items(), key=rank_key, reverse=True):
        if e["share_delta"] is not None and e["share_delta"] == 0.0:
            continue
        direction = None
        if e["share_delta"] is not None:
            direction = ("measured share above model — attack this "
                         "lever" if e["share_delta"] > 0 else
                         "measured share below model — model too "
                         "pessimistic here")
        ranked.append({"lever": lever, "share_delta": e["share_delta"],
                       "bound": e["bound"], "verdict": direction})

    assumptions = modeled_doc.get("assumptions") or {}
    return {
        "type": "divergence_report",
        "modeled_artifact": modeled_path,
        "modeled_row": row.get("lever"),
        "modeled_assumptions_scale": {
            k: assumptions.get(k) for k in ("ranks", "grid", "hbm_gbps",
                                            "ici_gbps_effective")},
        "measured_scale": {
            "backend": attribution.get("backend"),
            "device_kind": attribution.get("device_kind"),
            "devices": attribution.get("devices"),
            "wall_ms_per_frame": attribution.get("wall_ms_per_frame"),
            "coverage": attribution.get("coverage"),
        },
        "scale_note": (
            "modeled and measured scales differ unless this capture ran "
            "the model's own assumptions — rank levers by share_delta "
            "(scale-free), read raw ratios only on matching hardware"),
        "modeled_total_ms": modeled_total,
        "measured_total_ms": (round(measured_total, 4)
                              if measured_total else None),
        "unmodeled_ms": round(unmodeled_ms, 4),
        "unmodeled_share": (round(unmodeled_ms / measured_total, 4)
                            if measured_total else None),
        "levers": levers,
        "next_perf_pr": ranked,
    }


def report_from_files(attribution_path: str,
                      modeled_path: Optional[str] = None
                      ) -> Dict[str, Any]:
    with open(attribution_path) as f:
        doc = json.load(f)
    attr = extract_attribution(doc)
    if attr is None:
        raise ValueError(
            f"{attribution_path}: no phase_attribution record (neither "
            "bare nor embedded in a bench artifact)")
    modeled_path = modeled_path or latest_modeled()
    if modeled_path is None:
        raise FileNotFoundError(
            "no modeled_projection_*.json under benchmarks/results/")
    with open(modeled_path) as f:
        modeled_doc = json.load(f)
    return divergence_report(
        attr, modeled_doc,
        roofline=doc.get("roofline_verdicts") or doc.get("roofline"),
        measured_config=doc.get("config"),
        modeled_path=os.path.relpath(modeled_path,
                                     os.path.dirname(_HERE)))


def self_check(results_dir: str = RESULTS_DIR) -> int:
    """CI self-check: every committed attribution artifact must produce
    a schema-complete divergence report against the committed modeled
    projection. Returns a process exit code."""
    attrs = sorted(glob.glob(os.path.join(results_dir,
                                          "attribution_*.json")))
    modeled = latest_modeled(results_dir)
    if not attrs or modeled is None:
        print(f"[divergence] self-check needs >=1 attribution_*.json "
              f"and a modeled projection under {results_dir}",
              file=sys.stderr)
        return 1
    failures = 0
    for path in attrs:
        try:
            rep = report_from_files(path, modeled)
            assert rep["type"] == "divergence_report"
            assert rep["levers"], "no levers joined"
            assert rep["next_perf_pr"] is not None
            for e in rep["levers"].values():
                assert e["measured_ms"] is not None
            print(f"[divergence] OK {os.path.basename(path)}: "
                  f"{len(rep['levers'])} levers vs {rep['modeled_row']}"
                  f" (top: {rep['next_perf_pr'][0]['lever'] if rep['next_perf_pr'] else 'none'})")
        except Exception as e:      # noqa: BLE001 — each artifact judged
            # independently; a broken one fails the check loudly instead
            # of aborting the sweep
            from scenery_insitu_tpu import obs

            obs.degrade("divergence.modeled", os.path.basename(path),
                        "failed", f"divergence self-check failed: {e}",
                        warn=False)
            failures += 1
            print(f"[divergence] FAIL {os.path.basename(path)}: {e}",
                  file=sys.stderr)
    return 1 if failures else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--attribution",
                    help="phase_attribution artifact (bare or a bench "
                         "artifact embedding one)")
    ap.add_argument("--modeled",
                    help="modeled_projection_*.json (default: newest "
                         "committed)")
    ap.add_argument("--out", help="write the report here (default: "
                                  "stdout)")
    ap.add_argument("--self-check", action="store_true",
                    help="validate every committed attribution artifact")
    args = ap.parse_args(argv)
    if args.self_check:
        return self_check()
    if not args.attribution:
        ap.error("--attribution is required (or use --self-check)")
    rep = report_from_files(args.attribution, args.modeled)
    text = json.dumps(rep, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"[divergence] wrote {args.out}")
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
