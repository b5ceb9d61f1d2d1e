"""Print a one-table summary of every committed measurement artifact in
benchmarks/results/ (bench JSON lines, microbench/config JSONL sweeps).
Usage: python benchmarks/summarize_results.py
No JAX import — safe to run anywhere, any time."""

from __future__ import annotations

import glob
import json
import os

R = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")


def rows_of(path: str):
    out = []
    with open(path) as f:
        text = f.read()
    for line in text.splitlines():
        line = line.strip()
        if not line or not line.startswith("{"):
            continue
        try:
            out.append(json.loads(line))
        except json.JSONDecodeError:
            pass
    if not out:
        # pretty-printed (multi-line) artifacts — composite/wire/waves
        # A/Bs and the modeled projection are written with indent
        try:
            doc = json.loads(text)
            if isinstance(doc, dict):
                out.append(doc)
        except json.JSONDecodeError:
            pass
    return out


def _fmt_attribution(a: dict, head: str = "phase_attribution") -> list:
    """Lines for one phase_attribution record (bare or embedded in a
    bench artifact — the attribution plane, docs/OBSERVABILITY.md)."""
    lines = [f"{head}: [{a.get('backend', '?')}] "
             f"{a.get('wall_ms_per_frame')} ms/frame wall, coverage "
             f"{a.get('coverage')}"
             + (f" (op_parallelism {a.get('op_parallelism')}, "
                f"normalized)" if a.get("normalized") else "")]
    wall = float(a.get("wall_ms_per_frame") or 0.0)
    phs = sorted((a.get("phases") or {}).items(),
                 key=lambda kv: -float(kv[1].get("ms") or 0.0))
    for name, p in phs:
        ms = float(p.get("ms") or 0.0)
        share = f" ({ms / wall:5.1%})" if wall > 0 else ""
        lines.append(f"  {name:14s} {ms:10.2f} ms{share} "
                     f"events={p.get('events')}")
    return lines


def fmt(r: dict) -> str:
    if r.get("type") == "phase_attribution":     # bare attribution capture
        return "\n   ".join(_fmt_attribution(r))
    if r.get("type") == "divergence_report":     # model-vs-measured deltas
        lines = [f"divergence_report: vs {r.get('modeled_row')} "
                 f"[{r.get('modeled_artifact')}] unmodeled_share="
                 f"{r.get('unmodeled_share')}"]
        for lv, e in sorted((r.get("levers") or {}).items()):
            lines.append(
                f"  {lv:18s} modeled={e.get('modeled_ms')} measured="
                f"{e.get('measured_ms')} ms  share "
                f"{e.get('modeled_share')} -> {e.get('measured_share')} "
                f"(d={e.get('share_delta')}, bound={e.get('bound')})")
        for row in (r.get("next_perf_pr") or [])[:3]:
            lines.append(f"  next: {row.get('lever')} "
                         f"d_share={row.get('share_delta')} — "
                         f"{row.get('verdict')}")
        return "\n   ".join(lines)
    if r.get("type") == "slo_report":            # live SLO engine snapshot
        lines = [f"slo_report: healthy={r.get('healthy')} "
                 f"breaches={r.get('total_breaches')} "
                 f"(window={r.get('window')}, "
                 f"min_samples={r.get('min_samples')})"]
        for name, m in sorted((r.get("metrics") or {}).items()):
            budget = m.get("budget") or 0
            gate = (f"  budget {budget:g} "
                    f"{'BREACHED' if m.get('breached') else 'ok'}"
                    if budget else "  (untracked)")
            lines.append(f"  {name:22s} p50={m.get('p50'):8.2f} "
                         f"p99={m.get('p99'):8.2f} n={m.get('n')}{gate}")
        return "\n   ".join(lines)
    if r.get("type") == "trajectory":            # regression-gate ledger row
        keys = " ".join(f"{k}={v:g}" for k, v in
                        sorted((r.get("keys") or {}).items()))
        return (f"trajectory[{r.get('family')}]: {r.get('artifact')} "
                f"vs {r.get('baseline')}  {keys}")
    if "variant" in r:                           # fold microbench row
        if "error" in r:
            return f"variant={r['variant']:14s} ERROR {r['error'][:50]}"
        return (f"variant={r['variant']:14s} {r['ms_per_march']:8.2f} ms/march"
                f"  hw={r['hw'][0]}x{r['hw'][1]} k={r['k']} c={r['chunk']}")
    if "workload" in r:                          # configs sweep row
        w = r["workload"]
        return (f"{r.get('metric', '?')}: {r['ms_per_frame']:.0f} ms/frame "
                f"{w} mode={r.get('mode')} n={r.get('n_devices')}")
    if isinstance(r.get("exchange"), dict):      # composite/wire/waves A/B
        lines = [f"{r.get('metric', 'composite_ab')}: "
                 f"[{r.get('backend', '?')}]"]
        for key, e in sorted(r["exchange"].items()):
            mod = e.get("modeled") or {}
            extra = ""
            if "ici_bytes_per_rank" in mod:
                extra = f"  ici={mod['ici_bytes_per_rank']}B/rank"
            if mod.get("schedule") == "waves":
                extra += (f" hidden={mod.get('overlap_hidden_frac')} "
                          f"(T={mod.get('wave_tiles')})")
            lines.append(f"  {key:22s} {e.get('ms_per_iter')} ms/iter"
                         f"{extra}")
        if "wire_psnr_db" in r:
            lines.append(f"  psnr_db={r['wire_psnr_db']}")
        for pk in ("parity", "schedule_parity"):
            if pk in r:
                lines.append(
                    f"  {pk}: max|dcolor|="
                    f"{r[pk].get('max_abs_diff_color')}")
        return "\n   ".join(lines)
    if r.get("kind") == "delta_ab":              # temporal-delta A/B
        lines = [f"delta_ab: [{r.get('platform', '?')}] "
                 f"verdicts={r.get('verdicts')}"]
        for name, sc in sorted((r.get("scenes") or {}).items()):
            m, w = sc.get("march", {}), sc.get("wire", {})
            lines.append(
                f"  {name:5s} march {m.get('ms_per_frame_off')} -> "
                f"{m.get('ms_per_frame_on')} ms/frame, skip "
                f"{m.get('skip_frac')}")
            if "bytes_ratio" in w:
                lines.append(
                    f"  {name:5s} wire  {w.get('bytes_per_frame_qpack8')}"
                    f" -> {w.get('bytes_per_frame_delta')} B/frame "
                    f"(x{w.get('bytes_ratio')}), records {w.get('records')}"
                    f", bitexact={w.get('recon_bitexact_vs_qpack8')}")
        return "\n   ".join(lines)
    if "plan" in r and "even" in r \
            and ("occupancy" in r or "bricks" in r):   # rebalance A/B
        ev = r["even"]
        lines = [f"{r.get('metric', 'rebalance_ab')}: even straggler "
                 f"{ev.get('straggler_factor')} "
                 f"(max_ms {ev.get('max_ms')})"]
        if "occupancy" in r:
            oc = r["occupancy"]
            lines.append(f"  slabs  -> {oc.get('straggler_factor')} "
                         f"(x{r.get('value')} reduction, frame march "
                         f"x{r.get('frame_march_speedup')}) "
                         f"plan={r['plan']}")
        if "bricks" in r:
            bb = r["bricks"]
            bm = r.get("bricks_map", {})
            lines.append(f"  bricks -> {bb.get('straggler_factor')} "
                         f"(x{r.get('value_bricks')} reduction, frame "
                         f"march x{r.get('frame_march_speedup_bricks')})"
                         f" nbricks={bm.get('nbricks')} "
                         f"slots={bm.get('slots')}")
        return "\n   ".join(lines)
    if "scenarios" in r and str(r.get("metric", "")).startswith(
            "scenario_bench"):                     # scenario zoo bench
        lines = [f"{r['metric']}: {r.get('value')} scenario(s), "
                 f"parity_ok={r.get('parity_ok')}"]
        for name, row in sorted(r["scenarios"].items()):
            par = row.get("parity")
            extra = ""
            if par:
                extra = (f"  parity ok={par.get('ok')} "
                         f"perm_bitwise={par.get('perm_bitwise')}"
                         if "ok" in par else f"  parity {par}")
            if row.get("tf_updates"):
                extra += (f"  tf {row['tf_updates']} upd/"
                          f"{row['tf_steps_reused']} reused")
            lines.append(f"  {name:14s} {row.get('ms_per_frame'):8.1f} "
                         f"ms/frame [{row.get('mode')}/{row.get('engine')}]"
                         f"{extra}")
        return "\n   ".join(lines)
    if "measured" in r and "model" in r:         # occupancy A/B
        modes = (r["measured"] or {}).get("modes", {})
        ms = " ".join(f"{m}={v.get('ms_per_frame')}ms"
                      for m, v in modes.items() if isinstance(v, dict))
        red = (r["model"] or {}).get("reduction_vs_off", {})
        return (f"{r.get('metric', 'occupancy_ab')}: {ms}"
                f"  model reduction_vs_off={red}")
    if "stack" in r:                             # modeled projection
        lines = [f"{r.get('metric', 'modeled_projection')}: "
                 f"{r.get('value')} {r.get('unit', '')} "
                 f"(vs {r.get('baseline_ms_per_frame')} ms flagship)"]
        for row in r["stack"]:
            lines.append(f"  {row.get('lever', '?'):34s} "
                         f"{row.get('modeled_ms_per_frame')} ms/frame "
                         f"x{row.get('speedup_vs_baseline')}")
        return "\n   ".join(lines)
    if str(r.get("metric", "")).startswith("hier_weak_scaling"):
        # hierarchical weak scaling through the subprocess harness
        lines = [f"{r['metric']}: weak_efficiency={r.get('value')} "
                 f"(dcn_wire={r.get('config', {}).get('dcn_wire')})"]
        for row in r.get("sweep", []):
            if "error" in row:
                lines.append(f"  hosts={row.get('hosts')} ERROR "
                             f"{row['error']}")
                continue
            mod = row.get("modeled", {})
            lines.append(
                f"  hosts={row['hosts']} ranks={row['n_ranks']} "
                f"{row['ms_per_frame']:8.1f} ms/frame  dcn "
                f"{row['dcn_bytes_sent_per_host_measured']} B/host "
                f"(modeled raw {mod.get('dcn_bytes_sent_per_host')})")
        return "\n   ".join(lines)
    if str(r.get("metric", "")).startswith("hier_device_ab"):
        # flat vs hierarchical device-path A/B
        lines = [f"{r['metric']}: flat {r.get('flat_ms_per_frame')} "
                 f"ms/frame ({r.get('devices')} dev, {r.get('grid')}^3)"]
        for key, h in sorted((r.get("hier") or {}).items()):
            lines.append(
                f"  {key:5s} {h.get('ms_per_frame')} ms/frame "
                f"(x{h.get('vs_flat')} vs flat, parity "
                f"{h.get('parity_max_abs_diff')})")
        if r.get("note"):
            lines.append(f"  note: {r['note']}")
        return "\n   ".join(lines)
    if str(r.get("metric", "")).startswith("lod_ladder"):
        # multi-resolution march ladder
        sc = r.get("scene", {})
        lines = [f"{r['metric']}: x{r.get('value')} modeled march FLOPs "
                 f"at {r.get('psnr_db')} dB (floor "
                 f"{r.get('psnr_floor_db')} dB, error_px="
                 f"{r.get('best_error_px')}; {sc.get('nbricks')} bricks)"]
        for rung in r.get("ladder", []):
            hist = rung.get("level_hist") or {"0": len(rung["levels"])}
            hist_s = " ".join(f"L{k}:{v}" for k, v in sorted(hist.items()))
            lines.append(
                f"  err={str(rung.get('error_px')):>4s}px  "
                f"{str(rung.get('psnr_db')):>7s} dB  "
                f"x{rung.get('flop_reduction')} flops  "
                f"{rung.get('frame_ms')} ms  [{hist_s}]")
        return "\n   ".join(lines)
    if str(r.get("metric", "")).startswith("delivery_ab"):
        # async delivery plane A/B
        lines = [f"{r['metric']}: exposed host x{r.get('value')} of "
                 f"serial (bit_identical={r.get('bit_identical_all')}, "
                 f"fifo={r.get('ordering_fifo_all')})"]
        for name, a in (r.get("arms") or {}).items():
            lag = (f"  lag p50/p99 {a.get('delivery_lag_p50_ms')}/"
                   f"{a.get('delivery_lag_p99_ms')} ms"
                   if a.get("delivery_lag_p50_ms") is not None else "")
            lines.append(
                f"  {name:9s} frame {a.get('frame_ms'):9.2f} ms  "
                f"exposed {a.get('exposed_host_ms_per_frame'):7.2f} ms  "
                f"offloaded {a.get('offloaded_host_ms_per_frame'):7.2f} "
                f"ms{lag}")
        te = r.get("tile_encode") or {}
        if te:
            par = te.get(f"ms_workers{te.get('workers')}")
            lines.append(
                f"  tile encode w1 {te.get('ms_workers1')} ms -> "
                f"w{te.get('workers')} {par} ms "
                f"(byte_identical={te.get('byte_identical')})")
        return "\n   ".join(lines)
    if r.get("metric") == "serve_bench":          # edge-serving tier
        am = r.get("amortization", {})
        lines = [f"serve_bench: [{r.get('platform', '?')}] per-viewer "
                 f"N=16 is x{r.get('value')} of N=1 "
                 f"(verdicts={r.get('verdicts')})"]
        for n, row in sorted(am.get("proxy", {}).items(),
                             key=lambda kv: int(kv[0])):
            lines.append(f"  N={n:>2s} {row['per_viewer_ms']:8.2f} "
                         f"ms/viewer  {row['viewers_per_second']:7.1f} "
                         "viewers/s")
        lat = r.get("latency_ms", {})
        lines.append(f"  fetch {am.get('fetch_ms')} ms + proxy build "
                     f"{am.get('proxy_build_ms')} ms/frame; p50/p99 "
                     f"{lat.get('p50')}/{lat.get('p99')} ms; "
                     f"bytes/viewer {r.get('bytes_per_viewer')}")
        return "\n   ".join(lines)
    if "metric" in r:
        val = r.get("value")
        unit = r.get("unit", "")
        cfg = r.get("config", {})
        plat = cfg.get("platform", r.get("platform", "?"))
        extra = ""
        if "ms_per_frame" in r:
            extra = f"  {r['ms_per_frame']:.1f} ms/frame"
        elif "ms_per_frame" in cfg:
            extra = f"  {cfg['ms_per_frame']:.1f} ms/frame"
        if r.get("error"):
            return f"{r['metric']}: ERROR {str(r['error'])[:60]}"
        vs = r.get("vs_baseline")
        vs_s = f"  vs_baseline={vs}" if vs is not None else ""
        line = (f"{r['metric']}: {val} {unit} [{plat}]"
                f"{extra}{vs_s}")
        if isinstance(r.get("phase_attribution"), dict):
            # bench artifact with the attribution plane riding along
            return "\n   ".join(
                [line] + _fmt_attribution(r["phase_attribution"],
                                          head="attribution"))
        return line
    return json.dumps(r)[:100]


def main():
    for path in sorted(glob.glob(os.path.join(R, "*.json*"))):
        name = os.path.basename(path)
        rows = rows_of(path)
        if not rows:
            continue
        print(f"\n== {name}")
        for r in rows:
            print("   " + fmt(r))


if __name__ == "__main__":
    main()
