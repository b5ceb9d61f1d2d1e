"""Modeled 8-rank Config-2 projection — the per-lever ms/frame stack
committed with stated assumptions while no chip measurement exists.

Composes the EXISTING committed traffic models — nothing new is invented
here, the stack is just their sum at the BASELINE.md Config-2 shape
(8 ranks, 512^3 global Gray-Scott, 640x640 intermediate grid, K=16,
temporal adaptive = one march/frame):

- sim:       sim.pallas_stencil.modeled_sim_traffic (fused vs roll)
- march:     one volume read of the rank slab per march, f32 vs bf16
             (SliceMarchConfig.render_dtype), scaled by the committed
             sim-fused occupancy-pyramid reduction
             (benchmarks/results/occupancy_ab_r06_512.json, 2.43x)
- exchange:  ops.composite.modeled_exchange_traffic (all_to_all vs ring,
             f32 vs qpack8 wire, frame vs waves schedule — the waves row
             charges only the EXPOSED exchange bytes, docs/PERF.md
             "Tile waves")
- rebalance: the skewed-occupancy scenario rows multiply the march term
             by the per-rank straggler factor (max/mean march work —
             the frame barrier is the MAX over ranks): the even split's
             factor and the occupancy plan's come from the committed
             rank_slab_bench A/B (rebalance_ab_r10_cpu.json), with the
             stated assumption that the measured CPU 96^3 skew (dense
             low-z quarter) transfers to the 512^3 banded Gray-Scott
             regime PR 6 measured at live-cell 0.41
- composite: the same model's stream_bytes_per_rank (merge working set
             + k_out output write)
- delivery:  the host delivery plane (PR 19) — one rank's frame share
             over PCIe plus a codec sweep (quantize/pack + CRC) of the
             input bytes; every ladder row prices it SERIALLY (the
             pre-PR-19 critical path where the loop blocks on
             np.asarray and encodes inline) and the +async_delivery
             scenario row shows the depth-k pipeline + encode-worker
             fan-out leaving only max(0, host - device) exposed

Every row converts bytes -> ms with the stated bandwidth assumptions and
adds them (a traffic LOWER BOUND: compute, dispatch and host time are
excluded; the measured flagship runs well below peak bandwidth, so the
honest reading is the RELATIVE per-lever deltas, not the absolute ms).
The flagship datum (419.43 ms/frame, 1 chip, pre-lever) is carried for
reference. Usage:

    python benchmarks/modeled_projection.py \
        [--out benchmarks/results/modeled_projection_r08.json]

No accelerator access — safe anywhere (JAX_PLATFORMS=cpu is fine).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

R = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")

# ---- Config-2 shape (BASELINE.md; flagship capture bench_tpu_r4_512) ----
RANKS = 8
GRID = 512
SIM_STEPS = 10
NI = NJ = 640                    # flagship intermediate grid at 512^3
K = 16
WAVE_TILES = 4

# ---- bandwidth assumptions (stated, not measured) ----
# v5e HBM data-sheet peak; the flagship capture achieved ~8.4% of it, so
# absolute ms here are optimistic floors — the deltas are the signal.
HBM_GBPS = 819.0
# effective per-link ICI assumption for a v5e 1-D ring (conservative
# fraction of the ~400 GB/s aggregate the data sheet quotes per chip).
ICI_GBPS = 45.0
# effective per-host DCN assumption for the inter-domain hop of the
# hierarchical composite (docs/MULTIHOST.md) — a conservative 25 Gbit/s
# of usable cross-host bandwidth (~1/14 of the ICI link): DCN is the
# slow level by construction, which is the whole reason the composite
# splits into two levels instead of running one flat exchange over it.
DCN_GBPS = 3.125
# ---- host delivery plane (PR 19) ----
# PCIe Gen4 x16 assumption for the device->host copy of the rendered
# frame (the copy the async fetch overlaps behind the next dispatch).
PCIE_GBPS = 32.0
# single-worker codec throughput over the INPUT f32 bytes of the
# delivery path — qpack8 quantize/pack + CRC32 (or memcpy + CRC32 on
# an f32 wire): vectorized quantize plus zlib.crc32 land around
# 2 GB/s/core on the CPU reference; deflate-class codecs are slower
# and belong in delivery_bench's heavy-sink scenario, not here.
CODEC_GBPS = 2.0
# the committed async-delivery configuration (RuntimeConfig
# .pipeline_depth / DeliveryConfig.encode_workers defaults the bench
# sweeps around)
DELIVERY_WORKERS = 4
PIPELINE_DEPTH = 4


def _load(rel, default=None):
    try:
        with open(os.path.join(R, rel)) as f:
            return json.load(f)
    except Exception:
        return default


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="also write the JSON artifact to PATH")
    ap.add_argument("--lod", action="store_true",
                    help="expand every committed LOD ladder rung into "
                         "its own scenario row (default: only the best "
                         "rung holding the artifact's PSNR floor)")
    args = ap.parse_args()

    from scenery_insitu_tpu.ops.composite import modeled_exchange_traffic
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    flagship = _load("bench_tpu_r4_512.json", {})
    base_ms = float(flagship.get("ms_per_frame", 419.43))

    occ = _load("occupancy_ab_r06_512.json", {})
    pyr_reduction = float(
        (occ.get("model") or {}).get("reduction_vs_off", {}).get("sim",
                                                                 2.43))
    reb = _load("rebalance_ab_r10_cpu.json", {})
    strag_even = float((reb.get("even") or {}).get("straggler_factor",
                                                   2.88))
    strag_plan = float((reb.get("occupancy") or {}).get(
        "straggler_factor", 1.85))

    slab = (GRID // RANKS, GRID, GRID)
    slab_vox = slab[0] * slab[1] * slab[2]

    def ms_hbm(nbytes):
        return nbytes / (HBM_GBPS * 1e9) * 1e3

    def ms_ici(nbytes):
        return nbytes / (ICI_GBPS * 1e9) * 1e3

    # one rank's share of the delivered frame: K supersegments x
    # (4 color + 2 depth) planes x NI x NJ f32 over RANKS column bands —
    # the payload _fetch hands the delivery plane every frame
    frame_bytes_per_rank = K * 6 * NI * NJ * 4 // RANKS

    def ms_host_delivery(workers=1):
        """Serial host cost of delivering one rank's frame share:
        device->host copy over PCIe plus the codec sweep (quantize/pack
        + CRC) over the input bytes, fanned across ``workers`` per-tile
        encode threads (PCIe is serial regardless — one link)."""
        copy = frame_bytes_per_rank / (PCIE_GBPS * 1e9) * 1e3
        codec = frame_bytes_per_rank / (CODEC_GBPS * workers * 1e9) * 1e3
        return copy + codec

    def row(lever, sim_fused, march_bytes_per_vox, march_scale,
            exchange, wire, ring_slots, schedule, note):
        sim_b = ps.modeled_sim_traffic(slab, SIM_STEPS, fused=sim_fused)
        march_b = slab_vox * march_bytes_per_vox / march_scale
        ex = modeled_exchange_traffic(
            RANKS, K, NJ, NI, k_out=K, mode=exchange,
            ring_slots=ring_slots, wire=wire, schedule=schedule,
            wave_tiles=WAVE_TILES)
        ici_b = (ex["ici_bytes_exposed_per_rank"]
                 if schedule == "waves" else ex["ici_bytes_per_rank"])
        stream_b = ex["stream_bytes_per_rank"]
        # every ladder row prices delivery SERIALLY (pipeline_depth=1,
        # the pre-PR-19 behavior): the host term sits fully on the
        # frame's critical path — the +async_delivery scenario row at
        # the end is where it comes off
        host = ms_host_delivery()
        total = (ms_hbm(sim_b + march_b + stream_b) + ms_ici(ici_b)
                 + host)
        return {
            "lever": lever,
            "config": {"sim_fused": sim_fused,
                       "render_dtype": ("bf16" if march_bytes_per_vox == 2
                                        else "f32"),
                       "occupancy_march_reduction": march_scale,
                       "exchange": exchange, "wire": wire,
                       "ring_slots": ring_slots, "schedule": schedule,
                       "pipeline_depth": 1, "delivery": "serial"},
            "bytes": {"sim_hbm": round(sim_b),
                      "march_hbm": round(march_b),
                      "composite_stream_hbm": round(stream_b),
                      "exchange_ici_exposed": round(ici_b),
                      "exchange_ici_total": ex["ici_bytes_per_rank"],
                      "delivery_host": frame_bytes_per_rank},
            "ms": {"sim": round(ms_hbm(sim_b), 2),
                   "march": round(ms_hbm(march_b), 2),
                   "composite_stream": round(ms_hbm(stream_b), 3),
                   "exchange_exposed": round(ms_ici(ici_b), 3),
                   "host_delivery": round(host, 2)},
            "modeled_ms_per_frame": round(total, 2),
            "note": note,
        }

    stack = [
        row("baseline_no_levers", False, 4, 1.0, "all_to_all", "f32", 0,
            "frame", "roll-formulation sim, f32 march, monolithic "
            "all_to_all frame — the pre-PR-1 schedule at 8 ranks"),
        row("+sim_fused_stencil", True, 4, 1.0, "all_to_all", "f32", 0,
            "frame", "time-fused Pallas stencil (PR 1): T steps per "
            "u,v round trip"),
        row("+bf16_march", True, 2, 1.0, "all_to_all", "f32", 0,
            "frame", "bf16 marched-volume copy (PR 1): march + halo "
            "bytes halve, f32 accumulation"),
        row("+simfused_occupancy_pyramid", True, 2, pyr_reduction,
            "all_to_all", "f32", 0, "frame",
            f"sim-fused value-range pyramid (PR 6): march reads / "
            f"{pyr_reduction} at the committed 512^3 live fraction"),
        row("+ring_exchange", True, 2, pyr_reduction, "ring", "f32", K,
            "frame", "ring ppermute chain with ring_slots=K (PR 4): "
            "merge working set N*K -> 2K"),
        row("+qpack8_wire", True, 2, pyr_reduction, "ring", "qpack8", K,
            "frame", "qpack8 supersegment wire (PR 5): ICI bytes / 4"),
        row("+tile_waves", True, 2, pyr_reduction, "ring", "qpack8", K,
            "waves", f"tile-wave pipeline (this PR): {WAVE_TILES} waves "
            f"hide {(WAVE_TILES - 1)}/{WAVE_TILES} of the exchange "
            "behind march compute — only the last wave's bytes stay on "
            "the critical path"),
    ]
    # ---- skewed-occupancy scenario (ISSUE 10): the ladder above
    # assumes balanced bands; these two rows re-price the final stack's
    # march term under a skewed scene — frame march = mean * straggler
    # (max over ranks is the barrier) — first with the even split, then
    # with the occupancy render plan. Sim stays balanced (the SIM
    # decomposition is always the even z-slab; only the RENDER bands
    # re-plan).
    last = stack[-1]
    for lever, strag, note in (
            ("skewed_scene_even_split", strag_even,
             f"SCENARIO row: same levers, but the scene banding makes "
             f"the even split's densest rank the frame barrier — march "
             f"term x{strag_even} (measured straggler factor, "
             f"rank_slab_bench CPU A/B)"),
            ("+render_rebalance", strag_plan,
             f"occupancy render plan (this PR): uneven z bands re-planned "
             f"from pyramid live fractions cut the straggler factor to "
             f"x{strag_plan} (measured; plan recompiles bounded by "
             f"quantum+hysteresis)")):
        ms = dict(last["ms"])
        ms["march"] = round(ms["march"] * strag, 2)
        total = sum(ms.values())
        stack.append({
            "lever": lever,
            "config": {**last["config"], "scenario": "skewed-occupancy",
                       "rebalance": ("occupancy" if "rebalance" in lever
                                     else "even"),
                       "straggler_factor": strag},
            "bytes": last["bytes"],
            "ms": ms,
            "modeled_ms_per_frame": round(total, 2),
            "note": note,
        })

    # ---- steady-state temporal-delta scenario (ISSUE 12): frames are
    # coherent, so the march term scales with WHAT CHANGED. Clean ranks
    # skip their march entirely (temporal_reuse="ranges"; the dirty
    # detector reads the sim-fused ranges already in the stack, so a
    # skipped frame pays no extra sweep). skip_frac is the measured
    # slow-scene tile fraction from the committed delta_ab artifact.
    dab = _load("delta_ab_r12_cpu.json", {})
    slow = (dab.get("scenes") or {}).get("slow", {})
    skip_frac = float((slow.get("march") or {}).get("skip_frac", 0.75))
    wire_ratio = float((slow.get("wire") or {}).get("payload_ratio",
                                                    0.25))
    # base on the balanced-scene full ladder row BY NAME — positional
    # indexing rots silently as scenario rows accrete around it
    full_stack = next(r for r in stack if r["lever"] == "+tile_waves")
    ms = dict(full_stack["ms"])
    ms["march"] = round(ms["march"] * (1.0 - skip_frac), 2)
    stack.append({
        "lever": "steady_scene_temporal_reuse",
        "config": {**full_stack["config"],
                   "scenario": "steady-state (slow-evolving)",
                   "temporal_reuse": "ranges",
                   "skip_frac": skip_frac},
        "bytes": full_stack["bytes"],
        "ms": ms,
        "modeled_ms_per_frame": round(sum(ms.values()), 2),
        "note": f"SCENARIO row: dirty-tile re-march (ISSUE 12) on a "
                f"slow-evolving scene — {skip_frac:.0%} of tiles reuse "
                f"last frame's fragments (measured slow-scene skip "
                f"fraction, delta_bench CPU A/B); the win scales with "
                f"run steadiness, not grid size",
    })

    # ---- multi-resolution LOD scenario (ISSUE 16): the march term
    # re-priced by the committed LOD ladder (lod_ab_r16_cpu.json). The
    # planner's level tuple cuts modeled march FLOPs ~2^-l per coarse
    # brick (the resample's second matmul keeps the FINE output grid);
    # the HBM read of a level-l brick shrinks faster (~8^-l, the pooled
    # copy), so dividing this model's march TRAFFIC term by the ladder's
    # FLOP reduction is conservative. Default row: the best rung holding
    # the artifact's 40 dB floor; --lod expands every rung.
    lab = _load("lod_ab_r16_cpu.json", {})
    lod_rungs = [r_ for r_ in (lab.get("ladder") or [])
                 if r_.get("error_px") is not None]
    if args.lod:
        picked = lod_rungs
    else:
        floor = float(lab.get("psnr_floor_db", 40.0))
        picked = [r_ for r_ in lod_rungs
                  if r_.get("flop_reduction", 0) == lab.get("value")
                  and (r_["psnr_db"] == "inf"
                       or float(r_["psnr_db"]) >= floor)][:1]
    full_stack = next(r for r in stack if r["lever"] == "+tile_waves")
    for rung in picked:
        red = float(rung.get("flop_reduction", 1.0))
        if red <= 1.0:
            continue
        ms = dict(full_stack["ms"])
        ms["march"] = round(ms["march"] / red, 2)
        hist = rung.get("level_hist", {})
        stack.append({
            "lever": f"+lod_march_err{rung['error_px']}px",
            "config": {**full_stack["config"],
                       "scenario": "multi-resolution LOD",
                       "lod_error_px": rung["error_px"],
                       "level_hist": hist,
                       "psnr_db": rung["psnr_db"]},
            "bytes": full_stack["bytes"],
            "ms": ms,
            "modeled_ms_per_frame": round(sum(ms.values()), 2),
            "note": f"SCENARIO row (ISSUE 16): per-brick LOD marching "
                    f"at error_px={rung['error_px']} — the committed "
                    f"ladder's level histogram {hist} cuts modeled "
                    f"march FLOPs x{red} at {rung['psnr_db']} dB "
                    f"(lod_ab_r16_cpu); march traffic shrinks at least "
                    f"as fast (coarse reads are ~8^-l of fine)",
        })

    # ---- multi-host scale-out scenario (ISSUE 14): the full-lever
    # stack per DOMAIN plus the inter-domain DCN hop of the two-level
    # composite (parallel/hier.py). Per host the DCN term is
    # modeled_dcn_traffic's ring bytes over the stated DCN bandwidth —
    # what a FLAT exchange would pay instead is every rank's whole
    # (n-1)-fragment exchange crossing DCN, priced alongside so the
    # two-level win is explicit. Grid scales weakly (fixed per-rank
    # volume: H hosts render an H-times-deeper volume at the same
    # per-frame cost + the DCN term).
    from scenery_insitu_tpu.parallel.hier import modeled_dcn_traffic

    def ms_dcn(nbytes):
        return nbytes / (DCN_GBPS * 1e9) * 1e3

    full_stack = next(r for r in stack if r["lever"] == "+tile_waves")
    flat_ex = modeled_exchange_traffic(RANKS, K, NJ, NI, k_out=K,
                                       mode="ring", ring_slots=K,
                                       wire="qpack8")
    for hosts in (2, 4):
        dcn = modeled_dcn_traffic(hosts, RANKS, K, NJ, NI,
                                  dcn_wire="qpack8", ring_slots=K)
        ms = dict(full_stack["ms"])
        # PER-HOST bytes over the PER-HOST link: all of a host's ranks
        # funnel through its shared DCN NIC (DCN_GBPS is per host)
        ms["dcn_exchange"] = round(
            ms_dcn(dcn["dcn_bytes_sent_per_host"]), 2)
        # a flat H*RANKS-rank exchange would push (H-1)/H of every
        # rank's fragment traffic across DCN instead — the same
        # per-host funnel prices all RANKS ranks' share
        flat_over_dcn = round(
            ms_dcn(flat_ex["ici_bytes_per_rank"] * RANKS
                   * (hosts - 1) / hosts), 2)
        stack.append({
            "lever": f"+hier_composite_{hosts}hosts",
            "config": {**full_stack["config"],
                       "scenario": "multi-host weak scale-out",
                       "num_hosts": hosts, "dcn_wire": "qpack8",
                       "grid": [GRID * hosts, GRID, GRID]},
            "bytes": {**full_stack["bytes"],
                      "dcn_per_rank": dcn["dcn_bytes_sent_per_rank"],
                      "dcn_per_host": dcn["dcn_bytes_sent_per_host"]},
            "ms": ms,
            "modeled_ms_per_frame": round(sum(ms.values()), 2),
            "flat_exchange_over_dcn_ms": flat_over_dcn,
            "note": f"SCENARIO row (ISSUE 14): {hosts} ICI domains over "
                    f"DCN at {DCN_GBPS} GB/s/host — the two-level "
                    f"composite ships the capped accumulator's column "
                    f"sub-blocks ({ms['dcn_exchange']} ms) where a flat "
                    f"{hosts * RANKS}-rank exchange would drag "
                    f"{flat_over_dcn} ms of fragment traffic across "
                    f"DCN; volume scales weakly to "
                    f"{GRID * hosts}x{GRID}x{GRID}",
        })

    # ---- async delivery plane (ISSUE 19): every row above prices the
    # host delivery path (device->host copy + codec + sinks) SERIALLY —
    # the pre-PR-19 critical path, where the render loop blocks on
    # np.asarray and then encodes inline. The delivery executor takes it
    # off that path: with pipeline_depth >= 2 the async fetch of frame
    # i-1 and the worker-tier encode overlap frame i's dispatch, so the
    # steady-state frame is max(device, host), not device + host — the
    # exposed host term is what max() leaves sticking out. encode
    # workers fan the codec sweep across cores; the PCIe copy stays
    # serial (one link). depth bounds how many frames of host jitter the
    # bounded queue absorbs before the block/drop_oldest policy engages;
    # the steady-state model below assumes the queue never saturates.
    full_stack = next(r for r in stack if r["lever"] == "+tile_waves")
    host_serial = ms_host_delivery()
    host_async = ms_host_delivery(DELIVERY_WORKERS)
    ms = dict(full_stack["ms"])
    device_total = sum(v for k, v in ms.items() if k != "host_delivery")
    exposed = max(0.0, host_async - device_total)
    ms["host_delivery"] = round(exposed, 2)
    stack.append({
        "lever": "+async_delivery",
        "config": {**full_stack["config"],
                   "pipeline_depth": PIPELINE_DEPTH,
                   "delivery": "async",
                   "encode_workers": DELIVERY_WORKERS},
        "bytes": full_stack["bytes"],
        "ms": ms,
        "host_delivery_serial_ms": round(host_serial, 2),
        "host_delivery_async_ms": round(host_async, 2),
        "host_delivery_hidden_ms": round(host_async - exposed, 2),
        "modeled_ms_per_frame": round(sum(ms.values()), 2),
        "note": f"async delivery plane (this PR): depth-{PIPELINE_DEPTH} "
                f"fetch pipeline + background delivery executor + "
                f"{DELIVERY_WORKERS} per-tile encode workers — host "
                f"work drops {round(host_serial, 2)} -> "
                f"{round(host_async, 2)} ms ({DELIVERY_WORKERS}x codec "
                f"fan-out) and overlaps the device frame, leaving "
                f"{round(exposed, 2)} ms exposed: steady-state frame = "
                f"max(device, host)",
    })

    b0 = stack[0]["modeled_ms_per_frame"]
    for r_ in stack:
        r_["speedup_vs_baseline"] = round(b0 / r_["modeled_ms_per_frame"],
                                          2)

    from scenery_insitu_tpu.ops.delta import modeled_delta_traffic

    delta_wire = modeled_delta_traffic(
        K, NJ, NI, skip_frac=skip_frac,
        p_frac=max(0.0, 1.0 - skip_frac - 1.0 / RANKS), iframe_period=8)
    delta_wire["measured_slow_scene_payload_ratio"] = wire_ratio
    delta_wire["source"] = ("benchmarks/results/delta_ab_r12_cpu.json "
                            "(slow scene; compressed record payloads — "
                            "headers are constant per message and "
                            "vanish at flagship tile sizes)")

    out = {
        "metric": f"modeled_projection_{RANKS:02d}rank_config2_{GRID}",
        "value": stack[-1]["modeled_ms_per_frame"],
        "unit": "ms/frame (modeled lower bound)",
        "baseline_ms_per_frame": base_ms,
        "baseline_artifact": "benchmarks/results/bench_tpu_r4_512.json",
        "modeled_stack_speedup": stack[-1]["speedup_vs_baseline"],
        "assumptions": {
            "ranks": RANKS, "grid": GRID, "sim_steps": SIM_STEPS,
            "intermediate": [NI, NJ], "k": K,
            "wave_tiles": WAVE_TILES,
            "marches_per_frame": 1,
            "hbm_gbps": HBM_GBPS, "ici_gbps_effective": ICI_GBPS,
            "dcn_gbps_effective_per_host": DCN_GBPS,
            "pcie_gbps": PCIE_GBPS,
            "delivery_codec_gbps_per_worker": CODEC_GBPS,
            "delivery_pipeline_depth": PIPELINE_DEPTH,
            "delivery_encode_workers": DELIVERY_WORKERS,
            "host_delivery_source":
                "benchmarks/results/delivery_ab_r19_cpu.json (codec "
                "throughput order; assumption: quantize+CRC sweeps the "
                "input f32 bytes once at ~2 GB/s/worker, PCIe copy is "
                "serial per host link)",
            "occupancy_march_reduction_source":
                "benchmarks/results/occupancy_ab_r06_512.json (sim row)",
            "straggler_factor_source":
                "benchmarks/results/rebalance_ab_r10_cpu.json (measured "
                "CPU 96^3 skewed scene; assumption: the skew transfers "
                "to 512^3 banded Gray-Scott, PR-6 live-cell 0.41)",
            "excluded": "compute time, kernel launch/dispatch, "
                        "fold-state traffic beyond the composite "
                        "stream model — this is a TRAFFIC lower bound; "
                        "the flagship runs at ~8.4% of HBM peak, so "
                        "read the RELATIVE deltas, not the absolute ms "
                        "(host delivery joined the model in PR 19: "
                        "bytes x codec throughput + PCIe copy, "
                        "overlapped per the +async_delivery row)",
            "note_sim_attribution": "the '~290 of 419 ms is sim' split "
                                    "(ROADMAP item 1) is still "
                                    "hardware-unconfirmed; this model "
                                    "keeps sim and render terms "
                                    "separate so either outcome maps "
                                    "onto a subset of rows",
            "delta_skip_frac_source":
                "benchmarks/results/delta_ab_r12_cpu.json (slow scene; "
                "assumption: steady in-situ runs look like the "
                "slow-evolving scene most frames)",
        },
        "stack": stack,
        "delta_wire_steady_state": delta_wire,
    }
    print(json.dumps(out))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
