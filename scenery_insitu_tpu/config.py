"""Unified configuration system.

The reference scattered configuration across three tiers — JVM system
properties (``-DVolumeBenchmark.*``), fields poked in by C++ through JNI
before init, and hardcoded Kotlin vals / shader ``#define`` feature flags
(SURVEY.md §5 "Config / flag system"; reference DistributedVolumes.kt:88-131,
VolumeFromFileExample.kt:69-82). Here everything lives in one tree of frozen
dataclasses, overridable from environment variables, a JSON file, or
``key.path=value`` strings, in that precedence order.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Optional, Tuple

ENV_PREFIX = "SITPU_"

# The benched in-plane occupancy tile count (docs/PERF.md "Empty-space
# skipping") — the ONE place the default lives: slicer.make_spec's auto
# resolution (occupancy_vtiles == -1 on TPU),
# models.pipelines.resolve_occupancy_cfg's pyramid/sim modes, and
# occupancy.default_bricks' y-brick cap all read it, so re-benching the
# default can never leave the sites disagreeing.
OCCUPANCY_VTILES_DEFAULT = 16


@dataclass(frozen=True)
class RenderConfig:
    """Plain raycast / framebuffer settings (≅ VolumeRaycaster.comp knobs)."""

    width: int = 1280
    height: int = 720
    max_steps: int = 512           # samples along each ray
    step_scale: float = 1.0        # multiplies the nominal 1-voxel step
    gamma: float = 2.2             # display gamma applied at host boundary
    background: Tuple[float, float, float, float] = (0.0, 0.0, 0.0, 0.0)
    early_exit_alpha: float = 0.999  # ≅ AccumulatePlainImage.comp early exit
    # Ambient occlusion (off by default, like the reference's inactive
    # scaffolding ComputeRaycast.comp:147-191): 0 disables; > 0 darkens
    # samples by the blurred-opacity occlusion field (ops/ao.py).
    ao_strength: float = 0.0
    ao_radius: int = 4               # occlusion neighborhood radius, voxels


@dataclass(frozen=True)
class VDIConfig:
    """Supersegment (VDI) generation settings (≅ VDIGenerator.comp knobs)."""

    max_supersegments: int = 20     # K; reference default 20 (DistributedVolumes.kt:99)
    # Fixed color-difference threshold for closing a supersegment. The
    # reference adaptively binary-searches a per-pixel threshold so each ray
    # emits between K*(1-delta) and K segments (VDIGenerator.comp:380-529);
    # adaptive=True enables the same behavior via a bounded search.
    threshold: float = 0.0
    adaptive: bool = True
    adaptive_iters: int = 6         # binary search iterations when adaptive
    adaptive_delta: float = 0.15    # accept counts in [K*(1-delta), K]
    # "search": adaptive_iters counting marches (binary search).
    # "histogram": ONE counting march evaluating histogram_bins candidate
    # thresholds simultaneously (possible because the break metric compares
    # consecutive items — see ops/supersegments.py) then pick per pixel.
    # "temporal": NO counting march — the per-pixel threshold is carried
    # across frames and nudged by a feedback controller from the true
    # segment count observed during the write march itself (see
    # slicer.generate_vdi_mxu_temporal). One march per frame; exploits the
    # frame-to-frame coherence of an in-situ loop.
    adaptive_mode: str = "search"
    histogram_bins: int = 16
    # temporal mode: per-frame outward decay of the controller's bisection
    # bracket (1.0 = frozen bracket, never re-adapts; smaller = tracks
    # faster-changing scenes at the cost of steady-state wobble), and the
    # clamp range the controller moves inside (thr_max matches the
    # histogram candidate ceiling, ss.threshold_candidates).
    temporal_track: float = 0.9
    thr_min: float = 1e-3
    thr_max: float = 2.0

    def __post_init__(self):
        if self.adaptive_mode not in ("search", "histogram", "temporal"):
            raise ValueError(
                f"adaptive_mode must be 'search', 'histogram' or "
                f"'temporal', got {self.adaptive_mode!r}")
    # Occupancy grid (≅ OctreeCells r32ui [W/8, H/8, K]): cell size in pixels.
    occupancy_cell: int = 8


@dataclass(frozen=True)
class SliceMarchConfig:
    """MXU slice-march raycaster settings (ops/slicer.py — the TPU-native
    engine; the gather-path raycaster in ops/raycast.py is the portable
    reference implementation)."""

    # Render engine: "mxu" = slice march (fast on TPU), "gather" = per-ray
    # trilinear gathers (reference path), "auto" = mxu on TPU else gather
    # (resolved by ops.slicer.resolve_engine; consumed by the pipelines'
    # `engine=` argument and the session loop).
    engine: str = "auto"
    # Intermediate grid resolution multiplier over the in-plane voxel count.
    scale: float = 1.25
    # Slices folded per scan step (bounds carry round-trips through HBM).
    chunk: int = 16
    # Resampling matmul operand dtype: "bf16" (MXU-native) or "f32".
    matmul_dtype: str = "bf16"
    # Storage dtype of the MARCHED volume copy: "bf16" halves the volume
    # bytes every march (and the distributed halo-exchange bytes) — the
    # resampling matmuls were casting operands to bf16 anyway
    # (matmul_dtype) and all accumulation stays f32, so the render-side
    # precision loss is one storage rounding of the field. The SIM state
    # is never touched (its ~1e-3 per-step increments need f32 — see
    # models/pipelines.py). "f32" = render the sim field as-is.
    render_dtype: str = "f32"
    # Minimum eye-depth ratio; slices closer to the eye plane are dropped.
    s_floor: float = 1e-3
    # Empty-space skipping: skip slice chunks whose value range maps to
    # zero alpha (≅ the reference's OctreeCells occupancy acceleration,
    # VDIGenerator.comp:232-254 — here consumed, per-frame, by the march).
    skip_empty: bool = True
    # In-plane occupancy tiles: 0 = chunk-granular skipping only; N > 0
    # also splits each slice plane into N row tiles and skips the
    # resampling matmuls + TF for output row blocks whose support is
    # provably empty (see slicer.AxisSpec.vtiles). Adds N lax.cond
    # branches per chunk — worth it on sparse fields, overhead on dense.
    # -1 (the default) resolves per backend in slicer.make_spec: 16 on
    # TPU (the benched winner on sparse Gray-Scott — see
    # benchmarks/occupancy_bench.py and docs/PERF.md "Empty-space
    # skipping"), 0 elsewhere (the branches are pure overhead on CPU).
    # A request larger than the geometry supports is clamped and the
    # reduction recorded on the fallback ledger (occupancy.vtiles_clamp).
    occupancy_vtiles: int = -1
    # Supersegment-fold schedule for the VDI marches (docs/SEG_FOLD.md):
    #   "xla"        sequential ss.push machine in a lax.scan (every slice
    #                round-trips the [K] state through HBM — the portable
    #                reference schedule, fastest on CPU, and what every
    #                chipbench cell is compared against);
    #   "pallas_fused" the segmented-scan fold on VMEM pixel strips
    #                (ops/pallas_seg.py), shading in the kernel: TF +
    #                opacity correction + depths move into the fold (≅ the
    #                reference's single-kernel generation,
    #                VDIGenerator.comp + AccumulateVDI.comp); the march
    #                hands over its one-channel value plane and the
    #                shaded rgba chunk never crosses HBM;
    #   "pallas_seg" the same kernel fed the shaded rgba chunk;
    #   "auto"       pallas_fused on TPU (since PR 46), xla elsewhere.
    # pallas_fused bakes the transfer function's knots into the kernel,
    # so per march it needs a scalar volume and a concrete TF: a
    # pre-shaded RGBA volume (the novel-view proxy) and a TF that is
    # traced get pallas_seg's shaded feed of the same kernel instead
    # (ops/slicer.fold_schedule — chosen from what the march is given,
    # whether the value came from "auto" or was named). Any other name
    # is refused by slicer.make_spec.
    fold: str = "auto"

    def __post_init__(self):
        if self.matmul_dtype not in ("bf16", "f32"):
            raise ValueError(f"matmul_dtype must be 'bf16' or 'f32', "
                             f"got {self.matmul_dtype!r}")
        if self.render_dtype not in ("bf16", "f32"):
            raise ValueError(f"render_dtype must be 'bf16' or 'f32', "
                             f"got {self.render_dtype!r}")


@dataclass(frozen=True)
class CompositeConfig:
    """Sort-last VDI compositing (≅ VDICompositor.comp)."""

    max_output_supersegments: int = 20
    # Re-segmentation threshold search on the composited ray (same meaning as
    # VDIConfig.threshold/adaptive).
    adaptive: bool = True
    adaptive_iters: int = 6
    # Merge-fold schedule: "xla" = lax.scan over slots; "pallas" = fused
    # pixel-tile kernel (ops.pallas_composite); "auto" = pallas on TPU.
    backend: str = "auto"
    # Sort-last exchange schedule (docs/PERF.md "Exchange modes"):
    #   "all_to_all"  one blocking lax.all_to_all of all column fragments,
    #                 then an N·K-wide sort-merge per pixel (≅ the
    #                 reference's distributeVDIs MPI all-to-all shape);
    #   "ring"        n-1 lax.ppermute hops around the ICI ring, each
    #                 incoming K-fragment merged into a per-rank sorted
    #                 accumulator by the pairwise ordered merge
    #                 (ops.composite.merge_vdis_pairwise) — no N·K bitonic
    #                 sort, and XLA overlaps the next hop with the current
    #                 merge. Single-rank meshes fall back to all_to_all
    #                 (both are the identity there).
    exchange: str = "all_to_all"
    # Ring accumulator cap, in supersegment slots per pixel. 0 = lossless:
    # the accumulator grows to N·K slots and ring output matches the
    # all_to_all path exactly. > 0 bounds the live per-pixel working set
    # to ring_slots + K slots (e.g. 2K at ring_slots=K) by dropping the
    # FARTHEST segments of overfull pixels at every merge — bounded
    # memory, approximate on pixels that overflow the cap.
    ring_slots: int = 0
    # Supersegment wire format of the sort-last exchange (docs/PERF.md
    # "Wire formats"; ops/wire.py):
    #   "f32"     6 f32 lanes, 24 B/slot — bit-exact, the pre-wire path;
    #   "bf16"    color+depth cast to bfloat16, 12 B/slot (2×), lossy;
    #   "qpack8"  RGBA → u8 unorm in a u32 lane + the depth pair → u8
    #             each (per-fragment [near, far] normalization, sentinel
    #             0xFFFF round-trips +inf empty slots exactly) in a u16
    #             lane, 6 B/slot (4×), lossy.
    # Encode runs before the collective and decode after it in BOTH
    # exchange schedules, so ICI bytes shrink either way; the composite
    # itself always runs in f32. Quantized modes are lossy by contract
    # (tests hold them to PSNR floors).
    wire: str = "f32"
    # Frame schedule (docs/PERF.md "Tile waves"):
    #   "frame"  the whole frame is one march → one exchange → one
    #            composite (the monolithic SPMD chain — exchange time
    #            adds serially to march time);
    #   "waves"  the column block (tile) is the unit of march, exchange,
    #            composite and delivery: each rank marches one
    #            column-block wave at a time and, while wave w+1
    #            marches, wave w's fragments circulate and fold
    #            (software-pipelined lax.scan with a double-buffered
    #            fragment slot — XLA overlaps the collective with the
    #            next wave's march inside one compiled step). Lossless
    #            waves are parity-exact with the frame schedule; the
    #            session can deliver finished column blocks before the
    #            frame closes. Single-rank meshes degrade to "frame"
    #            (ledgered) — there is nothing to overlap.
    schedule: str = "frame"
    # Column-block waves per rank-owned block under schedule="waves"
    # (the frame is n_ranks * wave_tiles tiles). The intermediate width
    # must divide by ranks * wave_tiles. More waves = finer overlap and
    # lower tile-delivery latency, but each wave re-reads the volume's
    # live chunks (march state is per-wave) — 2-8 is the useful range.
    wave_tiles: int = 4
    # Per-rank supersegment budget of the sort-last fold (docs/PERF.md
    # "Empty-space skipping"):
    #   "static"     every rank's adaptive threshold targets the full K
    #                (the pre-ISSUE-6 behavior, bit-exact);
    #   "occupancy"  rank r targets its share of the mesh-wide budget
    #                N*K, proportional to its occupancy-pyramid live
    #                fraction and clamped to [k_budget_min, K]
    #                (ops/occupancy.k_budget_target). Array SHAPES stay
    #                at K on every rank (one SPMD program): sparse slabs
    #                emit coarser VDIs whose unused slots stay +inf
    #                (near-free on a quantized wire), dense slabs keep
    #                full fidelity — a quality/work re-balance, not a
    #                memory one.
    k_budget: str = "static"
    k_budget_min: int = 4      # floor of the occupancy budget, slots
    # Render rebalancing (docs/PERF.md "Render rebalancing"): the SIM
    # sharding always stays the even 1-D z-slab (halo exchange, sim
    # state untouched), but the RENDER decomposition can differ:
    #   "even"       rank r marches slab [r*D/n, (r+1)*D/n) — the
    #                pre-ISSUE-10 decomposition (note: the gather
    #                engine's SAMPLE LADDER now derives from the global
    #                box under every mode, matching single-device
    #                sample positions — docs/PERF.md "Render
    #                rebalancing"; the MXU engine always marched the
    #                global slice ladder and is bit-exact vs pre-10);
    #   "occupancy"  rank r marches a PLANNED contiguous z-slice band
    #                (ops/occupancy.slice_plan — greedy prefix-sum
    #                equalization of the occupancy pyramid's per-z live
    #                work), materialized from the even shards by
    #                parallel/mesh.reslab_z with the same seam-exact
    #                1-voxel halo contract as halo_exchange_z. Bands pad
    #                to the plan's max depth (static SPMD shapes; padded
    #                slices are masked and the pyramid admits zero for
    #                them, so skipping eats the padding). The plan is
    #                computed host-side between frames from fetched live
    #                fractions; a plan CHANGE recompiles the step — the
    #                quantum + hysteresis below bound how often.
    #   "bricks"     the render decomposition is a NON-CONVEX brick map
    #                (parallel/bricks.BrickMap; docs/SCENARIOS.md): the
    #                global z extent splits into rebalance_bricks equal
    #                bricks and the session re-plans by brick-STEALING —
    #                greedy per-brick live-work equalization moving at
    #                most rebalance_max_moves bricks per replan
    #                (parallel.bricks.steal_plan). Each rank marches its
    #                brick set through per-brick ownership intervals;
    #                the sort-last composite is invariant to which rank
    #                owns which brick (tests/test_bricks.py), and the
    #                even-convex map short-circuits bitwise to the
    #                pre-brick path.
    rebalance: str = "even"
    # Temporal fragment reuse (docs/PERF.md "Temporal deltas"):
    #   "off"     every frame re-marches every rank (the pre-ISSUE-12
    #             behavior — the off path inserts zero ops);
    #   "ranges"  each rank carries its previous marched VDI fragment
    #             plus a dirty signature — the occupancy pyramid's
    #             per-cell [lo, hi] value ranges (already computed every
    #             frame, PR 6) concatenated with the camera pose — and
    #             SKIPS the march (lax.cond; the matmul waves never
    #             issue) when the signature moved by at most
    #             delta.range_tol and the camera is bit-unchanged. The
    #             exchange + composite still run every frame (other
    #             ranks may be dirty). MXU VDI steps only; the gather /
    #             hybrid / plain builders ledger the knob inert
    #             (delta.reuse). range_tol = 0 with a static camera is
    #             bit-exact vs recompute; a field change that preserves
    #             every per-brick [lo, hi] exactly is invisible to the
    #             detector — the documented contract of a range-based
    #             dirty predicate.
    temporal_reuse: str = "off"
    # Frames between host-side re-plans under rebalance="occupancy"
    # (runtime/session.py fetches the z live profile and re-plans every
    # this many frames; each ADOPTED plan recompiles the step).
    rebalance_period: int = 8
    # Plan stability: a fresh plan is adopted only when some band
    # boundary moves by more than this fraction of the even slab depth
    # (D/n) — below it the previous plan is kept and nothing recompiles.
    rebalance_hysteresis: float = 0.25
    # Floor on any rank's planned band depth, slices. Must cover the
    # deepest halo the step needs (1 for trilinear seams; ao_radius + 1
    # for AO pre-shading) — parallel/mesh.reslab_z validates this and
    # names the offending rank.
    rebalance_min_depth: int = 4
    # Band boundaries snap to multiples of this many slices — coarser
    # quanta mean fewer distinct plans, fewer recompiles.
    rebalance_quantum: int = 4
    # rebalance="bricks": brick count of the regular z brick grid. 0 =
    # auto (parallel.bricks.auto_nbricks: the largest divisor of the
    # depth at most 4 * n_ranks — fine enough to steal by, coarse
    # enough that per-brick march overhead stays small).
    rebalance_bricks: int = 0
    # rebalance="bricks": bricks allowed to change owner per replan.
    # Caps both the recompile delta and the extra reslab routing one
    # replan can introduce (each move is one more distinct shard offset
    # the ppermute rotation set may need).
    rebalance_max_moves: int = 2

    def __post_init__(self):
        if self.exchange not in ("all_to_all", "ring"):
            raise ValueError(f"exchange must be 'all_to_all' or 'ring', "
                             f"got {self.exchange!r}")
        if self.ring_slots < 0:
            raise ValueError(f"ring_slots must be >= 0 (0 = lossless), "
                             f"got {self.ring_slots}")
        if self.wire not in ("f32", "bf16", "qpack8"):
            raise ValueError(f"wire must be 'f32', 'bf16' or 'qpack8', "
                             f"got {self.wire!r}")
        if self.schedule not in ("frame", "waves"):
            raise ValueError(f"schedule must be 'frame' or 'waves', "
                             f"got {self.schedule!r}")
        if self.wave_tiles < 1:
            raise ValueError(f"wave_tiles must be >= 1, "
                             f"got {self.wave_tiles}")
        if self.k_budget not in ("static", "occupancy"):
            raise ValueError(f"k_budget must be 'static' or 'occupancy', "
                             f"got {self.k_budget!r}")
        if self.k_budget_min < 1:
            raise ValueError(f"k_budget_min must be >= 1, "
                             f"got {self.k_budget_min}")
        if self.rebalance not in ("even", "occupancy", "bricks"):
            raise ValueError(f"rebalance must be 'even', 'occupancy' or "
                             f"'bricks', got {self.rebalance!r}")
        if self.temporal_reuse not in ("off", "ranges"):
            raise ValueError(f"temporal_reuse must be 'off' or 'ranges', "
                             f"got {self.temporal_reuse!r}")
        if self.rebalance_period < 1:
            raise ValueError(f"rebalance_period must be >= 1, "
                             f"got {self.rebalance_period}")
        if self.rebalance_hysteresis < 0.0:
            raise ValueError(f"rebalance_hysteresis must be >= 0, "
                             f"got {self.rebalance_hysteresis}")
        if self.rebalance_min_depth < 1:
            raise ValueError(f"rebalance_min_depth must be >= 1, "
                             f"got {self.rebalance_min_depth}")
        if self.rebalance_quantum < 1:
            raise ValueError(f"rebalance_quantum must be >= 1, "
                             f"got {self.rebalance_quantum}")
        if self.rebalance_bricks < 0:
            raise ValueError(f"rebalance_bricks must be >= 0 (0 = auto), "
                             f"got {self.rebalance_bricks}")
        if self.rebalance_max_moves < 1:
            raise ValueError(f"rebalance_max_moves must be >= 1, "
                             f"got {self.rebalance_max_moves}")


@dataclass(frozen=True)
class LODConfig:
    """Multi-resolution brick marching (docs/PERF.md "LOD marching";
    docs/SCENARIOS.md "LOD levels").

    Rides the brick render decomposition (``composite.rebalance ==
    "bricks"``): each brick of the map carries a refinement ``level``
    (parallel/bricks.BrickMap.level) chosen host-side at every replan
    (parallel/lod.py) from the occupancy profile, a conservative
    screen-space error bound from the camera, and the transfer-function
    straddle gate. A level-``l`` brick marches a ``2^l``-downsampled
    copy (average-pooled on device at materialization,
    parallel/mesh.reslab_bricks_lod) through the same `slice_march`
    machinery at ``step_scale = 2^-l``; its supersegments composite
    unchanged. An all-level-0 map is BITWISE the pre-LOD brick path.
    Enabled without a brick map, the knob is inert and ledgered
    (lod.inert). The MXU VDI march is the only coarse consumer — the
    gather engine samples fine and ledgers the map's levels inert
    (lod.engine)."""

    # Master switch: select per-brick refinement levels at every brick
    # replan. False = every brick stays level 0 (the flat PR-15 map).
    enabled: bool = False
    # Deepest refinement level a brick may coarsen to (downsample factor
    # 2^max_level). The planner additionally caps levels so 2^l divides
    # the brick depth and both in-plane extents.
    max_level: int = 2
    # Screen-space error budget, intermediate-grid pixels: a brick may
    # coarsen to level l only while its projected coarse-voxel footprint
    # 2^l * voxel * focal_px / eye_distance stays at or below this.
    error_px: float = 1.0
    # Coarsen provably-empty bricks (occupancy live fraction at or below
    # live_eps) to the admissible cap regardless of the screen bound —
    # air is marched at the coarsest resolution the geometry allows.
    coarsen_empty: bool = True
    live_eps: float = 1e-3
    # Opacity-edge sensitivity of the TF-straddle gate: an alpha knot
    # with |slope delta| > tf_edge_eps strictly inside a brick's value
    # range pins that brick at level 0 (never coarsened — downsampling
    # across a TF edge aliases).
    tf_edge_eps: float = 1e-4
    # Coarsening deadband: a brick coarsens (level increases, one level
    # per replan) only when the coarser footprint also clears
    # error_px * (1 - hysteresis) — refinement is immediate, coarsening
    # is damped so a camera at the threshold cannot oscillate the level
    # tuple (each adopted tuple recompiles the step).
    hysteresis: float = 0.2

    def __post_init__(self):
        if not 0 <= self.max_level <= 8:
            raise ValueError(f"max_level must be in [0, 8], "
                             f"got {self.max_level}")
        if self.error_px <= 0.0:
            raise ValueError(f"error_px must be > 0, "
                             f"got {self.error_px}")
        if self.live_eps < 0.0:
            raise ValueError(f"live_eps must be >= 0, "
                             f"got {self.live_eps}")
        if self.tf_edge_eps < 0.0:
            raise ValueError(f"tf_edge_eps must be >= 0, "
                             f"got {self.tf_edge_eps}")
        if not 0.0 <= self.hysteresis < 1.0:
            raise ValueError(f"hysteresis must be in [0, 1), "
                             f"got {self.hysteresis}")


@dataclass(frozen=True)
class TopologyConfig:
    """Mesh topology — the scale-out plane (docs/MULTIHOST.md).

    Every collective in the single-domain pipeline assumes one flat ICI
    domain. This block makes the ICI/DCN split first-class: ``num_hosts``
    ICI domains ("hosts" — one per pod slice / node) of ``domain_size``
    devices each. With ``num_hosts > 1`` the compositing mesh becomes a
    2-D ``(hosts, ranks)`` mesh (parallel/topology.py) and the sort-last
    composite runs in TWO levels: intra-domain ring/waves over ICI
    exactly as today, then an inter-domain exchange of already-partially-
    composited column blocks over DCN (parallel/hier.py), resegmented
    ONCE so a hierarchical frame matches the flat composite
    (tests/test_topology.py). ``num_hosts == 1`` (the default) is
    BITWISE the flat single-level path."""

    # Devices per ICI domain. 0 = auto: all devices / num_hosts (the
    # device count must split evenly — parallel/topology.py validates).
    domain_size: int = 0
    # ICI domains (hosts). 1 = the flat single-domain path, bitwise
    # identical to the pre-topology pipeline.
    num_hosts: int = 1
    # Mesh axis name of the inter-domain (DCN) axis; the intra-domain
    # axis reuses MeshConfig.axis_name ("ranks").
    hosts_axis: str = "hosts"
    # Wire format of the inter-domain (DCN) hop (docs/PERF.md "Wire
    # formats" — same codec family as CompositeConfig.wire, applied to
    # the partially-composited column blocks that cross DCN): "f32" is
    # bit-exact (the parity contract); "qpack8" is the recommended
    # production setting on bandwidth-starved DCN (4x fewer bytes, PSNR
    # floors tested). The intra-domain ICI hop keeps composite.wire.
    dcn_wire: str = "f32"

    def __post_init__(self):
        if self.domain_size < 0:
            raise ValueError(f"domain_size must be >= 0 (0 = auto), "
                             f"got {self.domain_size}")
        if self.num_hosts < 1:
            raise ValueError(f"num_hosts must be >= 1, "
                             f"got {self.num_hosts}")
        if not self.hosts_axis:
            raise ValueError("hosts_axis must be a non-empty axis name")
        if self.dcn_wire not in ("f32", "bf16", "qpack8"):
            raise ValueError(f"dcn_wire must be 'f32', 'bf16' or "
                             f"'qpack8', got {self.dcn_wire!r}")


@dataclass(frozen=True)
class MeshConfig:
    """Device-mesh / parallelism settings (replaces rank/commSize fields the
    reference received from C++: DistributedVolumes.kt:103-117).

    Domain decomposition is 1-D over z (the pipeline's halo exchange and
    ownership masks are built for z-slabs); unevenly-sized and multi-grid
    per-rank layouts go through core.scene.MultiGridScene instead of a
    decomposition knob here."""

    # Number of devices participating in sort-last compositing; 0 = all.
    num_devices: int = 0
    axis_name: str = "ranks"


@dataclass(frozen=True)
class SimConfig:
    """Built-in simulation settings (standalone mode; the reference could not
    run standalone — README.md:16 — this framework can)."""

    # gray_scott | vortex | lennard_jones | sho | hybrid (vortex + tracers)
    kind: str = "gray_scott"
    grid: Tuple[int, int, int] = (128, 128, 128)
    steps_per_frame: int = 10
    dt: float = 1.0
    # Gray-Scott parameters ("lambda" regime — stable labyrinths in 3D;
    # the classic 2D soliton params 0.0545/0.062 die out in 3D)
    gs_f: float = 0.037
    gs_k: float = 0.060
    gs_du: float = 0.16
    gs_dv: float = 0.08
    num_particles: int = 100_000
    # Sphere radius for the particle/hybrid render paths: world units for
    # lennard_jones/sho, voxel units for hybrid tracers.
    particle_radius: float = 0.35
    # Advance gray_scott through the time-fused Pallas stencil on TPU
    # (sim/pallas_stencil.py — T steps per volume round trip instead of
    # one); on a multi-rank mesh every rank runs it on its shard of the
    # z-sharded state and takes the z halos from its ring neighbours.
    # Off-TPU and on a grid (or shard) no tile of the kernel fits the XLA
    # roll formulation runs instead, and the ledger says so
    # (sim.fused_stencil). False pins the XLA roll formulation — the
    # sim-fusion lever's A/B switch.
    fused_stencil: bool = True


@dataclass(frozen=True)
class RuntimeConfig:
    """Session-loop, dump and benchmark flags (≅ the hardcoded vals
    generateVDIs/saveFinal/benchmarking, DistributedVolumes.kt:88-92)."""

    generate_vdis: bool = True
    save_final: bool = False
    dump_dir: str = "dumps"
    benchmark: bool = False
    benchmark_frames: int = 100
    stats_window: int = 100         # frames between timer-stat dumps
    # Names the transfer function (core/transfer.for_dataset). A name of
    # the raw-file table (core/volume.DATASET_DIMS_XYZ, e.g. "kingsnake")
    # also makes the session render that file instead of a simulation:
    # <data_dir>/<dataset>.raw, loaded in z-slabs at the file's dtype and
    # held resident (runtime/session.DatasetVolumeAdapter)
    dataset: str = "procedural"
    data_dir: str = "."
    # Device->host pipeline depth of the frame loop (docs/PERF.md "Async
    # delivery"): how many dispatched frames may have their host copies
    # in flight before the loop blocks on the oldest. 1 = the historical
    # one-deep overlap (bitwise the pre-async behavior); deeper values
    # only help when host delivery is slower than device compute AND the
    # background delivery executor is absorbing the payloads — each
    # extra slot pins roughly one more frame of host-copy memory.
    pipeline_depth: int = 1

    def __post_init__(self):
        if self.pipeline_depth < 1:
            raise ValueError(f"runtime.pipeline_depth must be >= 1, "
                             f"got {self.pipeline_depth}")


@dataclass(frozen=True)
class ObsConfig:
    """Observability / telemetry (scenery_insitu_tpu/obs — structured
    spans, device counters, the fallback ledger; docs/OBSERVABILITY.md).

    Disabled (the default) the recorder is a no-op shell around the
    per-phase Timers: no events are recorded and no files are written —
    the PR-1 hot path. Enabled, every session phase becomes a structured
    span (frame/rank attribution) and ``Session.run`` flushes the
    configured sinks at the end of the loop."""

    enabled: bool = False
    # Chrome-trace / Perfetto JSON ("" = don't write). Open the file at
    # ui.perfetto.dev; complements the device-side profiler dir that
    # ``Session.run(profile_dir=...)`` captures.
    trace_path: str = ""
    # JSONL event stream + final summary line ("" = don't write).
    metrics_path: str = ""
    # Timer window for the embedded Timers (0 = runtime.stats_window).
    window: int = 0
    # Fleet telemetry side-channel (obs/collector.py, docs/
    # OBSERVABILITY.md "Fleet tracing"): the Collector's event SUB
    # endpoint to PUB batched obs events/counters/ledger deltas to
    # ("" = no side-channel). Loss-tolerant by construction: every send
    # is non-blocking, a dead or slow collector costs drops (ledgered
    # `obs.collector`), never a stalled render loop.
    collector: str = ""
    # The Collector's heartbeat ROUTER endpoint ("" = no clock-offset
    # pings; batches then align on wall clocks alone).
    collector_hb: str = ""
    # Seconds between telemetry batch publishes (and heartbeat pings)
    # on the session's frame loop.
    collector_interval_s: float = 0.25

    def __post_init__(self):
        if self.collector_interval_s <= 0:
            raise ValueError(f"collector_interval_s must be > 0, "
                             f"got {self.collector_interval_s}")


@dataclass(frozen=True)
class SLOConfig:
    """Live service-level objectives (obs/slo.py, docs/OBSERVABILITY.md
    "SLO engine"): rolling-window p50/p99 estimators over frame latency,
    serve staleness and camera-to-pixel latency, checked ON the run.

    A budget of 0 disables that gate (the estimator still tracks the
    metric for ``snapshot()``). A breach mints a typed ``slo_breach``
    event, bumps the ``slo_breaches`` counter and lands one deduped
    ``slo.breach`` ledger row — machine-readable health for the relay
    tree's autoscale signal (ROADMAP item 2) and the elastic fleet's
    frames-to-recover gate (item 5)."""

    enabled: bool = False
    # Rolling window, in samples per metric (p50/p99 are computed over
    # at most this many most-recent observations — O(window) memory).
    window: int = 128
    # Breach checks need at least this many samples first (a p99 of 3
    # frames is noise, not a signal).
    min_samples: int = 16
    # End-to-end frame latency budget, ms (sim -> delivered payload,
    # the session's per-frame wall clock). 0 = no gate.
    frame_p99_ms: float = 0.0
    # Serve staleness budget: answers rendered from a VDI more than
    # this many frames behind the stream head breach. 0 = no gate.
    staleness_p99_frames: float = 0.0
    # Camera-to-pixel budget, ms (camera request received -> answer
    # bytes handed to the socket, measured on the serve tier). 0 = no
    # gate.
    camera_to_pixel_p99_ms: float = 0.0
    # Per-phase budget, ms, applied to every recorded session phase
    # span (sim/dispatch/fetch/sinks...). 0 = no gate.
    phase_p99_ms: float = 0.0
    # Delivery lag budget, ms: dispatch-to-delivered latency of a frame
    # through the async delivery executor (runtime/delivery.py,
    # docs/PERF.md "Async delivery") — how far behind the render loop
    # the background sink tier is running. 0 = no gate.
    delivery_lag_p99_ms: float = 0.0

    def __post_init__(self):
        if self.window < 8:
            raise ValueError(f"slo.window must be >= 8, got {self.window}")
        if self.min_samples < 1 or self.min_samples > self.window:
            raise ValueError(f"need 1 <= min_samples <= window, got "
                             f"{self.min_samples} (window {self.window})")
        for k in ("frame_p99_ms", "staleness_p99_frames",
                  "camera_to_pixel_p99_ms", "phase_p99_ms",
                  "delivery_lag_p99_ms"):
            if getattr(self, k) < 0:
                raise ValueError(f"slo.{k} must be >= 0 (0 = no gate), "
                                 f"got {getattr(self, k)}")


@dataclass(frozen=True)
class FaultConfig:
    """Self-healing delivery-plane knobs (docs/ROBUSTNESS.md): liveness
    deadlines, reconnect backoff, sink quarantine and the tile-frame
    assembler window. Every seam where bytes cross a failure domain
    (zmq VDI/steering streams, the UDP video stream, the shm ingest
    ring, in-process sinks) reads its tolerance from here."""

    # Publishers emit a lightweight heartbeat when idle this long, so
    # subscribers can tell "no frames" from "dead peer"
    # (VDIPublisher.maybe_heartbeat / SteeringPublisher.heartbeat).
    heartbeat_period_s: float = 2.0
    # A subscriber/endpoint that has seen NO traffic (frames, tiles or
    # heartbeats) for this long considers the peer lost and reconnects
    # with bounded exponential backoff (utils/retry.py). <= 0 disables
    # liveness supervision.
    liveness_timeout_s: float = 10.0
    # Reconnect backoff ladder: base * 2**attempt seconds, capped.
    backoff_base_s: float = 0.5
    backoff_cap_s: float = 30.0
    # A frame/tile sink or on_steer callback failing this many
    # CONSECUTIVE times is quarantined (disabled + `session.sink`
    # ledger) instead of killing the render loop; a success in between
    # resets the count (runtime/failsafe.SinkGuard).
    max_sink_failures: int = 3
    # FrameAssembler: an incomplete tile frame is abandoned (ledgered
    # `stream.gap`) once this many NEWER frames have started — the
    # `VideoReceiver._parts` eviction pattern, generalized.
    assembler_window: int = 4
    # Steering messages larger than this are dropped before unpack (the
    # steering socket is network-facing; a hostile/buggy viewer must
    # not be able to balloon the renderer).
    max_message_bytes: int = 1 << 20

    def __post_init__(self):
        if self.heartbeat_period_s <= 0:
            raise ValueError(f"heartbeat_period_s must be > 0, "
                             f"got {self.heartbeat_period_s}")
        if self.backoff_base_s <= 0 or self.backoff_cap_s < self.backoff_base_s:
            raise ValueError(
                f"need 0 < backoff_base_s <= backoff_cap_s, got "
                f"{self.backoff_base_s}, {self.backoff_cap_s}")
        if self.max_sink_failures < 1:
            raise ValueError(f"max_sink_failures must be >= 1, "
                             f"got {self.max_sink_failures}")
        if self.assembler_window < 1:
            raise ValueError(f"assembler_window must be >= 1, "
                             f"got {self.assembler_window}")
        if self.max_message_bytes < 1024:
            raise ValueError(f"max_message_bytes must be >= 1024, "
                             f"got {self.max_message_bytes}")


@dataclass(frozen=True)
class DeltaConfig:
    """Temporal-delta plane (docs/PERF.md "Temporal deltas"): steady
    frames cost bytes and FLOPs proportional to what changed.

    ``enabled`` turns on the P-frame WIRE codec on `VDIPublisher`
    (requires ``precision="qpack8"`` — the monotone quantizer is what
    makes code-space comparison exact): per published tile the stream
    carries a SKIP record, a sparse changed-slot residual, or a full
    I-tile, and subscribers reconstruct bit-exactly (ops/delta.py).
    The RE-MARCH half is switched separately by
    ``composite.temporal_reuse`` (it changes the compiled step's
    signature); ``range_tol`` is its dirty-detector tolerance."""

    # P-frame wire codec on VDIPublisher/VDISubscriber.
    enabled: bool = False
    # Forced I-tile cadence, frames: a joining subscriber or a stream
    # that dropped a record recovers within one period (the subscriber
    # ledgers the wait as stream.delta_resync). Smaller = faster
    # recovery, more bytes.
    iframe_period: int = 8
    # Dirty-detector tolerance of composite.temporal_reuse = "ranges":
    # a rank re-marches only when some occupancy-range cell moved by
    # more than this (absolute, field units). 0 = exact mode — any
    # range motion re-marches and reuse is bitwise vs recompute.
    range_tol: float = 0.0

    def __post_init__(self):
        if self.iframe_period < 1:
            raise ValueError(f"iframe_period must be >= 1, "
                             f"got {self.iframe_period}")
        if self.range_tol < 0.0:
            raise ValueError(f"range_tol must be >= 0, "
                             f"got {self.range_tol}")


@dataclass(frozen=True)
class DeliveryConfig:
    """Asynchronous delivery plane (runtime/delivery.py, docs/PERF.md
    "Async delivery"): a background worker tier drains the per-frame
    sink work off the render-loop thread, so steady-state frame time is
    max(device, host) instead of device + host.

    Disabled (the default) every sink runs inline on the loop thread —
    bitwise the pre-async behavior. Enabled, the loop enqueues each
    fetched frame's payloads onto a bounded FIFO and a worker thread
    runs the sinks (tile sinks in ascending column order, then frame
    sinks; frames strictly FIFO) behind the same SinkGuard quarantine.
    ``overflow`` decides what a full queue costs: ``block`` (lossless —
    the loop waits, correct for disk/checkpoint sinks) or
    ``drop_oldest`` (latest-wins — the oldest undelivered frame is shed
    with a ``delivery.shed`` ledger row + ``delivery_sheds`` counter,
    correct for live streaming where a stale frame has no value)."""

    # Run frame/tile sinks on the background executor instead of inline.
    enabled: bool = False
    # Bounded frame queue between the loop and the worker: at most this
    # many undelivered frames in flight before ``overflow`` applies.
    queue_frames: int = 4
    # Full-queue policy: "block" (lossless backpressure) or
    # "drop_oldest" (latest-wins shedding, ledgered).
    overflow: str = "block"
    # Per-tile encode fan-out (docs/PERF.md "Async delivery"): tile-sink
    # calls for one frame run across this many threads with the results
    # APPLIED in ascending tile order, so delivered bytes are
    # bit-identical to the serial path. 1 = serial. Also consumed by
    # VDIPublisher's parallel tile encoder.
    encode_workers: int = 1
    # Seconds ``drain()``/teardown waits for the queue to empty before
    # ledgering the abandon (`delivery.drain`). Generous by default —
    # a teardown must not lose committed frames.
    drain_timeout_s: float = 30.0

    def __post_init__(self):
        if self.queue_frames < 1:
            raise ValueError(f"delivery.queue_frames must be >= 1, "
                             f"got {self.queue_frames}")
        if self.overflow not in ("block", "drop_oldest"):
            raise ValueError(f"delivery.overflow must be 'block' or "
                             f"'drop_oldest', got {self.overflow!r}")
        if self.encode_workers < 1:
            raise ValueError(f"delivery.encode_workers must be >= 1, "
                             f"got {self.encode_workers}")
        if self.drain_timeout_s <= 0:
            raise ValueError(f"delivery.drain_timeout_s must be > 0, "
                             f"got {self.drain_timeout_s}")


@dataclass(frozen=True)
class ServeConfig:
    """VDI edge-serving tier (scenery_insitu_tpu/serve; docs/SERVING.md):
    a `ViewerServer` subscribes to the composited VDI stream and answers
    N concurrent client cameras per frame from ONE batched device
    dispatch (`ops.vdi_novel.render_vdi_batch`), so sim+march+composite
    stays O(1) while viewer cost scales on this separate, cacheable
    tier. Every shed, stale or degraded answer is minted on the obs
    ledger (serve.* components, docs/OBSERVABILITY.md)."""

    # Client-facing ROUTER endpoint (":0" = ephemeral port for tests)
    # and the upstream composited-VDI stream to subscribe to.
    bind: str = "tcp://*:6657"
    connect: str = "tcp://localhost:6655"
    # Admission control: clients beyond max_viewers get a typed "shed"
    # answer (serve.shed ledger) instead of service; pending camera
    # requests beyond queue_cap shed the same way (requests coalesce
    # latest-wins per client first, so the queue holds at most one
    # request per admitted client).
    max_viewers: int = 64
    queue_cap: int = 64
    # Cameras per render dispatch. A batch of B <= batch_size cameras
    # pads up to the next `buckets` entry (replicating its last camera;
    # padded lanes are discarded), so the jit cache holds at most
    # len(buckets) programs per (tier, regime) — bounded recompiles.
    batch_size: int = 16
    buckets: Tuple[int, ...] = (1, 2, 4, 8, 16)
    # Bounded staleness: answers rendered from a VDI more than this many
    # frames behind the newest frame the stream has STARTED are stamped
    # stale=True in the client protocol (+ serve.stale ledger) — the
    # viewer knows it is looking at the past.
    staleness_frames: int = 4
    # Quality ladder (docs/SERVING.md "Tiers"): "exact" = closed-form
    # render_vdi_exact; "proxy" = the MXU pre-shaded proxy volume, built
    # once per frame and marched per viewer (the amortization winner);
    # "wire" = the proxy render quantized to u8 wire precision (4x fewer
    # bytes per viewer). Clients pick a tier at hello; unknown tiers
    # degrade here (serve.tier ledger).
    default_tier: str = "proxy"
    # Camera-delta cache: a request whose camera moved by at most this
    # (max-abs over every camera leaf) since the client's last answer ON
    # THE SAME VDI FRAME re-serves the cached pixels without rendering.
    cam_tol: float = 1e-6
    # Served image size (fixed per server — per-request sizes would
    # defeat the bounded-recompile contract).
    width: int = 128
    height: int = 96
    # Novel-view plane count. 0 (the default) derives it per adopted
    # frame from the VDI's own deepest finite slab (quantized to 16 so
    # the jit key is stable) — this covers gather-engine VDIs, whose
    # reconstructed plane ladder starts at the camera near plane well
    # before the volume; a fixed count that stops short of the content
    # serves BLANK frames on the proxy tier.
    num_slices: int = 0
    # Intermediate-grid scale of the per-viewer proxy march. The render
    # path's 1.25x oversampling guards a RAW volume's features; the
    # serve proxy is already pre-shaded at the VDI's own resolution, so
    # 1.0 re-renders it without oversampling — ~1.6x cheaper per viewer,
    # which is most of the amortization headroom (docs/SERVING.md).
    march_scale: float = 1.0
    # Clients silent (no request/heartbeat) this long are evicted; their
    # next message re-admits them through admission control.
    client_timeout_s: float = 10.0
    # Liveness-supervise the upstream VDI subscription with fault.*
    # knobs (reconnect + backoff past liveness_timeout_s). Off by
    # default — the PR-11 convention: supervision needs a publisher
    # that pumps heartbeats, or a healthy-but-slow stream gets torn
    # down mid-frame.
    supervise_stream: bool = False

    def __post_init__(self):
        if self.max_viewers < 1:
            raise ValueError(f"max_viewers must be >= 1, "
                             f"got {self.max_viewers}")
        if self.queue_cap < 1:
            raise ValueError(f"queue_cap must be >= 1, got {self.queue_cap}")
        if self.batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, "
                             f"got {self.batch_size}")
        if not self.buckets or list(self.buckets) != sorted(set(self.buckets)):
            raise ValueError(f"buckets must be a strictly ascending ladder, "
                             f"got {self.buckets}")
        # buckets-vs-batch_size is a CROSS-FIELD constraint: it is
        # checked where the pair is consumed (ViewerServer.__init__),
        # not here — with_overrides applies one assignment at a time,
        # and a per-assignment check would make override order decide
        # whether a valid final config constructs.
        if self.staleness_frames < 0:
            raise ValueError(f"staleness_frames must be >= 0, "
                             f"got {self.staleness_frames}")
        if self.default_tier not in ("exact", "proxy", "wire"):
            raise ValueError(f"default_tier must be 'exact', 'proxy' or "
                             f"'wire', got {self.default_tier!r}")
        if self.cam_tol < 0.0:
            raise ValueError(f"cam_tol must be >= 0, got {self.cam_tol}")
        if self.width < 8 or self.height < 8:
            raise ValueError(f"served size must be >= 8x8, "
                             f"got {self.width}x{self.height}")
        if self.num_slices < 0:
            raise ValueError(f"num_slices must be >= 0 (0 = heuristic), "
                             f"got {self.num_slices}")
        if self.march_scale <= 0.0:
            raise ValueError(f"march_scale must be > 0, "
                             f"got {self.march_scale}")
        if self.client_timeout_s <= 0:
            raise ValueError(f"client_timeout_s must be > 0, "
                             f"got {self.client_timeout_s}")


@dataclass(frozen=True)
class StreamConfig:
    """Steering / streaming endpoints (≅ ZMQ :6655 + UDP :3337,
    VolumeFromFileExample.kt:840-854; DistributedVolumeRenderer.kt:278-283)."""

    steer_bind: str = "tcp://*:6655"
    steer_connect: str = "tcp://localhost:6655"
    video_port: int = 3337
    compress: str = "zstd"          # zstd | zlib | lzma | none (see io.vdi_io)


@dataclass(frozen=True)
class FrameworkConfig:
    render: RenderConfig = field(default_factory=RenderConfig)
    slicer: SliceMarchConfig = field(default_factory=SliceMarchConfig)
    vdi: VDIConfig = field(default_factory=VDIConfig)
    composite: CompositeConfig = field(default_factory=CompositeConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    topology: TopologyConfig = field(default_factory=TopologyConfig)
    sim: SimConfig = field(default_factory=SimConfig)
    runtime: RuntimeConfig = field(default_factory=RuntimeConfig)
    stream: StreamConfig = field(default_factory=StreamConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)
    slo: SLOConfig = field(default_factory=SLOConfig)
    fault: FaultConfig = field(default_factory=FaultConfig)
    delta: DeltaConfig = field(default_factory=DeltaConfig)
    delivery: DeliveryConfig = field(default_factory=DeliveryConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)
    lod: LODConfig = field(default_factory=LODConfig)

    # ------------------------------------------------------------------ IO
    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2)

    @classmethod
    def from_dict(cls, d: dict) -> "FrameworkConfig":
        return _merge_into(cls(), d)

    @classmethod
    def from_json_file(cls, path: str) -> "FrameworkConfig":
        with open(path) as f:
            return cls.from_dict(json.load(f))

    def with_overrides(self, *assignments: str, **env: Optional[dict]) -> "FrameworkConfig":
        """Apply ``section.key=value`` strings, e.g. ``render.width=512``."""
        cfg = self
        for a in assignments:
            key, _, raw = a.partition("=")
            if not _:
                raise ValueError(f"override must look like section.key=value: {a!r}")
            cfg = _assign(cfg, key.strip().split("."), _parse_value(raw.strip()))
        return cfg

    @classmethod
    def load(cls, path: Optional[str] = None, overrides: Tuple[str, ...] = ()) -> "FrameworkConfig":
        """File < env (SITPU_SECTION_KEY=value) < explicit overrides."""
        cfg = cls.from_json_file(path) if path else cls()
        for name, raw in os.environ.items():
            if not name.startswith(ENV_PREFIX):
                continue
            parts = name[len(ENV_PREFIX):].lower().split("_", 1)
            if len(parts) != 2 or not hasattr(cfg, parts[0]):
                # not a config section: other SITPU_* tooling vars (e.g.
                # SITPU_BENCH_*) share the prefix, so unknown sections
                # cannot be errors — only unknown KEYS of real sections are
                continue
            if tuple(parts) in _REMOVED_KEYS:
                from scenery_insitu_tpu import obs
                obs.degrade("config.removed_key", name, "ignored",
                            _REMOVED_KEYS[tuple(parts)])
                continue
            try:
                cfg = _assign(cfg, parts, _parse_value(raw))
            except (ValueError, AttributeError) as e:
                # a typo'd key/value must not silently do nothing (the
                # reference's three config tiers failed silently too)
                raise ValueError(
                    f"bad config override {name}={raw!r}: {e}") from e
        return cfg.with_overrides(*overrides)


# removed config keys -> deprecation note (accepted-and-warned, not fatal)
_REMOVED_KEYS = {
    ("mesh", "decomposition"): "decomposition is 1-D over z; multi-grid "
                               "layouts go through core.scene.MultiGridScene",
}


def _parse_value(raw: str) -> Any:
    try:
        return json.loads(raw)
    except json.JSONDecodeError:
        return raw


def _assign(cfg: Any, path: list, value: Any) -> Any:
    head = path[0]
    if not hasattr(cfg, head):
        raise AttributeError(f"no config field {head!r} on {type(cfg).__name__}")
    if len(path) == 1:
        current = getattr(cfg, head)
        if current is not None and not isinstance(value, type(current)):
            if isinstance(current, tuple):
                value = tuple(value)
            elif isinstance(current, float) and isinstance(value, int):
                value = float(value)
            elif isinstance(current, bool) and isinstance(value, str):
                value = value.lower() in ("1", "true", "yes", "on")
            elif isinstance(current, (int, float)) and isinstance(value, str):
                value = type(current)(value)
        return dataclasses.replace(cfg, **{head: value})
    return dataclasses.replace(cfg, **{head: _assign(getattr(cfg, head), path[1:], value)})


def _merge_into(cfg: Any, d: dict) -> Any:
    updates = {}
    for k, v in d.items():
        if not hasattr(cfg, k):
            raise AttributeError(f"no config field {k!r} on {type(cfg).__name__}")
        current = getattr(cfg, k)
        if dataclasses.is_dataclass(current) and isinstance(v, dict):
            updates[k] = _merge_into(current, v)
        elif isinstance(current, tuple) and isinstance(v, list):
            updates[k] = tuple(v)
        else:
            updates[k] = v
    return dataclasses.replace(cfg, **updates)
