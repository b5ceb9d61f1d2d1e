"""The distributed sort-last rendering pipeline (SURVEY.md §7 steps 5-6).

The reference's per-frame chain — per-rank VDI generation, JNI/MPI
``distributeVDIs`` all-to-all of image columns, GPU composite,
``gatherCompositedVDIs`` to rank 0 (DistributedVolumes.kt:683-933 and
:136-139) — collapses here into ONE jitted SPMD function under ``shard_map``:

    generate (local z-slab, halo-exact)
      → lax.all_to_all on the width axis over ICI
      → sort-merge composite of the n received column slices
      → a VDI frame re-sharded from column blocks to slot blocks (one
        more all_to_all a leaf: `_frame_out`), so that the host that
        takes it shard by shard copies n contiguous blocks; a plain
        image, a frame whose slots the ranks do not divide, a two-level
        or multi-process mesh leave sharded by W as composited (the
        gather is implicit in the output sharding either way)

No postRenderLambda/AtomicInteger interlock machinery survives
(DistributedVolumes.kt:736-796): XLA schedules generation, collective and
composite as one program and overlaps compute with ICI transfers.

Two exchange schedules (``CompositeConfig.exchange``; docs/PERF.md
"Exchange modes"): the default monolithic ``all_to_all`` + N·K-wide
sort-merge above, and a **ring** schedule — each rank keeps its own
column block and the others' fragments circulate over ICI in n-1
``lax.ppermute`` hops, each incoming K-fragment folded into a per-rank
sorted accumulator by the pairwise ordered merge
(ops.composite.merge_vdis_pairwise). The ring needs no N·K bitonic sort,
XLA's async collectives fly the next hop while the current fragment
merges, and with ``ring_slots`` set the per-pixel live state is bounded
at ring_slots + K instead of N·K.

Orthogonally, ``CompositeConfig.wire`` picks the supersegment encoding
that actually crosses ICI in either schedule (docs/PERF.md "Wire
formats"; ops/wire.py): fragments are encoded just before the collective
and decoded right after it — ``f32`` (bit-exact), ``bf16`` (12 B/slot,
2×) or ``qpack8`` (u8 color + u8×2 depth against per-fragment [near,
far] scalars, 6 B/slot, 4×). The merge/composite always runs in f32.

A third axis, ``CompositeConfig.schedule`` (docs/PERF.md "Tile waves"),
sets the GRANULARITY of the whole chain: ``"frame"`` runs one march →
one exchange → one composite per frame (exchange time adds serially to
march time), while ``"waves"`` makes the column block (tile) the unit of
march, exchange, composite and delivery — each rank marches one
column-block wave at a time (`ops.slicer.wave_camera` slices the virtual
camera's u grid; the frame's one `permute_volume` copy and occupancy
pyramid are shared by every wave) and, while wave w+1 marches, wave w's
fragments circulate and fold: a software-pipelined ``lax.scan`` over
waves holds the previous wave's fragments in a double-buffered carry
slot, so XLA schedules the collective (ring ``ppermute`` chain or
per-wave ``all_to_all``, per ``exchange``) concurrently with the next
wave's resampling matmuls inside ONE compiled step. Lossless waves are
parity-exact with the frame schedule (same per-pixel fragments, same
merge order), and the per-wave outputs land in the same W-sharded layout
(which `_frame_out` then re-shards like the frame schedule's) — plus
the session can deliver finished column blocks to subscribers before
the frame closes (runtime/session.py tile sinks slice the fetched
frame, whatever its sharding was).

A fourth axis, ``CompositeConfig.temporal_reuse = "ranges"``
(docs/PERF.md "Temporal deltas"), exploits coherence across FRAMES: the
MXU step carries each rank's previous marched fragment plus a dirty
signature (the occupancy pyramid's value ranges + the camera pose —
ops/delta.py) and skips the march entirely (``lax.cond``) on ranks
whose signature moved at most ``delta.range_tol``; the exchange +
composite are unchanged and still run every frame. The carried state
threads through the step signature exactly like the temporal threshold
maps (seed with `distributed_initial_reuse_mxu`).

The SIM decomposition is 1-D over the volume z axis with one-voxel halo
exchange, making distributed trilinear sampling seam-exact vs a
single-device render (tests assert PSNR, test_parallel.py). The RENDER
decomposition defaults to the same even z-slabs, but
``CompositeConfig.rebalance = "occupancy"`` (docs/PERF.md "Render
rebalancing") decouples it: each rank marches a PLANNED contiguous
z-slice band (``ops/occupancy.slice_plan`` equalizes the occupancy
pyramid's per-z live work; ``parallel/mesh.reslab_z`` materializes the
band from the even shards with the identical halo contract), so on
skewed scenes no rank marches air while another straggles — the
sort-last composite is invariant to which rank rendered which region,
and sampling is decomposition-invariant by construction (the MXU slice
ladder and the gather engine's global sample box), so a rebalanced
frame equals the even frame (tests/test_rebalance.py).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from scenery_insitu_tpu.config import (CompositeConfig, RenderConfig,
                                       VDIConfig)
from scenery_insitu_tpu.core.camera import Camera
from scenery_insitu_tpu.core.transfer import TransferFunction
from scenery_insitu_tpu.core.vdi import VDI
from scenery_insitu_tpu.core.volume import Volume
from scenery_insitu_tpu.obs.profiler import phase as _phase
from scenery_insitu_tpu.ops.composite import composite_plain, composite_vdis
from scenery_insitu_tpu.ops.raycast import raycast
from scenery_insitu_tpu.ops.vdi_gen import generate_vdi
from scenery_insitu_tpu.parallel.mesh import (halo_exchange_z,
                                              reslab_bricks,
                                              reslab_bricks_lod, reslab_z)
from scenery_insitu_tpu.parallel.topology import resolve_mesh_topology

from jax import shard_map


def _plan_rank_band(plan: tuple, axis_name: str):
    """Traced (band start, band depth) of this rank under a render plan
    (a static tuple of per-rank z-slice counts — docs/PERF.md "Render
    rebalancing"); helpers below pair it with `mesh.reslab_z`."""
    import numpy as np
    r = jax.lax.axis_index(axis_name)
    starts = np.concatenate([[0], np.cumsum(plan)])[:len(plan)]
    g0 = jnp.asarray(starts, jnp.int32)[r].astype(jnp.float32)
    p_r = jnp.asarray(plan, jnp.int32)[r].astype(jnp.float32)
    return g0, p_r


def _local_volume_and_clip(local_data: jnp.ndarray, origin: jnp.ndarray,
                           spacing: jnp.ndarray, d_global: int,
                           axis_name: str, plan=None
                           ) -> Tuple[Volume, jnp.ndarray, jnp.ndarray]:
    """Build this rank's halo-padded Volume and its exclusive clip AABB.

    ``plan`` switches the RENDER decomposition from the even z-slab to
    this rank's planned contiguous band (docs/PERF.md "Render
    rebalancing"): the volume becomes the `mesh.reslab_z` band (padded
    to the plan's max depth; clip bounds keep padding un-sampled) — the
    clip AABBs still tile the global volume exactly, so the sort-last
    composite is decomposition-invariant."""
    r = jax.lax.axis_index(axis_name)
    dn = local_data.shape[0]
    dz = spacing[2]
    if plan is None:
        with _phase("halo"):
            halo = halo_exchange_z(local_data, axis_name)  # [Dn+2, H, W]
        local_origin = origin.at[2].add((r * dn - 1) * dz)
        z_lo = origin[2] + r * dn * dz
        z_hi = origin[2] + (r + 1) * dn * dz
    else:
        with _phase("halo"):
            halo = reslab_z(local_data, plan, axis_name)   # [Pmax+2, H, W]
        g0, p_r = _plan_rank_band(plan, axis_name)
        local_origin = origin.at[2].add((g0 - 1) * dz)
        z_lo = origin[2] + g0 * dz
        z_hi = origin[2] + (g0 + p_r) * dz
    vol = Volume(halo, local_origin, spacing)
    h, w = local_data.shape[1], local_data.shape[2]
    gmax = origin + jnp.array([w, h, d_global], jnp.float32) * spacing
    clip_min = jnp.stack([origin[0], origin[1], z_lo])
    clip_max = jnp.stack([gmax[0], gmax[1], z_hi])
    # the GLOBAL box: rays ladder their samples against it so sample
    # positions are identical on every rank and under every render plan
    return vol, clip_min, clip_max, origin, gmax


def _exchange_columns(x: jnp.ndarray, n: int, axis_name: str) -> jnp.ndarray:
    """Sort-last column exchange: split trailing W axis into n blocks, block
    j goes to rank j; returns [n, ..., W/n] where the leading axis indexes
    the source rank (≅ distributeVDIs' MPI all-to-all with
    sizePerProcess = H*W*K*4/commSize, DistributedVolumes.kt:860-861)."""
    w = x.shape[-1]
    parts = jnp.moveaxis(x.reshape(x.shape[:-1] + (n, w // n)), -2, 0)
    with _phase("exchange"):
        return jax.lax.all_to_all(parts, axis_name, split_axis=0,
                                  concat_axis=0, tiled=True)


def _column_blocks(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """Split the trailing W axis into n blocks → [n, ..., W/n]; block j is
    the columns rank j composites (the pre-collective half of
    `_exchange_columns`, reused by the ring schedule which ships blocks
    one hop at a time instead of all at once)."""
    w = x.shape[-1]
    return jnp.moveaxis(x.reshape(x.shape[:-1] + (n, w // n)), -2, 0)


def _take_block(blocks: jnp.ndarray, j) -> jnp.ndarray:
    """blocks[j] for a traced rank index j."""
    return jax.lax.dynamic_index_in_dim(blocks, j, axis=0, keepdims=False)


def _encoded_all_to_all(a: jnp.ndarray, b: jnp.ndarray, n: int,
                        axis_name: str, encode, decode):
    """Wire-aware all_to_all column exchange (docs/PERF.md "Wire
    formats"): ``encode`` the pair before the collective, ``decode``
    after it, so only the narrow encoding crosses ICI. The per-fragment
    scale (qpack8) has no W axis to split — it rides an ``all_gather``
    so every rank decodes each source fragment against its SENDER's
    normalization ([n, 2], row order == all_to_all's source order)."""
    with _phase("wire_encode"):
        enc_a, enc_b, scale = encode(a, b)
    ra = _exchange_columns(enc_a, n, axis_name)
    rb = _exchange_columns(enc_b, n, axis_name)
    scales = (jax.lax.all_gather(scale, axis_name)
              if scale is not None else None)
    with _phase("wire_encode"):
        return decode(ra, rb, scales)


def _exchange_vdi_columns(color: jnp.ndarray, depth: jnp.ndarray,
                          n: int, axis_name: str, wire: str):
    """All_to_all column exchange of a VDI fragment under
    ``CompositeConfig.wire``. ``wire == "f32"`` is exactly the pre-wire
    exchange. Returns f32 ([n, K, 4, H, W/n], [n, K, 2, H, W/n]) with
    the leading axis indexing the source rank."""
    if wire == "f32":
        return (_exchange_columns(color, n, axis_name),
                _exchange_columns(depth, n, axis_name))
    from scenery_insitu_tpu.ops import wire as _wire

    return _encoded_all_to_all(
        color, depth, n, axis_name,
        lambda c, d: _wire.encode_fragment(c, d, wire),
        lambda c, d, s: _wire.decode_fragment(c, d, s, wire))


def _ring_exchange_composite(color: jnp.ndarray, depth: jnp.ndarray,
                             n: int, axis_name: str, cfg,
                             gap_eps: float = 1e-4):
    """Ring-pipelined sort-last compositing (CompositeConfig.exchange ==
    "ring"): this rank keeps its own column block; at hop s = 1..n-1 every
    rank ppermutes ONE K-fragment (its block for rank r-s) so rank r
    receives rank (r+s)'s fragment of ITS columns, and merges it into a
    per-pixel sorted accumulator with the pairwise ordered merge — XLA's
    async collectives let hop s+1 fly while fragment s merges, hiding ICI
    latency behind merge compute. The final accumulator is re-segmented by
    the SAME fold the all_to_all path runs after its global sort
    (ops.composite.resegment_stream), so lossless ring (ring_slots=0)
    output matches the all_to_all composite exactly; ring_slots > 0 caps
    the accumulator (bounded memory, farthest segments dropped on overfull
    pixels).

    Tie order among exactly-equal start depths follows arrival order
    (r, r+1, ... wrapping) instead of the all_to_all path's rank order —
    only observable for bit-identical live start depths, since empty
    slots' payloads are masked identically in both paths.
    """
    from scenery_insitu_tpu import obs as _obs
    from scenery_insitu_tpu.ops.composite import (modeled_exchange_traffic,
                                                  resegment_stream,
                                                  sort_stream)

    k = color.shape[0]
    h, w = color.shape[-2], color.shape[-1]
    cap = _ring_cap(cfg, k)

    # host-side build markers (this runs at trace time, once per compiled
    # step): the per-hop events give the trace one entry per ring step
    # with the modeled fragment bytes the hop moves
    rec = _obs.get_recorder()
    rec.count("ring_exchange_builds")
    rec.event("ring_exchange_build", ranks=n, k=k,
              slots=(cap or n * k), wire=cfg.wire,
              traffic=modeled_exchange_traffic(
                  n, k, h, w, k_out=cfg.max_output_supersegments,
                  mode="ring", ring_slots=cfg.ring_slots, wire=cfg.wire))

    # one K-wide per-pixel sort + stale-color mask of the LOCAL fragment
    # replaces the all_to_all path's N·K-wide post-exchange sort (the VDI
    # convention already promises front-to-back live slots; the sort makes
    # the merge's sorted-input precondition unconditional)
    with _phase("merge"):
        color, depth = sort_stream(color, depth)
    acc_c, acc_d = _ring_accumulate(color, depth, n, axis_name, cfg.wire,
                                    cap)
    with _phase("resegment"):
        return resegment_stream(acc_c, acc_d, cfg, gap_eps)


def _ring_cap(cfg, k: int):
    """Validated per-pixel accumulator cap of a ring merge (None =
    lossless): ring_slots must at least hold one incoming fragment."""
    cap = int(cfg.ring_slots) or None
    if cap is not None and cap < k:
        raise ValueError(
            f"ring_slots={cap} is below the per-rank fragment size K={k} "
            f"— the accumulator could not even hold one incoming fragment "
            f"(use 0 for lossless, or >= K, e.g. 2*K)")
    return cap


def _ring_accumulate(color: jnp.ndarray, depth: jnp.ndarray, n: int,
                     axis_name, wire: str, cap,
                     hop_counter: str = "ring_steps_built",
                     hop_event: str = "ring_step",
                     hop_scope: str = "exchange"):
    """The pipelined ring-merge core, shared by the single-level ring
    exchange above and the hierarchical composite's inter-domain (DCN)
    hop (parallel/hier.py): circulate each rank's column blocks of a
    per-pixel SORTED, empty-masked fragment ``[K, ...]`` around the
    ``n``-rank ``axis_name`` ring in n-1 ``ppermute`` hops, folding each
    arrival into a per-rank sorted accumulator with the pairwise ordered
    merge. Returns this rank's 1/n column-block accumulator (NOT
    re-segmented — callers resegment once at the top of their exchange).

    Wire encode runs ONCE on the local fragment; every hop ships the
    narrow encoding and decodes on receive (docs/PERF.md "Wire
    formats"). The own block round-trips the codec too, so the
    accumulator sees the same quantization whichever schedule ran —
    and the quantizers are monotone, so the pre-sorted stream decodes
    sorted (the pairwise-merge precondition). f32 inserts zero ops."""
    from scenery_insitu_tpu import obs as _obs
    from scenery_insitu_tpu.ops import wire as _wire
    from scenery_insitu_tpu.ops.composite import merge_vdis_pairwise

    rec = _obs.get_recorder()
    if wire == "f32":
        enc_c, enc_d, scale = color, depth, None
    else:
        with _phase("wire_encode"):
            enc_c, enc_d, scale = _wire.encode_fragment(color, depth,
                                                        wire)

    def dec(c, d, sc):
        return _wire.decode_fragment(c, d, sc, wire)

    blk_c = _column_blocks(enc_c, n)                  # [n, K, ..., H, W/n]
    blk_d = _column_blocks(enc_d, n)
    r = jax.lax.axis_index(axis_name)
    acc_c, acc_d = dec(_take_block(blk_c, r), _take_block(blk_d, r), scale)
    frag_bytes = (blk_c.size * blk_c.dtype.itemsize
                  + blk_d.size * blk_d.dtype.itemsize) // n
    for s in range(1, n):
        # rank i ships its block for rank i-s; receiver r hears from r+s
        perm = [(i, (i - s) % n) for i in range(n)]
        send_c = _take_block(blk_c, jnp.mod(r - s, n))
        send_d = _take_block(blk_d, jnp.mod(r - s, n))
        with _phase(hop_scope):
            recv_c = jax.lax.ppermute(send_c, axis_name, perm)
            recv_d = jax.lax.ppermute(send_d, axis_name, perm)
            recv_s = (jax.lax.ppermute(scale, axis_name, perm)
                      if scale is not None else None)
        rec.count(hop_counter)
        rec.event(hop_event, step=s, hops=s, frag_bytes=frag_bytes,
                  wire=wire)
        with _phase("merge"):
            mc, md = dec(recv_c, recv_d, recv_s)
            acc_c, acc_d = merge_vdis_pairwise(acc_c, acc_d, mc, md,
                                               k_cap=cap)
    return acc_c, acc_d


def _composite_exchanged(color: jnp.ndarray, depth: jnp.ndarray,
                         n: int, axis_name: str, comp_cfg, topo=None):
    """Sort-last exchange + composite under the configured schedule
    (CompositeConfig.exchange). Runs inside shard_map; returns the
    composited VDI of this rank's column block. n == 1 always takes the
    all_to_all path (both schedules are the identity exchange there, and
    it keeps the single-VDI fast path of `composite_vdis`). ``topo``
    (a parallel/topology.Topology) switches to the TWO-LEVEL composite:
    intra-domain exchange over the ranks sub-axis (ICI), inter-domain
    merge over the hosts sub-axis (DCN), re-segmented once at the top
    (parallel/hier.py) — parity-gated against this flat path."""
    if topo is not None:
        from scenery_insitu_tpu.parallel.hier import hier_composite_vdi

        return hier_composite_vdi(color, depth, topo, comp_cfg)
    if comp_cfg.exchange == "ring" and n > 1:
        return _ring_exchange_composite(color, depth, n, axis_name,
                                        comp_cfg)
    colors, depths = _exchange_vdi_columns(color, depth, n, axis_name,
                                           comp_cfg.wire)
    with _phase("merge"):
        return composite_vdis(colors, depths, comp_cfg)


# ------------------------------------------------------------- tile waves


def _wave_pipeline(n_waves: int, march_wave, compose, carry0=None):
    """Software-pipelined scan over tile waves (docs/PERF.md "Tile
    waves"): iteration w exchanges+composites wave w-1's fragments (held
    in the double-buffered carry slot) while marching wave w — the two
    are data-independent inside one scan body, so XLA overlaps the
    collective with the next wave's march.

    ``march_wave(w, carry) -> (fragments, carry')`` produces wave ``w``'s
    pre-exchange fragments (any pytree) plus carried per-wave state (the
    temporal threshold maps; None when stateless). ``compose(fragments)
    -> out`` runs the exchange + composite of one wave. Returns (outs
    stacked on a leading wave axis, final carry). The prologue marches
    wave 0 and the epilogue composites wave T-1, so every wave is
    composited exactly once."""
    with _phase("wave"):
        frag, carry = march_wave(jnp.int32(0), carry0)

    def body(c, w):
        fr, cr = c
        out = compose(fr)                  # wave w-1 circulates ...
        with _phase("wave"):
            fr2, cr = march_wave(w, cr)    # ... while wave w marches
        return (fr2, cr), out

    (frag, carry), outs = jax.lax.scan(body, (frag, carry),
                                       jnp.arange(1, n_waves))
    last = compose(frag)
    outs = jax.tree_util.tree_map(
        lambda s, l: jnp.concatenate([s, l[None]], axis=0), outs, last)
    return outs, carry


def _wave_assemble(x: jnp.ndarray) -> jnp.ndarray:
    """[T, ..., wb] per-wave tiles -> [..., T*wb]: wave w's tile is the
    w-th sub-block of this rank's contiguous owned column block, so
    concatenating along waves reproduces EXACTLY the frame schedule's
    composited layout (W-sharded, rank blocks contiguous)."""
    t = x.shape[0]
    moved = jnp.moveaxis(x, 0, -2)                    # [..., T, wb]
    return moved.reshape(moved.shape[:-2] + (t * moved.shape[-1],))


def _wave_build_marker(n: int, t: int, k: int, h: int, w: int, k_out: int,
                       exchange: str, ring_slots: int, wire: str,
                       marched: bool) -> None:
    """Host-side trace-time marker of one wave-schedule build
    (docs/OBSERVABILITY.md): counters for the build and its T waves plus
    one event carrying the modeled overlap accounting — what fraction of
    the exchange bytes the pipeline hides behind march compute.
    ``marched=False`` tags the monolithic-march variant (gather/plain
    engines pipeline exchange+composite only)."""
    from scenery_insitu_tpu import obs as _obs
    from scenery_insitu_tpu.ops.composite import modeled_exchange_traffic

    rec = _obs.get_recorder()
    rec.count("wave_schedule_builds")
    rec.count("wave_steps_built", t)
    rec.event("wave_schedule_build", ranks=n, tiles=t, k=k,
              wave_cols=w // t, tile_cols=w // (n * t),
              march_per_wave=marched,
              traffic=modeled_exchange_traffic(
                  n, k, h, w, k_out=k_out, mode=exchange,
                  ring_slots=ring_slots, wire=wire,
                  schedule="waves", wave_tiles=t))


def _composite_exchanged_waves(color: jnp.ndarray, depth: jnp.ndarray,
                               n: int, axis_name: str, comp_cfg,
                               topo=None) -> VDI:
    """Tile-wave exchange + composite of an ALREADY-generated full-frame
    fragment (the gather-engine waves path — the march was monolithic,
    so the pipeline overlaps each wave's collective with the next wave's
    merge+resegment instead of with march compute). Per wave: slice the
    wave's column blocks, run the frame compositor on them
    (`_composite_exchanged` — ring or all_to_all per ``exchange``), and
    reassemble; per-pixel identical to the frame schedule."""
    from scenery_insitu_tpu.ops import slicer as _slicer

    t = comp_cfg.wave_tiles
    k = color.shape[0]
    h, w = color.shape[-2], color.shape[-1]
    _slicer.wave_block(w, n, t)            # validates the geometry
    _wave_build_marker(n, t, k, h, w, comp_cfg.max_output_supersegments,
                       comp_cfg.exchange, comp_cfg.ring_slots,
                       comp_cfg.wire, marched=False)

    def march(wv, _):
        return (_slicer.wave_cols(color, n, t, wv),
                _slicer.wave_cols(depth, n, t, wv)), None

    def compose(fr):
        out = _composite_exchanged(fr[0], fr[1], n, axis_name, comp_cfg,
                                   topo=topo)
        return out.color, out.depth

    (oc, od), _ = _wave_pipeline(t, march, compose)
    return VDI(_wave_assemble(oc), _wave_assemble(od))


def _composite_exchanged_sched(color: jnp.ndarray, depth: jnp.ndarray,
                               n: int, axis_name: str, comp_cfg,
                               topo=None) -> VDI:
    """Schedule dispatcher of the sort-last exchange + composite
    (CompositeConfig.schedule): "frame" = the monolithic chain above,
    "waves" = the per-column-block-wave scan. A single-rank mesh
    degrades waves -> frame on the ledger — there is no exchange to
    pipeline and the frame path keeps the single-VDI fast path."""
    if comp_cfg.schedule == "waves":
        if n > 1:
            return _composite_exchanged_waves(color, depth, n, axis_name,
                                              comp_cfg, topo=topo)
        from scenery_insitu_tpu import obs as _obs

        _obs.degrade("composite.schedule", "waves", "frame",
                     "single-rank mesh has no exchange to pipeline",
                     warn=False)
    return _composite_exchanged(color, depth, n, axis_name, comp_cfg,
                                topo=topo)


def _slots_from_columns(x: jnp.ndarray, axis_name) -> jnp.ndarray:
    """The composited frame's last move: this rank's column block of
    every slot, [K, C, H, W/n], for whole rows of ITS K/n slots,
    [K/n, C, H, W] — one tiled ``all_to_all`` over the rank axis (slot
    block j goes to rank j, the received column blocks line up along W in
    source order) plus the compiler's layout copy. Exact: every element of
    the global array stays where it was, only its owner changes."""
    with _phase("exchange"):
        return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=3,
                                  tiled=True)


def _leaves_slot_major(mesh: Mesh, n: int, topo, k_out: int) -> bool:
    """Whether a composited VDI frame of ``k_out`` slots leaves this mesh
    sharded over its slots (`_frame_out`): the ranks divide the slots,
    the rank axis is flat (the two-level composite hands its columns out
    ranks-major, ``topo.out_axis``, to consumers of column tiles), and
    one process holds the whole mesh (across processes a frame is
    gathered column block by column block,
    parallel/multihost.gather_vdi_tiles). One rank has nothing to
    re-shard."""
    return (topo is None and n > 1 and k_out % n == 0
            and not mesh.is_multi_process)


def _frame_out(mesh: Mesh, axis, n: int, topo, k_out: int):
    """How a composited VDI frame leaves the mesh: ``(out_specs, leave)``
    for a builder whose ranks each hold their column block [k_out, C, H,
    W/n] — ``out_specs`` the VDI of PartitionSpecs, ``leave(vdi)`` the
    body's last op.

    The host takes a frame shard by shard and copies each into its place
    in one array (runtime/session.py ``HostFrames``). A W-sharded frame
    makes that W/n-wide runs under a W-wide stride — 245,760 runs of
    640 B for a 157 MB frame on four ranks, 9 GB/s on 16 threads. Sharded
    over its LEADING axis the same frame is n contiguous blocks. So where
    `_leaves_slot_major` says it can, the frame is re-sharded slot-major
    on the device before it leaves (`_slots_from_columns`): the same
    global array, shape, dtype and bytes, under ``P(axis, None, None,
    None)``. Anywhere else it leaves W-sharded as it always did."""
    if _leaves_slot_major(mesh, n, topo, k_out):
        spec = P(axis, None, None, None)

        def leave(vdi: VDI) -> VDI:
            return VDI(_slots_from_columns(vdi.color, axis),
                       _slots_from_columns(vdi.depth, axis))
    else:
        spec = P(None, None, None, axis if topo is None else topo.out_axis)

        def leave(vdi: VDI) -> VDI:
            return vdi
    return VDI(spec, spec), leave


def _resolve_waves(comp_cfg, n: int, width: int, slicer_mod=None) -> bool:
    """Build-time resolution of CompositeConfig.schedule for a step
    builder: True = run the tile-wave path (validating that ``width``
    splits into ranks * wave_tiles blocks — a bad geometry fails at
    build, not trace), False = frame path. A waves request on a
    single-rank mesh lands on the ledger (nothing to pipeline)."""
    if comp_cfg.schedule != "waves":
        return False
    if n == 1:
        from scenery_insitu_tpu import obs as _obs

        _obs.degrade("composite.schedule", "waves", "frame",
                     "single-rank mesh has no exchange to pipeline",
                     warn=False)
        return False
    if slicer_mod is None:
        from scenery_insitu_tpu.ops import slicer as slicer_mod
    slicer_mod.wave_block(width, n, comp_cfg.wave_tiles)
    return True


def _resolve_reuse(comp_cfg, supported: bool = True,
                   where: str = "") -> bool:
    """Build-time resolution of CompositeConfig.temporal_reuse for a
    step builder (docs/PERF.md "Temporal deltas"): True = thread the
    carried ReuseState through the step signature. Builders with no
    marched VDI fragment to carry (gather engine, hybrid, plain) ledger
    the configured-but-inert knob instead of silently ignoring it."""
    if comp_cfg is None or comp_cfg.temporal_reuse != "ranges":
        return False
    if not supported:
        from scenery_insitu_tpu import obs as _obs

        _obs.degrade("delta.reuse", "ranges", "off",
                     f"{where} carries no reusable VDI fragment "
                     "(temporal_reuse is an MXU VDI step feature)",
                     warn=False)
        return False
    from scenery_insitu_tpu import obs as _obs

    rec = _obs.get_recorder()
    rec.count("reuse_steps_built")
    return True


def _reuse_state_spec(axis):
    """Sharding spec of the distributed ReuseState: per-rank leaves
    stack along their leading axis (the thr-state convention) — sig [S]
    → [n*S], fragments [K, ...] → [n*K, ...], valid/dirty [1] → [n]."""
    from scenery_insitu_tpu.ops.delta import ReuseState

    return ReuseState(sig=P(axis), color=P(axis, None, None, None),
                      depth=P(axis, None, None, None),
                      valid=P(axis), dirty=P(axis))


def distributed_initial_reuse_mxu(mesh: Mesh, tf: TransferFunction,
                                  spec,
                                  vdi_cfg: Optional[VDIConfig] = None,
                                  comp_cfg: Optional[CompositeConfig]
                                  = None,
                                  axis_name: Optional[str] = None,
                                  plan=None):
    """Jitted seeder for ``temporal_reuse = "ranges"`` steps: returns
    ``f(vol_data (z-sharded), origin, spacing, cam) -> ReuseState`` with
    ``valid = 0`` everywhere, so the first real frame marches and fills
    the carry (the `distributed_initial_threshold_mxu` pattern). The
    per-rank signature length comes out of the same frame-state prelude
    the step runs, so the shapes can never disagree."""
    from scenery_insitu_tpu.ops import delta as _delta

    vdi_cfg = vdi_cfg or VDIConfig()
    comp_cfg = comp_cfg or CompositeConfig()
    # hierarchical meshes seed over the flat axis view (the carry is
    # per-rank state; the composite levels never see it)
    axis, n, _ = resolve_mesh_topology(mesh, axis_name)
    plan = _resolve_plan(comp_cfg, n, plan)

    def seed(local_data, origin, spacing, cam: Camera):
        # comp_cfg=None: the seed needs only the pyramid's SHAPE — no
        # K-budget psum, no budget ledger rows
        _, _, _, _, _, pyr, _ = _rank_frame_state(
            local_data, origin, spacing, spec, tf, vdi_cfg, axis, n,
            None, plan=plan, need_pyramid=True)
        sig = _delta.reuse_signature(pyr, cam)
        return _delta.init_reuse_like(sig, vdi_cfg.max_supersegments,
                                      spec.nj, spec.ni)

    f = shard_map(seed, mesh=mesh,
                  in_specs=(P(axis, None, None), P(), P(), P()),
                  out_specs=_reuse_state_spec(axis), check_vma=False)
    return jax.jit(f)


def _rebalance_build_marker(plan, n: int) -> None:
    """Host-side trace-time marker of one rebalanced-step build
    (docs/OBSERVABILITY.md): counts the build and records the plan's
    shape — the slice histogram and the pad overhead every rank pays for
    static SPMD shapes (max(plan)/mean(plan) - 1)."""
    from scenery_insitu_tpu import obs as _obs

    rec = _obs.get_recorder()
    rec.count("rebalance_steps_built")
    rec.event("rebalance_build", ranks=n, plan=list(plan),
              max_depth=int(max(plan)), min_depth=int(min(plan)),
              pad_overhead=round(
                  int(max(plan)) * n / float(sum(plan)) - 1.0, 4))


def _resolve_plan(comp_cfg, n: int, plan, min_halo: int = 1):
    """Build-time resolution of a render z-plan for a step builder
    (CompositeConfig.rebalance; docs/PERF.md "Render rebalancing").
    Returns the validated static plan tuple, or None for the even
    fast path: ``plan=None`` (no plan computed yet — the session passes
    one once live fractions are known) and the literal even plan both
    take the even-slab path — no reslab shuffle, no band padding, no
    ownership masks beyond the pre-existing ``v_bounds``. (Note the
    gather engine's SAMPLING semantics changed with this feature for
    every decomposition, even splits included: its t ladder now derives
    from the global box so sample positions match a single-device
    render — see ops/vdi_gen.generate_vdi and docs/PERF.md "Render
    rebalancing".) A plan without ``rebalance="occupancy"`` is a caller
    bug, not a silent ignore."""
    if plan is None:
        return None
    if comp_cfg is None:
        rebalance = "even"
    elif isinstance(comp_cfg, str):
        rebalance = comp_cfg
    else:
        rebalance = comp_cfg.rebalance
    if rebalance != "occupancy":
        raise ValueError(
            f"a render plan was passed but rebalance={rebalance!r} — "
            f"plans are the mechanism of rebalance='occupancy'")
    from scenery_insitu_tpu.parallel.mesh import validate_plan

    plan = validate_plan(plan, n, h=min_halo)
    if n == 1 or all(p == plan[0] for p in plan):
        return None
    _rebalance_build_marker(plan, n)
    return plan


def _bricks_build_marker(bmap, n: int) -> None:
    """Host-side trace-time marker of one brick-partitioned step build
    (docs/OBSERVABILITY.md): the brick grid, the padded slot count every
    rank marches, and the ownership histogram."""
    from scenery_insitu_tpu import obs as _obs

    counts = [len(bmap.rank_bricks(r)) for r in range(n)]
    rec = _obs.get_recorder()
    rec.count("bricks_steps_built")
    rec.event("bricks_build", ranks=n, nbricks=bmap.nbricks,
              brick_depth=bmap.brick_depth, slots=bmap.slots,
              owner=list(bmap.owner), bricks_per_rank=counts,
              level=list(bmap.level), max_level=bmap.max_level,
              total_slots=bmap.total_slots)


def _resolve_bricks(comp_cfg, n: int, bricks):
    """Build-time resolution of a brick→rank render partition for a
    step builder (CompositeConfig.rebalance == "bricks";
    docs/SCENARIOS.md "Brick maps"). Returns the validated
    `parallel.bricks.BrickMap`, or None for the slab fast path: no map,
    a single-rank mesh (every map is the whole volume there), or the
    even-convex map — which short-circuits BITWISE to the pre-brick
    path (the composite-invariance anchor). A map without
    ``rebalance="bricks"`` is a caller bug, not a silent ignore."""
    if bricks is None:
        return None
    from scenery_insitu_tpu.parallel.bricks import BrickMap

    if not isinstance(bricks, BrickMap):
        raise TypeError(f"bricks= takes a parallel.bricks.BrickMap, got "
                        f"{type(bricks).__name__}")
    if comp_cfg is None:
        rebalance = "even"
    elif isinstance(comp_cfg, str):
        rebalance = comp_cfg
    else:
        rebalance = comp_cfg.rebalance
    if rebalance != "bricks":
        raise ValueError(
            f"a brick map was passed but rebalance={rebalance!r} — brick "
            f"partitions are the mechanism of rebalance='bricks'")
    if bricks.n_ranks != n:
        raise ValueError(f"brick map built for {bricks.n_ranks} ranks on "
                         f"a {n}-rank mesh")
    # a single-rank mesh only short-circuits when every brick is level 0
    # — a coarse level still changes WHAT is marched, not just where
    if (n == 1 and bricks.max_level == 0) or bricks.is_even_convex():
        return None
    _bricks_build_marker(bricks, n)
    return bricks


def _bricks_inert(bricks, where: str):
    """Builders with no brick march (hybrid, plain, particle layers)
    must say a configured brick partition is inert, not silently render
    the even decomposition."""
    if bricks is None:
        return None
    from scenery_insitu_tpu import obs as _obs

    _obs.degrade("bricks.partition", "bricks", "slabs",
                 f"{where} has no brick march (gather/MXU VDI steps "
                 "only); the even z-slab decomposition renders",
                 warn=False)
    return None


def _brick_units(local_data, origin, spacing, spec, axis, n, bmap):
    """Per-brick march units of this rank under a BrickMap — the brick
    generalization of `_rank_slab` (docs/SCENARIOS.md "Brick maps").

    Materializes the rank's brick set ONCE (`mesh.reslab_bricks`, halo
    rows from the TRUE global neighbors whichever rank owns them) and
    returns ``([(vol, v_bounds, w_bounds, f)] * total_slots, gmax, dims,
    ref)`` — one unit per brick slot, each a `_rank_slab`-shaped
    (volume, ownership bounds) pair the existing per-chunk march
    consumes unchanged: z marches own their brick through the
    ``w_bounds`` world interval, x/y marches through the ``v_bounds``
    half-open interval (the brick owning the global top keeps the even
    path's +dz edge slack). Absent slots (rank owns fewer bricks than
    the busiest) carry zero rows and an EMPTY interval — every sample
    masks dead, the occupancy pyramid admits them as dead, and the
    fragment comes out all-+inf.

    LOD (docs/PERF.md "LOD marching"): when the map carries levels,
    slots group BY LEVEL (`mesh.reslab_bricks_lod` — global per-level
    slot counts keep SPMD shapes rank-uniform) and a level-l unit is the
    2^l reshape-mean-pooled brick as a Volume with spacing*2^l at the
    SAME corner origin, marched on the shared fine-pitch camera with
    ``dwm*2^l`` / ``step_scale=2^-l`` so coarse slices accumulate the
    opacity of the fine slices they replace. Ownership bounds stay the
    FINE brick world interval — the composited fragment stream is
    resolution-agnostic. ``f`` is the unit's downsample factor (1 for
    level 0); ``ref`` is the fine-pitch reference Volume for the shared
    camera/metadata (the all-level-0 path returns the existing units +
    f=1 + ref=units[0] — BITWISE the pre-LOD build)."""
    if getattr(spec, "render_dtype", "f32") == "bf16" \
            and local_data.dtype == jnp.float32:
        local_data = local_data.astype(jnp.bfloat16)
    r = jax.lax.axis_index(axis)
    dn = local_data.shape[0]
    h, w = local_data.shape[1], local_data.shape[2]
    d = dn * n
    dz = spacing[2]
    gmax = origin + jnp.array([w, h, d], jnp.float32) * spacing
    bz = bmap.brick_depth
    z_march = spec.axis == 2
    units = []
    if bmap.max_level == 0:
        table = jnp.asarray(bmap.start_table(), jnp.int32)  # [n, B]
        with _phase("halo"):
            bands = reslab_bricks(local_data, bmap, axis,
                                  h=0 if z_march else 1)
        for s in range(bmap.slots):
            start = table[r, s]                            # -1 = absent
            present = start >= 0
            startf = start.astype(jnp.float32)
            z_lo = origin[2] + startf * dz
            z_hi = origin[2] + (startf + bz) * dz
            if z_march:
                vol = Volume(bands[s], origin.at[2].add(startf * dz),
                             spacing)
                # open-interval march ownership (slice centers sit half
                # a voxel inside); an absent slot's interval is empty
                wb = (jnp.where(present, z_lo, jnp.inf),
                      jnp.where(present, z_hi, -jnp.inf))
                units.append((vol, None, wb, 1))
            else:
                vol = Volume(bands[s],
                             origin.at[2].add((startf - 1.0) * dz),
                             spacing)
                # the brick covering the global top keeps the even
                # path's +dz slack (its clamped halo row may re-admit
                # pos == max)
                hi = jnp.where(start + bz == d, z_hi + dz, z_hi)
                vb = (jnp.where(present, z_lo, jnp.inf),
                      jnp.where(present, hi, -jnp.inf))
                units.append((vol, vb, None, 1))
        return units, gmax, (w, h, d), units[0][0]
    halo = 0 if z_march else 1
    with _phase("halo"):
        bands = reslab_bricks_lod(local_data, bmap, axis, h=halo)
    for lvl in bmap.levels_present():
        f = 1 << lvl
        arr = bands[lvl]
        table_l = jnp.asarray(bmap.start_table_at(lvl), jnp.int32)
        for s in range(table_l.shape[1]):
            start = table_l[r, s]
            present = start >= 0
            startf = start.astype(jnp.float32)
            z_lo = origin[2] + startf * dz
            z_hi = origin[2] + (startf + bz) * dz
            org = origin.at[2].add((startf - halo * float(f)) * dz)
            vol = Volume(arr[s], org, spacing * float(f))
            if z_march:
                wb = (jnp.where(present, z_lo, jnp.inf),
                      jnp.where(present, z_hi, -jnp.inf))
                units.append((vol, None, wb, f))
            else:
                # coarse top-edge slack scales with the pooled pitch
                # (the clamped halo row spans f fine rows)
                hi = jnp.where(start + bz == d, z_hi + float(f) * dz,
                               z_hi)
                vb = (jnp.where(present, z_lo, jnp.inf),
                      jnp.where(present, hi, -jnp.inf))
                units.append((vol, vb, None, f))
    ref = Volume(jnp.zeros((1, 1, 1), local_data.dtype), origin, spacing)
    return units, gmax, (w, h, d), ref


def _brick_clip_units(local_data, origin, spacing, d_global, axis, bmap):
    """`_local_volume_and_clip`'s brick twin for the gather engine: one
    (volume, clip AABB) per brick slot. The clip AABBs tile the global
    volume exactly like the slab AABBs do (absent slots get an empty
    box), and the sample ladder stays the GLOBAL box — which is what
    makes the composited frame bitwise invariant to ownership.

    The gather engine has no coarse march (its t ladder is global and
    level-free): a level-carrying map renders every brick at level 0
    here, declared on the `lod.engine` ledger — not silently."""
    if bmap.max_level:
        from scenery_insitu_tpu import obs as _obs

        _obs.degrade("lod.engine", "lod", "fine",
                     "the gather engine has no LOD march (MXU builders "
                     "only); every brick samples at level 0", warn=False)
    r = jax.lax.axis_index(axis)
    h, w = local_data.shape[1], local_data.shape[2]
    dz = spacing[2]
    gmax = origin + jnp.array([w, h, d_global], jnp.float32) * spacing
    bz = bmap.brick_depth
    table = jnp.asarray(bmap.start_table(), jnp.int32)
    with _phase("halo"):
        bands = reslab_bricks(local_data, bmap, axis, h=1)
    units = []
    for s in range(bmap.slots):
        start = table[r, s]
        present = start >= 0
        startf = start.astype(jnp.float32)
        vol = Volume(bands[s], origin.at[2].add((startf - 1.0) * dz),
                     spacing)
        z_lo = origin[2] + startf * dz
        z_hi = origin[2] + (startf + bz) * dz
        cmin = jnp.stack([origin[0], origin[1],
                          jnp.where(present, z_lo, jnp.inf)])
        cmax = jnp.stack([gmax[0], gmax[1],
                          jnp.where(present, z_hi, -jnp.inf)])
        units.append((vol, cmin, cmax))
    return units, gmax


def _thr_slot(thr, s: int, nj: int):
    """Brick slot ``s``'s [nj, ni] threshold maps out of the row-stacked
    per-rank state (slots stack along rows, ranks along the mesh axis —
    the `_thr_state_spec` sharding is unchanged)."""
    import jax.tree_util as jtu

    return jtu.tree_map(lambda m: m[s * nj:(s + 1) * nj], thr)


def _stack_thr(states):
    import jax.tree_util as jtu

    return jtu.tree_map(lambda *xs: jnp.concatenate(xs, axis=0), *states)


def _mxu_rank_generate_bricks(local_data, origin, spacing, cam, slicer,
                              spec, tf, vdi_cfg, axis, n, bmap,
                              threshold=None):
    """Per-rank brick-set VDI generation on the MXU engine: march each
    brick slot through the existing per-chunk machinery (per-brick
    ownership bounds, per-brick occupancy pyramid) and CONCATENATE the
    K-slot fragments into one ``[slots*K]`` pre-exchange stream — the
    downstream exchange + composite sort per pixel anyway
    (`sort_stream` / the ring's unconditional local sort), so
    interleaved per-brick depth ranges need no pre-merge. Every brick's
    fragment depends only on the brick, the camera and the field —
    never on which rank marched it — which is the composite-invariance
    argument (tests/test_bricks.py). Temporal mode carries one
    [nj, ni] threshold map set PER SLOT, row-stacked.

    Returns (vdi [total_slots*K], meta, axcam, thr'). Coarse slots (LOD
    maps, docs/PERF.md "LOD marching") march on the shared fine-pitch
    camera with per-unit ``dwm*f`` / ``step_scale=1/f`` — the f==1 path
    is bitwise the pre-LOD build (``axc is axcam``, default scale)."""
    units, gmax, dims, ref = _brick_units(local_data, origin, spacing,
                                          spec, axis, n, bmap)
    axcam = slicer.make_axis_camera(ref, cam, spec,
                                    box_min=origin, box_max=gmax)
    nj = spec.nj
    colors, depths, thr2s = [], [], []
    for s, (vol, vb, wb, f) in enumerate(units):
        axc = axcam if f == 1 else axcam._replace(dwm=axcam.dwm * f)
        with _phase("march"):
            if threshold is None:
                vdi, _, _ = slicer.generate_vdi_mxu(
                    vol, tf, cam, spec, vdi_cfg, v_bounds=vb,
                    w_bounds=wb, axcam=axc, step_scale=1.0 / f)
            else:
                vdi, _, _, t2 = slicer.generate_vdi_mxu_temporal(
                    vol, tf, cam, spec, _thr_slot(threshold, s, nj),
                    vdi_cfg, v_bounds=vb, w_bounds=wb, axcam=axc,
                    step_scale=1.0 / f)
                thr2s.append(t2)
        colors.append(vdi.color)
        depths.append(vdi.depth)
    thr2 = _stack_thr(thr2s) if thr2s else None
    meta = slicer._vdi_meta(ref, axcam, spec.ni, spec.nj, 0)
    meta = meta._replace(volume_dims=jnp.array(dims, jnp.float32))
    return (VDI(jnp.concatenate(colors, axis=0),
                jnp.concatenate(depths, axis=0)), meta, axcam, thr2)


def _mxu_rank_generate_bricks_waves(local_data, origin, spacing, cam,
                                    slicer, spec, tf, vdi_cfg, comp_cfg,
                                    axis, n, bmap, threshold=None,
                                    topo=None):
    """Tile-wave twin of `_mxu_rank_generate_bricks`: per wave, march
    every brick slot on the wave camera's column block and concatenate
    the slot fragments into that wave's ``[slots*K]`` pre-exchange
    stream; wave w's fragments circulate while wave w+1 marches exactly
    like the slab path. Per-slot permuted copies and occupancy pyramids
    are built once per frame and shared by every wave."""
    import jax.tree_util as jtu

    from scenery_insitu_tpu.ops import occupancy as _occ

    units, gmax, dims, ref = _brick_units(local_data, origin, spacing,
                                          spec, axis, n, bmap)
    t = comp_cfg.wave_tiles
    slicer.wave_block(spec.ni, n, t)
    axcam = slicer.make_axis_camera(ref, cam, spec,
                                    box_min=origin, box_max=gmax)
    volps = [slicer.permute_volume(vol, spec) for vol, _, _, _ in units]
    pyrs = [(_occ.pyramid_from_volume(vol, tf, spec, volp=vp)
             if spec.skip_empty else None)
            for (vol, _, _, _), vp in zip(units, volps)]
    _wave_build_marker(n, t, len(units) * vdi_cfg.max_supersegments,
                       spec.nj, spec.ni,
                       comp_cfg.max_output_supersegments,
                       comp_cfg.exchange, comp_cfg.ring_slots,
                       comp_cfg.wire, marched=True)
    nj = spec.nj

    def march_wave(w, thr_full):
        axcam_w, spec_w = slicer.wave_camera(axcam, spec, n, t, w)
        cs, ds, t2s = [], [], []
        for s, (vol, vb, wb, f) in enumerate(units):
            axc = (axcam_w if f == 1
                   else axcam_w._replace(dwm=axcam_w.dwm * f))
            thr_s = (None if thr_full is None else
                     jtu.tree_map(lambda m: slicer.wave_cols(m, n, t, w),
                                  _thr_slot(thr_full, s, nj)))
            with _phase("march"):
                if thr_s is None:
                    vdi, _, _ = slicer.generate_vdi_mxu(
                        vol, tf, cam, spec_w, vdi_cfg, v_bounds=vb,
                        w_bounds=wb, occupancy=pyrs[s], axcam=axc,
                        volp=volps[s], step_scale=1.0 / f)
                else:
                    vdi, _, _, t2 = slicer.generate_vdi_mxu_temporal(
                        vol, tf, cam, spec_w, thr_s, vdi_cfg,
                        v_bounds=vb, w_bounds=wb, occupancy=pyrs[s],
                        axcam=axc, volp=volps[s], step_scale=1.0 / f)
                    t2s.append(t2)
            cs.append(vdi.color)
            ds.append(vdi.depth)
        if thr_full is not None:
            parts = [jtu.tree_map(
                lambda m, mw: slicer.wave_update_cols(m, mw, n, t, w),
                _thr_slot(thr_full, s, nj), t2s[s])
                for s in range(len(units))]
            thr_full = _stack_thr(parts)
        return (jnp.concatenate(cs, axis=0),
                jnp.concatenate(ds, axis=0)), thr_full

    def compose(fr):
        out = _composite_exchanged(fr[0], fr[1], n, axis, comp_cfg,
                                   topo=topo)
        return out.color, out.depth

    (oc, od), thr2 = _wave_pipeline(t, march_wave, compose, threshold)
    vdi = VDI(_wave_assemble(oc), _wave_assemble(od))
    meta = slicer._vdi_meta(ref, axcam, spec.ni, spec.nj, 0)
    meta = meta._replace(volume_dims=jnp.array(dims, jnp.float32))
    return vdi, meta, axcam, thr2


def _ring_exchange_plain(image: jnp.ndarray, depth: jnp.ndarray,
                         n: int, axis_name: str, wire: str = "f32",
                         hop_counter: str = "ring_steps_built",
                         build_counter: str = "ring_exchange_builds",
                         hop_scope: str = "exchange"):
    """Ring schedule for the plain-image exchange: n-1 single-fragment
    ppermute hops (pipelined like the VDI ring), then the stacked
    fragments are rolled back into SOURCE-RANK order so the downstream
    `composite_plain` sees the exact [n, ...] layout the all_to_all
    delivers — bitwise-identical output at ``wire="f32"``. Plain
    fragments are one RGBA+depth per pixel, so there is no N·K working
    set to cap; the win is the pipelined exchange, and a quantized wire
    (docs/PERF.md "Wire formats") shrinks what each hop moves — hops ship
    the encoding and decode on receive. Returns (images [n, 4, H, W/n],
    depths [n, H, W/n])."""
    from scenery_insitu_tpu import obs as _obs
    from scenery_insitu_tpu.ops import wire as _wire

    if wire == "f32":
        enc_i, enc_d, scale = image, depth, None
    else:
        with _phase("wire_encode"):
            enc_i, enc_d, scale = _wire.encode_plain(image, depth, wire)

    def dec(i, d, sc):
        return _wire.decode_plain(i, d, sc, wire)

    blk_i = _column_blocks(enc_i, n)                  # [n, ..., H, W/n]
    blk_d = _column_blocks(enc_d, n)                  # [n, H, W/n]
    r = jax.lax.axis_index(axis_name)
    rec = _obs.get_recorder()
    rec.count(build_counter)
    own_i, own_d = dec(_take_block(blk_i, r), _take_block(blk_d, r), scale)
    frags_i = [own_i]
    frags_d = [own_d]
    for s in range(1, n):
        perm = [(i, (i - s) % n) for i in range(n)]
        with _phase(hop_scope):
            recv_i = jax.lax.ppermute(
                _take_block(blk_i, jnp.mod(r - s, n)), axis_name, perm)
            recv_d = jax.lax.ppermute(
                _take_block(blk_d, jnp.mod(r - s, n)), axis_name, perm)
            recv_s = (jax.lax.ppermute(scale, axis_name, perm)
                      if scale is not None else None)
        with _phase("wire_encode"):
            di, dd = dec(recv_i, recv_d, recv_s)
        frags_i.append(di)
        frags_d.append(dd)
        rec.count(hop_counter)
    stacked_i = jnp.stack(frags_i)          # arrival order: r, r+1, ...
    stacked_d = jnp.stack(frags_d)
    # out[i] = stacked[(i - r) % n] = source rank i
    return jnp.roll(stacked_i, r, axis=0), jnp.roll(stacked_d, r, axis=0)


def _composite_plain_exchanged(image: jnp.ndarray, depth: jnp.ndarray,
                               n: int, axis_name: str, background,
                               exchange: str, wire: str = "f32",
                               topo=None):
    """Plain-image exchange + nearest-first composite under the configured
    schedule (`exchange` ∈ {"all_to_all", "ring"}) and wire format
    (`wire` ∈ {"f32", "bf16", "qpack8"}). ``topo`` switches to the
    two-level plain composite (parallel/hier.py): domain partials over
    ICI, nearest-first merge of the partials over DCN."""
    if topo is not None:
        from scenery_insitu_tpu.parallel.hier import hier_composite_plain

        return hier_composite_plain(image, depth, topo, background,
                                    exchange, wire)
    if exchange == "ring" and n > 1:
        images, depths = _ring_exchange_plain(image, depth, n, axis_name,
                                              wire)
    elif wire == "f32":
        images = _exchange_columns(image, n, axis_name)  # [n, 4, H, W/n]
        depths = _exchange_columns(depth, n, axis_name)  # [n, H, W/n]
    else:
        from scenery_insitu_tpu.ops import wire as _wire

        images, depths = _encoded_all_to_all(
            image, depth, n, axis_name,
            lambda i, d: _wire.encode_plain(i, d, wire),
            lambda i, d, s: _wire.decode_plain(i, d, s, wire))
    with _phase("merge"):
        return composite_plain(images, depths, background)


def _composite_plain_waves(image: jnp.ndarray, depth: jnp.ndarray,
                           n: int, axis_name: str, background,
                           exchange: str, wire: str, wave_tiles: int,
                           march_wave=None, topo=None) -> jnp.ndarray:
    """Tile-wave plain-image exchange + composite. ``march_wave(w, _) ->
    ((image_w, depth_w), _)`` optionally RENDERS each wave's column
    blocks (the MXU engine's tile-scoped `render_slices`) so the wave's
    collective overlaps the next wave's march; None slices pre-rendered
    full-frame fragments (the gather engine — exchange/composite
    pipelining only). Output layout == the frame schedule's."""
    from scenery_insitu_tpu.ops import slicer as _slicer

    t = wave_tiles
    w = image.shape[-1] if march_wave is None else None

    def slice_wave(wv, _):
        return (_slicer.wave_cols(image, n, t, wv),
                _slicer.wave_cols(depth, n, t, wv)), None

    if march_wave is None:
        _slicer.wave_block(w, n, t)
        _wave_build_marker(n, t, 1, image.shape[-2], w, 1, exchange, 0,
                           wire, marched=False)
        march_wave = slice_wave

    def compose(fr):
        return (_composite_plain_exchanged(fr[0], fr[1], n, axis_name,
                                           background, exchange, wire,
                                           topo=topo),)

    (img,), _ = _wave_pipeline(t, march_wave, compose)
    return _wave_assemble(img)


def distributed_vdi_step(mesh: Mesh, tf: TransferFunction,
                         width: int, height: int,
                         vdi_cfg: Optional[VDIConfig] = None,
                         comp_cfg: Optional[CompositeConfig] = None,
                         max_steps: int = 256,
                         axis_name: Optional[str] = None,
                         plan=None, bricks=None, topology=None):
    """Build the jitted distributed VDI render step.

    Returns ``f(vol_data f32[D, H, W] (z-sharded), origin f32[3],
    spacing f32[3], cam Camera) -> VDI`` whose color/depth are global
    arrays ([K_out, 4, height, width] / [K_out, 2, height, width]),
    sharded over their slots where `_frame_out` can (K_out % ranks == 0
    on a flat one-process mesh), else over their width.

    ``topology`` (a config.TopologyConfig; docs/MULTIHOST.md) selects
    the two-level composite on a hierarchical ``(hosts, ranks)`` mesh —
    generation and halo exchange run over the flat axis view unchanged,
    the sort-last composite splits into intra-domain (ICI) + inter-domain
    (DCN) levels. None on a flat mesh is exactly the single-level step.
    """
    vdi_cfg = vdi_cfg or VDIConfig()
    comp_cfg = comp_cfg or CompositeConfig()
    axis, n, topo = resolve_mesh_topology(mesh, axis_name, topology)
    if width % n:
        raise ValueError(f"width {width} not divisible by mesh size {n}")
    if comp_cfg.schedule == "waves" and n > 1:
        from scenery_insitu_tpu.ops.slicer import wave_block

        wave_block(width, n, comp_cfg.wave_tiles)   # fail at build time
    if comp_cfg.k_budget == "occupancy":
        # the gather engine has no occupancy pyramid to derive budgets
        # from — a configured-but-inert knob must land on the ledger
        from scenery_insitu_tpu import obs as _obs

        _obs.degrade("occupancy.k_budget", "occupancy", "static",
                     "gather-engine distributed step has no occupancy "
                     "pyramid (mxu builders only)", warn=False)
    _resolve_reuse(comp_cfg, supported=False,
                   where="the gather-engine distributed step")
    plan = _resolve_plan(comp_cfg, n, plan)
    bricks = _resolve_bricks(comp_cfg, n, bricks)

    def step(local_data, origin, spacing, cam: Camera) -> VDI:
        d_global = local_data.shape[0] * n
        if bricks is not None:
            # non-convex partition (docs/SCENARIOS.md): one K-fragment
            # per brick against the brick's clip AABB on the GLOBAL
            # sample ladder; the concatenated stream is sorted by the
            # composite, so the frame is bitwise invariant to ownership
            units, smax = _brick_clip_units(
                local_data, origin, spacing, d_global, axis, bricks)
            smin = origin
            cs, ds = [], []
            for vol, cmin, cmax in units:
                with _phase("march"):
                    vdi, _ = generate_vdi(vol, tf, cam, width, height,
                                          vdi_cfg, max_steps=max_steps,
                                          clip_min=cmin, clip_max=cmax,
                                          sample_min=smin,
                                          sample_max=smax)
                cs.append(vdi.color)
                ds.append(vdi.depth)
            return leave(_composite_exchanged_sched(
                jnp.concatenate(cs, axis=0), jnp.concatenate(ds, axis=0),
                n, axis, comp_cfg, topo=topo))
        vol, cmin, cmax, smin, smax = _local_volume_and_clip(
            local_data, origin, spacing, d_global, axis, plan=plan)
        with _phase("march"):
            vdi, _ = generate_vdi(vol, tf, cam, width, height, vdi_cfg,
                                  max_steps=max_steps, clip_min=cmin,
                                  clip_max=cmax, sample_min=smin,
                                  sample_max=smax)
        return leave(_composite_exchanged_sched(vdi.color, vdi.depth, n,
                                                axis, comp_cfg, topo=topo))

    spec_vol = P(axis, None, None)
    spec_out, leave = _frame_out(mesh, axis, n, topo,
                                 comp_cfg.max_output_supersegments)
    f = shard_map(step, mesh=mesh,
                  in_specs=(spec_vol, P(), P(), P()),
                  out_specs=spec_out, check_vma=False)
    return jax.jit(f)


def _rank_slab(local_data, origin, spacing, spec, axis, n,
               shade=None, shade_halo: int = 0, plan=None):
    """This rank's halo-padded slab Volume + global box + ownership bounds
    for a slice march (shared by generation and threshold seeding).
    Returns ``(vol, gmax, v_bounds, w_bounds, dims)``.

    ``shade``: optional per-rank volume shader (e.g. the AO pre-shader,
    ops/ao.shade_volume_ao) applied to a ``shade_halo``-deep extended
    slab BEFORE trimming to the march extent — a radius-``shade_halo``
    neighborhood operator inside ``shade`` then sees real neighbor
    slices, making its output seam-exact vs a single-device run. The
    shader may change the channel layout (scalar → pre-shaded RGBA).

    ``spec.render_dtype == "bf16"`` casts the marched slab to bf16 UP
    FRONT — the halo-exchange ICI bytes and every march's volume reads
    halve; shaded (AO) slabs shade in f32 first and cast the result.

    ``plan`` (docs/PERF.md "Render rebalancing") swaps the even slab for
    this rank's PLANNED contiguous z band, assembled from the even
    shards by `mesh.reslab_z` with the identical halo contract. The
    returned ownership bounds extend to the march axis: ``v_bounds``
    masks in-plane rows when z is the in-plane axis (x/y marches,
    exactly as before), and ``w_bounds`` masks marched slices when z IS
    the march axis — the band pads to the plan's max depth for static
    SPMD shapes, and padded slices must never shade."""
    if getattr(spec, "render_dtype", "f32") == "bf16" and shade is None \
            and local_data.dtype == jnp.float32:
        local_data = local_data.astype(jnp.bfloat16)
    r = jax.lax.axis_index(axis)
    dn = local_data.shape[0]
    h, w = local_data.shape[1], local_data.shape[2]
    dz = spacing[2]
    gmax = origin + jnp.array([w, h, dn * n], jnp.float32) * spacing
    if plan is not None:
        return _planned_slab(local_data, origin, spacing, spec, axis, n,
                             plan=plan, shade=shade, shade_halo=shade_halo,
                             dz=dz, gmax=gmax)

    if shade is not None:
        hr = shade_halo + 1
        with _phase("halo"):
            ext = halo_exchange_z(local_data, axis, h=hr)
        ext_origin = origin.at[2].add((r * dn - hr) * dz)
        local_data = shade(Volume(ext, ext_origin, spacing)).data
        if getattr(spec, "render_dtype", "f32") == "bf16" \
                and local_data.dtype == jnp.float32:
            local_data = local_data.astype(jnp.bfloat16)
        # trim back: [hr:hr+dn] is the bare slab; the branches below
        # re-add their own 1-slice interpolation halo from the REAL
        # (already-shaded) neighbors kept around it
        z_slice = lambda lo, hi: (local_data[..., lo:hi, :, :]
                                  if local_data.ndim == 4
                                  else local_data[lo:hi])

    if spec.axis == 2:
        # march along the domain axis: each rank marches only its own
        # slab slices — no halo, no ownership masks needed
        local_origin = origin.at[2].add(r * dn * dz)
        if shade is not None:
            local_data = z_slice(shade_halo + 1, shade_halo + 1 + dn)
        vol = Volume(local_data, local_origin, spacing)
        v_bounds = None
    else:
        # march along x/y: the in-plane v axis is the sharded z axis —
        # halo rows for seam-exact bilinear, half-open ownership so
        # every sample belongs to exactly one rank
        if shade is not None:
            halo = z_slice(shade_halo, shade_halo + dn + 2)
        else:
            with _phase("halo"):
                halo = halo_exchange_z(local_data, axis)   # [Dn+2, H, W]
        local_origin = origin.at[2].add((r * dn - 1) * dz)
        vol = Volume(halo, local_origin, spacing)
        z_lo = origin[2] + r * dn * dz
        z_hi = origin[2] + (r + 1) * dn * dz
        # edge ranks keep the exact global extent as their bound (the
        # clamped halo row must never render the band beyond it, which
        # single-device treats as outside the volume); the +dz slack on
        # the last rank only re-admits pos == global max, which the
        # volume-extent mask in _interp_matrix still caps
        v_bounds = (z_lo, jnp.where(r == n - 1, z_hi + dz, z_hi))
    return vol, gmax, v_bounds, None, (w, h, dn * n)


def _planned_slab(local_data, origin, spacing, spec, axis, n,
                  plan: tuple = (), shade=None, shade_halo=0,
                  dz=None, gmax=None):
    """`_rank_slab`'s planned-band twin (CompositeConfig.rebalance ==
    "occupancy"): the march volume is this rank's contiguous z band from
    the render plan, materialized by `mesh.reslab_z` (same seam-exact
    halo/clamp contract as the even path, zero-padded to the plan's max
    depth). Ownership stays exact and exclusive: x/y marches keep the
    half-open ``v_bounds`` interval — now the BAND interval — and z
    marches gain the ``w_bounds`` twin so padded slices shade nothing;
    together every world sample still belongs to exactly one rank, which
    is what makes the composite decomposition-invariant."""
    r = jax.lax.axis_index(axis)
    dn = local_data.shape[0]
    h, w = local_data.shape[1], local_data.shape[2]
    pmax = int(max(plan))
    g0, p_r = _plan_rank_band(plan, axis)
    z_lo = origin[2] + g0 * dz
    z_hi = origin[2] + (g0 + p_r) * dz

    if shade is not None:
        hr = shade_halo + 1
        with _phase("halo"):
            ext = reslab_z(local_data, plan, axis, h=hr)
        ext_origin = origin.at[2].add((g0 - hr) * dz)
        shaded = shade(Volume(ext, ext_origin, spacing)).data
        if getattr(spec, "render_dtype", "f32") == "bf16" \
                and shaded.dtype == jnp.float32:
            shaded = shaded.astype(jnp.bfloat16)
        # the band start sits at a FIXED offset hr inside the extended
        # band on every rank, so the trims below stay static; rows past
        # a rank's own band + halo were zero going in and are masked by
        # the ownership bounds coming out
        z_slice = lambda lo, hi: (shaded[..., lo:hi, :, :]
                                  if shaded.ndim == 4 else shaded[lo:hi])

    if spec.axis == 2:
        # march along z: the band's slices ARE the marched slices; the
        # pad slices (band depth < pmax) are dropped by w_bounds exactly
        # like v_bounds drops foreign in-plane rows on x/y marches
        if shade is not None:
            band = z_slice(hr, hr + pmax)
        else:
            with _phase("halo"):
                band = reslab_z(local_data, plan, axis,
                                h=0)                       # [Pmax, H, W]
        local_origin = origin.at[2].add(g0 * dz)
        vol = Volume(band, local_origin, spacing)
        return vol, gmax, None, (z_lo, z_hi), (w, h, dn * n)

    # march along x/y: the in-plane v axis is the planned z band — halo
    # rows for seam-exact bilinear, half-open PLAN-interval ownership
    if shade is not None:
        band = z_slice(hr - 1, hr + pmax + 1)              # [Pmax+2, ...]
    else:
        with _phase("halo"):
            band = reslab_z(local_data, plan, axis)        # [Pmax+2, H, W]
    local_origin = origin.at[2].add((g0 - 1) * dz)
    vol = Volume(band, local_origin, spacing)
    # same edge-rank slack as the even path: rank n-1 owns the global
    # top whatever the plan (band starts are monotone)
    v_bounds = (z_lo, jnp.where(r == n - 1, z_hi + dz, z_hi))
    return vol, gmax, v_bounds, None, (w, h, dn * n)


def _rank_frame_state(local_data, origin, spacing, spec, tf, vdi_cfg,
                      axis, n, comp_cfg, plan=None,
                      need_pyramid: bool = False, ranges=None):
    """Per-frame, per-rank shared state of an MXU generation: the
    halo-exact slab (or planned render band, ``plan``), the frame's ONE
    occupancy pyramid, and (when ``comp_cfg.k_budget == "occupancy"``)
    the psum-derived adaptive-K target. Shared by the frame-schedule
    generation (`_mxu_rank_generate`) and the tile-wave path
    (`_mxu_rank_generate_waves`) — T waves must not pay T pyramids or T
    psums. ``need_pyramid`` forces the pyramid even with skipping off —
    the temporal-reuse dirty detector reads its ranges every frame.
    ``ranges`` (`distributed_volume_ranges_mxu`'s two rank-stacked
    arrays, of a field that never changes) stand in for the pyramid's
    sweep of the volume."""
    vol, gmax, v_bounds, w_bounds, dims = _rank_slab(
        local_data, origin, spacing, spec, axis, n, plan=plan)
    occ_pyr = None
    k_target = None
    budgeted = comp_cfg is not None and comp_cfg.k_budget == "occupancy"
    if budgeted and not vdi_cfg.adaptive:
        # a fixed-threshold generation never consults the target — the
        # knob is inert, so say so instead of paying the psum per frame
        from scenery_insitu_tpu import obs as _obs

        _obs.degrade("occupancy.k_budget", "occupancy", "static",
                     "k budgets re-target the ADAPTIVE threshold; "
                     "vdi.adaptive=False ignores them", warn=False)
        budgeted = False
    if spec.skip_empty or budgeted or need_pyramid:
        from scenery_insitu_tpu.ops import occupancy as _occ

        if ranges is not None:      # this rank's of the rank-stacked two
            ranges = tuple(jnp.asarray(x)[jax.lax.axis_index(axis)]
                           for x in ranges)
        with _phase("march"):
            occ_pyr = _occ.pyramid_from_volume(vol, tf, spec,
                                               ranges=ranges)
    if budgeted:
        from scenery_insitu_tpu import obs as _obs
        from scenery_insitu_tpu.ops import occupancy as _occ

        live = occ_pyr.live_fraction()
        k_target = _occ.k_budget_target(
            live, jax.lax.psum(live, axis), n,
            vdi_cfg.max_supersegments, comp_cfg.k_budget_min)
        rec = _obs.get_recorder()
        rec.count("occupancy_kbudget_builds")
        rec.event("occupancy_kbudget_build", ranks=n,
                  k=vdi_cfg.max_supersegments,
                  k_min=comp_cfg.k_budget_min)
    return vol, gmax, v_bounds, w_bounds, dims, occ_pyr, k_target


def _mxu_rank_generate(local_data, origin, spacing, cam, slicer, spec,
                       tf, vdi_cfg, axis, n, threshold=None,
                       comp_cfg=None, plan=None, reuse=None,
                       reuse_tol: float = 0.0, ranges=None):
    """Per-rank slice-march VDI generation on a z-slab (shared by the
    distributed VDI and hybrid steps). Returns (vdi, meta, axcam,
    next_threshold, next_reuse) — the last two are None unless carried
    temporal threshold / reuse state was passed in.

    This is where the frame's ONE occupancy pyramid is built
    (ops/occupancy.pyramid_from_volume on the halo-exact slab) and
    shared by every march of the generation — the legacy path re-ran the
    permute + full-slab reduction per call site. The same pyramid's live
    fraction drives the load-aware per-rank K budget when
    ``comp_cfg.k_budget == "occupancy"``: a psum over the mesh turns the
    per-rank live fractions into shares of the N*K budget
    (occupancy.k_budget_target), so the adaptive threshold on a sparse
    slab stops chasing the same K as the densest rank.

    ``reuse`` (an ops/delta.ReuseState; docs/PERF.md "Temporal deltas")
    carries the previous frame's marched fragment plus its dirty
    signature: when the pyramid's ranges moved at most ``reuse_tol`` and
    the camera is bit-unchanged, the march is skipped under ``lax.cond``
    (no matmul wave issues — both branches are collective-free, so a
    per-rank divergent predicate is sound inside shard_map) and the
    carried fragment feeds the unchanged exchange + composite."""
    vol, gmax, v_bounds, w_bounds, dims, occ_pyr, k_target = \
        _rank_frame_state(local_data, origin, spacing, spec, tf, vdi_cfg,
                          axis, n, comp_cfg, plan=plan,
                          need_pyramid=reuse is not None, ranges=ranges)
    if reuse is None:
        with _phase("march"):
            if threshold is None:
                vdi, meta, axcam = slicer.generate_vdi_mxu(
                    vol, tf, cam, spec, vdi_cfg,
                    box_min=origin, box_max=gmax, v_bounds=v_bounds,
                    occupancy=occ_pyr, k_target=k_target,
                    w_bounds=w_bounds)
                thr2 = None
            else:
                vdi, meta, axcam, thr2 = slicer.generate_vdi_mxu_temporal(
                    vol, tf, cam, spec, threshold, vdi_cfg,
                    box_min=origin, box_max=gmax, v_bounds=v_bounds,
                    occupancy=occ_pyr, k_target=k_target,
                    w_bounds=w_bounds)
        # metadata must describe the GLOBAL volume, not this rank's slab
        meta = meta._replace(volume_dims=jnp.array(dims, jnp.float32))
        return vdi, meta, axcam, thr2, None

    from scenery_insitu_tpu.ops import delta as _delta

    axcam = slicer.make_axis_camera(vol, cam, spec, box_min=origin,
                                    box_max=gmax)
    sig = _delta.reuse_signature(occ_pyr, cam)
    dirty = _delta.reuse_dirty(sig, reuse.sig, reuse.valid, reuse_tol,
                               2 * occ_pyr.lo.size)

    def marched(_):
        with _phase("march"):
            if threshold is None:
                vdi, _, _ = slicer.generate_vdi_mxu(
                    vol, tf, cam, spec, vdi_cfg, v_bounds=v_bounds,
                    occupancy=occ_pyr, k_target=k_target, axcam=axcam,
                    w_bounds=w_bounds)
                return vdi.color, vdi.depth
            vdi, _, _, thr2 = slicer.generate_vdi_mxu_temporal(
                vol, tf, cam, spec, threshold, vdi_cfg,
                v_bounds=v_bounds, occupancy=occ_pyr, k_target=k_target,
                axcam=axcam, w_bounds=w_bounds)
            return vdi.color, vdi.depth, thr2

    def kept(_):
        # a clean rank: last frame's fragment IS this frame's (the
        # temporal threshold controller holds too — nothing marched, so
        # there is no observation to feed it)
        if threshold is None:
            return reuse.color, reuse.depth
        return reuse.color, reuse.depth, threshold

    out = jax.lax.cond(dirty, marched, kept, None)
    color, depth = out[0], out[1]
    thr2 = out[2] if threshold is not None else None
    reuse2 = _delta.ReuseState(
        # the signature tracks the last MARCHED frame, so sub-tolerance
        # drift accumulates instead of creeping away unseen
        sig=jnp.where(dirty, sig, reuse.sig),
        color=color, depth=depth,
        valid=jnp.ones_like(reuse.valid),
        dirty=dirty.astype(jnp.int32).reshape(1))
    meta = slicer._vdi_meta(vol, axcam, spec.ni, spec.nj, 0)
    meta = meta._replace(volume_dims=jnp.array(dims, jnp.float32))
    return VDI(color, depth), meta, axcam, thr2, reuse2


def _mxu_rank_generate_waves(local_data, origin, spacing, cam, slicer,
                             spec, tf, vdi_cfg, comp_cfg, axis, n,
                             threshold=None, plan=None, reuse=None,
                             reuse_tol: float = 0.0, topo=None):
    """The tile-wave twin of `_mxu_rank_generate` + `_composite_exchanged`
    (CompositeConfig.schedule == "waves"; docs/PERF.md "Tile waves"):
    instead of one whole-frame march followed by one exchange, each rank
    marches ONE column-block wave at a time (a tile-scoped generation on
    `slicer.wave_camera`'s u-sliced virtual camera — same slices, same
    per-pixel samples) and, while wave w+1 marches, wave w's fragments
    circulate and fold through the frame compositor. The slab, the halo
    exchange, the `permute_volume` copy, the occupancy pyramid and the
    occupancy K budget are all built ONCE per frame and shared by every
    wave.

    Temporal mode slices the carried threshold maps to each wave's
    columns and scatters the controller's update back — the full-frame
    state that crosses frames is bit-identical in meaning to the frame
    schedule's (each pixel is marched exactly once per frame either
    way). ``reuse`` (docs/PERF.md "Temporal deltas") works like
    `_mxu_rank_generate`'s: the dirty predicate is per rank (the range
    signature is rank-wide) and every wave of a clean rank skips its
    march under ``lax.cond`` — the wave slice of the carried full-frame
    fragment stands in, so the waves' exchange + composite overlap
    pipeline is untouched. Returns (vdi [K_out over this rank's
    contiguous column block], meta, axcam, thr', reuse')."""
    import jax.tree_util as jtu

    vol, gmax, v_bounds, w_bounds, dims, occ_pyr, k_target = \
        _rank_frame_state(local_data, origin, spacing, spec, tf, vdi_cfg,
                          axis, n, comp_cfg, plan=plan,
                          need_pyramid=reuse is not None)
    t = comp_cfg.wave_tiles
    slicer.wave_block(spec.ni, n, t)       # validates the geometry
    axcam = slicer.make_axis_camera(vol, cam, spec, box_min=origin,
                                    box_max=gmax)
    volp = slicer.permute_volume(vol, spec)
    _wave_build_marker(n, t, vdi_cfg.max_supersegments, spec.nj, spec.ni,
                       comp_cfg.max_output_supersegments,
                       comp_cfg.exchange, comp_cfg.ring_slots,
                       comp_cfg.wire, marched=True)
    if reuse is not None:
        from scenery_insitu_tpu.ops import delta as _delta

        sig = _delta.reuse_signature(occ_pyr, cam)
        dirty = _delta.reuse_dirty(sig, reuse.sig, reuse.valid,
                                   reuse_tol, 2 * occ_pyr.lo.size)

    def march_wave(w, carry):
        if reuse is not None:
            thr_full, acc_c, acc_d = carry
        else:
            thr_full = carry
        axcam_w, spec_w = slicer.wave_camera(axcam, spec, n, t, w)
        thr_w = (None if thr_full is None else
                 jtu.tree_map(lambda m: slicer.wave_cols(m, n, t, w),
                              thr_full))

        def marched(_):
            with _phase("march"):
                if thr_w is None:
                    vdi, _, _ = slicer.generate_vdi_mxu(
                        vol, tf, cam, spec_w, vdi_cfg,
                        v_bounds=v_bounds, occupancy=occ_pyr,
                        k_target=k_target, axcam=axcam_w, volp=volp,
                        w_bounds=w_bounds)
                    return vdi.color, vdi.depth
                vdi, _, _, thr2w = slicer.generate_vdi_mxu_temporal(
                    vol, tf, cam, spec_w, thr_w, vdi_cfg,
                    v_bounds=v_bounds, occupancy=occ_pyr,
                    k_target=k_target, axcam=axcam_w, volp=volp,
                    w_bounds=w_bounds)
                return vdi.color, vdi.depth, thr2w

        if reuse is None:
            out = marched(None)
        else:
            def kept(_):
                cw = slicer.wave_cols(acc_c, n, t, w)
                dw = slicer.wave_cols(acc_d, n, t, w)
                if thr_w is None:
                    return cw, dw
                return cw, dw, thr_w

            out = jax.lax.cond(dirty, marched, kept, None)
        cw, dw = out[0], out[1]
        if thr_full is not None:
            thr_full = jtu.tree_map(
                lambda m, mw: slicer.wave_update_cols(m, mw, n, t, w),
                thr_full, out[2])
        if reuse is None:
            return (cw, dw), thr_full
        # the carried full-frame fragment accumulates wave by wave; a
        # clean rank scatters back exactly what it sliced out (no-op)
        acc_c = slicer.wave_update_cols(acc_c, cw, n, t, w)
        acc_d = slicer.wave_update_cols(acc_d, dw, n, t, w)
        return (cw, dw), (thr_full, acc_c, acc_d)

    def compose(fr):
        out = _composite_exchanged(fr[0], fr[1], n, axis, comp_cfg,
                                   topo=topo)
        return out.color, out.depth

    carry0 = (threshold if reuse is None else
              (threshold, reuse.color, reuse.depth))
    (oc, od), carry = _wave_pipeline(t, march_wave, compose, carry0)
    if reuse is None:
        thr2, reuse2 = carry, None
    else:
        from scenery_insitu_tpu.ops import delta as _delta

        thr2, acc_c, acc_d = carry
        reuse2 = _delta.ReuseState(
            sig=jnp.where(dirty, sig, reuse.sig),
            color=acc_c, depth=acc_d,
            valid=jnp.ones_like(reuse.valid),
            dirty=dirty.astype(jnp.int32).reshape(1))
    vdi = VDI(_wave_assemble(oc), _wave_assemble(od))
    meta = slicer._vdi_meta(vol, axcam, spec.ni, spec.nj, 0)
    meta = meta._replace(volume_dims=jnp.array(dims, jnp.float32))
    return vdi, meta, axcam, thr2, reuse2


def distributed_vdi_step_mxu(mesh: Mesh, tf: TransferFunction,
                             spec, vdi_cfg: Optional[VDIConfig] = None,
                             comp_cfg: Optional[CompositeConfig] = None,
                             axis_name: Optional[str] = None,
                             plan=None, bricks=None,
                             reuse_tol: float = 0.0,
                             topology=None, ranges=None,
                             slot_counts: bool = False):
    """Distributed sort-last VDI pipeline on the MXU slice-march engine
    (ops/slicer.py) — generation runs as banded-matmul slice resampling
    instead of per-ray gathers; the rest of the chain (width-axis column
    exchange under ``comp_cfg.exchange`` — all_to_all or ring — then the
    sort-merge composite) is unchanged.

    ``spec`` is the static `slicer.AxisSpec` for the *current camera
    regime* (march axis/sign + intermediate resolution); the session keeps
    one jitted step per regime. The output VDI lives on the virtual
    axis camera's global pixel grid. It is composited sharded over its
    width (i) axis and leaves the mesh sharded over its SLOTS where
    `_frame_out` can re-shard it (K_out % ranks == 0, flat one-process
    mesh: blocks the host copies whole), else as composited.

    Domain decomposition is the same z-slab sharding as
    `distributed_vdi_step`; ownership of in-plane samples is half-open per
    rank, halo rows make boundary interpolation seam-exact.

    ``comp_cfg.temporal_reuse == "ranges"`` changes the signature to
    ``f(vol_data, origin, spacing, cam, reuse) -> ((VDI, meta),
    reuse')`` — seed ``reuse`` with `distributed_initial_reuse_mxu`;
    ``reuse_tol`` is the dirty tolerance (cfg.delta.range_tol).

    ``ranges``: what `distributed_volume_ranges_mxu` gave for a field
    that never changes (a dataset): the step then sweeps no volume for
    its occupancy pyramid. A planned band, a brick map and the
    tile-wave schedule sweep as before.

    ``slot_counts``: the frame comes as ``(VDI, meta, slots)``, the fold
    kernel's slot-row account a rank (see `_build_mxu_step`).
    """
    return _build_mxu_step(mesh, tf, spec, vdi_cfg, comp_cfg, axis_name,
                           temporal=False, plan=plan, bricks=bricks,
                           reuse_tol=reuse_tol, topology=topology,
                           ranges=ranges, slot_counts=slot_counts)


def _build_mxu_step(mesh, tf, spec, vdi_cfg, comp_cfg, axis_name,
                    temporal: bool, plan=None, bricks=None,
                    reuse_tol: float = 0.0, topology=None, ranges=None,
                    slot_counts: bool = False):
    """Shared builder of the MXU sort-last step (generate → column
    exchange under ``comp_cfg.exchange`` → composite), with or without
    carried temporal threshold state threaded through.

    ``comp_cfg.temporal_reuse == "ranges"`` (docs/PERF.md "Temporal
    deltas") appends a second carry: the step signature gains a trailing
    ``reuse`` argument (an ops/delta.ReuseState from
    `distributed_initial_reuse_mxu`) and the return gains ``reuse'`` —
    ranks whose occupancy-range signature moved at most ``reuse_tol``
    (``FrameworkConfig.delta.range_tol``) skip their march and feed the
    carried fragment to the exchange.

    ``slot_counts`` (a recorded session's step) makes the frame a triple
    ``(VDI, meta, slots)``: ``slots`` i32[ranks, 2], each rank's own
    ``(slot rows merged, slot rows visited)`` of its write march's fold
    kernel (ops/pallas_seg.fold_slot_counts), for the host to add up
    where it fetches the frame. Zeros where no kernel folds
    (``slicer.fold=xla``) and under the schedules that march inside a
    ``scan`` or a ``cond`` (tile waves, temporal reuse) or brick by
    brick; without it the account is dead code on the device."""
    from scenery_insitu_tpu.core.vdi import VDIMetadata
    from scenery_insitu_tpu.obs.profiler import fold_slot_account
    from scenery_insitu_tpu.ops import slicer

    vdi_cfg = vdi_cfg or VDIConfig()
    comp_cfg = comp_cfg or CompositeConfig()
    axis, n, topo = resolve_mesh_topology(mesh, axis_name, topology)
    if spec.ni % n:
        raise ValueError(f"intermediate width {spec.ni} not divisible by "
                         f"mesh size {n}")
    waves = _resolve_waves(comp_cfg, n, spec.ni, slicer)
    plan = _resolve_plan(comp_cfg, n, plan)
    bricks = _resolve_bricks(comp_cfg, n, bricks)
    if bricks is not None and comp_cfg.k_budget == "occupancy":
        # per-brick marches derive no per-rank psum budget (a brick's
        # pyramid sees one brick, not the rank's live share)
        from scenery_insitu_tpu import obs as _obs

        _obs.degrade("occupancy.k_budget", "occupancy", "static",
                     "brick-partitioned MXU steps derive no per-rank "
                     "psum budget (slab decompositions only)", warn=False)
    reuse = _resolve_reuse(comp_cfg, supported=bricks is None,
                           where="the brick-partitioned MXU step")

    def composited(local_data, origin, spacing, cam, thr, ru):
        if bricks is not None:
            if waves:
                out, meta, _, thr2 = _mxu_rank_generate_bricks_waves(
                    local_data, origin, spacing, cam, slicer, spec, tf,
                    vdi_cfg, comp_cfg, axis, n, bricks, threshold=thr,
                    topo=topo)
                return out, meta, thr2, None
            vdi, meta, _, thr2 = _mxu_rank_generate_bricks(
                local_data, origin, spacing, cam, slicer, spec, tf,
                vdi_cfg, axis, n, bricks, threshold=thr)
            return (_composite_exchanged(vdi.color, vdi.depth, n, axis,
                                         comp_cfg, topo=topo), meta,
                    thr2, None)
        if waves:
            out, meta, _, thr2, ru2 = _mxu_rank_generate_waves(
                local_data, origin, spacing, cam, slicer, spec, tf,
                vdi_cfg, comp_cfg, axis, n, threshold=thr, plan=plan,
                reuse=ru, reuse_tol=reuse_tol, topo=topo)
            return out, meta, thr2, ru2
        vdi, meta, _, thr2, ru2 = _mxu_rank_generate(
            local_data, origin, spacing, cam, slicer, spec, tf, vdi_cfg,
            axis, n, threshold=thr, comp_cfg=comp_cfg, plan=plan,
            reuse=ru, reuse_tol=reuse_tol,
            ranges=ranges if plan is None else None)
        return (_composite_exchanged(vdi.color, vdi.depth, n, axis,
                                     comp_cfg, topo=topo), meta, thr2,
                ru2)

    def body(local_data, origin, spacing, cam, thr, ru):
        with fold_slot_account(slot_counts and bricks is None
                               and not waves and not reuse) as noted:
            out, meta, thr2, ru2 = composited(local_data, origin, spacing,
                                              cam, thr, ru)
        frame = (leave(out), meta)
        if slot_counts:
            frame += (sum(noted or (), jnp.zeros((2,), jnp.int32))[None],)
        return frame, thr2, ru2

    spec_vol = P(axis, None, None)
    out_vdi, leave = _frame_out(mesh, axis, n, topo,
                                comp_cfg.max_output_supersegments)
    out_meta = VDIMetadata(*(P() for _ in VDIMetadata._fields))
    out_frame = (out_vdi, out_meta) + ((P(axis, None),) if slot_counts
                                       else ())

    if temporal and reuse:
        thr_spec = _thr_state_spec(axis)
        ru_spec = _reuse_state_spec(axis)

        def step(local_data, origin, spacing, cam: Camera, thr, ru):
            return body(local_data, origin, spacing, cam, thr, ru)

        f = shard_map(step, mesh=mesh,
                      in_specs=(spec_vol, P(), P(), P(), thr_spec,
                                ru_spec),
                      out_specs=(out_frame, thr_spec, ru_spec),
                      check_vma=False)
    elif temporal:
        thr_spec = _thr_state_spec(axis)

        def step(local_data, origin, spacing, cam: Camera, thr):
            frame, thr2, _ = body(local_data, origin, spacing, cam, thr,
                                  None)
            return frame, thr2

        f = shard_map(step, mesh=mesh,
                      in_specs=(spec_vol, P(), P(), P(), thr_spec),
                      out_specs=(out_frame, thr_spec),
                      check_vma=False)
    elif reuse:
        ru_spec = _reuse_state_spec(axis)

        def step(local_data, origin, spacing, cam: Camera, ru):
            frame, _, ru2 = body(local_data, origin, spacing, cam, None,
                                 ru)
            return frame, ru2

        f = shard_map(step, mesh=mesh,
                      in_specs=(spec_vol, P(), P(), P(), ru_spec),
                      out_specs=(out_frame, ru_spec),
                      check_vma=False)
    else:
        def step(local_data, origin, spacing, cam: Camera):
            return body(local_data, origin, spacing, cam, None, None)[0]

        f = shard_map(step, mesh=mesh,
                      in_specs=(spec_vol, P(), P(), P()),
                      out_specs=out_frame, check_vma=False)
    return jax.jit(f)


def distributed_volume_ranges_mxu(mesh: Mesh, spec,
                                  axis_name: Optional[str] = None):
    """Jitted ``f(vol_data (z-sharded), origin, spacing) -> (lo, hi)``:
    every rank's occupancy ranges of its own even slab for ``spec``'s
    march (`occupancy.volume_ranges`), rank-stacked f32[n, nchunks, nt].
    They depend on the field and the spec alone, so a session whose
    field never changes computes them once per march regime and hands
    them to the step builder (``ranges``), as the reference builds its
    octree once per dataset (VolumeFromFileExample.kt:226-327)."""
    from scenery_insitu_tpu.ops import occupancy as _occ

    axis, n, _ = resolve_mesh_topology(mesh, axis_name)

    def ranges(local_data, origin, spacing):
        vol = _rank_slab(local_data, origin, spacing, spec, axis, n)[0]
        return tuple(x[None] for x in _occ.volume_ranges(vol, spec))

    return jax.jit(shard_map(
        ranges, mesh=mesh, in_specs=(P(axis, None, None), P(), P()),
        out_specs=(P(axis, None, None),) * 2, check_vma=False))


def _thr_state_spec(axis):
    """Sharding spec of the distributed temporal ThresholdState: each
    rank's [nj, ni] maps stack on a leading rank axis → global
    [n*nj, ni] arrays, rank-sharded."""
    from scenery_insitu_tpu.ops import supersegments as ss

    return ss.ThresholdState(
        *(P(axis, None) for _ in ss.ThresholdState._fields))


def distributed_initial_threshold_mxu(mesh: Mesh, tf: TransferFunction,
                                      spec,
                                      vdi_cfg: Optional[VDIConfig] = None,
                                      axis_name: Optional[str] = None,
                                      plan=None, bricks=None):
    """Jitted seeder for `distributed_vdi_step_mxu_temporal`: one
    histogram counting march per rank on its own slab. Returns
    ``f(vol_data (z-sharded), origin, spacing, cam) -> ThresholdState``
    with rank-stacked [n*nj, ni] maps (``bricks``: one map set per
    brick slot, row-stacked like the step carries them)."""
    from scenery_insitu_tpu.ops import slicer

    vdi_cfg = vdi_cfg or VDIConfig()
    axis, n, _ = resolve_mesh_topology(mesh, axis_name)
    # the seeding march must run the SAME render decomposition the step
    # it seeds will march (no CompositeConfig here, so the mode is
    # implied by the plan/brick map itself)
    plan = _resolve_plan("occupancy", n, plan)
    bricks = _resolve_bricks("bricks", n, bricks)

    def seed(local_data, origin, spacing, cam: Camera):
        if bricks is not None:
            units, gmax, _, ref = _brick_units(local_data, origin,
                                               spacing, spec, axis, n,
                                               bricks)
            axcam = slicer.make_axis_camera(ref, cam, spec,
                                            box_min=origin, box_max=gmax)
            return _stack_thr([
                slicer.initial_threshold(
                    vol, tf, cam, spec, vdi_cfg,
                    box_min=origin, box_max=gmax,
                    v_bounds=vb, w_bounds=wb,
                    axcam=(axcam if f == 1
                           else axcam._replace(dwm=axcam.dwm * f)),
                    step_scale=1.0 / f)
                for vol, vb, wb, f in units])
        vol, gmax, v_bounds, w_bounds, _ = _rank_slab(
            local_data, origin, spacing, spec, axis, n, plan=plan)
        return slicer.initial_threshold(vol, tf, cam, spec, vdi_cfg,
                                        box_min=origin, box_max=gmax,
                                        v_bounds=v_bounds,
                                        w_bounds=w_bounds)

    f = shard_map(seed, mesh=mesh,
                  in_specs=(P(axis, None, None), P(), P(), P()),
                  out_specs=_thr_state_spec(axis), check_vma=False)
    return jax.jit(f)


def distributed_vdi_step_mxu_temporal(mesh: Mesh, tf: TransferFunction,
                                      spec,
                                      vdi_cfg: Optional[VDIConfig] = None,
                                      comp_cfg: Optional[CompositeConfig]
                                      = None,
                                      axis_name: Optional[str] = None,
                                      plan=None, bricks=None,
                                      reuse_tol: float = 0.0,
                                      topology=None, ranges=None,
                                      slot_counts: bool = False):
    """`distributed_vdi_step_mxu` with carried per-rank temporal threshold
    state (adaptive_mode="temporal": ONE march per rank per frame instead
    of counting + write — see slicer.generate_vdi_mxu_temporal).

    Returns ``f(vol_data (z-sharded), origin, spacing, cam, thr) ->
    ((VDI, meta), thr')`` where thr is the rank-sharded ThresholdState
    from `distributed_initial_threshold_mxu`. Each rank adapts the
    threshold map of its own generation camera footprint; the sort-last
    exchange and composite are unchanged. With ``comp_cfg.temporal_reuse
    == "ranges"`` the signature gains a trailing ``reuse`` carry and
    return (see `distributed_vdi_step_mxu`).
    """
    return _build_mxu_step(mesh, tf, spec, vdi_cfg, comp_cfg, axis_name,
                           temporal=True, plan=plan, bricks=bricks,
                           reuse_tol=reuse_tol, topology=topology,
                           ranges=ranges, slot_counts=slot_counts)


def distributed_hybrid_step_mxu(mesh: Mesh, tf: TransferFunction,
                                spec, vdi_cfg: Optional[VDIConfig] = None,
                                comp_cfg: Optional[CompositeConfig] = None,
                                radius: float = 0.02, stamp: int = 5,
                                colormap: str = "jet",
                                axis_name: Optional[str] = None,
                                temporal: bool = False,
                                plan=None, bricks=None, topology=None):
    """Distributed hybrid volume+particle frame (BASELINE.md Config 5):
    z-sharded volume through the sort-last MXU VDI chain, N-sharded
    tracers through the sort-first splat chain (per-rank z-buffer,
    all_gather, depth-min — ≅ InVisRenderer + Head running concurrently
    with DistributedVolumes), then the particle layer is depth-inserted
    into each rank's composited VDI columns (ops/hybrid.py). One jitted
    SPMD program.

    Returns ``f(vol_data f32[D,H,W] (z-sharded), origin, spacing,
    tracer_world f32[N,3] (N-sharded), tracer_vel f32[N,3] (same), cam)
    -> (image f32[4, Nj, Ni] W-sharded on the virtual grid, meta)``.
    Warp to the display camera with ops.slicer.warp_to_camera.

    ``temporal=True`` threads carried per-rank threshold state through the
    VDI pass exactly like `distributed_vdi_step_mxu_temporal` (seed with
    `distributed_initial_threshold_mxu`): the signature gains a trailing
    ``thr`` argument and the return becomes ``((image, meta), thr')`` —
    the hybrid frame then pays ONE march/frame like the plain VDI path
    (the steady-state economy of DistributedVolumes.kt:683-933).
    """
    from scenery_insitu_tpu.ops import slicer
    from scenery_insitu_tpu.ops.hybrid import composite_vdi_with_particles
    from scenery_insitu_tpu.ops.splat import SplatOutput
    from scenery_insitu_tpu.parallel.particles import sort_first_splat

    vdi_cfg = vdi_cfg or VDIConfig()
    comp_cfg = comp_cfg or CompositeConfig()
    axis, n, topo = resolve_mesh_topology(mesh, axis_name, topology)
    if spec.ni % n:
        raise ValueError(f"intermediate width {spec.ni} not divisible by "
                         f"mesh size {n}")
    waves = _resolve_waves(comp_cfg, n, spec.ni, slicer)
    plan = _resolve_plan(comp_cfg, n, plan)
    _bricks_inert(bricks, "the hybrid step")
    # the hybrid frame re-splats particles every frame anyway; carrying
    # the VDI half's fragments is future work — say so, don't ignore
    _resolve_reuse(comp_cfg, supported=False, where="the hybrid step")

    def body(local_data, origin, spacing, tr_pos, tr_vel, cam, thr):
        if waves:
            # the VDI half runs at tile-wave granularity; the splat half
            # is per-frame (particles are sort-first, exchange-free) and
            # inserts into the ASSEMBLED contiguous column block — the
            # same block the frame schedule composites
            comp, meta, axcam, thr2, _ = _mxu_rank_generate_waves(
                local_data, origin, spacing, cam, slicer, spec, tf,
                vdi_cfg, comp_cfg, axis, n, threshold=thr, plan=plan,
                topo=topo)
        else:
            vdi, meta, axcam, thr2, _ = _mxu_rank_generate(
                local_data, origin, spacing, cam, slicer, spec, tf,
                vdi_cfg, axis, n, threshold=thr, comp_cfg=comp_cfg,
                plan=plan)
            comp = _composite_exchanged(vdi.color, vdi.depth, n, axis,
                                        comp_cfg, topo=topo)
            # [Ko, ·, Nj, Ni/n]

        # sort-first particle pass on the virtual camera's rays
        with _phase("march"):
            sp = sort_first_splat(tr_pos, tr_vel, axis, spec.ni,
                                  spec.nj, radius, stamp, colormap,
                                  view=axcam.view, proj=axcam.proj)

        # my column block of the (replicated) particle layer — under a
        # hierarchical topology the composite hands this rank the block
        # at ranks-major flat position (topology.Topology.out_axis)
        r = _out_block_index(axis, topo)
        wb = spec.ni // n
        img_b = jax.lax.dynamic_slice_in_dim(sp.image, r * wb, wb, axis=2)
        dep_b = jax.lax.dynamic_slice_in_dim(sp.depth, r * wb, wb, axis=1)
        with _phase("merge"):
            hyb = composite_vdi_with_particles(
                comp, SplatOutput(img_b, dep_b))
        return hyb, meta, thr2

    from scenery_insitu_tpu.core.vdi import VDIMetadata
    w_axis = axis if topo is None else topo.out_axis
    out_meta = VDIMetadata(*(P() for _ in VDIMetadata._fields))
    in_base = (P(axis, None, None), P(), P(), P(axis, None), P(axis, None),
               P())

    if temporal:
        thr_spec = _thr_state_spec(axis)

        def step(local_data, origin, spacing, tr_pos, tr_vel, cam: Camera,
                 thr):
            img, meta, thr2 = body(local_data, origin, spacing, tr_pos,
                                   tr_vel, cam, thr)
            return (img, meta), thr2

        f = shard_map(step, mesh=mesh, in_specs=in_base + (thr_spec,),
                      out_specs=((P(None, None, w_axis), out_meta),
                                 thr_spec),
                      check_vma=False)
    else:
        def step(local_data, origin, spacing, tr_pos, tr_vel, cam: Camera):
            img, meta, _ = body(local_data, origin, spacing, tr_pos,
                                tr_vel, cam, None)
            return img, meta

        f = shard_map(step, mesh=mesh, in_specs=in_base,
                      out_specs=(P(None, None, w_axis), out_meta),
                      check_vma=False)
    return jax.jit(f)


def _out_block_index(axis, topo):
    """Traced flat index of this rank's OUTPUT column block: the plain
    axis index on flat meshes; on hierarchical meshes the two-level
    composite hands rank (h, d) the block at ranks-major position
    ``d * H + h`` (topology.Topology.out_axis)."""
    if topo is None:
        return jax.lax.axis_index(axis)
    return (jax.lax.axis_index(topo.ranks_axis) * topo.num_hosts
            + jax.lax.axis_index(topo.hosts_axis))


def distributed_plain_step_mxu(mesh: Mesh, tf: TransferFunction,
                               spec, cfg: Optional[RenderConfig] = None,
                               axis_name: Optional[str] = None,
                               comp_cfg: Optional[CompositeConfig] = None,
                               plan=None, bricks=None, topology=None):
    """Distributed plain-image rendering on the MXU slice-march engine —
    the TPU-fast counterpart of `distributed_plain_step` (the reference's
    non-VDI mode, VolumeRaycaster.comp:94-161 composited by
    PlainImageCompositor.comp; mode switch DistributedVolumeRenderer.kt:
    175-189). Per rank: `render_slices` on its z-slab (banded-matmul
    resampling, no gathers), then the same sort-last column all_to_all +
    nearest-first `composite_plain` as the gather path.

    Returns ``f(vol_data f32[D,H,W] (z-sharded), origin, spacing, cam) ->
    (image f32[4, Nj, Ni] W-sharded on the virtual grid, axcam)``. The
    intermediate image is background-free; warp to the display camera
    (which blends the background exactly once) with
    ``slicer.warp_to_camera(image, axcam, spec, cam, width, height,
    background)``. ``axcam`` is replicated (every rank derives it from the
    shared global box), so the warp runs on the gathered global image.

    ``comp_cfg.exchange``: "all_to_all" (one collective) or "ring" (n-1
    pipelined single-fragment ppermute hops; bitwise-identical output —
    see `_ring_exchange_plain`). ``comp_cfg.wire``: the fragment encoding
    that crosses ICI ("f32" bit-exact | "bf16" | "qpack8" — docs/PERF.md
    "Wire formats"; lossy modes quantize the exchanged RGBA+depth only,
    the composite runs in f32). ``schedule``/``wave_tiles`` (docs/PERF.md
    "Tile waves"): under "waves" each rank `render_slices`-marches one
    column-block wave at a time while the previous wave's fragments
    exchange+composite, sharing one permuted copy and occupancy gate per
    frame. ``rebalance`` + ``plan`` select the uneven render z bands
    (docs/PERF.md "Render rebalancing"). The fields a plain image has no
    use for (``ring_slots`` caps an N*K supersegment accumulator,
    ``k_budget`` re-targets the VDI threshold) are not read.
    """
    from scenery_insitu_tpu.ops import slicer

    cfg = cfg or RenderConfig()
    axis, n, topo = resolve_mesh_topology(mesh, axis_name, topology)
    if spec.ni % n:
        raise ValueError(f"intermediate width {spec.ni} not divisible by "
                         f"mesh size {n}")
    comp_cfg = comp_cfg or CompositeConfig()
    exchange, wire = comp_cfg.exchange, comp_cfg.wire
    wave_tiles = comp_cfg.wave_tiles
    waves = _resolve_waves(comp_cfg, n, spec.ni, slicer)
    # a planned band must be at least as deep as the AO shade halo
    plan = _resolve_plan(comp_cfg, n, plan,
                         min_halo=(cfg.ao_radius + 1
                                   if cfg.ao_strength > 0.0 else 1))
    _bricks_inert(bricks, "the plain-image MXU step")
    _resolve_reuse(comp_cfg, supported=False,
                   where="the plain-image MXU step")

    # distributed AO: pre-shade each rank's slab with TF + occlusion on a
    # radius-deep halo (seam-exact — see _rank_slab's shade hook), then
    # march the pre-shaded volume with tf=None exactly like the
    # single-device MXU AO path (ops/ao.shade_volume_ao)
    ao_on = cfg.ao_strength > 0.0
    if ao_on:
        from scenery_insitu_tpu.ops import ao as _ao

        shade = lambda v: _ao.shade_volume_ao(v, tf, cfg.ao_radius,
                                              cfg.ao_strength)

    def step(local_data, origin, spacing, cam: Camera):
        if ao_on:
            vol, gmax, v_bounds, w_bounds, _ = _rank_slab(
                local_data, origin, spacing, spec, axis, n,
                shade=shade, shade_halo=cfg.ao_radius, plan=plan)
        else:
            vol, gmax, v_bounds, w_bounds, _ = _rank_slab(
                local_data, origin, spacing, spec, axis, n, plan=plan)
        axcam = slicer.make_axis_camera(vol, cam, spec, box_min=origin,
                                        box_max=gmax)
        tf_r = tf if not ao_on else None
        bg = (0.0, 0.0, 0.0, 0.0)
        # rank partials stay background-free; the display warp blends it
        if waves:
            # tile-wave schedule: march ONE column-block wave at a time
            # (u-sliced wave camera), sharing the frame's permuted copy
            # and occupancy gate, while the previous wave's fragments
            # exchange + composite (docs/PERF.md "Tile waves")
            volp = slicer.permute_volume(vol, spec)
            occ = slicer.occupancy_for(vol, tf_r, spec, volp=volp)
            _wave_build_marker(n, wave_tiles, 1, spec.nj, spec.ni, 1,
                               exchange, 0, wire, marched=True)

            def march_wave(w, _):
                axcam_w, spec_w = slicer.wave_camera(axcam, spec, n,
                                                     wave_tiles, w)
                with _phase("march"):
                    out = slicer.render_slices(
                        vol, tf_r, axcam_w, spec_w,
                        cfg.early_exit_alpha, v_bounds=v_bounds,
                        step_scale=cfg.step_scale, occupancy=occ,
                        volp=volp, w_bounds=w_bounds)
                return (out.image, out.depth), None

            img = _composite_plain_waves(
                None, None, n, axis, bg, exchange, wire, wave_tiles,
                march_wave=march_wave, topo=topo)
            return img, axcam
        with _phase("march"):
            out = slicer.render_slices(vol, tf_r, axcam, spec,
                                       cfg.early_exit_alpha,
                                       v_bounds=v_bounds,
                                       step_scale=cfg.step_scale,
                                       w_bounds=w_bounds)
        return _composite_plain_exchanged(out.image, out.depth, n, axis,
                                          bg, exchange, wire,
                                          topo=topo), axcam

    from scenery_insitu_tpu.ops.slicer import AxisCamera
    w_axis = axis if topo is None else topo.out_axis
    out_axcam = AxisCamera(*(P() for _ in AxisCamera._fields))
    f = shard_map(step, mesh=mesh,
                  in_specs=(P(axis, None, None), P(), P(), P()),
                  out_specs=(P(None, None, w_axis), out_axcam),
                  check_vma=False)
    return jax.jit(f)


def distributed_plain_step(mesh: Mesh, tf: TransferFunction,
                           width: int, height: int,
                           cfg: Optional[RenderConfig] = None,
                           axis_name: Optional[str] = None,
                           comp_cfg: Optional[CompositeConfig] = None,
                           plan=None, bricks=None, topology=None):
    """Build the jitted distributed plain-image render step (the reference's
    non-VDI mode: VolumeRaycaster + PlainImageCompositor,
    DistributedVolumeRenderer.kt:175-189). Returns ``f(vol_data, origin,
    spacing, cam) -> image f32[4, height, width]`` sharded by W.
    ``comp_cfg.exchange`` selects the column-exchange schedule
    ("all_to_all" | "ring"), ``wire`` the fragment encoding that crosses
    ICI, and ``schedule``/``wave_tiles`` the frame granularity (the gather
    march is monolithic, so "waves" pipelines exchange against composite
    at column-block granularity) — see `distributed_plain_step_mxu`."""
    cfg = cfg or RenderConfig(width=width, height=height)
    axis, n, topo = resolve_mesh_topology(mesh, axis_name, topology)
    if width % n:
        raise ValueError(f"width {width} not divisible by mesh size {n}")
    comp_cfg = comp_cfg or CompositeConfig()
    exchange, wire = comp_cfg.exchange, comp_cfg.wire
    wave_tiles = comp_cfg.wave_tiles
    waves = _resolve_waves(comp_cfg, n, width)
    plan = _resolve_plan(comp_cfg, n, plan,
                         min_halo=(cfg.ao_radius + 1
                                   if cfg.ao_strength > 0.0 else 1))
    _bricks_inert(bricks, "the plain-image gather step")
    _resolve_reuse(comp_cfg, supported=False,
                   where="the plain-image gather step")

    # rank partials must stay background-free — the background is blended
    # exactly once, by the final composite (blending it per rank would
    # occlude farther ranks for any non-transparent background).
    # ao_strength is zeroed in the RANK config because the per-rank AO
    # field is built here from a RADIUS-DEEP halo (h = ao_radius + 1, so
    # each rank's occlusion blur sees the neighbor's slices; raycast's
    # own cfg-driven field would blur the 1-halo slab and band the
    # seams), then trimmed to the 1-halo extent the raycaster samples —
    # seam-exact vs the single-device AO render.
    rank_cfg = dataclasses.replace(cfg, background=(0.0, 0.0, 0.0, 0.0),
                                   ao_strength=0.0)
    ao_on = cfg.ao_strength > 0.0

    def step(local_data, origin, spacing, cam: Camera) -> jnp.ndarray:
        d_global = local_data.shape[0] * n
        vol, cmin, cmax, smin, smax = _local_volume_and_clip(
            local_data, origin, spacing, d_global, axis, plan=plan)
        ao_vol = None
        if ao_on:
            from scenery_insitu_tpu.ops import ao as _ao

            dn = local_data.shape[0]
            hr = cfg.ao_radius + 1
            if plan is None:
                with _phase("halo"):
                    ext = halo_exchange_z(local_data, axis, h=hr)
                n_keep = dn
            else:
                # the occlusion blur needs the radius-deep halo around
                # the PLANNED band; the trim below keeps the band's
                # 1-halo extent (matches vol.data row-for-row)
                with _phase("halo"):
                    ext = reslab_z(local_data, plan, axis, h=hr)
                n_keep = int(max(plan))
            with _phase("march"):
                occ = _ao.occlusion_field(
                    _ao.tf_alpha(Volume(ext, vol.origin, spacing), tf),
                    cfg.ao_radius, cfg.ao_strength)
            ao_vol = Volume(occ[hr - 1:hr + n_keep + 1], vol.origin,
                            spacing)
        with _phase("march"):
            out = raycast(vol, tf, cam, width, height, rank_cfg,
                          clip_min=cmin, clip_max=cmax, ao_field=ao_vol,
                          sample_min=smin, sample_max=smax)
        if waves:
            return _composite_plain_waves(out.image, out.depth, n, axis,
                                          cfg.background, exchange, wire,
                                          wave_tiles, topo=topo)
        return _composite_plain_exchanged(out.image, out.depth, n, axis,
                                          cfg.background, exchange, wire,
                                          topo=topo)

    w_axis = axis if topo is None else topo.out_axis
    f = shard_map(step, mesh=mesh,
                  in_specs=(P(axis, None, None), P(), P(), P()),
                  out_specs=P(None, None, w_axis), check_vma=False)
    return jax.jit(f)


def shard_volume(data: jnp.ndarray, mesh: Mesh,
                 axis_name: Optional[str] = None) -> jnp.ndarray:
    """Place a global volume onto the mesh z-sharded (host → HBM shards)."""
    axis = axis_name or mesh.axis_names[0]
    return jax.device_put(data, NamedSharding(mesh, P(axis, None, None)))
