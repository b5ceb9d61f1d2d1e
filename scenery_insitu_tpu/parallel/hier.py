"""Hierarchical two-level sort-last composite across ICI domains over DCN
(docs/MULTIHOST.md; ROADMAP item 3 — the scale-out plane).

The flat pipeline composites all N ranks in one exchange, which assumes
every pair of ranks shares a fast link (one ICI domain). Past one domain
the fabric splits into a fast intra-domain level and a slow inter-domain
(DCN) level, and the composite must split with it — the "Scalable Ray
Tracing Using the Distributed FrameBuffer" shape (PAPERS.md): dense
collective compositing inside the fast domain, compressed tile exchange
between domains, incremental head assembly.

Two implementations of the same two-level algebra live here:

- **Device path** (`hier_composite_vdi` / `hier_composite_plain`): runs
  inside one SPMD program on a 2-D ``(hosts, ranks)`` mesh
  (parallel/topology.py). Level 1 exchanges fragments over the *ranks*
  sub-axis (ICI — ring or all_to_all per ``CompositeConfig.exchange``,
  the existing machinery verbatim) but STOPS before re-segmentation,
  leaving each rank a per-pixel sorted [D*K]-slot accumulator of its
  column block. Level 2 circulates column sub-blocks of those
  accumulators over the *hosts* sub-axis (DCN — a pipelined ring with
  its own wire codec, ``TopologyConfig.dcn_wire``) and merges them
  pairwise. Re-segmentation happens ONCE, at the top — which is what
  makes a hierarchical frame match the flat composite (bitwise on the
  f32 gather path; tests/test_topology.py). On one process the 2-D mesh
  over the virtual device list EMULATES the hierarchy; on a multi-pod
  runtime XLA lowers hosts-axis collectives onto DCN.

- **Host path** (`domain_partial_vdi_step` + `publish_partial_tiles` +
  `HierTileAssembler`): for runtimes whose backend cannot run
  cross-process device collectives (the CPU backend of the multiprocess
  CI harness — testing/multiproc.py) or when the DCN hop should ride the
  delivery plane. Each host runs level 1 on its LOCAL mesh, fetches the
  domain-partial accumulator, and ships its column blocks to the head as
  qpack8/delta-compressed tile streams on the PR-11 sequenced+CRC
  substrate (runtime/streaming.VDIPublisher.publish_tile); the head
  merges each tile's H partials as they arrive — incremental assembly,
  the `multihost.gather_vdi_tiles` shape generalized to merge rather
  than concatenate — and re-segments once. A lost host follows the PR-11
  failure semantics: the head composes WITHOUT it, degraded, rather than
  stalling the fleet (docs/MULTIHOST.md "Failure semantics").
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from scenery_insitu_tpu.config import CompositeConfig, VDIConfig
from scenery_insitu_tpu.core.vdi import VDI
from scenery_insitu_tpu.obs.profiler import phase as _phase
from scenery_insitu_tpu.core.volume import Volume
from scenery_insitu_tpu.ops.composite import (composite_plain,
                                              resegment_stream,
                                              sort_stream)
from scenery_insitu_tpu.parallel.mesh import halo_exchange_z
from scenery_insitu_tpu.parallel.topology import Topology
from jax import shard_map

GAP_EPS = 1e-4


# ---------------------------------------------------------- traffic model

def modeled_dcn_traffic(num_hosts: int, domain_size: int, k: int,
                        height: int, width: int, dcn_wire: str = "f32",
                        ring_slots: int = 0) -> dict:
    """Modeled DCN bytes of the inter-domain hop for one frame — the
    hosts-level counterpart of ``ops.composite.modeled_exchange_traffic``
    (consumed by the hier build event, benchmarks/scaling_bench.py and
    benchmarks/modeled_projection.py).

    What crosses DCN is the level-1 accumulator: ``D * K`` slots per
    pixel lossless, ``min(D*K, ring_slots)`` under a capped ring (the
    pairwise merge truncates the accumulator to the cap — the ``+ K``
    incoming-fragment term of ``peak_stream_slots_per_pixel`` is live
    MEMORY during the merge, not shipped bytes). Each rank ships its
    ``1/(D*H)`` column sub-block to the other ``H - 1`` domains in the
    hosts-axis ring, encoded at the ``dcn_wire`` slot widths. Per-host
    numbers sum the domain's D ranks. Sent == received (a ring moves
    every block exactly once per hop)."""
    from scenery_insitu_tpu.ops.wire import wire_slot_bytes

    cb, db = wire_slot_bytes(dcn_wire)
    m = domain_size * k
    if ring_slots:
        m = min(int(ring_slots), m)
    sub = max(width // max(domain_size * num_hosts, 1), 1)
    per_rank = (num_hosts - 1) * m * height * sub * (cb + db)
    return {
        "hosts": num_hosts, "domain_size": domain_size, "k": k,
        "dcn_wire": dcn_wire, "slots_per_pixel": m,
        "dcn_bytes_sent_per_rank": per_rank,
        "dcn_bytes_sent_per_host": domain_size * per_rank,
        "dcn_bytes_received_per_host": domain_size * per_rank,
    }


def _hier_build_marker(topo: Topology, k: int, h: int, w: int,
                       comp_cfg) -> None:
    """Host-side trace-time marker of one two-level composite build
    (docs/OBSERVABILITY.md): one counter per build plus an event carrying
    the modeled intra-domain (ICI) and inter-domain (DCN) traffic."""
    from scenery_insitu_tpu import obs as _obs
    from scenery_insitu_tpu.ops.composite import modeled_exchange_traffic

    rec = _obs.get_recorder()
    rec.count("hier_composite_builds")
    rec.event(
        "hier_composite_build", hosts=topo.num_hosts,
        domain_size=topo.domain_size, k=k, dcn_wire=topo.dcn_wire,
        ici=modeled_exchange_traffic(
            topo.domain_size, k, h, w,
            k_out=comp_cfg.max_output_supersegments,
            mode=comp_cfg.exchange, ring_slots=comp_cfg.ring_slots,
            wire=comp_cfg.wire),
        dcn=modeled_dcn_traffic(topo.num_hosts, topo.domain_size, k, h, w,
                                dcn_wire=topo.dcn_wire,
                                ring_slots=comp_cfg.ring_slots))


# ------------------------------------------------------------ device path

def domain_accumulate(color: jnp.ndarray, depth: jnp.ndarray, d: int,
                      ranks_axis: str, comp_cfg) -> Tuple[jnp.ndarray,
                                                          jnp.ndarray]:
    """Level 1 — the intra-domain (ICI) exchange, stopped BEFORE
    re-segmentation: this rank's 1/d column block as a per-pixel sorted,
    empty-masked accumulator of the domain's fragments ([D*K] slots
    lossless; ``ring_slots`` caps the ring accumulator exactly as in the
    flat schedule). Runs inside shard_map over the domain's mesh axis —
    the 2-D mesh's ranks sub-axis on the device path, a per-host local
    mesh on the host path."""
    from scenery_insitu_tpu.parallel.pipeline import (_exchange_vdi_columns,
                                                      _ring_accumulate,
                                                      _ring_cap)

    k = color.shape[0]
    if comp_cfg.exchange == "ring" and d > 1:
        with _phase("merge"):
            color, depth = sort_stream(color, depth)
        return _ring_accumulate(color, depth, d, ranks_axis,
                                comp_cfg.wire, _ring_cap(comp_cfg, k))
    colors, depths = _exchange_vdi_columns(color, depth, d, ranks_axis,
                                           comp_cfg.wire)
    flat_c = colors.reshape((d * k,) + colors.shape[2:])
    flat_d = depths.reshape((d * k,) + depths.shape[2:])
    with _phase("merge"):
        return sort_stream(flat_c, flat_d)


def hier_composite_vdi(color: jnp.ndarray, depth: jnp.ndarray,
                       topo: Topology, comp_cfg,
                       gap_eps: float = GAP_EPS) -> VDI:
    """The two-level sort-last VDI composite (device path; runs inside
    shard_map over the 2-D ``(hosts, ranks)`` mesh). Level 1 accumulates
    the domain's fragments over ICI, level 2 ring-merges the domain
    accumulators' column sub-blocks over DCN (``dcn_wire`` encoded), and
    the merged stream re-segments ONCE — so lossless configurations
    reproduce the flat composite exactly (the parity contract,
    tests/test_topology.py). Returns the composited VDI of this rank's
    final column block (ranks-major layout — ``Topology.out_axis``)."""
    from scenery_insitu_tpu.parallel.pipeline import _ring_accumulate

    _hier_build_marker(topo, color.shape[0], color.shape[-2],
                       color.shape[-1], comp_cfg)
    acc_c, acc_d = domain_accumulate(color, depth, topo.domain_size,
                                     topo.ranks_axis, comp_cfg)
    if topo.num_hosts > 1:
        # level 2: the accumulator is already sorted + masked — circulate
        # its column sub-blocks around the hosts (DCN) ring, lossless
        # merge (the wire codec is the DCN byte lever, not truncation)
        acc_c, acc_d = _ring_accumulate(
            acc_c, acc_d, topo.num_hosts, topo.hosts_axis, topo.dcn_wire,
            None, hop_counter="dcn_hops_built", hop_event="dcn_hop",
            hop_scope="dcn_hop")
    with _phase("resegment"):
        return resegment_stream(acc_c, acc_d, comp_cfg, gap_eps)


def hier_composite_plain(image: jnp.ndarray, depth: jnp.ndarray,
                         topo: Topology, background,
                         exchange: str, wire: str) -> jnp.ndarray:
    """The two-level plain-image composite (device path): level 1
    exchanges the domain's RGBA+depth fragments over ICI and folds them
    nearest-first into a background-free domain partial (alpha-under is
    associative over depth-ordered groups — domains are disjoint z
    bands, so the partial's min depth orders the level-2 merge), level 2
    circulates the partials over the hosts (DCN) ring at ``dcn_wire``
    precision and folds them WITH the background, exactly once."""
    from scenery_insitu_tpu import obs as _obs
    from scenery_insitu_tpu.parallel.pipeline import (_encoded_all_to_all,
                                                      _exchange_columns,
                                                      _ring_exchange_plain)
    from scenery_insitu_tpu.ops import wire as _wire

    d, h = topo.domain_size, topo.num_hosts
    rec = _obs.get_recorder()
    rec.count("hier_composite_builds")
    if exchange == "ring" and d > 1:
        images, depths = _ring_exchange_plain(image, depth, d,
                                              topo.ranks_axis, wire)
    elif wire == "f32":
        images = _exchange_columns(image, d, topo.ranks_axis)
        depths = _exchange_columns(depth, d, topo.ranks_axis)
    else:
        images, depths = _encoded_all_to_all(
            image, depth, d, topo.ranks_axis,
            lambda i, z: _wire.encode_plain(i, z, wire),
            lambda i, z, s: _wire.decode_plain(i, z, s, wire))
    with _phase("merge"):
        partial = composite_plain(images, depths, (0.0, 0.0, 0.0, 0.0))
    pdepth = jnp.min(depths, axis=0)        # nearest contribution, +inf empty
    if h == 1:
        bg = jnp.asarray(background, jnp.float32).reshape(4, 1, 1)
        return partial + (1.0 - partial[3:4]) * bg
    imgs2, deps2 = _ring_exchange_plain(
        partial, pdepth, h, topo.hosts_axis, topo.dcn_wire,
        hop_counter="dcn_hops_built", build_counter="hier_plain_levels",
        hop_scope="dcn_hop")
    with _phase("merge"):
        return composite_plain(imgs2, deps2, background)


# -------------------------------------------------------------- host path

def _offset_slab_and_clip(local_data, origin, spacing, d_global: int,
                          axis: str, n_local: int, rank_offset,
                          halo_lo, halo_hi):
    """`pipeline._local_volume_and_clip`'s multi-process twin: this
    LOCAL rank's halo-padded Volume and exclusive clip AABB when the
    local mesh covers only ranks ``[rank_offset, rank_offset + n_local)``
    of an ``n_total``-rank global decomposition. Cross-host halo rows
    (``halo_lo``/``halo_hi``, each [1, H, W]) replace the clamped copies
    on the host-boundary ranks — pass the host's own boundary slice at
    the global edges to keep the single-device CLAMP_TO_EDGE semantics,
    and the neighbor host's boundary slice elsewhere (the harness ships
    them host-side; one slice per seam per frame)."""
    rl = jax.lax.axis_index(axis)
    r = rank_offset + rl                               # global rank
    dn = local_data.shape[0]
    dz = spacing[2]
    halo = halo_exchange_z(local_data, axis)           # [Dn+2, H, W]
    bottom = jnp.where(jnp.equal(rl, 0), halo_lo, halo[:1])
    top = jnp.where(jnp.equal(rl, n_local - 1), halo_hi, halo[-1:])
    halo = jnp.concatenate([bottom, halo[1:-1], top], axis=0)
    local_origin = origin.at[2].add((r * dn - 1) * dz)
    z_lo = origin[2] + r * dn * dz
    z_hi = origin[2] + (r + 1) * dn * dz
    vol = Volume(halo, local_origin, spacing)
    hh, w = local_data.shape[1], local_data.shape[2]
    gmax = origin + jnp.array([w, hh, d_global], jnp.float32) * spacing
    clip_min = jnp.stack([origin[0], origin[1], z_lo])
    clip_max = jnp.stack([gmax[0], gmax[1], z_hi])
    return vol, clip_min, clip_max, origin, gmax


def domain_partial_vdi_step(mesh, tf, width: int, height: int,
                            vdi_cfg: Optional[VDIConfig] = None,
                            comp_cfg: Optional[CompositeConfig] = None,
                            max_steps: int = 256,
                            axis_name: Optional[str] = None,
                            rank_offset: int = 0,
                            n_total: Optional[int] = None):
    """Build THIS HOST's half of the two-level composite (host path):
    generate on the host's slice of the global z decomposition, exchange
    + merge over the LOCAL mesh (level 1, ICI), and return the
    domain-partial accumulator — NOT re-segmented; that happens once, on
    the head, after the DCN hop (`HierTileAssembler`).

    Returns ``f(local_data f32[D_host, H, W] (z-sharded on the local
    mesh), origin f32[3] (GLOBAL), spacing f32[3], cam, halo_lo
    f32[1, H, W], halo_hi f32[1, H, W]) -> (acc_color [M, 4, height,
    width], acc_depth [M, 2, height, width])`` W-sharded over the local
    mesh, ``M = D_local * K`` (or ring_slots + K capped). ``rank_offset``
    / ``n_total`` place the host in the global decomposition (process p
    of H hosts with D-rank domains passes ``rank_offset=p*D,
    n_total=H*D``)."""
    from scenery_insitu_tpu.ops.vdi_gen import generate_vdi

    vdi_cfg = vdi_cfg or VDIConfig()
    comp_cfg = comp_cfg or CompositeConfig()
    axis = axis_name or mesh.axis_names[0]
    d = mesh.shape[axis]
    nt = n_total or d
    if width % (d or 1):
        raise ValueError(f"width {width} not divisible by the local mesh "
                         f"size {d}")

    def step(local_data, origin, spacing, cam, halo_lo, halo_hi):
        d_global = local_data.shape[0] * nt
        vol, cmin, cmax, smin, smax = _offset_slab_and_clip(
            local_data, origin, spacing, d_global, axis, d, rank_offset,
            halo_lo, halo_hi)
        vdi, _ = generate_vdi(vol, tf, cam, width, height, vdi_cfg,
                              max_steps=max_steps, clip_min=cmin,
                              clip_max=cmax, sample_min=smin,
                              sample_max=smax)
        return domain_accumulate(vdi.color, vdi.depth, d, axis, comp_cfg)

    f = shard_map(step, mesh=mesh,
                  in_specs=(P(axis, None, None), P(), P(), P(), P(), P()),
                  out_specs=(P(None, None, None, axis),
                             P(None, None, None, axis)),
                  check_vma=False)
    return jax.jit(f)


def publish_partial_tiles(pub, acc_c, acc_d, meta, tiles: int) -> int:
    """Ship one host's domain-partial accumulator over DCN as the PR-11
    tile stream (docs/MULTIHOST.md "DCN wire protocol"): ``tiles``
    column blocks through ``VDIPublisher.publish_tile`` — seq + epoch +
    CRC continuity, optional qpack8 pre-codec and temporal-delta records
    all inherited from the substrate. Returns the wire bytes sent
    (counted on the ``dcn_bytes_sent`` obs counter, one ``dcn_send``
    span per tile)."""
    from scenery_insitu_tpu import obs as _obs

    c = np.ascontiguousarray(np.asarray(acc_c))
    d = np.ascontiguousarray(np.asarray(acc_d))
    wb = c.shape[-1] // tiles
    rec = _obs.get_recorder()
    sent = 0
    for t in range(tiles):
        with rec.span("dcn_send", frame=int(np.asarray(meta.index)),
                      tile=t):
            nb = pub.publish_tile(
                VDI(c[..., t * wb:(t + 1) * wb],
                    d[..., t * wb:(t + 1) * wb]),
                meta, tile=t, tiles=tiles, col0=t * wb)
        rec.count("dcn_bytes_sent", nb)
        sent += nb
    return sent


def merge_partial_blocks(parts: List[Tuple[np.ndarray, np.ndarray]],
                         comp_cfg, gap_eps: float = GAP_EPS) -> VDI:
    """Head-side top of the two-level composite: merge the H domains'
    partial accumulators for the SAME columns into the final composited
    block — concatenate, per-pixel sort, re-segment ONCE (the same fold
    the flat composite runs after its global sort, so a complete merge
    is parity-exact with the flat frame). Jitted per shape on the head's
    local device."""
    flat_c = jnp.concatenate([jnp.asarray(c) for c, _ in parts], axis=0)
    flat_d = jnp.concatenate([jnp.asarray(z) for _, z in parts], axis=0)
    return _merge_resegment(flat_c, flat_d, comp_cfg, gap_eps)


def _merge_resegment(flat_c, flat_d, comp_cfg, gap_eps):
    from functools import partial

    @partial(jax.jit, static_argnums=(2, 3))
    def run(c, z, cfg, eps):
        sc, sd = sort_stream(c, z)
        return resegment_stream(sc, sd, cfg, eps)

    return run(flat_c, flat_d, comp_cfg, gap_eps)


class HierTileAssembler:
    """Incremental head-node assembly of the hosts' domain-partial tile
    streams — ``multihost.gather_vdi_tiles`` generalized from
    concatenation to a sort-last MERGE (docs/MULTIHOST.md): feed each
    arriving ``(host, vdi, meta, tile)`` from the per-host
    `VDISubscriber.receive_tile`; the moment a column block has all
    ``num_hosts`` partials it merges + re-segments and is emitted — the
    head publishes the first columns while later tiles are still in
    flight.

    A host that stays silent past ``frame window`` frames follows the
    PR-11 HeadNode semantics: `flush_incomplete` composes the block from
    the partials that DID arrive, stamps it degraded and ledgers
    ``multihost.host_down`` — one lost host costs its slab's content,
    not the frame."""

    def __init__(self, num_hosts: int, comp_cfg=None,
                 gap_eps: float = GAP_EPS):
        self.num_hosts = num_hosts
        self.comp_cfg = comp_cfg or CompositeConfig()
        self.gap_eps = gap_eps
        # (frame, tile) -> {host: (color, depth)}
        self._parts: Dict[Tuple[int, int], Dict[int, tuple]] = {}
        self.stats = {"tiles_in": 0, "blocks_out": 0, "degraded": 0,
                      "dcn_bytes_received": 0}

    def add(self, host: int, vdi, meta, tile: dict,
            nbytes: int = 0) -> List[tuple]:
        """Feed one received tile; returns the finished blocks it
        completes as ``[(frame, tile_idx, col0, VDI, degraded)]``."""
        from scenery_insitu_tpu import obs as _obs

        rec = _obs.get_recorder()
        frame = int(np.asarray(meta.index))
        key = (frame, int(tile["tile"]))
        self.stats["tiles_in"] += 1
        if nbytes:
            self.stats["dcn_bytes_received"] += nbytes
            rec.count("dcn_bytes_received", nbytes)
        slot = self._parts.setdefault(key, {})
        slot[int(host)] = (np.asarray(vdi.color), np.asarray(vdi.depth),
                           int(tile["col0"]))
        if len(slot) < self.num_hosts:
            return []
        return [self._emit(key, degraded=False)]

    def _emit(self, key, degraded: bool) -> tuple:
        from scenery_insitu_tpu import obs as _obs

        slot = self._parts.pop(key)
        col0 = next(iter(slot.values()))[2]
        with _obs.get_recorder().span("dcn_merge", frame=key[0],
                                      tile=key[1]):
            out = merge_partial_blocks(
                [(c, d) for c, d, _ in
                 (slot[h] for h in sorted(slot))],
                self.comp_cfg, self.gap_eps)
        self.stats["blocks_out"] += 1
        if degraded:
            self.stats["degraded"] += 1
        return (key[0], key[1], col0, out, degraded)

    def flush_incomplete(self) -> List[tuple]:
        """Compose every pending block from the partials that arrived —
        the lost-host degraded path (PR-11 HeadNode semantics): emitted
        blocks carry ``degraded=True`` and each missing host lands on
        the ledger as ``multihost.host_down``."""
        from scenery_insitu_tpu import obs as _obs

        out = []
        for key in sorted(self._parts):
            missing = self.num_hosts - len(self._parts[key])
            _obs.degrade(
                "multihost.host_down", f"{self.num_hosts} hosts",
                f"{self.num_hosts - missing} hosts",
                "a host's domain partial never arrived; the block "
                "composites without its slab content (degraded)",
                warn=False)
            out.append(self._emit(key, degraded=True))
        return out


def assemble_hier_frame(subs, num_hosts: int, comp_cfg=None,
                        tiles: Optional[int] = None,
                        timeout_ms: int = 10_000,
                        gap_eps: float = GAP_EPS):
    """Convenience head loop over per-host subscribers: drain ``tiles``
    column blocks from every host's stream, merge incrementally, return
    the assembled frame ``(VDI, degraded)`` in column order. ``subs`` is
    ``{host_index: VDISubscriber}``. Hosts that time out degrade (their
    content is dropped, the frame still assembles) — the chaos-tested
    PR-11 contract rather than a fleet-wide stall."""
    import time as _time

    asm = HierTileAssembler(num_hosts, comp_cfg, gap_eps)
    done: Dict[int, tuple] = {}
    want: Optional[int] = tiles
    deadline = _time.monotonic() + timeout_ms / 1000.0
    alive = dict(subs)
    while alive and (want is None or len(done) < want):
        if _time.monotonic() > deadline:
            break
        for host, sub in list(alive.items()):
            got = sub.receive_tile(timeout_ms=200)
            if got is None or hasattr(got, "kind"):      # timeout / drop
                continue
            vdi, meta, tile = got
            if tile is None:
                continue
            if want is None:
                want = int(tile["tiles"])
            nb = getattr(sub, "last_recv_bytes", 0)
            for frame, t, col0, block, deg in asm.add(host, vdi, meta,
                                                      tile, nbytes=nb):
                done[t] = (col0, block, deg)
    degraded = False
    for frame, t, col0, block, deg in asm.flush_incomplete():
        if t not in done:
            done[t] = (col0, block, deg)
            degraded = True
    if not done:
        return None, True
    blocks = [done[t] for t in sorted(done)]
    color = np.concatenate([np.asarray(b.color) for _, b, _ in blocks],
                           axis=-1)
    depth = np.concatenate([np.asarray(b.depth) for _, b, _ in blocks],
                           axis=-1)
    degraded = degraded or any(d for _, _, d in blocks)
    return VDI(color, depth), degraded
