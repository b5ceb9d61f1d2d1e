"""Sort-last compositing kernels (SURVEY.md §7 step 4).

- ``composite_vdis``: merge N ranks' sub-VDIs for the same pixels into one
  composited VDI (≅ VDICompositor.comp). The reference does a sequential
  k-way merge with per-process front pointers (VDICompositor.comp:58-91);
  on TPU we instead flatten to N*K segments per pixel, sort by start depth
  (``sort_stream``: ONE stable multi-operand ``lax.sort`` keyed on the
  start depth, the five payload planes riding through it as further
  operands — a sorting network, no divergence, and no permutation to
  apply afterwards), and fold the sorted stream through the shared
  supersegment state machine for re-segmentation.
- ``merge_vdis_pairwise``: the ring-exchange counterpart (docs/PERF.md
  "Exchange modes"): two per-pixel depth-SORTED segment streams interleave
  by searchsorted-style rank selection — no bitonic sort, peak live state
  is the two streams instead of all N·K slots. The ring compositor
  (parallel.pipeline) folds one incoming K-fragment per ``ppermute`` hop
  into its accumulator with this, then re-segments the final stream
  through ``resegment_stream`` — the same backend dispatch + adaptive
  threshold + fold ``composite_vdis`` runs after its global sort, which is
  what makes lossless ring output exactly match the all_to_all path.
- ``composite_plain``: depth-ordered alpha-under of N plain images
  (≅ PlainImageCompositor.comp:35-92).
- ``composite_depth_min``: sort-first min-depth pick across ranks
  (≅ NaiveCompositor.frag / Head.composite, Head.kt:98-134).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from scenery_insitu_tpu.config import CompositeConfig
from scenery_insitu_tpu.core.vdi import VDI
from scenery_insitu_tpu.obs.profiler import phase as _profile_phase
from scenery_insitu_tpu.ops import supersegments as ss


def composite_vdis(colors: jnp.ndarray, depths: jnp.ndarray,
                   cfg: Optional[CompositeConfig] = None,
                   gap_eps: float = 1e-4,
                   assume_sorted: Optional[bool] = None) -> VDI:
    """colors f32[N, K, 4, H, W], depths f32[N, K, 2, H, W] -> VDI[K_out].

    Segments from different ranks are assumed depth-disjoint per pixel up to
    interpolation overlap at domain boundaries (the sort-last invariant the
    reference also relies on); overlapping segments are composited in
    start-depth order.

    ``assume_sorted``: skip the per-pixel depth sort + stale-color masking.
    Defaults to True for N == 1, whose single VDI comes out of generation
    already front-to-back ordered with zeroed empty slots.
    """
    cfg = cfg or CompositeConfig()
    n, k, _, h, w = colors.shape
    nk = n * k
    flat_c = colors.reshape(nk, 4, h, w)
    flat_d = depths.reshape(nk, 2, h, w)

    if assume_sorted is None:
        assume_sorted = (n == 1)
    if assume_sorted:
        sc, sd = flat_c, flat_d
    else:
        with _profile_phase("merge"):
            sc, sd = sort_stream(flat_c, flat_d)

    k_out = cfg.max_output_supersegments

    if (assume_sorted and n == 1 and k_out >= k and cfg.adaptive
            and cfg.backend == "auto"):
        # Single already-segmented ray with enough output slots: the input
        # is returned verbatim (padded to K_out). This intentionally
        # differs from the merge fold, whose adaptive search floor
        # (thr_max / 2^iters) re-merges segments whose RGB differs by up
        # to ~0.03 — pure information loss when everything already fits.
        # Identity is the DEFINED behavior for the default backend; an
        # explicit backend= request ("xla"/"pallas") still runs the real
        # fold so kernel parity checks and timings stay meaningful.
        pad = k_out - k
        color = jnp.concatenate(
            [flat_c, jnp.zeros((pad,) + flat_c.shape[1:], flat_c.dtype)]) \
            if pad else flat_c
        depth = jnp.concatenate(
            [flat_d, jnp.full((pad,) + flat_d.shape[1:], jnp.inf,
                              flat_d.dtype)]) if pad else flat_d
        return VDI(color, depth)

    with _profile_phase("resegment"):
        return resegment_stream(sc, sd, cfg, gap_eps)


def sort_stream(flat_c: jnp.ndarray, flat_d: jnp.ndarray):
    """Per-pixel depth sort + stale-color masking of a stacked segment
    stream — the pre-fold half of ``composite_vdis``, shared with the
    hierarchical composite (parallel/hier.py), whose intra-domain
    accumulator is exactly this sorted masked stream before the
    once-at-the-top re-segmentation.

    ``flat_c`` f32[M, 4, H, W], ``flat_d`` f32[M, 2, H, W] → the same
    shapes sorted by start depth per pixel (empty slots carry +inf start
    so they sort to the back) with non-live slots' colors zeroed (they
    may carry stale payloads). Equal start depths keep their input order
    (rank order — the ring path's local sort relies on it).

    The payload travels with the key: one stable ``lax.sort`` whose only
    key is the start depth and whose other operands are R, G, B, A and
    the end depth, so no permutation is materialised or applied (a
    gather by a sorted index gives the same bits, but runs serially on a
    TPU: 130 ms against 3 for [64, 4|2, 640, 160] on a v5e, PERF.md §6)."""
    start, *rgba, end = jax.lax.sort(
        (flat_d[:, 0], *(flat_c[:, i] for i in range(4)), flat_d[:, 1]),
        dimension=0, is_stable=True, num_keys=1)
    sc = jnp.stack(rgba, axis=1)
    sd = jnp.stack([start, end], axis=1)
    live = jnp.isfinite(start)
    return jnp.where(live[:, None], sc, 0.0), sd


def resolve_backend(cfg: CompositeConfig) -> str:
    """The merge-fold schedule ``cfg.backend`` names on this backend:
    "auto" is the fused Pallas kernel on TPU and the XLA scan elsewhere.
    What Mosaic says about the kernel reaches the caller."""
    if cfg.backend != "auto":
        return cfg.backend
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def resegment_stream(sc: jnp.ndarray, sd: jnp.ndarray,
                     cfg: Optional[CompositeConfig] = None,
                     gap_eps: float = 1e-4) -> VDI:
    """Re-segment one per-pixel depth-SORTED segment stream into at most
    ``cfg.max_output_supersegments`` output supersegments.

    ``sc`` f32[M, 4, H, W] premultiplied colors, ``sd`` f32[M, 2, H, W]
    depth extents, sorted by start depth per pixel with empty slots masked
    (zero color, +inf depth). This is the post-sort half of
    ``composite_vdis`` — backend dispatch, adaptive threshold search and
    the supersegment fold — shared with the ring exchange path
    (parallel.pipeline), whose pairwise-merged accumulator arrives here
    already sorted. Identical streams produce identical output whichever
    path built them.
    """
    cfg = cfg or CompositeConfig()
    _, _, h, w = sc.shape
    k_out = cfg.max_output_supersegments

    if resolve_backend(cfg) == "pallas":
        # fully fused: the adaptive threshold search runs inside the kernel
        from scenery_insitu_tpu.ops.pallas_composite import resegment_sorted
        color, depth = resegment_sorted(
            sc, sd, None, k_out, gap_eps,
            adaptive_iters=cfg.adaptive_iters if cfg.adaptive else 0)
        return VDI(color, depth)

    if cfg.adaptive:
        def count_fn(thr):
            def body(st, item):
                c, d = item
                return ss.push_count(st, thr, c, d[0], d[1], gap_eps), None
            st, _ = jax.lax.scan(body, ss.init_count(h, w), (sc, sd))
            return st.count
        threshold = ss.adaptive_threshold(count_fn, k_out,
                                          cfg.adaptive_iters, h, w)
    else:
        threshold = jnp.zeros((h, w), jnp.float32)

    def body(st, item):
        c, d = item
        return ss.push(st, k_out, threshold, c, d[0], d[1], gap_eps), None

    state, _ = jax.lax.scan(body, ss.init_state(k_out, h, w), (sc, sd))
    color, depth = ss.finalize(state)
    return VDI(color, depth)


def merge_vdis_pairwise(color_a: jnp.ndarray, depth_a: jnp.ndarray,
                        color_b: jnp.ndarray, depth_b: jnp.ndarray,
                        k_cap: Optional[int] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pairwise ordered merge of two per-pixel depth-SORTED segment
    streams (the ring-exchange merge operator; docs/PERF.md "Exchange
    modes").

    ``color_a`` f32[Ka, 4, H, W] / ``depth_a`` f32[Ka, 2, H, W] and the
    ``b`` pair likewise. PRECONDITION: each stream is sorted by start
    depth per pixel (empty slots at +inf — the VDI convention; generation
    output and any previous merge's output both satisfy it) with empty
    slots' colors masked to zero. Unsorted inputs produce garbage — the
    position arithmetic below is only a permutation for sorted inputs.

    This is the sort-last depth-disjointness payoff: because the two
    lists are already ordered, the merged position of every segment is
    its own index plus how many of the OTHER list precede it — a
    searchsorted-style rank selection of O(Ka·Kb) vectorized compares per
    pixel, not an O(M log² M) bitonic network over the concatenation, and
    the only live state is the two input streams (2K slots for two
    K-lists vs the N·K slots the all_to_all sort materializes). Ties
    break toward stream ``a`` (the accumulator), keeping the merge
    deterministic. Payloads move by gather, so depth +inf survives
    bit-exactly (no one-hot arithmetic against inf).

    ``k_cap``: truncate the merged stream to its nearest ``k_cap``
    segments (drop the farthest) — the bounded-memory ring mode
    (CompositeConfig.ring_slots). None keeps all Ka+Kb slots.

    Returns the merged (color [M, 4, H, W], depth [M, 2, H, W]),
    M = min(Ka+Kb, k_cap or Ka+Kb), sorted with empties at the back.
    """
    ka, kb = color_a.shape[0], color_b.shape[0]
    sa, sb = depth_a[:, 0], depth_b[:, 0]                  # [K?, H, W]
    # merged position = own index + count of the other list before me;
    # b_j precedes a_i iff sb_j < sa_i (ties -> a first)
    b_before_a = jnp.sum((sb[None] < sa[:, None]).astype(jnp.int32), axis=1)
    a_before_b = jnp.sum((sa[None] <= sb[:, None]).astype(jnp.int32), axis=1)
    ia = jax.lax.broadcasted_iota(jnp.int32, (ka, 1, 1), 0)
    ib = jax.lax.broadcasted_iota(jnp.int32, (kb, 1, 1), 0)
    pos = jnp.concatenate([ia + b_before_a, ib + a_before_b], axis=0)
    m = ka + kb
    m_out = m if k_cap is None else min(int(k_cap), m)
    # invert the permutation by an O(M) scatter (pos is a permutation of
    # 0..M-1 per pixel for sorted inputs, so every update is in bounds),
    # then GATHER payloads — depth +inf must survive bit-exactly, so no
    # arithmetic ever touches the payload values. Truncation = dropping
    # the output slots past m_out (the farthest segments).
    in_ids = jnp.broadcast_to(
        jax.lax.broadcasted_iota(jnp.int32, (m, 1, 1), 0), pos.shape)
    inv = jnp.put_along_axis(jnp.zeros_like(pos), pos, in_ids, axis=0,
                             inplace=False)[:m_out]        # [M_out, H, W]
    all_c = jnp.concatenate([color_a, color_b], axis=0)
    all_d = jnp.concatenate([depth_a, depth_b], axis=0)
    color = jnp.take_along_axis(all_c, inv[:, None], axis=0)
    depth = jnp.take_along_axis(all_d, inv[:, None], axis=0)
    return color, depth


def modeled_exchange_traffic(n: int, k: int, height: int, width: int,
                             k_out: Optional[int] = None,
                             mode: str = "all_to_all", ring_slots: int = 0,
                             itemsize: int = 4,
                             wire: str = "f32",
                             schedule: str = "frame",
                             wave_tiles: int = 1) -> dict:
    """Modeled per-rank bytes of the sort-last exchange + composite for
    one frame — the composite counterpart of
    ``sim.pallas_stencil.modeled_sim_traffic`` (usable
    off-TPU), consumed by ``benchmarks/composite_bench.py`` and the ring
    build's obs event.

    ``ici_bytes_per_rank`` is the wire traffic each rank ships (n-1
    K-fragments of its W/n column block — identical in both modes; the
    ring only changes WHEN it moves and what must be live meanwhile). It
    scales with the per-component ``wire`` itemsizes
    (``ops.wire.WIRE_SLOT_BYTES``): f32 24 B/slot, bf16 12, qpack8 6 —
    the model matches what the pipeline actually ships (qpack8's 8-byte
    per-fragment [near, far] sideband is scalar noise and excluded).
    ``peak_stream_slots_per_pixel`` is the per-pixel working set of the
    merge: the all_to_all path materializes and sorts all N·K received
    slots; the capped ring holds ring_slots + K (accumulator + incoming
    fragment, e.g. 2K at ring_slots=K); the lossless ring (ring_slots=0)
    grows back to N·K by the last hop. ``stream_bytes_per_rank`` is that
    working set PLUS the resegmented ``k_out``-slot output write, both in
    f32 ``itemsize`` — the composite always decodes to and folds in f32,
    so HBM stream bytes do not shrink with the wire.

    ``schedule="waves"`` (+ ``wave_tiles``; docs/PERF.md "Tile waves")
    adds the overlap accounting of the tile-wave pipeline: total wire
    bytes are unchanged (every fragment still crosses ICI once), but the
    exchange is issued per column-block wave and each wave's collective
    flies while the NEXT wave marches — so the bytes of waves 0..T-2 are
    hidden behind march compute and only the LAST wave's exchange (plus
    wave 0's march) stays exposed on the critical path:
    ``ici_bytes_hidden_per_rank = (T-1)/T`` of the total, and the
    per-pixel merge working set is unchanged (waves split columns, not
    slots).
    """
    from scenery_insitu_tpu.ops.wire import wire_slot_bytes

    wb = max(width // max(n, 1), 1)
    cb, db = wire_slot_bytes(wire)        # per-slot wire bytes (color, depth)
    seg = 6 * itemsize                    # 4 color + 2 depth f32 HBM lanes
    frag = k * height * wb * (cb + db)
    if mode == "ring" and ring_slots:
        slots = min(int(ring_slots), n * k) + k
    else:
        slots = n * k
    out = {
        "mode": mode, "ranks": n, "k": k,
        "k_out": k_out, "ring_slots": ring_slots,
        "wire": wire,
        "schedule": schedule,
        "wire_color_bytes_per_slot": cb,
        "wire_depth_bytes_per_slot": db,
        "ici_bytes_per_rank": (n - 1) * frag,
        "peak_stream_slots_per_pixel": slots,
        "stream_bytes_per_rank": (slots + (k_out or 0)) * height * wb * seg,
    }
    if schedule == "waves":
        t = max(int(wave_tiles), 1)
        # split the TOTAL so hidden + exposed always equals
        # ici_bytes_per_rank — a tiling the pipeline would reject
        # (wb % t != 0) still yields a self-consistent model, with the
        # remainder charged to the exposed (last) wave
        total = out["ici_bytes_per_rank"]
        per_wave = total // t
        hidden = (t - 1) * per_wave
        out["wave_tiles"] = t
        out["ici_bytes_per_wave_per_rank"] = per_wave
        # waves 0..T-2 circulate while wave 1..T-1 march; the last wave's
        # exchange has no next march to hide behind
        out["ici_bytes_hidden_per_rank"] = hidden
        out["ici_bytes_exposed_per_rank"] = total - hidden
        out["overlap_hidden_frac"] = round(hidden / total, 4) if total \
            else 0.0
    return out


def composite_plain(images: jnp.ndarray, depths: jnp.ndarray,
                    background: Tuple[float, ...] = (0, 0, 0, 0)
                    ) -> jnp.ndarray:
    """images f32[N, 4, H, W] premultiplied, depths f32[N, H, W] (+inf for
    empty pixels) -> composited f32[4, H, W] by per-pixel nearest-first
    alpha-under (≅ PlainImageCompositor.comp:35-92)."""
    order = jnp.argsort(depths, axis=0)                    # [N, H, W]
    sorted_imgs = jnp.take_along_axis(images, order[:, None], axis=0)

    def body(acc, src):
        return acc + (1.0 - acc[3:4]) * src, None

    acc, _ = jax.lax.scan(body, jnp.zeros_like(images[0]), sorted_imgs)
    bg = jnp.asarray(background, jnp.float32).reshape(4, 1, 1)
    return acc + (1.0 - acc[3:4]) * bg


def composite_depth_min(images: jnp.ndarray, depths: jnp.ndarray
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sort-first composite: per pixel, take the rank whose fragment is
    nearest (≅ the head node's NaiveCompositor min-depth selection,
    NaiveCompositor.frag:15-28). Returns (image [4,H,W], depth [H,W])."""
    idx = jnp.argmin(depths, axis=0)                       # [H, W]
    img = jnp.take_along_axis(images, idx[None, None], axis=0)[0]
    d = jnp.take_along_axis(depths, idx[None], axis=0)[0]
    return img, d
