"""MXU slice-march volume rendering — the TPU-native raycaster core.

The reference raycasts per pixel through GPU texture hardware: every march
step does a trilinear texture fetch at an arbitrary world position
(reference VDIGenerator.comp:333-529, VolumeRaycaster.comp:94-161). The
literal translation — per-step random gathers into the ``[D, H, W]``
volume — is the one access pattern a TPU cannot run fast: XLA lowers it to
serialized HBM gathers (measured ~19 s/frame at 256³, 720p, 256 steps on a
v5e chip). GPUs have texture units; TPUs have a 128×128 systolic array.
So this module re-derives volume raycasting as matrix multiplication:

1. Pick the volume axis ``w`` most aligned with the view direction
   (`choose_axis`) and build a **virtual axis-aligned camera**: same eye,
   looking straight down ``w``, off-axis frustum whose *near plane is the
   nearest slice plane* and covers the whole volume footprint
   (`make_axis_camera`). This is the shear-warp factorization of the view
   transform, MXU-style.
2. March slice by slice, front to back. Because every virtual-camera ray
   passes through the eye, its crossing of slice ``w = z`` is a uniform
   scale-and-shift of the intermediate pixel grid (scale ``s(z) =
   depth(z)/depth(ref plane)``), so resampling a slice onto the whole ray
   bundle is **separable bilinear** — two banded interpolation matrices
   applied as ``Wv @ slice @ Wuᵀ``, built on the fly from ``iota`` and run
   on the MXU. The hot loop contains no gathers at all.
3. The per-slice samples feed any per-pixel fold: alpha-under
   accumulation (plain image, ≅ AccumulatePlainImage.comp) or the
   supersegment counting/writing machines (VDI generation,
   ≅ AccumulateVDI.comp) — the same folds the gather-path raycaster uses.
4. Outputs live on the virtual camera's pixel grid, and the virtual
   camera's projection/view matrices go into `VDIMetadata`, so every
   downstream consumer — sort-last compositor, novel-view VDI renderer,
   streaming — works unchanged. For display, `warp_to_camera` reprojects
   to the real camera: both cameras share an eye, so the warp is an exact
   plane-induced homography (depth-independent, no parallax error).

Sampling schedule vs the gather path: samples land exactly on slice
planes (in-plane bilinear, exact in ``w``) instead of at uniform
per-ray parameter steps; opacity correction by the per-ray inter-slice
path length (`adjust_opacity`) makes the accumulated integral agree —
parity is asserted by tests/test_slicer.py.

The march axis and intermediate resolution are static (compile-time):
an orbiting camera triggers at most one recompile per (axis, sign)
regime, cached by jit.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from scenery_insitu_tpu.config import SliceMarchConfig, VDIConfig
from scenery_insitu_tpu.core.camera import Camera, frustum, look_at
from scenery_insitu_tpu.core.transfer import TransferFunction
from scenery_insitu_tpu.core.vdi import VDI, VDIMetadata
from scenery_insitu_tpu.core.volume import Volume, value_scale
from scenery_insitu_tpu.obs.profiler import in_phase as _in_phase
from scenery_insitu_tpu.obs.profiler import \
    note_fold_chunks as _note_fold_chunks
from scenery_insitu_tpu.obs.profiler import \
    note_fold_slots as _note_fold_slots
from scenery_insitu_tpu.obs.profiler import phase as _phase
from scenery_insitu_tpu.ops import pallas_seg as psg
from scenery_insitu_tpu.ops import seg_fold as sf
from scenery_insitu_tpu.ops import supersegments as ss
from scenery_insitu_tpu.ops.raycast import RaycastOutput, nominal_step
from scenery_insitu_tpu.ops.sampling import adjust_opacity

# xyz axis index -> data dim of Volume.data [..., z, y, x], counted from
# the END so an optional leading channel dim (pre-shaded RGBA volumes)
# never shifts the lookup
_DATA_DIM = {0: -1, 1: -2, 2: -3}
# march axis -> (u axis, v axis), both xyz indices
_UV = {2: (0, 1), 1: (0, 2), 0: (1, 2)}


@dataclasses.dataclass(frozen=True)
class AxisSpec:
    """Static (compile-time) parameters of a slice march."""

    axis: int                 # march axis, xyz index (0=x, 1=y, 2=z)
    sign: int                 # +1: march toward +axis; -1: toward -axis
    ni: int                   # intermediate image width (u direction)
    nj: int                   # intermediate image height (v direction)
    chunk: int = 16           # slices folded per scan step
    matmul_dtype: str = "bf16"   # resampling matmul operand dtype
    s_floor: float = 1e-3     # min depth ratio: slices closer are dropped
    skip_empty: bool = True   # chunk_occupancy-based empty-space skipping
    # supersegment-fold schedule: "xla" (sequential machine, lax.scan:
    # the reference) | "pallas_fused" (the segmented-scan fold's VMEM
    # kernel shading the march's value plane itself) | "pallas_seg" (the
    # same kernel fed the shaded chunk); per march `fold_schedule` says
    # which feed runs
    fold: str = "xla"
    # storage dtype of the marched volume copy: "bf16" makes
    # `permute_volume` emit a bf16 march layout — volume bytes halve for
    # every march (and for the distributed halo exchange) while all
    # accumulation stays f32 (the resampling einsum sets
    # preferred_element_type=f32 and the folds run f32 throughout)
    render_dtype: str = "f32"
    # in-plane occupancy granularity: 0 = whole-chunk skipping only;
    # N > 0 additionally splits each slice plane into N row (v) tiles and
    # skips the resampling matmuls + TF for OUTPUT row blocks whose
    # bilinear support lies entirely in empty tiles (≅ the reference's
    # per-(8x8 pixel, z-interval) OctreeCells skip,
    # VDIGenerator.comp:232-254 — here at (chunk x v-tile) granularity,
    # the axis the banded-matmul factorization can gate with static
    # shapes). Conservative: gated blocks are provably zero-alpha.
    vtiles: int = 0

    @property
    def u_axis(self) -> int:
        return _UV[self.axis][0]

    @property
    def v_axis(self) -> int:
        return _UV[self.axis][1]


def resolve_engine(engine: str) -> str:
    """Resolve a render-engine name ("auto" | "mxu" | "gather") against the
    current backend; raises on anything else so typos can't silently bench
    the wrong engine."""
    if engine == "auto":
        return "mxu" if jax.default_backend() == "tpu" else "gather"
    if engine not in ("mxu", "gather"):
        raise ValueError(f"unknown render engine {engine!r} "
                         "(expected 'auto', 'mxu' or 'gather')")
    return engine


def march_axis(eye, target) -> Tuple[int, int]:
    """(axis, sign) of the volume axis most aligned with target - eye, in
    float64 on the host. A leaf that lives on the device is read back
    here; a session whose camera arrived as host floats passes those
    (runtime/session.camera_regime)."""
    d = np.asarray(target, np.float64) - np.asarray(eye, np.float64)
    axis = int(np.argmax(np.abs(d)))
    return axis, (1 if d[axis] >= 0 else -1)


def choose_axis(cam: Camera) -> Tuple[int, int]:
    """Pick the volume axis most aligned with the view direction (host-side,
    concrete camera). Returns (axis, sign)."""
    return march_axis(cam.eye, cam.target)


def make_spec(cam: Camera, vol_shape: Tuple[int, int, int],
              cfg: Optional[SliceMarchConfig] = None,
              axis_sign: Optional[Tuple[int, int]] = None,
              multiple_of: int = 1) -> AxisSpec:
    """Build the static spec for a camera + volume shape ([D, H, W]).

    ``multiple_of``: round the intermediate dims up to this multiple — the
    distributed pipeline needs ni divisible by the mesh size for its
    width-axis all_to_all."""
    cfg = cfg or SliceMarchConfig()
    axis, sign = axis_sign or choose_axis(cam)
    u_axis, v_axis = _UV[axis]
    dims_xyz = (vol_shape[2], vol_shape[1], vol_shape[0])
    step = int(8 * multiple_of // np.gcd(8, multiple_of))
    rnd = lambda n: max(step, int(-(-int(n * cfg.scale) // step)) * step)
    # bf16 matmuls are MXU-native on TPU but emulated (slowly) on CPU
    dtype = cfg.matmul_dtype
    if dtype == "bf16" and jax.default_backend() != "tpu":
        dtype = "f32"
    ni = rnd(dims_xyz[u_axis])
    nj = rnd(dims_xyz[v_axis])
    fold = cfg.fold
    if fold == "auto":
        # On TPU the default is the segmented-scan fold's Pallas VMEM
        # twin fed the march's one-channel VALUE plane, which it shades
        # itself (the shaded rgba chunk never crosses HBM); a march that
        # has no scalar volume or no concrete transfer function takes
        # the same kernel's shaded feed instead (`fold_schedule`, per
        # march). Its counting march runs psg.count_multi_chunk; what
        # Mosaic says about either kernel reaches the caller. On CPU the
        # sequential machine wins (state lives in cache, and seg's
        # K-masked reductions are real extra compute on a scalar core —
        # measured 3x slower at 64x96^2), so tests and the virtual mesh
        # keep "xla".
        fold = "pallas_fused" if jax.default_backend() == "tpu" else "xla"
    if fold not in ("xla", "pallas_seg", "pallas_fused"):
        raise ValueError(f"unknown fold schedule {fold!r} (expected 'auto', "
                         "'xla', 'pallas_seg' or 'pallas_fused')")
    # resolve the benched auto default (-1): in-plane tiling pays on the
    # TPU march (the A/B in benchmarks/occupancy_bench.py — sparse
    # fields skip most cells) but adds nt lax.cond branches per chunk,
    # pure overhead for the CPU/test path, which keeps chunk-only
    # skipping unless a tile count is configured explicitly
    vt = cfg.occupancy_vtiles
    if vt < 0:
        from scenery_insitu_tpu.config import OCCUPANCY_VTILES_DEFAULT

        vt = (OCCUPANCY_VTILES_DEFAULT
              if jax.default_backend() == "tpu" else 0)
    # clamp the tile count to what the geometry supports: each band needs
    # >= 2 volume rows (the apron + a zero-size reduction guard) and each
    # output block >= 2 rows — a too-large request degrades to coarser
    # tiles instead of an obscure trace-time error, and the degradation
    # goes on the fallback ledger (it silently coarsens skip granularity;
    # distributed slabs re-clamp again in occupancy.resolved_tiles)
    if vt:
        vt_req = vt
        vt = max(1, min(vt, dims_xyz[v_axis] // 2, nj // 2))
        # ledger only EXPLICITLY configured counts the geometry cannot
        # honor — the auto default clamping on a small grid is the
        # default adapting, not a configuration silently ignored
        if vt < vt_req and cfg.occupancy_vtiles > 0:
            from scenery_insitu_tpu import obs

            obs.degrade("occupancy.vtiles_clamp", str(vt_req), str(vt),
                        f"volume v extent {dims_xyz[v_axis]} / grid nj "
                        f"{nj} support at most {vt} bands of >= 2 rows",
                        warn=False)
    return AxisSpec(axis=axis, sign=sign, ni=ni, nj=nj,
                    chunk=cfg.chunk, matmul_dtype=dtype,
                    s_floor=cfg.s_floor, skip_empty=cfg.skip_empty,
                    fold=fold, vtiles=vt, render_dtype=cfg.render_dtype)


class AxisCamera(NamedTuple):
    """The traced (per-frame) state of the virtual axis-aligned camera.
    All fields are jnp arrays; pairs with a static `AxisSpec`."""

    eye_uvw: jnp.ndarray   # f32[3] eye in (u, v, w) component order
    view: jnp.ndarray      # f32[4, 4]  (goes into VDIMetadata)
    proj: jnp.ndarray      # f32[4, 4]  off-axis frustum projection
    u_grid: jnp.ndarray    # f32[Ni] world u of intermediate pixel columns
    v_grid: jnp.ndarray    # f32[Nj] world v of intermediate pixel rows
    zp: jnp.ndarray        # f32[] eye→reference-plane distance (near plane)
    w0: jnp.ndarray        # f32[] world w of marched slice 0 (= ref plane)
    dwm: jnp.ndarray       # f32[] signed world w step per marched slice
    far: jnp.ndarray       # f32[]

    @property
    def eye_u(self):
        return self.eye_uvw[0]

    @property
    def eye_v(self):
        return self.eye_uvw[1]

    @property
    def eye_w(self):
        return self.eye_uvw[2]

    def ray_lengths(self) -> jnp.ndarray:
        """f32[Nj, Ni]: distance from the eye to each reference-plane grid
        point = the ray parameter t at depth ratio s == 1."""
        du = self.u_grid - self.eye_u
        dv = self.v_grid - self.eye_v
        return jnp.sqrt(dv[:, None] ** 2 + du[None, :] ** 2 + self.zp ** 2)


def permute_volume(vol: Volume, spec: AxisSpec) -> jnp.ndarray:
    """Volume data -> march layout ``[S, (ch,) Nv, Nu]`` (slice, optional
    channels, in-plane v, u) in STORAGE order along the slice dim: the
    array itself for a z march, one transpose for an x or y march. It is
    never flipped and never padded: `slice_march` and the occupancy pass
    walk it front to back by ``spec.sign`` (`march_chunks`), so a z march
    reads the field where it lives and a march toward -axis costs no
    reversed copy. A leading channel dim of pre-shaded RGBA volumes moves
    BEHIND the slice dim so the march can slab-slice on dim 0.

    ``spec.render_dtype == "bf16"`` emits the march layout in bf16 — the
    copy every march reads halves in HBM (XLA CSEs the one cast+transpose
    across the occupancy pass and the marches of a frame); accumulation
    downstream stays f32. An integer field (a raw file's dtype,
    `core.volume.value_scale`) stays as it is."""
    data = vol.data
    if spec.render_dtype == "bf16" and data.dtype == jnp.float32:
        data = data.astype(jnp.bfloat16)
    nd = data.ndim
    perm3 = {2: (0, 1, 2), 1: (1, 0, 2), 0: (2, 0, 1)}[spec.axis]
    if nd == 3 and perm3 == (0, 1, 2):
        return data
    dims = [nd - 3 + p for p in perm3]
    return jnp.transpose(data, [dims[0]] + list(range(nd - 3)) + dims[1:])


def march_chunks(volp: jnp.ndarray, spec: AxisSpec):
    """How a march walks the storage-order layout ``volp`` in chunks of
    ``spec.chunk`` slices, front to back: ``(nchunks, fetch)``, where
    ``fetch(ci)`` is marched chunk ``ci`` (``ci`` may be traced): storage
    slices ``[ci*c, (ci+1)*c)`` for sign > 0, and for sign < 0
    ``[S-(ci+1)*c, S-ci*c)`` reversed — a reverse of one chunk, fused
    into its consumer, where a flipped layout was a copy of the volume.
    Where ``c`` does not divide the slice count S, the last chunk is the
    remaining ``S % c`` marched slices zero-padded to ``c``: one
    chunk-sized array made beside the loop and selected in for that one
    ``ci`` (the select reads it beside every chunk's window: a chunk's
    bytes again, never the volume's), so a depth that is no chunk
    multiple pads no volume. One implementation for the march and every
    occupancy pass, so chunk boundaries can never disagree."""
    c = spec.chunk
    s_total = volp.shape[0]
    nfull, rem = divmod(s_total, c)
    fwd = spec.sign > 0

    def window(ci):
        start = ci * c if fwd else s_total - (ci + 1) * c
        sl = jax.lax.dynamic_slice_in_dim(volp, start, c, 0)
        return sl if fwd else jnp.flip(sl, axis=0)

    if not rem:
        return nfull, window
    last = volp[s_total - rem:] if fwd else jnp.flip(volp[:rem], axis=0)
    tail = jnp.concatenate(
        [last, jnp.zeros((c - rem,) + volp.shape[1:], volp.dtype)], axis=0)
    if not nfull:
        return 1, lambda ci: tail
    return nfull + 1, lambda ci: jnp.where(ci == nfull, tail, window(ci))


def make_axis_camera(vol: Volume, cam: Camera, spec: AxisSpec,
                     box_min: Optional[jnp.ndarray] = None,
                     box_max: Optional[jnp.ndarray] = None) -> AxisCamera:
    """Build the virtual camera for this frame (all values traced).

    box_min/box_max override the footprint AABB — the distributed pipeline
    passes the *global* volume AABB so every rank shares one intermediate
    grid (a requirement for the sort-last column exchange)."""
    a, ua, va = spec.axis, spec.u_axis, spec.v_axis
    box_min = vol.world_min if box_min is None else box_min
    box_max = vol.world_max if box_max is None else box_max

    eye = cam.eye
    ew, eu, ev = eye[a], eye[ua], eye[va]
    dw = vol.spacing[a]

    # nearest slice plane (= reference/near plane) and signed march step.
    # NOTE: w0 is derived from the *global* box when given, so all ranks of
    # a decomposed volume agree on the slice ladder.
    gw0 = box_min[a]
    gw1 = box_max[a]
    w0 = jnp.where(spec.sign > 0, gw0 + 0.5 * dw, gw1 - 0.5 * dw)
    dwm = spec.sign * dw

    zp = jnp.maximum(spec.sign * (w0 - ew), dw)            # eye may sit inside

    # static unit basis of the virtual camera
    fwd = np.zeros(3, np.float32)
    fwd[a] = spec.sign
    up = np.zeros(3, np.float32)
    up[va] = 1.0
    right = np.cross(fwd, up)
    true_up = np.cross(right, fwd)
    right_u = float(right[ua])                             # exactly ±1
    up_v = float(true_up[va])

    fwd_j = jnp.asarray(fwd)
    right_j = jnp.asarray(right)
    true_up_j = jnp.asarray(true_up)

    view = look_at(eye, eye + fwd_j, jnp.asarray(up))

    # off-axis frustum covering the box footprint projected from the eye
    # onto the reference plane (corners closer than the plane clamp to it)
    xs, ys, zs = [], [], []
    for bits in range(8):
        c = jnp.stack([(box_max if bits >> d & 1 else box_min)[d]
                       for d in range(3)])
        rel = c - eye
        ze = jnp.dot(rel, fwd_j)
        zec = jnp.maximum(ze, zp)
        xs.append(jnp.dot(rel, right_j) * zp / zec)
        ys.append(jnp.dot(rel, true_up_j) * zp / zec)
        zs.append(ze)
    xs, ys, zs = jnp.stack(xs), jnp.stack(ys), jnp.stack(zs)
    mu = vol.spacing[ua]
    mv = vol.spacing[va]
    l, r = jnp.min(xs) - mu, jnp.max(xs) + mu
    b, t = jnp.min(ys) - mv, jnp.max(ys) + mv
    r = jnp.maximum(r, l + 1e-5)
    t = jnp.maximum(t, b + 1e-5)
    far = jnp.maximum(jnp.max(zs), zp * 1.001) + dw

    proj = frustum(l, r, b, t, zp, far)

    # intermediate pixel grids, consistent with the projection: column i
    # center ↔ ndc_x = 2(i+.5)/Ni - 1; row j center ↔ ndc_y = 1 - 2(j+.5)/Nj
    ndc_x = (jnp.arange(spec.ni, dtype=jnp.float32) + 0.5) / spec.ni * 2 - 1
    ndc_y = 1.0 - (jnp.arange(spec.nj, dtype=jnp.float32) + 0.5) / spec.nj * 2
    u_grid = eu + (ndc_x * (r - l) + (r + l)) * 0.5 * right_u
    v_grid = ev + (ndc_y * (t - b) + (t + b)) * 0.5 * up_v

    return AxisCamera(eye_uvw=jnp.stack([eu, ev, ew]), view=view, proj=proj,
                      u_grid=u_grid, v_grid=v_grid, zp=zp, w0=w0, dwm=dwm,
                      far=far)


# ------------------------------------------------------------- tile waves


def wave_block(ni: int, n_ranks: int, wave_tiles: int) -> int:
    """Column width of one tile wave's per-rank block: the intermediate
    width splits into ``n_ranks`` rank-owned blocks, each into
    ``wave_tiles`` tiles (docs/PERF.md "Tile waves"). Raises when the
    geometry does not divide — the wave schedule needs exact blocks."""
    if ni % (n_ranks * wave_tiles):
        raise ValueError(
            f"intermediate width {ni} not divisible by ranks*wave_tiles "
            f"= {n_ranks}*{wave_tiles} (pick wave_tiles so every rank's "
            f"{ni // n_ranks if n_ranks and ni % n_ranks == 0 else ni}"
            f"-column block splits evenly)")
    return ni // (n_ranks * wave_tiles)


def wave_cols(x: jnp.ndarray, n_ranks: int, wave_tiles: int, w):
    """Slice the trailing (width) axis of ``x [..., Ni]`` to tile wave
    ``w``'s columns: for each of the ``n_ranks`` rank-owned blocks, the
    w-th of ``wave_tiles`` sub-tiles → ``[..., n_ranks * wb]``. ``w``
    may be traced (the wave scan's induction variable)."""
    ni = x.shape[-1]
    wb = wave_block(ni, n_ranks, wave_tiles)
    # reshaped dims: x.shape[:-1] + (n_ranks @ x.ndim-1, T @ x.ndim, wb)
    g = x.reshape(x.shape[:-1] + (n_ranks, wave_tiles, wb))
    g = jax.lax.dynamic_index_in_dim(g, w, axis=x.ndim, keepdims=False)
    return g.reshape(x.shape[:-1] + (n_ranks * wb,))


def wave_update_cols(x: jnp.ndarray, xw: jnp.ndarray, n_ranks: int,
                     wave_tiles: int, w) -> jnp.ndarray:
    """Inverse of `wave_cols`: scatter wave ``w``'s columns ``xw
    [..., n_ranks * wb]`` back into ``x [..., Ni]`` (the temporal
    threshold maps update only the wave they marched)."""
    ni = x.shape[-1]
    wb = wave_block(ni, n_ranks, wave_tiles)
    g = x.reshape(x.shape[:-1] + (n_ranks, wave_tiles, wb))
    upd = xw.reshape(xw.shape[:-1] + (n_ranks, 1, wb))
    g = jax.lax.dynamic_update_index_in_dim(g, upd, w, axis=x.ndim)
    return g.reshape(x.shape)


def wave_camera(axcam: AxisCamera, spec: AxisSpec, n_ranks: int,
                wave_tiles: int, w) -> Tuple[AxisCamera, AxisSpec]:
    """Column-sliced (AxisCamera, AxisSpec) of tile wave ``w``.

    Every virtual-camera column is an independent ray fan (the banded
    resampling matrices are built per output column from ``u_grid``), so
    marching a subset of columns is exactly the column slice of the full
    march — the wave camera just carries wave ``w``'s ``n_ranks * wb``
    u-grid entries (one ``wb``-wide tile per rank-owned block, so the
    sliced frame still splits into n rank blocks for the sort-last
    exchange). The spec's ``ni`` shrinks to match; everything else
    (march axis, chunking, fold, occupancy gating — all u-independent)
    is reused, as are the frame's one ``permute_volume`` copy and
    occupancy pyramid. ``w`` may be traced."""
    ug = wave_cols(axcam.u_grid, n_ranks, wave_tiles, w)
    return (axcam._replace(u_grid=ug),
            dataclasses.replace(spec, ni=ug.shape[-1]))


def slice_march_wave(vol: Volume, tf: TransferFunction, axcam: AxisCamera,
                     spec: AxisSpec, consume: Callable, carry0,
                     n_ranks: int, wave_tiles: int, w, **kwargs):
    """Tile-scoped `slice_march`: march only tile wave ``w``'s column
    blocks (docs/PERF.md "Tile waves"). Accepts every `slice_march`
    keyword — pass the frame's shared ``volp`` (permute_volume copy) and
    ``occupancy`` (the per-frame pyramid gate, u-independent) so T waves
    cost one permuted copy and one pyramid, not T."""
    axcam_w, spec_w = wave_camera(axcam, spec, n_ranks, wave_tiles, w)
    return slice_march(vol, tf, axcam_w, spec_w, consume, carry0, **kwargs)


# ------------------------------------------------------------------ march


def _axis_params(vol: Volume, spec: AxisSpec):
    """(origin, spacing, count) of the u and v axes of this volume."""
    ua, va = spec.u_axis, spec.v_axis
    nu = vol.data.shape[_DATA_DIM[ua]]
    nv = vol.data.shape[_DATA_DIM[va]]
    return (vol.origin[ua], vol.spacing[ua], nu,
            vol.origin[va], vol.spacing[va], nv)


def byte_planes(x: jnp.ndarray):
    """An unsigned-integer array as its bytes, most significant first:
    each plane holds 0..255 at ``x``'s dtype, so it is exact in bfloat16
    (8 bits of mantissa) where the value itself is not, and
    ``sum(plane_k * 256 ** (n - 1 - k))`` is ``x``."""
    return [(x >> (8 * k)) & 0xFF for k in reversed(range(x.dtype.itemsize))]


def operand_planes(dtype, matmul_dtype: str = "bf16") -> int:
    """As how many matmul operands a chunk of a field of ``dtype`` is
    resampled: its bytes for an integer wider than one that meets bf16
    matmuls (`resample_wide`), else 1 (the chunk itself; with
    ``slicer.matmul_dtype=f32`` a wide integer is one f32 operand at
    `Precision.HIGHEST`, `slice_march`)."""
    dtype = jnp.dtype(dtype)
    wide = value_scale(dtype) != 1.0 and matmul_dtype == "bf16"
    return dtype.itemsize if wide else 1


def rounded_row_sums(w: jnp.ndarray) -> jnp.ndarray:
    """Row sums ``[C, M]`` of an interpolation matrix ``[C, M, n]`` as a
    bf16 matmul sees it: 1 within 2^-9 where the f32 rows sum to 1 (0
    outside the volume)."""
    return w.astype(jnp.bfloat16).astype(jnp.float32).sum(-1)


def resample_wide(wv: jnp.ndarray, slices: jnp.ndarray, wu: jnp.ndarray,
                  wu_sums: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """``einsum("cjy,cyx,cix->cji", wv, slices, wu)`` in f32 for a chunk
    of integers wider than a byte (u16), in the file's own units, with
    bf16 matmul operands and no precision asked above the default.

    One bf16 operand keeps 8 of the 16 bits (and an f32 operand goes
    through a TPU's MXU as ONE bf16 pass at the default precision, so it
    keeps no more), so the chunk is contracted with ``wv`` byte plane by
    byte plane (`byte_planes`: each exact in bf16) and the planes are
    recombined, 256 * hi + lo, on the f32 accumulator. The f32
    intermediate then meets ``wu`` as TWO bf16 terms, ``t1 = bf16(t)``
    and ``t2 = bf16(t - t1)`` (16 bits of mantissa between them: one
    rounded term would lose the low byte again, one matmul later).
    ``t1`` is taken with `lax.reduce_precision`, not with a cast there
    and back: XLA may drop such a cast pair as excess precision, and on
    a TPU it does (``t - t1`` was 0 there and the frame read as with one
    rounded term: PERF.md, PR 49). The
    result is divided by the row sums of the weights AS ROUNDED:
    a bf16 weight pair sums to 1 only within 2^-9, an error
    proportional to the VALUE (57 counts of 65,535 at mid range, as
    much as the operand's own rounding), where renormalised it is one
    of the sample's position alone (``wu_sums``: `rounded_row_sums` of
    ``wu`` where the caller has them: `slice_march` takes them once a
    chunk, not once a row block)."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    wv_m, wu_m = wv.astype(bf16), wu.astype(bf16)
    t = None
    for plane in byte_planes(slices):
        tp = jnp.einsum("cjy,cyx->cjx", wv_m, plane.astype(bf16),
                        preferred_element_type=f32)
        t = tp if t is None else t * 256.0 + tp
    t1 = jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)
    val = sum(jnp.einsum("cjx,cix->cji", part.astype(bf16), wu_m,
                         preferred_element_type=f32)
              for part in (t1, t - t1))
    if wu_sums is None:
        wu_sums = rounded_row_sums(wu)
    # a row outside the volume sums to 0, and so does its result
    inv = lambda sums: 1.0 / jnp.maximum(sums, 1e-6)
    return val * inv(rounded_row_sums(wv))[:, :, None] \
        * inv(wu_sums)[:, None, :]


def _interp_matrix(pos: jnp.ndarray, origin, spacing, n: int,
                   bounds: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None
                   ) -> jnp.ndarray:
    """Banded bilinear interpolation weights for world positions ``pos
    [C, M]`` against voxel rows 0..n-1 → ``[C, M, n]``. Clamp-to-edge
    inside the volume extent, zero outside; `bounds` further restricts to a
    half-open world interval (domain-decomposition ownership).
    ``origin``/``spacing`` may be scalars or per-chunk [C] arrays (the
    novel-view renderer resamples slices whose grids scale per slice)."""
    origin = jnp.reshape(origin, (-1, 1)) if jnp.ndim(origin) else origin
    spacing = jnp.reshape(spacing, (-1, 1)) if jnp.ndim(spacing) else spacing
    x = (pos - origin) / spacing - 0.5
    valid = (x >= -0.5) & (x <= n - 0.5)
    if bounds is not None:
        valid &= (pos >= bounds[0]) & (pos < bounds[1])
    xc = jnp.clip(x, 0.0, n - 1.0)
    cols = jax.lax.broadcasted_iota(jnp.float32, (1, 1, n), 2)
    w = jnp.maximum(0.0, 1.0 - jnp.abs(xc[..., None] - cols))
    return w * valid[..., None].astype(jnp.float32)


def chunk_occupancy(vol: Volume, tf: TransferFunction, spec: AxisSpec,
                    alpha_eps: float = 1e-5,
                    volp: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """bool[nchunks]: can the slab of ``spec.chunk`` slices contribute any
    opacity? The TPU-native occupancy structure (≅ the reference's
    OctreeCells grid, VDIGenerator.comp:232-254 + GridCellsToZero.comp —
    but computed in one cheap reduction pass per frame instead of
    atomic-add during the march, and consumed by `slice_march` to skip
    whole chunks). Conservative: in-plane bilinear resampling keeps values
    inside each slice's [min, max], so a slab whose value range maps to
    zero alpha everywhere (``tf.max_alpha_in``) is provably invisible.

    Since ISSUE 6 this (and the vtile refinement below) is the nt=1
    level of the shared occupancy pyramid — ops/occupancy.py owns the
    band-range machinery; ``volp`` shares one permuted copy per frame."""
    from scenery_insitu_tpu.ops import occupancy as _occ

    return _occ.pyramid_from_volume(vol, tf, spec, volp=volp,
                                    alpha_eps=alpha_eps, ntiles=1).chunks


def chunk_occupancy_vtiles(vol: Volume, tf: TransferFunction,
                           spec: AxisSpec, alpha_eps: float = 1e-5,
                           volp: Optional[jnp.ndarray] = None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(bool[nchunks], bool[nchunks, vtiles]): chunk- and
    (chunk x v-row-band)-granular occupancy in ONE pass over the volume —
    the in-plane refinement of `chunk_occupancy` (≅ OctreeCells' per-cell
    skip, VDIGenerator.comp:232-254), with the chunk level derived from
    the same per-band value ranges (identical to the separate whole-slab
    reduction, at no extra volume traffic).

    Each band's range carries a ONE-ROW APRON into its neighbors: an
    output row's bilinear support is two adjacent volume rows which may
    straddle a band boundary, and the interpolated value lies between
    values in the gap of the two bands' ranges — with a band-pass (non-
    monotone) transfer function that gap can hit an alpha peak neither
    apron-less band sees. The apron makes every adjacent-row pair fully
    contained in at least one band, restoring the conservative argument.
    Tiles split the VOLUME's v axis; the last band absorbs the remainder.

    The tile count re-clamps against THIS volume's v extent
    (occupancy.resolved_tiles — distributed ranks march slabs far
    smaller than the global shape `make_spec` clamped against; the
    reduction lands on the fallback ledger). Consumers read the count
    from the array's shape, so the clamp propagates automatically.
    Implementation lives in ops/occupancy.py (the shared pyramid)."""
    from scenery_insitu_tpu.ops import occupancy as _occ

    pyr = _occ.pyramid_from_volume(vol, tf, spec, volp=volp,
                                   alpha_eps=alpha_eps)
    return pyr.chunks, pyr.tiles


def fold_schedule(spec: AxisSpec, vol: Volume, tf) -> str:
    """The fold schedule THIS march runs. ``pallas_fused`` (what ``auto``
    means on a TPU) hands the kernel the resampled value plane and bakes
    the transfer function's knots in, so it needs a scalar volume and a
    concrete TF. A pre-shaded volume (``vol.data.ndim == 4``: it has no
    TF) and a TF that is traced (a caller that jits over it) take the
    same kernel's shaded feed, ``pallas_seg``: one algorithm, its input
    form chosen from what the march is given. Every other schedule runs
    as configured."""
    if spec.fold == "pallas_fused" and (
            vol.data.ndim == 4 or not psg.tf_is_concrete(tf)):
        return "pallas_seg"
    return spec.fold


def _note_write_fold(volp: jnp.ndarray, spec: AxisSpec, fold: str) -> None:
    """Tell a recorded step how many chunks its write march folds,
    whether the kernel shades them, and as how many operand planes a
    chunk is resampled (counters ``fold_chunks`` / ``fold_chunks_fused``
    / ``march_operand_planes``, obs/profiler.scoped_step)."""
    _note_fold_chunks(-(-volp.shape[0] // spec.chunk),
                      fold == "pallas_fused",
                      operand_planes(volp.dtype, spec.matmul_dtype))


def occupancy_for(vol: Volume, tf: TransferFunction, spec: AxisSpec,
                  volp: Optional[jnp.ndarray] = None):
    """The occupancy structure `slice_march` consumes for this spec:
    None (skipping off), bool[nchunks], or (chunk, tile) tuple when
    ``spec.vtiles > 0`` — one occupancy-pyramid build
    (ops/occupancy.pyramid_from_volume), gated down to the march's
    contract. ``volp`` shares the frame's permuted volume copy."""
    if not spec.skip_empty:
        return None
    from scenery_insitu_tpu.ops import occupancy as _occ

    return _occ.pyramid_from_volume(vol, tf, spec, volp=volp).gate(spec)


def _resolve_occupancy(vol: Volume, tf: TransferFunction, spec: AxisSpec,
                       occupancy, volp: Optional[jnp.ndarray]):
    """Normalize a caller-provided occupancy (an ops/occupancy
    OccupancyPyramid — built once per frame, possibly from sim-fused
    field ranges — or the legacy gate arrays) to the `slice_march`
    contract; None builds the per-call pyramid like the pre-ISSUE-6
    path did. Skipping off always wins."""
    if not spec.skip_empty:
        return None
    if occupancy is None:
        return occupancy_for(vol, tf, spec, volp=volp)
    from scenery_insitu_tpu.ops import occupancy as _occ

    if isinstance(occupancy, _occ.OccupancyPyramid):
        return occupancy.gate(spec)
    return occupancy


def slice_march(vol: Volume, tf: TransferFunction, axcam: AxisCamera,
                spec: AxisSpec, consume: Callable, carry0,
                u_bounds=None, v_bounds=None, step_scale: float = 1.0,
                occupancy: Optional[jnp.ndarray] = None,
                early_stop: Optional[Callable] = None, raw: bool = False,
                shaded_compact: bool = False,
                volp: Optional[jnp.ndarray] = None,
                w_bounds=None):
    """The chunked slice march. Calls ``consume`` for each chunk of
    slices, front to back, and returns the final carry. It speaks three
    ``consume`` contracts:

    - planes (the default): ``consume(carry, rgba [C,4,Nj,Ni],
      t0 [C,Nj,Ni], t1 [C,Nj,Ni]) -> carry`` — the plain render
      (`render_slices`), every counting march (`_histogram_threshold`,
      "search" mode) and the XLA reference fold of `write_march`;
    - ``shaded_compact=True``: `write_march`'s ``pallas_seg`` feed;
    - ``raw=True``: `write_march`'s ``pallas_fused`` feed.

    rgba is premultiplied, already opacity-corrected for the per-ray
    inter-slice path length, and zero outside the volume/ownership bounds.

    Pre-shaded RGBA volumes (``vol.data f32[4, D, H, W]``, premultiplied,
    alpha encoded for a ``nominal_step(vol)``-long traversal — the
    novel-view proxy) march without a transfer function: pass ``tf=None``
    and the per-slice shading resamples the stored channels instead.

    ``occupancy`` (bool[nchunks], from `chunk_occupancy`) skips the
    resampling matmuls and fold for provably-empty chunks; the skipped
    branch still feeds ONE all-empty sample so stream-gap semantics
    (supersegment closing on empty) are identical to the full march.
    A TUPLE ``(chunk_occ, tile_occ)`` (see `chunk_occupancy_vtiles` and
    `occupancy_for`) additionally gates output row BLOCKS inside live
    chunks on the in-plane tile occupancy — the reference's OctreeCells
    granularity along the axis the matmul factorization can skip.
    ``early_stop(carry) -> bool[]`` additionally skips every chunk after
    the predicate turns true (alpha-saturation early-out, ≅ the
    reference's early exit in AccumulatePlainImage.comp:8-13).

    ``raw=True`` changes the consume contract to ``consume(carry,
    val [C,Nj,Ni], sk [C]) -> carry``: the RESAMPLED VALUE plane with a
    ``-1`` sentinel for dead samples (outside volume/bounds, dropped
    slices) and the per-slice eye-depth ratios — no transfer function,
    no opacity correction, no t0/t1 streams. This is the fused-kernel
    feed (ops/pallas_seg.fused_fold_chunk shades in-kernel); scalar
    volumes only. Occupancy-skipped iterations feed a C=1 plane of
    sentinels.

    ``w_bounds`` (an open world interval ``(w_lo, w_hi)`` on the march
    axis) additionally drops slices whose plane lies outside it — the
    ownership mask of a PLANNED render band (docs/PERF.md "Render
    rebalancing"): a band volume padded to the plan's max depth marches
    only its own slices, exactly like ``v_bounds`` owns in-plane rows.
    Slice centers sit half a voxel inside any slice-aligned boundary, so
    the open comparison is exact.

    ``shaded_compact=True`` keeps the full shading (premultiplied,
    opacity-corrected rgba) but replaces the depth planes with the
    per-slice ratios: ``consume(carry, rgba [C,4,Nj,Ni], sk0 [C],
    sk1 [C]) -> carry`` where the plane path's t0/t1 are exactly
    ``sk0*length`` / ``sk1*length`` (length = axcam.ray_lengths()).
    Occupancy-skipped iterations feed a C=1 all-empty chunk, like the
    default contract. This is the compact pallas_seg feed — the
    [C,2,Nj,Ni] depth planes never materialize in HBM.
    """
    pre_shaded = vol.data.ndim == 4
    if raw and pre_shaded:
        raise ValueError("raw slice_march feeds a transfer-function "
                         "kernel; pre-shaded volumes have no TF")
    # the trace's phases: what the consumer does with a chunk is `fold`
    # (innermost scope wins); the resampling and shading around it stay
    # in the caller's scope — `march` in every VDI generator and builder
    consume = _in_phase("fold")(consume)
    occ_tiles = None
    if isinstance(occupancy, tuple):
        occupancy, occ_tiles = occupancy
    # ``volp`` shares the frame's one march layout (occupancy pass +
    # every march of the frame read the same array; XLA CSEs an x/y
    # march's transpose either way inside one jit, but the explicit
    # handoff also serves eager callers and keeps the structure visible)
    if volp is None:
        volp = permute_volume(vol, spec)
    s_total = volp.shape[0]
    c = spec.chunk
    mm = jnp.bfloat16 if spec.matmul_dtype == "bf16" else jnp.float32
    # an integer field's stored value v stands for v * vscale: the scale
    # goes on the matmul's f32 result, so the volume operand is the
    # file's own values. u8 is exact in bf16; a wider integer is not,
    # nor as an f32 operand on a TPU (one bf16 pass at the default
    # precision), so its chunks are contracted byte plane by byte plane
    # (`resample_wide`) or, where f32 operands are asked for, at
    # Precision.HIGHEST (f32 arithmetic on every backend)
    vscale = value_scale(volp.dtype)
    wide = operand_planes(volp.dtype, spec.matmul_dtype) > 1
    exact = ({"precision": jax.lax.Precision.HIGHEST}
             if operand_planes(volp.dtype) > 1 and not wide else {})
    if jnp.issubdtype(volp.dtype, jnp.floating) \
            and volp.dtype.itemsize > jnp.dtype(mm).itemsize:
        # a float layout wider than the matmul operand: the compiler
        # lifts the chunks' cast out of the loop anyway (one convert of
        # the layout a frame); written here it carries the march's scope
        volp = volp.astype(mm)
    nchunks, fetch = march_chunks(volp, spec)
    if occupancy is not None and occupancy.shape[0] != nchunks:
        # both sides chunk through the shared march_chunks, so a
        # mismatch means the occupancy was built for a DIFFERENT volume
        # or chunk size — skipping with it would be silently wrong
        raise ValueError(
            f"occupancy describes {occupancy.shape[0]} chunks but this "
            f"march has {nchunks} (volume {vol.data.shape}, chunk {c})")

    ou, su, nu, ov, sv, nv = _axis_params(vol, spec)
    eu, ev, ew = axcam.eye_u, axcam.eye_v, axcam.eye_w

    # per-ray geometry (constant over the march)
    length = axcam.ray_lengths()                           # [Nj, Ni]
    ds = jnp.abs(axcam.dwm) / axcam.zp                     # depth-ratio step
    ratio = ds * length / (nominal_step(vol, step_scale))  # [Nj, Ni]

    # the volume's own w ladder may start offset from the global one
    # (distributed slabs): marched slice k of THIS volume sits at world
    # w = local_w0 + k*dwm
    a = spec.axis
    now_ = vol.data.shape[_DATA_DIM[a]]
    local_w0 = jnp.where(axcam.dwm > 0,
                         vol.origin[a] + 0.5 * vol.spacing[a],
                         vol.origin[a] + (now_ - 0.5) * vol.spacing[a])

    def work(carry, ci):
        ks = ci * c + jnp.arange(c, dtype=jnp.float32)     # [C]
        wk = local_w0 + ks * axcam.dwm
        sk = jnp.float32(spec.sign) * (wk - ew) / axcam.zp   # depth ratios
        live = (sk > spec.s_floor) & (ks < s_total)
        if w_bounds is not None:
            live &= (wk > w_bounds[0]) & (wk < w_bounds[1])

        slices = fetch(ci)

        pos_u = eu + (axcam.u_grid[None, :] - eu) * sk[:, None]    # [C, Ni]
        pos_v = ev + (axcam.v_grid[None, :] - ev) * sk[:, None]    # [C, Nj]
        wu = _interp_matrix(pos_u, ou, su, nu, u_bounds)           # [C,Ni,Nu]
        wv = _interp_matrix(pos_v, ov, sv, nv, v_bounds)           # [C,Nj,Nv]

        inside = (wv.sum(-1) > 0.0)[:, :, None] & (wu.sum(-1) > 0.0)[:, None, :]
        keep = inside & live[:, None, None]

        wu_sums = rounded_row_sums(wu) if wide else None

        def resample(wv_r):
            """A block of output rows of the chunk resampled onto the
            grid, normalised: ``[C, B, Ni]`` f32 (scalar volumes)."""
            if wide:
                val = resample_wide(wv_r, slices, wu, wu_sums)
            else:
                val = jnp.einsum("cjy,cyx,cix->cji",
                                 wv_r.astype(mm), slices.astype(mm),
                                 wu.astype(mm),
                                 preferred_element_type=jnp.float32,
                                 **exact)
            if vscale != 1.0:
                val = val * jnp.float32(vscale)
            return val

        def rows_val(wv_r, keep_r):
            """Raw-mode block: resampled values, -1 where dead."""
            val = resample(wv_r)
            # clip BEFORE the sentinel so a genuine value <= -0.5 (un-
            # normalized field) can't be conflated with a dead sample;
            # exact — every shading path clips to [0,1] anyway
            return jnp.where(keep_r, jnp.clip(val, 0.0, 1.0), -1.0)

        def rows_rgba(wv_r, keep_r, ratio_r):
            """Resample + shade one block of output rows ([C,B,*])."""
            if pre_shaded:
                # stored premultiplied RGBA; alpha encoded per nominal step
                val = jnp.einsum("cjy,cdyx,cix->cdji",
                                 wv_r.astype(mm), slices.astype(mm),
                                 wu.astype(mm),
                                 preferred_element_type=jnp.float32)
                a_res = jnp.clip(val[:, 3], 0.0, 1.0 - 1e-6)
                a_res = jnp.where(keep_r, a_res, 0.0)
                alpha = adjust_opacity(a_res, ratio_r[None])
                # premultiplied rgb scales with its alpha re-correction
                scale = alpha / jnp.maximum(a_res, 1e-6)
                return jnp.concatenate(
                    [jnp.clip(val[:, :3], 0.0, 1.0) * scale[:, None],
                     alpha[:, None]], axis=1)
            val = resample(wv_r)
            val = jnp.clip(val, 0.0, 1.0)

            rgb, alpha = tf(val)                   # [C,B,Ni,3], [C,B,Ni]
            # outside-volume samples must be fully transparent even when
            # the transfer function maps value 0 to nonzero alpha
            alpha = jnp.where(keep_r, alpha, 0.0)
            alpha = adjust_opacity(alpha, ratio_r[None])
            return jnp.concatenate(
                [jnp.moveaxis(rgb, -1, 1) * alpha[:, None],
                 alpha[:, None]], axis=1)

        rows_fn = ((lambda wv_r, keep_r, ratio_r: rows_val(wv_r, keep_r))
                   if raw else rows_rgba)
        if occ_tiles is None:
            rgba = rows_fn(wv, keep, ratio)
        else:
            # in-plane skipping: gate each OUTPUT row block on whether
            # its bilinear support intersects any occupied (chunk,
            # v-tile). The support of output rows is derived from the
            # block's sampled voxel coordinates over LIVE slices; a block
            # whose whole support lies in empty tiles is provably
            # zero-alpha (value ranges are preserved by interpolation).
            nt = occ_tiles.shape[1]
            tv = nv // nt
            occ_row = occ_tiles[ci]                        # bool[nt]
            tile_ids = jnp.arange(nt)
            xv = (pos_v - ov) / sv - 0.5                   # [C, Nj] voxels
            nb = nt
            bsz = spec.nj // nb
            blocks = []
            for b in range(nb):
                b0 = b * bsz
                b1 = spec.nj if b == nb - 1 else (b0 + bsz)
                xb = xv[:, b0:b1]
                big = jnp.float32(2 * nv)
                xlo = jnp.min(jnp.where(live[:, None], xb, big))
                xhi = jnp.max(jnp.where(live[:, None], xb, -big))
                r_lo = jnp.clip(jnp.floor(xlo), 0, nv - 1)
                r_hi = jnp.clip(jnp.floor(xhi) + 1.0, 0, nv - 1)
                t_lo = jnp.minimum(r_lo // tv, nt - 1).astype(jnp.int32)
                t_hi = jnp.minimum(r_hi // tv, nt - 1).astype(jnp.int32)
                hit = jnp.any(occ_row & (tile_ids >= t_lo)
                              & (tile_ids <= t_hi)) & (xlo <= xhi)
                wv_b = wv[:, b0:b1]
                keep_b = keep[:, b0:b1]
                ratio_b = ratio[b0:b1]
                fill = -1.0 if raw else 0.0
                shp = ((c, b1 - b0, spec.ni) if raw
                       else (c, 4, b1 - b0, spec.ni))
                cat_ax = 1 if raw else 2
                blocks.append(jax.lax.cond(
                    hit,
                    lambda wv_b=wv_b, keep_b=keep_b, ratio_b=ratio_b:
                        rows_fn(wv_b, keep_b, ratio_b),
                    lambda shp=shp, fill=fill: jnp.full(shp, fill,
                                                        jnp.float32)))
            rgba = jnp.concatenate(blocks, axis=cat_ax)

        if raw:
            return consume(carry, rgba, sk)
        if shaded_compact:
            # compact contract: shaded rgba + BOTH per-slice depth ratios
            # (sk0, sk1 = sk + ds) so the step geometry stays defined in
            # ONE place; the consumer owns only t = sk*length (in-kernel
            # for the compact pallas_seg fold — the [C,2,Nj,Ni] planes
            # never materialize)
            return consume(carry, rgba, sk, sk + ds)
        t0 = sk[:, None, None] * length[None]
        t1 = (sk + ds)[:, None, None] * length[None]
        return consume(carry, rgba, t0, t1)

    def skip(carry, ci):
        # one explicit empty sample: closes any open supersegment exactly
        # like the stream of empties the full march would have produced
        s0 = jnp.float32(spec.sign) * (local_w0 + ci * c * axcam.dwm - ew) \
            / axcam.zp
        if raw:
            return consume(carry,
                           jnp.full((1, spec.nj, spec.ni), -1.0,
                                    jnp.float32), s0[None])
        empty = jnp.zeros((1, 4, spec.nj, spec.ni), jnp.float32)
        if shaded_compact:
            # all-empty chunk: slot -1 records never match a depth mask,
            # so sk1 = sk0 + ds vs the plane path's t0 == t1 is moot
            return consume(carry, empty, s0[None], s0[None] + ds)
        t = (s0 * length)[None]                            # [1, Nj, Ni]
        return consume(carry, empty, t, t)

    gated = occupancy is not None or early_stop is not None

    def body(carry, ci):
        if not gated:
            return work(carry, ci), None
        occupied = jnp.bool_(True) if occupancy is None else occupancy[ci]
        if early_stop is not None:
            occupied &= ~early_stop(carry)
        return jax.lax.cond(occupied, work, skip, carry, ci), None

    carry, _ = jax.lax.scan(body, carry0, jnp.arange(nchunks))
    return carry


# ------------------------------------------------------- plain-image render


def hittable_mask(vol: Volume, axcam: AxisCamera, spec: AxisSpec
                  ) -> jnp.ndarray:
    """bool[Nj, Ni]: can this intermediate-grid pixel's ray intersect the
    volume AABB at any marched depth? The intermediate grid covers the
    whole projected footprint plus margins, so its edge pixels never
    accumulate alpha — any all-pixels predicate (saturation early-out)
    must ignore them. Per pixel, pos_u(s) = eu + (u_i - eu)·s lies in the
    volume's u extent for an interval of depth ratios s; the pixel is
    hittable iff the u and v intervals overlap somewhere in s > 0
    (conservative: the actual march range is a subset)."""
    a, ua, va = spec.axis, spec.u_axis, spec.v_axis

    def axis_interval(grid, e, lo, hi):
        d = grid - e
        big = jnp.float32(1e30)
        s0 = jnp.where(d > 0, (lo - e) / jnp.where(d == 0, 1.0, d),
                       jnp.where(d < 0, (hi - e) / jnp.where(d == 0, 1.0, d),
                                 jnp.where((e >= lo) & (e <= hi), 0.0, big)))
        s1 = jnp.where(d > 0, (hi - e) / jnp.where(d == 0, 1.0, d),
                       jnp.where(d < 0, (lo - e) / jnp.where(d == 0, 1.0, d),
                                 jnp.where((e >= lo) & (e <= hi), big, -big)))
        return s0, s1

    u0, u1 = axis_interval(axcam.u_grid, axcam.eye_u,
                           vol.world_min[ua], vol.world_max[ua])
    v0, v1 = axis_interval(axcam.v_grid, axcam.eye_v,
                           vol.world_min[va], vol.world_max[va])
    # the march only visits depth ratios between the volume's w faces
    sa = jnp.float32(spec.sign) * (vol.world_min[a] - axcam.eye_w) / axcam.zp
    sb = jnp.float32(spec.sign) * (vol.world_max[a] - axcam.eye_w) / axcam.zp
    s_lo = jnp.minimum(sa, sb)
    s_hi = jnp.maximum(sa, sb)
    lo = jnp.maximum(jnp.maximum(u0[None, :], v0[:, None]), s_lo)
    hi = jnp.minimum(jnp.minimum(u1[None, :], v1[:, None]), s_hi)
    return jnp.maximum(lo, 0.0) <= hi


def render_slices(vol: Volume, tf: TransferFunction, axcam: AxisCamera,
                  spec: AxisSpec, early_exit_alpha: float = 0.999,
                  u_bounds=None, v_bounds=None,
                  step_scale: float = 1.0,
                  occupancy=None,
                  volp: Optional[jnp.ndarray] = None,
                  w_bounds=None) -> RaycastOutput:
    """Front-to-back alpha-under accumulation on the intermediate grid
    (≅ VolumeRaycaster.comp, but slice-order). Background-free premultiplied
    image + first-hit depth (ray parameter; +inf where empty). Skips
    provably-empty chunks; saturated pixels stop accumulating via the
    per-pixel gate (≅ AccumulatePlainImage.comp:8-13 — a whole-chunk
    saturation stop is NOT wired up: silhouette pixels get tapered
    partial-weight edge samples and never reach the threshold, so an
    all-pixels predicate can essentially never fire)."""

    def consume(carry, rgba, t0, t1):
        # chunk-parallel alpha-under (same factorization as the seg fold:
        # contribution_s = rgba_s * prod_{s'<s}(1-alpha)), EXACT including
        # the per-pixel saturation gate: the sequential gate tests the
        # PRE-update accumulated alpha, which equals 1-(1-A0)*Tl_excl(s)
        # — a prefix quantity — and once a pixel crosses, every later
        # sample is zeroed either way, so masking with the unmasked
        # prefix reproduces the frozen-accumulator semantics (up to fp
        # association; a pixel landing within ~1 ulp of the threshold
        # can round the gate differently and shift by one sample —
        # measure-zero in practice, bounded by one sample's alpha).
        acc, first_t = carry
        cc = rgba.shape[0]
        t_run = jnp.ones_like(acc[3])
        tls = []
        for i in range(cc):                    # 2 ops/slice, tiny loop
            tls.append(t_run)
            t_run = t_run * (1.0 - rgba[i, 3])
        tl = jnp.stack(tls)                                # [C, Nj, Ni]
        a0 = acc[3:4]
        a_pre = 1.0 - (1.0 - a0) * tl                      # [C, Nj, Ni]
        gate = a_pre < early_exit_alpha
        contrib = jnp.sum(rgba * (tl * gate)[:, None], axis=0)
        acc = acc + (1.0 - a0) * contrib
        hit = gate & (rgba[:, 3] > 1e-4)
        t_hit = jnp.min(jnp.where(hit, t0, jnp.inf), axis=0)
        return acc, jnp.minimum(first_t, t_hit)

    acc0 = jnp.zeros((4, spec.nj, spec.ni), jnp.float32)
    t0 = jnp.full((spec.nj, spec.ni), jnp.inf, jnp.float32)
    if volp is None:
        volp = permute_volume(vol, spec)
    occ = _resolve_occupancy(vol, tf, spec, occupancy, volp)
    acc, first_t = slice_march(vol, tf, axcam, spec, consume, (acc0, t0),
                               u_bounds, v_bounds, step_scale,
                               occupancy=occ, volp=volp,
                               w_bounds=w_bounds)
    return RaycastOutput(acc, first_t)


def bilinear_image_sample(img: jnp.ndarray, gy: jnp.ndarray, gx: jnp.ndarray,
                          fill: float = 0.0) -> jnp.ndarray:
    """Sample ``img f32[ch, H, W]`` at continuous pixel coords (gy, gx)
    ``[...]`` (pixel centers at integers). Out-of-range → fill."""
    ch, h, w = img.shape
    inb = (gx >= -0.5) & (gx <= w - 0.5) & (gy >= -0.5) & (gy <= h - 0.5)
    x = jnp.clip(gx, 0.0, w - 1.0)
    y = jnp.clip(gy, 0.0, h - 1.0)
    x0 = jnp.clip(jnp.floor(x).astype(jnp.int32), 0, max(w - 2, 0))
    y0 = jnp.clip(jnp.floor(y).astype(jnp.int32), 0, max(h - 2, 0))
    fx = x - x0
    fy = y - y0
    flat = img.reshape(ch, h * w)

    def at(yi, xi):
        return jnp.take(flat, yi * w + xi, axis=1)

    x1 = jnp.minimum(x0 + 1, w - 1)
    y1 = jnp.minimum(y0 + 1, h - 1)
    out = (at(y0, x0) * ((1 - fx) * (1 - fy))[None]
           + at(y0, x1) * (fx * (1 - fy))[None]
           + at(y1, x0) * ((1 - fx) * fy)[None]
           + at(y1, x1) * (fx * fy)[None])
    return jnp.where(inb[None], out, fill)


def warp_to_camera(image: jnp.ndarray, axcam: AxisCamera, spec: AxisSpec,
                   cam: Camera, width: int, height: int,
                   background: Optional[Tuple[float, ...]] = (0.0, 0.0, 0.0, 0.0),
                   fill: float = 0.0, nearest: bool = False) -> jnp.ndarray:
    """Resample an intermediate-grid image ``[ch, Nj, Ni]`` to the real
    camera's ``[ch, H, W]``. Exact: both cameras share an eye, so the map
    is the homography induced by the reference plane. ``fill`` is used for
    rays that miss the reference plane or fall outside the grid;
    ``background`` (4-channel images only) is alpha-under-composited.
    ``nearest`` disables bilinear blending — required for channels with
    sentinel values (depth maps), where blending a sentinel with a valid
    neighbor would fabricate a value."""
    from scenery_insitu_tpu.core.camera import pixel_rays

    _, dirs = pixel_rays(cam, width, height)               # [3, H, W]
    de = jnp.float32(spec.sign) * dirs[spec.axis]
    hit = de > 1e-6
    tp = axcam.zp / jnp.where(hit, de, 1.0)
    pu = axcam.eye_u + tp * dirs[spec.u_axis]
    pv = axcam.eye_v + tp * dirs[spec.v_axis]
    du = axcam.u_grid[1] - axcam.u_grid[0]
    dv = axcam.v_grid[1] - axcam.v_grid[0]
    gi = (pu - axcam.u_grid[0]) / du
    gj = (pv - axcam.v_grid[0]) / dv
    if nearest:
        gi = jnp.round(gi)
        gj = jnp.round(gj)
    out = bilinear_image_sample(image, gj, gi, fill)
    out = jnp.where(hit[None], out, fill)
    if background is None:
        return out
    bg = jnp.asarray(background, jnp.float32).reshape(-1, 1, 1)
    return out + (1.0 - out[3:4]) * bg


def raycast_mxu(vol: Volume, tf: TransferFunction, cam: Camera,
                width: int, height: int, spec: AxisSpec,
                early_exit_alpha: float = 0.999,
                background: Tuple[float, ...] = (0.0, 0.0, 0.0, 0.0),
                step_scale: float = 1.0) -> RaycastOutput:
    """Full plain render: slice march on the intermediate grid + homography
    warp to the display camera. Drop-in output-compatible with
    ops.raycast.raycast."""
    axcam = make_axis_camera(vol, cam, spec)
    inter = render_slices(vol, tf, axcam, spec, early_exit_alpha,
                          step_scale=step_scale)
    img = warp_to_camera(inter.image, axcam, spec, cam, width, height,
                         background)
    # depth: nearest-sample warp with -1 standing in for "empty" (bilinear
    # would blend the sentinel with valid neighbors at silhouette pixels)
    depth = warp_to_camera(
        jnp.where(jnp.isfinite(inter.depth), inter.depth, -1.0)[None],
        axcam, spec, cam, width, height, background=None, fill=-1.0,
        nearest=True)[0]
    depth = jnp.where(depth >= 0.0, depth, jnp.inf)
    return RaycastOutput(img, depth)


# ----------------------------------------------------------- VDI generation


def write_march(vol: Volume, tf, axcam: AxisCamera, spec: AxisSpec,
                threshold: jnp.ndarray, k: int, *, volp: jnp.ndarray,
                step_scale: float = 1.0, count: bool = False, **bounds):
    """THE write march: one `slice_march` (``volp``, ``step_scale`` and
    ``bounds`` — its ``u_bounds`` / ``v_bounds`` / ``w_bounds`` /
    ``occupancy`` — go to it as given; here it is only told which
    ``consume`` contract to feed) folded into K supersegments at the
    ``threshold`` map -> ``(color [K,4,Nj,Ni], depth [K,2,Nj,Ni],
    count i32[Nj,Ni])``. ``count`` is the TRUE per-pixel segment starts
    at this threshold (the temporal controller's feedback): the kernel
    folds carry it for free, the XLA machine folds ``ss.push_count``
    beside ``ss.push`` only where ``count=True`` and returns None
    otherwise. The only place a fold schedule is chosen
    (`fold_schedule`) and noted."""
    nj, ni = spec.nj, spec.ni
    march = functools.partial(slice_march, vol, tf, axcam, spec, volp=volp,
                              step_scale=step_scale, **bounds)
    fold = fold_schedule(spec, vol, tf)
    _note_write_fold(volp, spec, fold)
    if fold == "xla":
        def consume(carry, rgba, t0, t1):
            st, cst = carry
            for i in range(rgba.shape[0]):
                st = ss.push(st, k, threshold, rgba[i], t0[i], t1[i])
                if count:
                    cst = ss.push_count(cst, threshold, rgba[i])
            return st, cst

        state, cstate = march(
            consume, (ss.init_state(k, nj, ni),
                      ss.init_count(nj, ni) if count else None))
        with _phase("fold"):
            color, depth = ss.finalize(state)
        return color, depth, (cstate.count if count else None)

    # the kernel folds carry the packed tuple: the [K,...] state keeps
    # one layout across the whole scan so `input_output_aliases` update
    # it in place, and the kernel forms t = sk*length itself — the
    # [C,2,Nj,Ni] depth planes never hit HBM
    length = axcam.ray_lengths()
    if fold == "pallas_fused":
        # the march feeds the raw resampled value plane and the kernel
        # applies TF + opacity correction itself (≅ the reference's
        # one-kernel generation): the shaded chunk never exists in HBM.
        # ds/ratio match slice_march's own shading formula INCLUDING
        # step_scale
        ds = jnp.abs(axcam.dwm) / axcam.zp
        ratio = ds * length / nominal_step(vol, step_scale)

        def consume(packed, val, sk):
            return psg.fused_fold_chunk(packed, val, length, ratio, sk,
                                        sk + ds, threshold, max_k=k, tf=tf)

        packed = march(consume, psg.init_seg_packed(k, nj, ni), raw=True)
    else:
        def consume(packed, rgba, sk0, sk1):
            return psg.fold_chunk_packed(packed, rgba, threshold, max_k=k,
                                         sk0=sk0, sk1=sk1, length=length)

        packed = march(consume, psg.init_seg_packed(k, nj, ni),
                       shaded_compact=True)
    state = psg.unpack_seg_state(packed)
    with _phase("fold"):
        # the kernel's own account of its K-loops, summed once a march:
        # kept by a step that was built to hand it on, dead code otherwise
        _note_fold_slots(psg.fold_slot_counts(packed))
        color, depth = sf.seg_finalize(state)
    return color, depth, state.cnt


@_in_phase("march")
def generate_vdi_mxu(vol: Volume, tf: TransferFunction, cam: Camera,
                     spec: AxisSpec, cfg: Optional[VDIConfig] = None,
                     frame_index: int = 0,
                     box_min: Optional[jnp.ndarray] = None,
                     box_max: Optional[jnp.ndarray] = None,
                     u_bounds=None, v_bounds=None,
                     occupancy=None, k_target=None,
                     axcam: Optional[AxisCamera] = None,
                     volp: Optional[jnp.ndarray] = None,
                     w_bounds=None, step_scale: float = 1.0,
                     ) -> Tuple[VDI, VDIMetadata, AxisCamera]:
    """VDI generation on the MXU slice march (≅ VDIGenerator.comp +
    AccumulateVDI.comp, see ops.vdi_gen for the gather-path equivalent).

    The VDI lives on the virtual camera's pixel grid; its metadata carries
    the virtual projection/view, so compositing, novel-view rendering and
    streaming treat it exactly like a gather-path VDI. Depths are the world
    ray parameter of the (virtual = real) eye.

    ``occupancy``: a per-frame ops/occupancy.OccupancyPyramid (built once
    and shared across every march of the frame — possibly from sim-fused
    field ranges, costing no volume sweep at all) or a legacy gate; None
    rebuilds from the volume here. ``k_target`` (traced scalar or
    [nj, ni]) re-targets the adaptive threshold at fewer than
    ``cfg.max_supersegments`` segments — output SHAPES stay at K; this is
    the load-aware K budget hook (occupancy.k_budget_target).

    ``axcam`` overrides the virtual camera (the tile-wave path passes a
    column-sliced `wave_camera` whose u_grid matches ``spec.ni``);
    ``volp`` shares a pre-built `permute_volume` copy across calls (T
    waves march the same frame copy).

    ``step_scale`` rescales the opacity-correction reference step
    (`nominal_step(vol, step_scale)`) — the LOD brick path marches a
    2^l-downsampled volume with ``step_scale = 2^-l`` so coarse slices
    accumulate the opacity of the 2^l fine slices they replace (the
    shared reference stays the FINE voxel pitch; docs/PERF.md "LOD
    marching")."""
    cfg = cfg or VDIConfig()
    k = cfg.max_supersegments
    kt = k if k_target is None else k_target
    nj, ni = spec.nj, spec.ni
    if axcam is None:
        axcam = make_axis_camera(vol, cam, spec, box_min, box_max)

    # ONE permuted copy + one occupancy structure shared by every
    # counting + writing march of this generation
    if volp is None:
        volp = permute_volume(vol, spec)
    bound = dict(u_bounds=u_bounds, v_bounds=v_bounds, w_bounds=w_bounds,
                 step_scale=step_scale, volp=volp,
                 occupancy=_resolve_occupancy(vol, tf, spec, occupancy,
                                              volp))
    march = functools.partial(slice_march, vol, tf, axcam, spec, **bound)

    if cfg.adaptive and cfg.adaptive_mode == "temporal":
        raise ValueError(
            "adaptive_mode='temporal' carries per-frame threshold state — "
            "call generate_vdi_mxu_temporal(..., threshold=...) instead "
            "(seed the state with initial_threshold())")
    if cfg.adaptive and cfg.adaptive_mode == "histogram":
        threshold = _histogram_threshold(march, cfg, kt, nj, ni, spec.fold)
    elif cfg.adaptive:
        # "search" mode: adaptive_iters counting marches (XLA fold — the
        # default modes are histogram/temporal; search stays the portable
        # reference schedule)
        def count_fn(thr):
            def consume(st, rgba, t0, t1):
                for i in range(rgba.shape[0]):
                    st = ss.push_count(st, thr, rgba[i])
                return st
            return march(consume, ss.init_count(nj, ni)).count
        threshold = ss.adaptive_threshold(count_fn, kt, cfg.adaptive_iters,
                                          nj, ni)
    else:
        threshold = jnp.full((nj, ni), cfg.threshold, jnp.float32)

    color, depth, _ = write_march(vol, tf, axcam, spec, threshold, k,
                                  **bound)
    meta = _vdi_meta(vol, axcam, ni, nj, frame_index, step_scale)
    return VDI(color, depth), meta, axcam


def _vdi_meta(vol: Volume, axcam: AxisCamera, ni: int, nj: int,
              frame_index: int, step_scale: float = 1.0) -> VDIMetadata:
    dims = jnp.asarray(vol.dims_xyz, jnp.float32)
    # model = voxel->world affine (diag spacing + origin): consumers that
    # only get metadata (axis_camera_from_meta) read the per-axis pitch
    # from here — nw alone is min(spacing), wrong for anisotropic volumes
    model = jnp.diag(jnp.concatenate([vol.spacing, jnp.ones(1)]))
    model = model.at[:3, 3].set(vol.origin)
    return VDIMetadata.create(projection=axcam.proj, view=axcam.view,
                              model=model, volume_dims=dims,
                              window_dims=(ni, nj),
                              nw=nominal_step(vol, step_scale),
                              index=frame_index)


def _histogram_threshold(march, cfg: VDIConfig, k: int, nj: int, ni: int,
                         fold: str = "xla") -> jnp.ndarray:
    """One counting march for ALL candidate thresholds at once."""
    tvec = ss.threshold_candidates(cfg.histogram_bins, cfg.thr_max)

    # a kernel fold implies a TPU backend where the VMEM counting kernel
    # is also the right schedule for the histogram march
    if fold != "xla":
        def consume_multi(carry, rgba, t0, t1):
            return psg.count_multi_chunk(carry, rgba, tvec)

        counts = march(consume_multi, psg.init_count_multi_packed(
            cfg.histogram_bins, nj, ni))[0]
    else:
        def consume_multi(st, rgba, t0, t1):
            for i in range(rgba.shape[0]):
                st = ss.push_count(st, tvec[:, None, None], rgba[i])
            return st

        counts = march(consume_multi,
                       ss.init_count_multi(cfg.histogram_bins, nj, ni)).count
    return ss.pick_threshold(counts, tvec, k)


@_in_phase("march")
def initial_threshold(vol: Volume, tf: TransferFunction, cam: Camera,
                      spec: AxisSpec, cfg: Optional[VDIConfig] = None,
                      box_min: Optional[jnp.ndarray] = None,
                      box_max: Optional[jnp.ndarray] = None,
                      u_bounds=None, v_bounds=None,
                      occupancy=None, k_target=None,
                      w_bounds=None,
                      axcam: Optional[AxisCamera] = None,
                      step_scale: float = 1.0) -> ss.ThresholdState:
    """Seed state for the temporal threshold controller ([nj, ni] maps):
    one histogram counting march on the current scene (the same pass
    adaptive_mode="histogram" runs every frame — temporal mode runs it
    once at session start, then `generate_vdi_mxu_temporal` keeps the map
    in band for one-march frames). ``occupancy``/``k_target``/``axcam``/
    ``step_scale``: see `generate_vdi_mxu` (the LOD brick path passes the
    shared fine-pitch camera with rescaled dwm)."""
    cfg = cfg or VDIConfig()
    if axcam is None:
        axcam = make_axis_camera(vol, cam, spec, box_min, box_max)
    volp = permute_volume(vol, spec)
    occ = _resolve_occupancy(vol, tf, spec, occupancy, volp)
    march = functools.partial(
        slice_march, vol, tf, axcam, spec, u_bounds=u_bounds,
        v_bounds=v_bounds, step_scale=step_scale, occupancy=occ, volp=volp,
        w_bounds=w_bounds)
    kt = cfg.max_supersegments if k_target is None else k_target
    thr = _histogram_threshold(march, cfg, kt,
                               spec.nj, spec.ni, spec.fold)
    return ss.init_threshold_state(thr, cfg.thr_min, cfg.thr_max)


@_in_phase("march")
def generate_vdi_mxu_temporal(vol: Volume, tf: TransferFunction,
                              cam: Camera, spec: AxisSpec,
                              threshold: ss.ThresholdState,
                              cfg: Optional[VDIConfig] = None,
                              frame_index: int = 0,
                              box_min: Optional[jnp.ndarray] = None,
                              box_max: Optional[jnp.ndarray] = None,
                              u_bounds=None, v_bounds=None,
                              occupancy=None, k_target=None,
                              axcam: Optional[AxisCamera] = None,
                              volp: Optional[jnp.ndarray] = None,
                              w_bounds=None, step_scale: float = 1.0,
                              ) -> Tuple[VDI, VDIMetadata, AxisCamera,
                                         ss.ThresholdState]:
    """VDI generation with ONE march per frame (adaptive_mode="temporal").

    ``threshold`` is carried controller state (seed with
    `initial_threshold`). The write march folds the supersegment writer
    and the O(1) start counter side by side — same slices, same threshold —
    so the true per-pixel segment count comes out of the march that wrote
    the VDI, and `ss.update_threshold` bisects the map toward the target
    band for the next frame. Returns (vdi, meta, axcam, next_threshold).

    Compared to "histogram" mode this halves the march count per frame at
    the cost of one-frame adaptation lag: a pixel whose content changed
    drastically this frame is written with last frame's threshold (its
    overflow merges into the last slot — the same graceful degradation
    every mode shares) and corrected over the following frames.

    ``occupancy``/``k_target``/``axcam``/``volp``: see
    `generate_vdi_mxu` — the controller bisects toward ``k_target`` (the
    occupancy K budget) instead of K when given; output shapes stay at
    K; the tile-wave path passes a column-sliced camera, the shared
    frame copy, and column-sliced threshold maps.
    """
    cfg = cfg or VDIConfig()
    k = cfg.max_supersegments
    kt = k if k_target is None else k_target
    nj, ni = spec.nj, spec.ni
    thr = threshold.thr
    if axcam is None:
        axcam = make_axis_camera(vol, cam, spec, box_min, box_max)
    if volp is None:
        volp = permute_volume(vol, spec)
    color, depth, count = write_march(
        vol, tf, axcam, spec, thr, k, count=True, u_bounds=u_bounds,
        v_bounds=v_bounds, w_bounds=w_bounds, step_scale=step_scale,
        volp=volp,
        occupancy=_resolve_occupancy(vol, tf, spec, occupancy, volp))
    with _phase("fold"):        # the controller reads the fold's count
        next_thr = ss.update_threshold(threshold, count, kt,
                                       cfg.adaptive_delta, cfg.thr_min,
                                       cfg.thr_max, cfg.temporal_track)
    meta = _vdi_meta(vol, axcam, ni, nj, frame_index, step_scale)
    return VDI(color, depth), meta, axcam, next_thr
