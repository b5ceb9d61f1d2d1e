"""Volume data model.

A Volume is a scalar field ``f32[D, H, W]`` (indexed ``[z, y, x]``) with a
world-space placement: ``origin`` (world position of the grid's min corner)
and per-axis ``spacing`` (world size of one voxel). This replaces the
reference's scenery ``Volume.fromBuffer`` nodes positioned at per-grid origins
(reference DistributedVolumes.kt:147-240; DistributedVolumeRenderer.kt:326-394)
and its raw-file loader ``fromPathRaw`` (VolumeFromFileExample.kt:159-217).

Values are kept normalized to [0, 1]; `load_raw` divides by the dtype range
(uint8/uint16 raw files, is16bit flag ≅ DistributedVolumes.kt:147). A field
may also stay at its file's integer dtype (`load_dataset`, the session's
dataset source): its stored value v stands for v / iinfo(dtype).max, and the
slice march and the occupancy pass apply that scale where they read it
(`value_scale`), so the volume is never widened.
"""

from __future__ import annotations

import os
from typing import NamedTuple, Tuple

import jax.numpy as jnp
import numpy as np


class Volume(NamedTuple):
    # f32[D, H, W] normalized scalar field, vol[z, y, x] — or, for
    # pre-shaded content (the novel-view proxy), f32[ch, D, H, W] with a
    # leading channel dim (premultiplied RGBA; rendered without a TF)
    data: jnp.ndarray
    origin: jnp.ndarray    # f32[3] world position of min corner (x, y, z)
    spacing: jnp.ndarray   # f32[3] world size of a voxel (x, y, z)

    @staticmethod
    def _field_dtype(data):
        """Everything normalizes to f32 EXCEPT bf16, which is preserved:
        a bf16 field is the deliberate memory plan of very large volumes
        (the 1024^3 march's permuted copy halves; the resampling einsum
        casts to bf16 anyway — see models/pipelines.py render_dtype), and
        the unsigned integers of a raw file (`value_scale`)."""
        dtype = getattr(data, "dtype", None)
        if dtype == jnp.bfloat16 or dtype in RAW_DTYPES:
            return dtype
        return jnp.float32

    @classmethod
    def create(cls, data, origin=(0.0, 0.0, 0.0), spacing=(1.0, 1.0, 1.0)) -> "Volume":
        return cls(jnp.asarray(data, cls._field_dtype(data)),
                   jnp.asarray(origin, jnp.float32),
                   jnp.asarray(spacing, jnp.float32))

    @classmethod
    def centered(cls, data, extent: float = 2.0) -> "Volume":
        """Place the volume centered at the world origin with its largest side
        spanning `extent` world units."""
        data = jnp.asarray(data, cls._field_dtype(data))
        d, h, w = data.shape
        vox = extent / max(d, h, w)
        size = jnp.array([w * vox, h * vox, d * vox], jnp.float32)
        return cls(data, -size / 2.0, jnp.full((3,), vox, jnp.float32))

    @property
    def dims_xyz(self) -> Tuple[int, int, int]:
        d, h, w = self.data.shape[-3:]
        return (w, h, d)

    @property
    def world_min(self) -> jnp.ndarray:
        return self.origin

    @property
    def world_max(self) -> jnp.ndarray:
        d, h, w = self.data.shape[-3:]
        return self.origin + jnp.array([w, h, d], jnp.float32) * self.spacing

    def world_to_voxel(self, p: jnp.ndarray) -> jnp.ndarray:
        """World position [..., 3] (x,y,z) -> continuous voxel coords [..., 3]
        (x,y,z), where voxel centers sit at integer+0.5."""
        return (p - self.origin) / self.spacing


# the dtypes a raw file is read at and a field may stay resident at
RAW_DTYPES = (np.dtype(np.uint8), np.dtype(np.uint16))


def value_scale(dtype) -> float:
    """What a stored value is multiplied by to give the normalized one:
    1 / iinfo.max for the raw integer dtypes (255, 65535: `load_raw`'s
    divisor), 1 for a float field."""
    dtype = np.dtype(dtype)
    return 1.0 / float(np.iinfo(dtype).max) if dtype in RAW_DTYPES else 1.0


def load_raw(path: str, dims_xyz: Tuple[int, int, int],
             is16bit: bool = False, extent: float = 2.0) -> Volume:
    """Load a raw binary volume file (x-fastest layout, as the reference's
    dataset table expects: VolumeFromFileExample.kt:104-120, 159-217),
    widened to normalized f32 on the host."""
    w, h, d = dims_xyz
    dtype = np.uint16 if is16bit else np.uint8
    raw = np.fromfile(path, dtype=dtype, count=w * h * d).reshape(d, h, w)
    data = raw.astype(np.float32) / float(np.iinfo(dtype).max)
    return Volume.centered(jnp.asarray(data), extent)


# Dataset dimension table mirroring VolumeFromFileExample.kt:104-120 so raw
# files drop in by name.
DATASET_DIMS_XYZ = {
    "kingsnake": (1024, 1024, 795),
    "beechnut": (1024, 1024, 1546),
    "simulation": (2048, 2048, 1920),
    "rayleigh_taylor": (1024, 1024, 1024),
    "microscopy": (1024, 1024, 1040),
    "rotstrat": (4096, 4096, 4096),
}

# The dtype each named file is stored at, beside its dims. The reference's
# table gives dims only and `fromPathRaw` reads 8 bits per voxel unless its
# caller says `is16bit` (VolumeFromFileExample.kt:159-217), so these are
# this repo's assumptions: Beechnut 16-bit (ROADMAP R-C9), the rest 8-bit.
DATASET_DTYPES = {
    "kingsnake": np.uint8,
    "beechnut": np.uint16,
    "simulation": np.uint8,
    "rayleigh_taylor": np.uint8,
    "microscopy": np.uint8,
    "rotstrat": np.uint8,
}


def load_raw_parts(path: str, dims_xyz: Tuple[int, int, int], dtype,
                   parts: int = 8, device=None, timings: dict = None):
    """A raw file straight to the device at the file's dtype, in ``parts``
    z-slabs (≅ `fromPathRaw` x num_parts, VolumeFromFileExample.kt:
    159-217): each slab is read, put and dropped, so the host never holds
    more than one slab and never a widened copy. Returns the resident
    ``dtype[D, H, W]`` array. A file whose size is not dims x itemsize is
    an error. ``timings`` (a dict) takes the seconds of ``read`` and of
    ``put`` apart."""
    import time

    import jax

    w, h, d = dims_xyz
    dtype = np.dtype(dtype)
    want = w * h * d * dtype.itemsize
    have = os.path.getsize(path)
    if have != want:
        raise ValueError(
            f"{path}: {have} bytes, but {w}x{h}x{d} voxels of {dtype.name} "
            f"are {want}")
    parts = max(1, min(int(parts), d))
    cuts = [d * i // parts for i in range(parts + 1)]
    t_read = t_put = 0.0
    slabs = []
    for z0, z1 in zip(cuts, cuts[1:]):
        t0 = time.perf_counter()
        slab = np.fromfile(path, dtype=dtype, count=(z1 - z0) * h * w,
                           offset=z0 * h * w * dtype.itemsize)
        t1 = time.perf_counter()
        slabs.append(jax.device_put(slab.reshape(z1 - z0, h, w), device))
        slabs[-1].block_until_ready()
        t_read, t_put = t_read + t1 - t0, t_put + time.perf_counter() - t1
    t0 = time.perf_counter()
    field = slabs[0] if parts == 1 else jnp.concatenate(slabs, axis=0)
    field.block_until_ready()
    if timings is not None:
        timings.update(read=t_read,
                       put=t_put + time.perf_counter() - t0, parts=parts)
    return field


def load_dataset(name: str, data_dir: str, extent: float = 2.0,
                 parts: int = 8) -> Volume:
    """The named dataset's raw file (``<data_dir>/<name>.raw``) by the
    dims and dtype tables, resident at the file's dtype."""
    key = name.lower()
    path = os.path.join(data_dir, f"{name}.raw")
    return Volume.centered(
        load_raw_parts(path, DATASET_DIMS_XYZ[key], DATASET_DTYPES[key],
                       parts), extent)


def procedural_volume(size: int = 128, seed: int = 0,
                      kind: str = "blobs") -> Volume:
    """Procedural test volume (≅ Volume.generateProceduralVolume used as the
    fake-simulation fixture, reference VDIGenerationExample.kt:182-213)."""
    rng = np.random.default_rng(seed)
    z, y, x = np.meshgrid(*(np.linspace(-1, 1, size, dtype=np.float32),) * 3,
                          indexing="ij")
    if kind == "blobs":
        field = np.zeros_like(x)
        for _ in range(6):
            c = rng.uniform(-0.6, 0.6, 3).astype(np.float32)
            r = rng.uniform(0.15, 0.4)
            field += np.exp(-(((x - c[0]) ** 2 + (y - c[1]) ** 2
                               + (z - c[2]) ** 2) / (r * r)))
        field /= field.max()
    elif kind == "shell":
        r = np.sqrt(x * x + y * y + z * z)
        field = np.exp(-((r - 0.6) ** 2) / 0.01).astype(np.float32)
    elif kind == "gradient":
        field = (x + 1) / 2
    else:
        raise ValueError(f"unknown procedural volume kind {kind!r}")
    return Volume.centered(jnp.asarray(field.astype(np.float32)))
