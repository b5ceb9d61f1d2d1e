"""The benchmark's own plain references and comparisons. Imports nothing of
the program: a later PR may change the program, not the yardstick.

- `gray_scott_start`, `gray_scott_steps`, `gray_scott_frame0`: the
  Gray-Scott initial field made from the seed and advanced by the plain roll
  formulation, f32 (or, for the control, in a lower precision).
- `decode`, `psnr`: one delivered VDI decoded from its own view, and the
  agreement of two decoded images.
- `payload_faults`: what the sink's frames must satisfy one by one.
"""

import numpy as np

GS_DEFAULTS = {"gs_f": 0.037, "gs_k": 0.060, "gs_du": 0.16, "gs_dv": 0.08,
               "dt": 1.0}


def fold_seed(seed: int) -> int:
    """--seed may exceed 32 signed bits; the PRNG key takes 31."""
    return int(seed) % (2 ** 31 - 1)


def gray_scott_init(grid):
    """u = 1, v = 0, one central cube of a quarter width and four
    satellite cubes of an eighth where PRNGKey(0) puts them: the
    Gray-Scott start the session's adapter builds by default
    (sim/grayscott.py `GrayScott.init`), written out again here."""
    import jax
    import jax.numpy as jnp

    d, h, w = grid
    zz, yy, xx = jnp.meshgrid(jnp.arange(d), jnp.arange(h), jnp.arange(w),
                              indexing="ij")

    def stamp(u, v, c, r):
        mask = ((jnp.abs(zz - c[0]) < r) & (jnp.abs(yy - c[1]) < r)
                & (jnp.abs(xx - c[2]) < r))
        return jnp.where(mask, 0.5, u), jnp.where(mask, 0.25, v)

    u = jnp.ones(grid, jnp.float32)
    v = jnp.zeros(grid, jnp.float32)
    u, v = stamp(u, v, (d // 2, h // 2, w // 2), max(min(d, h, w) // 4, 2))
    rs = max(min(d, h, w) // 8, 2)
    for k in jax.random.split(jax.random.PRNGKey(0), 4):
        c = jax.random.randint(k, (3,), rs,
                               jnp.array([d - rs, h - rs, w - rs]))
        u, v = stamp(u, v, c, rs)
    return u, v


def perturb(v, key, amplitude):
    """What --seed does to the start: v inside the seeded cubes times
    (1 + amplitude * uniform(-1, 1)), drawn from `seed_key(seed)`. Every
    seed gets the same cubes in the same places, so the same work, on data
    that differs: random cube places changed the frame time by +-10 % from
    seed to seed (PERF.md, PR 23). Key and amplitude are traced, so one
    compiled program serves every seed."""
    import jax

    noise = jax.random.uniform(key, v.shape, v.dtype, -1.0, 1.0)
    return v * (1.0 + amplitude * noise)


def seed_key(seed: int):
    import jax

    return jax.random.PRNGKey(fold_seed(seed))


def gray_scott_start(grid, seed: int, amplitude: float = 0.0) -> tuple:
    """(u, v) of the session's default start with v perturbed from `seed`,
    f32, on the device."""
    import jax
    import jax.numpy as jnp

    u, v = gray_scott_init(tuple(grid))
    return u, jax.jit(perturb)(v, seed_key(seed), jnp.float32(amplitude))


def gray_scott_steps(u, v, steps: int, dtype: str = "float32") -> tuple:
    """(u, v) after `steps` steps of the plain roll formulation, on the
    device, held in `dtype`. `dtype` below float32 is the control: the same
    mathematics with state and arithmetic in that type."""
    import jax
    import jax.numpy as jnp

    p = GS_DEFAULTS
    dt = jnp.dtype(dtype)

    def lap(x):
        return (jnp.roll(x, 1, 0) + jnp.roll(x, -1, 0) + jnp.roll(x, 1, 1)
                + jnp.roll(x, -1, 1) + jnp.roll(x, 1, 2)
                + jnp.roll(x, -1, 2) - 6.0 * x)

    def step(_, uv):
        u, v = uv
        uvv = u * v * v
        du = p["gs_du"] * lap(u) - uvv + p["gs_f"] * (1.0 - u)
        dv = p["gs_dv"] * lap(v) + uvv - (p["gs_f"] + p["gs_k"]) * v
        return ((u + p["dt"] * du).astype(dt), (v + p["dt"] * dv).astype(dt))

    @jax.jit
    def run(u, v):
        return jax.lax.fori_loop(0, steps, step,
                                 (u.astype(dt), v.astype(dt)))

    return run(u, v)


def gray_scott_frame0(grid, seed: int, steps: int, dtype: str = "float32",
                      amplitude: float = 0.0) -> np.ndarray:
    """The rendered field (v) after one frame's `steps` steps of the plain
    roll formulation, as a host array: the session's default start,
    perturbed from `seed`."""
    import jax.numpy as jnp

    v = gray_scott_steps(*gray_scott_start(grid, seed, amplitude), steps,
                         dtype)[1]
    return np.asarray(v.astype(jnp.float32))


def round_bf16(x: np.ndarray) -> np.ndarray:
    """An f32 array as bfloat16 would hold it (the control's precision)."""
    import ml_dtypes

    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def decode(color: np.ndarray, depth: np.ndarray) -> np.ndarray:
    """One VDI (f32[K, 4, H, W] premultiplied RGBA, f32[K, 2, H, W] depths)
    decoded from its own view: slots ordered by start depth and composited
    front to back, alpha-under. f64[4, H, W], on the host."""
    order = np.argsort(depth[:, 0], axis=0, kind="stable")      # [K, H, W]
    c = np.take_along_axis(color.astype(np.float64), order[:, None], axis=0)
    acc = np.zeros(c.shape[1:], np.float64)
    for k in range(c.shape[0]):
        acc += (1.0 - acc[3:4]) * c[k]
    return acc


def psnr(a: np.ndarray, b: np.ndarray, peak: float = 1.0) -> float:
    mse = float(np.mean((np.asarray(a, np.float64)
                         - np.asarray(b, np.float64)) ** 2))
    return float("inf") if mse == 0 else 10.0 * np.log10(peak * peak / mse)


def payload_faults(payload: dict, k: int, covered_min: float) -> list:
    """Why this delivered frame is not a sound VDI of the configuration
    (empty when it is): f32 at K slots of 24 B, finite colours, ordered
    finite depths on live slots, a share of covered pixels."""
    c, d = payload["vdi_color"], payload["vdi_depth"]
    out = []
    if c.dtype != np.float32 or d.dtype != np.float32:
        out.append(f"dtypes {c.dtype}/{d.dtype}, not float32")
    if (c.ndim != 4 or d.ndim != 4 or c.shape[:2] != (k, 4)
            or d.shape[:2] != (k, 2) or c.shape[2:] != d.shape[2:]):
        return out + [f"shapes {c.shape}/{d.shape}, not ({k},4,H,W)/"
                      f"({k},2,H,W)"]
    if not np.isfinite(c).all():
        out.append("non-finite colour")
    live = c[:, 3] > 0.0
    if not ((d[:, 0][live] <= d[:, 1][live]).all()
            and np.isfinite(d[:, 1][live]).all()):
        out.append("a live slot has start > end or an infinite depth")
    covered = float(live.any(axis=0).mean())
    if not covered > covered_min:
        out.append(f"{covered:.4f} of pixels covered (<= {covered_min})")
    return out
