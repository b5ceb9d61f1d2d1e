"""The field source `host_ring`, for the rehearsal only: a field the
session does not compute. A seeded ring of host arrays is handed to
`InSituSession(cfg, sim=...)`; every frame the next one is put on the
device and rendered. It has no Gray-Scott state and no roll to be compared
with: what it keeps, its reference and its comparison are its own, and
`harness.py` runs it with no line that names it. This is the shape a source
fed through shared memory will have (its producer process, its channel
and its cell are a later PR's).
"""

import numpy as np

SLOTS = 4


def ring(grid, seed: int, dtype=np.float32) -> list:
    """`SLOTS` fields from the seed, in numpy alone: a central block whose
    level differs from slot to slot, times seeded noise (every seed the
    same block, so the same work, on other data)."""
    d, h, w = grid
    rng = np.random.default_rng(seed)
    out = []
    for slot in range(SLOTS):
        f = np.zeros(grid, np.float64)
        f[d // 4: 3 * d // 4, h // 4: 3 * h // 4, w // 4: 3 * w // 4] = \
            0.2 + 0.04 * slot
        f *= 1.0 + 0.05 * rng.uniform(-1.0, 1.0, grid)
        out.append(f.astype(dtype).astype(np.float32))
    return out


class HostRing:
    """The session's sim adapter (`kind`, `advance`, `field`): `advance`
    puts the next host array of the ring on the device."""

    kind = "external"

    def __init__(self, fields: list):
        import jax

        self._put = jax.device_put
        self.fields = fields
        self.uploads = 0
        self.ended = False
        self.field = self._put(fields[0])

    def advance(self, n: int) -> None:      # n means nothing to a host field
        self.field = self._put(self.fields[self.uploads % len(self.fields)])
        self.uploads += 1


def build_session(cell: dict, overrides, seed: int, sink=None, viewer=None,
                  fed=None):
    """`InSituSession(cfg, sim=HostRing, sinks=[sink])`: the ring made from
    the seed, for the run and for the reference session alike."""
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import InSituSession

    cfg = FrameworkConfig().with_overrides(*overrides)
    sess = InSituSession(
        cfg, sim=HostRing(ring(cell["config_file"]["shape"]["grid"], seed)),
        sinks=[sink] if sink else [])
    sess.steering = viewer
    return sess


def end_session(sess) -> None:
    """What a source with a producer process would stop here."""
    sess.sim.ended = True


def keep(sess) -> dict:
    """After frame 0: the field on the device, read back, and how many
    fields have been put there."""
    return {"field0": np.asarray(sess.sim.field),
            "uploads": sess.sim.uploads}


def wait(sess) -> None:
    import jax

    jax.block_until_ready(sess.sim.field)


def window_checks(cell: dict, kept: dict) -> list:
    return [("host_fields_put_by_frame0", kept["uploads"], 1,
             kept["uploads"] == 1)]


def plain_reference(cell: dict, seed: int) -> dict:
    return {"field0": ring(cell["config_file"]["shape"]["grid"], seed)[0]}


def compare(cell: dict, kept: dict, ref: dict) -> list:
    """Bytes put on the device are read back as they were: exact."""
    err = float(np.abs(kept["field0"] - ref["field0"]).max())
    return [("host_field_frame0_max_abs_diff", err, 0.0, err == 0.0)]


def rounded(cell: dict, seed: int, kept: dict) -> dict:
    """The control: the ring held in bfloat16."""
    import ml_dtypes

    return dict(kept, field0=ring(cell["config_file"]["shape"]["grid"], seed,
                                  ml_dtypes.bfloat16)[0])
