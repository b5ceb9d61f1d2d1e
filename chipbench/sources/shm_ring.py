"""The field source `shm_ring`: the field is not computed by the session.
A host process off JAX (this file run with `--produce`) writes a new field
into a two-slot shared-memory channel for every frame, in lockstep with the
session's `ingest.shm.ShmVolumeSource`, which pins the newest slot, puts it
on the device beside the frame loop and hands the landed field to the next
frame: the reference's own data path (SURVEY 0: ShmAllocator / ShmBuffer /
SemManager), as `InSituSession(cfg, sim=<shm source>, sinks=[...])`.

The field. Base = the seeded Gray-Scott start (`reference.perturb`, `--seed`)
advanced `pre_evolve_steps` steps by the program's own sim on the chip in
set-up (data, not a reference), v read back once and piped to the producer
once. Field i = base x float32(1 + field_amplitude x sin(2 pi i /
field_period_frames)): one f32 multiply per cell, written by the producer IN
PLACE into the slot it acquired, on a few threads over z-slabs. The producer
holds one base field and no ring of ready fields; no process ever holds a
whole-grid float64 array. Frame i renders field i (sequence number i + 1):
the producer publishes field i + 1 only once the reader has pinned field i
(`consumed_seq` of the channel's control block, read through the producer's
own handle), so a run is the same for the same (cell, seed) and the
reference session can be fed the same fields without a channel.

Nothing outlives a run. The channel's name is fixed per cell AND per ground
(the checkout and the TMPDIR the run was given, hashed into the name: POSIX
shared memory has one namespace for the whole machine, and two checkouts or
two drivers on it must never meet in one segment); the same checkout finds
the same name again, so a stale one is superseded by `shm_channel_create`.
It is unlinked as soon as both sides have attached: the memory then lives
only as long as a process maps it, so SIGKILL of either side leaks nothing.
The producer dies with its parent (PR_SET_PDEATHSIG, and a look at
`getppid` in every wait), and `end_session` kills and reaps it, also when
the run fails.

What it owns of `correct` (printed where the sim's checks stand):
`ingest_fields_in_order` (the sequence number each frame's `advance` took,
from frame 0 to the window's end: i + 1 for frame i, one for every frame
dispatched), `producer_frames_dropped` 0, `ingest_fields_repeated` 0 (the program's counter), and
`host_field_frame0_max_abs_diff`: the field on the device after frame 0,
read back, against field 0 recomputed here from a base made by the
benchmark's own plain roll (`reference.gray_scott_steps`): exact, 0.0.
Field 0 lands before the producer has a second field to write, so
`host_field_last_slabs_differing` asks the same of the field the window's
LAST frame took, uploaded with everything else in flight: read back slab by
slab after the window, kept as digests, against the plain field of its
sequence number: 0 slabs differ. The
plain reference session takes no channel, no process and no uploader: a sim
adapter whose `advance` computes field i in numpy and puts it on the device,
blocking.

Read from the traffic file: `field_perturbation`, `pre_evolve_steps`,
`field_period_frames`, `field_amplitude`. From the configuration:
`base_overrides` (what the set-up's sim is built from), `channel_slots`,
`producer_threads`, `limits.field_max_abs_diff`.
"""

import hashlib
import math
import os
import subprocess
import sys
import tempfile
import time
import types

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
POLL_S = 0.0005             # the producer's look at the control block
READY = b"channel\n"        # the producer's one line: the channel exists


def factor(i: int, traf: dict) -> np.float32:
    """What field i multiplies the base by."""
    return np.float32(1.0 + traf["field_amplitude"] * math.sin(
        2.0 * math.pi * i / traf["field_period_frames"]))


def channel_name(cell: dict) -> str:
    """One name per cell and per ground: the same for every run of this
    checkout under this TMPDIR, and no other run's."""
    ground = hashlib.sha1(
        (ROOT + "\0" + tempfile.gettempdir()).encode()).hexdigest()[:10]
    return f"/chipbench_{cell['name'].replace('.', '_')}_{ground}"


# ------------------------------------------------------------- the producer

def die_with_parent(parent: int) -> None:
    """SIGKILL for this process when the thread that started it is gone;
    `orphaned` is the same question asked again in every wait."""
    import ctypes
    import signal

    ctypes.CDLL(None, use_errno=True).prctl(1, signal.SIGKILL)  # PDEATHSIG
    if orphaned(parent):        # it went before the call
        os._exit(0)


def orphaned(parent: int) -> bool:
    return os.getppid() != parent


def produce(channel: str, grid, slots: int, period: int, amplitude: float,
            threads: int, parent: int) -> None:
    """The producer process: one base field from stdin, then field after
    field written in place and published in lockstep, until it is killed
    or its parent is gone. Imports nothing of JAX."""
    from concurrent.futures import ThreadPoolExecutor

    from scenery_insitu_tpu.ingest.shm import ShmProducer

    die_with_parent(parent)
    traf = {"field_period_frames": period, "field_amplitude": amplitude}
    prod = ShmProducer(channel, grid, nslots=slots)
    sys.stdout.buffer.write(READY)
    sys.stdout.buffer.flush()
    base = np.empty(grid, np.float32)
    into, got = memoryview(base).cast("B"), 0
    while got < base.nbytes:
        n = sys.stdin.buffer.readinto(into[got:])
        if not n:
            return              # the harness went away before the base
        got += n
    bounds = np.linspace(0, grid[0], threads + 1).astype(int)
    slabs = [slice(a, b) for a, b in zip(bounds[:-1], bounds[1:]) if b > a]
    pool = ThreadPoolExecutor(len(slabs))

    def until(ready) -> None:
        while not ready(prod.stats()):
            if orphaned(parent):
                os._exit(0)
            time.sleep(POLL_S)

    def writable(st: dict) -> bool:     # what shm_producer_acquire takes
        return any(s["readers"] == 0 for j, s in enumerate(st["slots"])
                   if j != st["latest_slot"])

    i = 0
    while True:
        until(writable)                 # never a drop: ask before acquiring
        slot = prod.acquire()
        f = factor(i, traf)
        list(pool.map(lambda z: np.multiply(base[z], f, out=slot[z]), slabs))
        del slot
        # lockstep: field i goes out once the reader has pinned field i - 1
        until(lambda st: st["consumed_seq"] >= i)
        prod.commit()
        i += 1


# ---------------------------------------------------------- the timed path

def base_on_the_chip(cell: dict, seed: int) -> np.ndarray:
    """The base field by the program's own sim: the seeded start advanced
    through the traffic's set-up steps, v on the host."""
    import gc

    import jax

    from chipbench import reference
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import VolumeSimAdapter

    conf, traf = cell["config_file"], cell["traffic_file"]
    cfg = FrameworkConfig().with_overrides(*conf["base_overrides"])
    sim = VolumeSimAdapter(cfg)
    sim.state = sim.state._replace(v=jax.jit(reference.perturb)(
        sim.state.v, reference.seed_key(seed),
        np.float32(traf["field_perturbation"])))
    pre, chunk = int(traf["pre_evolve_steps"]), cfg.sim.steps_per_frame
    if pre % chunk:
        raise ValueError(f"pre_evolve_steps {pre} is not a multiple of "
                         f"sim.steps_per_frame {chunk}")
    for _ in range(pre // chunk):       # the program a frame of the sim
        sim.advance(chunk)              # cells runs: 50 calls, not 500 steps
    base = np.asarray(sim.field)
    del sim
    gc.collect()                        # u, v and the scratch leave the chip
    return base


def start_producer(cell: dict):
    conf, traf = cell["config_file"], cell["traffic_file"]
    grid = conf["shape"]["grid"]
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--produce",
         channel_name(cell), *map(str, grid), str(conf["channel_slots"]),
         str(traf["field_period_frames"]), repr(traf["field_amplitude"]),
         str(conf["producer_threads"]), str(os.getpid())],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=ROOT,
        env=dict(os.environ, PYTHONPATH=ROOT))
    return proc


def end_producer(proc) -> None:
    """Kill and reap; its mapping of the channel goes with it."""
    if proc.poll() is None:
        proc.kill()
    for pipe in (proc.stdin, proc.stdout):
        try:
            pipe.close()
        except OSError:         # a pipe whose other end is already gone
            pass
    proc.wait()


def build_session(cell: dict, overrides, seed: int, sink=None, viewer=None,
                  fed=None):
    """`InSituSession(cfg, sim=ShmVolumeSource(...), sinks=[sink])` with the
    producer process behind the channel; with `fed` (what `plain_reference`
    returned) the reference session: `InSituSession(cfg, sim=Fed(...))`."""
    from chipbench import harness
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.ingest import shm
    from scenery_insitu_tpu.runtime.session import InSituSession

    cfg = FrameworkConfig().with_overrides(*overrides)
    if fed is not None:
        sess = InSituSession(cfg, sim=Fed(fed.pop("base"),
                                          cell["traffic_file"]),
                             sinks=[sink] if sink else [])
        sess.steering = viewer
        return sess
    if not (hasattr(shm.ShmVolumeSource, "close")
            and hasattr(shm.ShmProducer, "commit")
            and hasattr(InSituSession, "close")):
        raise harness.BenchFailure(
            "this program's ingest.shm has no overlapped ShmVolumeSource "
            "(close, in-place slots, consumed_seq): the cell cannot run on "
            "it")
    conf = cell["config_file"]
    phases, t0 = [], time.perf_counter()

    def phase(name: str) -> None:
        nonlocal t0
        phases.append(f"{name} {time.perf_counter() - t0:.2f}")
        t0 = time.perf_counter()

    shm.ensure_built()
    phase("native library")
    proc, src = start_producer(cell), None
    try:
        if proc.stdout.read(len(READY)) != READY:
            raise harness.BenchFailure("the producer ended before it made "
                                       "its channel")
        src = shm.ShmVolumeSource(
            channel_name(cell), conf["shape"]["grid"], timeout_ms=30000,
            frame_timeout_ms=conf["frame_timeout_ms"])
        shm.unlink(channel_name(cell))      # both sides hold their mapping
        phase("producer up, channel attached and unlinked")
        base = base_on_the_chip(cell, seed)
        phase("base field on the chip")
        proc.stdin.write(memoryview(base).cast("B"))
        proc.stdin.flush()
        del base
        phase("base handed over")
        ring = types.SimpleNamespace(proc=proc, seqs=[], frames=None,
                                     dropped=None, repeated=None,
                                     last_field=None)
        take = src.advance

        def advance(n: int) -> None:        # which field each frame took
            take(n)
            ring.seqs.append(src.last_seq)

        src.advance = advance
        sess = InSituSession(cfg, sim=src, sinks=[sink] if sink else [])
        sess.shm_ring = ring
        phase("session (waits for field 0 to land)")
    except BaseException:
        if src is not None:
            src.close()
        end_producer(proc)
        shm.unlink(channel_name(cell))      # if it failed before the unlink
        raise
    print("[chipbench] shm_ring set-up by phase (s): " + ", ".join(phases),
          flush=True)
    sess.steering = viewer
    return sess


def end_session(sess) -> None:
    """The program's own end (uploader joined, consumer detached), then the
    producer killed and reaped. The reference session has neither."""
    ring = getattr(sess, "shm_ring", None)
    try:
        sess.close()
    finally:
        if ring is not None:
            end_producer(ring.proc)


def keep(sess) -> dict:
    """After frame 0: the field the frame was rendered from, read back,
    and the log `wait` completes at the window's end."""
    return {"field0": np.asarray(sess.sim.field),
            "ring": getattr(sess, "shm_ring", None)}


def wait(sess) -> None:
    """The event a window ends on: the last field taken is on the device,
    and the one the uploader fetched ahead has landed and let go of its
    slot, so that a window of n frames holds n uploads. Then the counts
    the window checks read."""
    import jax

    jax.block_until_ready(sess.sim.field)
    ring = getattr(sess, "shm_ring", None)
    if ring is None:
        return
    deadline = time.monotonic() + 10.0
    st = sess.sim.consumer.stats()
    while time.monotonic() < deadline and not (
            st["consumed_seq"] > len(ring.seqs)
            and not any(s["readers"] for s in st["slots"])):
        time.sleep(0.001)
        st = sess.sim.consumer.stats()
    ring.frames = sess.frame_index
    ring.last_field = sess.sim.field    # of seqs[-1]; a reference, no copy
    ring.dropped = st["frames_dropped"]
    ring.repeated = int(sess.obs.counters.get("ingest_fields_repeated", 0))


SLABS = 8                   # the last field is read back in this many


def slabs_of(planes: int) -> list:
    step = -(-planes // SLABS)
    return [(a, min(a + step, planes)) for a in range(0, planes, step)]


def digest(slab: np.ndarray) -> bytes:
    return hashlib.blake2b(np.ascontiguousarray(slab).data).digest()


def last_field_digests(field) -> list:
    """The field the window's last frame took, off the device one slab at
    a time: digests stay, no second whole field on the host."""
    import jax

    planes = field.shape[0]
    spans = slabs_of(planes)
    step = spans[0][1]
    cut = jax.jit(lambda f, a: jax.lax.dynamic_slice_in_dim(f, a, step, 0))
    out = []
    for a, _ in spans:
        start = min(a, planes - step)   # what dynamic_slice clamps `a` to
        out.append(digest(np.asarray(cut(field, start))[a - start:]))
    return out


def window_checks(cell: dict, kept: dict) -> list:
    ring = kept["ring"]
    kept["last"] = (ring.seqs[-1] - 1, last_field_digests(ring.last_field))
    ring.last_field = None
    n = ring.frames
    in_order = sum(1 for i, s in enumerate(ring.seqs) if s == i + 1)
    return [("ingest_fields_in_order", f"{in_order}/{n}", n,
             in_order == n == len(ring.seqs)),
            ("producer_frames_dropped", ring.dropped, 0, ring.dropped == 0),
            ("ingest_fields_repeated", ring.repeated, 0, ring.repeated == 0)]


# ------------------------------------------------------ the plain reference

class Fed:
    """The reference session's sim adapter (`kind`, `advance`, `field`):
    field i computed in numpy and put on the device, blocking."""

    kind = "external"

    def __init__(self, base: np.ndarray, traf: dict):
        self.base, self.traf, self.frames = base, traf, 0
        self.field = self._put(0)       # the session asks for its shape

    def _put(self, i: int):
        import jax

        return jax.block_until_ready(jax.device_put(
            self.base * factor(i, self.traf)))

    def advance(self, n: int) -> None:  # n means nothing to a host field
        self.field = self._put(self.frames)
        self.frames += 1


def plain_reference(cell: dict, seed: int) -> dict:
    """Field 0 from a base made by the benchmark's own plain roll, and that
    base for `build_session` to feed the reference session from."""
    from chipbench import reference

    shape, traf = cell["config_file"]["shape"], cell["traffic_file"]
    u, v = reference.gray_scott_start(shape["grid"], seed,
                                      traf["field_perturbation"])
    base = np.asarray(reference.gray_scott_steps(
        u, v, int(traf["pre_evolve_steps"]))[1])
    del u, v
    return {"field0": base * factor(0, traf), "base": base}


def max_abs_diff(a: np.ndarray, b: np.ndarray) -> float:
    """Slab by slab: no temporary of the whole grid."""
    return max(float(np.abs(a[z] - b[z]).max()) for z in range(a.shape[0]))


def compare(cell: dict, kept: dict, ref: dict) -> list:
    """Bytes that crossed the channel and the link are read back as the
    plain formula gives them: exact."""
    limit = cell["config_file"]["limits"]["field_max_abs_diff"]
    err = max_abs_diff(kept["field0"], ref["field0"])
    # field 0 is the base to the bit (its factor is 1.0), so the plain
    # field i is `ref["field0"]` times factor(i), as the producer wrote it
    i, got = kept["last"]
    f = factor(i, cell["traffic_file"])
    differing = sum(d != digest(ref["field0"][a:b] * f) for d, (a, b) in zip(
        got, slabs_of(ref["field0"].shape[0])))
    return [("host_field_frame0_max_abs_diff", err, limit, err <= limit),
            ("host_field_last_slabs_differing", differing, 0,
             differing == 0)]


def rounded(cell: dict, seed: int, kept: dict) -> dict:
    """The control: the plain field 0 held in bfloat16."""
    from chipbench import reference

    return dict(kept, field0=reference.round_bf16(
        plain_reference(cell, seed)["field0"]))


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    if sys.argv[1] != "--produce":
        sys.exit("usage: the harness starts this file with --produce")
    a = sys.argv[2:]
    produce(a[0], tuple(int(x) for x in a[1:4]), int(a[4]), int(a[5]),
            float(a[6]), int(a[7]), int(a[8]))
