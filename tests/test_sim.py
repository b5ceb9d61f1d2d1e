import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from scenery_insitu_tpu.sim import grayscott as gs


def test_grayscott_stays_bounded():
    st = gs.GrayScott.init((16, 16, 16), n_seeds=2)
    st = gs.multi_step(st, 50)
    u, v = np.asarray(st.u), np.asarray(st.v)
    assert np.isfinite(u).all() and np.isfinite(v).all()
    assert u.min() >= -0.1 and u.max() <= 1.5
    assert v.min() >= -0.1 and v.max() <= 1.5


def test_grayscott_develops_structure():
    st = gs.GrayScott.init((16, 16, 16), n_seeds=2)
    st2 = gs.multi_step(st, 100)
    # the v field must neither die out nor saturate
    v = np.asarray(st2.field)
    assert v.max() > 0.05
    assert v.std() > 1e-3


def test_grayscott_sharded_matches_single():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("ranks",))
    st = gs.GrayScott.init((16, 8, 8), n_seeds=2)
    ref = gs.multi_step(st, 20)
    shard = NamedSharding(mesh, P("ranks", None, None))
    sh = gs.GrayScott(jax.device_put(st.u, shard),
                      jax.device_put(st.v, shard), st.params)
    out = gs.multi_step(sh, 20)
    assert np.allclose(np.asarray(ref.v), np.asarray(out.v), atol=1e-5)


def test_graft_entry_single():
    import __graft_entry__ as ge
    fn, args = ge.entry()
    color, depth, u, v = jax.jit(fn)(*args)
    assert np.isfinite(np.asarray(color)).all()
    assert np.isfinite(np.asarray(u)).all() and np.isfinite(np.asarray(v)).all()
    d = np.asarray(depth)
    live = np.asarray(color)[:, 3] > 0
    assert np.isfinite(d[:, 0][live]).all()  # empty slots are +inf by design


def test_graft_dryrun_multichip():
    import __graft_entry__ as ge
    ge.dryrun_multichip(4)
    ge.dryrun_multichip(8)


def test_pallas_stencil_parity():
    """The fused Pallas Gray-Scott step (TPU fast path) must match the XLA
    roll formulation exactly (interpret mode on CPU)."""
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    st = gs.GrayScott.init((8, 16, 128), n_seeds=2)
    p = st.params
    pvec = jnp.stack([p.f, p.k, p.du, p.dv, p.dt])
    assert ps.pick_tz(st.u.shape) > 0
    u2, v2 = ps.step_pallas(st.u, st.v, pvec, interpret=True)
    ref = gs.step(st)
    np.testing.assert_allclose(np.asarray(ref.u), np.asarray(u2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.v), np.asarray(v2), atol=1e-6)


@pytest.mark.parametrize("t_steps", [2, 4])
def test_pallas_stencil_multistep_parity(t_steps):
    """T fused steps in one kernel pass ≡ T single XLA steps: the T-slice
    halo + shrinking-validity scheme must keep the central slab exact,
    including periodic wrap across the z seam."""
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    st = gs.GrayScott.init((16, 16, 128), n_seeds=2)
    p = st.params
    pvec = jnp.stack([p.f, p.k, p.du, p.dv, p.dt])
    assert ps.pick_tz(st.u.shape, t_steps) > 0
    u2, v2 = ps.step_pallas(st.u, st.v, pvec, t_steps, interpret=True)
    ref = st
    for _ in range(t_steps):
        ref = gs.step(ref)
    np.testing.assert_allclose(np.asarray(ref.u), np.asarray(u2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref.v), np.asarray(v2), atol=1e-5)


def test_pallas_multistep_remainder():
    """multi_step_pallas must advance exactly n steps for n not divisible
    by the preferred fusion factor."""
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    st = gs.GrayScott.init((16, 16, 128), n_seeds=2)
    p = st.params
    pvec = jnp.stack([p.f, p.k, p.du, p.dv, p.dt])
    u2, v2 = ps.multi_step_pallas(st.u, st.v, pvec, 6, interpret=True)
    ref = gs.multi_step(st, 6)
    np.testing.assert_allclose(np.asarray(ref.u), np.asarray(u2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref.v), np.asarray(v2), atol=1e-5)


def _eqns_outside_kernels(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold
    (jit, loops, branches), in order; a ``pallas_call``'s kernel body is
    not entered (the kernel's own plane loops are scans)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns_outside_kernels(sub)


@pytest.mark.parametrize("with_ranges", [False, True],
                         ids=["plain", "ranges"])
@pytest.mark.parametrize("n", [10, 6, 4, 3, 1])
def test_pallas_multistep_is_a_static_walk(n, with_ranges):
    """The traced sim program is exactly the scheduled kernels, in
    order, each fed by the one before: sum(reps) `pallas_call`s of the
    scheduled (T, tz, th) and no loop around them (a `fori_loop` with
    static bounds traces as `scan`): around a loop the TPU compiler
    copies u and v into the carry once per trip."""
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    shape = (16, 32, 128)
    assert ps.fused_supported(shape)
    u = jnp.zeros(shape, jnp.float32)
    pvec = jnp.zeros((5,), jnp.float32)
    if with_ranges:
        fn = lambda u, v, p: ps.multi_step_pallas_ranges(
            u, v, p, n=n, nzb=2, nyb=2, interpret=True)
    else:
        fn = lambda u, v, p: ps.multi_step_pallas(u, v, p, n=n,
                                                  interpret=True)
    eqns = list(_eqns_outside_kernels(jax.make_jaxpr(fn)(u, u, pvec).jaxpr))
    names = [e.primitive.name for e in eqns]
    assert not {"scan", "while"} & set(names), names

    passes, remaining = ps.schedule(shape, n)
    assert remaining == 0
    want = [(t, tz, th) for _, t, tz, th, reps in passes
            for _ in range(reps)]
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    got = []
    for e in calls:
        # in_specs: params (SMEM), then the centre (tz, th, W) block of u
        centre = e.params["grid_mapping"].block_mappings[1].block_shape
        tz, th, _ = (int(getattr(b, "block_size", b)) for b in centre)
        assert e.params["name"].startswith("gray_scott_fused_t")
        got.append((int(e.params["name"].rsplit("t", 1)[1]), tz, th))
    assert got == want
    # each pass reads the u and v the pass before it wrote, directly
    for prev, nxt in zip(calls, calls[1:]):
        assert set(nxt.invars[1:]) == set(prev.outvars[:2])
    # one device: the parameters and nine views each of u and v, as
    # before the kernel served shards (no halo operands, used or not)
    assert {len(e.invars) for e in calls} == {19}


def test_stencil_rejection_raises_on_default_path(monkeypatch):
    """On TPU the fused stencil is the default sim path and nothing
    stands between it and the compiler: a kernel the backend cannot
    compile must RAISE with the compiler's message, not hand the session
    the XLA roll path under a ledger row. Shown on CPU by claiming the
    TPU backend — the Mosaic kernel cannot lower here."""
    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    st = gs.GrayScott.init((8, 8, 128), n_seeds=1)
    assert ps.fused_supported(st.u.shape)
    obs.clear_ledger()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(Exception) as ei:
        jax.block_until_ready(gs.multi_step_fast(st, 2).u)
    assert not isinstance(ei.value, AssertionError)
    assert not any(e["component"] == "sim.fused_stencil"
                   for e in obs.ledger())
    # a grid no tile of the kernel fits is a selection the code can
    # observe, not a refusal: that one still gives way, on the ledger
    odd = gs.GrayScott.init((8, 8, 48), n_seeds=1)
    assert not ps.fused_supported(odd.u.shape)
    out = gs.multi_step_fast(odd, 1)
    assert out.u.shape == (8, 8, 48)
    assert any(e["component"] == "sim.fused_stencil" for e in obs.ledger())


@pytest.mark.parametrize("t_steps", [2, 4])
def test_pallas_stencil_2d_multistep_parity(t_steps):
    """T fused steps of the 2D-blocked (z x h) kernel ≡ T single XLA
    steps — the square T-halo (edges + corners, periodic wrap in BOTH
    blocked axes via index_map arithmetic) must keep every central tile
    exact. The asymmetric grid makes a z/h axis swap impossible to miss."""
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    st = gs.GrayScott.init((16, 32, 128), n_seeds=3)
    p = st.params
    pvec = jnp.stack([p.f, p.k, p.du, p.dv, p.dt])
    cands = ps.tile2d_candidates(st.u.shape, t_steps)
    assert cands, "no 2D tile for the test grid"
    # exercise a non-trivial grid in both axes, not just the best tile
    tz, th = [c for c in cands if c[0] < 16 and c[1] < 32][0] \
        if any(c[0] < 16 and c[1] < 32 for c in cands) else cands[-1]
    u2, v2 = ps.step_pallas2d(st.u, st.v, pvec, t_steps, interpret=True,
                              tz=tz, th=th)
    ref = st
    for _ in range(t_steps):
        ref = gs.step(ref)
    np.testing.assert_allclose(np.asarray(ref.u), np.asarray(u2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref.v), np.asarray(v2), atol=1e-5)


def test_best_schedule_prefers_lower_traffic():
    """_best_schedule must rank 2D tiles above the 1D slab when the
    whole-H slab does not fit VMEM (the 512^3 regime), and take the 1D
    slab where it does."""
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    kind, tz, th = ps._best_schedule((512, 512, 512), 4)
    assert kind == "2d" and tz % 4 == 0 and th % 8 == 0
    # a small plane fits whole: the z slab (th == H) has the least halo
    sched = ps._best_schedule((64, 48, 128), 1)
    assert sched is not None and sched[0] == "1d" and sched[2] == 48


# ------------------------------------------------ the fused stencil on a
# z-sharded field: every pass takes its outer z halo from the ring
# neighbours (ISSUE 34)

_RANKS = 4
_RING_GRID = (32, 32, 128)          # 8 planes per rank


def _ring_mesh():
    return jax.sharding.Mesh(np.array(jax.devices()[:_RANKS]), ("ranks",))


def _seam_state(grid=_RING_GRID, ranks=_RANKS) -> gs.GrayScott:
    """A start whose pattern crosses every rank boundary and the global
    wrap: one cube ON each seam (plane 0 is the seam between the last
    rank and the first), at a place of its own in the plane, under noise
    that is symmetric about nothing — a cube centred on a seam looks the
    same from both sides, and would hide north and south exchanged."""
    d, h, w = grid
    zz, yy, xx = np.meshgrid(np.arange(d), np.arange(h), np.arange(w),
                             indexing="ij")
    u = np.ones(grid, np.float32)
    v = np.zeros(grid, np.float32)
    for r in range(ranks):
        z0, y0, x0 = r * d // ranks, 4 + 7 * r, 16 + 24 * r
        dz = np.minimum((zz - z0) % d, (z0 - zz) % d)
        cube = (dz < 3) & (abs(yy - y0) < 4) & (abs(xx - x0) < 6)
        u[cube], v[cube] = 0.5, 0.25
    rng = np.random.default_rng(34)
    u -= 0.02 * rng.random(grid, np.float32)
    v += 0.02 * rng.random(grid, np.float32)
    return gs.GrayScott(jnp.asarray(u), jnp.asarray(v),
                        gs.GrayScottParams.create())


def _on_ring(st, mesh):
    shard = NamedSharding(mesh, P("ranks", None, None))
    return jax.device_put(st.u, shard), jax.device_put(st.v, shard)


def _seam_planes(d, ranks, t):
    """The 2T planes around each rank boundary and around the wrap."""
    return sorted({(r * d // ranks + o) % d
                   for r in range(ranks) for o in range(-t, t)})


def _assert_ring_parity(got, ref, t):
    for name, a, b in (("u", got[0], ref.u), ("v", got[1], ref.v)):
        a, b = np.asarray(a), np.asarray(b)
        seams = _seam_planes(a.shape[0], _RANKS, t)
        err = np.abs(a - b).max(axis=(1, 2))
        assert err[seams].max() <= 1e-5, (
            f"{name}: seam planes differ from the roll by {err[seams]}")
        np.testing.assert_allclose(a, b, atol=1e-5)


# (T, tz, th) on the 8-plane shard of `_RING_GRID`: the 1-D slab and the
# 2-D tile at each T, and a shard that is ONE z-block (first and last)
_RING_TILES = [(1, 2, 32), (1, 4, 16), (2, 4, 32), (2, 2, 8),
               (4, 4, 32), (4, 4, 16), (4, 8, 32), (4, 8, 16)]


@pytest.mark.parametrize("t,tz,th", _RING_TILES,
                         ids=[f"t{t}-{tz}x{th}" for t, tz, th in _RING_TILES])
def test_pallas_stencil_sharded_pass_parity(t, tz, th):
    """One T-step pass on four z-shards == T plain steps of the whole
    field, on the planes next to every seam in particular; and the
    start does tell: the same kernel wrapping inside each shard differs
    there."""
    from jax import shard_map

    from scenery_insitu_tpu.sim import pallas_stencil as ps

    mesh = _ring_mesh()
    st = _seam_state()
    u, v = _on_ring(st, mesh)
    spec = P("ranks", None, None)

    def sharded(ring):
        def local(u, v, p):
            halos = ps._ring_halos(u, v, t, "ranks") if ring else ()
            return tuple(ps._fused_call(u, v, p, t, tz, th, True, False,
                                        halos))
        return jax.jit(shard_map(local, mesh=mesh, in_specs=(spec, spec, P()),
                                 out_specs=(spec, spec), check_vma=False))

    ref = gs.multi_step(st, t)
    pvec = jnp.stack(tuple(st.params))
    got = sharded(True)(u, v, pvec)
    assert got[0].sharding.spec == spec and got[1].sharding.spec == spec
    _assert_ring_parity(got, ref, t)
    wrapped = sharded(False)(u, v, pvec)
    seams = _seam_planes(_RING_GRID[0], _RANKS, t)
    assert np.abs(np.asarray(wrapped[1]) - np.asarray(ref.v))[seams].max() \
        > 1e-3


@pytest.mark.parametrize("n", [10, 7, 1])
def test_pallas_multistep_sharded_parity(n):
    """The jitted entry the session runs: n steps in `schedule`'s passes
    on the shard (n = 10: 4 + 4 + 2), halos exchanged before each."""
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    mesh = _ring_mesh()
    st = _seam_state()
    u, v = _on_ring(st, mesh)
    local = (_RING_GRID[0] // _RANKS,) + _RING_GRID[1:]
    passes, remaining = ps.schedule(local, n, ring=True)
    assert remaining == 0
    if n == 10:
        assert [(t, reps) for _, t, _, _, reps in passes] == [(4, 2), (2, 1)]
    got = ps.multi_step_pallas_sharded(u, v, tuple(st.params), n, mesh,
                                       "ranks", interpret=True)
    assert got[0].sharding == u.sharding and got[1].sharding == v.sharding
    _assert_ring_parity(got, gs.multi_step(st, n), 4)


@pytest.mark.parametrize("n", [10, 6, 3])
def test_pallas_multistep_sharded_is_a_static_walk(n):
    """The sharded sim program is the scheduled kernels, each fed by the
    one before, with the halo permutes of T planes between them: no loop,
    and nothing but the kernels writes an array the size of a shard (a
    padded copy of the shard per pass is what the halo operands are
    there to avoid)."""
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    mesh = _ring_mesh()
    dn = _RING_GRID[0] // _RANKS
    local = (dn,) + _RING_GRID[1:]
    shard = NamedSharding(mesh, P("ranks", None, None))
    u = jax.ShapeDtypeStruct(_RING_GRID, jnp.float32, sharding=shard)
    params = (jax.ShapeDtypeStruct((), jnp.float32),) * 5
    fn = lambda u, v, p: ps.multi_step_pallas_sharded(
        u, v, p, n=n, mesh=mesh, axis="ranks", interpret=True)
    eqns = list(_eqns_outside_kernels(jax.make_jaxpr(fn)(u, u, params).jaxpr))
    names = [e.primitive.name for e in eqns]
    assert not {"scan", "while"} & set(names), names

    passes, remaining = ps.schedule(local, n, ring=True)
    assert remaining == 0
    want = [(t, tz, th) for _, t, tz, th, reps in passes
            for _ in range(reps)]
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    got = []
    for e in calls:
        centre = e.params["grid_mapping"].block_mappings[1].block_shape
        tz, th, _ = (int(getattr(b, "block_size", b)) for b in centre)
        got.append((int(e.params["name"].rsplit("t", 1)[1]), tz, th))
        # the parameters, then per field nine views of the shard and
        # three each of the two neighbours' planes
        assert len(e.invars) == 1 + 2 * (9 + 6)
    assert got == want
    assert ps.ring_halo_traffic(local, n) == (
        len(want), sum(4 * t for t, _, _ in want) * 4 * local[1] * local[2])
    assert names.count("ppermute") <= 2 * 2 * len(want)
    for prev, nxt in zip(calls, calls[1:]):
        assert set(prev.outvars[:2]) <= set(nxt.invars[1:])
    # what else writes an array as deep as the shard: nothing (slices and
    # permutes are T planes deep, T < Dn here)
    writers = {e.primitive.name for e in eqns for o in e.outvars
               if getattr(o.aval, "shape", ())[:1] in ((dn,), (dn + 2,),
                                                       (dn + 4,), (dn + 8,))
               and e.primitive.name not in ("pallas_call", "shard_map",
                                            "jit", "pjit")}
    assert not writers, writers


def test_fused_stencil_selection_on_a_shard():
    """What decides between the fused kernel and the roll on a z-sharded
    field is whether a tile fits ONE RANK'S SHARD at that T."""
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    shard = (512 // 4, 512, 512)                # gs512-4rank, per rank
    for t in (1, 2, 4):
        assert ps.fused_supported(shard, t, ring=True)
    assert ps.schedule(shard, 10, ring=True)[1] == 0
    # the halo blocks are counted: +5 MiB at T = 4, (32, 64) (Mosaic
    # allocates 71.00 MiB for that kernel, 66.00 without them)
    extra = ps._vmem_bytes(32, 64, 4, 512, True) - ps._vmem_bytes(32, 64, 4,
                                                                  512)
    assert extra == 2 * 2 * 2 * 4 * (64 + 16) * 512 * 4
    # a shard shallower than T has no T-step tile (T | tz | Dn) ...
    assert not ps.fused_supported((2, 16, 128), 4, ring=True)
    assert ps.fused_supported((2, 16, 128), 2, ring=True)
    assert [p[1] for p in ps.schedule((2, 16, 128), 10, ring=True)[0]] == [2]
    # ... and a shard no tile fits at all takes the roll
    assert not ps.fused_supported((8, 16, 48), ring=True)
    assert not ps.fused_supported((8, 12, 128), ring=True)
    # how a field is placed, as `multi_step_fast` reads it
    mesh = _ring_mesh()
    x = jnp.zeros(_RING_GRID, jnp.float32)
    assert gs._z_ring(x) == (None, None, _RING_GRID)
    on = lambda *spec: jax.device_put(x, NamedSharding(mesh, P(*spec)))
    assert gs._z_ring(on("ranks", None, None)) == (mesh, "ranks",
                                                   (8, 32, 128))
    assert gs._z_ring(on("ranks")) == (mesh, "ranks", (8, 32, 128))
    assert gs._z_ring(on(None, "ranks", None)) is None
    assert gs._z_ring(on()) is None             # replicated over four
    one = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("ranks",))
    assert gs._z_ring(jax.device_put(
        x, NamedSharding(one, P("ranks", None, None)))) == (
            None, None, _RING_GRID)


def _adapter(ranks, *extra):
    from scenery_insitu_tpu.config import FrameworkConfig
    from scenery_insitu_tpu.runtime.session import VolumeSimAdapter

    g = list(_RING_GRID)
    cfg = FrameworkConfig().with_overrides(f"sim.grid={g}".replace(" ", ""),
                                           *extra)
    mesh = _ring_mesh() if ranks > 1 else None
    return VolumeSimAdapter(cfg, mesh=mesh,
                            axis="ranks" if mesh is not None else None)


@pytest.mark.parametrize("pinned", [False, True], ids=["default", "pinned"])
def test_sharded_session_sim_off_tpu_takes_the_roll(pinned):
    """Off-TPU a sharded session still runs the roll: on the ledger
    (reason: the backend) when the fused stencil was asked for, silently
    when `sim.fused_stencil=false` named the roll. Either way the state
    keeps its placement."""
    from scenery_insitu_tpu import obs

    obs.clear_ledger()
    sim = _adapter(_RANKS, *(["sim.fused_stencil=false"] if pinned else []))
    placed = sim.state.u.sharding
    assert placed.spec == P("ranks", None, None)
    sim.advance(2)
    assert sim.state.u.sharding == placed and sim.state.v.sharding == placed
    rows = [e for e in obs.ledger() if e["component"] == "sim.fused_stencil"]
    if pinned:
        assert not rows
    else:
        assert len(rows) == 1 and "backend" in rows[0]["reason"]
    obs.clear_ledger()


@pytest.mark.parametrize("ranks,recording", [(4, True), (4, False),
                                             (1, True)])
def test_sharded_session_sim_on_tpu_is_fused_and_counted(ranks, recording,
                                                        monkeypatch):
    """Where the backend is a TPU (claimed here; the kernels interpreted)
    a sharded session's sim is the fused kernel on every shard: no ledger
    row, the placement kept, the roll's field; a recorder that records
    counts one halo exchange per pass (3 per frame at n = 10), one that
    does not counts nothing, and a one-device session has no such line."""
    import functools

    from scenery_insitu_tpu import obs
    from scenery_insitu_tpu.sim import pallas_stencil as ps

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for name in ("multi_step_pallas", "multi_step_pallas_sharded"):
        monkeypatch.setattr(ps, name, functools.partial(getattr(ps, name),
                                                        interpret=True))
    obs.clear_ledger()
    rec = obs.Recorder(enabled=recording)
    prev = obs.set_recorder(rec)
    try:
        sim = _adapter(ranks)
        start = sim.state
        placed = start.u.sharding
        sim.advance(10)
        sim.advance(10)
    finally:
        obs.set_recorder(prev)
    assert not obs.ledger()
    assert sim.state.u.sharding == placed and sim.state.v.sharding == placed
    ref = gs.multi_step(gs.GrayScott(np.asarray(start.u),
                                     np.asarray(start.v), start.params), 20)
    np.testing.assert_allclose(np.asarray(sim.state.v), np.asarray(ref.v),
                               atol=5e-5)
    halo = {k: v for k, v in rec.counters.items() if k.startswith("sim_halo")}
    if ranks > 1 and recording:
        plane = 4 * _RING_GRID[1] * _RING_GRID[2]
        assert halo == {"sim_halo_exchanges": 2 * 3,
                        "sim_halo_bytes": 2 * (4 + 4 + 2) * 2 * 2 * plane}
    else:
        assert not halo
