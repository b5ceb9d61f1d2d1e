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
