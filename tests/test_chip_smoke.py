"""`chip_smoke.py` on the CPU: it must refuse to run without a TPU, its
checks must pass at a tiny size on one and on four virtual devices (the
function the script calls, with the CPU's schedules named), and a
fallback-ledger row must fail it. What the chip says is in CHANGES.md;
this keeps the script from rotting between chip runs."""

import os
import subprocess
import sys

import jax
import pytest

import chip_smoke

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# on the CPU the session's sim runs the XLA roll path; named, it is a
# configuration, not a fallback
_CPU = ("sim.fused_stencil=false",)


def test_script_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    p = subprocess.run([sys.executable, os.path.join(_ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "platform='cpu'" in p.stderr, p.stderr[-400:]
    assert '"ok"' not in p.stdout


def test_smoke_passes_on_one_and_four_virtual_devices(tmp_path):
    s = chip_smoke.smoke(grid=32, k=8, warmup=1, steady=1, four=True,
                         out_dir=str(tmp_path), extra=_CPU)
    assert s["one_rank"]["schedules"]["ranks"] == 1
    assert s["four_ranks"]["sim_devices"] == 4
    assert s["four_ranks"]["schedules"]["ranks"] == 4
    assert s["reference"]["sim_field_max_abs_diff_frame0"] <= \
        chip_smoke.SIM_ATOL
    assert s["ledger"] == []
    assert (tmp_path / "chip_smoke_frame.png").stat().st_size > 0


def test_a_ledger_row_fails_it():
    """Default config on the CPU: the fused stencil gives way to the roll
    path on the ledger (sim.fused_stencil) — exactly the kind of row that
    must not pass on the chip."""
    with pytest.raises(chip_smoke.SmokeFailure, match="sim.fused_stencil"):
        chip_smoke.smoke(grid=32, k=8, warmup=1, steady=1)


def test_compile_cache_is_placed_from_outside(monkeypatch):
    from scenery_insitu_tpu.utils.backend import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before   # code set nothing
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert enable_compile_cache() == os.path.join(_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(_ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
