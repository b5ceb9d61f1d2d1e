"""The driver runs `python bench.py` and parses ONE JSON line. This runs
the real script as a subprocess on the CPU platform with tiny knobs and
checks the contract: one process, one JSON line, non-zero exit on any
failure."""

import json
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_emits_one_parseable_json_line():
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": _ROOT,
        "SITPU_BENCH_GRID": "24",
        "SITPU_BENCH_K": "4",
        "SITPU_BENCH_FRAMES": "2",
        "SITPU_BENCH_SIM_STEPS": "1",
    })
    p = subprocess.run([sys.executable, os.path.join(_ROOT, "bench.py")],
                       env=env, capture_output=True, text=True, timeout=480)
    assert p.returncode == 0, p.stderr[-800:]
    lines = [l for l in p.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, p.stdout
    d = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in d, d
    assert d["value"] is not None and d["value"] > 0
    assert d["unit"] == "frames/s"
    assert d["config"]["platform"] == "cpu"
    assert d["config"]["adaptive_mode"] == "temporal"   # bench default
    # observability contract (ISSUE 3): every artifact embeds the
    # fallback ledger and the device-cost snapshot of the compiled frame
    assert "degradations" in d, d
    assert any(e["component"] == "sim.fused_stencil"
               for e in d["degradations"])   # CPU run degrades the stencil
    assert "cost_analysis" in d, d


def test_bench_exits_nonzero_without_json_on_failure():
    """No fallback ladder: a backend JAX cannot initialize fails the run —
    non-zero exit, no JSON line carrying a number from somewhere else."""
    env = dict(os.environ)
    env.update({"PYTHONPATH": _ROOT, "JAX_PLATFORMS": "nope",
                "SITPU_BENCH_GRID": "24"})
    p = subprocess.run([sys.executable, os.path.join(_ROOT, "bench.py")],
                       env=env, capture_output=True, text=True, timeout=480)
    assert p.returncode != 0
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]


def test_bench_scanloop_render_only_modes():
    """The round-5 diagnostic modes: SIM_STEPS=0 (render-only, the
    reference FPS-harness semantics) + SCAN_FRAMES=1 (whole loop in one
    lax.scan executable) must produce the tagged metric and a real
    number — these are the dispatch-tax / in-situ-split A/Bs, so a
    silent breakage would burn chip time."""
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": _ROOT,
        "SITPU_BENCH_GRID": "24",
        "SITPU_BENCH_K": "4",
        "SITPU_BENCH_FRAMES": "2",
        "SITPU_BENCH_SIM_STEPS": "0",
        "SITPU_BENCH_SCAN_FRAMES": "1",
    })
    p = subprocess.run([sys.executable, os.path.join(_ROOT, "bench.py")],
                       env=env, capture_output=True, text=True, timeout=480)
    assert p.returncode == 0, p.stderr[-800:]
    d = json.loads([l for l in p.stdout.strip().splitlines()
                    if l.startswith("{")][-1])
    assert d["value"] is not None and d["value"] > 0
    assert d["metric"].endswith("_render_only_scanloop"), d["metric"]
    assert d["config"]["scan_frames"] is True
    assert d["config"]["sim_steps"] == 0
    # render-only is not the sim-in-loop primary config: vs_baseline null
    assert d["vs_baseline"] is None


def test_hbm_and_rank_slab_harnesses_emit_json():
    """The round-5 diagnostic harnesses (micro-roofline, Config-2
    per-rank projection) are first in line for chip time; a silent
    breakage would burn it. Tiny-shape CPU smoke of both."""
    env = dict(os.environ)
    env.update({"PYTHONPATH": _ROOT, "SITPU_CPU": "1",
                "SITPU_HBM_BENCH_MB": "8", "SITPU_HBM_BENCH_GRID": "32"})
    p = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "benchmarks/hbm_bench.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-800:]
    d = json.loads([l for l in p.stdout.strip().splitlines()
                    if l.startswith("{")][-1])
    for key in ("copy_gbps", "sim10_ms", "dispatch_tiny_us",
                "dispatch_chain_us", "matmul_tflops"):
        assert key in d and d[key] is not None, (key, d)

    env = dict(os.environ)
    env.update({"PYTHONPATH": _ROOT, "SITPU_CPU": "1",
                "SITPU_BENCH_GRID": "32", "SITPU_BENCH_RANKS": "4",
                "SITPU_BENCH_SIM_STEPS": "1", "SITPU_BENCH_K": "4"})
    p = subprocess.run(
        [sys.executable,
         os.path.join(_ROOT, "benchmarks/rank_slab_bench.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-800:]
    d = json.loads([l for l in p.stdout.strip().splitlines()
                    if l.startswith("{")][-1])
    assert d["projected_fps_v5e8"] > 0
    assert d["per_rank_march_ms"] > 0
    assert d["a2a_assumed_gbps"] > 0    # the stated-assumption contract
