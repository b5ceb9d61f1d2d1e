"""Rehearse the whole command without the chip (on-chip-measurement guide,
section 2): the harness end to end at a tiny size on the CPU, Pallas in
interpret mode, on one virtual device and on a mesh of four.

    python chipbench/rehearse.py [--ranks 1|4 | --config <name>]
        [--traffic <name>] [--trace 0|1] [--seed N]

Not a cell: its sizes are in `rehearsal/configs/` (`--ranks N` is
`--config tiny-Nrank`), a field source that exists only here is in
`rehearsal/sources/`, BENCHMARK.json names neither, and it prints counts
and checks only: no time, rate or share of the device, because a CPU run
has none to give. The traffic is any file of `traffic/`.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

COUNTS = ("frames_in_flight", "d2h_MB_per_frame")


def rehearsal_cell(ranks: int = 1, config: str = "",
                   traffic: str = "insitu10-steer") -> dict:
    from chipbench import harness

    name = config or f"tiny-{ranks}rank"
    cell = harness.find_files(
        {"name": f"rehearsal-{name}", "config": name, "traffic": traffic},
        home=os.path.join(harness.HERE, "rehearsal"))
    return dict(cell, chips=cell["config_file"]["chips"])


def rehearse(cell: dict, seed: int, seconds: float, trace: bool) -> dict:
    """The harness's own chain of a run (`harness.run_cell`) without the
    look for a chip and without its lines, which hold times that a CPU run
    has none to give."""
    from chipbench import harness as h

    run = h.open_run(cell, seed, trace, on_chip=False, verbose=False)
    failed, layers, produced = h.run_window(run, seconds)
    h.compare(run, produced, h.references(cell, seed, produced))
    return h.result(run, failed, layers)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--ranks", type=int, choices=(1, 4), default=1)
    ap.add_argument("--config", default="")
    ap.add_argument("--traffic", default="insitu10-steer")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seed", type=int, default=3000000019)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)

    cell = rehearsal_cell(args.ranks, args.config, args.traffic)
    res = rehearse(cell, args.seed, args.seconds, bool(args.trace))
    for name, value, limit, ok in res["checks"]:
        print(f"check {name}: {value} (limit {limit}) "
              f"{'ok' if ok else 'FAILED'}")
    counts = {k: v[0] for k, v in res["per_layer"].items() if k in COUNTS}
    print(f"rehearsal {cell['name']}: correct={res['correct']} "
          f"attempted={res['attempted']} failed={res['failed']} "
          f"counts={counts} per_layer_read={sorted(res['per_layer'])} "
          f"device={res['device']['platform']} (no device metric printed)")
    return 0 if res["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
