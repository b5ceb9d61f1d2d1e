"""Read the numbers that decide `correct` on many seeds in one process, at
the cell's own size on the chip: the program's sound runs, and the control.

    python chipbench/control.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 7,8,9] [--seconds 3] [--out file.json]

The control is the lower precision put in the program's place: `rounded`
holds the plain references themselves in bfloat16 where the program's field
(the field source's own `rounded`) and frames would stand (the mildest bf16
path: anything computed in bf16 errs at least as much), and `program`
switches the configuration's
`control_overrides` on, where it has a lower-precision path of its own.
The benchmark's own runs never run this; PERF.md gives the readings each
limit was set from. Every session is built, measured over a short window
at the cell's own load, compared and freed before the next.
"""

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def rounded(cell: dict, seed: int, produced: dict, refs: dict) -> dict:
    """What `harness.produced_by` hands over, with the references held in
    bfloat16 in the program's place: the source's plain reference of what
    it kept, computed in bfloat16, and the reference session's frames
    rounded to bfloat16."""
    from chipbench import harness, reference

    return dict(produced, kept=harness.load_source(cell).rounded(
        cell, seed, produced["kept"]), frames={
            f: {k: reference.round_bf16(p[k])
                for k in ("vdi_color", "vdi_depth")}
            for f, p in refs["frames"].items()})


def read(cell: dict, seed: int, seconds: float, control: str = "",
         on_chip: bool = True) -> dict:
    """One short run of the harness's own chain, sound (`control` empty)
    or with one of the two controls in the program's place."""
    from chipbench import harness as h

    timed = cell
    if control == "program":        # the references keep the plain cell
        timed = copy.deepcopy(cell)
        timed["config_file"]["overrides"] += cell["config_file"][
            "control_overrides"]
    run = h.open_run(timed, seed, False, on_chip=on_chip, verbose=False)
    failed, layers, produced = h.run_window(run, seconds)
    refs = h.references(cell, seed, produced)
    if control == "rounded":
        produced = rounded(cell, seed, produced, refs)
    h.compare(run, produced, refs)
    return h.result(run, failed, layers)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    from chipbench import harness

    cell = harness.load_cell(args.workload)
    rows = []
    ints = lambda s: [int(x) for x in s.split(",") if x]
    kinds = ["rounded"] + (["program"] if cell["config_file"][
        "control_overrides"] else [])
    for control, seeds in [("", ints(args.seeds))] + [
            (k, ints(args.control_seeds)) for k in kinds]:
        for seed in seeds:
            t0 = time.perf_counter()
            res = read(cell, seed, args.seconds, control)
            row = {"seed": seed, "control": control,
                   "whole_read_s": time.perf_counter() - t0,
                   "correct": res["correct"], "failed": res["failed"],
                   "attempted": res["attempted"],
                   "fps": res["end_to_end"]["fps"][0],
                   "checks": {n: v for n, v, _, _ in res["checks"]},
                   "not_ok": [n for n, _, _, ok in res["checks"] if not ok]}
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
