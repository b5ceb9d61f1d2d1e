"""One run of one cell of the chip benchmark:

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process; no result without a TPU of a kind in `peaks.json` and as many
chips as the cell asks for. Prints what it measured, every number it
compared beside its limit (again as the last lines of standard error), and
as its last line one JSON object: `correct`, `attempted`, `failed`,
`metrics` (the cell's end-to-end metrics with `--trace 0`, its per-layer
metrics with `--trace 1`), `device`, with `--trace 1` `breakdown`, and last
`checks`: {name: [value, limit]}. See chipbench/README.md.
"""

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def declared(bench: dict, group: str, workload: str) -> list:
    """The metrics of `group` that BENCHMARK.json declares for this cell."""
    return [m["name"] for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    try:
        cell = harness.load_cell(args.workload)
        res = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace))
    except harness.BenchFailure as e:
        print(f"chipbench: no result: {e}", file=sys.stderr)
        return 1
    group = "per_layer" if args.trace else "end_to_end"
    have = res[group]
    names = declared(cell["bench"], group, args.workload)
    missing = [n for n in names if n not in have]
    if missing and not args.trace:
        print(f"chipbench: no result: {args.workload} did not report "
              f"{missing}", file=sys.stderr)
        return 1
    if missing:     # a reader that found nothing to read gives nothing
        print(f"[chipbench] left out of the line, nothing to read: "
              f"{missing}", file=sys.stderr)
    for name, (value, unit) in sorted({**res["end_to_end"],
                                       **res["per_layer"]}.items()):
        print(f"[chipbench] {name} = {value!r} {unit}"
              + ("" if name in names else "  (not judged in this cell)"))
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"],
            "metrics": {n: {"value": have[n][0], "unit": have[n][1]}
                        for n in names if n in have},
            "device": res["device"]}
    if res["breakdown"]:
        line["breakdown"] = res["breakdown"]
    # json has no infinity (two decoded frames that are equal)
    plain = lambda v: (repr(v) if isinstance(v, float)
                       and not math.isfinite(v) else v)
    line["checks"] = {n: [plain(value), plain(limit)]
                      for n, value, limit, _ in res["checks"]}
    sys.stdout.flush()
    for n, value, limit, ok in res["checks"]:
        print(f"[chipbench] compared {n}: {value} (limit {limit}) "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
