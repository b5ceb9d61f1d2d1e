"""What the frame loop's launches cost above a launch that nothing holds
(PR 43): per frame the loop thread's time inside `steer` + `sim` +
`dispatch` (the spans a program or a put is enqueued in), less the window's
own 10th percentile of that sum (nearest rank); the mean, in ms. On this
runtime a launch made while a transfer is in flight returns only when the
transfer is done, in whichever of the three spans it falls:
`step_dispatch_busy_ms` (`shm512-ingest`'s `dispatch`) made whole, for
every cell.

To stderr: the mean by class of the frame's `dispatch` span (`prev_ready`,
`upload_busy`), the span that grew (each span's mean over its own 10th
percentile), and, where deliveries over 1.3 x the median interval exist
(`gs1024-4rank-insitu`'s 327 ms frames), which span holds their excess:
every loop-thread span's own time (a parent's less its children's) in such
an interval against its median over the others. Nothing from a program
whose spans carry no `thread`."""

import statistics
import sys

NAME = "launch_held_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "entry"
MOVES = "fps"
CELLS = "all"
LAUNCH = ("steer", "sim", "dispatch")


def tenth(values) -> float:
    """The 10th percentile by nearest rank."""
    ordered = sorted(values)
    return ordered[int(0.1 * (len(ordered) - 1))]


def slow_intervals(spans, say) -> None:
    """Name the spans that hold the excess of the deliveries that took
    over 1.3 x the median interval (between the starts of consecutive
    `sinks` spans)."""
    sinks = sorted(e["ts"] for e in spans if e["name"] == "sinks")
    gaps = list(zip(sinks, sinks[1:]))
    if len(gaps) < 4:
        return
    median = statistics.median(b - a for a, b in gaps)
    slow = [g for g in gaps if g[1] - g[0] > 1.3 * median]
    if not slow or len(slow) == len(gaps):
        return
    top = [e for e in spans if e["depth"] == 0]
    kids = [e for e in spans if e["depth"] == 1]

    def own(gap) -> dict:
        a, b = gap
        found = {}
        for e in top:
            if a <= e["ts"] < b:
                inner = [k for k in kids
                         if e["ts"] <= k["ts"] < e["ts"] + e["dur"]]
                for k in inner:
                    found[k["name"]] = found.get(k["name"], 0.0) + k["dur"]
                found[e["name"]] = (found.get(e["name"], 0.0) + e["dur"]
                                    - sum(k["dur"] for k in inner))
        found["no span"] = b - a - sum(found.values())
        return found

    usual = [own(g) for g in gaps if g not in slow]
    held = [own(g) for g in slow]
    names = {n for f in usual + held for n in f}
    base = {n: statistics.median(f.get(n, 0.0) for f in usual)
            for n in names}
    excess = {n: sum(f.get(n, 0.0) - base[n] for f in held) / len(held)
              for n in names}
    ranked = sorted(excess.items(), key=lambda kv: -kv[1])[:4]
    say(f"{len(slow)} of {len(gaps)} deliveries took over 1.3 x the median "
        f"interval {median * 1e3:.2f} ms (mean "
        f"{sum(b - a for a, b in slow) / len(slow) * 1e3:.2f}); their "
        f"excess by span (own time, ms over the span's median in the other "
        f"intervals): " + ", ".join(f"{n} {v * 1e3:+.2f}" for n, v in ranked))


def read(ctx):
    from chipbench import loop_spans

    spans = loop_spans.loop(ctx)
    if spans is None:
        return None
    say = lambda s: print("[chipbench] launch_held_ms: " + s,
                          file=sys.stderr, flush=True)
    frames = {}
    for e in spans:
        if e["name"] in LAUNCH and e["depth"] == 0:
            frames.setdefault(e.get("frame"), {})[e["name"]] = e
    frames = [f for f in frames.values() if "dispatch" in f]
    totals = [sum(e["dur"] for e in f.values()) * 1e3 for f in frames]
    floor = tenth(totals)
    classes, grew = {}, {}
    for f, total in zip(frames, totals):
        attrs = f["dispatch"].get("attrs") or {}
        key = (bool(attrs.get("prev_ready")), bool(attrs.get("upload_busy")))
        classes.setdefault(key, []).append(total - floor)
    for name in LAUNCH:
        durs = [f[name]["dur"] * 1e3 for f in frames if name in f]
        if durs:
            grew[name] = sum(durs) / len(durs) - tenth(durs)
    say(f"over a floor of {floor:.3f} ms (the window's 10th percentile of "
        f"steer + sim + dispatch), by (prev_ready, upload_busy) of the "
        f"dispatch: " + ", ".join(
            f"{k}: {sum(v) / len(v):.3f} ms x {len(v)}"
            for k, v in sorted(classes.items()))
        + "; by span, mean over its own 10th percentile: " + ", ".join(
            f"{n} {v:.3f}" for n, v in grew.items())
        + f"; grew most: {max(grew, key=grew.get)}")
    slow_intervals(spans, say)
    return sum(totals) / len(totals) - floor
