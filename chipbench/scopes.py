"""What the per-layer readers of PR 24 share: the recorder's spans summed
by name, and the frame-step program's device time by the program's own
`sitpu_*` scope.

The join of device ops to scopes. A TPU trace names an `XLA Ops` event by
its whole HLO text and carries neither `op_name` nor module in its stats
(looked at on a v5e, jax 0.9), so the scope cannot be read from the trace.
The program keeps the other half: with `obs.enabled` every step executable
it dispatches leaves `{module: {instruction: phase}}`, parsed from its
compiled HLO, on the session's recorder (`Recorder.hlo_scopes`). An op
joins by its instruction name inside the `XLA Modules` event that encloses
it. The compiler gives some instructions no `op_name` at all (the
update-slice fusions and prefetch copies of a chunk loop); the table gives
those the phase of the `while` or `conditional` around them and names them
in `Recorder.hlo_inherited`, and `by_scope` keeps their time apart, so
what a phase owns by the source's scope and what it owns by position stay
two numbers.

A missing source reads as nothing, not as a number: where the program
keeps no table, or records spans but none of the name, the reader returns
None with a `MISSING SOURCE` line on stderr, and the harness leaves the
metric out of the line. 0 is the best value a `lower` metric can have, so
a renamed span must not read as one.
"""

import bisect
import re
import sys


def _missing(what: str) -> None:
    print(f"[chipbench] MISSING SOURCE: {what}. The metric is left out of "
          "the line.", file=sys.stderr, flush=True)


def span_ms(ctx, name: str):
    """Host ms per frame inside the recorder spans called `name`; None
    from a run that recorded no span at all, and None with a `MISSING
    SOURCE` line where spans were recorded but none is called `name`."""
    if not ctx["spans"]:
        return None
    durs = [e["dur"] for e in ctx["spans"] if e["name"] == name]
    if not durs:
        _missing(f"the program recorded spans but none called {name!r} "
                 "(renamed, removed, not opened under this traffic, or a "
                 "commit from before it)")
        return None
    return sum(durs) / ctx["frames"] * 1e3


def table() -> tuple:
    """(`{module: {instruction: phase}}`, `{module: instructions that
    only inherited their phase}`) as the program's recorder holds them;
    empty where the program has none."""
    try:
        from scenery_insitu_tpu import obs

        rec = obs.get_recorder()
    except ImportError:     # a checkout without the program
        return {}, {}
    return (dict(getattr(rec, "hlo_scopes", None) or {}),
            dict(getattr(rec, "hlo_inherited", None) or {}))


def by_scope(trace, pattern: str, scopes: dict, inherited=None) -> dict:
    """Device seconds of the programs whose name matches `pattern`, by
    scope, averaged over the devices: {"program": all of it, "ops": in
    which an op ran, "scopes": {phase: self seconds of the ops the table
    gives that phase}, "kinds": {phase: {op kind: self seconds}}, with
    the ops the table gives no phase under None, "inherited": the part
    of "kinds" whose ops are named in `inherited`, {module:
    instructions}}."""
    from chipbench import xplane

    rx = re.compile(pattern)
    inherited = {m: set(v) for m, v in (inherited or {}).items()}
    program, kinds, passed = 0.0, {}, {}
    for dev, lines in trace.devices.items():
        mods = sorted((start, start + dur, name)
                      for name, start, dur in lines["modules"]
                      if rx.search(name))
        starts = [m[0] for m in mods]
        program += sum(m[1] - m[0] for m in mods)
        for s, e, op, _ in trace.segments(dev):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= mods[i][1]:
                continue
            module = mods[i][2].split("(", 1)[0]
            phase = scopes.get(module, {}).get(op)
            per = kinds.setdefault(phase, {})
            kind = xplane.op_kind(op)
            per[kind] = per.get(kind, 0.0) + (e - s)
            if op in inherited.get(module, ()):
                per = passed.setdefault(phase, {})
                per[kind] = per.get(kind, 0.0) + (e - s)
    n = len(trace.devices) * 1e9
    kinds, passed = ({ph: {k: v / n for k, v in per.items()}
                      for ph, per in d.items()} for d in (kinds, passed))
    return {"program": program / n,
            "ops": sum(sum(per.values()) for per in kinds.values()),
            "scopes": {ph: sum(per.values()) for ph, per in kinds.items()
                       if ph is not None},
            "kinds": kinds, "inherited": passed}


def step(ctx) -> dict:
    """`by_scope` of the cell's frame-step program with the program's own
    table, once per run (kept in `ctx`), with "runs": its executions."""
    if "_step_by_scope" not in ctx:
        pattern = ctx["config"]["programs"]["step"]
        scopes, inherited = table()
        if not scopes:
            _missing("the program keeps no scope table "
                     "(Recorder.hlo_scopes): no scope's device time and no "
                     "step_unscoped_share")
        got = by_scope(ctx["trace"], pattern, scopes, inherited)
        got["table"] = bool(scopes)
        got["runs"] = runs = ctx["trace"].program_runs(pattern)
        ctx["_step_by_scope"] = got
        if runs:
            per = lambda sec: round(sec / runs * 1e3, 4)
            top = lambda kinds: {str(ph): [(k, per(v)) for k, v in sorted(
                per_kind.items(), key=lambda kv: -kv[1])[:5]]
                for ph, per_kind in kinds.items()}
            total = lambda d: {k: per(v) for k, v in sorted(d.items())}
            passed = got["inherited"]
            print(f"[chipbench] step program, ms per run: program "
                  f"{per(got['program'])}, ops {per(got['ops'])}, by "
                  f"scope {total(got['scopes'])}, of which by inheritance "
                  f"from the enclosing while/conditional (no op_name of "
                  f"their own) "
                  f"{total({k: sum(v.values()) for k, v in passed.items()})}"
                  f", their largest op kinds {top(passed)}; largest op "
                  f"kinds of each scope {top(got['kinds'])}")
    return ctx["_step_by_scope"]


def step_scope_ms(ctx, *phases):
    """Device ms per frame of the step program's ops whose innermost
    scope is one of `phases`; None where the step program did not run or
    the program keeps no scope table."""
    got = step(ctx)
    if not got["runs"] or not got["table"]:
        return None
    return sum(got["scopes"].get(p, 0.0) for p in phases) \
        / got["runs"] * 1e3
