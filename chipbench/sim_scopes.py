"""What the vortex cell's per-layer readers share: the SIM program's
device time by the program's own `sitpu_*` scope (`scopes.by_scope` with
the table the sim executable left on the recorder, PR 37), and the time a
collective was in flight inside that program.

As in `scopes.py`, a missing source reads as nothing: where the recorder
holds no table for a program matching `programs.sim` (a checkout from
before the sim program was scoped, a renamed program), the reader returns
None with a `MISSING SOURCE` line and the metric is left out of the line.
"""

import re

from chipbench import scopes, xplane


def sim(ctx) -> dict:
    """`scopes.by_scope` of the cell's sim program, once per run (kept in
    `ctx`), with "runs": its executions on the first device, and "table":
    whether the recorder holds a table of that program."""
    if "_sim_by_scope" not in ctx:
        pattern = ctx["config"]["programs"]["sim"]
        table, inherited = scopes.table()
        rx = re.compile(pattern)
        own = {m: ops for m, ops in table.items() if rx.search(m)}
        if not own:
            scopes._missing(
                f"the program keeps no scope table for {pattern!r} "
                "(Recorder.hlo_scopes): no sim scope's device time")
        got = scopes.by_scope(ctx["trace"], pattern, own, inherited)
        got["table"] = bool(own)
        got["runs"] = runs = ctx["trace"].program_runs(pattern)
        ctx["_sim_by_scope"] = got
        if runs and own:
            per = lambda sec: round(sec / runs * 1e3, 4)
            top = {str(ph): [(k, per(v)) for k, v in sorted(
                kinds.items(), key=lambda kv: -kv[1])[:6]]
                for ph, kinds in got["kinds"].items()}
            by = {k: per(v) for k, v in sorted(got["scopes"].items())}
            print(f"[chipbench] sim program, ms per run: program "
                  f"{per(got['program'])}, ops {per(got['ops'])}, by scope "
                  f"{by}; largest op kinds of each scope {top}")
    return ctx["_sim_by_scope"]


def sim_scope_ms(ctx, phase: str):
    """Device ms per frame of the sim program's ops whose innermost scope
    is `phase` (self time, averaged over the devices); None where the
    program did not run or left no table."""
    got = sim(ctx)
    if not got["runs"] or not got["table"]:
        return None
    return got["scopes"].get(phase, 0.0) / got["runs"] * 1e3


def sim_collective_s(trace, pattern: str) -> float:
    """Seconds in which a collective was in flight on the first device
    INSIDE the programs whose name matches: the op line's collective
    segments and the start-to-done spans of the asynchronous ones
    (`xplane.Trace.collective_s` counts the same over the whole window,
    the step program's all-to-all included)."""
    rx = re.compile(pattern)
    dev = next(iter(trace.devices))
    inside = [(s, s + d) for name, s, d in trace.devices[dev]["modules"]
              if rx.search(name)]
    flights = xplane._union(
        [[s, e] for s, e, _, code in trace.segments(dev)
         if xplane._COLLECTIVE.match(code)]
        + [[e[1], e[1] + e[2]] for e in trace.devices[dev]["async"]
           if xplane._COLLECTIVE.match(e[3])])
    total = 0
    for m0, m1 in inside:
        total += sum(max(0, min(e, m1) - max(s, m0)) for s, e in flights)
    return total / 1e9
