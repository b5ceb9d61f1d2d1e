"""From the profiler's `.xplane.pb` to the numbers the per-layer readers
take: device time by XLA program and by op, the busy union, collectives and
their exposed part, and the idle gaps with what the host was doing.

Two steps, so that the second can be checked on a recorded trace without
the profiler: `compact()` turns the protobuf into a small event list
(`fixtures/*.json` holds one recorded on the chip), and `Trace` reduces that
list. What a TPU trace looks like (jax 0.9, libtpu 0.0.34, looked at by
hand): one plane `/device:TPU:<n>` per chip with the lines `XLA Modules`
(one event per executed program, named `jit_<fn>(<fingerprint>)`), `XLA Ops`
(one event per executed HLO instruction, named by its whole HLO text;
`while` and `conditional` events enclose their bodies' events) and `Async
XLA Ops` (start-to-done spans of asynchronous copies and collectives); the
host's threads are lines of `/host:CPU`, and a `TraceAnnotation` lands on
the line of the thread that made it. All on one clock, in ns.
"""

import re

ANCHOR = "chipbench_window"
_COLLECTIVE = re.compile(
    r"^(all-to-all|all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|collective-broadcast|ragged-all-to-all)")


def op_name(hlo_text: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return hlo_text.split(" = ", 1)[0].strip().lstrip("%")


def op_code(hlo_text: str) -> str:
    """The HLO opcode of an instruction's text: `%all_to_all.1 = (f32[..])
    all-to-all(...)` -> `all-to-all`. Instruction names follow the JAX
    primitive (`all_to_all`), opcodes are XLA's own."""
    m = re.search(r"\s([a-z][a-z\-]*)\(", hlo_text.split(" = ", 1)[-1])
    return m.group(1) if m else ""


def op_kind(name: str) -> str:
    """`fusion.12` -> `fusion`, `copy-start.3` -> `copy-start`: the name
    without the instruction's serial number."""
    return re.sub(r"(\.\d+)+$", "", re.sub(r"(\.clone)+$", "", name))


def compact(xplane_path: str) -> dict:
    """The event list of one `.xplane.pb`: per device the module events as
    [name, start_ns, dur_ns], the op and async-op events as [name,
    start_ns, dur_ns, opcode], and the anchor annotation the harness put
    around the window."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    out = {"anchor": None, "devices": {}}
    for plane in data.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            dev = {"modules": [], "ops": [], "async": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops",
                       "Async XLA Ops": "async"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    row = [ev.name if key == "modules" else op_name(ev.name),
                           int(ev.start_ns), int(ev.duration_ns)]
                    if key != "modules":
                        row.append(op_code(ev.name))
                    dev[key].append(row)
            out["devices"][m.group(1)] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        out["anchor"] = [int(ev.start_ns),
                                         int(ev.duration_ns)]
    return out


def _union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        elif e > s:
            merged.append([s, e])
    return merged


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _subtract(a: list, b: list) -> list:
    """Merged intervals `a` minus merged intervals `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        i = j
        while i < len(b) and b[i][0] < e:
            if b[i][0] > cur:
                out.append([cur, b[i][0]])
            cur = max(cur, b[i][1])
            i += 1
        if cur < e:
            out.append([cur, e])
    return out


def self_segments(ops: list) -> list:
    """The op line flattened: [start, end, name, opcode] pieces in which
    `name` is the innermost op running (a `while` owns only what its body
    leaves)."""
    evs = sorted(([e[0], e[1], e[2], e[3] if len(e) > 3 else ""]
                  for e in ops), key=lambda e: (e[1], -e[2]))
    segs, stack = [], []          # stack: [name, end, cursor, opcode]

    def close_until(t):
        while stack and stack[-1][1] <= t:
            name, end, cur, code = stack.pop()
            if end > cur:
                segs.append([cur, end, name, code])
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, start, dur, code in evs:
        close_until(start)
        if stack:
            top = stack[-1]
            if start > top[2]:
                segs.append([top[2], start, top[0], top[3]])
            top[2] = max(top[2], start)
            end = min(start + dur, top[1])
        else:
            end = start + dur
        stack.append([name, end, start, code])
    close_until(float("inf"))
    segs.sort()
    return segs


class Trace:
    """One traced window reduced. `window` is [start_ns, end_ns) of the
    anchor annotation; events outside it are left out."""

    def __init__(self, events: dict):
        if not events.get("anchor"):
            raise ValueError(f"the trace holds no {ANCHOR!r} annotation")
        if not events.get("devices"):
            raise ValueError("the trace holds no /device:TPU plane")
        a0, adur = events["anchor"]
        self.window = (a0, a0 + adur)
        self.devices = {}
        for dev, lines in sorted(events["devices"].items(),
                                 key=lambda kv: int(kv[0])):
            clip = lambda evs: [e for e in evs
                                if e[1] >= a0 and e[1] < a0 + adur]
            self.devices[dev] = {k: clip(v) for k, v in lines.items()}
        self._segs = {}
        self._collective = None

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def segments(self, dev: str) -> list:
        if dev not in self._segs:
            self._segs[dev] = self_segments(self.devices[dev]["ops"])
        return self._segs[dev]

    def busy(self, dev: str) -> list:
        """Merged intervals in which an op ran on this device."""
        return _union([[s, e] for s, e, *_ in self.segments(dev)])

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        return sum(_length(self.busy(d)) for d in self.devices) / 1e9 \
            / len(self.devices)

    def idle_share(self, dev: str = None) -> float:
        dev = dev if dev is not None else next(iter(self.devices))
        return 1.0 - _length(self.busy(dev)) / 1e9 / self.window_s

    def program_runs(self, pattern: str) -> int:
        """Executions of the programs whose name matches, on the first
        device."""
        rx = re.compile(pattern)
        first = next(iter(self.devices.values()))
        return sum(1 for name, _, _ in first["modules"] if rx.search(name))

    def program_s(self, pattern: str) -> float:
        """Device seconds of the programs whose name matches, averaged
        over the devices."""
        rx = re.compile(pattern)
        total = sum(dur for lines in self.devices.values()
                    for name, _, dur in lines["modules"] if rx.search(name))
        return total / 1e9 / len(self.devices)

    def program_ms_per_run(self, pattern: str):
        """Device ms per execution of the programs whose name matches
        (mean over the devices); None where none ran."""
        runs = self.program_runs(pattern)
        return self.program_s(pattern) / runs * 1e3 if runs else None

    def program_names(self) -> dict:
        first = next(iter(self.devices.values()))
        out = {}
        for name, _, dur in first["modules"]:
            out[name] = out.get(name, 0.0) + dur / 1e9
        return out

    def collective_s(self) -> tuple:
        """(seconds in which a collective was in flight, seconds of those
        in which no other op ran), on the first device: the profiler
        writes the start-to-done spans of asynchronous collectives (`Async
        XLA Ops`) for that device only."""
        if self._collective is not None:
            return self._collective
        dev = next(iter(self.devices))
        segs = self.segments(dev)
        coll = _union(
            [[s, e] for s, e, _, c in segs if _COLLECTIVE.match(c)]
            + [[e[1], e[1] + e[2]] for e in self.devices[dev]["async"]
               if _COLLECTIVE.match(e[3])])
        compute = _union([[s, e] for s, e, _, c in segs
                          if not _COLLECTIVE.match(c)])
        self._collective = (_length(coll) / 1e9,
                            _length(_subtract(coll, compute)) / 1e9)
        return self._collective

    def top_ops(self, n: int = 10) -> list:
        """[[op kind, self seconds]] on the first device, largest first."""
        dev = next(iter(self.devices))
        acc = {}
        for s, e, name, _ in self.segments(dev):
            kind = op_kind(name)
            acc[kind] = acc.get(kind, 0) + (e - s)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def idle_gaps(self, host_spans: list, n: int = 10) -> list:
        """[[what the host was doing, seconds]] for the longest gaps of
        the first device inside the window. `host_spans` are [name,
        start_ns, end_ns] on the trace's clock; a gap is named after the
        span that covers most of it, `host:between_spans` if none does."""
        dev = next(iter(self.devices))
        gaps = _subtract([list(self.window)], self.busy(dev))
        out = []
        for s, e in gaps:
            best, cover = "between_spans", 0
            for name, hs, he in host_spans:
                ov = min(e, he) - max(s, hs)
                if ov > cover:
                    best, cover = name, ov
            if 2 * cover < e - s:
                best = "between_spans"
            out.append([f"host:{best}", (e - s) / 1e9])
        out.sort(key=lambda g: -g[1])
        return out[:n]
