"""Phase attribution inside compiled steps.

The distributed frame is ONE jitted SPMD program by design (XLA overlaps
march, exchange and composite), so host-side spans can only see
dispatch+fetch — the march/exchange/merge split inside the step is
invisible to every timer the repo has. This module makes the device
explain itself:

1. Every step builder in ``parallel/pipeline.py`` (plus hier.py,
   ops/composite.py and models/pipelines.py) wraps its phases in
   ``phase(name)`` — a ``jax.named_scope`` with the ``sitpu_`` prefix.
   XLA carries the scope through fusion into per-instruction
   ``op_name`` metadata in the compiled HLO.
2. ``ProfileCapture`` runs N bracketed frames under
   ``jax.profiler.trace`` and joins each XLA op event of the emitted
   ``*.xplane.pb`` back to its scope via the compiled HLO text:
   instruction names are module-unique, and every op event names its
   instruction and its module (``_xplane_events``). The CPU backend
   runs its ops on host threads, and their events carry ``hlo_op`` +
   ``hlo_module`` stats. On a TPU the ``XLA Ops`` events of a device
   plane are named by their whole HLO text (``%fusion.12 = ...``),
   carry neither ``op_name`` nor module, and ``while``/``conditional``
   events enclose their bodies' events — so the reader takes each op's
   SELF time and the module from the enclosing ``XLA Modules`` event
   (jax 0.9 + libtpu 0.0.34, looked at on a v5e). The
   ``*.trace.json.gz`` that ``jax.profiler.trace`` writes beside it on
   both backends mixes host function events in and nests device ops:
   it is not read.
3. A session with ``obs.enabled`` keeps the same ``{module:
   {instruction: phase}}`` table of every step executable it dispatches
   on its recorder (``scoped_step`` -> ``Recorder.hlo_scopes``, with the
   instructions that only inherited their phase named in
   ``Recorder.hlo_inherited``), so a
   reader of a profile of the whole run (chipbench's per-phase device
   ms) can make the join without the executables.

Accounting (validated against an 8-device virtual-mesh probe):

- events are NOT duplicated per pooled runtime thread — one event per
  (op, device, frame) — so per-phase ms = Σ dur / (frames × devices);
- scan-body ops legitimately recur per iteration, which total-sum
  accounting handles for free;
- the innermost (**last**) ``sitpu_`` component of an op_name wins, so
  an outer ``sitpu_wave`` scope never subsumes the march/exchange
  scopes nested inside it;
- device time the scopes don't explain lands in ``unattributed``; the
  gap between wall-clock and total device time lands in ``host`` (one
  of the roofline bound classes); when an intra-op thread pool makes
  summed op time EXCEED wall (CPU backends), the breakdown is
  normalized onto the wall (``normalized: true``, raw ratio kept in
  ``op_parallelism``) — so the per-phase sum matches the measured step
  wall-clock by construction and ``coverage`` records how much of the
  wall the device actually explained.

Module-level ``import jax`` is intentional: only JAX-bearing code
(pipeline builders, bench children, tests) imports this file; the
JAX-free artifact consumers live in obs/roofline.py and
benchmarks/divergence.py.
"""

from __future__ import annotations

import contextlib
import functools
import glob
import os
import re
import tempfile
import threading
import time
from typing import Any, Callable, Dict, Optional

import jax

from scenery_insitu_tpu.obs import recorder as _rec

SCOPE_PREFIX = "sitpu_"

# The phase catalog — every named scope the step builders emit. Tests
# assert per-builder subsets of these appear in lowered HLO; the CI
# attribution lane asserts the captured breakdown names come from here
# (plus the two synthetic phases the capture itself mints).
PHASES = ("march", "fold", "halo", "exchange", "merge", "resegment",
          "wire_encode", "sim_step", "dcn_hop", "wave",
          # inside the vortex sim's per-frame program (sim/vortex.py)
          "sim_advect", "sim_project", "sim_field")

# Synthetic phases ProfileCapture adds on top of the scope catalog.
EXTRA_PHASES = ("unattributed", "host")


def phase(name: str):
    """Named scope for one step phase — ``with phase("march"): ...``.
    Zero runtime cost inside jit (it only tags HLO metadata)."""
    return jax.named_scope(SCOPE_PREFIX + name)


def in_phase(name: str):
    """Decorator form of ``phase``: every call of the function runs
    under a scope of its own (a ``jax.named_scope`` instance keeps its
    exit state on itself, so one shared instance must neither nest nor
    run on two threads)."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with phase(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


def scope_of(op_name: str) -> Optional[str]:
    """Extract the phase from an HLO ``op_name`` metadata path. The LAST
    ``sitpu_`` component wins so nested scopes attribute to the
    innermost phase (wave(march) → march). A Pallas kernel's ``name``
    is a path component too: ``sitpu_<phase>_<kernel>`` is its phase."""
    found = None
    for comp in op_name.split("/"):
        if comp.startswith(SCOPE_PREFIX):
            found = comp[len(SCOPE_PREFIX):]
    if found is None or found in PHASES:
        return found
    for name in sorted(PHASES, key=len, reverse=True):
        if found.startswith(name + "_"):
            return name
    return found


def scope_names(text: str) -> set:
    """All ``sitpu_*`` phase names present in an HLO / StableHLO dump —
    works on both ``lower().as_text()`` (loc metadata) and
    ``compile().as_text()`` (op_name metadata)."""
    return set(re.findall(r"sitpu_(\w+)", text))


_HLO_MODULE_RE = re.compile(r"^HloModule ([^,\s]+)", re.M)
_HLO_INST_RE = re.compile(r"^\s+(?:ROOT )?%?([\w\.\-]+) = ")
_HLO_COMP_RE = re.compile(r"^(?:ENTRY )?%?([\w\.\-]+) .*\{$")
_HLO_OP_NAME_RE = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_HLO_CALLS_RE = re.compile(
    r"(?:calls|body|condition|to_apply|true_computation"
    r"|false_computation)=%?([\w\.\-]+)|branch_computations=\{([^}]*)\}")


def parse_hlo_scopes(hlo_text: str):
    """(module_name, {instruction_name: phase}, inherited) from compiled
    HLO text. Instruction names are module-unique, so they key the trace
    join. ``inherited`` is the set of instruction names whose phase is
    not their own: a reader keeps their time apart
    (``phases[...]["inherited_ms"]``), because it is the enclosing
    loop's by position, not by what the source scoped.

    An instruction takes the phase of its own ``op_name``. One without
    (the copies and prefetches the compiler inserts, fusions it builds
    from constants) takes the phase of the instruction that calls the
    computation it stands in — the ``while`` or ``conditional`` around
    it, transitively — where every caller agrees: what runs inside the
    march's loop is the march's, unless it says otherwise itself."""
    m = _HLO_MODULE_RE.search(hlo_text)
    module = m.group(1) if m else None
    own: Dict[str, str] = {}
    comp_of: Dict[str, str] = {}
    callers: Dict[str, list] = {}
    comp = None
    for line in hlo_text.splitlines():
        mi = _HLO_INST_RE.match(line)
        if mi is None:
            mc = _HLO_COMP_RE.match(line)
            if mc is not None:
                comp = mc.group(1)
            continue
        inst = mi.group(1)
        comp_of[inst] = comp
        mo = _HLO_OP_NAME_RE.search(line)
        sc = scope_of(mo.group(1)) if mo else None
        if sc is not None:
            own[inst] = sc
        for one, many in _HLO_CALLS_RE.findall(line):
            for callee in (one,) if one else many.replace("%", "").split(","):
                callers.setdefault(callee.strip(), []).append(inst)

    ops = dict(own)
    seen: Dict[str, Optional[str]] = {}

    def of_comp(name):
        """The one phase every caller of computation ``name`` has."""
        if name not in seen:
            seen[name] = None           # a cycle cannot be, but be safe
            phases = {own.get(i) or of_comp(comp_of.get(i))
                      for i in callers.get(name, ())}
            seen[name] = phases.pop() if len(phases) == 1 else None
        return seen[name]

    inherited = set()
    for inst, where in comp_of.items():
        if inst not in own:
            sc = of_comp(where)
            if sc is not None:
                ops[inst] = sc
                inherited.add(inst)
    return module, ops, inherited


_HLO_RESULT_RE = re.compile(
    r"^\s+(?:ROOT )?%?[\w\.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")
_HLO_FUSED_RE = re.compile(r" fusion\(.*calls=%?([\w\.\-]+)")
# results that are another buffer's bytes under a new name or shape
_HLO_NO_WRITE = ("parameter", "get-tuple-element", "bitcast", "constant",
                 "while", "conditional", "call", "iota")


def hlo_large_writes(hlo_text: str, shape) -> list:
    """The opcodes of the compiled program's instructions that WRITE an
    array of the rank of ``shape`` and no smaller than it in any dim
    (dims compared sorted, so a transposed or a padded copy counts),
    outside fused computations (a fusion's own result counts, what it
    computes inside does not exist in memory). With ``shape`` = the
    field a step takes, this lists the per-frame copies of the whole
    volume: a transpose into the march layout, a cast, a pad, a halo
    concatenate. An instruction inside a loop's body counts once,
    however often it runs."""
    want = sorted(shape)
    fused = set(_HLO_FUSED_RE.findall(hlo_text))
    out, comp = [], None
    for line in hlo_text.splitlines():
        mr = _HLO_RESULT_RE.match(line)
        if mr is None:
            mc = _HLO_COMP_RE.match(line)
            if mc is not None:
                comp = mc.group(1)
            continue
        dims, opcode = mr.groups()
        if comp in fused or opcode in _HLO_NO_WRITE:
            continue
        have = sorted(int(d) for d in dims.split(",") if d)
        if len(have) == len(want) and all(h >= w
                                          for h, w in zip(have, want)):
            out.append(opcode)
    return out


_FOLD_NOTES = threading.local()     # .open: [chunks, fused, planes] while
#                                     a recorded step's first call traces;
#                                     .slots: see `fold_slot_account`


def note_fold_chunks(chunks: int, fused: bool, planes: int = 1) -> None:
    """A VDI generator says, while it is traced, that its write march
    folds ``chunks`` chunks, whether the fold kernel shades them itself,
    and as how many operand ``planes`` a chunk meets the resampling
    matmuls (2: a u16 field's two bytes). Kept only while a recorded
    step makes its first call (`scoped_step`); said to nobody
    otherwise."""
    notes = getattr(_FOLD_NOTES, "open", None)
    if notes is not None:
        notes[0] += chunks
        notes[1] += chunks if fused else 0
        notes[2] = max(notes[2], planes)


@contextlib.contextmanager
def fold_slot_account(collect: bool):
    """While a step that hands its folds' slot account on is traced
    (``collect``), the list `note_fold_slots` fills: one traced i32[2] a
    write march, ``(slot rows merged, slot rows visited)``. None where
    the step was not built to (an unrecorded step: the account is then
    dead code on the device). The marches have to be traced at the
    opener's own level — a march inside a ``cond`` or a ``scan`` would
    hand out a tracer of that inner trace — so a step opens this only
    around marches it calls directly."""
    before = getattr(_FOLD_NOTES, "slots", None)
    _FOLD_NOTES.slots = noted = [] if collect else None
    try:
        yield noted
    finally:
        _FOLD_NOTES.slots = before


def note_fold_slots(counts) -> None:
    """A write march that folded through the kernel hands on what the
    kernel counted (ops/pallas_seg.fold_slot_counts, a traced i32[2]);
    kept only inside a `fold_slot_account` that collects."""
    noted = getattr(_FOLD_NOTES, "slots", None)
    if noted is not None:
        noted.append(counts)


def scoped_step(fn, rec):
    """``fn`` (a jitted step) where ``rec`` is disabled. Where it is
    enabled, a wrapper that after its FIRST call reads the executable's
    compiled HLO and keeps ``{instruction: phase}`` under the module's
    name in ``rec.hlo_scopes`` (and the names that only inherited their
    phase in ``rec.hlo_inherited``) — the table a trace reader joins
    device ops to ``sitpu_*`` scopes with. The call has just compiled the
    program, so ``lower().compile()`` is answered from jit's own cache
    and issues no compile request; nothing is read on later calls.
    ``lower`` stays reachable (obs/device.cost_snapshot).

    From the same text, for a step whose first argument is a field
    (ndim 3): how many of its instructions write an array as large
    as that field (`hlo_large_writes`), added to the counter
    ``volume_copies_per_frame`` on EVERY call — 0 where the march reads
    the field where it lives. And from what the VDI generators said
    while that first call traced them (`note_fold_chunks`): the chunks
    the step's write marches fold, and those of them the fold kernel
    shades itself, added to ``fold_chunks`` / ``fold_chunks_fused`` on
    every call of a step that folds, and the operand planes of a
    marched chunk, added to ``march_operand_planes``."""
    if not rec.enabled:
        return fn
    noted = {}      # after the first call: the step's volume-sized
    #                 writes and its write marches' [chunks, fused, planes]

    def call(*args, **kwargs):
        if noted:
            out = fn(*args, **kwargs)
        else:
            noted["copies"] = None
            _FOLD_NOTES.open = noted["folds"] = [0, 0, 0]
            try:
                out = fn(*args, **kwargs)
            finally:
                _FOLD_NOTES.open = None
            try:
                text = fn.lower(*args, **kwargs).compile().as_text()
                module, ops, inherited = parse_hlo_scopes(text)
                rec.hlo_scopes.setdefault(module, {}).update(ops)
                rec.hlo_inherited.setdefault(module, set()).update(
                    inherited)
                if args and getattr(args[0], "ndim", 0) == 3:
                    # a rank's share of a field sharded over the mesh
                    noted["copies"] = len(hlo_large_writes(
                        text, args[0].addressable_shards[0].data.shape))
            except Exception as e:      # noqa: BLE001 — observability
                # must never take the frame down
                _rec.degrade("obs.profiler", "hlo_scopes", "none",
                             f"scope table unavailable: {e}", warn=False)
        if noted["copies"] is not None:
            rec.count("volume_copies_per_frame", noted["copies"])
        if noted["folds"][0]:
            rec.count("fold_chunks", noted["folds"][0])
            rec.count("fold_chunks_fused", noted["folds"][1])
            rec.count("march_operand_planes", noted["folds"][2])
        return out

    call.lower = fn.lower
    return call


def _self_times(ops):
    """(name, start_ns, self_ns) for nested ``(name, start_ns, dur_ns)``
    events of one line: an op that encloses others (``while``,
    ``conditional``) keeps only what they leave."""
    out, stack = [], []             # stack rows: [name, start, end, self]
    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= start:
            top = stack.pop()
            out.append((top[0], top[1], top[3]))
        if stack:
            stack[-1][3] -= min(dur, stack[-1][2] - start)
        stack.append([name, start, start + dur, dur])
    out.extend((name, start, self_ns) for name, start, _, self_ns in stack)
    return out


def _xplane_events(path: str):
    """The op events of one ``.xplane.pb`` in the trace-event form the
    join takes (``dur`` in us, ``args.hlo_op``, ``args.hlo_module``).
    Host plane: events that carry an ``hlo_op`` stat (the CPU backend
    runs its ops on host threads). ``/device:TPU:<n>`` planes: SELF
    time of each ``XLA Ops`` event, named by the instruction its HLO
    text defines, module from the enclosing ``XLA Modules`` event."""
    import bisect

    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           ev.name.split("(", 1)[0])
                          for ev in (lines["XLA Modules"].events
                                     if "XLA Modules" in lines else ()))
            starts = [m[0] for m in mods]
            ops = [(ev.name.split(" = ", 1)[0].strip().lstrip("%"),
                    ev.start_ns, ev.duration_ns)
                   for ev in lines["XLA Ops"].events]
            for name, start, self_ns in _self_times(ops):
                i = bisect.bisect_right(starts, start) - 1
                module = (mods[i][2] if i >= 0 and start < mods[i][1]
                          else None)
                yield {"ph": "X", "name": name, "dur": self_ns / 1e3,
                       "args": {"hlo_op": name, "hlo_module": module}}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    stats = dict(ev.stats)
                    if "hlo_op" in stats:
                        yield {"ph": "X", "name": ev.name,
                               "dur": ev.duration_ns / 1e3,
                               "args": {"hlo_op": stats["hlo_op"],
                                        "hlo_module":
                                            stats.get("hlo_module")}}


def _trace_events(trace_dir: str):
    """Yield the op events of the newest trace under ``trace_dir``
    (``<dir>/plugins/profile/<ts>/*.xplane.pb``) through
    ``_xplane_events``: op events only, self times on a TPU."""
    runs = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*")))
    if not runs:
        raise FileNotFoundError(
            f"no trace emitted under {trace_dir!r} (profiler backend "
            "absent?)")
    planes = sorted(glob.glob(os.path.join(runs[-1], "*.xplane.pb")))
    if not planes:
        raise FileNotFoundError(f"no .xplane.pb under {runs[-1]!r}")
    for path in planes:
        yield from _xplane_events(path)


class ProfileCapture:
    """Run N traced frames of a compiled step and attribute device time
    back to the ``sitpu_*`` phase scopes.

    ``capture(fn, *args, step=None)``:

    - ``fn`` must be jitted (it is lowered via ``fn.lower(*args)`` to
      get the compiled HLO — lowering is abstract, so donated buffers
      are fine);
    - ``step`` optionally runs ONE frame (a zero-arg callable returning
      something blockable). Required when ``fn`` donates its inputs and
      the caller threads state between frames (bench.py); when omitted,
      frames are ``fn(*args)``.

    ``host_time_fn`` (zero-arg, returns CUMULATIVE host seconds) lets
    the caller attribute measured host-side work — e.g. the delivery
    plane's encode/compress/sink time accumulated inside ``step`` — to
    the ``host`` phase explicitly. Without it, CPU backends structurally
    report ``host: 0``: the intra-op pool makes summed device-op time
    exceed wall, the breakdown is normalized onto the WHOLE wall, and
    host = wall - device vanishes. With the hook, device phases
    normalize onto (wall - hooked host) instead, so the host-delivery
    share survives normalization and the divergence engine can model it
    (docs/OBSERVABILITY.md "Divergence engine").

    Disabled captures return None without touching the profiler, the
    trace machinery or the step — the zero-overhead path. Failures
    degrade through the ``obs.profiler`` ledger component and also
    return None; they never take the caller down.
    """

    def __init__(self, frames: int = 3, enabled: bool = True,
                 trace_dir: Optional[str] = None, warmup: int = 1,
                 devices: Optional[int] = None,
                 host_time_fn: Optional[Callable[[], float]] = None):
        self.frames = max(1, int(frames))
        self.enabled = bool(enabled)
        self.trace_dir = trace_dir
        self.warmup = max(0, int(warmup))
        self.devices = devices
        self.host_time_fn = host_time_fn

    def capture(self, fn, *args,
                step: Optional[Callable[[], Any]] = None
                ) -> Optional[Dict[str, Any]]:
        if not self.enabled:
            return None
        try:
            return self._capture(fn, args, step)
        except Exception as e:          # noqa: BLE001 — capture is
            # best-effort observability; the step being profiled must
            # keep running whatever the trace backend did
            _rec.degrade("obs.profiler", "device_trace", "none",
                         f"profile capture failed: {e}", warn=False)
            return None

    # ------------------------------------------------------------------
    def _capture(self, fn, args, step):
        hlo = fn.lower(*args).compile().as_text()
        module, op_scopes, inherited = parse_hlo_scopes(hlo)

        run = step if step is not None else (
            lambda: jax.block_until_ready(fn(*args)))
        for _ in range(self.warmup):
            jax.block_until_ready(run())

        trace_dir = self.trace_dir or tempfile.mkdtemp(
            prefix="sitpu_profile_")
        h0 = self.host_time_fn() if self.host_time_fn else 0.0
        t0 = time.perf_counter()
        with jax.profiler.trace(trace_dir):
            for _ in range(self.frames):
                jax.block_until_ready(run())
        wall_ms = (time.perf_counter() - t0) * 1e3 / self.frames
        hook_ms = 0.0
        if self.host_time_fn:
            hook_ms = max(0.0, (self.host_time_fn() - h0) * 1e3
                          / self.frames)
            hook_ms = min(hook_ms, wall_ms)   # a hook cannot exceed wall

        phase_us: Dict[str, float] = {}
        inherited_us: Dict[str, float] = {}
        phase_events: Dict[str, int] = {}
        total_events = joined = 0
        for ev in _trace_events(trace_dir):
            ev_args = ev.get("args") or {}
            if module is not None and ev_args.get(
                    "hlo_module") not in (None, module):
                continue
            op = ev_args.get("hlo_op") or ev.get("name")
            if op is None:
                continue
            total_events += 1
            sc = op_scopes.get(op)
            if sc is None:
                sc = "unattributed"
            else:
                joined += 1
            dur = float(ev.get("dur") or 0.0)
            phase_us[sc] = phase_us.get(sc, 0.0) + dur
            if op in inherited:
                inherited_us[sc] = inherited_us.get(sc, 0.0) + dur
            phase_events[sc] = phase_events.get(sc, 0) + 1

        devices = self.devices or jax.local_device_count()
        per_frame = 1e3 * self.frames * devices
        # inherited_ms: the part of ms whose ops have no op_name of their
        # own and took the phase of the while/conditional around them
        phases = {
            name: {"ms": round(us / per_frame, 4),
                   "inherited_ms": round(
                       inherited_us.get(name, 0.0) / per_frame, 4),
                   "events": phase_events.get(name, 0)}
            for name, us in sorted(phase_us.items())}
        device_ms = sum(p["ms"] for p in phases.values())
        # CPU runtimes execute ops across an intra-op thread pool, so
        # summed op time can exceed wall-clock (parallelism > 1); a TPU
        # core's timeline is serialized, so this is a no-op there. The
        # breakdown is normalized onto the wall MINUS the hooked host
        # time (measured host work is not the device's to claim) so the
        # per-phase sum matches the measured step wall-clock by
        # construction; op_parallelism keeps the raw ratio honest.
        device_budget = max(0.0, wall_ms - hook_ms)
        op_parallelism = (device_ms / device_budget
                          if device_budget > 0 else None)
        normalized = False
        if op_parallelism is not None and op_parallelism > 1.0:
            scale = device_budget / device_ms
            for p in phases.values():
                p["ms"] = round(p["ms"] * scale, 4)
                p["inherited_ms"] = round(p["inherited_ms"] * scale, 4)
            device_ms = sum(p["ms"] for p in phases.values())
            normalized = True
        host_ms = hook_ms + max(0.0, wall_ms - hook_ms - device_ms)
        phases["host"] = {"ms": round(host_ms, 4), "inherited_ms": 0.0,
                          "events": 0}

        attr = {
            "type": "phase_attribution",
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "hlo_module": module,
            "frames": self.frames,
            "devices": devices,
            "wall_ms_per_frame": round(wall_ms, 4),
            "device_ms_per_frame": round(device_ms, 4),
            "host_hook_ms_per_frame": round(hook_ms, 4),
            "coverage": (round(min(1.0, op_parallelism), 4)
                         if op_parallelism is not None else None),
            "op_parallelism": (round(op_parallelism, 4)
                               if op_parallelism is not None else None),
            "normalized": normalized,
            "scoped_ops": len(op_scopes),
            "events_total": total_events,
            "events_joined": joined,
            "phases": phases,
        }
        _rec.get_recorder().count("profile_captures")
        return attr


# ------------------------------------------------- fleet-trace export

def publish_attribution(attr: Dict[str, Any], rec=None,
                        frame: Optional[int] = None) -> None:
    """Publish a capture into the live fleet Recorder as an instant
    event carrying the per-phase breakdown (shows up in the PR-17
    Perfetto trace alongside the host-side spans)."""
    rec = rec or _rec.get_recorder()
    rec.event("phase_attribution", frame=frame,
              wall_ms_per_frame=attr.get("wall_ms_per_frame"),
              coverage=attr.get("coverage"),
              **{f"ms_{name}": p.get("ms")
                 for name, p in (attr.get("phases") or {}).items()})
