"""What a frame's device-to-host copy costs alone and beside compute, on
which pages its bytes land, and how long a launch made behind it is held:

    python -m scenery_insitu_tpu.obs.transfer_probe --bytes 157286400
        [--devices 4] [--repeats 3] [--filler-ms 100] [--json]

No session and no benchmark file. A frame is what a session fetches: f32
colour [16, 4, s, s] and depth [16, 2, s, s] (24 B a slot and pixel; s from
``--bytes``, a multiple of 16), the OUTPUT of a jitted program, on a mesh
sharded over its slots (the K-major blocks of `parallel/pipeline._frame_out`).
Each trial makes a fresh frame, waits for it, and then does what
`InSituSession` does: ``copy_to_host_async`` on every array, ``np.asarray``
on every shard, and lets the arrays go.

The rows (medians over ``--repeats`` trials; every trial is in the JSON):

- ``transfer``: {whole, 4, 16 pieces (the frame cut along its rows into so
  many arrays a leaf)} x {the device idle, a filler program running}. Two
  fillers of ``--filler-ms`` each, enough of them enqueued BEFORE the copies
  are asked for to outlast the transfer: ``matmul`` (bf16 [1024, 1024]
  products in a ``fori_loop``: the MXU, nothing of HBM) and ``stream`` (an
  elementwise pass over 128 MB a device in a ``fori_loop``: HBM and the
  DMAs). Columns: ``ms`` from the first ``copy_to_host_async`` to the last
  ``np.asarray``, ``GB/s``, ``start_ms`` (the async calls alone),
  ``fresh_MB`` (what the process's resident set grew by meanwhile: the
  bytes that landed on pages nobody had touched; obs/hostmem.py) and
  ``minflt`` (its minor faults, where the kernel counts them), ``free_dev_ms``
  / ``free_host_ms`` (letting go the device arrays, then the numpy arrays,
  which are views of the runtime's own host buffers) and ``free_fresh_MB``
  (negative: what that gave back); ``filler_left``: the filler was still
  running when the last byte had landed (else the tail of the transfer ran
  alone).
- ``held``: the same transfers IN FLIGHT, and made just after them either a
  trivial jitted call (``call``) or a 64-byte ``device_put`` (``put``):
  ``ret_ms`` until it returns, ``done_ms`` until its result is there, and
  ``landed``: how many of the frame's pieces were already on the host when
  it was (read back in order; one that answers inside 0.3 ms had landed).
- ``shard_ends`` (a mesh): one thread a device waits for that device's
  shards; the ms at which each was done says whether the links run side by
  side or one after the other.
- ``destination``: the whole-frame rows again where the bytes land
  elsewhere and the program stays what it is: ``mallopt``, a child process
  in which glibc keeps large blocks (``M_MMAP_MAX`` 0, ``M_TRIM_THRESHOLD``
  and ``M_ARENA_MAX`` set through ``ctypes`` before the backend starts: a
  freed host buffer stays mapped, the next one reuses its pages), and
  ``late``, a child that starts the backend and brings one frame to the
  host on glibc's defaults (its ``before`` row) and only THEN tells glibc
  the same on the calling thread, which is all a session can do
  (`runtime/hostheap.py`): on the process's first thread, and as
  ``late-thread`` all of it on a thread that is not (a frame loop that
  runs beside a host application's own); and
  ``pinned_host``, ``jax.device_put`` of the ready frame to the same
  sharding with ``memory_kind="pinned_host"`` (``ready_ms``: until it is
  there; ``ms``: until it is numpy arrays) or ``unsupported`` with the
  runtime's reason.

The process that prints never starts a backend (a chip belongs to one
process at a time): it runs one child after the other and reads each one's
JSON. ``thp`` is ``/sys/kernel/mm/transparent_hugepage/enabled`` as found
(with huge pages one fault maps 2 MB: ``minflt`` x the page size is then
far under ``fresh_MB``).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import subprocess
import sys
import threading
import time

from scenery_insitu_tpu.obs.hostmem import PAGE, host_pages
from scenery_insitu_tpu.runtime.hostheap import keep_large_blocks

K = 16                  # slots
CHANNELS = (4, 2)       # colour, depth: f32, 24 B a slot and pixel
PIECES = (1, 4, 16)
FILLERS = ("matmul", "stream")
LATE = ("late", "late-thread")      # the children that call mallopt late
LANDED_MS = 0.3
_THP = "/sys/kernel/mm/transparent_hugepage/enabled"


def frame_side(nbytes: int) -> int:
    """The side s of the square frame nearest under ``nbytes``, a multiple
    of 16 (so that 16 pieces cut its rows evenly)."""
    side = math.isqrt(nbytes // (K * 4 * sum(CHANNELS)))
    return max(16, side - side % 16)


def _touched(pages, before: tuple, prefix: str = "") -> dict:
    """A row's account of the host's memory since the reading ``before``:
    ``fresh_MB`` (net growth of the resident set) and, where the kernel
    counts them, ``minflt``."""
    found = pages.since(before)
    row = {prefix + "fresh_MB": found.get("rss_pages", 0) * PAGE / 1e6}
    if "minflt" in found:
        row[prefix + "minflt"] = found["minflt"]
    return row


def _ms(t0: float) -> float:
    return (time.perf_counter() - t0) * 1e3


class Probe:
    """One process's measurements on ``n_dev`` devices of its backend."""

    def __init__(self, nbytes: int, n_dev: int, filler_ms: float):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        self.jax, self.jnp, self.np = jax, jnp, np
        devs = jax.devices()
        if len(devs) < n_dev:
            raise SystemExit(f"transfer_probe: {n_dev} device(s) asked "
                             f"for, {len(devs)} found")
        self.devs = devs[:n_dev]
        self.mesh = Mesh(np.array(self.devs), ("ranks",))
        self.rows_sharding = NamedSharding(self.mesh, P("ranks"))
        self.whole = NamedSharding(self.mesh, P())
        self.side = frame_side(nbytes)
        self.nbytes = K * 4 * sum(CHANNELS) * self.side ** 2
        self.filler_ms = filler_ms
        self._makers = {}
        self._seed = 0
        self._tiny = jax.jit(lambda x: x + 1.0)
        self._tiny_arg = jax.device_put(jnp.zeros((8,), jnp.float32),
                                        self.devs[0])
        self._small = np.zeros((16,), np.float32)       # 64 bytes
        jax.block_until_ready(self._tiny(self._tiny_arg))
        self._fillers = {name: self._build_filler(name) for name in FILLERS}

    # ------------------------------------------------------------ programs
    def frame(self, pieces: int) -> list:
        """A fresh frame as ``pieces`` arrays a leaf, computed and there."""
        jax, jnp = self.jax, self.jnp
        if pieces not in self._makers:
            shapes = [(K, c, self.side // pieces, self.side)
                      for c in CHANNELS for _ in range(pieces)]

            def make(seed):
                return [jax.lax.broadcasted_iota(jnp.float32, s, 3) + seed
                        for s in shapes]

            self._makers[pieces] = jax.jit(
                make, out_shardings=[self.rows_sharding] * len(shapes))
        self._seed += 1
        return jax.block_until_ready(
            self._makers[pieces](jnp.float32(self._seed)))

    def _build_filler(self, name: str):
        """``launch()`` enqueues ``filler_ms`` of the named program on every
        device and returns its output; the loop count is fitted here."""
        jax, jnp = self.jax, self.jnp
        n = len(self.devs)
        if name == "matmul":
            x = jnp.full((n * 1024, 1024), 0.5, jnp.bfloat16)
            w = jax.device_put(jnp.full((1024, 1024), 1e-3, jnp.bfloat16),
                               self.whole)
            step = lambda _, x: (x @ w).astype(jnp.bfloat16)
        else:
            x = jnp.zeros((n * 32, 1024, 1024), jnp.float32)
            step = lambda _, x: x * 0.999 + 1.0
        x = jax.device_put(x, self.rows_sharding)
        run = jax.jit(lambda x, k: jax.lax.fori_loop(0, k, step, x),
                      out_shardings=self.rows_sharding)
        loops = 8
        jax.block_until_ready(run(x, loops))
        for _ in range(3):          # fit the loop count to the time asked
            t0 = time.perf_counter()
            jax.block_until_ready(run(x, loops))
            took = _ms(t0)
            loops = max(1, int(loops * self.filler_ms / max(took, 1e-3)))
        t0 = time.perf_counter()
        jax.block_until_ready(run(x, loops))
        self.filler_read = {**getattr(self, "filler_read", {}),
                            name: {"loops": loops, "ms": _ms(t0)}}
        return lambda: run(x, loops)

    def _fill(self, name: str):
        """Enough of filler ``name`` in the device's queue to outlast the
        transfer at 2.5 GB/s; the last program's output."""
        out = None
        for _ in range(max(1, math.ceil(
                self.nbytes / 2.5e6 / self.filler_ms))):
            out = self._fillers[name]()
        return out

    def _shards(self, arrays: list) -> list:
        """What the session asks ``np.asarray`` of: the array itself on
        one device, every shard's on a mesh."""
        if len(self.devs) == 1:
            return list(arrays)
        return [sh.data for a in arrays for sh in a.addressable_shards]

    # -------------------------------------------------------------- trials
    def transfer(self, pieces: int, beside: str, held: str = "") -> dict:
        """One trial: a fresh frame of ``pieces`` arrays a leaf brought to
        the host with the device idle (``beside`` "") or behind a filler;
        ``held`` "call" / "put" makes that launch right after the copies
        are asked for."""
        jax, np = self.jax, self.np
        arrays = self.frame(pieces)
        row = {"pieces": pieces, "beside": beside or "idle"}
        busy = self._fill(beside) if beside else None
        pages = host_pages()
        before, t0 = pages.read(), time.perf_counter()
        for a in arrays:
            a.copy_to_host_async()
        row["start_ms"] = _ms(t0)
        if held:
            t1 = time.perf_counter()
            out = (self._tiny(self._tiny_arg) if held == "call"
                   else jax.device_put(self._small, self.devs[0]))
            row.update(held=held, ret_ms=_ms(t1))
            jax.block_until_ready(out)
            row["done_ms"] = _ms(t1)
        host, waits = [], []
        for sh in self._shards(arrays):
            t1 = time.perf_counter()
            host.append(np.asarray(sh))
            waits.append(_ms(t1))
        row["ms"] = _ms(t0)
        row.update(_touched(pages, before))
        if held:
            landed = next((i for i, w in enumerate(waits)
                           if w > LANDED_MS), len(waits))
            row["landed"] = f"{landed}/{len(waits)}"
        row["GB/s"] = self.nbytes / row["ms"] / 1e6
        if busy is not None:
            row["filler_left"] = not busy.is_ready()
            jax.block_until_ready(busy)
        before, t0 = pages.read(), time.perf_counter()
        del arrays, a, sh
        row["free_dev_ms"] = _ms(t0)
        t0 = time.perf_counter()
        host.clear()
        row["free_host_ms"] = _ms(t0)
        row.update(_touched(pages, before, "free_"))
        return row

    def shard_ends(self, beside: str) -> dict:
        """The whole frame on the mesh, one waiting thread a device: the
        ms after the copies were asked for at which each device's shards
        were numpy arrays."""
        np = self.np
        arrays = self.frame(1)
        busy = self._fill(beside) if beside else None
        by_dev = {}
        for a in arrays:
            for sh in a.addressable_shards:
                by_dev.setdefault(sh.device.id, []).append(sh.data)
        ends = {}
        t0 = time.perf_counter()
        for a in arrays:
            a.copy_to_host_async()

        def wait(dev, shards):
            for sh in shards:
                np.asarray(sh)
            ends[dev] = _ms(t0)

        threads = [threading.Thread(target=wait, args=item)
                   for item in by_dev.items()]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if busy is not None:
            self.jax.block_until_ready(busy)
        return {"beside": beside or "idle",
                "ends_ms": [ends[d] for d in sorted(ends)]}

    def pinned(self, beside: str) -> dict:
        """The whole frame moved by ``device_put`` to pinned host memory
        under its own sharding, then read as numpy arrays; where this jax
        or its runtime does not take that, the row says ``unsupported``
        with the reason."""
        try:
            return self._pinned(beside)
        except Exception as e:
            return {"pieces": 1, "beside": beside or "idle", "unsupported": (
                str(e).strip().splitlines() or [type(e).__name__])[0][:160]}

    def _pinned(self, beside: str) -> dict:
        jax, np = self.jax, self.np
        row = {"pieces": 1, "beside": beside or "idle"}
        where = self.rows_sharding.with_memory_kind("pinned_host")
        arrays = self.frame(1)
        busy = self._fill(beside) if beside else None
        pages = host_pages()
        before, t0 = pages.read(), time.perf_counter()
        there = jax.device_put(arrays, [where] * len(arrays))
        row["start_ms"] = _ms(t0)
        jax.block_until_ready(there)
        row["ready_ms"] = _ms(t0)
        host = [np.asarray(sh) for sh in self._shards(there)]
        row["ms"] = _ms(t0)
        row.update(_touched(pages, before))
        row["GB/s"] = self.nbytes / row["ms"] / 1e6
        if busy is not None:
            row["filler_left"] = not busy.is_ready()
            jax.block_until_ready(busy)
        del host
        return row


def _median_row(trials: list) -> dict:
    """The median of every number over a row's trials; what is not a number
    (names, ``landed``, ``filler_left``) from the trial in the middle."""
    mid = dict(trials[len(trials) // 2])
    for key, v in mid.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            mid[key] = statistics.median(t[key] for t in trials)
    mid["trials"] = trials
    return mid


def measure(args) -> dict:
    """A child's whole program; ``args.child`` says which destination."""
    if args.child == "mallopt":
        keep_large_blocks()
    probe = Probe(args.bytes, args.devices, args.filler_ms)
    dev = probe.devs[0]
    out = {"platform": dev.platform, "kind": dev.device_kind,
           "devices": len(probe.devs), "bytes": probe.nbytes,
           "side": probe.side, "page": PAGE, "child": args.child,
           "counts_faults": host_pages().counts_faults,
           "fillers": probe.filler_read}
    repeat = lambda fn, *a: _median_row([fn(*a) for _ in range(args.repeats)])
    states = ("",) + FILLERS
    pieces = PIECES if args.child == "default" else (1,)
    probe.transfer(1, "")                               # first-use costs
    if args.child in LATE:
        def late() -> None:
            out["before"] = [repeat(probe.transfer, 1, "")]
            out["kept"] = keep_large_blocks()
            probe.transfer(1, "")           # the heap grows, once
            out["transfer"] = [repeat(probe.transfer, 1, b) for b in states]

        if args.child == "late":
            late()
        else:
            other = threading.Thread(target=late, name="probe-late")
            other.start()
            other.join()
        return out
    out["transfer"] = [repeat(probe.transfer, p, b)
                       for p in pieces for b in states]
    if args.child != "default":
        return out
    out["held"] = [repeat(probe.transfer, p, b, h) for p in pieces
                   for b in ("", "stream") for h in ("call", "put")]
    if len(probe.devs) > 1:
        out["shard_ends"] = [probe.shard_ends(b) for b in states
                             for _ in range(args.repeats)]
    out["pinned_host"] = [repeat(probe.pinned, b) for b in states]
    return out


# -------------------------------------------------------------- the parent

_COLUMNS = {
    "transfer": ("dest", "pieces", "beside", "ms", "GB/s", "start_ms",
                 "fresh_MB", "minflt", "free_dev_ms", "free_host_ms",
                 "free_fresh_MB", "filler_left"),
    "held": ("pieces", "beside", "held", "ret_ms", "done_ms", "landed",
             "ms", "GB/s"),
    "pinned_host": ("dest", "pieces", "beside", "ready_ms", "ms", "GB/s",
                    "start_ms", "fresh_MB", "minflt", "filler_left",
                    "unsupported"),
}


def _cell(v) -> str:
    if isinstance(v, float):
        return f"{v:.2f}"
    return "-" if v is None else str(v)


def table(title: str, columns, rows: list) -> str:
    cells = [[_cell(r.get(c)) for c in columns] for r in rows]
    widths = [max(len(c), *(len(row[i]) for row in cells))
              for i, c in enumerate(columns)]
    line = lambda vals: "  ".join(v.rjust(w) for v, w in zip(vals, widths))
    return "\n".join([title, line(columns), *map(line, cells)])


def report(res: dict) -> str:
    head = (f"transfer_probe: {res['bytes']} B a frame (f32 [{K}, 4|2, "
            f"{res['side']}, {res['side']}]) on {res['devices']} x "
            f"{res['kind']} ({res['platform']}); page {res['page']} B; "
            f"the kernel counts page faults: {res['counts_faults']}; thp "
            f"{res['thp']}; fillers {res['fillers']}")
    dest = lambda rows, name: [dict(r, dest=name) for r in rows]
    parts = [head, table(
        "transfer: copy_to_host_async -> last np.asarray",
        _COLUMNS["transfer"], dest(res["transfer"], "default")
        + dest(res["mallopt"], "mallopt") + [
            row for name in LATE
            for row in dest(res[name + ":before"], name + ":before")
            + dest(res[name], name)])]
    parts.append(table("held: a launch made with the transfer in flight",
                       _COLUMNS["held"], res["held"]))
    if res.get("shard_ends"):
        parts.append("shard_ends: ms at which each device's shards were on "
                     "the host\n" + "\n".join(
                         f"  {r['beside']:>6}  " + "  ".join(
                             f"{v:.2f}" for v in r["ends_ms"])
                         for r in res["shard_ends"]))
    parts.append(table("destination pinned_host: device_put, then numpy",
                       _COLUMNS["pinned_host"],
                       dest(res["pinned_host"], "pinned_host")))
    return "\n\n".join(parts)


def _thp() -> str:
    if not os.path.exists(_THP):
        return "not found"
    with open(_THP) as f:
        return f.read().strip()


def _child(args, which: str) -> dict:
    cmd = [sys.executable, "-m", "scenery_insitu_tpu.obs.transfer_probe",
           "--child", which, "--bytes", str(args.bytes), "--devices",
           str(args.devices), "--repeats", str(args.repeats),
           "--filler-ms", str(args.filler_ms)]
    p = subprocess.run(cmd, stdout=subprocess.PIPE, text=True)
    if p.returncode != 0:
        raise SystemExit(f"transfer_probe: the {which} child ended with "
                         f"{p.returncode}")
    return json.loads(p.stdout.strip().splitlines()[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--bytes", type=int, default=157286400)
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--filler-ms", type=float, default=100.0)
    ap.add_argument("--json", action="store_true",
                    help="the whole result as one JSON line, last")
    ap.add_argument("--child", choices=("default", "mallopt") + LATE,
                    default="",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        print(json.dumps(measure(args)), flush=True)
        return 0
    res = _child(args, "default")
    res["mallopt"] = _child(args, "mallopt")["transfer"]
    for name in LATE:
        child = _child(args, name)
        res[name], res[name + ":before"] = child["transfer"], child["before"]
    res["thp"] = _thp()
    print(report(res), flush=True)
    if args.json:
        print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
