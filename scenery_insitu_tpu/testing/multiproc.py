"""Subprocess multi-process harness — real ``jax.distributed`` fleets in
ordinary CI (docs/MULTIHOST.md "The CI harness").

The old two-process smoke (tests/test_multihost.py pre-ISSUE-14) was
slow-marked and permanently failing: the CPU backend cannot run
cross-process DEVICE collectives, so any test built on a global-mesh
jitted program died with "Multiprocess computations aren't implemented".
What DOES work multi-process on CPU — verified, and what the host path
of the hierarchical composite is built on — is everything on the HOST
plane: the coordination-service KV store and barriers, zmq tile streams,
and per-process LOCAL-mesh SPMD programs. This harness spawns real
``jax.distributed.initialize`` processes (one coordinator, N workers,
each with its own virtual CPU device set) and runs an ENTRY FUNCTION in
every worker, so hierarchical paths, host gathers and the obs-event
merge run for real in CI instead of being skipped.

Usage (from a test)::

    from scenery_insitu_tpu.testing import multiproc

    results = multiproc.run_multiproc(
        "tests.test_multihost:_entry_hier", n_procs=2,
        devices_per_proc=2, workdir=tmp_path)
    assert all(r.returncode == 0 for r in results), results

The entry is ``module:function`` taking one `MPContext`; it runs AFTER
``jax.distributed`` is initialized (through the retry-laddered
``multihost.initialize``) on the CPU backend (`virtual_mesh_env` pins
it in the worker's environment). Workers share ``workdir`` for artifacts; the parent only
collects exit codes + stdout — assertions live in the entry (a failed
assert is a nonzero exit) and in the parent over the artifacts.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import List, NamedTuple, Optional


class MPContext(NamedTuple):
    """What an entry function gets: its place in the fleet plus the
    shared scratch directory."""

    process_id: int
    num_processes: int
    workdir: str
    args: tuple = ()


class ProcResult(NamedTuple):
    process_id: int
    returncode: int
    output: str

    @property
    def ok(self) -> bool:
        return self.returncode == 0


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_multiproc(entry: str, n_procs: int, devices_per_proc: int = 2,
                  workdir: Optional[str] = None, args: tuple = (),
                  timeout_s: float = 420.0) -> List[ProcResult]:
    """Spawn ``n_procs`` real jax.distributed worker processes on this
    machine and run ``entry`` (``module:function``) in each. Returns one
    `ProcResult` per worker; a worker that wedges past ``timeout_s`` is
    killed (its siblings too — they would block on the dead coordinator)
    and reported with returncode -9."""
    from scenery_insitu_tpu.utils.backend import virtual_mesh_env

    coordinator = f"127.0.0.1:{free_port()}"
    workdir = workdir or os.getcwd()
    procs = []
    for pid in range(n_procs):
        base = dict(os.environ)
        # each worker pins its OWN virtual device count — the parent's
        # (e.g. the 8-device test mesh) must not leak through
        base["XLA_FLAGS"] = " ".join(
            f for f in base.get("XLA_FLAGS", "").split()
            if "host_platform_device_count" not in f)
        env = virtual_mesh_env(devices_per_proc, base)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "scenery_insitu_tpu.testing.multiproc",
             "--entry", entry, "--coordinator", coordinator,
             "--processes", str(n_procs), "--process-id", str(pid),
             "--workdir", str(workdir)] + [str(a) for a in args],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            cwd=_repo_root()))

    results: List[ProcResult] = []
    for pid, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=timeout_s)
            results.append(ProcResult(pid, p.returncode,
                                      out.decode("utf-8", "replace")))
        except subprocess.TimeoutExpired:  # sitpu-lint: disable=SITPU-LEDGER — harness verdict IS the ProcResult(-9); nothing degrades silently
            for q in procs:
                if q.poll() is None:
                    q.kill()
            for q in procs:     # reap: SIGKILL delivery is asynchronous
                try:
                    q.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    pass
            out = b""
            try:
                out = p.stdout.read() or b""
            except Exception:
                pass
            results.append(ProcResult(pid, -9, out.decode(
                "utf-8", "replace") + f"\n[harness] worker {pid} timed "
                f"out after {timeout_s:.0f}s and was killed"))
    return results


def _child_main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--entry", required=True,
                    help="module:function taking one MPContext")
    ap.add_argument("--coordinator", required=True)
    ap.add_argument("--processes", type=int, required=True)
    ap.add_argument("--process-id", type=int, required=True)
    ap.add_argument("--workdir", default=".")
    args, extra = ap.parse_known_args(argv)

    from scenery_insitu_tpu.parallel import multihost

    multihost.initialize(args.coordinator, args.processes,
                         args.process_id, timeout_s=120.0,
                         attempt_timeout_s=30.0)

    import importlib

    mod_name, _, fn_name = args.entry.partition(":")
    if not fn_name:
        raise SystemExit(f"--entry must be module:function, "
                         f"got {args.entry!r}")
    fn = getattr(importlib.import_module(mod_name), fn_name)
    ctx = MPContext(process_id=args.process_id,
                    num_processes=args.processes,
                    workdir=args.workdir, args=tuple(extra))
    rc = 0
    try:
        fn(ctx)
        print(f"[mp {args.process_id}] ENTRY_OK", flush=True)
    except BaseException as e:          # noqa: B036  # sitpu-lint: disable=SITPU-LEDGER — exit code IS the verdict; the parent raises on it
        import traceback

        traceback.print_exc()
        print(f"[mp {args.process_id}] ENTRY_FAILED "
              f"{type(e).__name__}: {e}", flush=True)
        rc = 1
    finally:
        import jax

        try:
            jax.distributed.shutdown()
        except Exception:
            pass
    return rc


if __name__ == "__main__":
    sys.exit(_child_main())
