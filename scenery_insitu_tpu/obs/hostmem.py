"""What a process touches of the host's memory, for a RECORDED run's spans
and the transfer probe (docs/OBSERVABILITY.md): the resident set, and the
page faults where the kernel counts them. JAX-free.

Two sources, because neither is enough alone (PERF.md, PR 43). The minor
page faults of ``getrusage`` are what a first touch costs, but a sandboxed
kernel (gVisor: the machines this repo's chips sit in) counts none, and
under transparent huge pages one fault maps 2 MB. The resident set of
``/proc/self/statm`` (one ``pread`` of a descriptor opened once) grows by
exactly the pages first touched, on either kernel: 38,401 pages for a fresh
157 MB array on both. It also shrinks by what is unmapped meanwhile, so a
reading interval says NET growth; `HostPages` sums the growth of every
interval between two of its readings that grew (``grown``), which is what a
frame's ``touched_frame`` is: a span in which 157 MB land and a later one
in which the 157 MB of the frame before are let go count 157, not 0.
"""

from __future__ import annotations

import mmap
import os
import resource

PAGE = resource.getpagesize()
_STATM = "/proc/self/statm"


class HostPages:
    """The process's memory as two counters read together: ``read()`` gives
    ``(resident pages, minor faults, major faults)`` — the first None where
    there is no ``/proc/self/statm``, the others None where this kernel
    counts no fault (asked once, by touching sixteen fresh pages: then
    ``getrusage`` is not called again)."""

    def __init__(self):
        self._fd = (os.open(_STATM, os.O_RDONLY)
                    if os.path.exists(_STATM) else None)
        self.counts_faults = True
        self.grown, self._last = 0, None
        before = self.read()[1]
        with mmap.mmap(-1, 16 * PAGE) as fresh:
            for page in range(16):
                fresh[page * PAGE] = 1
        self.counts_faults = self.read()[1] > before
        self.grown = 0

    def read(self) -> tuple:
        rss = minflt = majflt = None
        if self._fd is not None:
            rss = int(os.pread(self._fd, 128, 0).split()[1])
            if self._last is not None:
                self.grown += max(0, rss - self._last)
            self._last = rss
        if self.counts_faults:
            ru = resource.getrusage(resource.RUSAGE_SELF)
            minflt, majflt = ru.ru_minflt, ru.ru_majflt
        return rss, minflt, majflt

    @staticmethod
    def delta(before: tuple, now: tuple, suffix: str = "") -> dict:
        """What changed between two readings, as span attributes:
        ``rss_pages<suffix>`` (signed: net growth of the resident set),
        ``minflt<suffix>`` where faults are counted and ``majflt<suffix>``
        where there was one."""
        found = {}
        if now[0] is not None:
            found["rss_pages" + suffix] = now[0] - before[0]
        if now[1] is not None:
            found["minflt" + suffix] = now[1] - before[1]
            if now[2] != before[2]:
                found["majflt" + suffix] = now[2] - before[2]
        return found

    def since(self, before: tuple) -> dict:
        """`delta` from the reading ``before`` to one made now."""
        return self.delta(before, self.read())

    def take_grown(self) -> int:
        """The pages the resident set grew by over the reading intervals
        since the last call (up to the last reading), and start over."""
        grown, self.grown = self.grown, 0
        return grown


_PAGES = None


def host_pages() -> HostPages:
    """The process's one `HostPages`, made at its first use (by a recorded
    run: an unrecorded one never asks)."""
    global _PAGES
    if _PAGES is None:
        _PAGES = HostPages()
    return _PAGES
