"""The process's heap, told to keep its large blocks: where a frame's
device-to-host copy lands (docs/OBSERVABILITY.md "The host heap"). JAX-free.

The runtime allocates a fetched frame's host buffers with ``malloc`` when
``copy_to_host_async`` is called, and its transfer threads then write
them. glibc maps every block over its ceiling for heap blocks afresh
(`DEFAULT_MMAP_THRESHOLD_MAX`) and gives the heap's free top back to the
kernel, so each frame's bytes land on pages nobody has touched: on a v5e's
host that first touch is 78-87 % of a 157 MB transfer (60 ms against 13
into mapped pages on one chip, 46 against 6 on four: PERF.md, PR 43).
`keep_large_blocks` ends both, for the whole process and for good; a
session asks for it where the frame it fetches is that large
(`InSituSession._start_host_copy`).
"""

from __future__ import annotations

import ctypes
import threading

from scenery_insitu_tpu import obs

# glibc's own ceiling (malloc/malloc.c, 64-bit) for the threshold over
# which a block is mapped by itself rather than cut from the heap
DEFAULT_MMAP_THRESHOLD_MAX = 32 * 1024 * 1024

# <malloc.h>
_M_TRIM_THRESHOLD, _M_MMAP_MAX, _M_ARENA_MAX = -1, -4, -8
_NEVER = -1         # mallopt(3): "-1 disables trimming completely"

_LOCK = threading.Lock()
_kept = None        # None until asked; then what the first call found


def _mallopt(say):
    """glibc's ``mallopt``; where it is not there None, the reason said
    through ``say`` and in a ``host.heap`` ledger row."""
    try:
        mallopt = ctypes.CDLL("libc.so.6").mallopt
    except (OSError, AttributeError) as e:
        why = f"glibc's mallopt is not there ({type(e).__name__}: {e})"
        obs.degrade("host.heap", "keep_large_blocks", "libc_defaults", why,
                    warn=False)
        say(f"[hostheap] {why}: the heap stays as it is")
        return None
    mallopt.argtypes, mallopt.restype = (ctypes.c_int, ctypes.c_int), \
        ctypes.c_int
    return mallopt


def keep_large_blocks(log=None) -> bool:
    """Tell glibc to serve every block from the heap and never give it
    back: ``M_MMAP_MAX`` 0, ``M_TRIM_THRESHOLD`` -1 (never: a threshold of
    ``INT_MAX`` would still give back a free top of 2 GB, which four
    frames of 629-786 MB let go at the end of a run are), one arena. A
    freed host buffer then stays mapped and the next one of its size
    takes its pages.

    Acts once per process (later calls return what the first found) and
    cannot be undone; where there is no ``libc.so.6`` with a ``mallopt``
    it does nothing and says why through ``log`` and in a ``host.heap``
    ledger row, as it says in one line what it did. Returns whether the
    heap keeps its blocks.

    What it reaches when called late, with threads running (glibc 2.36):
    ``M_TRIM_THRESHOLD`` is process-wide and takes at once; ``M_MMAP_MAX``
    0 takes for every block cut from the main arena, the arena of the
    process's first thread; a thread that already has an arena of its own
    keeps it (``M_ARENA_MAX`` moves nobody), and there a block over the
    arena's 64 MB heaps is still mapped afresh."""
    global _kept
    with _LOCK:
        if _kept is not None:
            return _kept
        say = log or (lambda s: None)
        mallopt = _mallopt(say)
        if mallopt is None:
            _kept = False
            return _kept
        took = [mallopt(_M_MMAP_MAX, 0),
                mallopt(_M_TRIM_THRESHOLD, _NEVER),
                mallopt(_M_ARENA_MAX, 1)]
        _kept = all(took)
        say("[hostheap] this process's heap keeps its large blocks from "
            "now on (mallopt M_MMAP_MAX 0, M_TRIM_THRESHOLD never, "
            f"M_ARENA_MAX 1: {took}): its resident set stands at its "
            "high-water mark")
        return _kept
