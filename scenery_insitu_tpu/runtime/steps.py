"""The step table both sessions dispatch through: one compiled step per
key, the state each carries from frame to frame, and the policy of when
that state is seeded, carried and dropped."""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

from scenery_insitu_tpu.obs.profiler import scoped_step


class StepEntry(NamedTuple):
    """One compiled step with what its mode hangs on it."""
    step: Callable                          # f(*args[, thr][, reuse])
    seed_thr: Optional[Callable] = None     # f(*seed_args) -> thresholds
    seed_reuse: Optional[Callable] = None   # f(*seed_args) -> ReuseState
    after: Optional[Callable] = None        # plain / hybrid: display warp


class StepTable:
    """A session's compiled steps, one per key (a march regime; for a
    scene also its grid signature), and the state each carries from
    frame to frame: the temporal threshold maps and the reuse fragments.
    Both sessions dispatch through it, so what a compile counts, when
    carried state is seeded and when it is dropped are written once.

    ``fixed`` is the step of a mode that compiles once for every regime
    (gather engine, particles); ``helpers`` are the other compiled
    functions that bake in the TF or the decomposition (the replan's
    profile fetches). `reset` forgets all of it; `swap` puts it aside
    under an identity and takes back what was put aside under another
    (a steered TF seen before). ``last_key`` is the previous frame's key:
    it outlives both, and a checkpoint carries it verbatim so that a
    resumed run makes the same drop/keep decisions."""

    ASIDE = 8       # identities kept aside: bounds the executables pinned

    def __init__(self, obs, max_entries: int = 0):
        self.obs = obs
        self.max_entries = max_entries      # 0: no bound
        self.last_key = None
        self._aside = {}
        self.reset()

    def reset(self) -> None:
        self.steps = {}
        self.thr = {}
        self.reuse = {}
        self.fixed = None
        self.helpers = {}

    def swap(self, old, new) -> bool:
        """Put the compiled steps aside under ``old`` and take those put
        aside under ``new``; False where there are none (the caller
        builds). Carried state re-seeds either way: it tracked the scene
        under the old identity."""
        self._aside[old] = (self.steps, self.fixed, self.helpers)
        while len(self._aside) > self.ASIDE:
            self._aside.pop(next(iter(self._aside)))
        kept = self._aside.get(new)
        self.reset()
        if kept is None:
            return False
        self.steps, self.fixed, self.helpers = kept
        return True

    def enter(self, key) -> None:
        """The policy of a session that carries state: when the camera
        enters a key other than the previous frame's (counter
        ``regime_switches``), that key's carried state is dropped and
        re-seeds. A threshold map frozen many frames ago, while the
        camera was elsewhere and the data kept evolving, would cost the
        controller several overflow-degraded frames to walk back; a
        retained reuse signature could mask the change altogether (the
        camera leaves match again)."""
        last = self.last_key
        if last is not None and key != last:
            self.obs.count("regime_switches")
            self.thr.pop(key, None)
            self.reuse.pop(key, None)
        self.last_key = key

    def compile(self, key, build, frame: int, what: str,
                regime) -> StepEntry:
        """A miss: count it (``compile_step``, event ``compile``: the
        next call of the step jits it), take ``build()``'s entry with
        its step under `scoped_step`, and keep at most ``max_entries``,
        oldest out first with their state."""
        self.obs.count("compile_step")
        self.obs.event("compile", frame=frame, what=what,
                       regime=str(regime))
        entry = build()
        entry = entry._replace(step=scoped_step(entry.step, self.obs))
        self.steps[key] = entry
        while self.max_entries and len(self.steps) > self.max_entries:
            old = next(iter(self.steps))
            del self.steps[old]
            self.thr.pop(old, None)
            self.reuse.pop(old, None)
        return entry

    def run(self, key, entry: StepEntry, args, seed_args=None):
        """Call ``entry.step`` on ``args`` followed by the state it
        carries, seeded from ``seed_args`` (default ``args``) where the
        key has none yet; keep the state it returns, return its output."""
        seed_thr, seed_reuse = entry.seed_thr, entry.seed_reuse
        if seed_thr is None and seed_reuse is None:
            return entry.step(*args)
        carried = []
        for seed, store in ((seed_thr, self.thr), (seed_reuse, self.reuse)):
            if seed is not None:
                state = store.get(key)
                if state is None:
                    state = seed(*(seed_args or args))
                carried.append(state)
        out, *carried = entry.step(*args, *carried)
        if seed_thr is not None:
            self.thr[key] = carried[0]
        if seed_reuse is not None:
            self.reuse[key] = carried[-1]
        return out

    def snapshot(self):
        """What a prewarm must leave as it found it."""
        return dict(self.thr), dict(self.reuse), self.last_key

    def restore(self, snap) -> None:
        self.thr, self.reuse, self.last_key = snap
