"""sitpu-lint golden tests (docs/STATIC_ANALYSIS.md).

Per checker: the seeded bad fixture is flagged, the good twin is clean,
and inline suppressions are honored. Plus the baseline gate mechanics,
the repo-wide clean run against the committed baseline, and the ledger
round-trip (every statically discovered degrade component appears in
``obs.ledger_registry()`` and vice versa).

Pure host-side AST work — no jax arrays, no device, fast.
"""

import os

import pytest

from scenery_insitu_tpu.tools.lint import counters as C
from scenery_insitu_tpu.tools.lint import ledger as L
from scenery_insitu_tpu.tools.lint import pallas as P
from scenery_insitu_tpu.tools.lint import thread as TH
from scenery_insitu_tpu.tools.lint import trace as TR
from scenery_insitu_tpu.tools.lint.runner import (default_baseline_path,
                                                  run_checks, run_lint)
from scenery_insitu_tpu.tools.lint.core import (Baseline, find_repo_root,
                                                load_sources)

ROOT = find_repo_root()
FIX = os.path.join(ROOT, "tests", "lint_fixtures")


def fixture_sources(*names):
    return load_sources(ROOT, [os.path.join(FIX, n) for n in names])


def codes_of(diags):
    return [d.code for d in diags]


# ------------------------------------------------------------ SITPU-LEDGER

class TestLedger:
    def test_bad_flagged(self):
        diags = L.check(fixture_sources("bad_ledger.py"))
        msgs = [d.message for d in diags]
        # ImportError impl swap, print-and-swap handler, probe consult
        assert len(diags) == 3, diags
        assert any("missing optional dependency" in m for m in msgs)
        assert any("swaps result" in m for m in msgs)
        assert any("have_turbo" in m for m in msgs)
        assert {d.symbol for d in diags} == {"load_codec", "pick_backend",
                                             "run"}

    def test_good_clean(self):
        # run_checks applies the inline-suppression filter, so the good
        # fixture (whose one remaining handler carries a justified
        # disable comment) comes out fully clean
        diags = run_checks(fixture_sources("good_ledger.py"))
        assert diags == [], [d.render() for d in diags]

    def test_suppression_honored(self):
        # the raw checker DOES flag suppressed(); the runner's
        # suppression filter is what silences it — prove both halves
        src = fixture_sources("good_ledger.py")
        raw = L.check(src)
        assert [d.symbol for d in raw] == ["suppressed"]
        assert src[0].suppressed(raw[0].line, raw[0].code)
        assert run_checks(src) == []

    def test_discovery_literal_components(self):
        srcs = fixture_sources("good_ledger.py")
        comps = L.discover_degrade_components(srcs)
        assert set(comps) == {"fixture.codec", "fixture.backend",
                              "fixture.turbo"}


# ----------------------------------------------------------- SITPU-COUNTER

class TestCounter:
    def test_bad_flagged(self):
        diags = C.check(fixture_sources("bad_counter.py"))
        msgs = [d.message for d in diags]
        # unregistered literal, unregistered *_counter default,
        # unregistered *_counter keyword, dynamic non-parameter name
        assert len(diags) == 4, [d.render() for d in diags]
        assert sum("not registered" in m for m in msgs) == 3
        assert any("'frames_rendered_totally_unregistered'" in m
                   for m in msgs)
        assert any("'fixture_unregistered_steps'" in m for m in msgs)
        assert any("'fixture_unregistered_hops'" in m for m in msgs)
        assert any("dynamic variable 'metric'" in m for m in msgs)

    def test_good_clean(self):
        # run_checks applies the inline-suppression filter, silencing
        # the one deliberately-suppressed dynamic name
        diags = run_checks(fixture_sources("good_counter.py"))
        assert diags == [], [d.render() for d in diags]

    def test_counter_param_pattern_accepted(self):
        # the raw checker only flags the suppressed dynamic call — the
        # *_counter-parameter call and registered literals are clean
        raw = C.check(fixture_sources("good_counter.py"))
        assert [d.symbol for d in raw] == ["suppressed"]

    def test_discovery(self):
        srcs = fixture_sources("good_counter.py")
        disc = C.discover_counters(srcs)
        assert set(disc) == {"build_steps", "ring_steps_built",
                             "dcn_hops_built"}


# ------------------------------------------------------------ SITPU-THREAD

def thread_check(pipeline):
    return TH.check(fixture_sources(pipeline),
                    pipeline_path=f"tests/lint_fixtures/{pipeline}")


def thread_messages(pipeline):
    by_sym = {}
    for d in thread_check(pipeline):
        by_sym.setdefault(d.symbol, []).append(d.message)
    return by_sym


class TestThread:
    def test_loose_fields_builder_flagged(self):
        """A builder that takes CompositeConfig fields one by one (the
        plain builders until PR 29) instead of the config is refused."""
        msgs = thread_messages("bad_thread.py")["distributed_missing_step"]
        assert any("does not accept comp_cfg" in m for m in msgs), msgs

    def test_rebuilt_config_flagged(self):
        """Both ways of handing on a different config than the one that
        came in: a fresh CompositeConfig(fields...) and
        dataclasses.replace. The bare default fill is neither."""
        by_sym = thread_messages("bad_thread.py")
        for sym in ("distributed_bad_step", "distributed_replaced_step"):
            assert sum("rebuilds CompositeConfig" in m
                       for m in by_sym[sym]) == 1, by_sym[sym]

    def test_dropped_config_fails(self):
        """A builder that accepts the config and never hands it on."""
        by_sym = thread_messages("bad_thread.py")
        assert any("never forwards it" in m
                   for m in by_sym["distributed_dropped_obj_step"])
        # the rebuilding builder forwards only its own copy
        assert any("never forwards it" in m
                   for m in by_sym["distributed_bad_step"])

    def test_good_builders_clean(self):
        diags = thread_check("good_thread.py")
        assert diags == [], [d.render() for d in diags]

    def test_plain_builders_take_config_whole(self):
        """The real plain builders name no CompositeConfig field in
        their signatures: the config arrives as ``comp_cfg``."""
        import dataclasses
        import inspect

        from scenery_insitu_tpu.config import CompositeConfig
        from scenery_insitu_tpu.parallel import pipeline

        fields = {f.name for f in dataclasses.fields(CompositeConfig)}
        for fn in (pipeline.distributed_plain_step,
                   pipeline.distributed_plain_step_mxu):
            params = set(inspect.signature(fn).parameters)
            assert "comp_cfg" in params and not params & fields, params

    def test_topology_threading_enforced(self):
        """ISSUE 14: every distributed builder must accept AND consume
        the TopologyConfig — a builder that drops it silently composites
        flat on a hierarchical mesh."""
        by_sym = thread_messages("bad_thread.py")
        for sym in ("distributed_bad_step", "distributed_missing_step",
                    "distributed_dropped_obj_step"):
            assert any("does not accept 'topology'" in m
                       for m in by_sym[sym]), by_sym[sym]
        # the compliant fixtures resolve it — clean
        assert thread_check("good_thread.py") == []

    def test_real_builders_thread_whole_config(self):
        """The real pipeline: every builder takes the config whole,
        forwards it and consumes the topology — nothing is baselined."""
        diags = TH.check(load_sources(ROOT, [os.path.join(
            ROOT, "scenery_insitu_tpu/parallel/pipeline.py")]))
        assert diags == [], [d.render() for d in diags]


# ------------------------------------------------------------- SITPU-TRACE

class TestTrace:
    def test_bad_flagged(self):
        diags = TR.check(fixture_sources("bad_trace.py"))
        msgs = [d.message for d in diags]
        assert any("Python `if` on a traced value" in m for m in msgs)
        assert any("float() on a traced value" in m for m in msgs)
        assert any("pulls a traced value to host" in m for m in msgs)
        assert any("inside a lax.scan body" in m for m in msgs)
        assert any("static_argnames ['engine']" in m for m in msgs)
        assert len(diags) == 5, [d.render() for d in diags]

    def test_good_clean(self):
        diags = TR.check(fixture_sources("good_trace.py"))
        assert diags == [], [d.render() for d in diags]

    def test_real_pipeline_clean(self):
        """The distributed pipeline (ring/waves/scan machinery) must stay
        free of host-sync hazards — this is the invariant that protects
        the PR 4/8 overlap structure."""
        paths = [os.path.join(ROOT, "scenery_insitu_tpu", "parallel",
                              "pipeline.py")]
        diags = TR.check(load_sources(ROOT, paths))
        assert diags == [], [d.render() for d in diags]


# ------------------------------------------------------------ SITPU-PALLAS

class TestPallas:
    def test_bad_flagged(self):
        diags = P.check(fixture_sources("bad_pallas.py"))
        msgs = [d.message for d in diags]
        assert any("tile-divisibility" in m for m in msgs)
        assert any("blocked SMEM operand" in m for m in msgs)
        assert len(diags) == 2, [d.render() for d in diags]

    def test_good_clean(self):
        diags = P.check(fixture_sources("good_pallas.py"))
        assert diags == [], [d.render() for d in diags]

    def test_real_kernels_clean(self):
        """Every production pallas_call declares its tile-divisibility
        handling and keeps its SMEM operands whole."""
        pkg = os.path.join(ROOT, "scenery_insitu_tpu")
        paths = []
        for dirpath, _, files in os.walk(pkg):
            if "tools" in dirpath or "__pycache__" in dirpath:
                continue
            paths += [os.path.join(dirpath, f) for f in files
                      if f.endswith(".py")]
        diags = P.check(load_sources(ROOT, paths))
        assert diags == [], [d.render() for d in diags]


# ---------------------------------------------------------- baseline gate

class TestBaseline:
    def test_gate_mechanics(self, tmp_path):
        diags = L.check(fixture_sources("bad_ledger.py"))
        assert diags
        # no baseline: everything is new
        new, acc, stale = Baseline([]).split(diags)
        assert len(new) == len(diags) and not acc and not stale
        # full baseline: everything accepted
        bl = Baseline([Baseline.entry_for(d, "seeded fixture") for d in
                       diags])
        new, acc, stale = bl.split(diags)
        assert not new and len(acc) == len(diags) and not stale
        # baseline survives a save/load round trip
        p = tmp_path / "bl.json"
        bl.save(str(p))
        new, acc, _ = Baseline.load(str(p)).split(diags)
        assert not new and len(acc) == len(diags)
        # stale entries are reported once the finding disappears
        _, _, stale = bl.split(diags[1:])
        assert len(stale) == 1

    def test_cli_fail_on_stale(self, tmp_path):
        """ISSUE 15 satellite: with --fail-on-stale a baseline entry
        that no longer matches any finding FAILS the gate instead of
        lingering as a dead row (CI runs the flag)."""
        from scenery_insitu_tpu.tools.lint.__main__ import main as cli

        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        bl = tmp_path / "bl.json"
        Baseline([{"code": "SITPU-LEDGER", "path": "gone.py",
                   "message": "long since fixed", "symbol": "f",
                   "reason": "a debt that was paid off and never pruned"
                   }]).save(str(bl))
        args = ["--baseline", str(bl), str(clean)]
        assert cli(args) == 0                     # stale alone passes...
        assert cli(["--fail-on-stale"] + args) == 1   # ...the flag gates
        # and the committed baseline stays stale-free under the flag
        assert cli(["--fail-on-stale"]) == 0

    def test_reasons_are_mandatory(self):
        with pytest.raises(ValueError, match="without a reason"):
            Baseline([{"code": "X", "path": "p", "message": "m",
                       "reason": ""}])

    def test_committed_baseline_reasons(self):
        bl = Baseline.load(default_baseline_path())
        assert bl.entries, "committed baseline missing"
        assert all(len(e["reason"]) > 20 for e in bl.entries)

    def test_repo_is_clean_against_baseline(self):
        """The acceptance criterion: the suite exits 0 on the repo."""
        new, accepted, stale, _ = run_lint()
        assert new == [], [d.render() for d in new]
        assert stale == [], stale

    def test_unparseable_file_is_a_finding_not_a_crash(self, tmp_path):
        bad = tmp_path / "broken.py"
        bad.write_text("def oops(:\n")
        new, _, _, diags = run_lint(paths=[str(bad)],
                                    repo_root=str(tmp_path))
        assert [d.code for d in diags] == ["SITPU-PARSE"]
        assert new == diags

    def test_inline_suppression_filtered_by_runner(self):
        srcs = fixture_sources("good_ledger.py", "bad_ledger.py")
        diags = run_checks(srcs)
        # bad fixture findings survive, nothing from the good one
        assert all("bad_ledger" in d.path for d in diags
                   if d.code == "SITPU-LEDGER")


# ------------------------------------------------- ledger round-trip test

class TestLedgerRoundTrip:
    def test_registry_matches_static_scan(self):
        """Every statically discovered degrade component is registered in
        obs.ledger_registry() and every registry row has a live site."""
        from scenery_insitu_tpu import obs
        from scenery_insitu_tpu.tools.lint.core import default_scan_paths

        srcs = load_sources(ROOT, default_scan_paths(ROOT))
        discovered = L.discover_degrade_components(srcs)
        registry = obs.ledger_registry()
        assert set(discovered) - set(registry) == set(), \
            f"degrade sites missing from obs.ledger_registry(): " \
            f"{ {c: discovered[c] for c in set(discovered) - set(registry)} }"
        assert set(registry) - set(discovered) == set(), \
            f"registry rows with no degrade site: " \
            f"{sorted(set(registry) - set(discovered))}"

    def test_registry_descriptions(self):
        from scenery_insitu_tpu import obs

        reg = obs.ledger_registry()
        assert all(isinstance(v, str) and len(v) > 10
                   for v in reg.values())

    def test_counter_registry_matches_static_scan(self):
        """Counter twin of the degrade round-trip: every statically
        discovered counter name is registered in obs.counter_registry()
        and every registry row has a live count() site."""
        from scenery_insitu_tpu import obs
        from scenery_insitu_tpu.tools.lint.core import default_scan_paths

        srcs = load_sources(ROOT, default_scan_paths(ROOT))
        discovered = C.discover_counters(srcs)
        registry = obs.counter_registry()
        assert set(discovered) - set(registry) == set(), \
            f"count() sites missing from obs.counter_registry(): " \
            f"{ {c: discovered[c] for c in set(discovered) - set(registry)} }"
        assert set(registry) - set(discovered) == set(), \
            f"registry rows with no count() site: " \
            f"{sorted(set(registry) - set(discovered))}"

    def test_counter_registry_descriptions(self):
        from scenery_insitu_tpu import obs

        reg = obs.counter_registry()
        assert all(isinstance(v, str) and len(v) > 10
                   for v in reg.values())

    def test_runtime_entry_matches_registry(self):
        """A runtime degrade of a registered component round-trips into
        the ledger snapshot."""
        from scenery_insitu_tpu import obs

        before = {tuple(sorted(e.items())) for e in obs.ledger()}
        obs.degrade("io.vdi_codec", "zstd", "zlib",
                    "lint round-trip test entry", warn=False)
        after = obs.ledger()
        assert any(e["component"] == "io.vdi_codec" for e in after)
        assert "io.vdi_codec" in obs.ledger_registry()
        assert len(after) >= len(before)
