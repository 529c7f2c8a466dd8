"""graftlint: the static-analysis suite's tier-1 gate.

Three layers:
- the whole-tree gate — `python -m tools.analysis` over THIS repo exits 0
  (every finding fixed, pragma'd with a reason, or baselined with a
  justification), which is what CI runs;
- determinism — two fresh runs produce byte-identical reports, and the
  stable finding IDs survive line drift (IDs carry no line numbers);
- per-pass fixtures under tests/analysis_fixtures/ — each rule has a
  tree with flagged sites, decoy sites that must NOT flag, and a pragma'd
  site that must be suppressed; the fixtures are parsed, never imported.
"""

import json
import os

import pytest

from tools.analysis import runner
from tools.analysis import baseline as baseline_mod
from tools.analysis.core import Project
from tools.analysis.passes.surface import (collect_config_keys,
                                           collect_debug_routes,
                                           collect_metric_names)

FIXTURES = os.path.join(os.path.dirname(__file__), "analysis_fixtures")


def _fixture_run(name, rule, baseline=None):
    return runner.run(root=os.path.join(FIXTURES, name), rules=[rule],
                      baseline_path=baseline)


def _failing(report):
    return {(f.qualname, f.symbol) for f in report.failing}


# -- the whole-tree gate ------------------------------------------------------

def test_repo_tree_is_clean():
    """The CI contract: the analyzer exits 0 on this repo. A new finding
    must be fixed, pragma'd with a reason, or baselined with a
    justification before it can land."""
    report = runner.run()
    assert report.exit_code == 0, (
        "graftlint found unhandled findings:\n" + "\n".join(
            f"  {f.file}:{f.line} [{f.rule}] {f.message} (id: {f.id})"
            for f in report.failing))
    # the baseline is a ratchet: stale entries must be pruned
    assert report.stale_baseline == [], (
        f"baseline entries no longer produced: {report.stale_baseline}")


def test_repo_run_is_deterministic():
    """Two fresh runs (fresh Project each) serialize identically — sorted
    findings, stable IDs, no set/dict iteration-order leakage."""
    a = json.dumps(runner.run().to_dict(), sort_keys=True)
    b = json.dumps(runner.run().to_dict(), sort_keys=True)
    assert a == b


def test_finding_ids_survive_line_drift(tmp_path):
    """IDs carry no line numbers: inserting a comment above every finding
    shifts lines but must not change a single ID (the baseline survives
    unrelated edits)."""
    import shutil

    src = os.path.join(FIXTURES, "hotloop")
    dst = tmp_path / "drifted"
    shutil.copytree(src, dst)
    before = {f.id for f in runner.run(root=src, rules=["hotloop"],
                                       baseline_path=None).failing}
    target = dst / "gofr_tpu" / "tpu" / "engine.py"
    target.write_text("# drift: an unrelated leading comment\n" * 7
                      + target.read_text())
    after = {f.id for f in runner.run(root=str(dst), rules=["hotloop"],
                                      baseline_path=None).failing}
    assert before == after


# -- hotloop ------------------------------------------------------------------

def test_hotloop_fixture_flags_and_decoys():
    report = _fixture_run("hotloop", "hotloop")
    assert _failing(report) == {
        ("Engine._step", "float()"),
        ("Engine._step", ".item"),
        ("Engine._step", "np.asarray"),      # tainted arg only
        ("Engine._helper", "jax.device_get"),
        ("Engine._helper", ".block_until_ready"),
    }
    # the host-side asarray decoys and the unreachable .item stayed quiet
    assert not any(f.qualname == "Engine.stats" for f in report.findings)
    # the pragma'd designated sync point is suppressed, with its reason
    sup = [f for f in report.findings if f.suppressed is not None]
    assert [(f.qualname, f.suppressed) for f in sup] == [
        ("Engine._sync_oldest", "the designated completion check")]
    assert report.exit_code == 1


@pytest.mark.parametrize("method, found", [
    # an array made to describe a shape: through `self._jnp`'s local name,
    # through the module's `jnp`, through `jax.random`
    ("Paged._prefill_program", {("lookup.zeros", None), ("lookup.ones", None),
                                ("lookup.PRNGKey", None)}),
    # the decoy: jax.ShapeDtypeStruct, jnp.dtype and numpy describe, or stay
    # on the host
    ("Paged._decode_program", set()),
    ("Paged._restore_program", {
        ("lookup.zeros", "the fixture's designated array")}),
    # not a lookup: the engine's own state is made with jnp.zeros
    ("Paged._init_device_state", set()),
])
def test_a_program_lookup_that_makes_an_array_is_a_hotloop_finding(method,
                                                                   found):
    """Inside `@program_lookup` a call rooted at jax / jnp / self._jnp
    that makes an array waits out the device's queue (PR 36)."""
    report = _fixture_run("lookup", "hotloop")
    assert {(f.symbol, f.suppressed) for f in report.findings
            if f.qualname == method} == found
    assert report.exit_code == 1


# -- clock --------------------------------------------------------------------

def test_clock_fixture_flags_and_scope():
    report = _fixture_run("clock", "clock")
    assert _failing(report) == {
        ("deadline", "time.time"),
        ("aliased", "time()"),               # from-import alias
    }
    # fleet/ is out of scope; monotonic is never flagged
    assert not any("router" in f.file for f in report.findings)
    sup = [f for f in report.findings if f.suppressed is not None]
    assert [(f.qualname, f.suppressed) for f in sup] == [
        ("display_anchor", "display anchor for the fixture")]
    assert report.exit_code == 2


# -- oneengine ----------------------------------------------------------------

def test_oneengine_fixture_flags_forwards_and_bare_loops():
    """tpu/engine.py imports no model function but the ones excepted by
    name, and nothing outside tpu/paging.py constructs LLMEngine."""
    report = _fixture_run("oneengine", "oneengine")
    assert {(f.file, f.symbol) for f in report.failing
            if f.qualname == "<module>"} == {
        ("gofr_tpu/tpu/engine.py", "llama_decode_step"),
        ("gofr_tpu/tpu/engine.py", "init_kv_cache"),
        ("tools/soak.py", "LLMEngine("),
    }
    assert sum(f.file == "tools/soak.py" for f in report.failing) == 2
    # classes, params_nbytes and scoring's forward pass; paging.py may
    # construct the loop; a name that is not called is not a construction
    assert not any(f.symbol in ("LlamaConfig", "params_nbytes",
                                "llama_forward_nocache")
                   or f.file.endswith("paging.py") for f in report.findings)
    sup = [f for f in report.findings if f.suppressed is not None]
    assert [(f.symbol, f.suppressed) for f in sup] == [
        ("llama_prefill", "fixture pragma")]
    assert report.exit_code == 32


def test_oneengine_fixture_holds_the_loops_hooks():
    """What PagedLLMEngine replaces without super() is a bodiless hook in
    the loop, and every hook is filled."""
    report = _fixture_run("oneengine", "oneengine")
    assert {(f.qualname, f.message.split(":")[0].split(" that ")[0])
            for f in report.failing if f.qualname != "<module>"} == {
        ("LLMEngine._export_slot_kv", "a hook of the loop"),
        ("LLMEngine._admission_ready",
         "PagedLLMEngine replaces this method without super()"),
    }


# -- ownership ----------------------------------------------------------------

def test_ownership_fixture_flags_offloop_call_and_write():
    report = _fixture_run("ownership", "ownership")
    assert _failing(report) == {
        ("Engine.submit", "Ledger.bump"),        # call off-loop
        ("Ledger.reset_external", "self._acc"),  # owned-field write
    }
    # _loop and its callees (incl. the marked method itself) stayed quiet
    for quiet in ("Engine._loop", "Engine._drain", "Ledger.bump",
                  "Ledger.__init__"):
        assert not any(f.qualname == quiet for f in report.findings), quiet
    assert report.exit_code == 4


def test_loop_only_marker_is_zero_overhead():
    """The runtime half: @loop_only returns the function unwrapped (no
    call indirection), stamps the marker attributes, and registers the
    owned fields."""
    from gofr_tpu.tpu.ownership import (LOOP_ONLY_REGISTRY, is_loop_only,
                                        loop_only)

    @loop_only(fields=("_x",))
    def probe(self):
        return 41

    assert probe(None) == 41
    assert is_loop_only(probe)
    assert probe.__loop_owned_fields__ == ("_x",)
    key = f"{probe.__module__}.{probe.__qualname__}"
    assert LOOP_ONLY_REGISTRY[key] == ("_x",)
    # the real annotations registered at import time
    from gofr_tpu.tpu import stepledger  # noqa: F401
    assert any(k.endswith("StepLedger.step_start")
               for k in LOOP_ONLY_REGISTRY)


# -- lockorder ----------------------------------------------------------------

def test_lockorder_fixture_cycles_and_decoys():
    report = _fixture_run("lockorder", "lockorder")
    assert {f.symbol for f in report.failing} == {
        "cycle:AB._a<->AB._b",                   # via the call-graph closure
        "cycle:SelfNest._m->SelfNest._m",
    }
    # RLock reentry and the nested-def (foreign-thread) acquisition are ok
    assert not any("Reentrant" in f.symbol or "ThreadedProbe" in f.symbol
                   for f in report.findings)
    assert report.exit_code == 8


# -- surface ------------------------------------------------------------------

def test_surface_fixture_flags_each_inventory():
    report = _fixture_run("surface", "surface")
    assert {f.symbol for f in report.failing} == {
        "app_tpu_missing_total", "MISSING_KEY", "/debug/missing"}
    # the documented siblings stayed quiet
    assert not any(f.symbol in ("app_tpu_documented_total",
                                "DOCUMENTED_KEY", "/debug/documented")
                   for f in report.findings)
    assert report.exit_code == 16


def test_surface_extractors_on_real_tree():
    """The shared extractors (also consumed by test_utilization.py's
    runtime inventory gates) see the repo's real surfaces."""
    repo = os.path.join(os.path.dirname(__file__), "..")
    project = Project(repo)
    metrics = collect_metric_names(project)
    routes = collect_debug_routes(project)
    keys = collect_config_keys(project)
    assert "app_tpu_step_seconds" in metrics
    assert "/debug/engine" in routes
    assert any(k.startswith("TPU_") for k in keys)
    for inventory in (metrics, routes, keys):
        relpath, line = next(iter(inventory.values()))
        assert not os.path.isabs(relpath) and line >= 1


# -- pragma + baseline mechanics ---------------------------------------------

def test_bare_pragma_without_reason_suppresses_nothing(tmp_path):
    tree = tmp_path / "gofr_tpu" / "tpu"
    tree.mkdir(parents=True)
    (tree / "m.py").write_text(
        "import time\n\n"
        "def f():\n"
        "    return time.time()  # lint: clock-ok\n")
    report = runner.run(root=str(tmp_path), rules=["clock"],
                        baseline_path=None)
    assert len(report.failing) == 1
    assert report.failing[0].suppressed is None


def test_pragma_on_preceding_line_is_honored(tmp_path):
    tree = tmp_path / "gofr_tpu" / "tpu"
    tree.mkdir(parents=True)
    (tree / "m.py").write_text(
        "import time\n\n"
        "def f():\n"
        "    # lint: clock-ok reason on the line above\n"
        "    return time.time()\n")
    report = runner.run(root=str(tmp_path), rules=["clock"],
                        baseline_path=None)
    assert report.exit_code == 0
    assert report.findings[0].suppressed == "reason on the line above"


def test_baseline_is_honored_and_warns_on_stale(tmp_path):
    live = _fixture_run("clock", "clock")
    target = next(f for f in live.failing if f.qualname == "deadline")
    path = tmp_path / "baseline.json"
    baseline_mod.save({target.id: "grandfathered for the fixture",
                       "clock:gone.py:f:time.time:0": "stale entry"},
                      str(path))
    report = _fixture_run("clock", "clock", baseline=str(path))
    by_id = {f.id: f for f in report.findings}
    assert by_id[target.id].baselined == "grandfathered for the fixture"
    assert report.stale_baseline == ["clock:gone.py:f:time.time:0"]
    # the aliased finding is NOT baselined, so the rule still fails
    assert report.exit_code == 2


def test_baseline_entry_without_reason_is_a_load_error(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps(
        {"version": 1, "findings": {"clock:x.py:f:time.time:0": "  "}}))
    with pytest.raises(ValueError, match="without a justification"):
        baseline_mod.load(str(path))


def test_rule_exit_bits_compose():
    """Per-rule exit bits OR together, so CI output names the failing
    rules from the status alone."""
    from tools.analysis.passes import BITS
    assert BITS == {"hotloop": 1, "clock": 2, "ownership": 4,
                    "lockorder": 8, "surface": 16, "oneengine": 32}
    hot = _fixture_run("hotloop", "hotloop")
    clk = _fixture_run("clock", "clock")
    assert hot.exit_code | clk.exit_code == 3
