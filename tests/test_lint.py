"""sdlint CI gate + self-tests (tools/sdlint).

Three layers:

1. **The gate** — run every pass over the real package and fail the
   suite on any finding the checked-in baseline doesn't cover (and on
   baseline rot: entries without a justification, entries nothing hits).
   This is what makes the linter CI-enforced rather than advisory.
2. **Seeded fixtures** — each pass must FIRE on its violation tree under
   tests/lint_fixtures/ (a checker that never trips proves nothing).
3. **Concurrency/closure regressions** — pin the real lock graph
   (cross-subsystem edges, no cycles, known thread entrypoints) and the
   aggregate merge closure against the live runtime tables, so drift
   shows up as a named assertion, not a lint finding alone.

Everything except the runtime-closure test is pure ast — no engine
import, no jax dispatch.
"""

import json
import os
import subprocess
import sys

import pytest

import spark_druid_olap_tpu
from spark_druid_olap_tpu.tools.sdlint import PASSES
from spark_druid_olap_tpu.tools.sdlint.core import (Baseline, Project,
                                                    report_json, run_passes)
from spark_druid_olap_tpu.tools.sdlint.locks import LockAnalysis

PKG_ROOT = os.path.dirname(os.path.abspath(spark_druid_olap_tpu.__file__))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "lint_fixtures")
BASELINE = os.path.join(PKG_ROOT, "tools", "sdlint", "baseline.json")


def _fixture(name, passes):
    p = Project(os.path.join(FIXTURES, name), package="fixture")
    return run_passes(p, passes)


# -- 1. the CI gate -----------------------------------------------------------

def test_package_has_no_unbaselined_findings():
    findings = run_passes(Project(PKG_ROOT))
    baseline = Baseline.load(BASELINE)
    fresh = [f for f in findings if not baseline.matches(f)]
    assert not fresh, \
        "sdlint findings not covered by tools/sdlint/baseline.json " \
        "(fix them, or baseline WITH a justification):\n" \
        + "\n".join(f.render() for f in fresh)


def test_baseline_entries_are_justified_and_live():
    findings = run_passes(Project(PKG_ROOT))
    baseline = Baseline.load(BASELINE)
    unjust = baseline.missing_justifications()
    assert not unjust, f"baseline entries missing justification: {unjust}"
    stale = baseline.unmatched(findings)
    assert not stale, \
        f"stale baseline entries (nothing emits them any more — " \
        f"delete them): {stale}"


def test_cli_exit_codes():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    clean = subprocess.run(
        [sys.executable, "-m", "spark_druid_olap_tpu.tools.sdlint"],
        capture_output=True, text=True, env=env)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    dirty = subprocess.run(
        [sys.executable, "-m", "spark_druid_olap_tpu.tools.sdlint",
         "--root", os.path.join(FIXTURES, "deadlock"),
         "--package", "fixture", "--baseline", "none"],
        capture_output=True, text=True, env=env)
    assert dirty.returncode == 1, dirty.stdout + dirty.stderr
    assert "deadlock-cycle" in dirty.stdout


# -- 2. each pass fires on its seeded fixture ---------------------------------

def test_locks_pass_fires_on_deadlock_fixture():
    rules = {(f.rule, f.path) for f in _fixture("deadlock", ("locks",))}
    assert ("deadlock-cycle", "app.py") in rules
    assert ("unguarded-write", "app.py") in rules


def test_purity_pass_fires_on_impure_jit_fixture():
    found = _fixture("purity", ("purity",))
    rules = {f.rule for f in found}
    assert "traced-branch" in rules
    assert "host-call" in rules
    # the host calls are attributed to the jitted function itself
    assert any(f.symbol.startswith("bad_kernel") for f in found)
    # factory-returned pallas kernels are roots too: the violations in
    # ``_make_bad_wave``'s returned kernel fire even though the kernel
    # reaches pallas_call only through the factory's return value
    wave = [f for f in found
            if f.symbol.startswith("_make_bad_wave.wave_kernel")]
    assert {f.rule for f in wave} == {"traced-branch", "host-call"}, found
    # deep rooting: a functools.partial-wrapped factory-of-a-factory
    # product still resolves to the traced body two host layers down
    deep = [f for f in found
            if f.symbol.startswith("_make_deep._inner.deep_kernel")]
    assert {f.rule for f in deep} == {"traced-branch", "host-call"}, found


def test_contracts_pass_fires_on_undeclared_key_fixture():
    by_rule = {f.rule: f for f in _fixture("contracts", ("contracts",))}
    assert by_rule["undeclared-key"].symbol == "sdot.fixture.mystery"
    assert by_rule["unread-key"].symbol == "sdot.fixture.declared"


def test_contracts_pass_fires_on_phase_fixture():
    """The phase contract fires in all three directions: a timer call
    using a name the PHASES registry lacks, a registered name missing
    from the docs/STATS.md marker table, and a documented name nothing
    registers. Other passes stay quiet on the tree (liveness proof that
    the findings come from the contracts pass alone)."""
    by_rule = {f.rule: f for f in _fixture("phases", ("contracts",))}
    assert by_rule["unregistered-phase"].symbol == "rogue.phase"
    assert by_rule["unregistered-phase"].path == "engine.py"
    assert by_rule["undocumented-phase"].symbol == "ghost.phase"
    assert by_rule["stale-phase-doc"].symbol == "stale.phase"
    assert len(by_rule) == 3, by_rule
    others = tuple(p for p in PASSES if p != "contracts")
    assert not _fixture("phases", others)


def test_mergeclosure_pass_fires_on_unmergeable_agg_fixture():
    found = _fixture("mergeclosure", ("mergeclosure",))
    by_rule = {f.rule: f for f in found}
    assert by_rule["unmergeable-agg"].symbol == "median"
    assert by_rule["unregistered-agg"].symbol == "mode"
    # sketch-valued window agg with no declared register algebra:
    # unmergeable by contract, the cluster/mesh tiers have nothing to
    # verify their folds against
    assert by_rule["undeclared-sketch-merge"].symbol == "window_p95"
    # declared algebra drifts from the runtime dispatch table
    assert by_rule["sketch-merge-drift"].symbol == "quantile"
    assert "stale-registry" not in by_rule, found


def test_suppression_comment_silences_a_finding(tmp_path):
    # same violation as the contracts fixture, but disabled on the line
    (tmp_path / "engine.py").write_text(
        "class E:\n"
        "    def run(self, config):\n"
        "        return config.get('sdot.nope')"
        "  # sdlint: disable=contracts known probe key\n")
    found = run_passes(Project(str(tmp_path), package="fixture"),
                       ("contracts",))
    assert not found, [f.render() for f in found]


def test_keys_pass_fires_on_keys_fixture():
    found = _fixture("keys", ("keys",))
    by_rule = {}
    for f in found:
        by_rule.setdefault(f.rule, []).append(f)
    # four _cached_program call shapes resolve: the lambda build, the
    # loop-nested local ``def build`` (engine.py:30 / engine.py:35), the
    # pallas wave build reading a tiling key (engine.py:43), and the
    # signature whose common part a helper returns (engine.py:54)
    k1 = by_rule["compile-sig-missing-config"]
    assert {f.symbol for f in k1} == {
        "Engine.run:HLL_LOG2M",
        "Engine.run_wave:PALLAS_TILE_BYTES",
        "Engine.run_helper:HLL_LOG2M"}, found
    assert sorted(f.line for f in k1) == [30, 35, 43, 54], \
        [f.render() for f in k1]
    assert by_rule["key-missing-field"][0].symbol == \
        "normalize_spec:granularity"
    assert by_rule["key-field-never-read"][0].symbol == \
        "normalize_spec:legacy_hint"
    assert by_rule["fingerprint-missing-key"][0].symbol == "config:TZ_ID"
    assert by_rule["fingerprint-churn-key"][0].symbol == \
        "config:WLM_POLL_MS"
    assert by_rule["fingerprint-unfiltered"][0].symbol == \
        "Config.fingerprint"


def test_keys_pass_reads_a_signature_through_its_helper():
    """The program signatures take their common part from one helper
    (``QueryEngine._sig_base``). K1 reads what the helper returns: a key
    it folds is covered (the fixture's build reads TZ_ID, no finding), a
    key the build reads and the helper lacks is still found — and on the
    live tree a key dropped from the helper fails, by name, the
    signature of every tier whose build reads it (the hashed tier
    refuses sketches, so it never reads the KLL lane count)."""
    k1 = {f.symbol for f in _fixture("keys", ("keys",))
          if f.rule == "compile-sig-missing-config"}
    assert "Engine.run_helper:HLL_LOG2M" in k1
    assert "Engine.run_helper:TZ_ID" not in k1
    import ast
    from spark_druid_olap_tpu.tools.sdlint import keys as K
    proj = Project(PKG_ROOT)
    mod = proj.by_suffix("parallel/executor.py")
    helper = next(n for n in ast.walk(mod.tree)
                  if isinstance(n, ast.FunctionDef)
                  and n.name == "_sig_base")
    ret = next(n for n in ast.walk(helper) if isinstance(n, ast.Return))
    ret.value.elts = [e for e in ret.value.elts
                      if "QUANTILE_LANES" not in ast.dump(e)]
    dropped = {f.symbol for f in K.run(proj)
               if f.rule == "compile-sig-missing-config"}
    assert dropped == {"QueryEngine._run_agg:QUANTILE_LANES",
                       "SharedScanCoalescer._run_fused:QUANTILE_LANES"}, \
        dropped


def test_leaks_pass_fires_on_leaks_fixture():
    by_rule = {f.rule: f for f in _fixture("leaks", ("leaks",))}
    assert by_rule["unreleased-quota"].symbol == \
        "Admission.admit_quota:quota"
    assert by_rule["unreleased-lane-waiter"].symbol == \
        "Admission.admit_slot:lane-waiter"


def test_ordering_pass_fires_on_ordering_fixture():
    by_rule = {f.rule: f for f in _fixture("ordering", ("ordering",))}
    assert by_rule["rename-before-fsync"].symbol == \
        "publish_manifest:os.replace"
    assert by_rule["publish-not-durable"].symbol == \
        "publish_manifest:os.replace"
    assert by_rule["truncate-without-checkpoint"].symbol == \
        "compact:truncate_through"
    assert by_rule["register-before-wal-commit"].symbol == "ingest:register"
    assert by_rule["swap-before-truncate"].symbol == \
        "compact_swap:truncate_through"
    assert by_rule["dir-fsync-after-swap"].symbol == \
        "swap_generations:os.replace"
    assert by_rule["no-register-before-publish"].symbol == \
        "publish_compacted:register"
    # each seeded compaction-protocol function fires EXACTLY its own
    # rule — the three orderings differ only in statement order, so any
    # cross-fire means a rule's reachability predicate is too loose
    assert len(by_rule) == 7, sorted(by_rule)


def test_kernels_pass_fires_on_kernels_fixture():
    """Every kernel-contract rule fires on its seeded violation: the
    oversized scratch block, both planner-clamp drifts plus the config
    budget drift, both unpriced _prep_dtype widths, the unapplied int8
    promotion, the init-free accumulator kernel, the theta stripes the
    step-0 init never writes, the program_id-derived ref index, and the
    cumsum helper outside the probe's coverage."""
    found = _fixture("kernels", ("kernels",))
    got = {(f.rule, f.symbol) for f in found}
    assert got == {
        ("vmem-budget", "MAX_OUT_ROWS"),
        ("tile-clamp-mismatch", "plan_wave_tiles.min_rows"),
        ("tile-clamp-mismatch", "plan_wave_tiles.max_rows"),
        ("tile-clamp-mismatch", "sdot.pallas.wave.tile.bytes"),
        ("cost-floor-mismatch", "wave_tile_itemsize:1"),
        ("cost-floor-mismatch", "wave_tile_itemsize:4"),
        ("dtype-promotion-gap", "build_wave_fn.wave_fn:int8"),
        ("missing-stripe-init", "_make_kernel.kernel"),
        ("incomplete-identity-init", "build_wave_fn.kernel:theta_base"),
        ("dynamic-ref-index", "build_wave_fn.kernel:out_ref"),
        ("non-whitelisted-primitive", "_bucket_offsets:jnp.cumsum"),
    }, sorted(got)


def test_mesh_pass_fires_on_mesh_fixture():
    """Every SPMD replication-safety rule fires on its seeded
    violation: the undeclared "chips" axis (collective arg AND
    shard_map spec), the sum-merged HLL registers, the psum'd min
    branch, the jax.random / io_callback escapes inside the shard body,
    and both host-state writes (module dict + self attribute). The
    correctly pmin-merged theta sketch stays quiet."""
    found = _fixture("mesh", ("mesh",))
    got = {(f.rule, f.symbol) for f in found}
    assert got == {
        ("unknown-axis-name", "ShardedRunner.run.core:chips"),
        ("unknown-axis-name", "ShardedRunner.run:chips"),
        ("sketch-merge-mismatch", "hll.merge_registers"),
        ("merge-op-mismatch", "ShardedRunner.merge:min"),
        ("host-call-in-shard", "ShardedRunner.run.core:jax.random.PRNGKey"),
        ("host-call-in-shard",
         "ShardedRunner.run.core:jax.experimental.io_callback"),
        ("host-state-write-in-shard", "ShardedRunner.run.core:_STATS[...]"),
        ("host-state-write-in-shard", "ShardedRunner.run.core:self.last"),
    }, sorted(got)
    assert not any(f.path == "ops/theta.py" for f in found), found


def test_new_fixtures_are_quiet_when_their_pass_is_disabled():
    """Liveness proof: every finding on the seeded trees comes from the
    one pass under test — running the other eight passes yields nothing,
    so disabling the pass makes the seeded violations invisible."""
    for name in ("keys", "leaks", "ordering", "kernels", "mesh"):
        others = tuple(p for p in PASSES if p != name)
        found = _fixture(name, others)
        assert not found, (name, [f.render() for f in found])


def test_json_report_matches_golden():
    """--format json is a stable machine interface: schema-versioned,
    findings sorted, golden-pinned on the ordering fixture."""
    findings = _fixture("ordering", ("ordering",))
    doc = json.loads(report_json(findings, Baseline()))
    assert doc["schema_version"] == 2
    keys = [(f["pass_name"], f["path"], f["rule"], f["symbol"], f["line"])
            for f in doc["findings"]]
    assert keys == sorted(keys), keys
    with open(os.path.join(FIXTURES, "ordering", "golden.json")) as f:
        golden = json.load(f)
    assert doc == golden, json.dumps(doc, indent=2, sort_keys=True)


def test_mesh_json_report_matches_golden():
    """Same machine-interface pin for the newest pass: the mesh fixture
    findings render byte-identically to the checked-in golden."""
    findings = _fixture("mesh", ("mesh",))
    doc = json.loads(report_json(findings, Baseline()))
    assert doc["schema_version"] == 2
    with open(os.path.join(FIXTURES, "mesh", "golden.json")) as f:
        golden = json.load(f)
    assert doc == golden, json.dumps(doc, indent=2, sort_keys=True)


def test_shared_index_timing_and_perf_budget():
    """One parse + one Index serves all nine passes; the timing hook
    reports per-pass wall time and the whole run stays inside the CI
    budget (observed ~7s on this tree; 30s leaves slack for slow CI)."""
    timing = {}
    run_passes(Project(PKG_ROOT), timing=timing)
    assert set(timing) == {"index", *PASSES}, sorted(timing)
    total = sum(timing.values())
    assert total < 30.0, timing


def test_file_scoped_suppression(tmp_path):
    (tmp_path / "persist").mkdir()
    src = ("# sdlint: disable-file=ordering fixture copy, seeded on "
           "purpose\n"
           "import json\n"
           "import os\n\n\n"
           "def publish_manifest(root, doc):\n"
           "    tmp = os.path.join(root, 'manifest.json.tmp')\n"
           "    with open(tmp, 'w') as f:\n"
           "        json.dump(doc, f)\n"
           "    os.replace(tmp, os.path.join(root, 'manifest.json'))\n")
    (tmp_path / "persist" / "store.py").write_text(src)
    found = run_passes(Project(str(tmp_path), package="fixture"),
                       ("ordering",))
    assert not found, [f.render() for f in found]
    # ...but only within the first 10 lines: buried late it's inert
    buried = "\n" * 12 + src
    (tmp_path / "persist" / "store.py").write_text(buried)
    found = run_passes(Project(str(tmp_path), package="fixture"),
                       ("ordering",))
    assert found, "disable-file past line 10 must NOT suppress"


def test_def_suppression_covers_decorators_and_multiline_sigs(tmp_path):
    # the disable comment sits on the decorator line / the closing line
    # of a multi-line signature — both are part of the def header span
    (tmp_path / "engine.py").write_text(
        "def trace(f):\n"
        "    return f\n\n\n"
        "@trace  # sdlint: disable=contracts probe key, decorator form\n"
        "def probe_a(config):\n"
        "    return config.get('sdot.nope.a')\n\n\n"
        "def probe_b(\n"
        "    config,\n"
        "):  # sdlint: disable=contracts probe key, multi-line sig\n"
        "    return config.get('sdot.nope.b')\n")
    found = run_passes(Project(str(tmp_path), package="fixture"),
                       ("contracts",))
    assert not found, [f.render() for f in found]


def test_changed_files_fails_open_outside_git(tmp_path):
    from spark_druid_olap_tpu.tools.sdlint.__main__ import _changed_files
    assert _changed_files(str(tmp_path)) is None


def test_changed_only_filters_to_dirty_files(tmp_path):
    git = ["git", "-c", "user.email=a@b", "-c", "user.name=t"]
    root = tmp_path / "pkg"
    (root / "persist").mkdir(parents=True)
    bad = ("import json\nimport os\n\n\n"
           "def publish_manifest(root, doc):\n"
           "    tmp = os.path.join(root, 'manifest.json.tmp')\n"
           "    with open(tmp, 'w') as f:\n"
           "        json.dump(doc, f)\n"
           "    os.replace(tmp, os.path.join(root, 'manifest.json'))\n")
    (root / "persist" / "a.py").write_text(bad)
    (root / "persist" / "b.py").write_text(bad)
    try:
        subprocess.run(git + ["init", "-q"], cwd=tmp_path, check=True,
                       capture_output=True)
        subprocess.run(git + ["add", "-A"], cwd=tmp_path, check=True,
                       capture_output=True)
        subprocess.run(git + ["commit", "-q", "-m", "seed"], cwd=tmp_path,
                       check=True, capture_output=True)
    except (OSError, subprocess.CalledProcessError) as e:
        pytest.skip(f"git unavailable: {e}")
    (root / "persist" / "b.py").write_text(bad + "\n# dirty now\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "spark_druid_olap_tpu.tools.sdlint",
         "--root", str(root), "--package", "fixture", "--baseline", "none",
         "--changed-only", "--format", "json"],
        capture_output=True, text=True, env=env)
    assert out.returncode == 1, out.stdout + out.stderr
    doc = json.loads(out.stdout)
    paths = {f["path"] for f in doc["findings"]}
    assert paths == {"persist/b.py"}, doc["findings"]


# -- 3. regressions pinning the real findings this linter forced fixed --------

def test_live_tree_stays_clean_of_the_fixed_rules():
    """The first clean run surfaced two dozen–plus real findings, all
    FIXED in the runtime (none baselined): compile sigs missing
    sketch/route keys,
    WLM/persist operational keys churning ``Config.fingerprint``, the
    admission wait loop leaking its lane waiter on error, publish
    renames without directory fsync. Pin each family at zero so a
    reintroduction fails by name, not just via the generic gate."""
    findings = run_passes(Project(PKG_ROOT), ("keys", "leaks", "ordering"))
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f.render())
    for rule in ("compile-sig-missing-config", "fingerprint-churn-key",
                 "fingerprint-unfiltered", "unreleased-lane-waiter",
                 "unreleased-quota", "unclosed-wal-handle",
                 "publish-not-durable", "rename-before-fsync"):
        assert not by_rule.get(rule), by_rule[rule]


def test_kernel_and_mesh_invariants_stay_clean():
    """Pin the new pass families at zero on the live tree: the VMEM
    budget arithmetic closes (scratch + floor tile fits the configured
    clamp), every _prep_dtype promotion is applied at dispatch, both
    kernels identity-init every stripe they accumulate, kernel-reachable
    code stays inside the Mosaic-safe set, all collectives run over the
    declared segment axis, and the sketch merges match the register
    algebra AGG_CLOSURE declares. A reintroduction fails by rule name."""
    findings = run_passes(Project(PKG_ROOT), ("kernels", "mesh"))
    assert not findings, [f.render() for f in findings]


def test_registry_declares_sketch_merge_algebra():
    """The merge field is what the sketch-merge-mismatch rule checks
    ops/<sketch>.py:merge_registers against — it must stay declared and
    correct (HLL rho registers are maxima, theta k-min hashes minima,
    KLL survivor lanes lex-minima plus exact count sums) and must agree
    with the runtime dispatch table the device fold actually uses."""
    from spark_druid_olap_tpu.ops.agg_registry import AGG_CLOSURE
    from spark_druid_olap_tpu.ops.groupby import SKETCH_MERGE_OPS
    for kind, ent in AGG_CLOSURE.items():
        if ent.get("sketch"):
            assert ent.get("merge") in ("max", "min", "minsum"), kind
            assert SKETCH_MERGE_OPS[ent["sketch"]] == ent["merge"], kind
    assert AGG_CLOSURE["cardinality"]["merge"] == "max"
    assert AGG_CLOSURE["thetasketch"]["merge"] == "min"
    assert AGG_CLOSURE["quantile"]["merge"] == "minsum"


def test_fingerprint_excludes_operational_keys():
    """cache/wlm fix: result-neutral knobs (lane topology, quota family,
    fsync cadence) no longer churn the plan-cache fingerprint, while
    semantic keys and UNKNOWN keys still do (unknown fails toward
    correctness: an unregistered key busts the cache, never poisons)."""
    from spark_druid_olap_tpu.utils import config as C
    cfg = C.Config({
        C.TZ_ID.key: "America/New_York",
        C.WLM_LANES.key: "interactive:slots=1,queue=1",
        C.PERSIST_WAL_FSYNC.key: False,
        "sdot.wlm.quota.acme": "concurrent=1",
        "sdot.future.unknown": 1,
    })
    fp = dict(cfg.fingerprint())
    assert C.TZ_ID.key in fp
    assert "sdot.future.unknown" in fp
    assert C.WLM_LANES.key not in fp
    assert C.PERSIST_WAL_FSYNC.key not in fp
    assert "sdot.wlm.quota.acme" not in fp


def test_key_exempt_fields_is_declared_and_minimal():
    """cache/keys.py fix: the exec-metadata carve-out is an explicit,
    justified declaration the keys pass checks — not silence."""
    from spark_druid_olap_tpu.cache.keys import KEY_EXEMPT_FIELDS
    assert KEY_EXEMPT_FIELDS == ("context",)


def test_failed_snapshot_publish_leaves_no_temp_dir(tmp_path):
    """persist fix: an exception after the temp snapshot dir exists must
    remove it (unclosed-tmpdir) — a crashed publish can't strand
    .tmp-* dirs that a later publish would trip over."""
    from spark_druid_olap_tpu.persist import snapshot as SNAP

    class BoomDS:
        name = "boom"

        def require_complete(self, why):
            return None

        @property
        def num_rows(self):
            raise RuntimeError("boom")

    root = tmp_path / "boom"
    with pytest.raises(RuntimeError, match="boom"):
        SNAP.write_snapshot(str(root), BoomDS(), 1, 0)
    leftovers = sorted(os.listdir(root)) if root.exists() else []
    assert not [n for n in leftovers if n.startswith(".tmp-")], leftovers


# -- 4. concurrency / closure regressions over the real package ---------------

def _edge_present(edges, held_suffix, acq_suffix):
    return any(h.endswith(held_suffix) and a.endswith(acq_suffix)
               for (h, a) in edges)


def test_real_lock_graph_shape():
    """Pin the package's lock graph: the known cross-subsystem orderings
    must stay modeled (proof the analysis sees through the layers), and
    the graph must stay acyclic. The documented global lock order is
    WLM lane lock -> shared-scan group lock, and
    persist manager lock -> history lock; never the reverse."""
    la = LockAnalysis(Project(PKG_ROOT))
    assert len(la.lock_kinds) >= 10, sorted(la.lock_kinds)
    edges = set(la.edges)
    assert _edge_present(edges, "WorkloadManager._lock",
                         "SharedScanCoalescer._lock"), sorted(edges)
    assert _edge_present(edges, "PersistManager.lock",
                         "QueryHistory._lock"), sorted(edges)
    assert la.cycles == [], la.cycles
    ep_names = {fid[1].split(".")[-1] for fid in la.entrypoints}
    # coalescer/WLM/checkpointer bg loops, HTTP + Flight servers,
    # backend-loss probe: the threads the race pass guards against
    assert "_bg_loop" in ep_names, sorted(ep_names)
    assert "do_GET" in ep_names, sorted(ep_names)
    assert "do_get" in ep_names, sorted(ep_names)
    assert len(la.entrypoints) >= 6, sorted(la.entrypoints)


def test_agg_closure_matches_runtime_tables():
    """ops/agg_registry.py:AGG_CLOSURE is the declared merge closure;
    the executor's live _AGG_KIND table must agree exactly (the static
    pass checks the literal; this checks the imported runtime value,
    catching non-literal edits the ast reader can't see)."""
    from spark_druid_olap_tpu.ops.agg_registry import AGG_CLOSURE
    from spark_druid_olap_tpu.parallel.executor import _AGG_KIND
    assert set(AGG_CLOSURE) == set(_AGG_KIND)
    for kind, (route, np_dtype) in _AGG_KIND.items():
        ent = AGG_CLOSURE[kind]
        assert ent["route"] == route, kind
        assert ent["dtype"] == np_dtype.__name__, kind
