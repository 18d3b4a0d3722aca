"""graft-lint (accelerate_tpu/analysis): rule-by-rule coverage for both
engines, the planted-bug fixture pack (every planted bug flagged, every
corrected twin quiet), suppression semantics, the repo-wide zero-findings
gate, and the accelerator/CLI surfaces.  All CPU-only: the jaxpr auditor is
a pure abstract trace (``jax.jit(...).trace``) — nothing executes on
device."""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from accelerate_tpu.analysis import (
    RULES,
    Finding,
    Report,
    Severity,
    apply_suppressions,
    audit_fn,
    audit_jitted,
    lint_paths,
    lint_source,
    parse_marker,
)

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "analysis_fixtures"


def _load_fixture(name):
    spec = importlib.util.spec_from_file_location(name, FIXTURES / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rules_of(report_or_findings):
    findings = getattr(report_or_findings, "unsuppressed", None)
    findings = findings() if findings else report_or_findings
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# report model + suppression syntax
# ---------------------------------------------------------------------------


def test_severity_ordering_and_parse():
    assert Severity.ERROR > Severity.WARNING > Severity.INFO
    assert Severity.parse("warning") is Severity.WARNING
    assert Severity.parse(Severity.ERROR) is Severity.ERROR


def test_parse_marker_variants():
    rules, reason = parse_marker("x = 1  # graft-lint: disable=GL103 -- intentional host pin")
    assert rules == ("GL103",) and reason == "intentional host pin"
    rules, reason = parse_marker("# graft-lint: disable=GL101, GL104 -- twin hazards")
    assert rules == ("GL101", "GL104") and reason == "twin hazards"
    rules, reason = parse_marker("# graft-lint: disable=GL202")
    assert rules == ("GL202",) and reason is None
    assert parse_marker("# just a comment about graft-lint") is None


def test_suppression_same_line_and_line_above(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text(
        "a = 1  # graft-lint: disable=GL204 -- same-line\n"
        "# graft-lint: disable=GL202 -- line-above\n"
        "b = 2\n"
        "c = 3\n"
    )
    findings = [
        Finding("GL204", Severity.ERROR, "m", path=str(f), line=1),
        Finding("GL202", Severity.ERROR, "m", path=str(f), line=2),
        Finding("GL202", Severity.ERROR, "m", path=str(f), line=3),  # below marker
        Finding("GL202", Severity.ERROR, "m", path=str(f), line=4),  # out of reach
        Finding("GL204", Severity.ERROR, "m", path=str(f), line=3),  # wrong rule
    ]
    out = apply_suppressions(findings)
    assert [x.suppressed for x in out[:5]] == [True, True, True, False, False]
    assert out[0].suppress_reason == "same-line"


def test_suppression_continuation_line_normalizes_to_statement_start(tmp_path):
    """Regression: a jaxpr finding whose source_info points at a
    CONTINUATION line of a multi-line statement must still honor a marker
    anchored on the statement's FIRST line (or the line above it)."""
    f = tmp_path / "mod.py"
    f.write_text(
        "# graft-lint: disable=GL103 -- marker above the statement\n"
        "a = some_call(  # graft-lint: disable=GL104 -- marker on first line\n"
        "    one,\n"
        "    two,\n"
        ")\n"
        "b = other_call(\n"
        "    three,\n"
        ")\n"
    )
    out = apply_suppressions([
        # anchored at continuation lines 3/4 -> normalized to statement
        # start (line 2), where both markers are in reach
        Finding("GL104", Severity.ERROR, "m", path=str(f), line=3),
        Finding("GL103", Severity.ERROR, "m", path=str(f), line=4),
        # the second statement has no marker: normalization must not
        # borrow the first statement's markers
        Finding("GL104", Severity.ERROR, "m", path=str(f), line=7),
    ])
    assert [x.suppressed for x in out] == [True, True, False]
    assert out[0].suppress_reason == "marker on first line"
    assert out[1].suppress_reason == "marker above the statement"


def test_finding_and_report_json_round_trip():
    """to_json -> from_json -> to_json is the identity: same findings,
    same summary, identical re-render (the CI round-trip contract)."""
    rep = Report([
        Finding("GL104", Severity.ERROR, "e", fix_hint="h", path="a.py",
                line=3, engine="jaxpr"),
        Finding("GL402", Severity.WARNING, "w", engine="distributed"),
        Finding("GL103", Severity.WARNING, "s", suppressed=True,
                suppress_reason="why"),
    ])
    back = Report.from_json(rep.to_json())
    assert back.findings == rep.findings
    assert back.to_json() == rep.to_json()
    assert back.render(show_suppressed=True) == rep.render(show_suppressed=True)


def test_bare_suppression_marker_reported_as_gl001(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text("a = 1  # graft-lint: disable=GL204\n")
    out = apply_suppressions(
        [Finding("GL204", Severity.ERROR, "m", path=str(f), line=1)]
    )
    assert out[0].suppressed and out[0].suppress_reason is None
    gl001 = [x for x in out if x.rule == "GL001"]
    assert len(gl001) == 1 and gl001[0].severity == Severity.WARNING


def test_report_counts_exit_code_and_json():
    rep = Report([
        Finding("GL104", Severity.ERROR, "e"),
        Finding("GL102", Severity.WARNING, "w"),
        Finding("GL103", Severity.WARNING, "s", suppressed=True),
    ])
    assert rep.counts() == {"error": 1, "warning": 1, "info": 0, "suppressed": 1}
    assert rep.exit_code(Severity.ERROR) == 1
    assert Report([rep.findings[1]]).exit_code(Severity.ERROR) == 0
    assert Report([rep.findings[1]]).exit_code(Severity.WARNING) == 1
    payload = json.loads(rep.to_json())
    assert payload["summary"]["ok"] is False
    assert {f["rule"] for f in payload["findings"]} == {"GL104", "GL102", "GL103"}


def test_every_emitted_rule_is_in_the_catalog():
    # all three engines draw severities/hints from rules.RULES; ids must resolve
    for rule_id in ("GL001", "GL002", "GL101", "GL102", "GL103", "GL104",
                    "GL105", "GL106", "GL107", "GL108", "GL110", "GL201",
                    "GL202", "GL204", "GL205", "GL301", "GL302",
                    "GL303", "GL304", "GL305", "GL306", "GL401", "GL402",
                    "GL403", "GL404"):
        assert rule_id in RULES
        assert RULES[rule_id].summary and RULES[rule_id].fix_hint


# ---------------------------------------------------------------------------
# jaxpr auditor: rule-by-rule over the planted/clean fixture twins
# ---------------------------------------------------------------------------

_JAXPR_CASES = [
    ("wasted_donation_step", "GL101", {"donate_argnums": (0,)}),
    ("key_reuse_step", "GL104", {}),
    ("key_reuse_after_split_step", "GL104", {}),
    ("const_capture_step", "GL102", {}),
    ("transfer_in_trace_step", "GL103", {"default_memory_kind": "device"}),
    ("unsharded_output_step", "GL105", {}),
    ("collective_matmul_hint_step", "GL106", {}),
    ("collective_matmul_rs_hint_step", "GL107", {}),
    ("flat_dcn_reduce_step", "GL108", {}),
    ("unscaled_fp8_dot_step", "GL110", {}),
    ("fused_decode_unscaled_kv_step", "GL110", {}),
    ("fused_verify_unscaled_kv_step", "GL110", {}),
]


@pytest.mark.parametrize("fname,rule,kwargs", _JAXPR_CASES)
def test_jaxpr_planted_bug_is_flagged(fname, rule, kwargs):
    mod = _load_fixture("planted_jaxpr")
    rep = audit_fn(getattr(mod, fname), *mod.example_args()[fname], **kwargs)
    assert rule in _rules_of(rep), rep.render()
    assert all(f.rule in RULES for f in rep.findings)


@pytest.mark.parametrize("fname,rule,kwargs", _JAXPR_CASES)
def test_jaxpr_corrected_twin_is_quiet(fname, rule, kwargs):
    mod = _load_fixture("clean_jaxpr")
    rep = audit_fn(getattr(mod, fname), *mod.example_args()[fname], **kwargs)
    assert not rep.unsuppressed(), rep.render()


def test_jaxpr_audit_accepts_abstract_inputs():
    # ShapeDtypeStruct stand-ins: a 7B-shaped step audits without the memory
    def f(state, batch):
        return state * 0.9 + batch.mean(), (state * batch).sum()

    args = (jax.ShapeDtypeStruct((64, 64), jnp.float32),
            jax.ShapeDtypeStruct((64, 64), jnp.float32))
    assert not audit_fn(f, *args, donate_argnums=(0,)).unsuppressed()

    def wasteful(state, batch):
        return (state * batch).sum()

    assert "GL101" in _rules_of(audit_fn(wasteful, *args, donate_argnums=(0,)))


def test_jaxpr_suppression_resolves_through_source_info(tmp_path):
    # the same inline marker silences a finding discovered from the TRACE
    f = tmp_path / "traced_mod.py"
    f.write_text(
        "import jax\n"
        "def reuse(key, x):\n"
        "    a = jax.random.normal(key, x.shape)\n"
        "    # graft-lint: disable=GL104 -- fixture: correlated streams are the point here\n"
        "    b = jax.random.normal(key, x.shape)\n"
        "    return a + b\n"
    )
    spec = importlib.util.spec_from_file_location("traced_mod", f)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rep = audit_fn(mod.reuse, jax.random.key(0), jnp.ones((4,)))
    assert not rep.unsuppressed(), rep.render()
    assert any(x.rule == "GL104" and x.suppressed for x in rep.findings)


def test_gl107_hint_severity_matches_gl106():
    # GL107 is GL106's row-parallel mirror: same INFO severity, same
    # never-fails-a-run contract
    mod = _load_fixture("planted_jaxpr")
    fname = "collective_matmul_rs_hint_step"
    rep = audit_fn(getattr(mod, fname), *mod.example_args()[fname])
    hints = [f for f in rep.findings if f.rule == "GL107"]
    assert hints and all(f.severity == Severity.INFO for f in hints)
    assert rep.exit_code() == 0


def test_gl108_hint_severity_and_slab_hop_quiet():
    # GL108 is a hint like GL106/107: INFO severity, never fails a run —
    # and a psum over ('dcn',) ALONE (the hierarchical path's own slab hop)
    # must stay quiet even above the size threshold
    mod = _load_fixture("planted_jaxpr")
    fname = "flat_dcn_reduce_step"
    rep = audit_fn(getattr(mod, fname), *mod.example_args()[fname])
    hints = [f for f in rep.findings if f.rule == "GL108"]
    assert hints and all(f.severity == Severity.INFO for f in hints)
    assert rep.exit_code() == 0

    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    try:
        from jax import shard_map as _shard_map

        _no_check = {"check_vma": False}
    except ImportError:
        from jax.experimental.shard_map import shard_map as _shard_map

        _no_check = {"check_rep": False}

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("dcn", "dp_shard"))

    def dcn_only(gl):
        return jax.lax.psum(gl[0], ("dcn",))  # the slab hop itself

    fn = _shard_map(dcn_only, mesh=mesh, in_specs=P(("dcn", "dp_shard")),
                    out_specs=P("dp_shard", None), **_no_check)
    rep2 = audit_fn(fn, jax.ShapeDtypeStruct((4, 520, 520), jnp.float32))
    assert not [f for f in rep2.findings if f.rule == "GL108"], rep2.render()


def test_gl106_hint_severity_and_suppressible(tmp_path):
    # GL106 is a *hint*: info severity (never fails a run) and the same
    # source-anchored marker silences it at the all_gather's line
    mod = _load_fixture("planted_jaxpr")
    fname = "collective_matmul_hint_step"
    rep = audit_fn(getattr(mod, fname), *mod.example_args()[fname])
    hints = [f for f in rep.findings if f.rule == "GL106"]
    assert hints and all(f.severity == Severity.INFO for f in hints)
    assert rep.exit_code() == 0  # info never flips the exit code

    f = tmp_path / "ring_candidate.py"
    f.write_text(
        "import jax, numpy as np\n"
        "from jax.sharding import Mesh, PartitionSpec as P\n"
        "try:\n"
        "    from jax import shard_map as sm\n"
        "    NC = {'check_vma': False}\n"
        "except ImportError:\n"
        "    from jax.experimental.shard_map import shard_map as sm\n"
        "    NC = {'check_rep': False}\n"
        "def pipe(x, w):\n"
        "    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ('x',))\n"
        "    def body(xl, wl):\n"
        "        # graft-lint: disable=GL106 -- fixture: the monolithic pipe is the point here\n"
        "        full = jax.lax.all_gather(xl, 'x', axis=0, tiled=True)\n"
        "        return jax.lax.dot_general(full, wl, (((1,), (0,)), ((), ())))\n"
        "    return sm(body, mesh=mesh, in_specs=(P('x', None), P(None, None)),\n"
        "              out_specs=P(None, None), **NC)(x, w)\n"
    )
    spec = importlib.util.spec_from_file_location("ring_candidate", f)
    mod2 = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod2)
    rep2 = audit_fn(mod2.pipe, jnp.ones((8, 16)), jnp.ones((16, 4)))
    assert any(x.rule == "GL106" and x.suppressed for x in rep2.findings), rep2.render()
    assert not rep2.unsuppressed(), rep2.render()


def test_audit_jitted_rejects_non_jitted():
    with pytest.raises(TypeError):
        audit_jitted(lambda x: x, jnp.ones(()))


# ---------------------------------------------------------------------------
# AST engine: precise per-rule semantics on inline snippets
# ---------------------------------------------------------------------------


def test_ast_donated_reuse_flags_read_after_donating_call():
    src = (
        "import jax\n"
        "jitted = jax.jit(lambda s, b: s, donate_argnums=(0,))\n"
        "def train(state, batch):\n"
        "    new_state = jitted(state, batch)\n"
        "    return state.sum() + new_state\n"
    )
    findings = lint_source(src, "m.py")
    assert [(f.rule, f.line) for f in findings] == [("GL201", 5)]


def test_ast_donated_reuse_rebinding_is_safe():
    # the canonical loop shape: the result rebinds the donated name
    src = (
        "import jax\n"
        "jitted = jax.jit(lambda s, b: (s, 0.0), donate_argnums=(0,))\n"
        "def train(state, batches):\n"
        "    for b in batches:\n"
        "        state, metrics = jitted(state, b)\n"
        "    return state\n"
    )
    assert lint_source(src, "m.py") == []


def test_ast_donated_reuse_inline_jit_call():
    src = (
        "import jax\n"
        "def f(state, batch):\n"
        "    out = jax.jit(lambda s, b: s, donate_argnums=(0,))(state, batch)\n"
        "    return state, out\n"
    )
    assert "GL201" in _rules_of(lint_source(src, "m.py"))


def test_ast_host_sync_only_inside_jit_contexts():
    src = (
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return np.asarray(x).sum()\n"
        "def host_side(x):\n"
        "    return np.asarray(x).sum()\n"  # identical call, no jit: quiet
    )
    findings = lint_source(src, "m.py")
    assert [(f.rule, f.line) for f in findings] == [("GL202", 5)]


def test_ast_jit_context_propagates_through_calls_and_nesting():
    src = (
        "import jax, time\n"
        "def helper(x):\n"
        "    return x.item()\n"          # jitted transitively via step
        "def step(x):\n"
        "    def inner(y):\n"
        "        return time.time() + y\n"  # lexically nested in a context
        "    return helper(x) + inner(x)\n"
        "jitted = jax.jit(step)\n"
    )
    assert _rules_of(lint_source(src, "m.py")) == {"GL202", "GL204"}


def test_ast_float_only_flagged_on_traced_parameters():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def step(x, lr_config):\n"
        "    a = float(x)\n"       # parameter: traced -> flagged
        "    b = float('1e-3')\n"  # literal: quiet
        "    return a + b\n"
    )
    findings = lint_source(src, "m.py")
    assert [(f.rule, f.line) for f in findings] == [("GL202", 4)]


def test_ast_impure_in_jit_variants():
    src = (
        "import time, random\n"
        "import numpy as np\n"
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return x * time.perf_counter() + random.gauss(0, 1) + np.random.rand()\n"
    )
    findings = [f for f in lint_source(src, "m.py") if f.rule == "GL204"]
    assert len(findings) == 3


def test_ast_donated_reuse_augassign_is_not_a_safe_rebinding():
    # `state += 1` READS the donated buffer before writing it — the Store
    # ctx on the AugAssign target must not retire the hazard
    src = (
        "import jax\n"
        "jitted = jax.jit(lambda s, b: s, donate_argnums=(0,))\n"
        "def train(state, batch):\n"
        "    out = jitted(state, batch)\n"
        "    state += 1\n"
        "    return out\n"
    )
    findings = lint_source(src, "m.py")
    assert [(f.rule, f.line) for f in findings] == [("GL201", 5)]


def test_ast_empty_donate_argnums_donates_nothing():
    # explicit `donate_argnums=()` is fully literal: no GL201 false positive
    src = (
        "import jax\n"
        "jitted = jax.jit(lambda s, b: s, donate_argnums=())\n"
        "def train(state, batch):\n"
        "    out = jitted(state, batch)\n"
        "    return state, out\n"
    )
    assert lint_source(src, "m.py") == []


def test_stale_bare_marker_is_reported_and_not_doubled(tmp_path):
    # a bare marker matching NO finding still violates the GL001 contract
    stale = tmp_path / "stale.py"
    stale.write_text("x = 1  # graft-lint: disable=GL204\n")
    rep = lint_paths([stale])
    assert [(f.rule, f.line) for f in rep.unsuppressed()] == [("GL001", 1)]
    # and when a bare marker DOES suppress something, GL001 appears once
    both = tmp_path / "both.py"
    both.write_text(
        "import jax, time\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return x * time.time()  # graft-lint: disable=GL204\n"
    )
    rep2 = lint_paths([both])
    gl001 = [f for f in rep2.unsuppressed() if f.rule == "GL001"]
    assert len(gl001) == 1 and gl001[0].line == 4
    assert any(f.rule == "GL204" and f.suppressed for f in rep2.findings)


def test_ast_syntax_error_is_reported_as_engine_error():
    findings = lint_source("def f(:\n", "broken.py")
    assert findings and findings[0].rule == "GL002"
    assert findings[0].severity == Severity.ERROR


def test_lint_paths_reports_missing_explicit_target(tmp_path):
    # a typo'd CI path must fail the run, never report clean
    rep = lint_paths([tmp_path / "no_such_file.py"])
    assert _rules_of(rep) == {"GL002"}
    assert rep.exit_code(Severity.ERROR) == 1


def test_directory_sweeps_prune_vendored_dirs(tmp_path):
    (tmp_path / ".venv" / "lib").mkdir(parents=True)
    (tmp_path / ".venv" / "lib" / "vendored.py").write_text("import jax\n@jax.jit\ndef f(x):\n    return x.item()\n")
    (tmp_path / "mine.py").write_text("import jax\n@jax.jit\ndef f(x):\n    return x.item()\n")
    rep = lint_paths([tmp_path])
    assert [Path(f.path).name for f in rep.unsuppressed()] == ["mine.py"]


# ---------------------------------------------------------------------------
# the fixture pack: planted bugs flagged, corrected twins quiet
# ---------------------------------------------------------------------------


def test_fixture_donate_race_planted_vs_fixed():
    planted = lint_paths([FIXTURES / "planted_donate_race.py"], excludes=())
    assert _rules_of(planted) == {"GL201"}, planted.render()
    fixed = lint_paths([FIXTURES / "fixed_donate_race.py"], excludes=())
    assert not fixed.unsuppressed(), fixed.render()


def test_fixture_snapshot_race_planted_vs_clean():
    """GL206: donating a name an async_save=True initiator still holds is
    flagged; draining (wait_for_checkpoint) or rebinding first is quiet."""
    planted = lint_paths([FIXTURES / "planted_snapshot_race.py"], excludes=())
    assert _rules_of(planted) == {"GL206"}, planted.render()
    clean = lint_paths([FIXTURES / "clean_snapshot_race.py"], excludes=())
    assert not clean.unsuppressed(), clean.render()


def test_fixture_ast_planted_all_rules_fire():
    rep = lint_paths([FIXTURES / "planted_ast_rules.py"], excludes=())
    assert _rules_of(rep) == {"GL202", "GL204"}, rep.render()
    # every planted host-sync variant is individually caught
    gl202 = [f for f in rep.unsuppressed() if f.rule == "GL202"]
    assert len(gl202) == 4  # .item / np.asarray / float(param) / .tolist


def test_fixture_ast_clean_twins_quiet():
    rep = lint_paths([FIXTURES / "clean_ast_rules.py"], excludes=())
    assert not rep.unsuppressed(), rep.render()


def test_fixture_resilience_planted_gl205_fires():
    rep = lint_paths([FIXTURES / "planted_resilience.py"], excludes=())
    assert _rules_of(rep) == {"GL205"}, rep.render()
    findings = [f for f in rep.unsuppressed() if f.rule == "GL205"]
    # 3 non-atomic write variants (open-wb, json.dump, pickle.dump) + 1
    # swallowed-exception variant, each individually located
    assert len(findings) == 4, rep.render()
    assert sum("atomic publish" in f.message for f in findings) == 3
    assert sum("except Exception: pass" in f.message for f in findings) == 1


def test_fixture_resilience_clean_twin_quiet():
    rep = lint_paths([FIXTURES / "clean_resilience.py"], excludes=())
    assert not rep.unsuppressed(), rep.render()


def test_fixture_serving_planted_gl201_fires():
    """The serving-decode donated-cache reuse (the paged-pool flavor of the
    PR 2 async-ckpt race) is flagged at the AST level."""
    rep = lint_paths([FIXTURES / "planted_serving.py"], excludes=())
    assert "GL201" in _rules_of(rep), rep.render()


def test_fixture_serving_planted_gl101_wasted_pool_donation():
    """A serving step that donates the cache but returns only logits wastes
    the donation — the jaxpr auditor flags it, and the corrected twin
    (updated pool returned) is quiet."""
    planted = _load_fixture("planted_serving")
    args = planted.example_args()["decode_step_drops_pool"]
    rep = audit_fn(planted.decode_step_drops_pool, *args, donate_argnums=(0,))
    assert "GL101" in _rules_of(rep), rep.render()

    clean = _load_fixture("clean_serving")
    args = clean.example_args()["decode_step_drops_pool"]
    rep = audit_fn(clean.decode_step_drops_pool, *args, donate_argnums=(0,))
    assert not rep.unsuppressed(), rep.render()


def test_fixture_serving_clean_twin_quiet():
    rep = lint_paths([FIXTURES / "clean_serving.py"], excludes=())
    assert not rep.unsuppressed(), rep.render()


def test_fixture_lora_planted_gl305_adapter_count_trace():
    """A program keyed on the adapter-stack width re-specializes per tenant
    census — the AST recompile rule flags it; the clean twin (static pool
    width, id routing) stays quiet."""
    rep = lint_paths([FIXTURES / "planted_lora.py"], excludes=())
    assert "GL305" in _rules_of(rep), rep.render()


def test_fixture_lora_planted_gl101_dropped_pool_donation():
    """An adapter-pool insert that donates the stacks but returns only a
    scalar wastes the donation (the hot-swap analog of the dropped-KV-pool
    shape) — the jaxpr auditor flags it; the corrected twin (updated pool
    returned) is quiet."""
    planted = _load_fixture("planted_lora")
    args = planted.example_args()["insert_drops_pool"]
    rep = audit_fn(planted.insert_drops_pool, *args, donate_argnums=(0,))
    assert "GL101" in _rules_of(rep), rep.render()

    clean = _load_fixture("clean_lora")
    args = clean.example_args()["insert_drops_pool"]
    rep = audit_fn(clean.insert_drops_pool, *args, donate_argnums=(0,))
    assert not rep.unsuppressed(), rep.render()


def test_fixture_lora_clean_twin_quiet():
    rep = lint_paths([FIXTURES / "clean_lora.py"], excludes=())
    assert not rep.unsuppressed(), rep.render()


def test_fixture_speculate_planted_gl201_draft_verify_boundary():
    """The drafting layer reading the donated cache after the verify
    dispatch (the draft/verify boundary race) is flagged at the AST
    level."""
    rep = lint_paths([FIXTURES / "planted_speculate.py"], excludes=())
    assert "GL201" in _rules_of(rep), rep.render()


def test_fixture_speculate_planted_gl305_k_dependent_trace():
    """A verify program keyed on the drafts' width re-specializes per draft
    depth — the AST recompile rule flags it; the clean twin (static bucket
    from the fixed ladder) stays quiet."""
    rep = lint_paths([FIXTURES / "planted_speculate.py"], excludes=())
    assert "GL305" in _rules_of(rep), rep.render()


def test_fixture_speculate_clean_twin_quiet():
    rep = lint_paths([FIXTURES / "clean_speculate.py"], excludes=())
    assert not rep.unsuppressed(), rep.render()


def test_fixture_overload_planted_gl201_cancel_release_boundary():
    """The cancel path's reclaim accounting reading the donated cache after
    the release dispatch (the async-ckpt race across the cancel/release
    boundary) is flagged at the AST level."""
    rep = lint_paths([FIXTURES / "planted_overload.py"], excludes=())
    assert "GL201" in _rules_of(rep), rep.render()


def test_fixture_overload_planted_gl305_queue_length_trace():
    """A shed program keyed on the waiting line's live length re-specializes
    per queue depth — the AST recompile rule flags it; the clean twin
    (static ``max_queue`` bound) stays quiet."""
    rep = lint_paths([FIXTURES / "planted_overload.py"], excludes=())
    assert "GL305" in _rules_of(rep), rep.render()


def test_fixture_overload_clean_twin_quiet():
    rep = lint_paths([FIXTURES / "clean_overload.py"], excludes=())
    assert not rep.unsuppressed(), rep.render()


def test_fixture_prefix_planted_gl201_share_boundary():
    """Reading the donated block table back AFTER the adopt dispatch to
    build the COW release keep counts (the async-ckpt race applied across
    the share boundary) is flagged at the AST level."""
    rep = lint_paths([FIXTURES / "planted_prefix.py"], excludes=())
    assert "GL201" in _rules_of(rep), rep.render()


def test_fixture_prefix_planted_gl305_hit_length_trace():
    """An adopt program keyed on this admission's matched-prefix length
    re-specializes per hit depth — the AST recompile rule flags it; the
    clean twin (static pages_per_slot bound, hit length as a masked
    argument) stays quiet."""
    rep = lint_paths([FIXTURES / "planted_prefix.py"], excludes=())
    assert "GL305" in _rules_of(rep), rep.render()


def test_fixture_prefix_clean_twin_quiet():
    rep = lint_paths([FIXTURES / "clean_prefix.py"], excludes=())
    assert not rep.unsuppressed(), rep.render()


def test_gl205_one_hop_name_resolution_and_scope():
    # the live path reaches the write through a local assignment — still hit
    src = (
        "import os, pickle\n"
        "def save(step, tree):\n"
        "    d = 'runs/checkpoint_%d' % step\n"
        "    with open(d + '/w.bin', 'wb') as f:\n"
        "        f.write(tree)\n"
    )
    assert {f.rule for f in lint_source(src, "m.py")} == {"GL205"}
    # the tmp-stage + os.replace idiom retires it
    fixed = (
        "import os, pickle\n"
        "def save(step, tree):\n"
        "    d = 'runs/checkpoint_%d.tmp' % step\n"
        "    with open(d + '/w.bin', 'wb') as f:\n"
        "        f.write(tree)\n"
        "    os.replace(d, d[:-4])\n"
    )
    assert lint_source(fixed, "m.py") == []
    # a 2-argument str.replace path-mangle is NOT an atomic publish — only
    # the 1-argument Path.replace/rename form (or os.replace & co.) retires
    # the hazard
    str_replace = (
        "def save(step, data):\n"
        "    d = ('ckpts/checkpoint_%d' % step).replace('//', '/')\n"
        "    with open(d + '/w.bin', 'wb') as f:\n"
        "        f.write(data)\n"
    )
    assert {f.rule for f in lint_source(str_replace, "m.py")} == {"GL205"}
    # except-pass only fires on the resilience/checkpoint spine paths
    swallow = "try:\n    x = 1\nexcept Exception:\n    pass\n"
    assert lint_source(swallow, "some/module.py") == []
    assert {f.rule for f in lint_source(swallow, "pkg/checkpoint_utils.py")} == {"GL205"}


def test_fixture_telemetry_planted_gl109_fires():
    """Every planted timing-without-block shape is individually caught: the
    decorated jit, the `name = jax.jit(...)` binding, the inline
    `jax.jit(f)(x)` call, and the materialize-before-the-LAST-dispatch
    variant (the float() covers only the first call)."""
    rep = lint_paths([FIXTURES / "planted_telemetry.py"], excludes=())
    assert _rules_of(rep) == {"GL109"}, rep.render()
    hits = [f for f in rep.unsuppressed() if f.rule == "GL109"]
    assert len(hits) == 4, rep.render()
    # INFO hint: flags the delta line, never fails a run
    assert all(f.severity == Severity.INFO for f in hits)
    assert rep.exit_code() == 0


def test_fixture_telemetry_clean_twin_quiet():
    """The corrected twins (block_until_ready / float fetch / np.asarray
    before the closing clock read, plain host timing, jit outside the
    window) stay quiet — the timed-loop idiom passes clean."""
    rep = lint_paths([FIXTURES / "clean_telemetry.py"], excludes=())
    assert not rep.unsuppressed(), rep.render()


def test_gl109_suppressible_with_rationale(tmp_path):
    f = tmp_path / "timed.py"
    f.write_text(
        "import time\n"
        "import jax\n"
        "f = jax.jit(lambda x: x)\n"
        "def g(x):\n"
        "    t0 = time.perf_counter()\n"
        "    y = f(x)\n"
        "    # graft-lint: disable=GL109 -- fixture: dispatch latency is what this micro-bench measures\n"
        "    dt = time.perf_counter() - t0\n"
        "    return y, dt\n"
    )
    rep = lint_paths([f])
    assert not rep.unsuppressed(), rep.render()
    assert any(x.rule == "GL109" and x.suppressed for x in rep.findings)


def test_fixture_distributed_planted_gl401_schedule_divergence():
    """Two roles whose traced collective schedules reverse the rendezvous
    order: the comparator flags the first diverging index — the deadlock a
    launched gang would hit, caught before any process spawns."""
    from accelerate_tpu.analysis import audit_collective_schedules

    mod = _load_fixture("planted_distributed")
    findings = audit_collective_schedules(mod.gl401_schedules())
    assert _rules_of(findings) == {"GL401"}, findings
    assert "rendezvous 0" in findings[0].message
    assert findings[0].severity == Severity.ERROR


def test_fixture_distributed_planted_gl402_double_pin():
    """A ≥1 MiB activation pinned to one sharding and re-pinned to another:
    the predicted GSPMD reshard is flagged with its byte cost."""
    from accelerate_tpu.analysis import audit_resharding

    mod = _load_fixture("planted_distributed")
    (x,) = mod.example_args()["gl402_double_pin_step"]
    findings = audit_resharding(jax.jit(mod.gl402_double_pin_step).trace(x))
    assert _rules_of(findings) == {"GL402"}, findings
    assert "MiB" in findings[0].message


def test_fixture_distributed_planted_gl403_schema_mismatch():
    """int8-quantized prefill vs dense-bf16 decode: the schemas disagree on
    dtype, payload leaves, and bytes/page — the gate flags it AND the
    runtime (check_wire_schemas, the PagedKVTransport constructor's check)
    raises with the pinned historical phrasing."""
    from accelerate_tpu.analysis import audit_wire_schema, check_wire_schemas

    mod = _load_fixture("planted_distributed")
    src, dst = mod.gl403_schemas()
    findings = audit_wire_schema(src, dst)
    assert _rules_of(findings) == {"GL403"}, findings
    assert "kv_dtype" in findings[0].message
    with pytest.raises(ValueError, match="KV page dtypes must match"):
        check_wire_schemas(src, dst)


def test_fixture_distributed_planted_gl404_warmup_gap():
    """The decode role warms only the decode program but can be dispatched
    release + wire_recv — the statically-proven strict_compiles violation."""
    from accelerate_tpu.analysis import audit_warmup_coverage

    mod = _load_fixture("planted_distributed")
    findings = audit_warmup_coverage(*mod.gl404_coverage())
    assert _rules_of(findings) == {"GL404"}, findings
    assert "release" in findings[0].message and "wire_recv" in findings[0].message


def test_fixture_distributed_clean_twins_quiet():
    """Every corrected GL4xx twin is quiet: matched schedules, idempotent
    pins, identical schemas (check_wire_schemas passes), covering warmup."""
    from accelerate_tpu.analysis import (
        audit_collective_schedules,
        audit_resharding,
        audit_warmup_coverage,
        audit_wire_schema,
        check_wire_schemas,
    )

    mod = _load_fixture("clean_distributed")
    assert audit_collective_schedules(mod.gl401_schedules()) == []
    (x,) = mod.example_args()["gl402_double_pin_step"]
    assert audit_resharding(jax.jit(mod.gl402_double_pin_step).trace(x)) == []
    src, dst = mod.gl403_schemas()
    assert audit_wire_schema(src, dst) == []
    check_wire_schemas(src, dst)  # must not raise
    assert audit_warmup_coverage(*mod.gl404_coverage()) == []


def test_pair_preflight_matched_pair_clean_and_planted_mismatch_fires():
    """The full pair gate: a matched prefill/decode pair audits clean
    (schema_ok, symmetric wire legs, covered warmup on both roles); the
    same pair with a planted kv_dtype skew fires GL403.  Trace-only —
    nothing compiles."""
    from accelerate_tpu.analysis import pair_preflight
    from accelerate_tpu.models import LlamaConfig
    from accelerate_tpu.utils.dataclasses import ServingPlugin

    cfg = LlamaConfig.tiny()
    plugin = ServingPlugin(num_slots=4, page_size=4, pages_per_slot=16,
                           num_pages=40, prefill_chunk=32,
                           prefill_buckets=(16, 32), decode_kernel="native")
    findings, summary = pair_preflight(cfg, plugin, plugin)
    assert findings == [], findings
    assert summary["schema_ok"] and summary["wire_legs"]
    for role in ("prefill", "decode"):
        r = summary["roles"][role]
        assert set(r["dispatchable"]) <= set(r["warmed"]), r

    import dataclasses
    planted = dataclasses.replace(plugin, kv_dtype="fp8")
    findings, summary = pair_preflight(cfg, planted, plugin, trace_wire=False)
    assert "GL403" in _rules_of(findings), findings
    assert summary["schema_ok"] is False


def test_fixture_fleet_planted_router_pair_fires_gl401_and_gl403():
    """The fleet-router go-live gate: a role-mismatched replica pair
    (int8 prefill vs dense decode) routed through ``pair_preflight`` fires
    BOTH GL403 (schemas disagree) and GL401 (the handoff wire-leg
    schedules diverge — the scale legs exist on one side only).
    Trace-only — nothing compiles."""
    from accelerate_tpu.analysis import pair_preflight

    mod = _load_fixture("planted_fleet")
    findings, summary = pair_preflight(*mod.router_pair())
    rules = _rules_of(findings)
    assert {"GL401", "GL403"} <= rules, findings
    assert summary["schema_ok"] is False


def test_fixture_fleet_clean_router_pair_quiet():
    """The corrected twin: matched int8 wire schemas with per-role
    geometry freedom (slots/pages/chunk/buckets/speculation differ across
    the split) audits clean through the FULL gate, traced wire programs
    included."""
    from accelerate_tpu.analysis import pair_preflight

    mod = _load_fixture("clean_fleet")
    findings, summary = pair_preflight(*mod.router_pair())
    assert findings == [], findings
    assert summary["schema_ok"] and summary["wire_legs"]


def test_every_rule_has_planted_and_clean_fixture_twins():
    """The fixture meta-gate: every registered GLxxx rule id appears in at
    least one planted-fires fixture AND at least one clean-quiet twin under
    ``tests/analysis_fixtures/`` — a future rule can't land untested."""
    import re

    planted, clean = set(), set()
    for p in FIXTURES.glob("*.py"):
        ids = set(re.findall(r"\bGL\d{3}\b", p.read_text()))
        if p.name.startswith("planted_"):
            planted |= ids
        elif p.name.startswith(("clean_", "fixed_")):
            clean |= ids
    for rule_id in RULES:
        assert rule_id in planted, f"{rule_id} has no planted-fires fixture"
        assert rule_id in clean, f"{rule_id} has no clean-quiet fixture twin"


def test_fixture_meta_planted_gl001_and_gl002_fire():
    """The engine-discipline twins: a bare (rationale-less) marker that DOES
    suppress a finding fires GL001; an unparseable target fires GL002."""
    rep = lint_paths([FIXTURES / "planted_meta.py"], excludes=())
    assert _rules_of(rep) == {"GL001"}, rep.render()
    assert any(f.rule == "GL204" and f.suppressed for f in rep.findings)
    rep2 = lint_paths([FIXTURES / "planted_engine_error.py"], excludes=())
    assert _rules_of(rep2) == {"GL002"}, rep2.render()


def test_fixture_meta_clean_twin_quiet():
    rep = lint_paths([FIXTURES / "clean_meta.py"], excludes=())
    assert not rep.unsuppressed(), rep.render()


def test_fixtures_are_excluded_from_repo_sweeps_by_default():
    rep = lint_paths([FIXTURES])
    assert rep.findings == []


# ---------------------------------------------------------------------------
# the repo gate + the real hot spots
# ---------------------------------------------------------------------------


def test_repo_is_lint_clean():
    """The acceptance gate: zero unsuppressed findings over the whole tree
    (fixtures excluded — they are the planted bugs)."""
    rep = lint_paths([REPO])
    assert not rep.unsuppressed(), rep.render()


def test_canonical_train_step_audits_clean():
    # hot spot 1: the real prepare_train_step donation/pinning/RNG plumbing
    from accelerate_tpu.commands.lint import audit_canonical_step

    for optimizer in ("lion", "adamw-sr8"):
        rep = audit_canonical_step(optimizer)
        assert not rep.unsuppressed(), f"{optimizer}:\n{rep.render()}"
        from accelerate_tpu.state import AcceleratorState, GradientState
        AcceleratorState._reset_state(reset_partial_state=True)
        GradientState._reset_state()


def test_offloaded_pipelined_step_audits_clean_tpu_shaped(monkeypatch):
    """Hot spot 2 (ops/streaming.py pipeline inside the offloaded step),
    traced as the TPU traces it: the CPU backend keeps offloaded state in
    "device" memory (``host_offload_supported``), so its own trace holds no
    transfer at all.  The state is built on the CPU, then handed to the
    step as abstract leaves placed where the TPU places them (``host_plan``:
    params and optimizer state in ``pinned_host``) — tracing compiles
    nothing, so no backend has to accept the placement.  Every in-trace
    transfer must be an inline-suppressed intentional pipeline stage."""
    import accelerate_tpu.accelerator as accelerator_mod
    from accelerate_tpu import Accelerator
    from accelerate_tpu.parallel.sharding import host_plan
    from accelerate_tpu.utils.dataclasses import FullyShardedDataParallelPlugin

    plugin = FullyShardedDataParallelPlugin(
        cpu_offload=True, host_update_chunk_gib=1e-6, host_update_pipeline=True
    )
    acc = Accelerator(fsdp_plugin=plugin)
    params = {"w": jnp.zeros((16, 16)), "b": jnp.zeros((16,))}

    def loss_fn(p, batch):
        return jnp.mean((batch @ p["w"] + p["b"]) ** 2)

    state = acc.create_train_state(params, "lion-sr")
    monkeypatch.setattr(accelerator_mod, "host_offload_supported", lambda: True)
    plan = acc._state_sharding
    plan = plan.replace(params=host_plan(plan.params), opt_state=host_plan(plan.opt_state))
    acc._state_sharding = plan
    abstract_state = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), state, plan
    )
    step = acc.prepare_train_step(loss_fn)
    rep = audit_jitted(step, abstract_state, jax.ShapeDtypeStruct((8, 16), jnp.float32),
                       default_memory_kind="device")
    assert not rep.unsuppressed(), rep.render()
    suppressed = [f for f in rep.findings if f.suppressed]
    assert suppressed, "expected the intentional pipeline transfers to be visible-but-suppressed"
    assert {f.rule for f in suppressed} == {"GL103"}
    assert all(f.suppress_reason for f in suppressed)


def test_async_snapshot_copy_audits_clean():
    # hot spot 3: the PR 2 fix's snapshot primitive itself
    from accelerate_tpu.checkpointing import _sharded_copy_fn
    from accelerate_tpu.analysis import audit_traced

    arr = jnp.ones((8, 8))
    tr = _sharded_copy_fn(arr.sharding).trace(arr)
    rep = audit_traced(tr, default_memory_kind="device")
    assert not rep.unsuppressed(), rep.render()


# ---------------------------------------------------------------------------
# accelerator + CLI surfaces
# ---------------------------------------------------------------------------


def test_accelerator_audit_step_returns_report():
    from accelerate_tpu import Accelerator

    acc = Accelerator()
    params = {"w": jnp.zeros((4, 4))}

    def loss_fn(p, batch):
        return jnp.mean((batch @ p["w"]) ** 2)

    state = acc.create_train_state(params, "lion")
    step = acc.prepare_train_step(loss_fn)
    rep = acc.audit_step(step, state, jax.ShapeDtypeStruct((2, 4), jnp.float32),
                         log=False)
    assert isinstance(rep, Report) and not rep.unsuppressed()
    # default: audits the last prepared step
    rep2 = acc.audit_step(None, state, jax.ShapeDtypeStruct((2, 4), jnp.float32),
                          log=False)
    assert not rep2.unsuppressed()


def test_accelerate_lint_env_hook_audits_at_first_step(monkeypatch):
    from accelerate_tpu import Accelerator

    monkeypatch.setenv("ACCELERATE_LINT", "1")
    acc = Accelerator()
    params = {"w": jnp.zeros((4, 4))}

    def loss_fn(p, batch):
        return jnp.mean((batch @ p["w"]) ** 2)

    state = acc.create_train_state(params, "lion")
    step = acc.prepare_train_step(loss_fn)
    assert step._lint_report is None
    state, _ = step(state, jnp.ones((2, 4)))
    assert step._lint_report is not None
    assert step._lint_report.summary()["ok"] is True
    # the step still trains (the audit is trace-only)
    state, metrics = step(state, jnp.ones((2, 4)))
    assert jnp.isfinite(metrics["loss"])


def test_lint_cli_end_to_end():
    """The acceptance command: ``python -m accelerate_tpu lint`` exits 0 on
    the repo (AST sweep + canonical step audit)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "accelerate_tpu", "lint", "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=600, env=env,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    payload = json.loads(out.stdout)
    assert payload["summary"]["ok"] is True
    assert payload["summary"]["error"] == payload["summary"]["warning"] == 0


def test_lint_cli_fails_on_planted_bugs():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "accelerate_tpu", "lint", "--no-step-audit",
         str(FIXTURES / "planted_donate_race.py")],
        cwd=REPO, capture_output=True, text=True, timeout=300, env=env,
    )
    assert out.returncode == 1
    assert "GL201" in out.stdout
