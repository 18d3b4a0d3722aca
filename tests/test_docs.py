"""The generated API reference stays in sync with the live docstrings
(role of reference docs/source/package_reference autodoc: the docs can't
describe code that no longer exists)."""

import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def test_api_reference_is_current():
    sys.path.insert(0, str(REPO / "docs"))
    try:
        import gen_api
    finally:
        sys.path.pop(0)
    pages = gen_api.generate()
    api_dir = REPO / "docs" / "api"
    stale = []
    for page, content in pages.items():
        on_disk = api_dir / f"{page}.md"
        if not on_disk.exists() or on_disk.read_text() != content:
            stale.append(page)
    assert not stale, (
        f"docs/api pages out of date: {stale} — run `python docs/gen_api.py`"
    )
    on_disk_pages = {p.stem for p in api_dir.glob("*.md")} - {"index"}
    assert on_disk_pages == set(pages), (
        f"orphaned/missing api pages: {on_disk_pages ^ set(pages)}"
    )


def test_rule_catalog_table_is_current():
    """The rule table in docs/static_analysis.md is generated from
    ``analysis.rules.RULES`` — registering a rule without regenerating
    (the GL110 hand-edit shape from PR 17) must fail here, not drift."""
    sys.path.insert(0, str(REPO / "docs"))
    try:
        import gen_api
    finally:
        sys.path.pop(0)
    on_disk = (REPO / "docs" / "static_analysis.md").read_text()
    assert gen_api.RULE_TABLE_BEGIN in on_disk and gen_api.RULE_TABLE_END in on_disk, (
        "rule-table markers missing from docs/static_analysis.md"
    )
    assert gen_api.inject_rule_table(on_disk) == on_disk, (
        "docs/static_analysis.md rule table out of date — run `python docs/gen_api.py`"
    )
    from accelerate_tpu.analysis.rules import RULES

    for rule_id in RULES:
        assert f"| {rule_id} |" in on_disk, f"{rule_id} missing from the rule table"


# ---------------------------------------------------------------------------
# basic-tutorials tier: the step-by-step pages must
# stay truthful — code blocks parse, referenced files/subcommands/links exist
# ---------------------------------------------------------------------------

import re

TUTORIALS = ["install.md", "first_launch.md", "notebook.md", "pod.md"]


def _blocks(page, lang):
    text = (REPO / "docs" / "tutorials" / page).read_text()
    return re.findall(rf"```{lang}\n(.*?)```", text, re.DOTALL)


def test_tutorial_pages_exist_and_are_linked():
    for page in TUTORIALS:
        assert (REPO / "docs" / "tutorials" / page).exists(), page
    readme = (REPO / "README.md").read_text()
    assert "tutorials" in readme, "README must point newcomers at docs/tutorials/"


def test_tutorial_python_blocks_compile():
    n = 0
    for page in TUTORIALS:
        for i, block in enumerate(_blocks(page, "python")):
            compile(block, f"{page}[{i}]", "exec")
            n += 1
    assert n >= 4


def test_tutorial_shell_blocks_use_real_subcommands_and_paths():
    import argparse

    from accelerate_tpu.commands.accelerate_cli import build_parser

    sub = next(a for a in build_parser()._actions
               if isinstance(a, argparse._SubParsersAction))
    known = set(sub.choices)
    for page in TUTORIALS:
        for block in _blocks(page, "bash"):
            for m in re.finditer(r"accelerate-tpu\s+([a-z-]+)", block):
                assert m.group(1) in known, f"{page}: unknown subcommand {m.group(1)}"
            for m in re.finditer(r"examples/config_templates/\S+\.yaml", block):
                assert (REPO / m.group(0)).exists(), f"{page}: missing {m.group(0)}"


def test_tutorial_internal_links_resolve():
    for page in TUTORIALS:
        text = (REPO / "docs" / "tutorials" / page).read_text()
        for m in re.finditer(r"\]\(([^)#]+\.md)\)", text):
            target = (REPO / "docs" / "tutorials" / m.group(1)).resolve()
            assert target.exists(), f"{page}: broken link {m.group(1)}"


def test_first_launch_script_actually_trains():
    """The tutorial's train.py is executed verbatim — a beginner's first
    contact must not be broken copy-paste."""
    from accelerate_tpu.state import AcceleratorState, GradientState

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    block = _blocks("first_launch.md", "python")[0]
    exec(compile(block, "first_launch.md", "exec"), {"__name__": "__tutorial__"})
