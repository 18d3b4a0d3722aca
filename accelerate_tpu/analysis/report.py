"""Finding/Report model shared by both graft-lint engines.

Both the jaxpr auditor (:mod:`.jaxpr_audit`) and the AST rule engine
(:mod:`.ast_rules`) reduce to the same output contract: a flat list of
:class:`Finding` records — rule id, severity, source location, message, fix
hint — collected into a :class:`Report` that renders for humans, serializes
to JSON for CI, and decides the process exit code.

Suppression is **source-anchored** for both engines: a finding whose
location carries a file path is suppressed by an inline marker

    # graft-lint: disable=GL103 -- moving host-resident members is the point

on the flagged line or the line directly above it.  The rationale after
``--`` is mandatory — a bare ``disable`` without one is itself reported
(GL001), so every suppression in the tree documents *why* the hazard is
intentional.  Jaxpr findings resolve their file/line from the equation's
``source_info``, so the same marker silences the same hazard whether it was
found syntactically or from the traced program.
"""

from __future__ import annotations

import dataclasses
import enum
import io
import json
import re
import tokenize
from pathlib import Path
from typing import Iterable, Optional


class Severity(enum.IntEnum):
    """Ordered so findings filter with a plain ``>=`` comparison."""

    INFO = 10
    WARNING = 20
    ERROR = 30

    @classmethod
    def parse(cls, name) -> "Severity":
        if isinstance(name, Severity):
            return name
        return cls[str(name).upper()]


@dataclasses.dataclass
class Finding:
    """One diagnostic from either engine.

    ``path``/``line`` locate the hazard (``path`` may be ``None`` for
    jaxpr findings whose equation has no user frame, e.g. synthetic
    programs built in a REPL); ``engine`` is ``"jaxpr"`` or ``"ast"``;
    ``suppressed``/``suppress_reason`` are filled in by
    :func:`apply_suppressions`.
    """

    rule: str
    severity: Severity
    message: str
    fix_hint: str = ""
    path: Optional[str] = None
    line: Optional[int] = None
    engine: str = "ast"
    suppressed: bool = False
    suppress_reason: Optional[str] = None

    @property
    def location(self) -> str:
        if self.path is None:
            return "<no source location>"
        return f"{self.path}:{self.line}" if self.line else self.path

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["severity"] = self.severity.name
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "Finding":
        """Inverse of :meth:`to_dict` — ``from_dict(f.to_dict()) == f``,
        so a JSON report round-trips losslessly (the CI contract)."""
        d = dict(d)
        d["severity"] = Severity.parse(d["severity"])
        return cls(**d)


# ``# graft-lint: disable=GL101 -- why this is fine`` (one or more comma-
# separated rule ids; the rationale after ``--`` is what keeps suppressions
# honest).  Matches anywhere in the line so it can trail code.
_MARKER = re.compile(
    r"#\s*graft-lint:\s*disable=(?P<rules>GL\d+(?:\s*,\s*GL\d+)*)"
    r"(?:\s*--\s*(?P<reason>\S.*?))?\s*$"
)


def parse_marker(line: str):
    """``(rule_ids, rationale)`` of the suppression marker on ``line``, or
    ``None``.  ``rationale`` is ``None`` when the marker omits it (a GL001
    finding at the call-site of :func:`apply_suppressions`)."""
    m = _MARKER.search(line)
    if m is None:
        return None
    rules = tuple(r.strip() for r in m.group("rules").split(","))
    return rules, m.group("reason")


def _markers_for_file(path: str, _cache: dict) -> dict:
    """line number -> (rule ids, rationale) for every marker in ``path``."""
    if path in _cache:
        return _cache[path]
    markers: dict = {}
    try:
        lines = Path(path).read_text().splitlines()
    except OSError:
        _cache[path] = markers
        return markers
    for lineno, text in enumerate(lines, start=1):
        parsed = parse_marker(text)
        if parsed is not None:
            markers[lineno] = parsed
    _cache[path] = markers
    return markers


def _stmt_starts_for_file(path: str, _cache: dict) -> dict:
    """line number -> first line of the logical statement it belongs to,
    for every line of a multi-line statement in ``path``.

    Jaxpr findings anchor at the equation's ``source_info`` line, which for
    a statement wrapped across several lines can be a CONTINUATION line —
    while the author's suppression marker naturally sits on (or above) the
    statement's FIRST line.  This map lets :func:`apply_suppressions`
    normalize the finding back to the statement start so the marker is
    honored.  Tokenize-based: ``NEWLINE`` tokens terminate logical lines,
    ``NL`` tokens (blank/continuation breaks) do not."""
    if path in _cache:
        return _cache[path]
    mapping: dict = {}
    try:
        text = Path(path).read_text()
    except OSError:
        _cache[path] = mapping
        return mapping
    skip = (tokenize.NL, tokenize.COMMENT, tokenize.INDENT, tokenize.DEDENT,
            tokenize.ENDMARKER)
    try:
        start = None
        for tok in tokenize.generate_tokens(io.StringIO(text).readline):
            if tok.type in skip:
                continue
            if tok.type == tokenize.NEWLINE:
                start = None
                continue
            if start is None:
                start = tok.start[0]
            for lineno in range(tok.start[0], tok.end[0] + 1):
                mapping.setdefault(lineno, start)
    except (tokenize.TokenError, SyntaxError, IndentationError):
        pass  # an unparseable file falls back to exact-line matching
    _cache[path] = mapping
    return mapping


def apply_suppressions(findings: Iterable[Finding]) -> list[Finding]:
    """Resolve inline markers: mark matching findings suppressed, and emit a
    GL001 finding for every marker that omits its rationale.  A marker
    suppresses findings on its own line and the line below (so it can sit
    above a long expression).  A finding anchored on a CONTINUATION line of
    a multi-line statement is normalized to the statement's first line, so
    a marker there (or directly above) still suppresses it."""
    findings = list(findings)
    cache: dict = {}
    stmt_cache: dict = {}
    bare_marker_sites: set = set()
    for f in findings:
        if f.path is None or f.line is None:
            continue
        markers = _markers_for_file(f.path, cache)
        candidates = [f.line, f.line - 1]
        stmt_start = _stmt_starts_for_file(f.path, stmt_cache).get(f.line)
        if stmt_start is not None and stmt_start != f.line:
            candidates += [stmt_start, stmt_start - 1]
        for lineno in candidates:
            entry = markers.get(lineno)
            if entry is None:
                continue
            rules, reason = entry
            if f.rule in rules:
                f.suppressed = True
                f.suppress_reason = reason
                if reason is None:
                    bare_marker_sites.add((f.path, lineno))
                break
    out = findings
    already = {(f.path, f.line) for f in findings if f.rule == "GL001"}
    for path, lineno in sorted(bare_marker_sites - already):
        out.append(
            Finding(
                rule="GL001",
                severity=Severity.WARNING,
                message="suppression marker without a rationale "
                        "(add `-- <why this hazard is intentional>`)",
                fix_hint="graft-lint: disable=GLxxx -- <reason>",
                path=path,
                line=lineno,
                engine="ast",
            )
        )
    return out


class Report:
    """Ordered collection of findings with the CI-facing reductions."""

    def __init__(self, findings: Iterable[Finding] = ()):
        self.findings: list[Finding] = list(findings)

    def add(self, finding: Finding) -> None:
        self.findings.append(finding)

    def extend(self, findings: Iterable[Finding]) -> None:
        self.findings.extend(findings)

    def unsuppressed(self, min_severity: Severity = Severity.INFO) -> list[Finding]:
        min_severity = Severity.parse(min_severity)
        return [
            f for f in self.findings
            if not f.suppressed and f.severity >= min_severity
        ]

    def counts(self) -> dict:
        c = {"error": 0, "warning": 0, "info": 0, "suppressed": 0}
        for f in self.findings:
            if f.suppressed:
                c["suppressed"] += 1
            else:
                c[f.severity.name.lower()] += 1
        return c

    def summary(self) -> dict:
        """Compact JSON-able digest (what trackers embed)."""
        return {
            **self.counts(),
            "rules": sorted({f.rule for f in self.findings if not f.suppressed}),
            "ok": not self.unsuppressed(Severity.ERROR),
        }

    def exit_code(self, fail_on: Severity = Severity.ERROR) -> int:
        return 1 if self.unsuppressed(Severity.parse(fail_on)) else 0

    def render(self, *, show_suppressed: bool = False) -> str:
        lines = []
        for f in sorted(
            self.findings,
            key=lambda f: (-int(f.severity), f.path or "~", f.line or 0),
        ):
            if f.suppressed and not show_suppressed:
                continue
            tag = f"suppressed:{f.severity.name}" if f.suppressed else f.severity.name
            lines.append(f"{f.location}: {tag} {f.rule} [{f.engine}] {f.message}")
            if f.fix_hint and not f.suppressed:
                lines.append(f"    hint: {f.fix_hint}")
            if f.suppressed and f.suppress_reason:
                lines.append(f"    rationale: {f.suppress_reason}")
        c = self.counts()
        lines.append(
            f"graft-lint: {c['error']} error(s), {c['warning']} warning(s), "
            f"{c['info']} info, {c['suppressed']} suppressed"
        )
        return "\n".join(lines)

    def to_json(self) -> str:
        return json.dumps(
            {"findings": [f.to_dict() for f in self.findings], "summary": self.summary()},
            indent=2,
        )

    @classmethod
    def from_json(cls, text: str) -> "Report":
        """Inverse of :meth:`to_json`: a serialized report reloads into an
        equal Report — same findings, same summary, identical re-render
        (the ``make lint`` / preflight-CLI round-trip check)."""
        payload = json.loads(text)
        return cls(Finding.from_dict(d) for d in payload.get("findings", ()))
