"""Analyzer engine: file walking, pragma parsing, baseline, reporting.

The per-rule logic lives in ``tools/analyzer/rules/``; this module owns
everything rule-independent — which files are scanned, how findings are
suppressed (inline pragmas with mandatory reasons, per-rule path
allowlists with reasons, the checked-in baseline), and the human/JSON
output formats.
"""
from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "baseline.json")

# inline suppression: `# repro-analyze: disable=RULE1,RULE2 (reason)`.
# A pragma on a code line suppresses findings on that line; a pragma on
# a comment-only line suppresses findings on the next line. The reason
# is MANDATORY — a pragma without one is itself a finding (PRAGMA001).
_PRAGMA_RE = re.compile(
    r"#\s*repro-analyze:\s*(?P<kind>disable(?:-file)?)\s*=\s*"
    r"(?P<rules>[A-Z0-9,\s]+?)\s*(?:\((?P<reason>[^)]*)\))?\s*$")


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str  # repo-relative, forward slashes
    line: int  # 1-based
    col: int
    rule: str
    message: str
    hint: str = ""

    def fingerprint(self, line_text: str = "") -> str:
        """Baseline key: stable across pure line-shift edits (keyed on
        the stripped line text, not the line number)."""
        return f"{self.rule}::{self.path}::{line_text.strip()}"

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class AnalyzerConfig:
    """What to scan and which findings are pre-approved.

    ``allow`` maps rule id → ((path-prefix, reason), ...): findings for
    that rule under that path are suppressed, each carrying a written
    reason (surfaced by ``--show-allowlisted``). This is how the
    determinism pass distinguishes wall-clock *reporting* (launch
    drivers, benchmark timers) from wall-clock *behavior* (sim-clock /
    scheduling code, where DET002 still fires).
    """

    roots: Tuple[str, ...] = ("src", "benchmarks", "tests")
    # substrings: any file whose repo-relative path contains one is
    # skipped entirely (the fixture corpus is known-bad on purpose)
    exclude: Tuple[str, ...] = ("tests/analyzer_fixtures",)
    allow: Dict[str, Tuple[Tuple[str, str], ...]] = \
        dataclasses.field(default_factory=dict)


class FileContext:
    """Everything a rule needs about one source file."""

    def __init__(self, path: str, rel: str, source: str):
        self.path = path
        self.rel = rel
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""

    def finding(self, node: ast.AST, rule: str, message: str,
                hint: str = "") -> Finding:
        return Finding(self.rel, getattr(node, "lineno", 0),
                       getattr(node, "col_offset", 0), rule, message, hint)


# --------------------------------------------------------------------------
# pragmas
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Pragma:
    line: int  # line the pragma text sits on
    kind: str  # "disable" | "disable-file"
    rules: Tuple[str, ...]
    reason: str
    applies_to: int  # effective line for `disable` (same or next line)


def parse_pragmas(ctx: FileContext) -> Tuple[List[Pragma], List[Finding]]:
    """Extract pragmas + pragma-hygiene findings (missing reason /
    unknown rule id). Hygiene findings are themselves unsuppressable —
    a silent suppression is exactly what the pragma contract forbids."""
    from tools.analyzer.rules import ALL_RULE_IDS

    pragmas: List[Pragma] = []
    problems: List[Finding] = []
    for i, text in enumerate(ctx.lines, start=1):
        m = _PRAGMA_RE.search(text)
        if m is None:
            if "repro-analyze:" in text and not text.lstrip().startswith(
                    ("'", '"')):
                problems.append(Finding(
                    ctx.rel, i, 0, "PRAGMA003",
                    "malformed repro-analyze pragma",
                    "use `# repro-analyze: disable=RULE (reason)`"))
            continue
        rules = tuple(r.strip() for r in m.group("rules").split(",")
                      if r.strip())
        reason = (m.group("reason") or "").strip()
        code_before = text[:m.start()].strip()
        applies_to = i if code_before else i + 1
        if not reason:
            problems.append(Finding(
                ctx.rel, i, m.start(), "PRAGMA001",
                f"pragma disables {','.join(rules)} without a reason",
                "every suppression must say why: "
                "`# repro-analyze: disable=RULE (reason)`"))
        unknown = [r for r in rules if r not in ALL_RULE_IDS]
        if unknown:
            problems.append(Finding(
                ctx.rel, i, m.start(), "PRAGMA002",
                f"pragma names unknown rule id(s): {', '.join(unknown)}",
                f"known ids: {', '.join(sorted(ALL_RULE_IDS))}"))
        pragmas.append(Pragma(i, m.group("kind"), rules, reason, applies_to))
    return pragmas, problems


def _suppressed(f: Finding, pragmas: Sequence[Pragma]) -> bool:
    for p in pragmas:
        if not p.reason or f.rule not in p.rules:
            continue
        if p.kind == "disable-file" or p.applies_to == f.line:
            return True
    return False


# --------------------------------------------------------------------------
# baseline
# --------------------------------------------------------------------------


def load_baseline(path: str = BASELINE_PATH) -> List[str]:
    if not os.path.exists(path):
        return []
    with open(path) as fh:
        return list(json.load(fh))


def write_baseline(fingerprints: Iterable[str],
                   path: str = BASELINE_PATH) -> None:
    with open(path, "w") as fh:
        json.dump(sorted(set(fingerprints)), fh, indent=2)
        fh.write("\n")


# --------------------------------------------------------------------------
# scan
# --------------------------------------------------------------------------


def iter_files(cfg: AnalyzerConfig,
               repo_root: str = REPO_ROOT) -> Iterable[Tuple[str, str]]:
    for root in cfg.roots:
        base = os.path.join(repo_root, root)
        if os.path.isfile(base) and base.endswith(".py"):
            rel = os.path.relpath(base, repo_root).replace(os.sep, "/")
            if not any(x in rel for x in cfg.exclude):
                yield base, rel
            continue
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames.sort()
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", ".git")]
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                full = os.path.join(dirpath, fn)
                rel = os.path.relpath(full, repo_root).replace(os.sep, "/")
                if any(x in rel for x in cfg.exclude):
                    continue
                yield full, rel


@dataclasses.dataclass
class ScanResult:
    findings: List[Finding]  # actionable (not suppressed / allowlisted)
    suppressed: List[Tuple[Finding, str]]  # (finding, pragma reason)
    allowlisted: List[Tuple[Finding, str]]  # (finding, allowlist reason)
    files_scanned: int = 0
    line_texts: Dict[Tuple[str, int], str] = \
        dataclasses.field(default_factory=dict)

    def fingerprint_of(self, f: Finding) -> str:
        return f.fingerprint(self.line_texts.get((f.path, f.line), ""))

    def partition_baseline(self, baseline: Sequence[str]):
        """Split actionable findings into (new, baselined)."""
        base = set(baseline)
        new, old = [], []
        for f in self.findings:
            (old if self.fingerprint_of(f) in base else new).append(f)
        return new, old


def analyze_file(ctx: FileContext,
                 cfg: AnalyzerConfig) -> Tuple[List[Finding],
                                               List[Tuple[Finding, str]],
                                               List[Tuple[Finding, str]]]:
    from tools.analyzer.rules import run_all

    pragmas, pragma_problems = parse_pragmas(ctx)
    raw = run_all(ctx)
    active: List[Finding] = list(pragma_problems)
    suppressed: List[Tuple[Finding, str]] = []
    allowlisted: List[Tuple[Finding, str]] = []
    for f in raw:
        allow_hit = next(
            (reason for prefix, reason in cfg.allow.get(f.rule, ())
             if f.path.startswith(prefix)), None)
        if allow_hit is not None:
            allowlisted.append((f, allow_hit))
            continue
        if _suppressed(f, pragmas):
            reason = next(p.reason for p in pragmas
                          if p.reason and f.rule in p.rules
                          and (p.kind == "disable-file"
                               or p.applies_to == f.line))
            suppressed.append((f, reason))
            continue
        active.append(f)
    return active, suppressed, allowlisted


def analyze_paths(cfg: Optional[AnalyzerConfig] = None,
                  repo_root: str = REPO_ROOT) -> ScanResult:
    cfg = cfg or default_config()
    result = ScanResult([], [], [])
    for full, rel in iter_files(cfg, repo_root):
        with open(full, encoding="utf-8") as fh:
            source = fh.read()
        try:
            ctx = FileContext(full, rel, source)
        except SyntaxError as e:
            result.findings.append(Finding(
                rel, e.lineno or 0, e.offset or 0, "PARSE001",
                f"file does not parse: {e.msg}"))
            continue
        active, suppressed, allowlisted = analyze_file(ctx, cfg)
        result.findings.extend(active)
        result.suppressed.extend(suppressed)
        result.allowlisted.extend(allowlisted)
        for f in active:
            result.line_texts[(f.path, f.line)] = ctx.line_text(f.line)
        result.files_scanned += 1
    result.findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return result


def default_config() -> AnalyzerConfig:
    """The repo's shipped scan configuration, allowlist reasons included.

    DET002 (wall-clock) is allowlisted exactly where wall-clock time is
    *reporting* on real host/device work rather than *behavior* in
    simulated time: the launch drivers time real compiles and decodes,
    the training host loop logs real step rates, and benchmarks measure
    real dispatches, and core/tracing.py's host spans time real work for
    reporting only. The rest of the sim-clock code (core/, serving/,
    vector/) is NOT allowlisted — a wall-clock read there corrupts
    replayability and fires.
    """
    return AnalyzerConfig(allow={
        "DET002": (
            ("src/repro/launch/",
             "launch drivers time real lowering/compile/decode work — "
             "wall-clock reporting, never fed back into sim time"),
            ("src/repro/training/train_loop.py",
             "host training loop logs real s/step — reporting only, "
             "no simulated clock exists here"),
            ("benchmarks/",
             "benchmarks time real host/device work by design"),
            ("src/repro/serving/traffic.py",
             "generate_timed() times real host-side trace synthesis — "
             "wall-clock reporting on generator throughput, never fed "
             "into sim time (arrivals are stamped in sim seconds before "
             "the run starts)"),
            ("src/repro/core/tracing.py",
             "host spans time real host work for reporting (span totals "
             "beside the profiler's annotations) — no pool, scheduler or "
             "simulated clock reads them"),
        ),
    })


# --------------------------------------------------------------------------
# reporting
# --------------------------------------------------------------------------


def render_human(result: ScanResult, new: List[Finding],
                 baselined: List[Finding],
                 show_allowlisted: bool = False) -> str:
    out: List[str] = []
    for f in new:
        out.append(f"{f.path}:{f.line}:{f.col}: {f.rule} {f.message}")
        if f.hint:
            out.append(f"    hint: {f.hint}")
    if baselined:
        out.append(f"[baseline] {len(baselined)} known finding(s) "
                   "suppressed by tools/analyzer/baseline.json")
    if result.suppressed:
        out.append(f"[pragma] {len(result.suppressed)} finding(s) "
                   "suppressed inline, every one with a reason")
    if result.allowlisted:
        out.append(f"[allowlist] {len(result.allowlisted)} finding(s) "
                   "allowlisted by path")
        if show_allowlisted:
            for f, reason in result.allowlisted:
                out.append(f"    {f.path}:{f.line}: {f.rule} — {reason}")
    status = "FAIL" if new else "OK"
    out.append(f"repro-analyze: {status} — {len(new)} actionable, "
               f"{len(baselined)} baselined, "
               f"{len(result.suppressed)} pragma-suppressed, "
               f"{len(result.allowlisted)} allowlisted "
               f"({result.files_scanned} files)")
    return "\n".join(out)


def render_json(result: ScanResult, new: List[Finding],
                baselined: List[Finding]) -> str:
    return json.dumps({
        "actionable": [f.as_dict() for f in new],
        "baselined": [f.as_dict() for f in baselined],
        "suppressed": [
            {**f.as_dict(), "reason": r} for f, r in result.suppressed],
        "allowlisted": [
            {**f.as_dict(), "reason": r} for f, r in result.allowlisted],
        "files_scanned": result.files_scanned,
    }, indent=2)
