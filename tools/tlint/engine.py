"""tlint driver: walk files, run rules, apply suppressions + baseline.

Exit contract (the CI gate): 0 iff every violation is either inline-
suppressed with a reason or matched by a baseline entry, and no
suppression is missing its reason. Stale baseline entries (matching
nothing anymore) are warnings — they mean a deferred violation got
fixed and the entry should be deleted.

The run is two-pass: parse every file first, build the cross-file
:class:`~tools.tlint.callgraph.Project` (call graph, donation
signatures, fault-site registry, one-program index), then run the rules
— single-file rules get ``(ctx)``, rules marked ``needs_project`` get
``(ctx, project)``.
"""

from __future__ import annotations

import json
import sys
from dataclasses import dataclass, field
from pathlib import Path

from . import jaxrules, rules as _rules_mod
from .callgraph import Project
from .context import FileContext
from .rules import Violation

# the full rule table: thread rules (TL0xx) + JAX trace rules (TL1xx)
# tlint: disable=TL006(read-only rule table, never mutated after import)
RULES = {**_rules_mod.RULES, **jaxrules.JAX_RULES}

REPO_ROOT = Path(__file__).resolve().parent.parent.parent
DEFAULT_BASELINE = Path(__file__).resolve().parent / "baseline.json"

# tlint: disable=TL006(read-only constant table)
_SKIP_DIRS = {"__pycache__", ".git", "node_modules", ".venv"}


@dataclass
class Report:
    violations: list[Violation] = field(default_factory=list)  # actionable
    baselined: list[Violation] = field(default_factory=list)
    suppressed_count: int = 0
    bad_suppressions: list[tuple[str, int, str]] = field(default_factory=list)
    stale_baseline: list[dict] = field(default_factory=list)
    parse_errors: list[tuple[str, str]] = field(default_factory=list)
    files_checked: int = 0

    @property
    def failed(self) -> bool:
        return bool(self.violations or self.bad_suppressions)


def iter_py_files(paths: list[Path]):
    for p in paths:
        if p.is_file() and p.suffix == ".py":
            yield p
        elif p.is_dir():
            for f in sorted(p.rglob("*.py")):
                if not any(part in _SKIP_DIRS for part in f.parts):
                    yield f


def _relpath(path: Path) -> str:
    try:
        return path.resolve().relative_to(REPO_ROOT).as_posix()
    except ValueError:
        return path.as_posix()


def _rule_violations(
    ctx: FileContext, project: Project, rules: dict
) -> list[Violation]:
    out: list[Violation] = []
    for rule_fn in rules.values():
        if getattr(rule_fn, "needs_project", False):
            out.extend(rule_fn(ctx, project))
        else:
            out.extend(rule_fn(ctx))
    return out


def check_source(
    source: str, rel: str, rules: dict | None = None
) -> tuple[list[Violation], FileContext]:
    """Run the rules over one in-memory file (its own one-file project, so
    cross-file rules still work same-module). Returns violations that
    are NOT inline-suppressed (baseline is the caller's business) plus
    the context (for suppression bookkeeping). The unit the fixture
    tests drive."""
    ctx = FileContext.parse(rel, source)
    project = Project.build({rel: ctx})
    out = [
        v
        for v in _rule_violations(ctx, project, rules or RULES)
        if not ctx.suppressed(v.rule, v.line)
    ]
    out.sort(key=lambda v: (v.rel, v.line, v.col, v.rule))
    return out, ctx


def check_project(
    files: dict[str, str], rules: dict | None = None
) -> list[Violation]:
    """Run the rules over a dict of in-memory files ``{rel: source}`` —
    the multi-file unit the call-graph propagation tests drive. Inline
    suppressions apply; no baseline."""
    contexts = {rel: FileContext.parse(rel, src) for rel, src in files.items()}
    project = Project.build(contexts)
    out: list[Violation] = []
    for rel in sorted(contexts):
        ctx = contexts[rel]
        out.extend(
            v
            for v in _rule_violations(ctx, project, rules or RULES)
            if not ctx.suppressed(v.rule, v.line)
        )
    out.sort(key=lambda v: (v.rel, v.line, v.col, v.rule))
    return out


def load_baseline(path: Path) -> list[dict]:
    """Baseline entries: ``{rule, file, scope, symbol, reason}``. Every
    entry must carry a non-empty reason — the baseline is a record of
    DELIBERATELY deferred violations, not a mute button."""
    if not path.exists():
        return []
    data = json.loads(path.read_text())
    entries = data.get("violations", data if isinstance(data, list) else [])
    for e in entries:
        missing = [
            k for k in ("rule", "file", "scope", "symbol", "reason") if k not in e
        ]
        if missing:
            raise ValueError(
                f"baseline entry {e!r} is missing {', '.join(missing)}"
            )
        if not str(e["reason"]).strip():
            raise ValueError(f"baseline entry {e!r} has an empty reason")
    return entries


def _baseline_match(v: Violation, entries: list[dict]) -> dict | None:
    for e in entries:
        if (
            e["rule"] == v.rule
            and e["file"] == v.rel
            and e["scope"] == v.scope
            and e["symbol"] == v.symbol
        ):
            return e
    return None


def run(
    paths: list[Path],
    *,
    baseline_path: Path | None = DEFAULT_BASELINE,
    rules: dict | None = None,
) -> Report:
    rep = Report()
    entries = load_baseline(baseline_path) if baseline_path else []
    matched_entries: set[int] = set()
    contexts: dict[str, FileContext] = {}
    for f in iter_py_files(paths):
        rel = _relpath(f)
        if rel in contexts:
            continue
        try:
            contexts[rel] = FileContext.parse(rel, f.read_text())
        except (SyntaxError, UnicodeDecodeError, OSError) as e:
            rep.parse_errors.append((rel, str(e)))
    project = Project.build(contexts)
    for rel in sorted(contexts):
        ctx = contexts[rel]
        rep.files_checked += 1
        for v in _rule_violations(ctx, project, rules or RULES):
            if ctx.suppressed(v.rule, v.line):
                rep.suppressed_count += 1
                continue
            entry = _baseline_match(v, entries)
            if entry is not None:
                matched_entries.add(id(entry))
                rep.baselined.append(v)
                continue
            rep.violations.append(v)
        for sup in ctx.bad_suppressions:
            rep.bad_suppressions.append(
                (
                    rel,
                    sup.line,
                    f"suppression of {sup.rule} without a reason — write "
                    f"`# tlint: disable={sup.rule}(why this is safe)`",
                )
            )
    rep.stale_baseline = [e for e in entries if id(e) not in matched_entries]
    rep.violations.sort(key=lambda v: (v.rel, v.line, v.col, v.rule))
    return rep


def format_report(rep: Report, *, verbose: bool = False) -> str:
    lines: list[str] = []
    for rel, err in rep.parse_errors:
        lines.append(f"{rel}: parse error: {err}")
    for v in rep.violations:
        lines.append(f"{v.rel}:{v.line}:{v.col + 1}: {v.rule} {v.message}")
    for rel, line, msg in rep.bad_suppressions:
        lines.append(f"{rel}:{line}:1: TL000 {msg}")
    if verbose:
        for v in rep.baselined:
            lines.append(
                f"{v.rel}:{v.line}:{v.col + 1}: {v.rule} [baselined] "
                f"{v.message}"
            )
    for e in rep.stale_baseline:
        lines.append(
            f"warning: stale baseline entry {e['rule']} {e['file']} "
            f"{e['scope']} {e['symbol']} — the violation is gone; delete "
            "the entry"
        )
    n_bad = len(rep.violations) + len(rep.bad_suppressions)
    lines.append(
        f"tlint: {rep.files_checked} files, {n_bad} violation(s), "
        f"{len(rep.baselined)} baselined, {rep.suppressed_count} suppressed"
        + (f", {len(rep.stale_baseline)} stale baseline entr(ies)"
           if rep.stale_baseline else "")
    )
    return "\n".join(lines)


def _gh_data(s: str) -> str:
    """Escape a workflow-command message per GitHub's grammar."""
    return s.replace("%", "%25").replace("\r", "%0D").replace("\n", "%0A")


def _gh_prop(s: str) -> str:
    """Escape a workflow-command property value (also , and :)."""
    return _gh_data(s).replace(":", "%3A").replace(",", "%2C")


def format_report_github(rep: Report) -> str:
    """GitHub Actions ``::error`` annotations — one per finding, so they
    render inline on the PR diff — followed by the plain report (the
    annotation grammar swallows everything after ``::``, so the human-
    readable block stays separate)."""
    lines: list[str] = []
    for rel, err in rep.parse_errors:
        lines.append(
            f"::error file={_gh_prop(rel)},title=tlint parse error"
            f"::{_gh_data(err)}"
        )
    for v in rep.violations:
        lines.append(
            f"::error file={_gh_prop(v.rel)},line={v.line},col={v.col + 1},"
            f"title={_gh_prop(v.rule)}::{_gh_data(v.message)}"
        )
    for rel, line, msg in rep.bad_suppressions:
        lines.append(
            f"::error file={_gh_prop(rel)},line={line},title=TL000"
            f"::{_gh_data(msg)}"
        )
    lines.append(format_report(rep))
    return "\n".join(lines)


def write_baseline(rep: Report, path: Path) -> int:
    """Record every current actionable violation as a deferred baseline
    entry (reason = TODO placeholder the author must fill in — the
    loader rejects empty reasons, so a freshly written baseline fails
    until each entry is justified)."""
    seen = set()
    entries = []
    for v in rep.violations:
        k = v.key()
        if k in seen:
            continue
        seen.add(k)
        entries.append(
            {
                "rule": v.rule,
                "file": v.rel,
                "scope": v.scope,
                "symbol": v.symbol,
                "reason": "",
            }
        )
    path.write_text(json.dumps({"violations": entries}, indent=2) + "\n")
    return len(entries)


def main(argv: list[str] | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m tools.tlint",
        description="project-native static analysis "
        "(thread rules TL001-TL007, JAX trace rules TL101-TL106)",
    )
    ap.add_argument(
        "paths",
        nargs="*",
        default=["tensorlink_tpu", "tests", "tools"],
    )
    ap.add_argument(
        "--baseline",
        default=str(DEFAULT_BASELINE),
        help="baseline JSON of deferred violations",
    )
    ap.add_argument(
        "--no-baseline", action="store_true", help="ignore the baseline"
    )
    ap.add_argument(
        "--write-baseline",
        action="store_true",
        help="write current violations as baseline entries (reasons left "
        "empty for the author to fill in) and exit",
    )
    ap.add_argument(
        "--verbose", action="store_true", help="also print baselined hits"
    )
    ap.add_argument(
        "--select",
        default="",
        help="comma-separated rule codes to run (default: all)",
    )
    ap.add_argument(
        "--format",
        choices=("plain", "github"),
        default="plain",
        help="output format: plain (default) or GitHub Actions ::error "
        "annotations",
    )
    args = ap.parse_args(argv)

    rules = RULES
    if args.select:
        want = {r.strip().upper() for r in args.select.split(",") if r.strip()}
        unknown = want - set(RULES)
        if unknown:
            print(f"unknown rule(s): {', '.join(sorted(unknown))}")
            return 2
        rules = {k: v for k, v in RULES.items() if k in want}

    baseline = None if args.no_baseline else Path(args.baseline)
    if args.write_baseline:
        rep = run([Path(p) for p in args.paths], baseline_path=None, rules=rules)
        n = write_baseline(rep, Path(args.baseline))
        print(f"tlint: wrote {n} baseline entr(ies) to {args.baseline}")
        return 0
    try:
        rep = run(
            [Path(p) for p in args.paths], baseline_path=baseline, rules=rules
        )
    except ValueError as e:  # malformed baseline
        print(f"tlint: {e}")
        return 2
    if args.format == "github":
        print(format_report_github(rep))
    else:
        print(format_report(rep, verbose=args.verbose))
    return 1 if rep.failed else 0


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
