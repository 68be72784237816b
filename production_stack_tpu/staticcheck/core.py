"""Staticcheck core: findings, projects, rules, waivers.

The stack's load-bearing invariants — zero per-step recompiles, no
host sync on the dispatch path, engine metrics round-tripping through
the router, mutually-exclusive feature combos rejected at config time
— are cheap to state over the AST and expensive (or impossible) to
cover with runtime tests. PRs 1-4 each hand-rolled a one-off AST lint;
this package is the shared framework they migrate into, so every new
invariant is ~one analyzer module instead of another bespoke walker.

Pieces:

- ``Finding``: one violation, with a line-number-independent
  fingerprint so the baseline survives unrelated edits.
- ``Project``: the file universe a run sees. ``Project.from_root``
  reads the repo; ``Project.from_sources`` builds a synthetic tree so
  tests can plant violations without touching disk.
- ``@rule(...)``: registers an analyzer. An analyzer is a function
  ``(project) -> list[Finding]``; per-file vs cross-file is its own
  business.
- Waivers: a ``# lint: allow-<rule>`` comment on the flagged line
  suppresses that rule there. Unknown rule names in a waiver are
  themselves findings (rule ``unknown-waiver``) so a typo fails
  loudly instead of silently disabling the check.
- Baseline (baseline.py): legacy findings checked in by fingerprint;
  only findings outside the baseline fail the CLI.

See docs/static_analysis.md for the rule catalog and how to add one.
"""

from __future__ import annotations

import ast
import concurrent.futures
import dataclasses
import datetime
import hashlib
import pathlib
import re
import threading
from typing import Callable, Dict, Iterable, List, Optional, Tuple

_WAIVER_RE = re.compile(
    r"#\s*lint:\s*allow-([A-Za-z0-9_-]+)(?:\s+until=([^\s#]+))?")
_DATE_RE = re.compile(r"^\d{4}-\d{2}-\d{2}$")

# Rendered call chains are capped at this many frames; the tail is
# summarized as "… (+N frames)" so --json and SARIF stay bounded and
# byte-stable no matter how deep the interprocedural path goes.
CHAIN_CAP = 6


def cap_frames(frames: Iterable[Tuple[str, int, str]]
               ) -> Tuple[Tuple[Tuple[str, int, str], ...], int]:
    """(first CHAIN_CAP frames, count of dropped frames)."""
    frames = tuple(tuple(f) for f in frames)
    if len(frames) <= CHAIN_CAP:
        return frames, 0
    return frames[:CHAIN_CAP], len(frames) - CHAIN_CAP


def render_chain(frames: Iterable[Tuple[str, int, str]]) -> str:
    """``a → b → c … (+N frames)`` — labels only, capped."""
    kept, dropped = cap_frames(frames)
    text = " → ".join(label for _p, _l, label in kept)
    if dropped:
        text += f" … (+{dropped} frames)"
    return text


@dataclasses.dataclass
class Finding:
    """One rule violation at (path, line).

    ``chain`` is the interprocedural call path behind the finding
    (empty for intraprocedural findings): up to ``CHAIN_CAP``
    ``(path, line, label)`` frames, already capped by the creating
    analyzer via :func:`cap_frames`, with the overflow count in
    ``chain_dropped``. The chain is deliberately **excluded** from the
    fingerprint — renaming a mid-chain helper must not churn the
    baseline for a finding whose flagged line did not change."""

    rule: str
    path: str  # repo-relative, posix separators
    line: int  # 1-based; 0 for file/project-level contract findings
    message: str
    snippet: str = ""
    chain: Tuple[Tuple[str, int, str], ...] = ()
    chain_dropped: int = 0

    def fingerprint(self) -> str:
        """Stable identity for baseline matching: rule + path + the
        normalized flagged source line (or the message for contract
        findings with no line). Deliberately excludes the line number
        so unrelated edits above a legacy finding don't make it
        'new'."""
        basis = self.snippet.strip() or self.message
        digest = hashlib.sha1(
            f"{self.rule}|{self.path}|{basis}".encode()).hexdigest()
        return digest[:12]

    def to_json(self) -> dict:
        out = {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "message": self.message,
            "snippet": self.snippet,
            "fingerprint": self.fingerprint(),
        }
        if self.chain:
            out["chain"] = [
                {"path": p, "line": line, "label": label}
                for p, line, label in self.chain]
            if self.chain_dropped:
                out["chain_dropped"] = self.chain_dropped
        return out

    def render(self) -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        out = f"{loc}: [{self.rule}] {self.message}"
        if self.snippet.strip():
            out += f"\n    {self.snippet.strip()}"
        return out


class SourceFile:
    """One parsed file: text, lines, AST, waiver comments."""

    def __init__(self, relpath: str, text: str):
        self.relpath = relpath
        self.text = text
        self.lines = text.splitlines()
        self._tree: Optional[ast.Module] = None
        self._parse_error: Optional[str] = None
        self._waivers: Optional[Dict[int, set]] = None
        self._waiver_expiries: Optional[Dict[int, Dict[str, str]]] = \
            None

    @property
    def tree(self) -> Optional[ast.Module]:
        if self._tree is None and self._parse_error is None:
            try:
                self._tree = ast.parse(self.text, filename=self.relpath)
            except SyntaxError as e:  # surfaced by run_rules
                self._parse_error = str(e)
        return self._tree

    @property
    def parse_error(self) -> Optional[str]:
        self.tree  # noqa: B018 - force the parse attempt
        return self._parse_error

    @property
    def waivers(self) -> Dict[int, set]:
        """{1-based line: {rule names waived on that line}}."""
        if self._waivers is None:
            self._waivers = {}
            for i, line in enumerate(self.lines, start=1):
                tokens = _WAIVER_RE.findall(line)
                if tokens:
                    self._waivers[i] = {name for name, _until in
                                        tokens}
        return self._waivers

    @property
    def waiver_expiries(self) -> Dict[int, Dict[str, str]]:
        """{1-based line: {rule: raw until= string}} for waivers that
        carry an expiry (``# lint: allow-<rule> until=YYYY-MM-DD``).
        The raw string is kept so the expiry check can parse strictly
        and fail loudly on a malformed date."""
        if self._waiver_expiries is None:
            self._waiver_expiries = {}
            for i, line in enumerate(self.lines, start=1):
                dated = {name: until for name, until in
                         _WAIVER_RE.findall(line) if until}
                if dated:
                    self._waiver_expiries[i] = dated
        return self._waiver_expiries

    def line_at(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""

    def finding(self, rule: str, node_or_line, message: str,
                chain: Iterable = ()) -> Finding:
        line = getattr(node_or_line, "lineno", node_or_line) or 0
        frames, dropped = cap_frames(chain)
        return Finding(rule=rule, path=self.relpath, line=line,
                       message=message, snippet=self.line_at(line),
                       chain=frames, chain_dropped=dropped)


def _glob_to_re(pattern: str) -> re.Pattern:
    """Translate a posix glob (with ** spanning directories) into a
    regex over relative paths."""
    out = []
    i = 0
    while i < len(pattern):
        c = pattern[i]
        if c == "*":
            if pattern[i:i + 3] == "**/":
                out.append(r"(?:[^/]+/)*")
                i += 3
                continue
            if pattern[i:i + 2] == "**":
                out.append(r".*")
                i += 2
                continue
            out.append(r"[^/]*")
        elif c == "?":
            out.append(r"[^/]")
        else:
            out.append(re.escape(c))
        i += 1
    return re.compile("^" + "".join(out) + "$")


class Project:
    """The set of files one staticcheck run analyzes.

    ``from_root`` loads the real tree (python under the package and
    tests, markdown docs); ``from_sources`` wraps an in-memory
    {relpath: text} mapping so analyzer self-tests can plant
    violations."""

    _DISK_PATTERNS = (
        "production_stack_tpu/**/*.py",
        "tests/*.py",
        "docs/**/*.md",
        "*.md",
    )

    def __init__(self, root: str, sources: Dict[str, str]):
        self.root = root
        self._sources = sources
        self._cache: Dict[str, SourceFile] = {}
        # Guards the memoized call graph / summaries when rules run
        # under --jobs (reentrant: summaries build the call graph).
        self._ipc_lock = threading.RLock()

    @classmethod
    def from_root(cls, root) -> "Project":
        root = pathlib.Path(root)
        sources: Dict[str, str] = {}
        for pattern in cls._DISK_PATTERNS:
            for path in sorted(root.glob(pattern)):
                if not path.is_file():
                    continue
                rel = path.relative_to(root).as_posix()
                if rel not in sources:
                    try:
                        sources[rel] = path.read_text()
                    except UnicodeDecodeError:
                        continue
        return cls(str(root), sources)

    @classmethod
    def from_sources(cls, sources: Dict[str, str]) -> "Project":
        return cls("<memory>", dict(sources))

    def paths(self, *patterns: str) -> List[str]:
        regexes = [_glob_to_re(p) for p in patterns]
        return sorted(p for p in self._sources
                      if any(r.match(p) for r in regexes))

    def files(self, *patterns: str) -> List[SourceFile]:
        return [self.source(p) for p in self.paths(*patterns)]

    def source(self, relpath: str) -> Optional[SourceFile]:
        if relpath not in self._sources:
            return None
        if relpath not in self._cache:
            with self._ipc_lock:
                if relpath not in self._cache:
                    self._cache[relpath] = SourceFile(
                        relpath, self._sources[relpath])
        return self._cache[relpath]

    def warm_parse_cache(self, jobs: int = 1) -> None:
        """Parse every python file up front (optionally in a thread
        pool) so rules running under ``--jobs`` share one AST per file
        instead of racing to parse."""
        sources = self.files("**/*.py")
        if jobs <= 1:
            for sf in sources:
                sf.tree  # noqa: B018 - force the parse
            return
        with concurrent.futures.ThreadPoolExecutor(
                max_workers=jobs) as pool:
            list(pool.map(lambda sf: sf.tree, sources))


@dataclasses.dataclass
class Rule:
    name: str
    description: str
    run: Callable[[Project], List[Finding]]
    # True for rules that reason through the project call graph
    # (callgraph.py / summaries.py); surfaced by --list-rules.
    interprocedural: bool = False


REGISTRY: Dict[str, Rule] = {}


def rule(name: str, description: str, interprocedural: bool = False):
    """Register ``fn(project) -> list[Finding]`` as analyzer ``name``."""
    def decorator(fn):
        REGISTRY[name] = Rule(name=name, description=description,
                              run=fn, interprocedural=interprocedural)
        return fn
    return decorator


def _parse_waiver_date(raw: str) -> Optional[datetime.date]:
    """Strict ``YYYY-MM-DD`` parse; None for anything else (wrong
    shape, impossible date)."""
    if not _DATE_RE.match(raw):
        return None
    try:
        year, month, day = (int(part) for part in raw.split("-"))
        return datetime.date(year, month, day)
    except ValueError:
        return None


def _waived(project: Project, finding: Finding,
            today: Optional[datetime.date] = None) -> bool:
    sf = project.source(finding.path)
    if sf is None or finding.line == 0:
        return False
    if finding.rule not in sf.waivers.get(finding.line, set()):
        return False
    # A dated waiver stops suppressing the moment it expires (or if
    # its date never parsed) — the finding resurfaces alongside the
    # expired-waiver finding instead of staying silently waived.
    raw = sf.waiver_expiries.get(finding.line, {}).get(finding.rule)
    if raw is not None:
        until = _parse_waiver_date(raw)
        if until is None:
            return False
        if until < (today or datetime.date.today()):
            return False
    return True


def _waiver_findings(project: Project,
                     today: Optional[datetime.date] = None
                     ) -> List[Finding]:
    """A misspelled waiver silently disables nothing — it IS a
    finding, so the typo surfaces in the same run that was supposed
    to be suppressed. Dated waivers get the same loud-failure
    treatment: an expired or unparseable ``until=`` is an
    ``expired-waiver`` finding."""
    known = set(REGISTRY) | {"unknown-waiver", "expired-waiver"}
    today = today or datetime.date.today()
    out = []
    # Scope: package sources only. Test files quote waiver syntax in
    # fixture strings (including deliberate typos), which a raw-line
    # scan cannot tell from a real comment.
    for sf in project.files("production_stack_tpu/**/*.py"):
        for line, tokens in sf.waivers.items():
            for token in sorted(tokens - known):
                out.append(sf.finding(
                    "unknown-waiver", line,
                    f"waiver names unknown rule '{token}' (known: "
                    f"{', '.join(sorted(REGISTRY))}) — fix the "
                    "spelling or the waiver is dead weight"))
        for line, dated in sf.waiver_expiries.items():
            for token in sorted(dated):
                if token not in known:
                    continue  # already an unknown-waiver finding
                until = _parse_waiver_date(dated[token])
                if until is None:
                    out.append(sf.finding(
                        "expired-waiver", line,
                        f"waiver for '{token}' has unparseable "
                        f"until={dated[token]!r} (strict YYYY-MM-DD) "
                        "— the waiver is treated as expired"))
                elif until < today:
                    out.append(sf.finding(
                        "expired-waiver", line,
                        f"waiver for '{token}' expired on "
                        f"{until.isoformat()} — renew it with a new "
                        "date and rationale, or fix the finding"))
    return out


def run_rules(project: Project,
              rules: Optional[Iterable[str]] = None,
              jobs: int = 1,
              today: Optional[datetime.date] = None) -> List[Finding]:
    """Run analyzers (all registered by default) plus the waiver
    spelling/expiry checks; waived findings are dropped, everything
    else is returned sorted. ``today`` is the clock dated waivers
    expire against (default: the real date).

    ``jobs > 1`` runs the analyzers in a thread pool after warming
    the shared parse cache (and the call-graph/summary memos, which
    every interprocedural rule shares); output is identical to a
    serial run — findings are sorted and rules are pure readers."""
    # Import for side effect: analyzer modules self-register.
    from production_stack_tpu.staticcheck import analyzers  # noqa: F401

    names = sorted(rules) if rules is not None else sorted(REGISTRY)
    unknown = [n for n in names if n not in REGISTRY]
    if unknown:
        raise KeyError(f"unknown rule(s): {', '.join(unknown)}")
    findings: List[Finding] = []
    if jobs > 1:
        project.warm_parse_cache(jobs=jobs)
        with concurrent.futures.ThreadPoolExecutor(
                max_workers=jobs) as pool:
            for result in pool.map(
                    lambda name: REGISTRY[name].run(project), names):
                findings.extend(result)
    else:
        for name in names:
            findings.extend(REGISTRY[name].run(project))
    findings.extend(_waiver_findings(project, today))
    # Files any analyzer failed to parse fail the run explicitly —
    # an unparseable file is unanalyzed, not clean.
    for sf in project.files("**/*.py"):
        if sf.parse_error is not None:
            findings.append(Finding(
                rule="parse-error", path=sf.relpath, line=0,
                message=f"file does not parse: {sf.parse_error}"))
    findings = [f for f in findings
                if not _waived(project, f, today)]
    return sorted(findings,
                  key=lambda f: (f.path, f.line, f.rule, f.message))


# ---- shared AST helpers used by several analyzers ----------------------


def tail_name(node: ast.AST) -> str:
    """Rightmost identifier of a Name/Attribute chain ('' otherwise)."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return ""


def recv_name(node: ast.AST) -> str:
    """Identifier of an Attribute's receiver ('' otherwise)."""
    if isinstance(node, ast.Attribute):
        return tail_name(node.value)
    return ""


def string_constants(node: ast.AST) -> List[str]:
    """Every string literal under ``node``, including the constant
    fragments of f-strings."""
    out = []
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
            out.append(sub.value)
    return out


def referenced_names(node: ast.AST) -> set:
    """Identifier pool of a subtree: bare names, attribute tails,
    keyword-argument names and string constants — the net used to
    decide whether a test 'references' a symbol."""
    names = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            names.add(sub.id)
        elif isinstance(sub, ast.Attribute):
            names.add(sub.attr)
        elif isinstance(sub, ast.keyword) and sub.arg:
            names.add(sub.arg)
        elif isinstance(sub, ast.Constant) and isinstance(
                sub.value, str):
            names.add(sub.value)
    return names
