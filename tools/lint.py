#!/usr/bin/env python
"""Fast lint gate: `python tools/lint.py` — runs before the test suite.

Prefers `ruff check` with a PINNED minimal rule set (no config drift):

    E9   syntax/indentation errors
    F63  comparison blunders (is-literal, == between incompatible types)
    F7   misplaced keywords (return/yield outside function, etc.)
    F82  undefined names

This container doesn't bake ruff in (and nothing may be pip-installed),
so without ruff the gate degrades to an in-repo subset with the same
spirit: every file must compile(), plus an AST pass for the E711/E712
comparison footguns and `is` against literals (F632). The ruff path and
the fallback agree on exit codes: 0 clean, 1 findings, 2 tool failure.

On top of either path run the repo's own rules (tools/analysis/ holds
the whole-program ones):

    SWFS001    bare jax.devices()/local_devices() outside mesh helpers
    SWFS002    wall-clock time.time() inside the tracing plane
    SWFS003    bare ThreadPoolExecutor on serving paths
    SWFS004    silent `except Exception` swallow on serving planes
    SWFS005    blocking call while a named lock is held
    LOCKGRAPH  cycle in the whole-program lock-acquisition graph

Flags: `--json` emits every finding (including marker-blessed ones,
with their marker status) as one JSON object so CI can diff counts
across PRs; `--knobs` prints the SWFS_* env-knob inventory that the
README consistency test enforces; `--archive-baseline <label>` appends
this tree's per-rule counts to LINT_BASELINE.json's `history` (ROADMAP
7c — the per-PR series the ratchet can diff, not just ceiling-check).
Exit codes are identical in every mode.
"""

from __future__ import annotations

import ast
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from analysis import (blocking as _blocking,  # noqa: E402
                      broadexcept as _broadexcept,
                      common as _common,
                      knobs as _knobs,
                      lockgraph as _lockgraph)

# the pinned rule set — keep in sync with the fallback checks below
# (E711/E712 are selected explicitly because the fallback implements
# them: the gate's verdict must not depend on whether ruff is installed)
RUFF_RULES = "E9,E711,E712,F63,F7,F82"

LINT_TARGETS = ("seaweedfs_tpu", "tests", "tools", "__graft_entry__.py")
# machine-generated wire code (protoc output style) is not hand-lintable
EXCLUDE_SUFFIX = "_pb2.py"

# SWFS001 (ISSUE 5): bare jax.devices()/jax.local_devices() enumeration is
# allowed ONLY here — device placement must go through the mesh helpers
# (parallel/mesh.local_devices / device_count / make_mesh) so mesh policy
# lives in one file. Runs under BOTH the ruff path and the fallback (ruff
# has no such rule).
DEVICE_ENUM_ALLOWED = (
    os.path.join("seaweedfs_tpu", "parallel", "mesh.py"),
)

# SWFS002 (ISSUE 7): span timing inside the tracing plane must come from
# the monotonic clocks (time.monotonic()/time.perf_counter(), or the
# module's own monotonic-anchored now_unix()). A bare time.time() there
# would make span durations and ordering lie across an NTP step — the
# exact corruption the trace plane exists to rule out.
SPAN_TIMING_FILES = (
    os.path.join("seaweedfs_tpu", "utils", "trace.py"),
)

# SWFS003 (ISSUE 14): bare ThreadPoolExecutor construction inside the
# request-serving packages is a lint error — per-call pools pay thread
# spawn/teardown on hot paths (the replicate_write bug) and mint
# unbounded concurrency that stampedes the keep-alive pool. Fan-out
# belongs on the shared bounded executor (seaweedfs_tpu/utils/fanout.py).
# Startup/admin/scoped-join sites opt out with an explicit
# `# lint: allow-executor` comment (same line or the line above)
# carrying the justification.
EXECUTOR_RULE_DIRS = (
    os.path.join("seaweedfs_tpu", "server"),
    os.path.join("seaweedfs_tpu", "filer"),
)
EXECUTOR_ALLOW_MARK = "lint: allow-executor"

Finding = _common.Finding


def _python_files() -> list[str]:
    out = []
    for target in LINT_TARGETS:
        path = os.path.join(REPO, target)
        if os.path.isfile(path):
            out.append(path)
            continue
        for dirpath, dirnames, filenames in os.walk(path):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            out.extend(os.path.join(dirpath, f) for f in filenames
                       if f.endswith(".py")
                       and not f.endswith(EXCLUDE_SUFFIX))
    return sorted(out)


def _package_files() -> list[str]:
    """The product tree only — what the whole-program concurrency
    passes gate (tests/tools mint scratch threads and locks of their
    own; the runtime witness covers those at execution time)."""
    prefix = os.path.join(REPO, "seaweedfs_tpu") + os.sep
    return [p for p in _python_files() if p.startswith(prefix)]


def _load_program(files: list[str] | None,
                  default: list[str]) -> list:
    return _common.load_program(
        files if files is not None else default, REPO)


# one parse, one lock table per CLI run (common.py's contract): every
# whole-program pass over the DEFAULT file set shares these. Explicit
# file lists (tests, editors) always load fresh.
_pkg_cache: dict = {}


def _package_program() -> list:
    key = tuple(_package_files())
    if _pkg_cache.get("key") != key:
        _pkg_cache.clear()
        _pkg_cache["key"] = key
        _pkg_cache["prog"] = _common.load_program(list(key), REPO)
    return _pkg_cache["prog"]


def _package_locks():
    if "locks" not in _pkg_cache or \
            _pkg_cache.get("key") != tuple(_package_files()):
        prog = _package_program()
        _pkg_cache["locks"] = _common.collect_locks(prog)
    return _pkg_cache["locks"]


def run_ruff() -> int:
    proc = subprocess.run(
        ["ruff", "check", "--select", RUFF_RULES, "--no-cache",
         "--exclude", "*" + EXCLUDE_SUFFIX, *LINT_TARGETS],
        cwd=REPO)
    return proc.returncode


def run_ruff_json() -> tuple[int, list[Finding]]:
    proc = subprocess.run(
        ["ruff", "check", "--select", RUFF_RULES, "--no-cache",
         "--output-format", "json",
         "--exclude", "*" + EXCLUDE_SUFFIX, *LINT_TARGETS],
        cwd=REPO, capture_output=True, text=True)
    findings = []
    try:
        for item in json.loads(proc.stdout or "[]"):
            findings.append(Finding(
                rule=item.get("code") or "E9",
                path=os.path.relpath(item.get("filename", ""), REPO)
                if os.path.isabs(item.get("filename", ""))
                else item.get("filename", ""),
                line=(item.get("location") or {}).get("row", 0),
                message=item.get("message", "")))
    except (ValueError, AttributeError):
        return 2, []
    return proc.returncode, findings


class _CompareVisitor(ast.NodeVisitor):
    """E711/E712 (==/!= against None/True/False) and F632 (`is` against
    a str/int/tuple literal — always an identity bug)."""

    def __init__(self, path: str):
        self.path = path
        self.findings: list[Finding] = []

    def visit_Compare(self, node: ast.Compare) -> None:
        for op, comp in zip(node.ops, node.comparators):
            if isinstance(op, (ast.Eq, ast.NotEq)) and \
                    isinstance(comp, ast.Constant) and (
                        comp.value is None or comp.value is True
                        or comp.value is False):
                self.findings.append(Finding(
                    rule="E711/E712", path=self.path, line=node.lineno,
                    message=f"comparison to {comp.value!r} — use "
                            f"`is`/`is not`"))
            if isinstance(op, (ast.Is, ast.IsNot)) and \
                    isinstance(comp, ast.Constant) and \
                    not isinstance(comp.value, bool) and \
                    isinstance(comp.value, (str, bytes, int, float)):
                self.findings.append(Finding(
                    rule="F632", path=self.path, line=node.lineno,
                    message="`is` against a literal — use `==`"))
        self.generic_visit(node)


def fallback_findings() -> list[Finding]:
    findings: list[Finding] = []
    for path in _python_files():
        rel = os.path.relpath(path, REPO)
        try:
            with open(path, "rb") as f:
                src = f.read()
            tree = ast.parse(src, filename=rel)
            compile(tree, rel, "exec")
        except SyntaxError as e:
            findings.append(Finding(rule="E9", path=rel,
                                    line=e.lineno or 0,
                                    message=str(e.msg)))
            continue
        v = _CompareVisitor(rel)
        v.visit(tree)
        findings.extend(v.findings)
    return findings


def run_fallback() -> int:
    findings = fallback_findings()
    for f in findings:
        print(f.format())
    n = len(_python_files())
    print(f"lint (builtin fallback): {n} files, "
          f"{len(findings)} finding(s)", file=sys.stderr)
    return 1 if findings else 0


# ---------------------------------------------------------------------------
# SWFS001 — device enumeration

class _DeviceEnumVisitor(ast.NodeVisitor):
    """SWFS001: `jax.devices()` / `jax.local_devices()` outside the mesh
    helpers (see DEVICE_ENUM_ALLOWED)."""

    def __init__(self, path: str):
        self.path = path
        self.findings: list[Finding] = []

    def visit_Call(self, node: ast.Call) -> None:
        f = node.func
        if isinstance(f, ast.Attribute) \
                and f.attr in ("devices", "local_devices") \
                and isinstance(f.value, ast.Name) and f.value.id == "jax":
            self.findings.append(Finding(
                rule="SWFS001", path=self.path, line=node.lineno,
                message=f"bare jax.{f.attr}() — device placement must "
                        f"go through seaweedfs_tpu/parallel/mesh.py "
                        f"helpers"))
        self.generic_visit(node)


def device_rule_findings(files: list[str] | None = None) -> list[Finding]:
    findings: list[Finding] = []
    for path in (files if files is not None else _python_files()):
        rel = os.path.relpath(path, REPO)
        if rel in DEVICE_ENUM_ALLOWED:
            continue
        try:
            with open(path, "rb") as f:
                tree = ast.parse(f.read(), filename=rel)
        except (OSError, SyntaxError):
            continue
        v = _DeviceEnumVisitor(rel)
        v.visit(tree)
        findings.extend(v.findings)
    return findings


def run_device_rule(files: list[str] | None = None) -> list[str]:
    """The in-repo device-enumeration rule; returns findings (files that
    fail to parse are the syntax gate's business, not this rule's)."""
    return [f.format() for f in device_rule_findings(files)]


# ---------------------------------------------------------------------------
# SWFS002 — span timing

class _SpanTimingVisitor(ast.NodeVisitor):
    """SWFS002: `time.time()` (and `time.time_ns()`) calls inside the
    tracing module — span timing must be monotonic."""

    def __init__(self, path: str):
        self.path = path
        self.findings: list[Finding] = []
        self.nodes: list[ast.Call] = []

    def visit_Call(self, node: ast.Call) -> None:
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in ("time", "time_ns") \
                and isinstance(f.value, ast.Name) and f.value.id == "time":
            self.findings.append(Finding(
                rule="SWFS002", path=self.path, line=node.lineno,
                message=f"time.{f.attr}() in the tracing plane — span "
                        f"timing must use time.monotonic()/"
                        f"time.perf_counter() (wall-clock anchoring "
                        f"goes through the module's _EPOCH_ANCHOR)"))
            self.nodes.append(node)
        self.generic_visit(node)


def span_timing_findings(files: list[str] | None = None) -> list[Finding]:
    """SWFS002 over SPAN_TIMING_FILES (or an explicit list); only the
    FIRST wall-clock read (the anchor) is legal, marked with
    `# lint: allow-wall-clock-anchor`."""
    findings: list[Finding] = []
    paths = files if files is not None \
        else [os.path.join(REPO, p) for p in SPAN_TIMING_FILES]
    for path in paths:
        sf = _common.load_source(path, REPO)
        if sf is None:
            continue
        idx = _common.MarkerIndex(sf, "wall-clock-anchor")
        v = _SpanTimingVisitor(sf.rel)
        v.visit(sf.tree)
        for f, node in zip(v.findings, v.nodes):
            # the anchor marker predates the reason grammar — presence
            # alone blesses it (it names itself)
            if node.lineno in idx.markers:
                f.marker, f.reason = "allowed", \
                    idx.markers[node.lineno] or "wall-clock anchor"
            findings.append(f)
    return findings


def run_span_timing_rule(files: list[str] | None = None) -> list[str]:
    return [f.format() for f in _common.active(span_timing_findings(files))]


# ---------------------------------------------------------------------------
# SWFS003 — executors on serving paths

class _ExecutorVisitor(ast.NodeVisitor):
    """SWFS003: `ThreadPoolExecutor(...)` (bare name or attribute form)
    construction inside the request-serving packages."""

    def __init__(self, path: str):
        self.path = path
        self.findings: list[Finding] = []
        self.nodes: list[ast.Call] = []

    def visit_Call(self, node: ast.Call) -> None:
        f = node.func
        name = f.id if isinstance(f, ast.Name) else (
            f.attr if isinstance(f, ast.Attribute) else "")
        if name == "ThreadPoolExecutor":
            self.findings.append(Finding(
                rule="SWFS003", path=self.path, line=node.lineno,
                message=f"bare ThreadPoolExecutor() on a serving path "
                        f"— use the shared bounded executor "
                        f"(seaweedfs_tpu/utils/fanout.py), or justify "
                        f"with `# {EXECUTOR_ALLOW_MARK}(<reason>)`"))
            self.nodes.append(node)
        self.generic_visit(node)


def executor_rule_findings(files: list[str] | None = None) -> list[Finding]:
    """SWFS003 over EXECUTOR_RULE_DIRS (or an explicit list). A site is
    exempt when the statement it belongs to carries the
    `lint: allow-executor` marker on its first line or the line above —
    the STATEMENT SPAN comes from the AST (the old line-window form
    blessed 5 arbitrary lines and could exempt an unrelated adjacent
    call)."""
    if files is None:
        files = [p for p in _python_files()
                 if any(os.sep + d + os.sep in p or
                        p.startswith(os.path.join(REPO, d) + os.sep)
                        for d in EXECUTOR_RULE_DIRS)]
    findings: list[Finding] = []
    for path in files:
        sf = _common.load_source(path, REPO)
        if sf is None:
            continue
        idx = _common.MarkerIndex(sf, "executor")
        v = _ExecutorVisitor(sf.rel)
        v.visit(sf.tree)
        for f, node in zip(v.findings, v.nodes):
            findings.append(_common.apply_marker(f, idx, node))
    return findings


def run_executor_rule(files: list[str] | None = None) -> list[str]:
    return [f.format() for f in _common.active(executor_rule_findings(files))]


# ---------------------------------------------------------------------------
# ISSUE 15 — whole-program concurrency passes (tools/analysis/)

def broad_except_findings(files: list[str] | None = None) -> list[Finding]:
    if files is None:
        program = [sf for sf in _package_program()
                   if sf.rel.replace(os.sep, "/").startswith(
                       tuple(_broadexcept.RULE_DIRS))]
        return _broadexcept.analyze(program)
    return _broadexcept.analyze(_load_program(files, files))


def run_broad_except_rule(files: list[str] | None = None) -> list[str]:
    return [f.format() for f in _common.active(broad_except_findings(files))]


def blocking_findings(files: list[str] | None = None) -> list[Finding]:
    if files is None:
        return _blocking.analyze(_package_program(),
                                 locks=_package_locks())
    return _blocking.analyze(_load_program(files, files))


def run_blocking_rule(files: list[str] | None = None) -> list[str]:
    return [f.format() for f in _common.active(blocking_findings(files))]


def lockgraph_findings(files: list[str] | None = None) -> list[Finding]:
    if files is None:
        return _lockgraph.analyze(_package_program(),
                                  locks=_package_locks())[1]
    return _lockgraph.run(_load_program(files, files))


def run_lockgraph_rule(files: list[str] | None = None) -> list[str]:
    return [f.format() for f in _common.active(lockgraph_findings(files))]


def custom_findings() -> list[Finding]:
    """Every repo-rule finding, INCLUDING marker-blessed ones (their
    marker status rides along so `--json` consumers can diff both)."""
    return (device_rule_findings() + span_timing_findings()
            + executor_rule_findings() + broad_except_findings()
            + blocking_findings() + lockgraph_findings())


def knob_inventory() -> dict[str, list[str]]:
    return _knobs.collect_knobs(_package_program())


# ---------------------------------------------------------------------------
# entry points

def _run_custom() -> list[str]:
    # derived from custom_findings() so the text and --json modes can
    # never disagree about which rules ran
    return [f.format() for f in _common.active(custom_findings())]


def main_json() -> int:
    if shutil.which("ruff"):
        rc, base = run_ruff_json()
    else:
        base = fallback_findings()
        rc = 1 if base else 0
    extra = custom_findings()
    act = _common.active(extra)
    if act and rc == 0:
        rc = 1
    out = {
        "findings": [f.as_json() for f in base + extra],
        "active": len(base) + len(act),
        "allowed": len(extra) - len(act),
        "by_rule": {},
    }
    for f in base + extra:
        out["by_rule"][f.rule] = out["by_rule"].get(f.rule, 0) + 1
    json.dump(out, sys.stdout, indent=1)
    print()
    return rc


def main_knobs() -> int:
    for line in _knobs.inventory_lines(knob_inventory()):
        print(line)
    return 0


def archive_baseline(label: str, path: str | None = None) -> dict:
    """Append this tree's per-rule finding counts to LINT_BASELINE.json's
    `history` (ROADMAP 7c): one {label, by_rule} entry per PR, so CI can
    DIFF counts across PRs instead of only enforcing the ceiling. Counts
    come from custom_findings() — marker-blessed included — exactly the
    population the ratchet test compares against `by_rule`. Re-archiving
    an existing label overwrites its entry (idempotent under CI retries);
    entries keep insertion order, one per PR."""
    path = path or os.path.join(REPO, "LINT_BASELINE.json")
    by_rule: dict[str, int] = {}
    for f in custom_findings():
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    with open(path) as fh:
        base = json.load(fh)
    hist = base.setdefault("history", [])
    entry = {"label": label, "by_rule": dict(sorted(by_rule.items()))}
    for i, e in enumerate(hist):
        if e.get("label") == label:
            hist[i] = entry
            break
    else:
        hist.append(entry)
    with open(path, "w") as fh:
        json.dump(base, fh, indent=1)
        fh.write("\n")
    return entry


def main_archive(argv: list[str]) -> int:
    i = argv.index("--archive-baseline")
    label = argv[i + 1] if len(argv) > i + 1 else "HEAD"
    json.dump(archive_baseline(label), sys.stdout, indent=1)
    print()
    return 0


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--knobs" in argv:
        return main_knobs()
    if "--archive-baseline" in argv:
        return main_archive(argv)
    if "--json" in argv:
        return main_json()
    rc = run_ruff() if shutil.which("ruff") else run_fallback()
    extra = _run_custom()
    for finding in extra:
        print(finding)
    if extra and rc == 0:
        rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
