#!/usr/bin/env python
"""Repo linter — the tier-1 flow's "repo lints itself" gate.

Prefers ``ruff`` (config in pyproject.toml: pyflakes + bugbear) when the
binary is installed; this container ships no linter, so the default path
is a dependency-free AST fallback implementing the highest-signal subset
of the same rules:

- ``F401``  module-level import bound but never used (skipped in
  ``__init__.py`` re-export surfaces)
- ``F632``  ``is``/``is not`` comparison against a str/int/tuple literal
- ``F811``  module-level def/class silently redefining an earlier one
- ``F841``  local variable assigned but never used (plain single-name
  assignments only; ``_``-prefixed names exempt; skipped under tests/
  to match the ruff per-file-ignores)
- ``B006``  mutable default argument ([], {}, set()/list()/dict())
- ``E722``  bare ``except:``
- ``W605``  invalid escape sequence in a non-raw string literal

``# noqa`` (bare, or ``# noqa: F401,...``) on the flagged line suppresses
a finding, matching ruff semantics, so both linters agree on the same
annotations. Exit status 0 = clean.

On top of the style/correctness rules, the gate runs the repo's own
**concurrency self-lint** (``deeplearning4j_tpu.analysis.concurrency``,
the DL4J-E2xx/W21x thread-safety codes) over the package with
warnings-as-errors — per-code suppressions live in pyproject.toml under
``[tool.dl4j.concurrency]`` and per-line ones as ``# dl4j: noqa=E201``
comments. Ruff has no equivalent rule set, so this half always runs.

The gate also re-imports every graph in the persisted TF conformance
corpus (``tests/fixtures/tfgraphs``) and requires a clean
``import_report`` (the DL4J-E16x/W16x import lints) with
warnings-as-errors — suppressions live in pyproject.toml under
``[tool.dl4j.imports]``.

Usage: ``python tools/lint.py [paths...]`` (default: the package, tests,
tools, benchmarks). ``--fallback`` forces the AST linter even when ruff
exists (what the test suite pins); ``--no-concurrency`` skips the
thread-safety pass (style-only run); ``--no-imports`` skips the
imported-fixture gate.
"""

from __future__ import annotations

import argparse
import ast
import io
import re
import shutil
import subprocess
import sys
import tokenize
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
DEFAULT_PATHS = ["deeplearning4j_tpu", "tests", "tools", "benchmarks",
                 "bench.py"]

_NOQA_RE = re.compile(r"#\s*noqa(?::\s*(?P<codes>[A-Z0-9, ]+))?", re.I)
_WORD_RE = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


class Finding:
    def __init__(self, path: Path, line: int, code: str, message: str):
        self.path, self.line, self.code, self.message = path, line, code, message

    def __str__(self):
        return f"{self.path}:{self.line}: {self.code} {self.message}"


def _noqa_lines(source: str):
    """line number -> set of suppressed codes (empty set = suppress all)."""
    out = {}
    for i, line in enumerate(source.splitlines(), 1):
        m = _NOQA_RE.search(line)
        if m:
            codes = m.group("codes")
            out[i] = {c.strip().upper() for c in codes.split(",")} \
                if codes else set()
    return out


def _used_names(nodes):
    """Every identifier the module can plausibly reference: Name loads,
    plus word tokens inside string constants (quoted annotations,
    __all__ entries, forward references)."""
    used = set()
    for node in nodes:
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and len(node.value) < 200:
            used.update(_WORD_RE.findall(node.value))
        elif isinstance(node, ast.Global):
            used.update(node.names)
    return used


def _check_f401(tree, nodes, path: Path, findings):
    if path.name == "__init__.py":
        return
    used = _used_names(nodes)
    for node in tree.body:                       # module level only
        if isinstance(node, ast.Import):
            for alias in node.names:
                bound = alias.asname or alias.name.split(".")[0]
                if bound not in used:
                    findings.append(Finding(
                        path, node.lineno, "F401",
                        f"'{alias.name}' imported but unused"))
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue
            for alias in node.names:
                if alias.name == "*":
                    continue
                bound = alias.asname or alias.name
                if bound not in used:
                    findings.append(Finding(
                        path, node.lineno, "F401",
                        f"'{node.module}.{alias.name}' imported but unused"))


def _check_f811(tree, path: Path, findings):
    seen = {}
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            if node.name in seen:
                findings.append(Finding(
                    path, node.lineno, "F811",
                    f"redefinition of '{node.name}' from line "
                    f"{seen[node.name]}"))
            seen[node.name] = node.lineno


def _check_f632(tree, nodes, path: Path, findings):
    for node in nodes:
        if not isinstance(node, ast.Compare):
            continue
        for op, comp in zip(node.ops, node.comparators):
            if isinstance(op, (ast.Is, ast.IsNot)) and \
                    isinstance(comp, ast.Constant) and \
                    isinstance(comp.value, (str, int, bytes)) and \
                    not isinstance(comp.value, bool):
                findings.append(Finding(
                    path, node.lineno, "F632",
                    "use == / != to compare with literals, not 'is'"))


def _check_b006(tree, nodes, path: Path, findings):
    for node in nodes:
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        defaults = list(node.args.defaults) + \
            [d for d in node.args.kw_defaults if d is not None]
        for d in defaults:
            mutable = isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(d, ast.Call) and isinstance(d.func, ast.Name)
                and d.func.id in ("list", "dict", "set") and not d.args
                and not d.keywords)
            if mutable:
                findings.append(Finding(
                    path, d.lineno, "B006",
                    f"mutable default argument in '{node.name}' — use "
                    f"None and create inside the function"))


def _check_e722(tree, nodes, path: Path, findings):
    for node in nodes:
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            findings.append(Finding(path, node.lineno, "E722",
                                    "bare 'except:' — name the exception"))


def _scope_statements(fn):
    """Nodes belonging to ``fn``'s own scope — descends everything except
    nested function/class/lambda bodies (their assignments are THEIR
    locals, and each nested def is linted as its own scope)."""
    stack = list(fn.body)
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
                continue
            stack.append(child)


def _check_f841(tree, nodes, path: Path, findings):
    """Local assigned but never used. Conservative subset of ruff's F841:
    plain single-Name ``x = ...`` / annotated assignments only (tuple
    unpacking, loop targets, and aug-assigns are deliberate far too often
    to flag), ``_``-prefixed names exempt, and a name counts as used if it
    is loaded ANYWHERE inside the function — including nested closures
    and short string constants (quoted forward refs)."""
    for fn in nodes:
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        used = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and not isinstance(node.ctx,
                                                             ast.Store):
                used.add(node.id)
            elif isinstance(node, (ast.Global, ast.Nonlocal)):
                used.update(node.names)
            elif isinstance(node, ast.Constant) \
                    and isinstance(node.value, str) and len(node.value) < 200:
                used.update(_WORD_RE.findall(node.value))
        first_assign = {}
        for node in _scope_statements(fn):
            target = None
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                target = node.targets[0].id
            elif isinstance(node, ast.AnnAssign) \
                    and isinstance(node.target, ast.Name) \
                    and node.value is not None:
                target = node.target.id
            if target and not target.startswith("_") \
                    and target not in used:
                first_assign.setdefault(target, node.lineno)
        for name, lineno in sorted(first_assign.items(),
                                   key=lambda kv: kv[1]):
            findings.append(Finding(
                path, lineno, "F841",
                f"local variable '{name}' is assigned to but never used"))


#: every escape the language defines for str literals (bytes' stricter
#: set is not distinguished — conservative)
_VALID_ESCAPES = frozenset("\n\\'\"abfnrtv01234567xNuU")


def _check_w605(source: str, path: Path, findings):
    """Invalid escape sequences in non-raw string literals — today a
    DeprecationWarning, eventually a SyntaxError, always a latent regex
    or path bug. Token-level (not AST) so every literal is seen exactly
    where it is written."""
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(source).readline))
    except (tokenize.TokenError, SyntaxError, IndentationError):
        return
    for tok in tokens:
        if tok.type != tokenize.STRING:
            continue
        text = tok.string
        prefix = re.match(r"[A-Za-z]*", text).group(0)
        if "r" in prefix.lower():
            continue
        rest = text[len(prefix):]
        qlen = 3 if rest[:3] in ('"""', "'''") else 1
        body = rest[qlen:-qlen]
        line = tok.start[0]
        i = 0
        while i < len(body) - 1:
            if body[i] == "\\":
                nxt = body[i + 1]
                if nxt not in _VALID_ESCAPES:
                    findings.append(Finding(
                        path, line + body[:i].count("\n"), "W605",
                        f"invalid escape sequence '\\{nxt}' — use a raw "
                        f"string (r'...') or double the backslash"))
                i += 2
            else:
                i += 1


def lint_file(path: Path):
    source = path.read_text(encoding="utf-8")
    try:
        tree = ast.parse(source, filename=str(path))
    except SyntaxError as e:
        return [Finding(path, e.lineno or 0, "E999", f"syntax error: {e.msg}")]
    findings = []
    nodes = list(ast.walk(tree))    # ONE tree walk shared by every check
    _check_f811(tree, path, findings)
    for check in (_check_f401, _check_f632, _check_b006, _check_e722):
        check(tree, nodes, path, findings)
    # tests/* keep F841 probes (mirrors the pyproject per-file-ignores)
    if "tests" not in path.parts:
        _check_f841(tree, nodes, path, findings)
    _check_w605(source, path, findings)
    noqa = _noqa_lines(source)
    return [f for f in findings
            if not (f.line in noqa and
                    (not noqa[f.line] or f.code in noqa[f.line]))]


def iter_py_files(paths):
    for p in paths:
        p = (REPO / p) if not Path(p).is_absolute() else Path(p)
        if p.is_file() and p.suffix == ".py":
            yield p
        elif p.is_dir():
            yield from sorted(p.rglob("*.py"))


def run_fallback(paths) -> int:
    findings = []
    for f in iter_py_files(paths):
        findings.extend(lint_file(f))
    for f in findings:
        print(f)
    n = len(findings)
    print(f"lint (ast fallback): {n} finding(s)" if n
          else "lint (ast fallback): clean")
    return 1 if findings else 0


#: what the concurrency self-lint covers: the shipped package only —
#: tests keep deliberately-racy fixtures, benchmarks are single-threaded
CONCURRENCY_PATHS = ["deeplearning4j_tpu"]


def _pyproject_suppress(section: str) -> list:
    """``[tool.dl4j.<section>] suppress = ["W212", ...]`` from
    pyproject.toml (line-scoped parse: this container is py3.10, no
    tomllib, and the gate must stay dependency-free). Scans the section
    line by line until the next ``[section]`` header, so other keys,
    comments, or '[' characters inside the section cannot silently
    defeat the parse."""
    try:
        text = (REPO / "pyproject.toml").read_text(encoding="utf-8")
    except OSError:
        return []
    header = re.escape(f"[tool.dl4j.{section}]")
    in_section = in_array = False
    body: list = []
    for line in text.splitlines():
        # strip TOML comments first: a ']' or quoted word inside one
        # must not end (or pollute) the array parse — codes never
        # contain '#'
        stripped = line.split("#", 1)[0].strip()
        if in_array:
            head = stripped.split("]", 1)[0]
            body.append(head)
            if "]" in stripped:
                return re.findall(r'"([^"]+)"', " ".join(body))
            continue
        if re.fullmatch(header, stripped):
            in_section = True
            continue
        if in_section and re.fullmatch(r"\[[^\]]+\]", stripped):
            break                       # next section header
        if in_section:
            m = re.match(r"suppress\s*=\s*\[(?P<rest>.*)", stripped)
            if m:
                rest = m.group("rest")
                if "]" in rest:         # single-line array
                    return re.findall(r'"([^"]+)"',
                                      rest.split("]", 1)[0])
                body.append(rest)       # multi-line array: keep reading
                in_array = True
    return []


def _pyproject_concurrency_suppress() -> list:
    return _pyproject_suppress("concurrency")


def _pyproject_imports_suppress() -> list:
    return _pyproject_suppress("imports")


def run_concurrency(paths=None) -> int:
    """The DL4J-E2xx/W21x thread-safety self-lint, warnings-as-errors.
    Returns 0 when every path is clean."""
    sys.path.insert(0, str(REPO))
    try:
        from deeplearning4j_tpu.analysis.concurrency import \
            analyze_concurrency
    finally:
        sys.path.pop(0)
    suppress = _pyproject_concurrency_suppress()
    failed = 0
    for p in (paths or CONCURRENCY_PATHS):
        try:
            report = analyze_concurrency(str(REPO / p), suppress=suppress)
        except ValueError as e:
            # a typo'd code in [tool.dl4j.concurrency] suppress must be
            # a clean usage error, not a traceback
            print(f"concurrency self-lint: bad suppress config in "
                  f"pyproject.toml: {e}")
            return 1
        print(report.format())
        if not report.ok(warnings_as_errors=True):
            failed = 1
    return failed


#: what the imported-fixture gate covers: the persisted TF conformance
#: corpus — every graph must re-import with a clean ``import_report``
IMPORT_FIXTURE_DIR = "tests/fixtures/tfgraphs"


def run_imports(fixture_dir=None) -> int:
    """Imported-fixture lint gate: re-import every graph in the persisted
    conformance corpus and require a clean ``import_report`` (the
    DL4J-E16x/W16x import lints), warnings-as-errors. Per-code
    suppressions live in pyproject.toml under ``[tool.dl4j.imports]``.
    Returns 0 when every fixture is clean; skips (0) when the corpus or
    the TF proto stubs are absent — the gate audits shipped fixtures, it
    does not require a TF install."""
    fdir = Path(fixture_dir) if fixture_dir else REPO / IMPORT_FIXTURE_DIR
    files = sorted(fdir.glob("*.npz")) if fdir.is_dir() else []
    if not files:
        print("imports lint: no import fixtures found — skipped")
        return 0
    try:
        from tensorflow.core.framework import graph_pb2
    except ImportError:
        print("imports lint: tensorflow protos unavailable — skipped")
        return 0
    import numpy as np
    sys.path.insert(0, str(REPO))
    try:
        from deeplearning4j_tpu.modelimport.tensorflow import TFGraphImport
    finally:
        sys.path.pop(0)
    suppress = _pyproject_imports_suppress()
    failed = checked = 0
    for path in files:
        data = np.load(path, allow_pickle=False)
        gd = graph_pb2.GraphDef()
        gd.ParseFromString(data["graph_def"].tobytes())
        try:
            sd = TFGraphImport.importGraphDef(gd)
        except ValueError as e:
            print(f"imports lint: {path.name}: import failed: {e}")
            failed = 1
            continue
        try:
            report = sd.import_report.apply_config(suppress=suppress)
        except ValueError as e:
            # a typo'd code in [tool.dl4j.imports] suppress must be a
            # clean usage error, not a traceback
            print(f"imports lint: bad suppress config in "
                  f"pyproject.toml: {e}")
            return 1
        checked += 1
        if not report.ok(warnings_as_errors=True):
            report.subject = path.name
            print(report.format())
            failed = 1
    print(f"imports lint: {checked} fixture(s) checked"
          + ("" if failed else " — clean"))
    return failed


def run_cost(chip: str = "tpu-v4") -> int:
    """Cost-model gate: every zoo architecture through the DL4J-E12x/W12x
    whole-program cost lints on the default chip, warnings-as-errors — a
    config change that statically OOMs (or regresses the predicted plan
    on) the reference chip fails the gate before any hardware sees it.
    Per-code suppressions live under ``[tool.dl4j.cost]``. Skips (0)
    when the model stack cannot import (the gate needs the layer
    definitions, not jax — analysis itself is jax-free)."""
    sys.path.insert(0, str(REPO))
    try:
        from deeplearning4j_tpu.analysis import analyze
        from deeplearning4j_tpu.analysis.cost import CostSpec
        from deeplearning4j_tpu.models import zoo
    except ImportError as e:
        print(f"cost lint: model stack unavailable ({e}) — skipped")
        return 0
    finally:
        sys.path.pop(0)
    suppress = _pyproject_suppress("cost")
    failed = checked = 0
    for name, cls in zoo.ZOO_MODELS.items():
        try:
            report = analyze(cls.for_cost_gate().conf_builder(),
                             cost=CostSpec(chip=chip),
                             suppress=suppress)
        except ValueError as e:
            # a typo'd code in [tool.dl4j.cost] suppress must be a clean
            # usage error, not a traceback
            print(f"cost lint: bad suppress config in pyproject.toml: {e}")
            return 1
        report.diagnostics = [d for d in report.diagnostics
                              if d.code.startswith(("DL4J-E12", "DL4J-W12"))]
        checked += 1
        if not report.ok(warnings_as_errors=True):
            report.subject = name
            print(report.format())
            failed = 1
    print(f"cost lint: {checked} zoo model(s) checked on {chip}"
          + ("" if failed else " — clean"))
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", default=None)
    ap.add_argument("--fallback", action="store_true",
                    help="force the AST fallback even when ruff is on PATH")
    ap.add_argument("--no-concurrency", action="store_true",
                    help="skip the DL4J-E2xx/W21x thread-safety self-lint")
    ap.add_argument("--no-imports", action="store_true",
                    help="skip the DL4J-E16x/W16x imported-fixture gate")
    ap.add_argument("--no-cost", action="store_true",
                    help="skip the DL4J-E12x/W12x zoo cost-model gate")
    args = ap.parse_args(argv)
    paths = args.paths or DEFAULT_PATHS
    if not args.fallback and shutil.which("ruff"):
        rc = subprocess.call(["ruff", "check", *paths], cwd=REPO)
    else:
        rc = run_fallback(paths)
    if not args.no_concurrency:
        rc = run_concurrency() or rc
    if not args.no_imports:
        rc = run_imports() or rc
    if not args.no_cost:
        rc = run_cost() or rc
    return rc


if __name__ == "__main__":
    sys.exit(main())
