"""Repo source lint: custom AST/token checks beyond what ruff covers.

Deliberately importable WITHOUT the paddle_tpu package (tools/lint.py
loads this file directly): stdlib only, no jax, no package-relative
imports — the lint gate must run in a bare interpreter in under a
second.

Rules:

  joined-continuation  a boolean connector ('or'/'and') preceded by a
      long run of spaces mid-line — the fossil of a lost continuation
      backslash, where three conditions collapse into one fragile
      physical line (ops/rnn_ops.py:39, ADVICE round 5, is the type
      specimen; its pre-fix form is the regression fixture in
      tests/test_analysis.py).

  undeclared-env-knob  a read of a PT_* / FLAGS_* environment variable
      that paddle_tpu/flags.py does not declare (DEFINE_flag for FLAGS_*,
      declare_env_knob for PT_*). Undeclared knobs are invisible to
      FLAGS.help() and to the next maintainer; every env switch must be
      registered where the others live.

  device-coercion  a numpy coercion (np.asarray/np.array/np.stack/
      np.concatenate/np.ravel), a float() call, or an .item()/.tolist()
      method call inside one of the HOT-LOOP FILES (the per-step train
      path: trainer, executors, scope, prefetch, async_fetch). On a
      device value each of these is a hidden host synchronization — the
      exact overhead class the async hot path removed (a stray
      np.asarray on a fetch re-serializes every step). Deliberate
      materialization points carry a `# host-sync: ok` marker on the
      call's line with a short justification; anything unmarked fails
      the gate.

  hardcoded-axis-spec  a mesh-axis-name string literal ("dp"/"tp"/"sp"/
      "ep"/"pp") outside parallel/mesh.py and paddle_tpu/analysis/.
      Placement truth lives in exactly two places — mesh.py's axis
      constants (DP/TP/PP/SP/EP) and the planner's PlacementPlan
      artifacts — so any other file spelling an axis name is either
      hand-picking a placement the planner should own or typo-prone
      stringly-typed code; import the constant instead. Deliberate
      exceptions (a CLI parsing user-typed axis names, a launch-script
      compat shim) carry `# spec: ok` on the literal's line or the line
      above with a short justification.
"""

from __future__ import annotations

import ast
import io
import os
import tokenize
from dataclasses import dataclass
from typing import Iterable, List, Optional, Sequence, Set

#: minimum run of spaces before or/and that marks a lost continuation —
#: aligned wrapped operators sit at line start (prev token on an earlier
#: line) and never hit this.
JOINED_GAP = 8

#: env-var prefixes the knob-declaration rule governs. BENCH_*/FLASH_*
#: and friends are bench-harness locals, out of scope by design.
GOVERNED_PREFIXES = ("PT_", "FLAGS_")

#: files the device-coercion rule governs — the per-step training hot
#: path. metrics.py/evaluator.py are deliberately NOT governed: their
#: update()/eval() methods are the documented read points where fetched
#: values become host scalars (feeding them device values syncs there,
#: by contract, once per update — not once per step primitive).
HOT_LOOP_FILES = (
    "paddle_tpu/trainer.py",
    "paddle_tpu/core/executor.py",
    "paddle_tpu/core/scope.py",
    "paddle_tpu/core/async_fetch.py",
    "paddle_tpu/parallel/parallel_executor.py",
    "paddle_tpu/reader/prefetch.py",
    "paddle_tpu/data/pipeline.py",
)

#: suppression marker: a justified, deliberate materialization point
HOST_SYNC_MARK = "host-sync: ok"

#: numpy-module coercion functions that force a device->host sync
COERCION_NP_FUNCS = ("asarray", "array", "stack", "concatenate", "ravel")

#: method calls that force a device->host sync on a device value
COERCION_METHODS = ("item", "tolist")

#: the mesh-axis alphabet the hardcoded-axis-spec rule polices (kept
#: literal: this module must import without the package, and these ARE
#: the canonical spellings mesh.py's constants bind)
AXIS_NAMES = frozenset({"dp", "tp", "pp", "sp", "ep"})

#: files allowed to spell axis names: the constants' home and the
#: analysis layer (whose planner/audit/verifier literally reason ABOUT
#: axis names as data)
AXIS_SPEC_EXEMPT = ("paddle_tpu/parallel/mesh.py", "paddle_tpu/analysis/")

#: suppression marker for deliberate axis-name literals
SPEC_OK_MARK = "spec: ok"


@dataclass(frozen=True)
class LintFinding:
    path: str
    line: int
    col: int
    code: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: [{self.code}] " \
               f"{self.message}"


# ---------------------------------------------------------------------------
# rule: joined-continuation
# ---------------------------------------------------------------------------

def check_joined_continuation(path: str, src: str) -> List[LintFinding]:
    findings: List[LintFinding] = []
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(src).readline))
    except (tokenize.TokenError, SyntaxError, IndentationError):
        return findings  # unparsable files are ruff/compile's problem
    prev = None
    for tok in tokens:
        if (tok.type == tokenize.NAME and tok.string in ("or", "and")
                and prev is not None
                and prev.end[0] == tok.start[0]
                and tok.start[1] - prev.end[1] >= JOINED_GAP):
            findings.append(LintFinding(
                path, tok.start[0], tok.start[1], "joined-continuation",
                f"{tok.string!r} preceded by "
                f"{tok.start[1] - prev.end[1]} spaces mid-line — a lost "
                "continuation backslash; parenthesize the condition "
                "across lines"))
        if tok.type not in (tokenize.NL, tokenize.NEWLINE, tokenize.INDENT,
                            tokenize.DEDENT, tokenize.COMMENT):
            prev = tok
    return findings


# ---------------------------------------------------------------------------
# rule: undeclared-env-knob
# ---------------------------------------------------------------------------

def _env_read_name(node: ast.AST) -> Optional[ast.Constant]:
    """The constant-string env name read by `node`, if it is an env read:
    os.environ.get(X…) / os.getenv(X…) / os.environ[X]."""

    def is_os_environ(n) -> bool:
        return (isinstance(n, ast.Attribute) and n.attr == "environ"
                and isinstance(n.value, ast.Name) and n.value.id == "os")

    key = None
    if isinstance(node, ast.Call):
        f = node.func
        if (isinstance(f, ast.Attribute) and f.attr == "get"
                and is_os_environ(f.value) and node.args):
            key = node.args[0]
        elif (isinstance(f, ast.Attribute) and f.attr == "getenv"
                and isinstance(f.value, ast.Name) and f.value.id == "os"
                and node.args):
            key = node.args[0]
    elif isinstance(node, ast.Subscript) and is_os_environ(node.value):
        key = node.slice
    if isinstance(key, ast.Constant) and isinstance(key.value, str):
        return key
    return None


def check_env_knobs(path: str, src: str,
                    declared: Set[str]) -> List[LintFinding]:
    try:
        tree = ast.parse(src)
    except SyntaxError:
        return []
    findings: List[LintFinding] = []
    for node in ast.walk(tree):
        const = _env_read_name(node)
        if const is None:
            continue
        name = const.value
        if name.startswith(GOVERNED_PREFIXES) and name not in declared:
            findings.append(LintFinding(
                path, const.lineno, const.col_offset,
                "undeclared-env-knob",
                f"env var {name!r} is read here but not declared in "
                "paddle_tpu/flags.py (declare_env_knob / DEFINE_flag)"))
    return findings


def declared_knobs_from_flags(flags_path: str) -> Set[str]:
    """Statically parse flags.py for the declared knob set — no package
    import, so the lint gate works in a bare interpreter."""
    with open(flags_path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    declared: Set[str] = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)):
            continue
        if not node.args or not isinstance(node.args[0], ast.Constant):
            continue
        name = node.args[0].value
        if not isinstance(name, str):
            continue
        if node.func.id == "declare_env_knob":
            declared.add(name)
        elif node.func.id == "DEFINE_flag":
            declared.add(f"FLAGS_{name}")
    return declared


# ---------------------------------------------------------------------------
# rule: device-coercion (hot-loop files only)
# ---------------------------------------------------------------------------

def is_hot_loop_file(path: str) -> bool:
    norm = path.replace(os.sep, "/")
    return any(norm.endswith(h) for h in HOT_LOOP_FILES)


def check_device_coercion(path: str, src: str) -> List[LintFinding]:
    if not is_hot_loop_file(path):
        return []
    try:
        tree = ast.parse(src)
    except SyntaxError:
        return []
    lines = src.splitlines()
    findings: List[LintFinding] = []

    def suppressed(node) -> bool:
        """Marker accepted on the call's own line or the line above (long
        expressions push the call mid-statement)."""
        for ln in (node.lineno - 1, node.lineno - 2):
            if 0 <= ln < len(lines) and HOST_SYNC_MARK in lines[ln]:
                return True
        return False

    def flag(node, what):
        findings.append(LintFinding(
            path, node.lineno, node.col_offset, "device-coercion",
            f"{what} in a hot-loop file forces a device->host sync per "
            "step if it ever sees a device value; mark deliberate "
            f"materialization points with `# {HOST_SYNC_MARK} — <why>` "
            "or move the read out of the step loop"))

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if (isinstance(f, ast.Attribute) and f.attr in COERCION_NP_FUNCS
                and isinstance(f.value, ast.Name) and f.value.id == "np"):
            if not suppressed(node):
                flag(node, f"np.{f.attr}(...)")
        elif (isinstance(f, ast.Name) and f.id == "float" and node.args
                and not isinstance(node.args[0], ast.Constant)):
            if not suppressed(node):
                flag(node, "float(...)")
        elif isinstance(f, ast.Attribute) and f.attr in COERCION_METHODS:
            # args don't exempt: arr.item(3) syncs exactly like arr.item()
            if not suppressed(node):
                flag(node, f".{f.attr}()")
    return findings


# ---------------------------------------------------------------------------
# rule: hardcoded-axis-spec
# ---------------------------------------------------------------------------

def is_axis_spec_exempt(path: str) -> bool:
    norm = path.replace(os.sep, "/")
    return any((e.endswith("/") and e in norm) or norm.endswith(e)
               for e in AXIS_SPEC_EXEMPT)


def check_axis_spec_literals(path: str, src: str) -> List[LintFinding]:
    if is_axis_spec_exempt(path):
        return []
    try:
        tree = ast.parse(src)
    except SyntaxError:
        return []
    lines = src.splitlines()
    findings: List[LintFinding] = []
    # docstrings are Expr-statement constants: an axis name can only
    # collide there as a whole two-letter docstring, which nothing writes
    doc_nodes = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Expr) and isinstance(node.value,
                                                     ast.Constant):
            doc_nodes.add(id(node.value))

    def suppressed(node) -> bool:
        for ln in (node.lineno - 1, node.lineno - 2):
            if 0 <= ln < len(lines) and SPEC_OK_MARK in lines[ln]:
                return True
        return False

    for node in ast.walk(tree):
        if not (isinstance(node, ast.Constant)
                and isinstance(node.value, str)
                and node.value in AXIS_NAMES):
            continue
        if id(node) in doc_nodes or suppressed(node):
            continue
        findings.append(LintFinding(
            path, node.lineno, node.col_offset, "hardcoded-axis-spec",
            f"mesh-axis literal {node.value!r} outside parallel/mesh.py "
            "and analysis/ — placement truth belongs to mesh.py's axis "
            "constants and planner-emitted plans; import the constant "
            "(from paddle_tpu.parallel.mesh import "
            f"{node.value.upper()}) or mark a deliberate exception with "
            f"`# {SPEC_OK_MARK} — <why>`"))
    return findings


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def lint_file(path: str, declared: Set[str]) -> List[LintFinding]:
    with open(path, encoding="utf-8") as f:
        src = f.read()
    return (check_joined_continuation(path, src)
            + check_env_knobs(path, src, declared)
            + check_device_coercion(path, src)
            + check_axis_spec_literals(path, src))


def default_targets(root: str) -> List[str]:
    """The governed source set: the package, tools, scripts."""
    targets: List[str] = []
    for rel in ("paddle_tpu", "tools", "scripts"):
        top = os.path.join(root, rel)
        for dirpath, _dirnames, filenames in os.walk(top):
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    targets.append(os.path.join(dirpath, fn))
    return targets


def lint_paths(paths: Sequence[str], flags_path: str) -> List[LintFinding]:
    declared = declared_knobs_from_flags(flags_path)
    findings: List[LintFinding] = []
    for p in paths:
        findings.extend(lint_file(p, declared))
    return findings
