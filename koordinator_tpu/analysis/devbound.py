"""Rule ``unregistered-jit-boundary``: serving-path jit boundaries must
register with the XLA launch ledger (ISSUE 19, obs/devprof.py).

Device-time truth only holds if every launch site is attributed: a jit
boundary added under ``solver/``, ``parallel/`` or ``bridge/`` without a
``@devprof.boundary("...")`` decorator silently escapes the compile
ledger, the device-time sampler, the /metrics families and the /healthz
``device`` block — the waterfall then under-reports device time and the
operator chases a phantom host-side gap.  The rule enforces, lexically
and module-locally (same philosophy as the donation/retrace rules):

1. every jitted DEF in a serving-path module carries a
   ``devprof.boundary("<name>")`` decorator;
2. the boundary decorator sits ABOVE the jit decorator (decorators apply
   bottom-up, so the wrapper must receive the jitted callable — below it
   the AOT ``.lower()`` capture has nothing to lower);
3. the boundary name is a string literal (the ledger keys and the lint
   greps both need a static name);
4. ``name = jax.jit(fn)`` call-form assignments are flagged outright —
   the call form cannot carry the decorator; spell it as a decorated def
   or suppress with a reason;
5. a ``shard_map`` launch outside any jitted def is its own
   unattributed device launch and is flagged.

Modules outside the serving path (tests, harness, obs itself) are out of
scope: their launches never sit on the Score/Assign path the ledger
attributes.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set, Tuple

from koordinator_tpu.analysis.core import SourceFile, Violation
from koordinator_tpu.analysis.jitscope import (
    _is_jit_ref,
    _jit_call_spec,
    is_jitted_def,
    jit_assignments,
)

RULE = "unregistered-jit-boundary"

# directory parts that mark a module as serving-path
_SCOPE_PARTS = {"solver", "parallel", "bridge"}

# spellings of the shard-mapped launch entry point the repo uses
_SHARD_MAP_NAMES = {"shard_map"}


def _in_scope(path: str) -> bool:
    parts = set(path.replace("\\", "/").split("/"))
    return bool(parts & _SCOPE_PARTS)


def _boundary_decorator(deco: ast.AST) -> Optional[ast.Call]:
    """Match ``@devprof.boundary("...")`` (or a bare ``@boundary(...)``
    from a ``from ... import boundary``)."""
    if not isinstance(deco, ast.Call):
        return None
    f = deco.func
    if isinstance(f, ast.Attribute) and f.attr == "boundary":
        return deco
    if isinstance(f, ast.Name) and f.id == "boundary":
        return deco
    return None


def _is_jit_deco(deco: ast.AST) -> bool:
    return _is_jit_ref(deco) or _jit_call_spec(deco) is not None


def _check_def(path: str, node: ast.FunctionDef) -> List[Violation]:
    out: List[Violation] = []
    boundary_at: Optional[int] = None
    jit_at: Optional[int] = None
    boundary_call: Optional[ast.Call] = None
    for i, deco in enumerate(node.decorator_list):
        if boundary_at is None:
            call = _boundary_decorator(deco)
            if call is not None:
                boundary_at, boundary_call = i, call
                continue
        if jit_at is None and _is_jit_deco(deco):
            jit_at = i
    if boundary_at is None:
        out.append(Violation(
            rule=RULE, path=path, line=node.lineno,
            message=f"jitted def {node.name}() is a serving-path launch "
            "site with no @devprof.boundary(...) registration: its "
            "compiles, retraces and device time escape the launch "
            "ledger (docs/OBSERVABILITY.md \"Device-time truth\").  "
            "Register it, or suppress with a reason if it truly never "
            "runs on the Score/Assign path",
        ))
        return out
    if jit_at is not None and boundary_at > jit_at:
        out.append(Violation(
            rule=RULE, path=path, line=node.lineno,
            message=f"{node.name}(): @devprof.boundary sits BELOW the "
            "jit decorator — decorators apply bottom-up, so the ledger "
            "wraps the raw Python function and the AOT compile capture "
            "has nothing to .lower().  Move the boundary decorator "
            "above the jit decorator",
        ))
    args = boundary_call.args if boundary_call is not None else []
    if not args or not (
        isinstance(args[0], ast.Constant) and isinstance(args[0].value, str)
    ):
        out.append(Violation(
            rule=RULE, path=path, line=node.lineno,
            message=f"{node.name}(): devprof.boundary name must be a "
            "string literal — the ledger, the /metrics labels and this "
            "lint all key on a static boundary name",
        ))
    return out


def _registered_jitted_defs(tree: ast.AST) -> List[ast.FunctionDef]:
    return [
        n for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        and is_jitted_def(n)
    ]


def _shard_map_calls(tree: ast.AST) -> List[ast.Call]:
    out = []
    for n in ast.walk(tree):
        if not isinstance(n, ast.Call):
            continue
        f = n.func
        name = None
        if isinstance(f, ast.Name):
            name = f.id
        elif isinstance(f, ast.Attribute):
            name = f.attr
        if name in _SHARD_MAP_NAMES:
            out.append(n)
    return out


def check(source: SourceFile) -> List[Violation]:
    if not _in_scope(source.path):
        return []
    out: List[Violation] = []
    jitted = _registered_jitted_defs(source.tree)
    for node in jitted:
        out.extend(_check_def(source.path, node))
    for name, spec in jit_assignments(source.tree).items():
        out.append(Violation(
            rule=RULE, path=source.path, line=spec.line,
            message=f"{name} = jax.jit(...) call-form boundary cannot "
            "carry a @devprof.boundary registration — spell it as a "
            "decorated def so the launch ledger attributes it, or "
            "suppress with a reason",
        ))
    # shard_map launches must sit lexically inside SOME jitted def (the
    # def-level check above owns whether that def is registered — do not
    # double-report); outside any jit they are unattributed launches.
    inside: Set[Tuple[int, int]] = set()
    for node in jitted:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                inside.add((sub.lineno, sub.col_offset))
    for call in _shard_map_calls(source.tree):
        if (call.lineno, call.col_offset) in inside:
            continue
        out.append(Violation(
            rule=RULE, path=source.path, line=call.lineno,
            message="shard_map launch outside any jitted def: this is "
            "its own device launch with no ledger attribution.  Wrap "
            "it in a registered @devprof.boundary jit boundary, or "
            "suppress with a reason",
        ))
    return out
