"""lock-held-dispatch: blocking device readbacks under a state lock.

The coalescing dispatch engine (ISSUE 5, bridge/server.py) exists
because the daemon once held ONE servicer lock across every RPC body —
including the device dispatch and the blocking ``np.asarray`` readback,
so sixteen parallel Score workers queued single-file behind a single
transfer.  The refactor's invariant is lexical and therefore checkable:
a ``with <...state lock...>:`` block must never contain a blocking
device->host transfer (``np.asarray``/``np.array``/``np.copy`` on
device values, ``.item()``, ``.block_until_ready()``,
``jax.device_get``).  Capture references under the lock; launch and
read back outside it (the device-dispatch queue serializes launches).

Scope: with-blocks whose context expression's terminal attribute names
a state/servicer lock (``_state_lock``, ``state_lock``,
``_servicer_lock``, or a bare ``_lock`` — the pre-split servicer's
spelling).  Nested function *definitions* inside the block are skipped:
a closure defined under the lock does not run under it.  Host-only
registries that guard plain dict/list state under a ``_lock`` never
trip the rule because they perform no device readbacks; a with-block
that legitimately must read back under a lock (none should) can carry
``# koordlint: disable=lock-held-dispatch``.

ISSUE 6 extends the rule to the **pipeline seam**: the dispatcher's
launch critical section (functions carrying the
``@launch_section`` decorator from bridge/coalesce.py, and with-blocks
on a ``*_launch_lock``) must only capture state and dispatch device
work asynchronously — a blocking ``device_get``/``block_until_ready``
inside it stalls every queued launch exactly the way the old single
lock did, un-pipelining the engine silently.  Nested defs inside a
launch-section function are exempt: that is precisely where the
readback closure (the only code allowed to block) lives.
"""

from __future__ import annotations

import ast
from typing import Iterator, List

from koordinator_tpu.analysis.core import SourceFile, Violation

RULE = "lock-held-dispatch"

_NP_MODULES = ("np", "numpy", "onp", "_np")
_NP_SYNC_FUNCS = ("asarray", "array", "copy")
_JAX_MODULES = ("jax",)
_LOCK_NAMES = ("_state_lock", "state_lock", "_servicer_lock", "_lock")
# the pipelined dispatcher's launch critical section (ISSUE 6)
_LAUNCH_LOCK_NAMES = ("_launch_lock", "launch_lock")
_LAUNCH_DECORATOR = "launch_section"


def _terminal_name(node: ast.AST) -> str:
    """The last segment of a Name/Attribute chain (``self.x._lock`` ->
    "_lock"); '' for anything else (calls like ``maybe_span(...)``)."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _root_module(node: ast.AST) -> str:
    while isinstance(node, ast.Attribute):
        node = node.value
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _is_state_lock_with(node: ast.With) -> bool:
    return any(
        _terminal_name(item.context_expr) in _LOCK_NAMES
        for item in node.items
    )


def _is_launch_lock_with(node: ast.With) -> bool:
    return any(
        _terminal_name(item.context_expr) in _LAUNCH_LOCK_NAMES
        for item in node.items
    )


def _is_launch_section_def(node: ast.AST) -> bool:
    """A function carrying the ``@launch_section`` marker (bare name or
    attribute form, e.g. ``@coalesce.launch_section``) runs under the
    dispatcher's launch lock."""
    if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return False
    return any(
        _terminal_name(dec) == _LAUNCH_DECORATOR
        for dec in node.decorator_list
    )


def _walk_skip_defs(nodes) -> Iterator[ast.AST]:
    """Walk statements without descending into nested function/class
    definitions (a closure defined under the lock runs elsewhere)."""
    stack = list(nodes)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef,
                   ast.Lambda)
        ):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _blocking_call(sub: ast.AST) -> str:
    """Name of the blocking device->host transfer this Call performs,
    or '' for anything else."""
    if not isinstance(sub, ast.Call):
        return ""
    fn = sub.func
    if isinstance(fn, ast.Attribute) and (
        _root_module(fn) in _NP_MODULES and fn.attr in _NP_SYNC_FUNCS
    ):
        return f"np.{fn.attr}()"
    if isinstance(fn, ast.Attribute) and fn.attr == "item":
        return ".item()"
    if isinstance(fn, ast.Attribute) and fn.attr == "block_until_ready":
        return ".block_until_ready()"
    if isinstance(fn, ast.Attribute) and (
        _root_module(fn) in _JAX_MODULES and fn.attr == "device_get"
    ):
        return "jax.device_get()"
    return ""


_STATE_MSG = (
    "{flagged} while the servicer state lock is held serializes every "
    "RPC behind one device->host transfer; capture references under "
    "the lock and read back outside it (the device-dispatch queue "
    "orders launches)"
)
_LAUNCH_MSG = (
    "{flagged} inside the dispatcher's launch critical section stalls "
    "every queued launch behind one device->host transfer — the "
    "pipeline un-pipelines silently; launch sections capture + "
    "dispatch asynchronously, only the readback closure (a nested "
    "def, exempt) may block"
)


def check(source: SourceFile) -> List[Violation]:
    # a blocking call can sit under BOTH scopes at once (a state-lock
    # with-block nested inside a launch-section def); one flagged line
    # is one violation, so dedup on (path, line) keeping the first
    # (outermost) scope's message
    out: List[Violation] = []
    seen: set = set()
    for node in ast.walk(source.tree):
        if isinstance(node, ast.With) and _is_state_lock_with(node):
            body, msg = node.body, _STATE_MSG
        elif isinstance(node, ast.With) and _is_launch_lock_with(node):
            body, msg = node.body, _LAUNCH_MSG
        elif _is_launch_section_def(node):
            body, msg = node.body, _LAUNCH_MSG
        else:
            continue
        for sub in _walk_skip_defs(body):
            flagged = _blocking_call(sub)
            if flagged:
                key = (source.path, sub.lineno)
                if key in seen:
                    continue
                seen.add(key)
                out.append(
                    Violation(
                        rule=RULE,
                        path=source.path,
                        line=sub.lineno,
                        message=msg.format(flagged=flagged),
                    )
                )
    return out
