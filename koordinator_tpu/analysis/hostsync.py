"""host-sync-in-jit: device->host round trips inside jitted functions.

A ``np.asarray``/``.item()``/``float()``/``int()`` on a jnp value inside
a jitted function forces a concretization during trace — either a tracer
error or, through weak-type escape hatches, a silent per-call host sync
that turns the single-device program into a ping-pong (every round
trip stalls the device until the host answers).  A bare
``print()`` traces once and then never runs again — debugging that
"works" until the cache warms; ``jax.debug.print`` is the traced form.

Scope: bodies of jitted functions (decorator or partial spelling),
excluding nested non-jitted closures only when they are themselves
jit-wrapped.  ``int()``/``float()`` are flagged only when applied to an
obvious jnp/jax expression — ``int(shape[0])`` and enum coercions are
host-side constants and stay legal.

The obs span API (koordinator_tpu/obs/spans.py) is covered too: a
``begin_span``/``end_span``/``.span()``/``.note()``, or a
``profiled()``/``tag_cycle()`` profiler annotation, inside jitted code
would record trace-time wall clock ONCE and then never run again (the
bare-print trap), and a note of a live tracer value would force a
concretization.  Telemetry instruments around device programs, never
inside them — that is the subsystem's zero-overhead contract
(tests/test_resident_warm.py locks it in at zero jit cache misses).
"""

from __future__ import annotations

import ast
from typing import List

from koordinator_tpu.analysis import jitscope
from koordinator_tpu.analysis.core import SourceFile, Violation

RULE = "host-sync-in-jit"

_NP_MODULES = ("np", "numpy", "onp", "_np")
_JNP_MODULES = ("jnp", "jax")
_NP_SYNC_FUNCS = ("asarray", "array", "copy")
# obs span API: begin/end are unambiguous names; span/note/commit only
# count on a receiver that is recognizably the telemetry/span recorder
_OBS_METHODS = ("begin_span", "end_span")
_OBS_RECEIVERS = ("obs", "spans", "telemetry", "recorder", "span_recorder")
_OBS_RECEIVER_METHODS = ("span", "note", "commit", "commit_cycle")
# obs profiler annotations, called bare or off the module
_OBS_FUNCS = ("profiled", "tag_cycle")


def _root_module(node: ast.AST) -> str:
    while isinstance(node, ast.Attribute):
        node = node.value
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _attr_chain(node: ast.AST):
    """All names along an attribute chain: ``self.telemetry.spans.note``
    -> ("self", "telemetry", "spans", "note")."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return tuple(reversed(parts))


def _mentions_jnp(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in _JNP_MODULES:
            return True
    return False


def check(source: SourceFile) -> List[Violation]:
    out: List[Violation] = []
    for spec in jitscope.jitted_defs(source.tree):
        # closures (lax.scan step fns) run under this trace and are
        # scanned; nested JITTED defs get their own pass — descending
        # into them here would double-report their bodies
        for node in jitscope.scope_walk(spec.func, into_closures=True):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            # np.asarray / np.array / np.copy on anything
            if isinstance(fn, ast.Attribute) and (
                _root_module(fn) in _NP_MODULES
                and fn.attr in _NP_SYNC_FUNCS
            ):
                out.append(
                    Violation(
                        rule=RULE,
                        path=source.path,
                        line=node.lineno,
                        message=(
                            f"np.{fn.attr}() inside jitted {spec.name}() "
                            "forces a device->host sync per call; use "
                            "jnp equivalents, or hoist to the caller"
                        ),
                    )
                )
            # .item() on anything
            elif isinstance(fn, ast.Attribute) and fn.attr == "item":
                out.append(
                    Violation(
                        rule=RULE,
                        path=source.path,
                        line=node.lineno,
                        message=(
                            f".item() inside jitted {spec.name}() is a "
                            "host sync; keep the value on device"
                        ),
                    )
                )
            # float()/int() over an expression that touches jnp/jax
            elif (
                isinstance(fn, ast.Name)
                and fn.id in ("float", "int", "bool")
                and node.args
                and _mentions_jnp(node.args[0])
            ):
                out.append(
                    Violation(
                        rule=RULE,
                        path=source.path,
                        line=node.lineno,
                        message=(
                            f"{fn.id}() on a jnp value inside jitted "
                            f"{spec.name}() concretizes the tracer (host "
                            "sync); compute on device or hoist the check"
                        ),
                    )
                )
            # obs span/telemetry API: trace-time-only wall clock (and a
            # tracer note forces a host sync); instrument OUTSIDE jit
            elif (isinstance(fn, ast.Name) and fn.id in _OBS_FUNCS) or (
                isinstance(fn, ast.Attribute) and (
                    fn.attr in _OBS_METHODS + _OBS_FUNCS
                    or (
                        fn.attr in _OBS_RECEIVER_METHODS
                        and any(
                            seg in _OBS_RECEIVERS
                            for seg in _attr_chain(fn)[:-1]
                        )
                    )
                )
            ):
                called = fn.id if isinstance(fn, ast.Name) else fn.attr
                out.append(
                    Violation(
                        rule=RULE,
                        path=source.path,
                        line=node.lineno,
                        message=(
                            f"obs span API .{called}() inside jitted "
                            f"{spec.name}() records trace-time wall "
                            "clock once (and concretizes tracer "
                            "arguments); instrument around the jitted "
                            "call, not inside it"
                        ),
                    )
                )
            # bare print(): traces once, then silently never runs
            elif isinstance(fn, ast.Name) and fn.id == "print":
                out.append(
                    Violation(
                        rule=RULE,
                        path=source.path,
                        line=node.lineno,
                        message=(
                            f"print() inside jitted {spec.name}() runs only "
                            "at trace time; use jax.debug.print"
                        ),
                    )
                )
    return out
