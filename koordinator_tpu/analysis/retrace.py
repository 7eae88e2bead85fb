"""retrace-hazard: things that silently recompile the hot cycle.

Six statically detectable shapes of the PR-1 name-tuple retrace:

1. Python control flow (``if``/``while``/``assert``) on a TRACED
   parameter inside a jitted function.  Branching on a tracer either
   raises (abstract truthiness) or — worse, for weak types — forces a
   concretization; branching on values that vary per cycle retraces.
   ``x is None`` / ``x is not None`` checks are exempt: pytree presence
   is part of the trace signature, branching on it is the idiomatic way
   to specialize a jitted function.
2. Unhashable or string-tuple STATIC arguments at call sites of a
   module-local jitted function: a list/dict/set static arg raises at
   call time, and a tuple-of-str static arg (names!) keys the jit cache
   on payload data — one retrace per distinct name set.
3. Name/str payloads registered as pytree METADATA: a field called
   ``name``/``names`` (or ``*_name``/``*_names``) in ``meta_fields`` of
   ``register_dataclass`` (or an aux_data tuple of
   ``register_pytree_node``) keys every downstream jit cache on object
   names — the exact PR-1 bug.  Intentional embedded-API registrations
   carry a reasoned disable tag instead.
4. TRACED wave knobs at a jit boundary: a jitted function that takes
   ``wave`` or ``top_m`` without declaring it static traces the wave
   width into the program — the loop structure then re-specializes on
   every distinct value, a silent per-cycle retrace of the round-based
   cycle (parallel/shard_assign.py passes them via ``static_argnames``).
   ISSUE 7 extends the same shape to the MESH knobs: a traced ``mesh``
   / device-count / shard-width argument at a jit boundary
   re-specializes the partitioned program per value exactly the same
   way (parallel/mesh.py, solver/resident.py and shard_assign.py all
   declare ``mesh`` static), and a shard_map BODY taking one of these
   names as a parameter receives it as a traced per-shard operand —
   the mesh belongs in the ``shard_map(..., mesh=)`` binding or the
   closure, never in the operand list.
5. UNHASHABLE / UNFROZEN CycleConfig term configs (ISSUE 15): the
   config rides jit as a static argument, so every dataclass reachable
   from CycleConfig's field annotations (the fused scoring-term
   configs, the LoadAware args, ...) must be ``frozen=True``, must not
   carry a mutable field default, and every mapping-typed field must
   go through ``_freeze`` in ``__post_init__`` — a raw dict field
   either raises at the first jit call (unhashable) or, frozen into an
   arbitrary-order tuple by a caller, mints one retrace per ordering.
6. TRACED candidate counts/widths at a jit boundary (ISSUE 16): the
   sparse engine's candidate width C is configuration (it rides the
   static CycleConfig), and per-pod candidate COUNTS vary per cycle —
   a jitted function taking ``num_candidates``/``c_width``/... as a
   traced argument specializes the [P, C] program per distinct value,
   one silent retrace per feasibility change.  Pad the candidate list
   to C with out-of-range sentinels instead (solver/candidates.py):
   pad the candidate list, don't trace the count.
"""

from __future__ import annotations

import ast
from typing import List, Set

from koordinator_tpu.analysis import jitscope
from koordinator_tpu.analysis.core import SourceFile, Violation

RULE = "retrace-hazard"

_NAMEY = ("name", "names")


def _is_namey(field: str) -> bool:
    return field in _NAMEY or any(
        field.endswith("_" + suffix) for suffix in _NAMEY
    )


# attribute reads that are concrete at trace time: branching on them
# specializes per shape bucket, it does not retrace per cycle
_TRACE_CONST_ATTRS = ("shape", "ndim", "dtype", "size")


def _exempt_names(test: ast.AST) -> Set[int]:
    """ids of Name nodes used only in trace-time-constant positions:
    ``x is (not) None`` compares, ``x.shape``/``.ndim``/``.dtype``/
    ``.size`` reads, and ``len(x)`` calls."""
    exempt: Set[int] = set()
    for node in ast.walk(test):
        if isinstance(node, ast.Compare):
            operands = [node.left] + list(node.comparators)
            if all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops
            ) and any(
                isinstance(o, ast.Constant) and o.value is None
                for o in operands
            ):
                for o in operands:
                    if isinstance(o, ast.Name):
                        exempt.add(id(o))
        elif isinstance(node, ast.Attribute):
            if node.attr in _TRACE_CONST_ATTRS and isinstance(
                node.value, ast.Name
            ):
                exempt.add(id(node.value))
        elif isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Name) and fn.id == "len":
                for arg in node.args:
                    if isinstance(arg, ast.Name):
                        exempt.add(id(arg))
    return exempt


def _tracer_branches(source: SourceFile, spec: jitscope.JitSpec) -> List[Violation]:
    out: List[Violation] = []
    traced = set(spec.params()) - spec.static_params()
    # closures run under this trace, so branches on the enclosing
    # traced params inside them count; nested JITTED defs get their own
    # pass with their own parameter namespace
    for node in jitscope.scope_walk(spec.func, into_closures=True):
        if isinstance(node, (ast.If, ast.While)):
            test, kind = node.test, type(node).__name__.lower()
        elif isinstance(node, ast.Assert):
            test, kind = node.test, "assert"
        else:
            continue
        exempt = _exempt_names(test)
        for name in ast.walk(test):
            if (
                isinstance(name, ast.Name)
                and isinstance(name.ctx, ast.Load)
                and name.id in traced
                and id(name) not in exempt
            ):
                out.append(
                    Violation(
                        rule=RULE,
                        path=source.path,
                        line=node.lineno,
                        message=(
                            f"Python {kind} on traced argument "
                            f"'{name.id}' inside jitted "
                            f"{spec.name}(); use lax.cond/jnp.where, or "
                            "declare it static if it is configuration"
                        ),
                    )
                )
                break
    return out


def _static_call_args(source: SourceFile) -> List[Violation]:
    """Unhashable / tuple-of-str values passed to static params of
    module-local jitted functions."""
    specs = {
        s.name: s for s in jitscope.jitted_defs(source.tree)
    }
    specs.update(jitscope.jit_assignments(source.tree))
    out: List[Violation] = []
    for node in ast.walk(source.tree):
        if not isinstance(node, ast.Call) or not isinstance(node.func, ast.Name):
            continue
        spec = specs.get(node.func.id)
        if spec is None:
            continue
        static = spec.static_params()
        if not static and not spec.static_nums:
            continue
        pos = spec.positional_params()
        candidates = []
        for i, arg in enumerate(node.args):
            pname = pos[i] if i < len(pos) else None
            if i in spec.static_nums or (pname and pname in static):
                candidates.append((pname or f"#{i}", arg))
        for kw in node.keywords:
            if kw.arg in static:
                candidates.append((kw.arg, kw.value))
        for pname, val in candidates:
            if isinstance(val, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                                ast.DictComp, ast.SetComp)):
                out.append(
                    Violation(
                        rule=RULE,
                        path=source.path,
                        line=val.lineno,
                        message=(
                            f"unhashable {type(val).__name__.lower()} passed "
                            f"as static arg '{pname}' of {spec.name}(); jit "
                            "static args must be hashable"
                        ),
                    )
                )
            elif isinstance(val, ast.Tuple) and val.elts and all(
                isinstance(e, ast.Constant) and isinstance(e.value, str)
                for e in val.elts
            ):
                out.append(
                    Violation(
                        rule=RULE,
                        path=source.path,
                        line=val.lineno,
                        message=(
                            f"tuple-of-str passed as static arg '{pname}' of "
                            f"{spec.name}(): the jit cache keys on the string "
                            "payload — one retrace per distinct value (the "
                            "PR-1 name-tuple bug); keep names host-side"
                        ),
                    )
                )
    return out


# cycle-batching knobs that select loop structure: traced values here
# mean one retrace per distinct width (rule docstring, shape 4)
_WAVE_STATIC_PARAMS = ("wave", "top_m")

# mesh-partitioning knobs (ISSUE 7): the mesh, the device count and the
# shard width all select the PARTITIONED program structure — traced,
# each distinct value re-specializes the sharded cycle silently
_MESH_STATIC_PARAMS = (
    "mesh", "device_count", "n_devices", "num_devices",
    "n_shards", "num_shards", "shard_width",
)

# incremental-rescore knobs (ISSUE 9): a dirty COUNT at a jit boundary
# is the same hazard shape — delta sizes vary per cycle, so a traced
# n_dirty/dirty_width specializes the rescore per distinct count, one
# silent retrace per delta size.  The count must never cross the
# boundary at all: dirty indices ride bucket-PADDED index vectors whose
# pad slots carry an out-of-range target dropped by mode="drop"
# (solver/incremental.py), exactly the delta scatter's discipline.
_DIRTY_STATIC_PARAMS = (
    "n_dirty", "num_dirty", "dirty_count", "dirty_width",
    "n_dirty_nodes", "n_dirty_pods",
)

# sparse candidate knobs (ISSUE 16): the candidate width selects the
# [P, C] program shape (configuration — it rides the static
# CycleConfig) and per-pod candidate counts vary with every
# feasibility change; traced, either one mints a retrace per distinct
# value.  The candidate list is padded to C with out-of-range
# sentinels (solver/candidates.py) so neither ever crosses a jit
# boundary: pad the candidate list, don't trace the count.
_CAND_STATIC_PARAMS = (
    "num_candidates", "n_candidates", "candidate_count",
    "candidate_width", "cand_width", "c_width",
)


def _traced_wave_knobs(source: SourceFile, spec: jitscope.JitSpec) -> List[Violation]:
    if spec.func is None:
        return []
    static = spec.static_params()
    out: List[Violation] = []
    for pname in spec.params():
        if pname in static:
            continue
        if pname in _WAVE_STATIC_PARAMS:
            out.append(
                Violation(
                    rule=RULE,
                    path=source.path,
                    line=spec.line,
                    message=(
                        f"jit boundary {spec.name}() takes '{pname}' as a "
                        "TRACED argument: the wave width selects loop "
                        "structure, so every distinct value retraces the "
                        "cycle silently; declare it in static_argnames "
                        "(it is configuration, like cfg)"
                    ),
                )
            )
        elif pname in _MESH_STATIC_PARAMS:
            out.append(
                Violation(
                    rule=RULE,
                    path=source.path,
                    line=spec.line,
                    message=(
                        f"jit boundary {spec.name}() takes '{pname}' as a "
                        "TRACED argument: the mesh/device-count/shard "
                        "width selects the partitioned program structure, "
                        "so every distinct value retraces the sharded "
                        "cycle silently; declare it in static_argnames "
                        "(it is configuration, like cfg)"
                    ),
                )
            )
        elif pname in _DIRTY_STATIC_PARAMS:
            out.append(
                Violation(
                    rule=RULE,
                    path=source.path,
                    line=spec.line,
                    message=(
                        f"jit boundary {spec.name}() takes '{pname}' as a "
                        "TRACED argument: delta sizes vary per cycle, so "
                        "a traced dirty count retraces the rescore per "
                        "distinct value; don't pass the count at all — "
                        "pad the dirty-index vector to a power-of-two "
                        "bucket with out-of-range slots mode=\"drop\" "
                        "discards (solver/incremental.py)"
                    ),
                )
            )
        elif pname in _CAND_STATIC_PARAMS:
            out.append(
                Violation(
                    rule=RULE,
                    path=source.path,
                    line=spec.line,
                    message=(
                        f"jit boundary {spec.name}() takes '{pname}' as a "
                        "TRACED argument: candidate counts vary with every "
                        "feasibility change (and the width is "
                        "configuration, like cfg), so each distinct value "
                        "retraces the sparse [P, C] program silently; "
                        "pad the candidate list, don't trace the count "
                        "(solver/candidates.py pads to C with "
                        "out-of-range sentinels)"
                    ),
                )
            )
    return out


def _shard_map_body_knobs(source: SourceFile) -> List[Violation]:
    """Mesh knobs in a shard_map BODY's parameter list (rule shape 4,
    the shard_map boundary): operands of ``shard_map`` are traced
    per-shard arrays, so a body taking ``mesh``/``n_devices``/... as a
    parameter would receive the partitioning configuration as a traced
    value.  The mesh rides the ``shard_map(..., mesh=)`` binding (or
    the closure); flag the def.

    Resolution is LEXICALLY SCOPED: a ``shard_map(body, ...)`` call
    resolves ``body`` among the defs of its own enclosing scope first,
    then the module scope — a file-wide name table would collide on
    same-named nested defs (``body`` is the natural name; two unrelated
    ``body`` defs in different functions must not flag each other)."""

    def scope_defs_and_calls(scope_body):
        """One lexical scope's direct defs and the calls in it, NOT
        descending into nested function bodies (each gets its own
        pass)."""
        defs, calls = {}, []
        stack = list(scope_body)
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs[node.name] = node  # visible here; body is its own scope
                continue
            if isinstance(node, ast.Call):
                calls.append(node)
            stack.extend(ast.iter_child_nodes(node))
        return defs, calls

    module_defs, _ = scope_defs_and_calls(source.tree.body)
    scopes = [source.tree] + [
        n for n in ast.walk(source.tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ]
    out: List[Violation] = []
    seen = set()
    for scope in scopes:
        defs, calls = scope_defs_and_calls(scope.body)
        for node in calls:
            fn = node.func
            name = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else ""
            )
            if not name.endswith("shard_map"):
                continue
            if not node.args or not isinstance(node.args[0], ast.Name):
                continue
            body = defs.get(node.args[0].id) or module_defs.get(
                node.args[0].id
            )
            if body is None or id(body) in seen:
                continue
            seen.add(id(body))
            params = [a.arg for a in (
                body.args.posonlyargs + body.args.args + body.args.kwonlyargs
            )]
            for pname in params:
                if pname in _MESH_STATIC_PARAMS:
                    out.append(
                        Violation(
                            rule=RULE,
                            path=source.path,
                            line=body.lineno,
                            message=(
                                f"shard_map body {body.name}() takes "
                                f"'{pname}' as a parameter: shard_map "
                                "operands are traced per-shard values, "
                                "so the mesh/device-count/shard width "
                                "would retrace the partitioned program "
                                "per value; bind it via "
                                "shard_map(..., mesh=) or the closure "
                                "instead"
                            ),
                        )
                    )
    return out


# annotation identifiers that mean "this field is a mapping and must be
# frozen to a sorted tuple before it can be a static jit argument"
_MAPPINGY_TYPES = {"ResMap", "Mapping", "MutableMapping", "Dict", "dict"}


def _annotation_names(ann) -> Set[str]:
    """Identifier names mentioned by a field annotation — handles
    Name/Attribute/Subscript forms and string annotations ("X | None")."""
    import re as _re

    if ann is None:
        return set()
    if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
        return set(_re.findall(r"[A-Za-z_][A-Za-z0-9_]*", ann.value))
    names: Set[str] = set()
    for node in ast.walk(ann):
        if isinstance(node, ast.Name):
            names.add(node.id)
        elif isinstance(node, ast.Attribute):
            names.add(node.attr)
    return names


def _dataclass_frozen(cls: ast.ClassDef):
    """(is_dataclass, frozen) from the decorator list."""
    for dec in cls.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = target.attr if isinstance(target, ast.Attribute) else (
            target.id if isinstance(target, ast.Name) else ""
        )
        if name != "dataclass":
            continue
        frozen = False
        if isinstance(dec, ast.Call):
            for kw in dec.keywords:
                if kw.arg == "frozen" and isinstance(kw.value, ast.Constant):
                    frozen = bool(kw.value.value)
        return True, frozen
    return False, False


def _frozen_fields(cls: ast.ClassDef) -> Set[str]:
    """Field names ``__post_init__`` re-binds through ``_freeze``:
    ``object.__setattr__(self, "field", _freeze(...))``."""
    out: Set[str] = set()
    for node in cls.body:
        if not (
            isinstance(node, ast.FunctionDef)
            and node.name == "__post_init__"
        ):
            continue
        for call in ast.walk(node):
            if not (
                isinstance(call, ast.Call)
                and isinstance(call.func, ast.Attribute)
                and call.func.attr == "__setattr__"
                and len(call.args) == 3
                and isinstance(call.args[1], ast.Constant)
                and isinstance(call.args[1].value, str)
            ):
                continue
            value = call.args[2]
            if (
                isinstance(value, ast.Call)
                and isinstance(value.func, ast.Name)
                and value.func.id == "_freeze"
            ):
                out.add(call.args[1].value)
    return out


def _term_config_classes(source: SourceFile):
    """The CycleConfig dataclass plus every module-local dataclass
    reachable from its field annotations (the term configs of ISSUE 15,
    LoadAwareArgs, ...).  Empty when the file defines no CycleConfig."""
    classes = {
        n.name: n for n in ast.walk(source.tree)
        if isinstance(n, ast.ClassDef)
    }
    if "CycleConfig" not in classes:
        return {}
    reach = {}
    queue = ["CycleConfig"]
    while queue:
        name = queue.pop()
        if name in reach:
            continue
        cls = classes.get(name)
        if cls is None:
            continue
        reach[name] = cls
        for node in cls.body:
            if isinstance(node, ast.AnnAssign):
                for ref in _annotation_names(node.annotation):
                    if ref in classes and ref not in reach:
                        queue.append(ref)
    return reach


def _term_config_fields(source: SourceFile) -> List[Violation]:
    """Rule shape 5 (ISSUE 15): CycleConfig and its term configs are
    STATIC jit arguments — unfrozen dataclasses, mutable field
    defaults, and mapping-typed fields that never pass through
    ``_freeze`` in ``__post_init__`` all fail lint."""
    out: List[Violation] = []
    for name, cls in _term_config_classes(source).items():
        is_dc, frozen = _dataclass_frozen(cls)
        if not is_dc:
            continue  # a plain class is not a config dataclass
        if not frozen:
            out.append(Violation(
                rule=RULE, path=source.path, line=cls.lineno,
                message=(
                    f"config dataclass {name} reachable from CycleConfig "
                    "is not frozen=True: CycleConfig rides jit as a "
                    "static argument, so every nested config must be "
                    "immutable and hashable"
                ),
            ))
        freezes = _frozen_fields(cls)
        for node in cls.body:
            if not isinstance(node, ast.AnnAssign) or not isinstance(
                node.target, ast.Name
            ):
                continue
            field = node.target.id
            if isinstance(node.value, (ast.List, ast.Dict, ast.Set,
                                       ast.ListComp, ast.DictComp,
                                       ast.SetComp)):
                out.append(Violation(
                    rule=RULE, path=source.path, line=node.lineno,
                    message=(
                        f"{name}.{field} has a mutable "
                        f"{type(node.value).__name__.lower()} default: "
                        "term-config fields must be hashable (freeze "
                        "mappings to sorted tuples via _freeze)"
                    ),
                ))
            ann_names = _annotation_names(node.annotation)
            if ann_names & _MAPPINGY_TYPES and field not in freezes:
                # a default that is already a _freeze(...) call AND
                # never reassigned is equally safe only if callers
                # cannot pass a raw dict — they can, so the
                # __post_init__ freeze is required regardless
                out.append(Violation(
                    rule=RULE, path=source.path, line=node.lineno,
                    message=(
                        f"{name}.{field} is mapping-typed but "
                        "__post_init__ never passes it through "
                        "_freeze: a caller-supplied dict makes the "
                        "config unhashable at the jit boundary "
                        "(mappings must go through _freeze)"
                    ),
                ))
    return out


def _pytree_metadata(source: SourceFile) -> List[Violation]:
    out: List[Violation] = []
    for node in ast.walk(source.tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        attr = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else ""
        )
        if attr != "register_dataclass":
            continue
        for kw in node.keywords:
            if kw.arg != "meta_fields":
                continue
            for field in jitscope._literal_strs(kw.value):
                if _is_namey(field):
                    out.append(
                        Violation(
                            rule=RULE,
                            path=source.path,
                            line=kw.value.lineno,
                            message=(
                                f"pytree meta field '{field}' looks like an "
                                "object-name payload: static metadata keys "
                                "every jit cache on it, so a changed name "
                                "retraces the cycle (the PR-1 bug); carry "
                                "names host-side or tag with a reason"
                            ),
                        )
                    )
    return out


def check(source: SourceFile) -> List[Violation]:
    out: List[Violation] = []
    for spec in jitscope.jitted_defs(source.tree):
        out.extend(_tracer_branches(source, spec))
        out.extend(_traced_wave_knobs(source, spec))
    for spec in jitscope.jit_assignments(source.tree).values():
        out.extend(_traced_wave_knobs(source, spec))
    out.extend(_shard_map_body_knobs(source))
    out.extend(_static_call_args(source))
    out.extend(_pytree_metadata(source))
    out.extend(_term_config_fields(source))
    return out
