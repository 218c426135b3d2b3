"""retrace pass: jitted functions must not pick up per-request Python state.

Every recompile of a dispatch kernel stalls serving for seconds; the
engine's kernels are shaped so that everything varying per request is a
traced array and everything static is bound once at construction
(``functools.partial`` kwargs, ``static_argnums``/``static_argnames``).
This pass checks that discipline stays intact:

  R1  ``jax.jit(...)`` created inside a for/while loop — a fresh jit
      wrapper per iteration defeats the compile cache
  R2  a jitted def/lambda closing over a loop variable of an enclosing
      scope — late binding means the trace constant silently varies
  R3  ``if``/``while``/ternary branching on a traced value inside a
      jitted body — TracerBoolConversionError at best, shape-dependent
      retrace at worst.  Static launder points: ``.shape``/``.ndim``/
      ``.dtype``/``.size`` attribute reads, ``len()``, ``isinstance()``,
      partial-bound kwargs and declared static args
  R4  list/dict/set literals passed in a static position — unhashable,
      so the jit cache lookup itself raises

Waive with ``# graftlint: allow(retrace) why``.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .core import (Context, Finding, SourceFile, allowed, attach_parents,
                   enclosing_function, make_finding, qualname_of)

RULE = "retrace"

_STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}
_STATIC_CALLS = {"len", "isinstance", "issubclass", "type"}


def _is_jax_jit(call: ast.Call) -> bool:
    f = call.func
    if isinstance(f, ast.Attribute) and f.attr == "jit" \
            and isinstance(f.value, ast.Name) and f.value.id in ("jax", "_jax"):
        return True
    return False


def _is_partial(call: ast.Call) -> bool:
    f = call.func
    # _named_partial: the engine's partial-with-a-name (same binding
    # semantics; servers/engine.py).
    if isinstance(f, ast.Name) and f.id in ("partial", "_named_partial"):
        return True
    if isinstance(f, ast.Attribute) and f.attr == "partial" \
            and isinstance(f.value, ast.Name) and f.value.id == "functools":
        return True
    return False


def _static_names_from_kwargs(kws: Sequence[ast.keyword]) -> Tuple[Set[str], Set[int]]:
    names: Set[str] = set()
    nums: Set[int] = set()
    for kw in kws:
        if kw.arg == "static_argnames":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, str):
                    names.add(n.value)
        elif kw.arg == "static_argnums":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, int):
                    nums.add(n.value)
    return names, nums


class _Jitted:
    """A function object known to be traced by jax.jit."""

    def __init__(self, fn: ast.AST, static_names: Set[str],
                 static_nums: Set[int], bound_kwargs: Set[str],
                 public_name: str):
        self.fn = fn  # FunctionDef or Lambda
        self.static_names = static_names
        self.static_nums = static_nums
        self.bound_kwargs = bound_kwargs
        self.public_name = public_name  # name call sites use, "" if unknown


def _decorator_jit(fn: ast.FunctionDef) -> Optional[Tuple[Set[str], Set[int]]]:
    for dec in fn.decorator_list:
        if isinstance(dec, ast.Attribute) and dec.attr == "jit" \
                and isinstance(dec.value, ast.Name) and dec.value.id in ("jax", "_jax"):
            return set(), set()
        if isinstance(dec, ast.Call):
            if _is_jax_jit(dec):
                return _static_names_from_kwargs(dec.keywords)
            if _is_partial(dec) and dec.args and isinstance(dec.args[0], (ast.Attribute, ast.Name)):
                inner = dec.args[0]
                is_jit = (isinstance(inner, ast.Attribute) and inner.attr == "jit") \
                    or (isinstance(inner, ast.Name) and inner.id == "jit")
                if is_jit:
                    return _static_names_from_kwargs(dec.keywords)
    return None


def _collect_jitted(sf: SourceFile) -> List[_Jitted]:
    out: List[_Jitted] = []
    # name -> def node, for resolving jax.jit(fn_name) and self._x_impl
    defs: Dict[str, ast.FunctionDef] = {}
    for node in ast.walk(sf.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs[node.name] = node

    for fn in list(defs.values()):
        res = _decorator_jit(fn)
        if res is not None:
            out.append(_Jitted(fn, res[0], res[1], set(), fn.name))

    for node in ast.walk(sf.tree):
        if not (isinstance(node, ast.Call) and _is_jax_jit(node) and node.args):
            continue
        static_names, static_nums = _static_names_from_kwargs(node.keywords)
        target = node.args[0]
        bound: Set[str] = set()
        if isinstance(target, ast.Call) and _is_partial(target):
            bound = {kw.arg for kw in target.keywords if kw.arg}
            target = target.args[0] if target.args else None
        public = ""
        parent = getattr(node, "_graftlint_parent", None)
        if isinstance(parent, ast.Assign) and len(parent.targets) == 1:
            t = parent.targets[0]
            if isinstance(t, ast.Name):
                public = t.id
            elif isinstance(t, ast.Attribute):
                public = t.attr
        fn_node: Optional[ast.AST] = None
        if isinstance(target, ast.Lambda):
            fn_node = target
        elif isinstance(target, ast.Name) and target.id in defs:
            fn_node = defs[target.id]
        elif isinstance(target, ast.Attribute) and target.attr in defs:
            fn_node = defs[target.attr]
        if fn_node is not None:
            out.append(_Jitted(fn_node, static_names, static_nums, bound, public))
    return out


def _param_names(fn: ast.AST) -> List[str]:
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args]
    return names


def _expr_static(e: ast.AST, traced: Set[str]) -> bool:
    """True when `e` cannot carry a traced value (safe to branch on)."""
    if isinstance(e, ast.Attribute) and e.attr in _STATIC_ATTRS:
        return True
    if isinstance(e, ast.Call):
        f = e.func
        if isinstance(f, ast.Name) and f.id in _STATIC_CALLS:
            return True
    if isinstance(e, ast.Name):
        return e.id not in traced
    if isinstance(e, ast.Constant):
        return True
    return all(_expr_static(c, traced) for c in ast.iter_child_nodes(e)
               if isinstance(c, ast.expr))


def _traced_locals(fn: ast.AST, traced: Set[str]) -> Set[str]:
    traced = set(traced)
    body = fn.body if isinstance(fn.body, list) else []
    for _ in range(2):
        for node in ast.walk(fn):
            targets: List[ast.AST] = []
            value: Optional[ast.AST] = None
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets, value = [node.target], node.value
            elif isinstance(node, ast.AugAssign):
                targets, value = [node.target], node.value
            if value is None:
                continue
            is_traced = not _expr_static(value, traced)
            for t in targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name):
                        if is_traced:
                            traced.add(n.id)
                        else:
                            traced.discard(n.id)
    return traced


def _loop_targets_above(fn: ast.AST) -> Set[str]:
    """Names bound as for-loop targets in scopes enclosing `fn`."""
    out: Set[str] = set()
    cur = getattr(fn, "_graftlint_parent", None)
    while cur is not None:
        if isinstance(cur, ast.For):
            for n in ast.walk(cur.target):
                if isinstance(n, ast.Name):
                    out.add(n.id)
        cur = getattr(cur, "_graftlint_parent", None)
    return out


def _free_names(fn: ast.AST) -> Set[str]:
    bound = set(_param_names(fn))
    a = fn.args
    bound.update(p.arg for p in a.kwonlyargs)
    if a.vararg:
        bound.add(a.vararg.arg)
    if a.kwarg:
        bound.add(a.kwarg.arg)
    loads: Set[str] = set()
    nodes = ast.walk(fn.body if isinstance(fn, ast.Lambda) else fn)
    for node in nodes:
        if isinstance(node, ast.Name):
            if isinstance(node.ctx, ast.Store):
                bound.add(node.id)
            else:
                loads.add(node.id)
    return loads - bound


def run(files: List[SourceFile], ctx: Context) -> List[Finding]:
    findings: List[Finding] = []
    for sf in files:
        attach_parents(sf.tree)
        jitted = _collect_jitted(sf)

        # R1: jit wrapper built inside a loop
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Call) and _is_jax_jit(node):
                cur = getattr(node, "_graftlint_parent", None)
                while cur is not None:
                    if isinstance(cur, (ast.For, ast.While)):
                        fn = enclosing_function(node)
                        if not allowed(sf, RULE, node.lineno,
                                       fn.lineno if fn else 0):
                            findings.append(make_finding(
                                sf, RULE, node.lineno,
                                "jax.jit created inside a loop — a fresh "
                                "wrapper per iteration defeats the compile cache",
                                "hoist the jit out of the loop and pass the "
                                "varying value as a traced argument",
                                qualname_of(node)))
                        break
                    cur = getattr(cur, "_graftlint_parent", None)

        for j in jitted:
            fn = j.fn
            fn_line = fn.lineno
            qn = qualname_of(fn) or j.public_name

            # R2: closure over an enclosing loop variable
            hazards = _free_names(fn) & _loop_targets_above(fn)
            for name in sorted(hazards):
                if allowed(sf, RULE, fn_line):
                    break
                findings.append(make_finding(
                    sf, RULE, fn_line,
                    f"jitted function closes over loop variable '{name}' — "
                    "late binding makes the baked-in constant vary per "
                    "iteration (silent retrace or wrong results)",
                    f"bind it explicitly: functools.partial(fn, {name}={name}) "
                    "or pass it as a traced argument",
                    qn))

            # R3: branch on traced value
            params = _param_names(fn)
            static = set(j.static_names) | set(j.bound_kwargs)
            for i in j.static_nums:
                if i < len(params):
                    static.add(params[i])
            traced0 = {p for p in params if p not in static and p != "self"}
            traced = _traced_locals(fn, traced0)
            body_nodes = ast.walk(fn)
            for node in body_nodes:
                test: Optional[ast.AST] = None
                kind = ""
                if isinstance(node, (ast.If, ast.While)):
                    test, kind = node.test, type(node).__name__.lower()
                elif isinstance(node, ast.IfExp):
                    test, kind = node.test, "conditional expression"
                if test is None or _expr_static(test, traced):
                    continue
                efn = enclosing_function(node)
                if allowed(sf, RULE, node.lineno, efn.lineno if efn else 0):
                    continue
                findings.append(make_finding(
                    sf, RULE, node.lineno,
                    f"{kind} branches on a traced value inside a jitted "
                    "function — TracerBoolConversionError or per-shape retrace",
                    "replace with jnp.where / lax.cond, or mark the argument "
                    "static if it is genuinely per-config",
                    qualname_of(node) or qn))

            # R4: unhashable literal at a static call site
            if j.public_name and (j.static_nums or j.static_names):
                _check_static_call_sites(sf, j, findings)
    return findings


def _check_static_call_sites(sf: SourceFile, j: _Jitted,
                             findings: List[Finding]) -> None:
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = f.attr if isinstance(f, ast.Attribute) else \
            (f.id if isinstance(f, ast.Name) else "")
        if name != j.public_name:
            continue
        # positional static args (account for bound self when calling a method)
        params = _param_names(j.fn)
        offset = 1 if params[:1] == ["self"] else 0
        for i, arg in enumerate(node.args):
            if (i + offset) in j.static_nums and \
                    isinstance(arg, (ast.List, ast.Dict, ast.Set)):
                _flag_unhashable(sf, node, arg, j, findings)
        for kw in node.keywords:
            if kw.arg in j.static_names and \
                    isinstance(kw.value, (ast.List, ast.Dict, ast.Set)):
                _flag_unhashable(sf, node, kw.value, j, findings)


def _flag_unhashable(sf: SourceFile, call: ast.Call, arg: ast.AST,
                     j: _Jitted, findings: List[Finding]) -> None:
    efn = enclosing_function(call)
    if allowed(sf, RULE, call.lineno, efn.lineno if efn else 0):
        return
    findings.append(make_finding(
        sf, RULE, call.lineno,
        f"unhashable {type(arg).__name__.lower()} literal passed in a "
        f"static position of jitted '{j.public_name}' — the jit cache "
        "lookup raises TypeError",
        "pass a tuple (hashable) or make the argument traced",
        qualname_of(call)))
