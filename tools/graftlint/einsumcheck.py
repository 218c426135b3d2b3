"""Einsum silent-broadcast and masked-softmax dtype checks (graftnum).

``jnp.einsum`` follows NumPy broadcasting: a repeated label whose two
bindings have different sizes does NOT raise when one of them is 1 —
it silently broadcasts.  That is exactly the PR 16 bug where a KV-head
dim expanded with ``[:, None]`` (size 1) met the real head dim under
the same label and every KV head summed ALL heads' values, with no
shape error and plausible-looking output.

Rule ``einsum-broadcast``: for every ``jnp.einsum`` / ``lax.dot_general``
whose operand shapes are statically traceable (tuple-unpacked
``.shape``, ``reshape``/``zeros``/``ones``/``full``/``broadcast_to``
literals — descriptor-driven fixed buffers),
flag a repeated label binding a literal size-1 dimension against a
dimension of literal size > 1 or a named (symbolic) size.  Two
bindings of the SAME symbol (legitimate batch that may be 1 at
runtime) are clean — the trap is a *structural* 1 meeting a real axis.

Rule ``mask-dtype``: the masked-softmax contract — the additive mask
and the scores combine in f32, rounding only at declared boundaries.
``jnp.where(cond, scores, -1e30)`` (or NEG_INF) where the scores
branch is cast to bf16/f16 means the -1e30 fill and any downstream
max/exp run in low precision: bf16 has 8 mantissa bits, so near-tied
logits flip under the mask instead of being suppressed exactly.

Waive with ``# graftlint: allow(einsum-broadcast) why`` /
``# graftlint: allow(mask-dtype) why``.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Tuple

from tools.graftlint import core

RULE_BROADCAST = "einsum-broadcast"
RULE_MASK = "mask-dtype"

# A shape is a tuple of dims; each dim is ("lit", int) | ("sym", str).
Dim = Tuple[str, object]
Shape = Tuple[Dim, ...]

_LOW_FLOATS = {"bfloat16", "float16"}


def _call_tail(func: ast.expr) -> str:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return ""


def _dim_of(node: ast.expr) -> Optional[Dim]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return ("lit", node.value)
    if isinstance(node, ast.Name):
        return ("sym", node.id)
    return None


def _shape_literal(node: ast.expr) -> Optional[Shape]:
    """Parse a (a, b, 1, c) shape expression; None when any dim is
    untraceable (opaque dims would poison size comparisons)."""
    if not isinstance(node, (ast.Tuple, ast.List)):
        return None
    dims = []
    for e in node.elts:
        d = _dim_of(e)
        if d is None:
            return None
        dims.append(d)
    return tuple(dims)


def _shape_env(fn: ast.AST) -> Dict[str, Shape]:
    """Function-local symbolic shapes:
      B, T, H, D = x.shape     -> x: (B, T, H, D)
      y = x.reshape(B, 1, D)   -> y: (B, 1, D)
      z = jnp.zeros((B, T))    -> z: (B, T)    (ones/full/empty too)
      w = jnp.broadcast_to(v, (B, T, D)) -> w: (B, T, D)
    Any other assignment to a tracked name drops it."""
    env: Dict[str, Shape] = {}
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        target = node.targets[0]
        value = node.value

        # B, T, H = x.shape  — names the dims of x.
        if (isinstance(target, ast.Tuple)
                and isinstance(value, ast.Attribute)
                and value.attr == "shape"
                and isinstance(value.value, ast.Name)
                and all(isinstance(e, ast.Name) for e in target.elts)):
            env[value.value.id] = tuple(
                ("sym", e.id) for e in target.elts)  # type: ignore
            continue

        shape: Optional[Shape] = None
        if isinstance(value, ast.Call):
            tail = _call_tail(value.func)
            if tail == "reshape" and value.args:
                if len(value.args) == 1:
                    shape = _shape_literal(value.args[0])
                else:
                    dims = [_dim_of(a) for a in value.args]
                    if all(d is not None for d in dims):
                        shape = tuple(dims)  # type: ignore
            elif tail in ("zeros", "ones", "empty", "full") and value.args:
                shape = _shape_literal(value.args[0])
                if shape is None:
                    d = _dim_of(value.args[0])
                    if d is not None:
                        shape = (d,)
            elif tail == "broadcast_to" and len(value.args) >= 2:
                shape = _shape_literal(value.args[1])

        if isinstance(target, ast.Name):
            if shape is not None:
                env[target.id] = shape
            else:
                env.pop(target.id, None)
    return env


def _operand_shape(node: ast.expr, env: Dict[str, Shape]) -> Optional[Shape]:
    if isinstance(node, ast.Name):
        return env.get(node.id)
    return None


def _parse_spec(spec: str) -> Optional[List[str]]:
    """Input label groups of an einsum spec; None for forms this pass
    doesn't model (ellipsis, implicit output is fine)."""
    spec = spec.replace(" ", "")
    if "..." in spec:
        return None
    ins = spec.split("->")[0]
    groups = ins.split(",")
    if not all(g.isalpha() for g in groups):
        return None
    return groups


def _broadcast_conflict(a: Dim, b: Dim) -> bool:
    """True when one binding is a structural literal 1 and the other
    is a literal > 1 or a symbol (a real axis).  Same symbol twice, or
    equal literals, is clean."""
    for x, y in ((a, b), (b, a)):
        if x == ("lit", 1):
            if y[0] == "lit" and y[1] != 1:
                return True
            if y[0] == "sym":
                return True
    return False


def _fmt_dim(d: Dim) -> str:
    return str(d[1])


def _check_einsum(sf: core.SourceFile, fn: ast.AST, call: ast.Call,
                  env: Dict[str, Shape],
                  findings: List[core.Finding]) -> bool:
    """Returns True when the site had traceable shapes (for stats)."""
    if not call.args or not isinstance(call.args[0], ast.Constant):
        return False
    spec = call.args[0].value
    if not isinstance(spec, str):
        return False
    groups = _parse_spec(spec)
    if groups is None:
        return False
    operands = call.args[1:1 + len(groups)]
    if len(operands) != len(groups):
        return False

    bindings: Dict[str, List[Tuple[int, Dim]]] = {}
    traced = False
    for oi, (labels, op) in enumerate(zip(groups, operands)):
        shape = _operand_shape(op, env)
        if shape is None or len(shape) != len(labels):
            continue
        traced = True
        for label, dim in zip(labels, shape):
            bindings.setdefault(label, []).append((oi, dim))

    for label, bound in bindings.items():
        for i in range(len(bound)):
            for j in range(i + 1, len(bound)):
                (oi, da), (oj, db) = bound[i], bound[j]
                if not _broadcast_conflict(da, db):
                    continue
                if core.allowed_above(sf, RULE_BROADCAST, call.lineno,
                                      fn.lineno):
                    return traced
                findings.append(core.make_finding(
                    sf, RULE_BROADCAST, call.lineno,
                    f"einsum '{spec}' label '{label}' binds size "
                    f"{_fmt_dim(da)} (operand {oi}) against size "
                    f"{_fmt_dim(db)} (operand {oj}) — a size-1 dim "
                    f"under a repeated label broadcasts silently "
                    f"instead of raising, summing across the real "
                    f"axis (the PR 16 every-KV-head-summed-ALL-heads "
                    f"bug)",
                    hint="squeeze the size-1 axis out of the spec, or "
                         "give it its own output label if the "
                         "broadcast is intended",
                    qualname=core.qualname_of(call)))
                return traced
    return traced


def _literal_int_pairs(node: ast.expr) -> Optional[List[Tuple[int, int]]]:
    """((l0, r0), ...) from a dimension_numbers pair literal like
    ((1,), (0,))."""
    if not isinstance(node, (ast.Tuple, ast.List)) or len(node.elts) != 2:
        return None
    sides = []
    for side in node.elts:
        if not isinstance(side, (ast.Tuple, ast.List)):
            return None
        idxs = []
        for e in side.elts:
            if not (isinstance(e, ast.Constant)
                    and isinstance(e.value, int)):
                return None
            idxs.append(e.value)
        sides.append(idxs)
    if len(sides[0]) != len(sides[1]):
        return None
    return list(zip(sides[0], sides[1]))


def _check_dot_general(sf: core.SourceFile, fn: ast.AST, call: ast.Call,
                       env: Dict[str, Shape],
                       findings: List[core.Finding]) -> bool:
    if len(call.args) < 3:
        return False
    lhs = _operand_shape(call.args[0], env)
    rhs = _operand_shape(call.args[1], env)
    dn = call.args[2]
    if lhs is None or rhs is None:
        return False
    if not isinstance(dn, (ast.Tuple, ast.List)) or len(dn.elts) != 2:
        return False
    contract = _literal_int_pairs(dn.elts[0])
    batch = _literal_int_pairs(dn.elts[1])
    if contract is None or batch is None:
        return False
    for kind, pairs in (("contracting", contract), ("batch", batch)):
        for li, ri in pairs:
            if li >= len(lhs) or ri >= len(rhs):
                continue
            if _broadcast_conflict(lhs[li], rhs[ri]):
                if core.allowed_above(sf, RULE_BROADCAST, call.lineno,
                                      fn.lineno):
                    return True
                findings.append(core.make_finding(
                    sf, RULE_BROADCAST, call.lineno,
                    f"dot_general {kind} dims pair lhs[{li}]="
                    f"{_fmt_dim(lhs[li])} with rhs[{ri}]="
                    f"{_fmt_dim(rhs[ri])} — a structural size-1 axis "
                    f"against a real axis broadcasts or miscontracts "
                    f"silently",
                    hint="squeeze the size-1 axis before the "
                         "contraction",
                    qualname=core.qualname_of(call)))
                return True
    return True


def _is_neg_inf(node: ast.expr) -> bool:
    if isinstance(node, ast.Constant) and isinstance(
            node.value, (int, float)):
        return node.value <= -1e9
    if (isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub)
            and isinstance(node.operand, ast.Constant)
            and isinstance(node.operand.value, (int, float))):
        return node.operand.value >= 1e9
    if isinstance(node, ast.Name) and "NEG_INF" in node.id.upper():
        return True
    if isinstance(node, ast.Attribute) and "NEG_INF" in node.attr.upper():
        return True
    return False


def _low_precision_cast(node: ast.expr) -> Optional[int]:
    """Line of a bf16/f16 astype inside the scores branch, if any."""
    for n in ast.walk(node):
        if not (isinstance(n, ast.Call)
                and _call_tail(n.func) == "astype" and n.args):
            continue
        arg = n.args[0]
        if isinstance(arg, ast.Attribute) and arg.attr in _LOW_FLOATS:
            return n.lineno
        if isinstance(arg, ast.Name) and arg.id in _LOW_FLOATS:
            return n.lineno
        if (isinstance(arg, ast.Constant)
                and arg.value in _LOW_FLOATS):
            return n.lineno
    return None


def _check_mask(sf: core.SourceFile, fn: ast.AST, call: ast.Call,
                findings: List[core.Finding]) -> None:
    if _call_tail(call.func) != "where" or len(call.args) != 3:
        return
    _, scores, fill = call.args
    if not _is_neg_inf(fill):
        return
    cast_line = _low_precision_cast(scores)
    if cast_line is None:
        return
    if core.allowed_above(sf, RULE_MASK, call.lineno, fn.lineno):
        return
    findings.append(core.make_finding(
        sf, RULE_MASK, call.lineno,
        "masked softmax combines a -inf fill with scores cast to "
        "bf16/f16 — the mask-add contract is f32 (round only at "
        "declared boundaries); with 8 mantissa bits near-tied logits "
        "flip under the mask instead of being suppressed exactly",
        hint="mask in f32 and cast AFTER the softmax: "
             "jnp.where(m, s, -1e30) with s float32",
        qualname=core.qualname_of(call)))


def run(files: List[core.SourceFile], ctx: core.Context) -> List[core.Finding]:
    findings: List[core.Finding] = []
    einsum_sites = 0
    traced_sites = 0
    for sf in files:
        core.attach_parents(sf.tree)
        for fn in ast.walk(sf.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            env = _shape_env(fn)
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                tail = _call_tail(node.func)
                if tail == "einsum":
                    einsum_sites += 1
                    if _check_einsum(sf, fn, node, env, findings):
                        traced_sites += 1
                elif tail == "dot_general":
                    einsum_sites += 1
                    if _check_dot_general(sf, fn, node, env, findings):
                        traced_sites += 1
                elif tail == "where":
                    _check_mask(sf, fn, node, findings)
    stats = getattr(ctx, "stats", None)
    if stats is not None:
        stats["einsumcheck"] = {
            "contraction_sites": einsum_sites,
            "shape_traced": traced_sites,
        }
    return findings
