"""Fusion-barrier certification for quantization numerics (graftnum).

Two silent bug classes cost PRs 15/16 days each, and both are invisible
to tests until a near-tied greedy argmax flips:

 * an int8 **quantization scale** (``max(abs(x))`` feeding a round/clip
   to int8) fused into its producer reads *unrounded f32 intermediates*
   — the scale, and hence the int8 bits, become a function of XLA's
   fusion choices, which differ between the single-chip and the
   SPMD-partitioned compilations of the same model (the PR 15 tp=2 vs
   tp=1 divergence);
 * a bf16 **dequant product** (``w.astype(dt) * scale.astype(dt)``)
   inside a fusion runs in f32 and only rounds at materialization
   boundaries — consumed unrounded it drifts ~2e-3 from the value the
   masked twin materializes (the PR 16 sparse-vs-masked greedy flips).

Both are fixed by ``jax.lax.optimization_barrier``: it pins the
intermediate to ONE materialized value shared by every consumer and
every compilation.  This pass makes the hand-placed barriers
(``models/transformer._quantize_act``/``_quantize_kv`` and the
dequanted prefix of ``_run_blocks_prefill_prefix``) machine-certified
instead of folklore, and every future kernel leg inherits the check.

Rule ``num-barrier``:

 * a ``max(abs(X))`` reduction in a function that also casts to int8
   must read a barrier-pinned ``X`` (assigned from
   ``jax.lax.optimization_barrier`` in the same function, or wrapped in
   the barrier call directly);
 * a dequant product — a ``*`` whose operands BOTH carry an
   ``.astype(...)`` (directly or through a one-level local) and at
   least one of which references a ``*scale*``-named value — must pass
   through ``optimization_barrier`` before flowing into a
   materialization boundary: a ``return``, a ``concatenate``/``stack``,
   or a ``lax.scan`` argument (the scan carry).

Waive with ``# graftlint: allow(num-barrier) why`` on the flagged line
(or the ``def`` line for the whole function) — e.g. load-time weight
quantization that runs once on the host outside any serving jit, or a
single-consumer dequant whose unique consumer IS the materialization
boundary.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from tools.graftlint import core

RULE = "num-barrier"

_BOUNDARY_CALLS = {"concatenate", "stack", "hstack", "vstack", "scan"}
_INT8_NAMES = {"int8", "int4"}


def _call_tail(func: ast.expr) -> str:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return ""


def _is_barrier_call(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and _call_tail(node.func) == "optimization_barrier")


def _contains_barrier(node: ast.AST) -> bool:
    return any(_is_barrier_call(n) for n in ast.walk(node))


def _has_int8_cast(fn: ast.AST) -> bool:
    """Function rounds something to int8: ``.astype(jnp.int8)`` /
    ``.astype("int8")`` (int4 packing counts — same hazard)."""
    for node in ast.walk(fn):
        if not (isinstance(node, ast.Call)
                and _call_tail(node.func) == "astype" and node.args):
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Attribute) and arg.attr in _INT8_NAMES:
            return True
        if isinstance(arg, ast.Name) and arg.id in _INT8_NAMES:
            return True
        if isinstance(arg, ast.Constant) and arg.value in _INT8_NAMES:
            return True
    return False


def _assign_names(target: ast.expr) -> List[str]:
    out = []
    for node in ast.walk(target):
        if isinstance(node, ast.Name):
            out.append(node.id)
    return out


def _barriered_names(fn: ast.AST) -> Set[str]:
    """Locals assigned (anywhere in fn) from an optimization_barrier
    call — the canonical ``x = jax.lax.optimization_barrier(x)`` pin."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if (isinstance(node, ast.Assign)
                and _is_barrier_call(node.value)):
            for t in node.targets:
                out.update(_assign_names(t))
    return out


def _first_name(node: ast.AST) -> Optional[str]:
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            return n.id
    return None


def _scaleish(node: ast.AST, scale_locals: Set[str]) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and (
                "scale" in n.id.lower() or n.id in scale_locals):
            return True
        if isinstance(n, ast.Attribute) and "scale" in n.attr.lower():
            return True
        if (isinstance(n, ast.Constant) and isinstance(n.value, str)
                and "scale" in n.value.lower()):
            return True
    return False


def _has_astype(node: ast.AST, astype_locals: Set[str]) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Call) and _call_tail(n.func) == "astype":
            return True
        if isinstance(n, ast.Name) and n.id in astype_locals:
            return True
    return False


def _local_facts(fn: ast.AST) -> Tuple[Set[str], Set[str]]:
    """(astype_locals, scale_locals): one-level dataflow — a local
    assigned from an expression that carries an ``.astype`` call /
    references a ``*scale*`` value inherits that fact (e.g.
    ``pk = pl["k"].astype(dt)``, ``ks = pool["k_scale"][bids]``)."""
    astype_locals: Set[str] = set()
    scale_locals: Set[str] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assign):
            continue
        names = [n for t in node.targets for n in _assign_names(t)]
        if any(isinstance(n, ast.Call)
               and _call_tail(n.func) == "astype"
               for n in ast.walk(node.value)):
            astype_locals.update(names)
        if _scaleish(node.value, set()):
            scale_locals.update(names)
    return astype_locals, scale_locals


def _dequant_mults(fn: ast.AST, astype_locals: Set[str],
                   scale_locals: Set[str]) -> List[ast.BinOp]:
    """Unbarriered dequant products in fn: ``L * R`` with astype on
    both sides and a scale reference on either.  Products wrapped in
    optimization_barrier (anywhere up the same expression) are the
    certified fix, not a finding."""
    barrier_spans: List[ast.AST] = [
        n for n in ast.walk(fn) if _is_barrier_call(n)
    ]
    inside_barrier: Set[int] = set()
    for b in barrier_spans:
        for n in ast.walk(b):
            inside_barrier.add(id(n))
    out = []
    for node in ast.walk(fn):
        if not (isinstance(node, ast.BinOp)
                and isinstance(node.op, ast.Mult)):
            continue
        if id(node) in inside_barrier:
            continue
        if not (_has_astype(node.left, astype_locals)
                and _has_astype(node.right, astype_locals)):
            continue
        if not (_scaleish(node.left, scale_locals)
                or _scaleish(node.right, scale_locals)):
            continue
        out.append(node)
    return out


def _index_parents(fn: ast.AST) -> Dict[int, ast.AST]:
    parents: Dict[int, ast.AST] = {}
    for node in ast.walk(fn):
        for child in ast.iter_child_nodes(node):
            parents[id(child)] = node
    return parents


def _enclosing_stmt(node: ast.AST, parents: Dict[int, ast.AST],
                    fn: ast.AST) -> ast.AST:
    cur = node
    while id(cur) in parents and parents[id(cur)] is not fn:
        nxt = parents[id(cur)]
        if isinstance(nxt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            break
        cur = nxt
    return cur


def _boundary_hit(fn: ast.AST, mults: List[ast.BinOp],
                  parents: Dict[int, ast.AST]) -> Dict[int, str]:
    """Which dequant products reach a materialization boundary.
    Returns {mult line: boundary description}.  A product reaches a
    boundary directly (its expression sits inside a return / concat /
    scan) or through taint: locals assigned from it (transitively)
    that appear inside one."""
    hits: Dict[int, str] = {}
    mult_ids = {id(m): m for m in mults}

    # Direct containment: boundary node whose subtree holds the mult.
    def note_direct(container: ast.AST, what: str) -> None:
        for n in ast.walk(container):
            if id(n) in mult_ids:
                hits.setdefault(mult_ids[id(n)].lineno, what)

    # Taint: name -> origin mult lines.
    taint: Dict[str, Set[int]] = {}
    for _ in range(2):  # two passes ~ transitive enough for real code
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            origins: Set[int] = set()
            for n in ast.walk(node.value):
                if id(n) in mult_ids and not _contains_ancestral_barrier(
                        n, node.value):
                    origins.add(mult_ids[id(n)].lineno)
                if isinstance(n, ast.Name) and n.id in taint:
                    origins |= taint[n.id]
            if _is_barrier_call(node.value):
                origins = set()  # barrier at assignment = the fix
            for t in node.targets:
                for name in _assign_names(t):
                    if origins:
                        taint[name] = taint.get(name, set()) | origins
                    else:
                        taint.pop(name, None)

    def note_tainted(container: ast.AST, what: str) -> None:
        for n in ast.walk(container):
            if isinstance(n, ast.Name) and n.id in taint:
                for ln in taint[n.id]:
                    hits.setdefault(ln, what)

    for node in ast.walk(fn):
        if isinstance(node, ast.Return) and node.value is not None:
            note_direct(node.value, "the jit return")
            note_tainted(node.value, "the jit return")
        elif (isinstance(node, ast.Call)
              and _call_tail(node.func) in _BOUNDARY_CALLS):
            what = (f"a {_call_tail(node.func)}() materialization"
                    if _call_tail(node.func) != "scan"
                    else "a lax.scan carry")
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                note_direct(arg, what)
                note_tainted(arg, what)
    return hits


def _contains_ancestral_barrier(node: ast.AST, root: ast.AST) -> bool:
    """True when `node` sits under an optimization_barrier call inside
    `root` (the barrier wraps the product in the same expression)."""
    for b in ast.walk(root):
        if _is_barrier_call(b):
            for n in ast.walk(b):
                if n is node:
                    return True
    return False


def run(files: List[core.SourceFile], ctx: core.Context) -> List[core.Finding]:
    findings: List[core.Finding] = []
    scale_sites = 0
    dequant_sites = 0
    certified = 0

    for sf in files:
        core.attach_parents(sf.tree)
        fns = [n for n in ast.walk(sf.tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        for fn in fns:
            # Innermost ownership: nodes belonging to a nested def are
            # analyzed with THAT def's barriers/locals, not the outer's.
            nested = [n for n in ast.walk(fn)
                      if isinstance(n, (ast.FunctionDef,
                                        ast.AsyncFunctionDef))
                      and n is not fn]
            owned_elsewhere: Set[int] = set()
            for sub in nested:
                for n in ast.walk(sub):
                    if n is not sub:
                        owned_elsewhere.add(id(n))

            def owned(node: ast.AST) -> bool:
                return id(node) not in owned_elsewhere

            has_int8 = _has_int8_cast(fn)
            barriered = _barriered_names(fn)
            parents = _index_parents(fn)

            # --- quantize-scale leg: max(abs(X)) -> int8 -------------
            if has_int8:
                for node in ast.walk(fn):
                    if not owned(node):
                        continue
                    if not (isinstance(node, ast.Call)
                            and _call_tail(node.func) == "max"
                            and node.args):
                        continue
                    absarg = None
                    for n in ast.walk(node.args[0]):
                        if (isinstance(n, ast.Call)
                                and _call_tail(n.func) == "abs"
                                and n.args):
                            absarg = n.args[0]
                            break
                    if absarg is None:
                        continue
                    scale_sites += 1
                    root = _first_name(absarg)
                    if (root in barriered
                            or _contains_barrier(node.args[0])):
                        certified += 1
                        continue
                    if core.allowed_above(sf, RULE, node.lineno, fn.lineno):
                        continue
                    findings.append(core.make_finding(
                        sf, RULE, node.lineno,
                        f"int8 quantization scale reduces max(abs("
                        f"{root or '?'})) without an optimization_barrier "
                        f"pin — fused into the producer it reads "
                        f"unrounded f32 intermediates, so the scale (and "
                        f"the int8 bits) depend on XLA fusion choices "
                        f"and diverge between tp=1 and SPMD compilations",
                        hint="pin the input first: "
                             "x = jax.lax.optimization_barrier(x) "
                             "(models/transformer._quantize_act)",
                        qualname=core.qualname_of(node),
                    ))

            # --- dequant-product leg ---------------------------------
            astype_locals, scale_locals = _local_facts(fn)
            # Barriered products are filtered out of _dequant_mults —
            # count them here as certified sites for the headline.
            for n in ast.walk(fn):
                if _is_barrier_call(n) and owned(n):
                    for m in ast.walk(n):
                        if (isinstance(m, ast.BinOp)
                                and isinstance(m.op, ast.Mult)
                                and _has_astype(m, astype_locals)):
                            certified += 1
                            dequant_sites += 1
                            break
            mults = [m for m in _dequant_mults(fn, astype_locals,
                                               scale_locals)
                     if owned(m)]
            if not mults:
                continue
            dequant_sites += len(mults)
            hits = _boundary_hit(fn, mults, parents)
            seen_lines: Set[int] = set()
            for m in mults:
                what = hits.get(m.lineno)
                if what is None or m.lineno in seen_lines:
                    continue
                seen_lines.add(m.lineno)
                if core.allowed_above(sf, RULE, m.lineno, fn.lineno):
                    continue
                findings.append(core.make_finding(
                    sf, RULE, m.lineno,
                    f"int8 dequant product flows into {what} without an "
                    f"optimization_barrier — inside a fusion the bf16 "
                    f"multiply runs in f32 and rounds only at "
                    f"materialization, so its value drifts (~2e-3) "
                    f"between kernel legs that materialize at different "
                    f"points",
                    hint="wrap the product: jax.lax.optimization_barrier"
                         "(w.astype(dt) * scale.astype(dt)) "
                         "(models/transformer._run_blocks_prefill_prefix)",
                    qualname=core.qualname_of(m),
                ))

    stats = getattr(ctx, "stats", None)
    if stats is not None:
        stats["numbarrier"] = {
            "scale_sites": scale_sites,
            "dequant_sites": dequant_sites,
            "certified": certified,
        }
    return findings
