"""graftnum: the static numerics & buffer-lifetime certifier.

Claims under test, by pass:

 * **num-barrier**: an int8 quantize scale (``max(abs(x))`` in an
   int8-casting function) must read a barrier-pinned input, and an
   int8 dequant product (astype * astype with a scale reference) must
   pass through ``optimization_barrier`` before a materialization
   boundary (return / concatenate / scan carry).  The two hand-placed
   barrier idioms (``transformer._quantize_act`` pin-the-input,
   ``transformer._run_blocks_prefill_prefix`` wrap-the-product) certify;
   their barrier-free twins are findings.
 * **use-after-donate**: reads of a donated binding after the donating
   call are flagged on ANY path; the three safe shapes (same-statement
   rebind, tuple rebind, hand-off return) are clean; host-side
   container captures of a later-donated binding are flagged;
   the registry sees assigned jits, ``functools.partial`` decorators,
   dict-of-jits, and conditional aliases; ``.shape``/``.dtype`` reads
   survive donation; an early-``return`` branch's donation does not
   leak into the fall-through path.
 * **einsum-broadcast / mask-dtype**: a repeated einsum label binding
   a structural literal 1 against a real axis is flagged (the PR 16
   every-KV-head-summed-ALL-heads bug); the same symbol twice is
   clean; ``dot_general`` contracting dims get the same check; a
   masked softmax whose scores branch is cast to bf16 before the
   -1e30 fill is flagged.
 * **wiring**: all three rules waive via inline allow comments,
   fingerprints survive line drift, the CLI exits 1 on findings and 0
   clean, the ``--budget-s`` self-runtime gate trips, the graftnum
   headline prints, and the REAL tree (models/, ops/,
   servers/engine.py) is clean with a non-trivial certified count —
   the empty-baseline discipline, machine-checked.
"""

import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

from tools.graftlint import core, donate, einsumcheck, numbarrier

REPO = Path(__file__).resolve().parents[1]


def lint(tmp_path, src, passes, name="fixture.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    files = core.load_tree([p], tmp_path)
    ctx = core.Context(tmp_path)
    return core.run_passes(files, ctx, passes)


def lint_stats(tmp_path, src, passes, name="fixture.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    files = core.load_tree([p], tmp_path)
    ctx = core.Context(tmp_path)
    return core.run_passes(files, ctx, passes), ctx.stats


def rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# num-barrier: quantize-scale leg
# ---------------------------------------------------------------------------


SCALE_BAD = """
    import jax
    import jax.numpy as jnp

    def quantize(x):
        s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
        q = jnp.round(x / s).astype(jnp.int8)
        return q, s
"""

SCALE_PINNED = """
    import jax
    import jax.numpy as jnp

    def quantize(x):
        x = jax.lax.optimization_barrier(x)
        s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
        q = jnp.round(x / s).astype(jnp.int8)
        return q, s
"""

SCALE_WRAPPED = """
    import jax
    import jax.numpy as jnp

    def quantize(x):
        s = jnp.max(jnp.abs(jax.lax.optimization_barrier(x))) / 127.0
        q = jnp.round(x / s).astype(jnp.int8)
        return q, s
"""


def test_scale_without_barrier_flagged(tmp_path):
    findings = lint(tmp_path, SCALE_BAD, [numbarrier.run])
    assert rules(findings) == ["num-barrier"]
    assert "max(abs" in findings[0].message
    assert "fusion" in findings[0].message


def test_scale_with_barrier_pin_clean(tmp_path):
    assert lint(tmp_path, SCALE_PINNED, [numbarrier.run]) == []


def test_scale_with_inline_barrier_clean(tmp_path):
    assert lint(tmp_path, SCALE_WRAPPED, [numbarrier.run]) == []


def test_scale_in_float_only_function_clean(tmp_path):
    # max(abs(x)) without any int8 cast nearby is a norm, not a scale.
    src = SCALE_BAD.replace(".astype(jnp.int8)", ".astype(jnp.float32)")
    assert lint(tmp_path, src, [numbarrier.run]) == []


# ---------------------------------------------------------------------------
# num-barrier: dequant-product leg
# ---------------------------------------------------------------------------


DEQUANT_BAD = """
    import jax
    import jax.numpy as jnp

    def dequant_concat(w, w_scale, prior, sink, dt):
        full = w.astype(dt) * w_scale.astype(dt)
        sink["kv"] = jnp.concatenate([prior, full], axis=0)
"""

DEQUANT_BARRIERED = """
    import jax
    import jax.numpy as jnp

    def dequant_concat(w, w_scale, prior, dt):
        full = jax.lax.optimization_barrier(
            w.astype(dt) * w_scale.astype(dt))
        return jnp.concatenate([prior, full], axis=0)
"""

DEQUANT_INTERNAL = """
    import jax.numpy as jnp

    def attend(w, w_scale, q, dt):
        full = w.astype(dt) * w_scale.astype(dt)
        probs = jnp.exp(full - jnp.sum(full))
        del probs
        return q
"""


def test_dequant_into_concat_flagged(tmp_path):
    findings = lint(tmp_path, DEQUANT_BAD, [numbarrier.run])
    assert rules(findings) == ["num-barrier"]
    assert "concatenate() materialization" in findings[0].message


def test_dequant_barriered_clean_and_certified(tmp_path):
    findings, stats = lint_stats(
        tmp_path, DEQUANT_BARRIERED, [numbarrier.run])
    assert findings == []
    assert stats["numbarrier"]["certified"] == 1
    assert stats["numbarrier"]["dequant_sites"] == 1


def test_dequant_consumed_internally_clean(tmp_path):
    # The product never reaches a materialization boundary — every
    # consumer lives inside the same fusion, so there is no cross-leg
    # drift to certify against.
    assert lint(tmp_path, DEQUANT_INTERNAL, [numbarrier.run]) == []


def test_dequant_into_return_flagged(tmp_path):
    src = """
    import jax.numpy as jnp

    def dequant(w, w_scale, dt):
        return w.astype(dt) * w_scale.astype(dt)
    """
    findings = lint(tmp_path, src, [numbarrier.run])
    assert rules(findings) == ["num-barrier"]
    assert "jit return" in findings[0].message


def test_num_barrier_waivable(tmp_path):
    src = SCALE_BAD.replace(
        "s = jnp.max",
        "# graftlint: allow(num-barrier) host-side load-time quant\n"
        "        s = jnp.max")
    assert lint(tmp_path, src, [numbarrier.run]) == []


# ---------------------------------------------------------------------------
# use-after-donate
# ---------------------------------------------------------------------------


DONATE_BAD = """
    import jax

    step = jax.jit(lambda p, s: s, donate_argnums=(1,))

    def loop(params, state):
        new = step(params, state)
        stale = state["kv"]
        return new, stale
"""

DONATE_REBIND = """
    import jax

    step = jax.jit(lambda p, s: s, donate_argnums=(1,))

    def loop(params, state):
        state = step(params, state)
        state = step(params, state)
        return state
"""

DONATE_TUPLE = """
    import jax

    step = jax.jit(lambda p, s: (s, 0), donate_argnums=(1,))

    def loop(params, state):
        state, tok = step(params, state)
        return state, tok
"""

DONATE_CAPTURED = """
    import jax

    step = jax.jit(lambda p, s: s, donate_argnums=(1,))

    def loop(params, state, book):
        book["warm"] = state
        state = step(params, state)
        return state
"""

DONATE_DECORATOR = """
    import functools
    import jax

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, params):
        return state

    def loop(params, state):
        out = step(state, params)
        return state
"""

DONATE_DICT = """
    import jax

    class Engine:
        def __init__(self, fns):
            self._jit_chunks = {
                n: jax.jit(f, donate_argnums=(1,))
                for n, f in fns.items()
            }

        def run(self, n, params, state):
            out = self._jit_chunks[n](params, state)
            return state, out
"""

DONATE_BRANCH = """
    import jax

    step = jax.jit(lambda p, s: s, donate_argnums=(1,))

    def loop(params, state, fast):
        if fast:
            out = step(params, state)
        else:
            out = state
        return state
"""

DONATE_EARLY_RETURN = """
    import jax

    step = jax.jit(lambda p, s: s, donate_argnums=(1,))

    def loop(params, state, fast):
        if fast:
            return step(params, state)
        return state
"""

DONATE_METADATA = """
    import jax

    step = jax.jit(lambda p, s: s, donate_argnums=(1,))

    def loop(params, state):
        new = step(params, state)
        n = state.shape[0] + state.ndim
        return new, n
"""


def test_read_after_donate_flagged(tmp_path):
    findings = lint(tmp_path, DONATE_BAD, [donate.run])
    assert rules(findings) == ["use-after-donate"]
    assert "reads state after its buffer was donated" in \
        findings[0].message


def test_same_statement_rebind_clean(tmp_path):
    assert lint(tmp_path, DONATE_REBIND, [donate.run]) == []


def test_tuple_rebind_clean(tmp_path):
    assert lint(tmp_path, DONATE_TUPLE, [donate.run]) == []


def test_donate_while_captured_flagged(tmp_path):
    findings = lint(tmp_path, DONATE_CAPTURED, [donate.run])
    assert rules(findings) == ["use-after-donate"]
    assert "container still holds a reference" in findings[0].message


def test_decorator_partial_donate_flagged(tmp_path):
    findings = lint(tmp_path, DONATE_DECORATOR, [donate.run])
    assert rules(findings) == ["use-after-donate"]


def test_dict_of_jits_donate_flagged(tmp_path):
    findings = lint(tmp_path, DONATE_DICT, [donate.run])
    assert rules(findings) == ["use-after-donate"]


def test_donation_on_one_path_flags_fallthrough_read(tmp_path):
    # Union merge: donated on ANY path means the read after the join
    # is a hazard on that path.
    findings = lint(tmp_path, DONATE_BRANCH, [donate.run])
    assert rules(findings) == ["use-after-donate"]


def test_early_return_donation_does_not_leak(tmp_path):
    # The donating branch returns — its state must NOT merge back, so
    # the fall-through `return state` is the undonated path and clean.
    assert lint(tmp_path, DONATE_EARLY_RETURN, [donate.run]) == []


def test_metadata_reads_survive_donation(tmp_path):
    assert lint(tmp_path, DONATE_METADATA, [donate.run]) == []


def test_use_after_donate_waivable(tmp_path):
    src = DONATE_BAD.replace(
        "stale = state",
        "# graftlint: allow(use-after-donate) copy taken upstream\n"
        "        stale = state")
    assert lint(tmp_path, src, [donate.run]) == []


# ---------------------------------------------------------------------------
# einsum-broadcast / mask-dtype
# ---------------------------------------------------------------------------


EINSUM_BAD = """
    import jax.numpy as jnp

    def attend(q, kv):
        B, H, D = q.shape
        k = kv.reshape(B, 1, D)
        return jnp.einsum("bhd,bhd->bh", q, k)
"""

EINSUM_SAME_SYMBOL = """
    import jax.numpy as jnp

    def attend(q, kv):
        B, H, D = q.shape
        k = kv.reshape(B, H, D)
        return jnp.einsum("bhd,bhd->bh", q, k)
"""

DOT_GENERAL_BAD = """
    import jax
    import jax.numpy as jnp

    def contract():
        a = jnp.zeros((4, 1))
        b = jnp.zeros((4, 8))
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((0,), (0,))))
"""

MASK_BAD = """
    import jax.numpy as jnp

    def masked(scores, mask):
        return jnp.where(mask, scores.astype(jnp.bfloat16), -1e30)
"""

MASK_F32 = """
    import jax.numpy as jnp

    def masked(scores, mask):
        return jnp.where(mask, scores.astype(jnp.float32), -1e30)
"""


def test_einsum_size1_broadcast_flagged(tmp_path):
    findings = lint(tmp_path, EINSUM_BAD, [einsumcheck.run])
    assert rules(findings) == ["einsum-broadcast"]
    assert "broadcasts silently" in findings[0].message


def test_einsum_same_symbol_clean(tmp_path):
    # Both operands bind 'h' to the SAME symbol H — a batch that may
    # be 1 at runtime is legitimate; the trap is a structural 1.
    assert lint(tmp_path, EINSUM_SAME_SYMBOL, [einsumcheck.run]) == []


def test_dot_general_size1_contraction_flagged(tmp_path):
    findings = lint(tmp_path, DOT_GENERAL_BAD, [einsumcheck.run])
    assert rules(findings) == ["einsum-broadcast"]
    assert "dot_general" in findings[0].message


def test_mask_low_precision_flagged(tmp_path):
    findings = lint(tmp_path, MASK_BAD, [einsumcheck.run])
    assert rules(findings) == ["mask-dtype"]


def test_mask_f32_clean(tmp_path):
    assert lint(tmp_path, MASK_F32, [einsumcheck.run]) == []


def test_einsum_broadcast_waivable(tmp_path):
    src = EINSUM_BAD.replace(
        "return jnp.einsum",
        "# graftlint: allow(einsum-broadcast) intended broadcast\n"
        "        return jnp.einsum")
    assert lint(tmp_path, src, [einsumcheck.run]) == []


# ---------------------------------------------------------------------------
# Fingerprint stability
# ---------------------------------------------------------------------------


def test_fingerprint_survives_line_drift(tmp_path):
    (f1,) = lint(tmp_path, SCALE_BAD, [numbarrier.run], name="a.py")
    drifted = SCALE_BAD.replace(
        "import jax\n", "import jax\n\n    # drift: unrelated comment\n")
    (f2,) = lint(tmp_path, drifted, [numbarrier.run], name="b.py")
    assert f1.line != f2.line  # the drift really moved the site
    # Same rule + qualname + normalized line -> same fingerprint tail;
    # only the path segment differs between the two fixture files.
    assert f1.fingerprint != f2.fingerprint  # path is in the print
    same = SCALE_BAD  # identical content, same file name now
    (f3,) = lint(tmp_path, same, [numbarrier.run], name="a.py")
    assert f3.fingerprint == f1.fingerprint


def test_fingerprint_stable_in_same_file_under_drift(tmp_path):
    (f1,) = lint(tmp_path, SCALE_BAD, [numbarrier.run], name="s.py")
    drifted = SCALE_BAD.replace(
        "import jax\n", "import jax\n\n    # drift: unrelated comment\n")
    (f2,) = lint(tmp_path, drifted, [numbarrier.run], name="s.py")
    assert f2.line == f1.line + 2
    assert f2.fingerprint == f1.fingerprint


# ---------------------------------------------------------------------------
# Real tree: the empty-baseline discipline, machine-checked
# ---------------------------------------------------------------------------


def test_real_tree_clean_with_nontrivial_certified_count():
    targets = [REPO / "seldon_tpu" / "models",
               REPO / "seldon_tpu" / "ops",
               REPO / "seldon_tpu" / "servers" / "engine.py"]
    files = core.load_tree(targets, REPO)
    ctx = core.Context(REPO)
    findings = core.run_passes(
        files, ctx, [numbarrier.run, donate.run, einsumcheck.run])
    assert findings == [], "\n".join(f.render() for f in findings)
    nb = ctx.stats["numbarrier"]
    # The hand-placed barriers are no longer folklore: the certifier
    # must SEE them. 2 scale pins (_quantize_act/_quantize_kv) + 2
    # prefix-KV products at minimum.
    assert nb["certified"] >= 4, nb
    assert nb["scale_sites"] >= 2, nb
    dn = ctx.stats["donate"]
    assert dn["donating_jits"] >= 5, dn
    assert dn["donating_calls"] >= 10, dn
    es = ctx.stats["einsumcheck"]
    assert es["contraction_sites"] >= 20, es
    assert es["shape_traced"] >= 1, es


def test_baseline_has_no_graftnum_entries():
    baseline = core.load_baseline(core.Context(REPO).baseline_path)
    num_rules = {"num-barrier", "use-after-donate", "einsum-broadcast",
                 "mask-dtype"}
    offenders = {fp: e for fp, e in baseline.items()
                 if e.get("rule") in num_rules}
    assert not offenders, offenders


# ---------------------------------------------------------------------------
# CLI wiring: exit codes, headline, self-runtime budget
# ---------------------------------------------------------------------------


def _cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "tools.graftlint", *args],
        cwd=cwd, capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": str(REPO)},
    )


def test_cli_exit_1_on_fixture_finding(tmp_path):
    p = tmp_path / "bad.py"
    p.write_text(textwrap.dedent(SCALE_BAD))
    r = _cli(str(p))
    assert r.returncode == 1, f"{r.stdout}\n{r.stderr}"
    assert "num-barrier" in r.stdout


def test_cli_exit_0_on_clean_fixture(tmp_path):
    p = tmp_path / "good.py"
    p.write_text(textwrap.dedent(SCALE_PINNED))
    r = _cli(str(p))
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"


def test_cli_prints_graftnum_headline(tmp_path):
    p = tmp_path / "good.py"
    p.write_text(textwrap.dedent(DEQUANT_BARRIERED))
    r = _cli(str(p))
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    m = re.search(
        r"graftnum: numbarrier (\d+) finding\(s\) "
        r"\((\d+) scale \+ (\d+) dequant site\(s\), "
        r"(\d+) barrier-certified\)", r.stdout)
    assert m, r.stdout
    assert m.group(1) == "0"
    assert m.group(4) == "1"
    assert "| donate 0 finding(s)" in r.stdout
    assert "einsumcheck 0 finding(s)" in r.stdout


def test_cli_budget_gate_trips(tmp_path):
    p = tmp_path / "good.py"
    p.write_text(textwrap.dedent(SCALE_PINNED))
    r = _cli(str(p), "--budget-s", "0.0001")
    assert r.returncode == 1, f"{r.stdout}\n{r.stderr}"
    assert "self-runtime budget exceeded" in r.stderr


def test_cli_budget_disabled_with_zero(tmp_path):
    p = tmp_path / "good.py"
    p.write_text(textwrap.dedent(SCALE_PINNED))
    r = _cli(str(p), "--budget-s", "0")
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
