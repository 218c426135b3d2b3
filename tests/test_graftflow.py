"""graftflow: the static dataflow trio end to end.

Claims under test, by layer:

 * **model** (``seldon_tpu/servers/shape_lattice.py``): the closed-form
   ``dispatch_keys`` and the operational ``simulate_keys`` agree — zero
   holes (statically proven live retraces) and zero waste (warmup
   compiles nobody can reach) — over the full certifier grid; the
   historical blind spot (a prefix width bucketing to ``max_seq_len``
   when the top bucket fills the cache window) is IN the lattice;
 * **engine**: ``warmup()`` declares exactly ``static_lattice()``, and
   the blind-spot config serves a warm-prefix request with ZERO live
   retraces — the regression the certifier was built to prevent;
 * **shape-lattice pass**: dispatch-site keys are pinned to
   ``FAMILIES`` (tuple literal, registered tag, right arity), the
   ``_warm_key`` dispatcher must handle every family its file uses, and
   an injected closed-form/simulation disagreement surfaces as
   ``shape-lattice`` / ``shape-lattice-waste``;
 * **config-matrix pass**: branch-narrowing computes per-method
   (paged, chunked, prefix) reachability, flags flag-algebra-dead
   methods (waivable), and the real engine's dense-slab kill-list is
   non-empty with every entry provably paged_kv=False-only;
 * **shard pass**: undeclared PartitionSpec/collective axes, host pulls
   on shard_map/device_put results, and sharding-free ``jax.jit`` in
   sharding-centric files are flagged; engine-style files are exempt;
 * **wiring**: the checked-in ``docs/config_matrix.md`` is fresh, the
   CLI prints the kill-list headline, and the default lint target set
   covers the tools entry points.
"""

import dataclasses
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from seldon_tpu.models import init_params
from seldon_tpu.models.config import get_config
from seldon_tpu.models.sampling import SamplingParams
from seldon_tpu.servers import shape_lattice
from seldon_tpu.servers.engine import EngineConfig, InferenceEngine
from tools.graftlint import configmatrix, core, shapelattice, shardcheck
from tools.graftlint.__main__ import default_targets

REPO = Path(__file__).resolve().parents[1]

GREEDY = SamplingParams(temperature=0.0, max_new_tokens=8)


def lint(tmp_path, src, passes, name="fixture.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    files = core.load_tree([p], tmp_path)
    ctx = core.Context(tmp_path)
    return core.run_passes(files, ctx, passes)


def rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# Model: closed form vs operational simulation
# ---------------------------------------------------------------------------


def test_grid_closed_form_matches_simulation():
    specs = shape_lattice.grid()
    # Derived, not pinned: PR 13 and PR 15 each shipped a stale-pin fix
    # here; GRID_COUNT is now the single source of truth next to the
    # grid components it is computed from.
    assert len(specs) == shape_lattice.GRID_COUNT
    for spec in specs:
        holes, waste = shape_lattice.check_spec(spec)
        assert holes == [], (spec, holes)
        assert waste == [], (spec, waste)


def test_the_grid_is_the_flag_cube_and_the_spec_legs():
    """Two ways to dispatch a wave, so two legs of the grid: the (paged,
    chunked, prefix) cube over every shape and the spec combos over
    two; no family but those a key of the grid can name."""
    specs = shape_lattice.grid()
    assert shape_lattice.GRID_COUNT == (
        len(shape_lattice.GRID_FLAG_COMBOS) * len(shape_lattice.GRID_SHAPES)
        + len(shape_lattice.GRID_SPEC_COMBOS)
        * shape_lattice.GRID_SPEC_SHAPES) == 40
    assert sum(s.spec for s in specs) == 8
    named = {k[0] for s in specs for k in shape_lattice.dispatch_keys(s)}
    assert named == set(shape_lattice.FAMILIES) == set(
        shape_lattice.FAMILY_TAGS)
    assert not [f for f in shape_lattice.FAMILIES if "ragged" in f]
    assert not [f.name for f in dataclasses.fields(shape_lattice.LatticeSpec)
                if "ragged" in f.name]


def test_every_lattice_key_matches_registered_arity():
    for spec in shape_lattice.grid():
        for key in shape_lattice.dispatch_keys(spec):
            assert key[0] in shape_lattice.FAMILIES, key
            assert len(key) == shape_lattice.FAMILIES[key[0]], key


def test_window_width_prefix_is_in_lattice():
    # The historical warmup blind spot: buckets (16, 64) with
    # max_seq_len 64 — a 32-token trie match buckets to 64 == the cache
    # window, which a `b < max_seq_len` warmup filter skips.
    spec = shape_lattice.LatticeSpec(
        buckets=(16, 64), max_seq_len=64, max_slots=4, max_admit=2,
        decode_rungs=(4, 8), prefix=True)
    keys = shape_lattice.dispatch_keys(spec)
    assert ("admit-prefix", 64, 16, 1) in keys
    assert ("admit-prefix", 64, 16, 2) in keys
    # And the simulation derives the same fact independently.
    assert ("admit-prefix", 64, 16, 1) in shape_lattice.simulate_keys(spec)


def test_warmup_order_is_deterministic_and_ranked():
    spec = shape_lattice.grid()[0]
    keys = shape_lattice.dispatch_keys(spec)
    order = shape_lattice.warmup_order(keys)
    assert order == shape_lattice.warmup_order(set(order))
    assert order[0] == ("deactivate",)
    assert order[-1][0] == "decode"
    assert len(order) == len(keys)


def test_spec_validation():
    with pytest.raises(ValueError, match="ascend"):
        shape_lattice.LatticeSpec(
            buckets=(64, 32), max_seq_len=64, max_slots=4, max_admit=2,
            decode_rungs=(8,))
    with pytest.raises(ValueError, match="chunked"):
        shape_lattice.LatticeSpec(
            buckets=(32,), max_seq_len=64, max_slots=4, max_admit=2,
            decode_rungs=(8,), chunked=True)


# ---------------------------------------------------------------------------
# Engine: warmup declares static_lattice(); blind-spot regression
# ---------------------------------------------------------------------------


def test_warm_prefix_at_window_width_no_live_retrace(monkeypatch):
    """buckets (16, 64) under max_seq_len 64 + prefix cache: the second
    submission of a 48-token prompt admits behind a 32-token trie match,
    whose width buckets to 64 == max_seq_len. The pre-lattice warmup
    filtered widths with `b < max_seq_len` and skipped that variant, so
    this exact request paid a live retrace. Now warmup iterates
    dispatch_keys() and the lattice proves the variant in."""
    monkeypatch.setenv("COMPILE_LEDGER", "1")
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=4, max_seq_len=64, prompt_buckets=(16, 64),
        max_admit=2, prefix_cache=True))
    eng.warmup()

    # Warmup declared exactly the closed-form lattice, no ad-hoc keys.
    static = eng.static_lattice()
    comp = eng.debug_compile()
    assert comp["warmup_complete"] is True
    assert comp["declared_variants"] == len(static)
    dispatched = {e["key"] for e in comp["lattice"]}
    assert dispatched <= set(static)
    # The blind-spot variant is statically declared...
    assert "admit-prefix/64/16/1" in static

    eng.start()
    try:
        prompt = list(range(2, 50))  # 48 tokens: 3 trie blocks
        eng.generate_blocking(prompt, GREEDY)
        eng.generate_blocking(prompt, GREEDY)  # warm-prefix admission
        comp = eng.debug_compile()
        assert comp["live_retrace_count"] == 0, comp["live_retraces"]
        # ...and live traffic actually exercised a window-width prefix.
        hits = [e for e in comp["lattice"]
                if e["key"].startswith("admit-prefix/64/")]
        assert hits, sorted(e["key"] for e in comp["lattice"])
        assert all(e["declared"] for e in hits)
    finally:
        eng.stop()


def test_engine_lattice_spec_matches_config():
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=4, max_seq_len=64, prompt_buckets=(8, 32)))
    spec = eng.lattice_spec()
    assert spec.buckets == (8, 32)
    assert spec.max_seq_len == 64
    assert not (spec.paged or spec.chunked or spec.prefix)
    # static_lattice renders warmup_order(dispatch_keys) as key strings.
    want = [
        "/".join(str(p) for p in k)
        for k in shape_lattice.warmup_order(
            shape_lattice.dispatch_keys(spec))
    ]
    assert eng.static_lattice() == want


# ---------------------------------------------------------------------------
# shape-lattice pass: AST leg
# ---------------------------------------------------------------------------

LATTICE_BAD = """
    class Engine:
        def _dispatch(self, key, rid, tag):
            self._note_dispatch(key, rid, 0.1)
            self._note_dispatch((tag, 8), rid, 0.1)
            self._note_dispatch(("mystery", 8), rid, 0.1)
            self._note_dispatch(("decode", 8, 9), rid, 0.1)
"""

LATTICE_OK = """
    class Engine:
        def _dispatch(self, rid):
            self._note_dispatch(("decode", 8), rid, 0.1)
            self._note_dispatch(("admit", 32, 4), rid, 0.1)

        def _warm_key(self, key):
            kind = key[0]
            if kind == "decode":
                pass
            elif kind == "admit":
                pass
"""

WARM_GAP = """
    class Engine:
        def _dispatch(self, rid):
            self._note_dispatch(("decode", 8), rid, 0.1)
            self._note_dispatch(("cow",), rid, 0.1)

        def _warm_key(self, key):
            kind = key[0]
            if kind == "decode":
                pass
"""


def test_shapelattice_flags_unpinned_sites(tmp_path):
    fs = lint(tmp_path, LATTICE_BAD, [shapelattice.run])
    assert rules(fs) == ["shape-lattice"]
    assert len(fs) == 4
    msgs = " | ".join(f.message for f in fs)
    assert "not a non-empty tuple literal" in msgs
    assert "not a string constant" in msgs
    assert '"mystery" is not registered' in msgs
    assert "3 components here but FAMILIES registers 2" in msgs


def test_shapelattice_clean_sites(tmp_path):
    assert lint(tmp_path, LATTICE_OK, [shapelattice.run]) == []


def test_shapelattice_warm_key_must_cover_used_families(tmp_path):
    fs = lint(tmp_path, WARM_GAP, [shapelattice.run])
    assert len(fs) == 1
    assert fs[0].rule == "shape-lattice"
    assert "cow" in fs[0].message
    assert fs[0].qualname == "_warm_key"


def _numeric_leg(tmp_path, monkeypatch, grid_result):
    """Run the numeric leg on a minimal engine+model tree with an
    injected _check_grid result."""
    eng = tmp_path / "seldon_tpu" / "servers" / "engine.py"
    eng.parent.mkdir(parents=True, exist_ok=True)
    eng.write_text("class InferenceEngine:\n    pass\n")
    model = tmp_path / "seldon_tpu" / "servers" / "shape_lattice.py"
    model.write_text("def dispatch_keys(spec):\n    return set()\n")
    monkeypatch.setattr(shapelattice, "_check_grid", lambda: grid_result)
    files = core.load_tree([tmp_path / "seldon_tpu"], tmp_path)
    return core.run_passes(files, core.Context(tmp_path),
                           [shapelattice.run])


def test_shapelattice_numeric_hole_is_a_proven_retrace(tmp_path,
                                                       monkeypatch):
    fs = _numeric_leg(tmp_path, monkeypatch,
                      [("--X grid", [("chunk", 64, 2, 0)], [])])
    assert len(fs) == 1 and fs[0].rule == "shape-lattice"
    assert "static retrace proof" in fs[0].message
    assert fs[0].path == "seldon_tpu/servers/shape_lattice.py"


def test_shapelattice_numeric_waste_is_flagged(tmp_path, monkeypatch):
    fs = _numeric_leg(tmp_path, monkeypatch,
                      [("P-- grid", [], [("admit", 32, 8)])])
    assert len(fs) == 1 and fs[0].rule == "shape-lattice-waste"
    assert "warmup waste" in fs[0].message


def test_shapelattice_numeric_agreement_is_clean(tmp_path, monkeypatch):
    assert _numeric_leg(tmp_path, monkeypatch, [("--- grid", [], [])]) == []


# ---------------------------------------------------------------------------
# config-matrix pass
# ---------------------------------------------------------------------------

CM_FIXTURE = """
    class Engine:
        def __init__(self, ecfg):
            self.ecfg = ecfg
            self._paged = bool(ecfg)

        def warmup(self):
            pass

        def submit(self):
            if self._paged:
                self._paged_only()
                return
            self._dense_only()

        def _paged_only(self):
            self._both()

        def _dense_only(self):
            self._both()

        def _both(self):
            pass

        def _dead(self):
            pass
"""


def _cm_model(tmp_path, src, name="fixture.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    return configmatrix.analyze(core.load_tree([p], tmp_path))


def test_configmatrix_narrows_reachability(tmp_path):
    model = _cm_model(tmp_path, CM_FIXTURE)
    P = configmatrix._FLAGS["self._paged"]
    ALL = configmatrix.ALL
    assert model.reach["_paged_only"] == P
    assert model.reach["_dense_only"] == ALL & ~P
    assert model.reach["_both"] == ALL
    assert model.reach["_dead"] == 0
    assert model.kill_list() == ["_dense_only"]
    assert model.dead() == ["_dead"]


def test_configmatrix_dead_method_is_flagged_and_waivable(tmp_path):
    fs = lint(tmp_path, CM_FIXTURE, [configmatrix.run])
    assert [f.rule for f in fs] == ["config-matrix"]
    assert "_dead" in fs[0].message and "unreachable" in fs[0].message
    waived = CM_FIXTURE.replace(
        "def _dead(self):",
        "def _dead(self):  # graftlint: allow(config-matrix) external")
    assert lint(tmp_path, waived, [configmatrix.run]) == []


def _real_engine_model():
    files = core.load_tree(
        [REPO / "seldon_tpu" / "servers" / "engine.py"], REPO)
    model = configmatrix.analyze(files)
    assert model is not None
    return model


@pytest.mark.lint
def test_real_engine_kill_list_nonempty_and_dense_only():
    model = _real_engine_model()
    kill = model.kill_list()
    assert kill, "dense-slab kill-list empty — ROADMAP item 2 needle lost"
    dense = configmatrix._DENSE
    for name in kill:
        m = model.reach[name]
        assert m and not (m & ~dense), (name, bin(m))
    # The paged-path implementations must never land on the kill-list.
    assert "_paged_admit_impl" not in kill
    assert "_cow_copy_impl" not in kill


@pytest.mark.lint
def test_config_matrix_doc_is_fresh():
    # docs/config_matrix.md must match what --gen-config-matrix would
    # write for the real engine (the knobs-doc freshness idiom).
    want = configmatrix.generate_matrix_md(_real_engine_model())
    have = (REPO / "docs" / "config_matrix.md").read_text()
    assert have == want, "docs/config_matrix.md is stale: run " \
        "`python -m tools.graftlint --gen-config-matrix`"


# ---------------------------------------------------------------------------
# shard pass
# ---------------------------------------------------------------------------

AXIS_BAD = """
    import jax
    AXES = ("dp", "tp")

    def f(x, P):
        s = P("dp", "zz")
        y = jax.lax.psum(x, "rogue")
        return s, y
"""

AXIS_OK = """
    import jax
    AXES = ("dp", "tp")

    def f(x, P):
        s = P("dp", None)
        y = jax.lax.psum(x, "tp")
        return s, y
"""

PULL_BAD = """
    import numpy as np

    def g(mesh, f, xs, device_put):
        y = shard_map(f, mesh)(xs)
        z = device_put(xs)
        a = y.item()
        b = np.asarray(y)
        c = float(z)
        return a, b, c
"""

PULL_OK = """
    import numpy as np

    def g(compute, xs):
        y = compute(xs)
        return y.item(), np.asarray(y)
"""

JIT_BAD = """
    import jax
    from jax.sharding import PartitionSpec

    def h(f):
        return jax.jit(f)
"""

JIT_OK = """
    import jax
    from jax.sharding import PartitionSpec

    def h(f, shardings):
        return jax.jit(f, in_shardings=shardings)
"""

JIT_EXEMPT = """
    import jax

    def h(f):
        # engine-style file: no sharding vocabulary imported
        return jax.jit(f, donate_argnums=(0,))
"""


def test_shard_axis_undeclared_names(tmp_path):
    fs = lint(tmp_path, AXIS_BAD, [shardcheck.run])
    assert rules(fs) == ["shard-axis"]
    msgs = " | ".join(f.message for f in fs)
    assert '"zz"' in msgs and '"rogue"' in msgs


def test_shard_axis_declared_names_clean(tmp_path):
    assert lint(tmp_path, AXIS_OK, [shardcheck.run]) == []


def test_shard_axis_skipped_without_axes_decl(tmp_path):
    src = AXIS_BAD.replace('AXES = ("dp", "tp")', "")
    assert lint(tmp_path, src, [shardcheck.run]) == []


AXIS_ALIAS_BAD = """
    from jax.sharding import PartitionSpec

    AXES = ("dp", "tp")
    TP_AXIS = "tensor"
"""

AXIS_ALIAS_OK = """
    from jax.sharding import PartitionSpec

    AXES = ("dp", "tp")
    TP_AXIS = AXES[-1]
    DP_AXIS = "dp"
"""

AXIS_ALIAS_EXEMPT = """
    # Not a sharding file (no PartitionSpec/shard_map import): an _AXIS
    # constant here is not a mesh-axis alias.
    AXES = ("dp", "tp")
    RULE_AXIS = "shard-axis"
"""


def test_shard_axis_string_alias_outside_vocabulary(tmp_path):
    # graftmesh drift guard: a module-level *_AXIS alias re-declared as
    # a raw string must still name a declared mesh axis.
    fs = lint(tmp_path, AXIS_ALIAS_BAD, [shardcheck.run])
    assert rules(fs) == ["shard-axis"]
    assert "TP_AXIS" in fs[0].message and '"tensor"' in fs[0].message


def test_shard_axis_alias_derived_or_in_vocabulary_clean(tmp_path):
    assert lint(tmp_path, AXIS_ALIAS_OK, [shardcheck.run]) == []


def test_shard_axis_alias_non_sharding_file_exempt(tmp_path):
    assert lint(tmp_path, AXIS_ALIAS_EXEMPT, [shardcheck.run]) == []


def test_shard_host_pull_on_tainted_locals(tmp_path):
    fs = lint(tmp_path, PULL_BAD, [shardcheck.run])
    assert rules(fs) == ["shard-host-pull"]
    pulled = " | ".join(f.message for f in fs)
    assert "y.item()" in pulled
    assert "asarray(y)" in pulled
    assert "float(z)" in pulled


def test_shard_host_pull_untainted_clean(tmp_path):
    assert lint(tmp_path, PULL_OK, [shardcheck.run]) == []


PULL_TP_SHARDERS = """
    import numpy as np

    def g(mesh, cfg, params, state, tp_sharding):
        p = tp_sharding.shard_params(mesh, cfg, params)
        s = tp_sharding.shard_state(mesh, state)
        a = np.asarray(p)
        b = s.item()
        return a, b
"""


def test_shard_host_pull_on_tp_sharder_results(tmp_path):
    # graftmesh: shard_params / shard_state return NamedSharding-pinned
    # trees; pulling them to the host gathers the whole TP group.
    fs = lint(tmp_path, PULL_TP_SHARDERS, [shardcheck.run])
    assert rules(fs) == ["shard-host-pull"]
    pulled = " | ".join(f.message for f in fs)
    assert "asarray(p)" in pulled and "s.item()" in pulled


def test_shard_jit_without_shardings_in_sharding_file(tmp_path):
    fs = lint(tmp_path, JIT_BAD, [shardcheck.run])
    assert rules(fs) == ["shard-jit"]


def test_shard_jit_with_shardings_clean(tmp_path):
    assert lint(tmp_path, JIT_OK, [shardcheck.run]) == []


def test_shard_jit_engine_style_file_exempt(tmp_path):
    assert lint(tmp_path, JIT_EXEMPT, [shardcheck.run]) == []


@pytest.mark.lint
def test_real_parallel_tree_is_shard_clean():
    files = core.load_tree([REPO / "seldon_tpu" / "parallel"], REPO)
    fs = shardcheck.run(files, core.Context(REPO))
    assert fs == [], "\n".join(f.render() for f in fs)


@pytest.mark.lint
def test_real_graftmesh_layer_is_shard_clean():
    # The TP serving layer is scanned TOGETHER with parallel/ so its
    # P(...) specs and collectives are held to the real mesh.AXES
    # vocabulary (the axes declaration lives in parallel/mesh.py), and
    # the baseline stays empty — no waivers in the sharded layer.
    files = core.load_tree(
        [REPO / "seldon_tpu" / "parallel",
         REPO / "seldon_tpu" / "models" / "tp_sharding.py",
         REPO / "seldon_tpu" / "servers" / "mesh_engine.py",
         REPO / "seldon_tpu" / "servers" / "engine.py"], REPO)
    fs = shardcheck.run(files, core.Context(REPO))
    assert fs == [], "\n".join(f.render() for f in fs)


# ---------------------------------------------------------------------------
# CLI wiring
# ---------------------------------------------------------------------------


def _cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "tools.graftlint", *args],
        cwd=cwd, capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": str(REPO)},
    )


@pytest.mark.lint
def test_cli_prints_kill_list_headline():
    r = _cli()
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    m = re.search(r"dense-slab kill-list: (\d+) method", r.stdout)
    assert m, r.stdout
    assert int(m.group(1)) >= 1


def test_default_targets_cover_tools_entry_points():
    rels = {sf.rel for sf in core.load_tree(default_targets(REPO), REPO)}
    assert "tools/trace_view.py" in rels
    assert "tools/bench_compare.py" in rels
    assert "seldon_tpu/loadtester.py" in rels
    assert "seldon_tpu/servers/shape_lattice.py" in rels
