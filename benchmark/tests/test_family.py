"""The first family's file holds what launcher.py, reference.py and
costs.py held before a configuration had a family: the key map, the
closed forms and the forward pass give what the files they were moved
from gave (tests/data/moved_from_pr25.json: computed once from the
parent's three files, before the move). Also the loader's refusals, the
preset a configuration registers, and `tp` from the cell."""
import hashlib
import json
import os
import sys

import pytest

import family

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "tests", "data", "moved_from_pr25.json")) as f:
    OLD = json.load(f)
CONFIGS = ("mistral-7b-v0.3", "mixtral-8x7b")


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CONFIGS)
def test_the_two_configurations_name_no_family_and_get_the_first(name):
    cfg = _cfg(name)
    assert "family" not in cfg and family.name_of(cfg) == family.DEFAULT
    fam = family.load(BENCH, cfg)
    assert fam.__file__ == os.path.join(BENCH, "families", "mistral.py")
    assert all(hasattr(fam, p) for p in family.PROVIDES)


@pytest.mark.parametrize("name", CONFIGS)
def test_key_map_equals_the_launchers_old_map_key_for_key(name):
    cfg = _cfg(name)
    new = family.load(BENCH, cfg).model_config_kwargs(cfg)
    old = OLD["configs"][name]["model_config_kwargs"]   # [key, type, value], in order
    assert [[k, type(v).__name__, v] for k, v in new.items()] == old
    with pytest.raises(ValueError):  # and refuses what it refused
        family.load(BENCH, cfg).model_config_kwargs(dict(cfg, head_dim=64))


@pytest.mark.parametrize("context", [1, 400, 1023])
@pytest.mark.parametrize("rows", [1, 4.2, 64])
@pytest.mark.parametrize("name", CONFIGS)
def test_decode_step_cost_equals_the_old_closed_form_exactly(name, rows, context):
    import costs
    import peaks
    cfg, fam = _cfg(name), family.load(BENCH, _cfg(name))
    (old,) = [g for g in OLD["configs"][name]["decode_step_cost"]
              if (g["rows"], g["context"]) == (rows, context)]
    assert fam.decode_step_cost(cfg, rows, context) == (old["flops"], old["bytes"])
    for f, v in OLD["configs"][name]["closed_forms"].items():
        assert getattr(fam, f)(cfg) == v and type(getattr(fam, f)(cfg)) is type(v)
    assert fam.experts_touched(cfg, rows) == old["experts_touched"]
    pk = peaks.peaks_for("TPU v5 lite")
    assert (costs.least_seconds(*fam.decode_step_cost(cfg, rows, context), pk)
            == (old["least_seconds"], old["side"]))


@pytest.mark.parametrize("control", [False, True])
@pytest.mark.parametrize("preset", ["tiny", "tiny-moe"])
def test_forward_logits_is_bit_equal_to_the_function_it_was_moved_from(preset, control):
    import jax
    import numpy as np
    from seldon_tpu.models.config import get_config
    from seldon_tpu.models.quantize import init_params_int8
    from test_reference import file_keys

    cfg = get_config(preset, weight_dtype="int8")
    params = init_params_int8(cfg, jax.random.key(5))
    toks = jax.random.randint(jax.random.key(6), (33,), 0, cfg.vocab_size)
    old = OLD["logits_sha256"][f"{preset}/{'control' if control else 'served'}"]
    got = np.asarray(family.load(BENCH, {}).forward_logits(params, toks, file_keys(cfg),
                                                           control=control))
    assert got.dtype == np.float32 and list(got.shape) == old["shape"]
    assert [float(x) for x in got[-1, :4]] == old["first"]
    assert hashlib.sha256(got.tobytes()).hexdigest() == old["sha256"]


def test_build_params_is_the_programs_seeded_int8_tree():
    import jax
    import numpy as np
    from seldon_tpu.models.config import ModelConfig
    from seldon_tpu.models.quantize import init_params_int8

    cfg = dict(_cfg("mixtral-8x7b"), hidden_size=64, intermediate_size=96,
               num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, vocab_size=300, num_local_experts=4)
    fam = family.load(BENCH, cfg)
    got = fam.build_params(cfg, 11)
    want = init_params_int8(ModelConfig(**fam.model_config_kwargs(cfg)).validate(),
                            jax.random.key(11))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))
    bf16 = dict(cfg, serving=dict(cfg["serving"], weight_dtype="bf16"))
    with pytest.raises(ValueError):  # its forward pass reads scales: no other tree
        fam.build_params(bf16, 11)


def test_a_family_without_a_file_or_without_a_function_is_refused_by_name(tmp_path):
    with pytest.raises(FileNotFoundError) as e:
        family.load(BENCH, {"name": "x", "family": "ghost"})
    assert os.path.join(BENCH, "families", "ghost.py") in str(e.value)
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "half.py").write_text(
        "CONTROL = 'none'\n\n\ndef model_config_kwargs(cfg):\n    return {}\n")
    with pytest.raises(AttributeError) as e:
        family.load(str(tmp_path), {"family": "half"})
    assert "build_params, forward_logits, decode_step_cost" in str(e.value)


def test_loading_a_family_imports_no_jax():
    import subprocess
    code = ("import sys; sys.path.insert(0, %r); import family; "
            "f = family.load(%r, {}); f.decode_step_cost; "
            "sys.exit('jax' in sys.modules)" % (BENCH, BENCH))
    assert subprocess.run([sys.executable, "-c", code], timeout=60).returncode == 0


def test_a_configuration_registers_as_the_preset_the_unit_is_asked_for(tmp_path):
    import launcher
    from seldon_tpu.models.config import PRESETS, ModelConfig

    cfg = dict(_cfg("mistral-7b-v0.3"), name="laid-over", num_hidden_layers=3)
    path = tmp_path / "laid-over.json"
    path.write_text(json.dumps(cfg))
    kw = family.load(BENCH, cfg).model_config_kwargs(cfg)
    try:
        assert launcher.register_preset(str(path)) == "laid-over"
        assert PRESETS["laid-over"] == ModelConfig(**kw)
        PRESETS["laid-over"] = ModelConfig(eos_token_id=7, n_layers=9)
        launcher.register_preset(str(path))   # the file states all the unit runs
        assert PRESETS["laid-over"] == ModelConfig(**kw)
        assert launcher.register_preset(str(path), "other-name") == "other-name"
        assert PRESETS["other-name"].n_layers == 3
    finally:
        PRESETS.pop("laid-over", None)
        PRESETS.pop("other-name", None)


def test_the_unit_gets_as_many_chips_as_the_cell_asks():
    import run

    class Args:
        workload, seed, seconds, trace, rehearse = "mixtral.chat", 2147483999, 10.0, 0, False
    r = run.Run(Args)
    tp = lambda: {p["name"]: p for p in r.unit_parameters()}["tp"]
    assert r.cell["chips"] == 1 and tp() == {"name": "tp", "value": "1", "type": "INT"}
    r.cell = dict(r.cell, chips=4)
    assert tp()["value"] == "4"
