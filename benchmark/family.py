"""What differs between architectures is a file of its own,
benchmark/families/<family>.py, found by the name a configuration gives
in its `family` key (absent: DEFAULT, the family of the two
configurations PR 23 brought). A family's file provides

    CONTROL                                          a few words: its lower-precision twin
    model_config_kwargs(cfg) -> dict                 ModelConfig keyword arguments
    build_params(cfg, seed) -> tree                  the weights the unit serves
    forward_logits(params, tokens, cfg, control=False) -> [S, V] float32
    decode_step_cost(cfg, rows, context[, touched]) -> (flops, bytes)

where `cfg` is always the configuration file as a dict (the source's key
names). launcher.py, run.py, reference.py and the roofline reader reach
the architecture through these and through nothing else. This module
imports no JAX, and loading a family imports none either: a family
imports it inside the functions that compute."""

from __future__ import annotations

import importlib.util
import os
from typing import Dict

DEFAULT = "mistral"
PROVIDES = ("CONTROL", "model_config_kwargs", "build_params", "forward_logits",
            "decode_step_cost")


def name_of(cfg: Dict) -> str:
    return cfg.get("family", DEFAULT)


def file_of(bench_dir: str, cfg: Dict) -> str:
    return os.path.join(bench_dir, "families", name_of(cfg) + ".py")


def load(bench_dir: str, cfg: Dict):
    """The module of cfg's family, from bench_dir/families/."""
    name, path = name_of(cfg), file_of(bench_dir, cfg)
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"configuration {cfg.get('name')!r} names the family {name!r}, and there "
            f"is no {path}: a family is a file there that provides {', '.join(PROVIDES)}")
    spec = importlib.util.spec_from_file_location("family_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [p for p in PROVIDES if not hasattr(mod, p)]
    if missing:
        raise AttributeError(f"{path} lacks {', '.join(missing)}")
    return mod
