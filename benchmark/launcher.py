"""Child process of the benchmark: the unit, through its normal entry point.

Its only extra acts: (1) register the cell's configuration in
seldon_tpu.models.PRESETS under its name before the entry point runs (the
program has no preset for these models and may not be edited by a
benchmark PR); (2) with --profile-dir, watch that directory for `start`
and `stop` files and run jax.profiler between them (only the process that
holds the chip can trace it). Everything else is
`python -m seldon_tpu.runtime.microservice <Class> ...` unchanged.

usage: launcher.py --config <file.json> [--preset-name NAME]
                   [--profile-dir DIR] -- <microservice arguments>
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time


def model_config_kwargs(cfg: dict) -> dict:
    """The benchmark's configuration file (HF key names) as keyword
    arguments of seldon_tpu.models.config.ModelConfig."""
    serving = cfg.get("serving", {})
    kw = dict(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        n_experts=int(cfg.get("num_local_experts", 0) or 0),
        weight_dtype=serving.get("weight_dtype", "bf16"),
        kv_cache_dtype=serving.get("kv_cache_dtype", "bf16"),
    )
    if kw["n_experts"]:
        kw["n_experts_per_token"] = int(cfg["num_experts_per_tok"])
    head_dim = cfg.get("head_dim")
    if head_dim and head_dim * kw["n_heads"] != kw["d_model"]:
        raise ValueError("the program derives head_dim as d_model / n_heads")
    return kw


def register_preset(config_file: str, name: str = "") -> str:
    from seldon_tpu.models.config import PRESETS, ModelConfig

    with open(config_file) as f:
        cfg = json.load(f)
    name = name or cfg["name"]
    PRESETS[name] = ModelConfig(**model_config_kwargs(cfg)).validate()
    return name


def _watch_profile(directory: str) -> None:
    """start -> jax.profiler.start_trace(directory); stop -> stop_trace.
    The wall-clock instants land in `started` / `stopped`."""
    import jax

    def stamp(name: str) -> None:
        with open(os.path.join(directory, name), "w") as f:
            f.write(repr(time.time()))

    running = False
    while True:
        if not running and os.path.exists(os.path.join(directory, "start")):
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(directory, profiler_options=opts)
            running = True
            stamp("started")
        if running and os.path.exists(os.path.join(directory, "stop")):
            stamp("stopping")
            jax.profiler.stop_trace()
            stamp("stopped")
            return
        time.sleep(0.02)


def main(argv) -> int:
    split = argv.index("--")
    own, rest = argv[:split], argv[split + 1:]
    opts = dict(zip(own[::2], own[1::2]))
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.dirname(here))  # the checkout: seldon_tpu/
    register_preset(opts["--config"], opts.get("--preset-name", ""))
    if opts.get("--profile-dir"):
        os.makedirs(opts["--profile-dir"], exist_ok=True)
        threading.Thread(target=_watch_profile, args=(opts["--profile-dir"],),
                         daemon=True).start()
    from seldon_tpu.runtime import microservice

    return microservice.main(rest) or 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
