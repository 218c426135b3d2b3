"""Child process of the benchmark: the unit, through its normal entry point.

Its only extra acts: (1) register the cell's configuration in
seldon_tpu.models.PRESETS under its name before the entry point runs. The
configuration's keys become ModelConfig's through the key map of the
configuration's family (benchmark/families/<family>.py, found by the
file's `family` key: benchmark/family.py), so an architecture with other
keys brings its own map as a new file; the ModelConfig fields it maps
onto have to exist in the program first; (2) with --profile-dir, watch
that directory for `start` and `stop` files and run jax.profiler between
them (only the process that holds the chip can trace it). Everything else
is `python -m seldon_tpu.runtime.microservice <Class> ...` unchanged.

usage: launcher.py --config <file.json> [--preset-name NAME]
                   [--profile-dir DIR] -- <microservice arguments>
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def register_preset(config_file: str, name: str = "") -> str:
    """The configuration, through its family's key map, as the preset the
    unit is asked for: the file states all that the unit runs, so a preset
    the program has under that name is replaced, not mixed in."""
    import family
    from seldon_tpu.models.config import PRESETS, ModelConfig

    with open(config_file) as f:
        cfg = json.load(f)
    name = name or cfg["name"]
    kw = family.load(HERE, cfg).model_config_kwargs(cfg)
    PRESETS[name] = ModelConfig(**kw).validate()
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
    sys.path.insert(0, os.path.dirname(HERE))  # the checkout: seldon_tpu/
    register_preset(opts["--config"], opts.get("--preset-name", ""))
    if opts.get("--profile-dir"):
        os.makedirs(opts["--profile-dir"], exist_ok=True)
        threading.Thread(target=_watch_profile, args=(opts["--profile-dir"],),
                         daemon=True).start()
    from seldon_tpu.runtime import microservice

    return microservice.main(rest) or 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
