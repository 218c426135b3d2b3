"""Test config: JAX on a virtual 8-device CPU mesh, so sharding tests
run without TPU hardware."""

import os
import sys

# Hard-set (not setdefault): tests never touch an accelerator, whatever
# the machine they run on offers.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from seldon_tpu import device  # noqa: E402

# Persistent XLA compilation cache: dozens of engine tests compile the
# SAME tiny-config kernel lattice from scratch (each bit-identical
# on/off pair boots two engines). Keyed by HLO + compile options, so
# hits return byte-identical executables — it changes wall time only.
# The directory is JAX_COMPILATION_CACHE_DIR's, or <checkout>/.jax_cache.
device.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
