"""Where this process runs: the platform question, asked in one place.

Every caller that must know whether it is on a TPU (Pallas kernels
compile there and nowhere else), every endpoint that tells a client
where it ran, and every entry point that wants a persistent compile
cache goes through here. A failed device query raises — nothing in
this module turns an error into "not a TPU".
"""

from __future__ import annotations

import os
from typing import Any, Dict

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def on_tpu() -> bool:
    """True iff JAX's default backend is a TPU."""
    import jax

    return jax.devices()[0].platform == "tpu"


def describe() -> Dict[str, Any]:
    """The device as JAX reports it, plus per-device memory where the
    backend keeps the statistic (TPU does, CPU returns None)."""
    import jax

    devs = jax.devices()
    memory = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        memory.append({
            "id": d.id,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
        })
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "count": len(devs),
        "memory": memory,
    }


def enable_compile_cache() -> str:
    """Persistent XLA compile cache, placeable from outside.

    With JAX_COMPILATION_CACHE_DIR set, JAX reads the variable itself
    and this touches nothing. Without it the cache lives at
    ``<checkout>/.jax_cache`` — a fixed path, because the path is part
    of the cache key and a directory that moves never hits. Returns the
    directory in effect."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
