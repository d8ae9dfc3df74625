"""Device acquisition and the compile cache for the program's entry points.

An entry point takes what ``jax.devices()`` gives and prints it. Finding
no TPU is an error, unless the user asked for the CPU explicitly with
``JAX_PLATFORMS=cpu``: that run is a rehearsal, and its output is stamped
``cpu``. Nothing here probes, retries or falls back.
"""

from __future__ import annotations

import os
import sys

#: Compile cache used when ``JAX_COMPILATION_CACHE_DIR`` is not set: one
#: fixed path inside the checkout (listed in ``.gitignore``), since the
#: path is part of the cache key.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_compilation_cache() -> None:
    """Turn on JAX's persistent compilation cache for accelerator programs.

    With ``JAX_COMPILATION_CACHE_DIR`` set (or a cache dir already
    configured) JAX owns the cache and nothing is set here. CPU programs
    are never cached, so tests write nothing into the checkout."""
    import jax

    if (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or jax.config.jax_compilation_cache_dir):
        return
    if jax.default_backend() == "cpu":
        return
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def device_stamp(devices) -> dict:
    """The device a result ran on, as JAX reports it."""
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def acquire_devices(allow_cpu: bool = None):
    """``jax.devices()`` for an entry point, printed as platform, kind and
    count. Raises ``RuntimeError`` when no TPU is found, unless
    ``allow_cpu`` (default: the user set ``JAX_PLATFORMS=cpu``)."""
    import jax

    if allow_cpu is None:
        allow_cpu = os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
    devices = jax.devices()
    stamp = device_stamp(devices)
    print(f"# devices: platform={stamp['platform']} kind={stamp['kind']} "
          f"count={stamp['count']}", file=sys.stderr)
    if stamp["platform"] != "tpu" and not allow_cpu:
        raise RuntimeError(
            f"no TPU found (jax.devices() gives {stamp['platform']}); "
            "set JAX_PLATFORMS=cpu to rehearse on the CPU")
    enable_compilation_cache()
    return devices
