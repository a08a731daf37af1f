"""JAX process set-up: backend pinning for CPU runs, and the one rule for
where the persistent compilation cache lives.

The program runs on whatever backend JAX selects (the TPU on a TPU host).
Tests, CPU child workers and dryruns pin the CPU backend instead:

- for *child processes*: ``cpu_only_env`` builds an environment with
  ``JAX_PLATFORMS=cpu`` (and optionally N virtual devices);
- for *this process*, before the first backend touch:
  ``force_cpu_platform`` sets the env vars and the ``jax.config``
  override.

Reference counterpart: the reference forces device selection per-process
via its own flags (scanner/engine/worker.cpp device registration).
"""

from __future__ import annotations

import os
from typing import Dict, Optional

_COUNT_FLAG = "--xla_force_host_platform_device_count"

# <checkout>/.jax_cache, derived from this file's location: the path is
# part of the persistent cache's key, so it must never move between runs
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _set_device_count(flags: str, n: int) -> str:
    """Set (or replace) the virtual CPU device-count flag in XLA_FLAGS."""
    kept = [f for f in flags.split() if not f.startswith(_COUNT_FLAG)]
    kept.append(f"{_COUNT_FLAG}={n}")
    return " ".join(kept)


def cpu_only_env(base: Optional[Dict[str, str]] = None,
                 n_devices: Optional[int] = None) -> Dict[str, str]:
    """Environment for a child Python process that must use JAX on CPU:
    sets ``JAX_PLATFORMS=cpu`` and (optionally) requests ``n_devices``
    virtual CPU devices so sharded code paths run without hardware."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        env["XLA_FLAGS"] = _set_device_count(
            env.get("XLA_FLAGS", ""), n_devices)
    return env


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache so jitted-kernel
    executables survive process restarts (a restarted worker, the next
    job process on the host, the next sealed chip run re-load their
    bucket-ladder executables instead of recompiling).  Returns the
    directory in effect.

    One rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and this function sets NO directory; where it is not, the
    directory is the fixed ``<checkout>/.jax_cache``.  Either way the
    min-size/min-compile-time thresholds are lowered so the small ladder
    executables are kept (the defaults skip sub-second compiles).

    An installed package has no checkout: the fixed path is then the
    parent of ``site-packages`` (or a read-only image root), and such a
    deployment must set the variable — the cluster manifests always do
    (deploy.py).  A default that cannot be created raises, naming it."""
    import jax
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    try:
        os.makedirs(_DEFAULT_CACHE_DIR, exist_ok=True)
    except OSError as e:
        raise RuntimeError(
            f"cannot create the compile cache at {_DEFAULT_CACHE_DIR} "
            f"({e}); this is not a writable checkout — set "
            "JAX_COMPILATION_CACHE_DIR to a directory the process can "
            "write") from e
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    return _DEFAULT_CACHE_DIR


def force_cpu_platform(n_devices: Optional[int] = None) -> None:
    """Force THIS process's JAX onto the CPU backend.

    Must run before the first ``jax.devices()`` / backend initialization.
    Safe to call whether or not jax is already imported (platform
    selection stays open until a backend is materialized).
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        os.environ["XLA_FLAGS"] = _set_device_count(
            os.environ.get("XLA_FLAGS", ""), n_devices)
    import jax
    # a jax_platforms value set at config level outranks the env var —
    # override it the same way
    jax.config.update("jax_platforms", "cpu")
    # Env/config are only read at backend init, so a too-late call would
    # otherwise degrade silently — fail fast instead.  (This materializes
    # the CPU backend, which is fine: that's what we're forcing.)
    plat = jax.devices()[0].platform
    if plat != "cpu":
        raise RuntimeError(
            f"force_cpu_platform() too late: JAX backend already "
            f"initialized on '{plat}'; call it before the first "
            "jax.devices()/computation")
    if n_devices is not None:
        have = len(jax.devices())
        if have < n_devices:
            raise RuntimeError(
                f"force_cpu_platform({n_devices}) too late: JAX backend "
                f"already initialized with {have} CPU device(s); call it "
                "before the first jax.devices()/computation")
