"""Backend / environment configuration, applied BEFORE the first JAX
import.

JAX reads ``JAX_PLATFORMS`` / ``JAX_ENABLE_X64`` / ``XLA_FLAGS`` once,
at import time — so a serving process that wants a pinned backend or a
deterministic CPU thread count must set them before ``import jax`` runs
anywhere in the process.  This module is import-safe for that purpose:
it imports neither jax nor anything that does (``repro`` is a namespace
package), so drivers can do::

    from repro import runtime
    runtime.apply_env_presets()      # reads REPRO_* overrides
    runtime.pin_cpu_threads(1)       # deterministic CPU-container runs

    import jax                       # only now

Every setter degrades gracefully when jax is already imported: the
platform / x64 toggles fall back to ``jax.config.update`` (which still
works post-import) and the XLA flag setters warn that the flags will
only take effect in a fresh process.

Environment overrides read by :func:`apply_env_presets`:

``REPRO_PLATFORM``     — ``cpu`` | ``gpu`` | ``tpu`` (JAX_PLATFORMS)
``REPRO_X64``          — ``1``/``true`` to enable float64
``REPRO_CPU_THREADS``  — pin host thread pools (OMP/MKL/Eigen) to N
``REPRO_HOST_DEVICES`` — fake N host devices (mesh tests on CPU)
``REPRO_XLA_FLAGS``    — extra raw XLA flags, merged (last wins)

:func:`use_compile_cache` places JAX's persistent compilation cache
(call it from an entry point's ``main``, before the first compile).
"""
from __future__ import annotations

import os
import sys
import warnings

_TRUTHY = {"1", "true", "yes", "on"}

# the checkout root (src/repro/runtime.py -> ../..)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def jax_imported() -> bool:
    """Whether jax is already in this process (flag changes that only
    apply at import time are too late once this is True)."""
    return "jax" in sys.modules


def _warn_too_late(what: str) -> None:
    warnings.warn(
        f"{what} was requested after jax was imported; it only takes "
        "effect in a fresh process (set it before the first jax import)",
        RuntimeWarning, stacklevel=3)


def merge_xla_flags(*flag_strings: str) -> str:
    """Merge whitespace-separated ``--flag=value`` strings, deduplicating
    by flag name — later strings win, order otherwise preserved."""
    merged: dict = {}
    for s in flag_strings:
        for tok in (s or "").split():
            name = tok.split("=", 1)[0]
            merged.pop(name, None)
            merged[name] = tok
    return " ".join(merged.values())


def add_xla_flags(flags: str) -> str:
    """Merge ``flags`` into ``XLA_FLAGS`` (existing different flags kept,
    same-name flags overridden).  Returns the resulting value."""
    if jax_imported():
        _warn_too_late(f"XLA_FLAGS {flags!r}")
    value = merge_xla_flags(os.environ.get("XLA_FLAGS", ""), flags)
    os.environ["XLA_FLAGS"] = value
    return value


def set_platform(name: str) -> None:
    """Pin the JAX backend (``cpu`` | ``gpu`` | ``tpu``).

    Before the first jax import this sets ``JAX_PLATFORMS``; after it,
    falls back to ``jax.config.update("jax_platforms", ...)``.
    """
    name = str(name).lower()
    if name not in ("cpu", "gpu", "tpu"):
        raise ValueError(f"platform must be cpu|gpu|tpu, got {name!r}")
    os.environ["JAX_PLATFORMS"] = name
    if jax_imported():
        import jax
        jax.config.update("jax_platforms", name)


def enable_x64(on: bool = True) -> None:
    """Toggle 64-bit mode (works before or after the jax import)."""
    os.environ["JAX_ENABLE_X64"] = "1" if on else "0"
    if jax_imported():
        import jax
        jax.config.update("jax_enable_x64", bool(on))


def set_host_device_count(n: int) -> None:
    """Fake ``n`` host devices on the CPU backend (multi-process mesh
    tests without hardware) — import-time only."""
    n = int(n)
    if n < 1:
        raise ValueError("host device count must be >= 1")
    add_xla_flags(f"--xla_force_host_platform_device_count={n}")


def pin_cpu_threads(n: int) -> None:
    """Pin every host-side thread pool to ``n`` threads so CPU-container
    runs (serving benchmarks especially) are deterministic: OMP / MKL /
    OpenBLAS workers plus, at ``n == 1``, XLA:CPU's multi-threaded Eigen
    contractions."""
    n = int(n)
    if n < 1:
        raise ValueError("thread count must be >= 1")
    for var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS",
                "MKL_NUM_THREADS", "VECLIB_MAXIMUM_THREADS",
                "NUMEXPR_NUM_THREADS"):
        os.environ[var] = str(n)
    if n == 1:
        add_xla_flags("--xla_cpu_multi_thread_eigen=false "
                      "intra_op_parallelism_threads=1")


def apply_env_presets() -> dict:
    """Apply the ``REPRO_*`` environment overrides (see module
    docstring).  Returns the settings that were applied — empty when no
    override is set, so calling this unconditionally is free."""
    applied: dict = {}
    platform = os.environ.get("REPRO_PLATFORM")
    if platform:
        set_platform(platform)
        applied["platform"] = platform.lower()
    x64 = os.environ.get("REPRO_X64")
    if x64 is not None:
        on = x64.strip().lower() in _TRUTHY
        enable_x64(on)
        applied["x64"] = on
    threads = os.environ.get("REPRO_CPU_THREADS")
    if threads:
        pin_cpu_threads(int(threads))
        applied["cpu_threads"] = int(threads)
    devices = os.environ.get("REPRO_HOST_DEVICES")
    if devices:
        set_host_device_count(int(devices))
        applied["host_devices"] = int(devices)
    extra = os.environ.get("REPRO_XLA_FLAGS")
    if extra:
        add_xla_flags(extra)
        applied["xla_flags"] = extra
    return applied


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache``: the directory is part of what a cached
    entry is found by, so a temporary or per-process path would never
    be hit again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
