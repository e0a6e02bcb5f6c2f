"""Computation-environment configuration: platforms, XLA flags, workers.

The multi-host roadmap item needs multi-process CPU simulation before it
needs real pods, and that is an *environment* problem: JAX fixes its
platform and host device count at first import from ``JAX_PLATFORMS`` /
``XLA_FLAGS``, so anything that spawns workers (the supervisor in
:mod:`repro.launch.supervisor`, a future ``jax.distributed`` launcher)
must assemble a child environment **before** the child's interpreter
starts. This module owns that assembly:

* :func:`merged_xla_flags` / :func:`host_device_flags` — pure string
  surgery on an ``XLA_FLAGS`` value: replace one ``--flag=value`` token
  while preserving every other flag the caller (or CI) already set.
* :func:`worker_env` — the subprocess environment for one worker: base
  env (default ``os.environ``), the platform inherited unless the caller
  pins one, and — on the CPU platform only — the host platform forced to
  ``devices`` virtual devices. This is how the supervisor respawns a
  CPU takeover on a *degraded* device count — the child's mesh is
  smaller, the checkpoint's virtual slot count is not, and elastic
  resume keeps the result bitwise. A TPU worker sees the chips it has.
* :func:`enable_compile_cache` — the persistent compilation cache every
  entry point turns on first.
* :func:`set_host_device_count` / :func:`set_platform` /
  :func:`enable_x64` — in-process setters for the same knobs, guarded
  against the classic footgun of calling them after JAX has already
  initialised its backends (they would silently do nothing).
* :func:`describe` — the effective environment, for logs and health.
"""
from __future__ import annotations

import os
import pathlib
import sys
from typing import Mapping, Optional

__all__ = ["DEVICE_COUNT_FLAG", "merged_xla_flags", "host_device_flags",
           "worker_env", "enable_compile_cache", "set_host_device_count",
           "set_platform", "enable_x64", "describe"]

DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"

# The checkout this package runs from (src/repro/launch/env.py -> root).
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def merged_xla_flags(existing: Optional[str], flag: str, value) -> str:
    """An ``XLA_FLAGS`` string with ``flag`` set to ``value``.

    Every other token of ``existing`` is preserved verbatim (CI sets its
    own device count there; a worker override must not clobber unrelated
    flags), and an existing occurrence of ``flag`` is replaced in place
    rather than appended — XLA takes the first occurrence, so appending
    would silently lose the override.
    """
    token = f"{flag}={value}"
    parts = (existing or "").split()
    out, replaced = [], False
    for p in parts:
        if p == flag or p.startswith(flag + "="):
            out.append(token)
            replaced = True
        else:
            out.append(p)
    if not replaced:
        out.append(token)
    return " ".join(out)


def host_device_flags(devices: int, existing: Optional[str] = None) -> str:
    """``XLA_FLAGS`` forcing ``devices`` virtual host-platform devices."""
    if devices < 1:
        raise ValueError(f"devices must be >= 1, got {devices}")
    return merged_xla_flags(existing, DEVICE_COUNT_FLAG, int(devices))


def worker_env(devices: int, base: Optional[Mapping] = None,
               platform: Optional[str] = None) -> dict:
    """The environment for one spawned worker process.

    ``base`` defaults to ``os.environ`` (the worker inherits PYTHONPATH,
    locale, ``JAX_PLATFORMS``, everything). ``platform`` pins
    ``JAX_PLATFORMS``; ``None`` leaves the inherited value, so a worker
    on a TPU host gets the chip unless its caller says otherwise. Only
    when the worker's platform is ``cpu`` is ``XLA_FLAGS`` rewritten to
    force ``devices`` virtual devices: the flag shapes the host platform
    and means nothing to an accelerator. The returned dict is a copy —
    mutating it never touches the parent's environment.
    """
    env = dict(os.environ if base is None else base)
    if platform is not None:
        env["JAX_PLATFORMS"] = platform
    if env.get("JAX_PLATFORMS") == "cpu":
        env["XLA_FLAGS"] = host_device_flags(devices, env.get("XLA_FLAGS"))
    return env


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set. Otherwise the cache lives at ``.jax_cache/`` in
    the checkout: a fixed path, so every process of every run finds what
    an earlier one compiled. ``JAX_ENABLE_COMPILATION_CACHE=false`` (the
    test suite sets it) turns the cache off and this returns ``None``.
    Call it at the top of an entry point, before the first compile.
    """
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _jax_initialized() -> bool:
    """Whether this process's JAX has already picked its backends."""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    try:
        from jax._src import xla_bridge
        return xla_bridge.backends_are_initialized()
    except Exception:
        # Compat: without the introspection API, jax being imported at
        # all is the conservative signal.
        return True


def set_host_device_count(devices: int) -> None:
    """Force this process's host platform to ``devices`` virtual devices.

    Mutates ``os.environ['XLA_FLAGS']`` (preserving unrelated flags).
    Must run before JAX initialises its backends — afterwards the flag
    is read-once stale and this raises instead of silently doing
    nothing. Worker processes should prefer :func:`worker_env`, which
    sets the child environment before its interpreter even starts.
    """
    if _jax_initialized():
        raise RuntimeError(
            "set_host_device_count called after JAX initialised its "
            "backends — the device count is fixed at first use. Set it "
            "earlier in the process, or spawn the work into a subprocess "
            "with worker_env()")
    os.environ["XLA_FLAGS"] = host_device_flags(
        devices, os.environ.get("XLA_FLAGS"))


def set_platform(platform: str = "cpu") -> None:
    """Pin the JAX platform (cpu/gpu/tpu) for this process.

    Sets ``JAX_PLATFORMS`` and, when JAX is importable, the
    ``jax_platform_name`` config — effective only before backend
    initialisation, so this raises once it is too late (same contract
    as :func:`set_host_device_count`).
    """
    if _jax_initialized():
        raise RuntimeError(
            "set_platform called after JAX initialised its backends — "
            "spawn a subprocess with worker_env() instead")
    os.environ["JAX_PLATFORMS"] = platform
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_platform_name", platform)


def enable_x64(enable: bool = True) -> None:
    """Toggle 64-bit array defaults (the x64 switch is runtime-safe)."""
    import jax

    jax.config.update("jax_enable_x64", bool(enable))


def describe() -> dict:
    """The effective environment (for logs, health endpoints, and the
    supervisor's status document); imports JAX only if already loaded."""
    out = {
        "xla_flags": os.environ.get("XLA_FLAGS"),
        "jax_platforms": os.environ.get("JAX_PLATFORMS"),
        "jax_imported": "jax" in sys.modules,
        "pid": os.getpid(),
    }
    jax = sys.modules.get("jax")
    if jax is not None and _jax_initialized():
        out["platform"] = jax.default_backend()
        out["device_count"] = jax.device_count()
        out["x64"] = bool(jax.config.read("jax_enable_x64"))
    return out
