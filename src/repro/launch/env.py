"""Opt-in launch environment profiles (allocator + XLA host flags).

The JAX launchers run with whatever environment they inherit; this module
packages the handful of host-level knobs that repeatedly matter for
CPU-hosted federation sims and multi-client mesh testing, applied ONLY
when a launcher is invoked with ``--env-profile`` (never implicitly —
an env profile re-execs the process, see below). Two profiles:

``host``
    Allocator + log hygiene for any launch:

    * ``LD_PRELOAD=<tcmalloc>`` — glibc malloc serializes the large
      short-lived host allocations of batch building / checkpoint IO;
      tcmalloc's thread caches remove that contention. Detected from the
      usual distro paths (:func:`find_tcmalloc`); silently skipped when
      the library isn't installed.
    * ``TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD=60000000000`` — quiets
      tcmalloc's large-alloc warnings for multi-GB numpy batches.
    * ``TF_CPP_MIN_LOG_LEVEL=4`` — silences the TF/XLA C++ banner noise.

``cpu-mesh``
    Everything in ``host`` plus the XLA host-platform flags:

    * ``--xla_force_host_platform_device_count=N`` — splits the host CPU
      into N XLA devices so shard_map engines and >1-device code paths
      (``repro.api.engines.resolve_engine``) are testable without
      accelerators; N comes from ``--host-devices``.
    * ``--xla_step_marker_location=1`` — puts step markers at the outer
      while loop, so profiles attribute time to whole training steps
      rather than the program entry.

    Flags are APPENDED to any existing ``XLA_FLAGS`` (existing settings
    win: a flag already present is not duplicated or overridden).

Because ``LD_PRELOAD`` and ``XLA_FLAGS`` must be set before the process
(and XLA) initialize, :func:`apply_env_profile` re-execs the current
interpreter with the profile environment; the re-exec is guarded by
``REPRO_ENV_PROFILE_APPLIED=1`` so it happens exactly once.
:func:`profile_env` is the pure (testable) computation of the env delta.

:func:`enable_compile_cache` turns on JAX's persistent compilation cache
for the entry points (``chip_smoke.py``, ``launch/train``, ``launch/serve``)
— called from their ``main``, never at import.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path
from typing import Mapping

ENV_PROFILES = ("none", "host", "cpu-mesh")

_APPLIED_VAR = "REPRO_ENV_PROFILE_APPLIED"

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# a fixed directory inside the checkout (git-ignored): the cache key holds
# the path, so a directory that moved between runs would never hit
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"

# distro locations of tcmalloc, preferred first (full > minimal)
TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
    "/usr/lib64/libtcmalloc.so.4",
)


def find_tcmalloc(paths: tuple[str, ...] = TCMALLOC_PATHS) -> str | None:
    """First installed tcmalloc shared object, or None."""
    for p in paths:
        if os.path.exists(p):
            return p
    return None


def _merge_xla_flags(existing: str, new_flags: list[str]) -> str:
    """Append ``new_flags`` to an ``XLA_FLAGS`` string, skipping any flag
    whose name (the ``--xla_...`` part before ``=``) is already set —
    user-provided flags win over profile defaults."""
    present = {f.split("=", 1)[0] for f in existing.split() if f}
    add = [f for f in new_flags if f.split("=", 1)[0] not in present]
    return " ".join([x for x in [existing.strip()] if x] + add)


def profile_env(profile: str, *, host_devices: int = 1,
                base: Mapping[str, str] | None = None) -> dict[str, str]:
    """The env-var delta ``profile`` applies on top of ``base`` (defaults
    to the current process env). Pure: nothing is mutated or exec'd."""
    if profile not in ENV_PROFILES:
        raise ValueError(f"env profile must be one of {ENV_PROFILES}, "
                         f"got {profile!r}")
    if host_devices < 1:
        raise ValueError(f"host_devices must be >= 1, got {host_devices}")
    base = dict(os.environ if base is None else base)
    if profile == "none":
        return {}
    env: dict[str, str] = {"TF_CPP_MIN_LOG_LEVEL": "4"}
    lib = find_tcmalloc()
    if lib is not None:
        preload = base.get("LD_PRELOAD", "")
        if lib not in preload.split(":"):
            env["LD_PRELOAD"] = ":".join(x for x in (preload, lib) if x)
        env["TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"] = "60000000000"
    if profile == "cpu-mesh":
        env["XLA_FLAGS"] = _merge_xla_flags(base.get("XLA_FLAGS", ""), [
            f"--xla_force_host_platform_device_count={host_devices}",
            "--xla_step_marker_location=1",
        ])
    return env


def add_env_profile_args(ap) -> None:
    """Attach the shared ``--env-profile`` / ``--host-devices`` flags to an
    argparse parser. Every launcher (train, serve, dryrun) exposes the same
    pair so a cpu-mesh invocation looks identical across entry points:

        python -m repro.launch.<any> --env-profile cpu-mesh --host-devices 8
    """
    ap.add_argument("--env-profile", default="none", choices=ENV_PROFILES,
                    help="re-exec under a tuned launch environment "
                         "(allocator + XLA host flags); 'cpu-mesh' splits "
                         "the host CPU into --host-devices XLA devices")
    ap.add_argument("--host-devices", type=int, default=1,
                    help="XLA host device count for the 'cpu-mesh' env "
                         "profile")


def apply_env_profile(profile: str | None, *,
                      host_devices: int = 1) -> bool:
    """Re-exec the current process under ``profile``'s environment.

    No-op (returns False) when the profile is ``None``/"none" or the
    process was already re-exec'd (``REPRO_ENV_PROFILE_APPLIED=1``). On
    the first call it does NOT return: the interpreter is replaced via
    ``os.execvpe`` with the same argv and the augmented env. Call this at
    the very top of a launcher ``main``, before any JAX work.
    """
    if profile is None or profile == "none":
        return False
    if os.environ.get(_APPLIED_VAR) == "1":
        return False
    env = dict(os.environ)
    env.update(profile_env(profile, host_devices=host_devices))
    env[_APPLIED_VAR] = "1"
    os.execvpe(sys.executable, [sys.executable] + sys.argv, env)
    raise AssertionError("unreachable: execvpe does not return")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache goes to the fixed in-checkout
    :data:`DEFAULT_COMPILE_CACHE`. Call before the first compile.
    """
    env = os.environ.get(COMPILE_CACHE_ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)
