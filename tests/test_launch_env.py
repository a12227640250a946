"""Direct unit tests for repro.launch.env (backfill satellite): the pure
profile computation, the user-flags-win XLA merge, and the re-exec guard
of apply_env_profile — everything testable without actually exec'ing."""
import os

import pytest

from repro.launch.env import (
    ENV_PROFILES,
    _APPLIED_VAR,
    _merge_xla_flags,
    apply_env_profile,
    find_tcmalloc,
    profile_env,
)


# ---------------------------------------------------------------------------
# _merge_xla_flags: profile defaults never override user flags
# ---------------------------------------------------------------------------

def test_merge_appends_to_empty_and_existing():
    assert _merge_xla_flags("", ["--xla_a=1"]) == "--xla_a=1"
    assert _merge_xla_flags("--xla_b=2", ["--xla_a=1"]) == \
        "--xla_b=2 --xla_a=1"


def test_merge_user_flags_win():
    """A flag NAME already present is skipped entirely — the user's value
    survives, no duplicate is appended."""
    merged = _merge_xla_flags(
        "--xla_force_host_platform_device_count=16",
        ["--xla_force_host_platform_device_count=4",
         "--xla_step_marker_location=1"])
    assert merged.count("--xla_force_host_platform_device_count") == 1
    assert "--xla_force_host_platform_device_count=16" in merged
    assert "--xla_step_marker_location=1" in merged


def test_merge_handles_whitespace_and_valueless_flags():
    assert _merge_xla_flags("  --xla_a  ", ["--xla_a=9", "--xla_b"]) == \
        "--xla_a --xla_b"


# ---------------------------------------------------------------------------
# profile_env: pure computation of the delta
# ---------------------------------------------------------------------------

def test_profile_env_validates_inputs():
    with pytest.raises(ValueError):
        profile_env("gpu-turbo")
    with pytest.raises(ValueError):
        profile_env("cpu-mesh", host_devices=0)
    assert set(ENV_PROFILES) == {"none", "host", "cpu-mesh"}


def test_profile_none_is_empty_delta():
    assert profile_env("none", base={}) == {}


def test_profile_host_sets_log_level_and_optional_tcmalloc():
    env = profile_env("host", base={})
    assert env["TF_CPP_MIN_LOG_LEVEL"] == "4"
    assert "XLA_FLAGS" not in env
    lib = find_tcmalloc()
    if lib is None:
        assert "LD_PRELOAD" not in env
    else:
        assert lib in env["LD_PRELOAD"].split(":")
        assert env["TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"]


def test_profile_cpu_mesh_adds_host_platform_flags():
    env = profile_env("cpu-mesh", host_devices=8, base={})
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    assert "--xla_step_marker_location=1" in env["XLA_FLAGS"]


def test_profile_cpu_mesh_respects_user_xla_flags():
    base = {"XLA_FLAGS": "--xla_force_host_platform_device_count=32"}
    env = profile_env("cpu-mesh", host_devices=4, base=base)
    assert "--xla_force_host_platform_device_count=32" in env["XLA_FLAGS"]
    assert "--xla_force_host_platform_device_count=4" not in env["XLA_FLAGS"]
    assert "--xla_step_marker_location=1" in env["XLA_FLAGS"]


def test_profile_env_does_not_mutate_process_env():
    before = dict(os.environ)
    profile_env("cpu-mesh", host_devices=2)
    assert dict(os.environ) == before


def test_tcmalloc_preload_not_duplicated():
    lib = find_tcmalloc()
    if lib is None:
        pytest.skip("no tcmalloc installed in this environment")
    env = profile_env("host", base={"LD_PRELOAD": lib})
    # already preloaded by the user: the profile adds nothing
    assert "LD_PRELOAD" not in env


def test_find_tcmalloc_prefers_listed_order(tmp_path):
    a, b = tmp_path / "full.so", tmp_path / "minimal.so"
    a.write_bytes(b"")
    b.write_bytes(b"")
    assert find_tcmalloc((str(a), str(b))) == str(a)
    assert find_tcmalloc((str(tmp_path / "nope.so"),)) is None


# ---------------------------------------------------------------------------
# apply_env_profile: the re-exec guard
# ---------------------------------------------------------------------------

def test_apply_none_profile_never_reexecs(monkeypatch):
    monkeypatch.delenv(_APPLIED_VAR, raising=False)
    assert apply_env_profile(None) is False
    assert apply_env_profile("none") is False


def test_apply_guard_blocks_second_exec(monkeypatch):
    """After the re-exec set REPRO_ENV_PROFILE_APPLIED=1, a second call is
    a no-op returning False — the guard is what makes the exec happen
    exactly once."""
    monkeypatch.setenv(_APPLIED_VAR, "1")
    called = []
    monkeypatch.setattr(os, "execvpe",
                        lambda *a, **kw: called.append(a))
    assert apply_env_profile("cpu-mesh", host_devices=4) is False
    assert not called


def test_apply_execs_with_guard_and_profile_env(monkeypatch):
    """First application: execvpe is invoked with the same argv, the
    profile's delta, and the guard variable set for the child."""
    monkeypatch.delenv(_APPLIED_VAR, raising=False)
    # user XLA_FLAGS win over the profile's, so a flag inherited from the
    # surrounding environment (CI exports one) would mask the profile value
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    captured = {}

    def fake_exec(exe, argv, env):
        captured.update(exe=exe, argv=argv, env=env)
        raise SystemExit(0)                  # stand-in for "does not return"

    monkeypatch.setattr(os, "execvpe", fake_exec)
    with pytest.raises(SystemExit):
        apply_env_profile("cpu-mesh", host_devices=2)
    import sys
    assert captured["exe"] == sys.executable
    assert captured["argv"] == [sys.executable] + sys.argv
    assert captured["env"][_APPLIED_VAR] == "1"
    assert "--xla_force_host_platform_device_count=2" in \
        captured["env"]["XLA_FLAGS"]
    assert captured["env"]["TF_CPP_MIN_LOG_LEVEL"] == "4"


# ---------------------------------------------------------------------------
# enable_compile_cache: JAX_COMPILATION_CACHE_DIR wins, else a fixed path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"],
                         ids=["default", "env"])
def test_enable_compile_cache(monkeypatch, env_dir):
    from pathlib import Path

    import jax

    from repro.launch.env import (
        COMPILE_CACHE_ENV,
        DEFAULT_COMPILE_CACHE,
        enable_compile_cache,
    )
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(COMPILE_CACHE_ENV, raising=False)
    else:
        monkeypatch.setenv(COMPILE_CACHE_ENV, env_dir)
    try:
        got = enable_compile_cache()
        if env_dir is None:
            # a fixed directory inside the checkout, which git ignores
            root = Path(__file__).resolve().parents[1]
            assert got == str(DEFAULT_COMPILE_CACHE) == str(root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert ".jax_cache/" in (root / ".gitignore").read_text().split()
        else:
            # JAX reads the variable itself; no other cache is set in code
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
