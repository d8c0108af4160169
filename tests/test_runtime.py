"""Backend/environment configuration (``repro/runtime.py``): XLA flag
merging, REPRO_* env presets, and the post-import degradation paths.

These tests run in a process where jax IS already imported (pytest
loads it via conftest), so the import-time-only setters must take the
warn-and-fallback branch — the before-import behavior is pinned through
the env-var values they write, which is all a fresh process would read.
"""
import os
import warnings

import pytest

from repro import runtime


@pytest.fixture
def clean_env(monkeypatch):
    # the setters write os.environ directly, and monkeypatch restores
    # only variables that existed when it touched them: put the whole
    # environment back, or an unknown XLA flag written here is parsed
    # (and refused) by the next backend this process loads
    saved = dict(os.environ)
    for var in ("XLA_FLAGS", "JAX_PLATFORMS", "JAX_ENABLE_X64",
                "REPRO_PLATFORM", "REPRO_X64", "REPRO_CPU_THREADS",
                "REPRO_HOST_DEVICES", "REPRO_XLA_FLAGS",
                "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
        monkeypatch.delenv(var, raising=False)
    yield monkeypatch
    os.environ.clear()
    os.environ.update(saved)


def test_merge_xla_flags_dedupes_by_name_last_wins():
    out = runtime.merge_xla_flags(
        "--xla_a=1 --xla_b=2", "--xla_a=9 --xla_c", "")
    assert out.split() == ["--xla_b=2", "--xla_a=9", "--xla_c"]
    assert runtime.merge_xla_flags("", None if False else "") == ""


def test_add_xla_flags_merges_into_environment(clean_env):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        runtime.add_xla_flags("--xla_foo=1")
        value = runtime.add_xla_flags("--xla_foo=2 --xla_bar=3")
    assert value == os.environ["XLA_FLAGS"]
    assert value.split() == ["--xla_foo=2", "--xla_bar=3"]


def test_set_platform_validates_and_sets_env(clean_env):
    runtime.set_platform("cpu")
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    with pytest.raises(ValueError, match="cpu|gpu|tpu"):
        runtime.set_platform("quantum")


def test_enable_x64_round_trip(clean_env):
    import jax

    try:
        runtime.enable_x64(True)
        assert os.environ["JAX_ENABLE_X64"] == "1"
        assert jax.config.jax_enable_x64 is True
    finally:
        runtime.enable_x64(False)
    assert os.environ["JAX_ENABLE_X64"] == "0"
    assert jax.config.jax_enable_x64 is False


def test_pin_cpu_threads_sets_pools_and_eigen_flag(clean_env):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        runtime.pin_cpu_threads(1)
    assert os.environ["OMP_NUM_THREADS"] == "1"
    assert os.environ["MKL_NUM_THREADS"] == "1"
    assert "--xla_cpu_multi_thread_eigen=false" in os.environ["XLA_FLAGS"]
    with pytest.raises(ValueError, match=">= 1"):
        runtime.pin_cpu_threads(0)


def test_import_time_setters_warn_after_jax_import(clean_env):
    assert runtime.jax_imported()      # conftest already imported it
    with pytest.warns(RuntimeWarning, match="after jax was imported"):
        runtime.add_xla_flags("--xla_probe=1")
    with pytest.warns(RuntimeWarning, match="fresh process"):
        runtime.set_host_device_count(2)


def test_apply_env_presets_reads_overrides(clean_env):
    clean_env.setenv("REPRO_PLATFORM", "cpu")
    clean_env.setenv("REPRO_CPU_THREADS", "1")
    clean_env.setenv("REPRO_XLA_FLAGS", "--xla_custom=7")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        applied = runtime.apply_env_presets()
    assert applied == {"platform": "cpu", "cpu_threads": 1,
                       "xla_flags": "--xla_custom=7"}
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert "--xla_custom=7" in os.environ["XLA_FLAGS"]


def test_apply_env_presets_no_overrides_is_noop(clean_env):
    assert runtime.apply_env_presets() == {}
    assert "XLA_FLAGS" not in os.environ


def test_runtime_module_does_not_import_jax():
    """The whole point of the module: importing it must not pull jax in
    (checked via a fresh interpreter, since this process has jax)."""
    import subprocess
    import sys

    code = ("import sys; from repro import runtime; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    proc = subprocess.run([sys.executable, "-c", code],
                          env={**os.environ,
                               "PYTHONPATH": os.pathsep.join(sys.path)},
                          capture_output=True)
    assert proc.returncode == 0, proc.stderr.decode()


@pytest.mark.parametrize("from_env", [True, False])
def test_use_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code;
    otherwise the cache sits at the fixed ``<checkout>/.jax_cache``."""
    import jax

    before = jax.config.jax_compilation_cache_dir
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want, config_after = str(tmp_path), before
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = config_after = os.path.join(checkout, ".jax_cache")
    try:
        assert runtime.use_compile_cache() == want
        assert runtime.use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == config_after
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
