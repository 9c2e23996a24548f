"""The one persistent compile cache (gpustack_tpu/utils/compile_cache.py):
placed from outside through JAX_COMPILATION_CACHE_DIR, else at one fixed
path inside the checkout — never anywhere that moves."""

import os
import subprocess
import sys

import jax

from gpustack_tpu.utils import compile_cache

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def test_env_var_set_means_no_directory_set_in_code(monkeypatch, tmp_path):
    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: updates.append((k, v))
    )
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in dict(updates)
    # the thresholds still drop, so that tiny programs are kept
    assert dict(updates) == {
        "jax_persistent_cache_min_compile_time_secs": 0,
        "jax_persistent_cache_min_entry_size_bytes": -1,
    }


def test_unset_means_the_fixed_path_in_the_checkout(monkeypatch):
    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: updates.append((k, v))
    )
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert dict(updates)["jax_compilation_cache_dir"] == first


def test_two_processes_arrive_at_the_same_directory(tmp_path):
    """Another process, another cwd, another pid: the same path."""
    code = (
        "from gpustack_tpu.utils.compile_cache import DEFAULT_CACHE_DIR;"
        "print(DEFAULT_CACHE_DIR)"
    )
    env = {k: v for k, v in os.environ.items() if k != compile_cache.ENV_VAR}
    env["PYTHONPATH"] = REPO
    outs = {
        subprocess.run(
            [sys.executable, "-c", code], cwd=cwd, env=env,
            capture_output=True, text=True, check=True, timeout=60,
        ).stdout.strip()
        for cwd in (str(tmp_path), REPO)
    }
    assert outs == {compile_cache.DEFAULT_CACHE_DIR}


def test_helper_derives_nothing_from_tmp_pid_or_time():
    with open(compile_cache.__file__) as f:
        src = f.read()
    for word in ("gettempdir", "mkdtemp", "getpid", "tempfile", "time."):
        assert word not in src, word
