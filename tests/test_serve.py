"""Entry-point contracts of ``repro.launch.serve`` and its compile cache."""

from __future__ import annotations

import sys

import jax
import pytest

from repro.launch import compile_cache, serve


def test_forced_kernel_without_tpu_exits_nonzero(monkeypatch, capsys):
    """``--use-kernel on`` off-TPU must fail, not serve interpret-mode QPS."""
    assert jax.default_backend() != "tpu"
    monkeypatch.setattr(sys, "argv", ["serve", "--use-kernel", "on",
                                      "--n", "64", "--dim", "16"])
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert exc.value.code not in (None, 0)
    assert "needs a TPU" in str(exc.value.code)
    assert "QPS" not in capsys.readouterr().out


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_directory(monkeypatch, tmp_path, restore_cache_config,
                                 env_dir):
    """The environment's directory wins and is left to JAX; otherwise the
    cache goes to the fixed in-checkout directory."""
    jax.config.update("jax_compilation_cache_dir", None)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.enable() == str(compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(compile_cache.DEFAULT_DIR)
        assert compile_cache.DEFAULT_DIR.parent.joinpath(".gitignore").is_file()
    else:
        env = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        assert compile_cache.enable() == env
        # Left to JAX, which reads the variable when it is imported.
        assert jax.config.jax_compilation_cache_dir is None
