"""Where the persistent compilation cache lives (repro.launch.compile_cache).
Only the directory is resolved here: tests never turn the cache on."""
from pathlib import Path

from repro.launch import compile_cache


def test_env_var_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    assert compile_cache.compile_cache_dir() == str(tmp_path / "cc")


def test_fixed_checkout_path_otherwise(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = Path(compile_cache.compile_cache_dir())
    checkout = Path(__file__).resolve().parents[1]
    assert path == checkout / ".jax_cache"
    # the same on every call: no pid, time or temp component
    assert compile_cache.compile_cache_dir() == str(path)
    assert ".jax_cache/" in (checkout / ".gitignore").read_text().splitlines()
