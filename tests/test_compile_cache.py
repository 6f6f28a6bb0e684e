"""Where the entry points put JAX's persistent compile cache."""

import jax

from repro.launch import compile_cache


def _updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    calls = _updates(monkeypatch)
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch, tmp_path):
    calls = _updates(monkeypatch)
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", tmp_path / ".jax_cache")
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    assert first == str(tmp_path / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", first)] * 2
    # the real default is <checkout>/.jax_cache, next to src/
    root = compile_cache.Path(compile_cache.__file__).resolve().parents[3]
    monkeypatch.undo()
    assert compile_cache.DEFAULT_DIR == root / ".jax_cache"
    assert (root / "src" / "repro").is_dir()
