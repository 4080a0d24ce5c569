"""The persistent compilation cache keys programs on their metadata
too, so a cached executable keeps the named scopes of the program that
asked for it."""

import hashlib

import jax
import jax.numpy as jnp

from repro import compile_cache


def _key(scope: str) -> str:
    """The computation part of JAX's cache key for a program that
    differs from the others only in its named scope."""
    from jax._src import cache_key

    def cycle(x):
        with jax.named_scope(scope):
            return x * 2

    module = jax.jit(cycle).lower(jnp.ones(3)).compiler_ir()
    h = hashlib.sha256()
    cache_key._hash_computation(h, module, cache_key.IgnoreCallbacks.NO)
    return h.hexdigest()


def test_enable_keys_the_cache_on_named_scopes(monkeypatch, tmp_path):
    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    try:
        jax.config.update(flag, False)
        assert _key("act") == _key("learn")
        assert compile_cache.enable() == str(tmp_path)
        assert _key("act") != _key("learn")
    finally:
        jax.config.update(flag, was)
