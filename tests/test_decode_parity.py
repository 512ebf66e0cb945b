"""Decode-with-cache must reproduce the full forward, per family."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M

FAMILIES = ["internlm2-1.8b", "starcoder2-3b", "gemma2-2b", "qwen2-vl-7b",
            "mamba2-1.3b", "zamba2-7b", "deepseek-v2-lite-16b",
            "qwen3-moe-235b-a22b"]


@pytest.mark.parametrize("arch", FAMILIES)
def test_decode_matches_full_forward(arch):
    cfg = get_config(arch).reduced()
    if cfg.has_moe():
        # capacity drops are routing-order dependent; remove them for parity
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    b, s, S = 2, 8, 16
    toks = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0,
                              cfg.vocab_size)
    full_logits, _ = M.forward_train(params, cfg, {"tokens": toks})
    caches = M.init_cache(cfg, b, S)
    outs = []
    for t in range(s):
        lg, caches = M.decode_step(params, cfg, toks[:, t:t + 1], caches, t)
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, 1)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full_logits, np.float32),
                               atol=5e-5, rtol=1e-3)


def test_prefill_then_decode_continuation():
    """prefill(prompt) caches + decode steps == full forward on the whole
    sequence (the serving path the sampler uses)."""
    from repro.serving.sampler import _pad_caches
    cfg = get_config("internlm2-1.8b").reduced()
    params = M.init_params(jax.random.PRNGKey(3), cfg)
    b, lp, extra = 2, 6, 4
    toks = jax.random.randint(jax.random.PRNGKey(4), (b, lp + extra), 0,
                              cfg.vocab_size)
    full_logits, _ = M.forward_train(params, cfg, {"tokens": toks})

    # prefill applies the LM head at each row's last prompt position only
    logits_p, caches = M.prefill(params, cfg, {"tokens": toks[:, :lp]})
    np.testing.assert_allclose(np.asarray(logits_p, np.float32),
                               np.asarray(full_logits[:, lp - 1], np.float32),
                               atol=5e-5, rtol=1e-3)
    lens = jnp.asarray([lp, lp - 2], jnp.int32)
    logits_r, _ = M.prefill(params, cfg, {"tokens": toks[:, :lp]}, lens)
    np.testing.assert_allclose(np.asarray(logits_r[1], np.float32),
                               np.asarray(full_logits[1, lp - 3], np.float32),
                               atol=5e-5, rtol=1e-3)
    caches = _pad_caches(caches, lp + extra, lp)
    for t in range(extra):
        lg, caches = M.decode_step(params, cfg, toks[:, lp + t: lp + t + 1],
                                   caches, lp + t)
        np.testing.assert_allclose(
            np.asarray(lg[:, 0], np.float32),
            np.asarray(full_logits[:, lp + t], np.float32),
            atol=5e-5, rtol=1e-3)


def test_ring_buffer_window_decode_matches_full():
    """Ring-buffer KV cache (cache size == window) must equal full-cache
    windowed attention at every step."""
    import dataclasses
    cfg = get_config("gemma2-2b").reduced()
    cfg = dataclasses.replace(cfg, sliding_window=4, force_window=4)
    params = M.init_params(jax.random.PRNGKey(5), cfg)
    b, s = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(6), (b, s), 0,
                              cfg.vocab_size)
    full_logits, _ = M.forward_train(params, cfg, {"tokens": toks})

    caches = M.init_cache(cfg, b, s)      # windowed layers -> ring of 4
    # verify the ring allocation actually happened
    kv_lens = {leaf.shape[3]
               for path, leaf in jax.tree_util.tree_flatten_with_path(caches)[0]
               if getattr(path[-1], "key", "") in ("k", "v")}
    assert kv_lens == {4}
    outs = []
    for t in range(s):
        lg, caches = M.decode_step(params, cfg, toks[:, t:t + 1], caches, t)
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, 1)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full_logits, np.float32),
                               atol=5e-5, rtol=1e-3)
