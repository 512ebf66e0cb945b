"""The serve path against the plain float32 reference forward.

Prefill through the dense or the paged KV cache, then greedy decode,
must give the logits the reference computes by one full forward over the
prompt and the generated tokens — the comparison ``chip_smoke.py`` makes
on the chip at the published width, here at a small width on the CPU.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.scope_estimator import CONFIG
from repro.kernels.decode_attention import KernelType
from repro.models import model as M
from repro.models import reference as R
from repro.serving import sampler
from repro.serving.kv_pool import KVPool

SMALL = dataclasses.replace(CONFIG, num_layers=2, d_model=128, num_heads=4,
                            num_kv_heads=2, head_dim=32, d_ff=256,
                            vocab_size=512, dtype="float32")
B, L, T, PAGE = 4, 24, 6, 8


def _serve(cfg, params, path):
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, size=(B, L)).astype(np.int32)
    lens = np.array([L, L - 3, L - 7, L])
    kw = {}
    if path != "dense":
        kw = {"kv_pool": KVPool(n_pages=B * -(-(L + T) // PAGE),
                                page_size=PAGE),
              "kv_kernel": {"paged-xla": KernelType.XLA,
                            "paged-pallas": KernelType.PALLAS}[path]}
    st = sampler.prefill_state(params, cfg, prompts, max_new_tokens=T,
                               prompt_lens=lens, **kw)
    last = np.asarray(st.last_logits)
    _, gen, dec = sampler.decode_segment(params, cfg, st, T)
    return prompts, lens, last, gen, dec


@pytest.mark.parametrize("path", ["dense", "paged-xla", "paged-pallas"])
def test_serve_path_matches_reference_f32(path):
    params = M.init_params(jax.random.PRNGKey(0), SMALL)
    res = R.serve_parity(params, SMALL, *_serve(SMALL, params, path),
                         sampler.DECISION_TOKENS)
    assert R.parity_failures(res, "float32") == [], res


def test_bf16_serve_path_within_bf16_tolerance():
    cfg = dataclasses.replace(SMALL, dtype="bfloat16")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    res = R.serve_parity(params, cfg, *_serve(cfg, params, "dense"),
                         sampler.DECISION_TOKENS)
    assert R.parity_failures(res, "bfloat16") == [], res
    # rounding to bf16 is visible: the f32 bound would not hold
    assert R.parity_failures(res, "float32")


def test_parity_catches_a_wrong_layer():
    """Zeroing one layer's attention output projection in the model the
    serve path runs (the reference keeps it) must break the tolerance."""
    params = M.init_params(jax.random.PRNGKey(0), SMALL)
    seg = params["segments"][0]["0"]
    wo = seg["attn"]["wo"]
    broken = jax.tree.map(lambda a: a, params)
    broken["segments"][0]["0"]["attn"]["wo"] = wo.at[1].set(0.0)
    res = R.serve_parity(params, SMALL, *_serve(SMALL, broken, "dense"),
                         sampler.DECISION_TOKENS)
    assert R.parity_failures(res, "float32")


def test_reference_matches_full_forward():
    params = M.init_params(jax.random.PRNGKey(1), SMALL)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                              SMALL.vocab_size)
    want, _ = M.forward_train(params, SMALL, {"tokens": toks})
    got = R.head(params, SMALL, R.hidden(params, SMALL, toks))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_reference_rejects_other_families():
    with pytest.raises(NotImplementedError, match="dense estimator"):
        R.hidden(None, get_config("gemma2-2b").reduced(), None)


def test_compare_decodes_stops_at_the_first_different_token():
    gen = np.array([[5, 6, 7], [5, 6, 7]])
    dec = np.arange(12, dtype=np.float64).reshape(2, 3, 2) + 1.0
    same = R.compare_decodes((gen, dec), (gen, dec))
    assert same["rel_rms"] == 0.0 and same["rows_equal"] == 1.0
    # row 1 parts at step 1: its step-2 logits saw another token and are
    # left out, however far they moved
    gen_b, dec_b = gen.copy(), dec.copy()
    gen_b[1, 1] = 9
    dec_b[1, 2] += 100.0
    parted = R.compare_decodes((gen_b, dec_b), (gen, dec))
    assert parted["rows_equal"] == 0.5
    assert parted["steps_compared"] == 5 / 6
    assert parted["rel_rms"] == 0.0
