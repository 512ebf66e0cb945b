"""Every Pallas kernel compiles for TPU v5e at scope-qwen3-4b shapes.

No chip is needed: the TPU compiler installed with jaxlib compiles for a
described (not attached) v5e, and refuses what the chip would refuse —
the interpret-mode kernel tests cannot see tiling rules or missing Mosaic
lowerings.  The topology is described inside a module fixture, never at
import time, so that every test worker collects the same tests and only
the one that runs this file loads the TPU library.  The persistent
compilation cache is off while these compile: an entry written for a
described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.scope_estimator import CONFIG
from repro.data.worldsim import EMBED_DIM
from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import ssd_scan as ss
from repro.kernels import topk_retrieval as tk

HQ, HKV, D = CONFIG.num_heads, CONFIG.num_kv_heads, CONFIG.resolved_head_dim
PAGE = 16
B = 8                       # decode slots
KV_CAP = 64 + 12            # max prompt + decode budget
N_W = -(-KV_CAP // PAGE)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _flash():
    return (lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                               interpret=False),
            [((2, HQ, 128, D), jnp.bfloat16), ((2, HKV, 128, D), jnp.bfloat16),
             ((2, HKV, 128, D), jnp.bfloat16)])


def _decode():
    return (lambda q, k, v, n: da.decode_attention(q, k, v, n,
                                                   interpret=False),
            [((B, HQ, 1, D), jnp.bfloat16), ((B, HKV, 512, D), jnp.bfloat16),
             ((B, HKV, 512, D), jnp.bfloat16), ((B,), jnp.int32)])


def _paged():
    n_pages = B * N_W + 1
    return (lambda q, k, v, n, t: da.paged_decode_attention(
                q, k, v, n, t, page_size=PAGE, kv_cap=KV_CAP,
                interpret=False),
            [((B, HQ, 1, D), jnp.bfloat16),
             ((n_pages, HKV, PAGE, D), jnp.bfloat16),
             ((n_pages, HKV, PAGE, D), jnp.bfloat16), ((B,), jnp.int32),
             ((B, N_W), jnp.int32)])


def _ssd():
    # Mamba2 heads: head dim 64, state 128, two 128-token chunks
    return (lambda x, dt, a, b, c: ss.ssd_scan(x, dt, a, b, c, chunk=128,
                                               interpret=False),
            [((1, 256, 8, 64), jnp.float32), ((1, 256, 8), jnp.float32),
             ((8,), jnp.float32), ((1, 256, 128), jnp.float32),
             ((1, 256, 128), jnp.float32)])


def _topk():
    # the retriever's anchor matrix is small; queries arrive a few at once
    return (lambda q, a: tk.topk_retrieval(q, a, 5, interpret=False,
                                           anchors_prenormalized=True),
            [((3, EMBED_DIM), jnp.float32), ((512, EMBED_DIM), jnp.float32)])


KERNELS = {"flash_attention": _flash, "decode_attention": _decode,
           "paged_decode_attention": _paged, "ssd_scan": _ssd,
           "topk_retrieval": _topk}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]()
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name} compiled without a Mosaic kernel"
