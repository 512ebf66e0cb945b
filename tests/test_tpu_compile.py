"""Every Pallas kernel compiles for TPU v5e at scope-qwen3-4b shapes, and
so do the paged decode executables at its attention geometry.

No chip is needed: the TPU compiler installed with jaxlib compiles for a
described (not attached) v5e, and refuses what the chip would refuse —
the interpret-mode kernel tests cannot see tiling rules or missing Mosaic
lowerings.  The topology is described inside a module fixture, never at
import time, so that every test worker collects the same tests and only
the one that runs this file loads the TPU library.  The persistent
compilation cache is off while these compile: an entry written for a
described chip cannot be read back without one.
"""
import dataclasses
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
from repro.launch import hlo_analysis as H
from repro.models import model as M
from repro.serving import sampler
from repro.serving.kv_pool import PagedSpec

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
             ((n_pages, PAGE, HKV, D), jnp.bfloat16),
             ((n_pages, PAGE, HKV, D), jnp.bfloat16), ((B,), jnp.int32),
             ((B, N_W), jnp.int32)])


def _paged_stacked():
    # one layer of the stacked pool the decode layer scan carries
    n_pages = B * N_W + 1
    return (lambda q, k, v, n, t, layer: da.paged_decode_attention(
                q, k, v, n, t, page_size=PAGE, kv_cap=KV_CAP, layer=layer,
                interpret=False),
            [((B, HQ, 1, D), jnp.bfloat16),
             ((CONFIG.num_layers, n_pages, PAGE, HKV, D), jnp.bfloat16),
             ((CONFIG.num_layers, n_pages, PAGE, HKV, D), jnp.bfloat16),
             ((B,), jnp.int32), ((B, N_W), jnp.int32), ((), jnp.int32)])


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
           "paged_decode_attention": _paged,
           "paged_decode_attention_stacked": _paged_stacked, "ssd_scan": _ssd,
           "topk_retrieval": _topk}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]()
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name} compiled without a Mosaic kernel"


# ---------------------------------------------------------------------------
# The paged decode executables at qwen3-4b's attention geometry (32/8 heads
# of 128, bf16, page 16): two layers and small widths elsewhere
# ---------------------------------------------------------------------------
GQA = dataclasses.replace(CONFIG, num_layers=2, d_model=256, d_ff=512,
                          vocab_size=1024)
SLOTS, STEPS = 16, 4
N_PAGES = SLOTS * N_W + 1                   # + the trash page
POOL_OPS = {"copy", "copy-start", "broadcast", "dynamic-slice", "transpose"}
# the parent layout relaid each leaf's gathered page view once a layer
# (two copies); the token-major gather needs no more
VIEW_COPIES = 2


def _paged_executable(name, sharding):
    """The described-v5e compile of sampler executable ``name`` (the plain
    segment, or the fused refill's smallest row bucket) and the shapes of
    its pool's leaves, and how many page blocks its prefill writes."""
    def abstract(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)

    params = jax.eval_shape(lambda k: M.init_params(k, GQA),
                            jax.random.PRNGKey(0))
    last, caches = jax.eval_shape(lambda p: sampler._paged_open(
        p, GQA, SLOTS, N_PAGES, PAGE), params)
    args = [abstract(params), GQA, abstract(last), abstract(caches),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sharding),
            STEPS, 0.0, True, PagedSpec(PAGE, KV_CAP, da.KernelType.XLA),
            ints(SLOTS, N_W), ints(SLOTS),
            jax.ShapeDtypeStruct((SLOTS,), jnp.bool_, sharding=sharding)]
    # the refill's prefill writes each layer's prompt page blocks whole
    blocks = 0
    if name == "_paged_refill_scan_decode":
        rows, width = sampler.row_buckets(SLOTS)[0], 64
        blocks = GQA.num_layers * rows * (width // PAGE)
        args += [ints(rows), ints(rows, width), ints(rows),
                 ints(rows * (width // PAGE))]
    text = getattr(sampler, name).lower(*args).compile().as_text()
    return text, [tuple(x.shape) for x in jax.tree.leaves(caches)], blocks


@pytest.mark.parametrize("name", ["_paged_scan_decode",
                                  "_paged_refill_scan_decode"])
def test_paged_decode_writes_one_tile_a_row_for_v5e(name, one_chip):
    """Token-major pages on the chip's compiler: the pool is updated in
    place (aliased, no pool- or layer-sized copy, broadcast, slice or
    transpose), each K/V write is one ``(hkv, hd)`` tile for each of the
    ``SLOTS`` rows (the head-major layout made one 128-wide row for each
    row and KV head: SLOTS * 8 updates), and the gathered page view is
    relaid no more often than the head-major layout did."""
    text, leaves, blocks = _paged_executable(name, one_chip)
    assert all(shape[1:] == (N_PAGES, PAGE, HKV, D) for shape in leaves)
    pool_params = [i for i, shape in H.entry_parameters(text).items()
                   if shape in set(leaves)]
    assert len(pool_params) == len(leaves)
    assert set(pool_params) <= set(H.aliased_parameters(text))
    shapes = set(leaves) | {shape[1:] for shape in leaves}
    assert H.ops_shaped_like(text, POOL_OPS, shapes) == []
    # one K and one V write a layer, each SLOTS updates of a whole tile
    # (and the refill's prefill: one update a page block)
    want = [SLOTS, SLOTS] + [blocks, blocks] * (blocks > 0)
    assert sorted(H.scatter_update_counts(text, leaves)) == sorted(want)
    views = {(SLOTS, N_W, PAGE, HKV, D), (SLOTS, N_W * PAGE, HKV, D),
             (SLOTS, KV_CAP, HKV, D)}
    assert len(H.ops_shaped_like(text, {"copy"}, views)) <= VIEW_COPIES
