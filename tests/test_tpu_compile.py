"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e at
ChatGLM3-6B widths (32 query heads, 2 KV heads of width 128, paged
blocks of 16 tokens), with ``interpret=False``.

The chip is described, not attached: nothing runs, but the TPU compiler
refuses what the chip would refuse (tiles not aligned to the layout,
more scoped VMEM than a kernel may use), which interpret mode cannot
show. The topology is described inside a module fixture, never at
import, and every compile happens in this process with the persistent
compilation cache off.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.chunk_prefill import chunk_prefill_attention
from repro.kernels.decode_attention import verify_attention
from repro.kernels.flash_prefill import flash_prefill

H, K, HD, BS = 32, 2, 128, 16          # ChatGLM3-6B heads; paged block
B, MAXBLK = 4, 32                      # 4 sequences of up to 512 tokens
NB = 1 + B * MAXBLK                    # the engine's default pool


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    return lowered.compile()


def _paged(sq):
    bf = jnp.bfloat16
    return [((B, sq, H, HD), bf), ((NB, BS, K, HD), bf),
            ((NB, BS, K, HD), bf), ((B, MAXBLK), jnp.int32),
            ((B,), jnp.int32)]


@pytest.mark.parametrize("sq", [1, 5])
def test_verify_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                           sq):
    _compile(lambda *a: verify_attention(*a, interpret=False), one_chip,
             *_paged(sq))


def test_chunk_prefill_attention_compiles_for_v5e(one_chip,
                                                  no_persistent_cache):
    _compile(lambda *a: chunk_prefill_attention(*a, interpret=False),
             one_chip, *_paged(128))


def test_flash_prefill_compiles_for_v5e(one_chip, no_persistent_cache):
    bf = jnp.bfloat16
    _compile(lambda *a: flash_prefill(*a, prefix_len=128, interpret=False),
             one_chip, ((1, 128, H, HD), bf), ((1, 512, K, HD), bf),
             ((1, 512, K, HD), bf))
