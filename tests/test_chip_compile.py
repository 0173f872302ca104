"""The main path's Pallas kernels compiled for a TPU v5e at llama2-7b widths.

Nothing here runs: each test lowers a kernel for one chip of a described
(not attached) ``v5e:2x2`` topology and compiles it with the TPU compiler,
which refuses what interpret mode accepts — a block that breaks the
(8, 128) tiling rule, scratch or scalar-prefetch operands that overflow
VMEM/SMEM. The kernels are called with ``interpret=False`` directly:
``repro.kernels.ops`` picks interpret mode from the default backend,
which is the CPU here. Keep these compiles in this one file: only the
process that describes the topology may hold the TPU library.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.kernels import flash_attention as fa
from repro.kernels import paged_decode_attention as pdec
from repro.kernels import swiglu

CFG = get_config("llama2-7b")
H, K, D = CFG.n_heads, CFG.n_kv_heads, CFG.dh
SLOTS, PAGE_TOKENS, MAX_LEN = 8, 16, 256
# page counts of chip_smoke.py's pool: 8 requests × 256 tokens of dense
# llama2-7b KV (1 GiB) in 16-token pages across 32 layers — 8 MiB bf16
# pages, or int8 pages of half that plus per-(layer, head) f32 scales —
# plus the scratch page
POOL_PAGES = {"bf16": 128 + 1, "int8": 255 + 1}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:             # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# the shapes that run the paged kernel: (arch, rows, kv-pool pages,
# page-table width). llama2-7b is chip_smoke.py's; glm4-9b the
# reason_long_kvhalf cell's (64 slots, 7659 pages + the scratch page,
# 4608-token rows); qwen3-14b the planned chat cell's (32 slots,
# 2560-token rows)
PAGED_SHAPES = {
    "bf16": ("llama2-7b", SLOTS, POOL_PAGES["bf16"], MAX_LEN // PAGE_TOKENS),
    "int8": ("llama2-7b", SLOTS, POOL_PAGES["int8"], MAX_LEN // PAGE_TOKENS),
    "glm4-9b-bf16": ("glm4-9b", 64, 7659 + 1, 4608 // PAGE_TOKENS),
    "glm4-9b-int8": ("glm4-9b", 64, 2 * 7659 + 1, 4608 // PAGE_TOKENS),
    "qwen3-14b-bf16": ("qwen3-14b", 32, 4294 + 1, 2560 // PAGE_TOKENS),
    "qwen3-14b-int8": ("qwen3-14b", 32, 2 * 4294 + 1, 2560 // PAGE_TOKENS),
}


@pytest.mark.parametrize("case", list(PAGED_SHAPES))
def test_paged_decode_compiles_for_v5e(one_chip, case):
    """One kernel for every kv-head count and page dtype: head-major
    pages travel as one DMA each into VMEM blocks of
    ``pages_per_block`` pages; int8 scales travel as a per-row VMEM
    block, so SMEM does not grow with the pool (llama2-7b K=32, G=1;
    glm4-9b K=2, G=16; qwen3-14b K=8, G=5)."""
    arch, slots, n_pages, max_pages = PAGED_SHAPES[case]
    cfg = get_config(arch)
    h, k, d = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    page_dt = jnp.int8 if case.endswith("int8") else jnp.bfloat16
    shapes = [((slots, 1, h, d), jnp.bfloat16),
              ((n_pages, k, PAGE_TOKENS, d), page_dt),
              ((n_pages, k, PAGE_TOKENS, d), page_dt),
              ((slots, max_pages), jnp.int32),
              ((slots,), jnp.int32)]
    if page_dt == jnp.bfloat16:
        fn = pdec.paged_decode_attention
    else:
        shapes += [((n_pages, k), jnp.float32)] * 2

        def fn(q, kp, vp, table, lengths, ks, vs):
            return pdec.paged_decode_attention(q, kp, vp, table, lengths,
                                               k_scales=ks, v_scales=vs)
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


def test_flash_attention_prefill_compiles_for_v5e(one_chip):
    q = ((1, MAX_LEN, H, D), jnp.bfloat16)
    kv = ((1, MAX_LEN, K, D), jnp.bfloat16)
    assert "tpu_custom_call" in _compiled_text(fa.flash_attention, one_chip,
                                               q, kv, kv)


@pytest.mark.parametrize("tokens", [SLOTS, MAX_LEN])
def test_fused_glu_compiles_for_v5e(one_chip, tokens):
    """The SwiGLU gate at llama2-7b's d_ff for a decode batch and a
    prefill chunk."""
    h = ((tokens, 2 * CFG.d_ff), jnp.bfloat16)
    assert "tpu_custom_call" in _compiled_text(swiglu.fused_glu, one_chip, h)
