"""Main-path kernels compiled by the real TPU compiler for a *described* v5e.

No chip is attached and nothing executes: ``jax.jit(...).lower(shapes).compile()``
against ``v5e:2x2`` raises exactly what the chip's compiler would raise (tile
misalignment, VMEM over-subscription, an unsupported lowering) — failures
interpret mode on CPU cannot see. About two seconds per case; no whole-model
compile belongs in tier-1.

This is the only file that describes a TPU. Only one process at a time may
load the TPU library, so the topology is described inside a fixture (never at
import, in ``skipif``, in ``parametrize`` arguments or in ``conftest.py``) and
every compile happens in this process. ``tests/conftest.py`` turns the
persistent compile cache on; a described-topology executable is written there
but cannot be read back without a chip, so the cache is off around this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dynamo_tpu.models.config import PRESETS

#: 128 is what the kernels were tuned at; 16 is what ``dynamo_tpu.launch``
#: actually serves (model_card default, no CLI flag) and chip_smoke.py runs.
PAGES = [16, 128]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory pinned to one described chip. For the module,
    the persistent compile cache is off and matmul precision is the serving
    default (conftest's "highest" is a CPU golden-parity setting; Mosaic
    refuses an fp32-precision bf16 matmul)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    one_chip = SingleDeviceSharding(topo.devices[0])
    cache_was = jax.config.jax_enable_compilation_cache
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    cc.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    jax.config.update("jax_default_matmul_precision", precision_was)
    jax.config.update("jax_enable_compilation_cache", cache_was)
    cc.reset_cache()


def _compiled_text(fn, *args, **static) -> str:
    return jax.jit(fn, static_argnames=tuple(static)).lower(*args, **static).compile().as_text()


def _gqa_operands(sds, preset: str, batch: int, t_q: int, page: int, context: int = 2048):
    cfg = PRESETS[preset]
    width = cfg.num_kv_heads * cfg.head_dim
    pages_per_seq = context // page
    cache = sds((batch * pages_per_seq + 1, page, width), jnp.bfloat16)
    return cfg, (
        sds((batch, t_q, cfg.num_heads, cfg.head_dim), jnp.bfloat16),
        cache, cache,
        sds((batch, pages_per_seq), jnp.int32),
        sds((batch, t_q), jnp.int32),
    )


@pytest.mark.parametrize("page", PAGES, ids=lambda p: f"page{p}")
@pytest.mark.parametrize("preset", ["llama-3.2-1b", "llama-3-8b"])
@pytest.mark.parametrize("t_q", [1, 5], ids=["decode", "verify5"])
def test_paged_decode_kernel_compiles(sds, preset, t_q, page):
    from dynamo_tpu.ops.pallas_paged import decode_kernel_supported, paged_decode_attention

    cfg, operands = _gqa_operands(sds, preset, batch=32, t_q=t_q, page=page)
    assert decode_kernel_supported(
        cfg.num_heads, cfg.head_dim, cfg.num_kv_heads * cfg.head_dim, t_q)
    text = _compiled_text(paged_decode_attention, *operands, scale=cfg.head_dim ** -0.5)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("page", PAGES, ids=lambda p: f"page{p}")
@pytest.mark.parametrize("preset", ["llama-3.2-1b", "llama-3-8b"])
def test_paged_prefill_kernel_compiles(sds, preset, page):
    from dynamo_tpu.ops.pallas_prefill import paged_prefill_attention

    # A 512-token chunk continuing a sequence whose earlier pages are cached.
    cfg, operands = _gqa_operands(sds, preset, batch=2, t_q=512, page=page)
    text = _compiled_text(paged_prefill_attention, *operands, scale=cfg.head_dim ** -0.5)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("t_q", [1, 5], ids=["decode", "verify5"])
def test_mla_decode_kernel_compiles(sds, t_q):
    from dynamo_tpu.models.mla import mla_cache_widths
    from dynamo_tpu.ops.pallas_mla import mla_decode_supported, mla_paged_decode

    cfg = PRESETS["mla-8b-proxy"]
    # kv_lora 512 latents + rope 64, the rope stream padded to one lane tile.
    r_kv, r_rope = mla_cache_widths(cfg)
    assert (r_kv, r_rope) == (512, 128)
    batch, page, pages_per_seq = 32, 128, 16
    assert mla_decode_supported(r_kv, r_rope, t_q, cfg.num_heads)
    text = _compiled_text(
        mla_paged_decode,
        sds((batch, t_q, cfg.num_heads, r_kv), jnp.bfloat16),
        sds((batch, t_q, cfg.num_heads, r_rope), jnp.bfloat16),
        sds((batch * pages_per_seq + 1, page, r_kv), jnp.bfloat16),
        sds((batch * pages_per_seq + 1, page, r_rope), jnp.bfloat16),
        sds((batch, pages_per_seq), jnp.int32),
        sds((batch, t_q), jnp.int32),
        scale=(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("mode,d_in,d_out,fuses", [
    ("int8", 4096, 14336, True),   # Llama-3-8B gate/up projection
    ("int4", 4096, 4096, True),    # Llama-3-8B q/o projection
    # Known (PERF.md, PR 21): at the MLP widths today's v5e compiler
    # materializes the dequantized int4 weight (temp = 2x the bf16 weight),
    # so this case pins only that the program compiles.
    ("int4", 4096, 14336, False),
])
def test_quantized_matmul_compiles(sds, mode, d_in, d_out, fuses):
    """Weight-only quantized matmul at Llama-3-8B widths, decode batch. Where
    the dequant fuses into the dot's operand read there is no temp buffer
    anywhere near the size of the bf16 weight."""
    from dynamo_tpu.models.quant import default_group_size, quant_matmul

    if mode == "int8":
        leaf = {"qw": sds((d_in, d_out), jnp.int8), "scale": sds((d_out,), jnp.bfloat16)}
    else:
        groups = d_in // default_group_size()
        leaf = {"qw4": sds((d_in // 2, d_out), jnp.int8),
                "scale": sds((groups, d_out), jnp.bfloat16)}
    compiled = jax.jit(quant_matmul).lower(sds((32, d_in), jnp.bfloat16), leaf).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    if fuses:
        assert temp < d_in * d_out * 2 // 4
