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


def _served_params(sds, cfg, laid: bool = True):
    """The int8 tree's shapes as an unsharded runner serves them: a latent
    model's per-head up-projections heads-major (``engine/runner.py``;
    ``laid=False``: as published, the control)."""
    from dynamo_tpu.models.mla import lay_heads_major
    from dynamo_tpu.models.quant import init_params_quantized

    lay = lay_heads_major if laid else (lambda tree: tree)
    return jax.tree.map(lambda x: sds(x.shape, x.dtype), jax.eval_shape(lambda: lay(init_params_quantized(cfg, 0, mode="int8"))))


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


#: Mellum2-12B-A2.5B's attention widths (benchmark/configs/mellum2-12b-a2.5b-int8.json):
#: 32 query and 4 KV heads of 128 (a 512-lane page slab), page 128, window 1024.
MELLUM2 = dict(heads=32, kv_heads=4, head_dim=128, page=128)


def _mellum2_operands(sds, batch: int, t_q: int, context: int):
    m = MELLUM2
    pages_per_seq = context // m["page"]
    cache = sds((batch * pages_per_seq + 1, m["page"], m["kv_heads"] * m["head_dim"]), jnp.bfloat16)
    return (sds((batch, t_q, m["heads"], m["head_dim"]), jnp.bfloat16), cache, cache,
            sds((batch, pages_per_seq), jnp.int32), sds((batch, t_q), jnp.int32))


@pytest.mark.parametrize("t_q", [1, 5], ids=["decode", "verify5"])
def test_windowed_decode_kernel_compiles(sds, t_q):
    """The windowed block walk (window a runtime scalar: two more prefetched
    operands) at 8 rows x 8,192 tokens of context."""
    from dynamo_tpu.ops.pallas_paged import paged_decode_attention

    text = _compiled_text(
        lambda *a: paged_decode_attention(*a, scale=MELLUM2["head_dim"] ** -0.5, window=a[-1][0, 0] * 0 + 1024),
        *_mellum2_operands(sds, batch=8, t_q=t_q, context=8192))
    assert "tpu_custom_call" in text


def test_windowed_prefill_kernel_compiles(sds):
    """A 64-token chunk (the benchmark's pinned chunk) per row of a mixed
    step, the window a runtime scalar."""
    from dynamo_tpu.ops.pallas_prefill import paged_prefill_attention

    text = _compiled_text(
        lambda *a: paged_prefill_attention(*a, scale=MELLUM2["head_dim"] ** -0.5, window=a[-1][0, 0] * 0 + 1024),
        *_mellum2_operands(sds, batch=8, t_q=64, context=8192))
    assert "tpu_custom_call" in text


#: ``mla-8b-proxy`` at 32 rows, then the two reason-saturated cells' calls
#: (benchmark/configs/joyai-llm-flash-ep8-int8.json at 32 heads,
#: longcat-flash-chat-ep32-int8.json at 64): 64 rows of 16 pages; a decode row
#: is one query, a chunk's queries go in tiles of 16 at 32 heads and of 8 at 64
#: (models/mla._query_tile), 512 kernel rows either way. The walk's tail copies
#: and waits under conditions and compiles a branch per count of held pages,
#: whose temporaries at 512 rows pass the default scoped VMEM.
MLA_CALLS = {
    "decode": (32, 32, 1), "verify5": (32, 32, 5),
    "joyai_decode": (64, 32, 1), "joyai_verify8": (64, 32, 8), "joyai_chunk_tile16": (64, 32, 16),
    "longcat_decode": (64, 64, 1), "longcat_chunk_tile8": (64, 64, 8),
}


@pytest.mark.parametrize("batch,heads,t_q", MLA_CALLS.values(), ids=MLA_CALLS.keys())
def test_mla_decode_kernel_compiles(sds, batch, heads, t_q):
    from dynamo_tpu.models.mla import mla_cache_widths
    from dynamo_tpu.ops.pallas_mla import mla_decode_supported, mla_paged_decode

    cfg = PRESETS["mla-8b-proxy"]
    # kv_lora 512 latents + rope 64, the rope stream padded to one lane tile.
    r_kv, r_rope = mla_cache_widths(cfg)
    assert (r_kv, r_rope) == (512, 128)
    page, pages_per_seq = 128, 16
    assert mla_decode_supported(r_kv, r_rope, t_q, heads)
    text = _compiled_text(
        mla_paged_decode,
        sds((batch, t_q, heads, r_kv), jnp.bfloat16),
        sds((batch, t_q, heads, r_rope), jnp.bfloat16),
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


# -- int8 expert grouped matmul (ops/pallas_moe.py) ---------------------------

#: (hidden, expert width, experts, top-k): OLMoE-1B-7B and DeepSeek-V2-Lite.
MOE_WIDTHS = {"olmoe-1b-7b": (2048, 1024, 64, 8), "deepseek-v2-lite": (2048, 1408, 64, 6)}


def _expert_leaves(sds, e, d, f):
    leaf = lambda d_in, d_out: {"qw": sds((e, d_in, d_out), jnp.int8), "scale": sds((e, d_out), jnp.bfloat16)}  # noqa: E731
    return leaf(d, f), leaf(d, f), leaf(f, d)


@pytest.mark.parametrize("preset, tokens", [
    ("olmoe-1b-7b", 4), ("olmoe-1b-7b", 64), ("olmoe-1b-7b", 256), ("olmoe-1b-7b", 4096),
    ("deepseek-v2-lite", 4), ("deepseek-v2-lite", 512),
], ids=lambda v: str(v))
def test_moe_grouped_matmul_kernel_compiles(sds, preset, tokens):
    """Gate+up ([64, 2048, F] twice over one left operand) and down
    ([64, F, 2048]) for 32 to 32,768 token copies: decode batches, a chunk
    of prefill, the 64-row mixed step of the saturated cell."""
    from dynamo_tpu.ops.pallas_moe import expert_ffn_int8, supported

    d, f, e, k = MOE_WIDTHS[preset]
    assert (PRESETS[preset].hidden_size, PRESETS[preset].moe_intermediate_size) == (d, f)
    assert supported(d, f) and supported(f, d)
    gate, up, down = _expert_leaves(sds, e, d, f)
    text = _compiled_text(expert_ffn_int8, sds((tokens * k, d), jnp.bfloat16), gate, up, down, sds((e,), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # The int8 arrays reach the kernel as stored: nothing widens them outside.
    assert f"bf16[{e},{d},{f}]" not in text and f"bf16[{e},{f},{d}]" not in text


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "widened"])
def test_olmoe_int8_step_moves_no_expert_array_outside_the_kernel(sds, kernel, monkeypatch):
    """A whole OLMoE int8 decode step (the layer scan over stacked weights, 4
    rows) lowered for the v5e. With the kernel, no operation of the program
    but the two custom calls touches a layer's experts: no convert-multiply
    to bf16[64, 2048, 1024], and no dynamic-slice copy of the int8 layer
    either (the kernel takes the stack and the layer index). Without it
    (what the CPU backend's predicate picks) the widened arrays are there:
    the assertion has something to miss."""
    import dataclasses
    import functools

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.quant import init_params_quantized
    from dynamo_tpu.parallel import moe

    cfg = dataclasses.replace(PRESETS["olmoe-1b-7b"], num_layers=2)
    if kernel:
        monkeypatch.setattr(moe, "_kernel_platform", lambda: True)  # the described chip, not this CPU
    like = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    params = like(jax.eval_shape(lambda: init_params_quantized(cfg, 0, mode="int8")))
    assert moe.experts_path(params["layers"]) == ("fused" if kernel else "widened")
    rows, page, pages_per_seq = 4, 128, 16
    k_cache, v_cache = like(jax.eval_shape(lambda: llama.init_kv_cache(cfg, rows * pages_per_seq + 1, page)))
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    text = jax.jit(functools.partial(llama.forward, cfg=cfg, attn_impl="pallas")).lower(
        params=params, tokens=i32(rows, 1), positions=i32(rows, 1), k_cache=k_cache, v_cache=v_cache,
        block_tables=i32(rows, pages_per_seq), slot_mapping=i32(rows, 1), last_token_index=i32(rows),
    ).compile().as_text()
    widened = [line for line in text.splitlines() if "bf16[64,2048,1024]" in line or "bf16[64,1024,2048]" in line]
    sliced = [line for line in text.splitlines() if "s8[1,64,2048,1024]" in line or "s8[1,64,1024,2048]" in line]
    if kernel:
        assert text.count("moe_grouped_matmul_int8") >= 2
        assert not widened, widened[:2]
        assert not sliced, sliced[:2]
    else:
        assert widened and "moe_grouped_matmul_int8" not in text


@pytest.mark.parametrize("t", [1, 64], ids=["decode", "mixed-chunk"])
def test_mixed_layer_step_is_one_layer_body_on_the_kernels(sds, t, monkeypatch):
    """Four layers (one period: sliding, sliding, sliding, full) of Mellum2 at
    its published widths, int8, 8 rows over 64 pages of 128: the scan that
    carries the per-layer window and RoPE compiles to ONE attention kernel and
    the two expert kernels (one layer body for both kinds), and no operation
    but those touches a layer's experts."""
    import functools
    import json
    import pathlib

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.quant import init_params_quantized
    from dynamo_tpu.parallel import moe

    doc = json.loads((pathlib.Path(__file__).parents[1] / "benchmark/configs/mellum2-12b-a2.5b-int8.json").read_text())
    hf = {k: v for k, v in doc.items() if k not in ("serve", "rehearsal", "assumed", "reduced_why")}
    hf.update(num_hidden_layers=4, layer_types=hf["layer_types"][:4], mlp_layer_types=hf["mlp_layer_types"][:4])
    cfg = ModelConfig.from_hf(hf, name="mellum2-one-period")
    assert cfg.mixed_attention
    monkeypatch.setattr(moe, "_kernel_platform", lambda: True)  # the described chip, not this CPU
    like = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    params = like(jax.eval_shape(lambda: init_params_quantized(cfg, 0, mode="int8")))
    rows, page, pages_per_seq = 8, 128, 64
    k_cache, v_cache = like(jax.eval_shape(lambda: llama.init_kv_cache(cfg, 321, page)))
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    text = jax.jit(functools.partial(llama.forward, cfg=cfg, attn_impl="pallas")).lower(
        params=params, tokens=i32(rows, t), positions=i32(rows, t), k_cache=k_cache, v_cache=v_cache,
        block_tables=i32(rows, pages_per_seq), slot_mapping=i32(rows, t), last_token_index=i32(rows),
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert ("paged_decode_attention" if t == 1 else "paged_prefill_attention") in text
    assert text.count("moe_grouped_matmul_int8") >= 2
    for shape in ("bf16[64,2304,896]", "bf16[64,896,2304]", "s8[1,64,2304,896]", "s8[1,64,896,2304]"):
        assert shape not in text, shape


def _olmoe_operands(sds, batch: int, t_q: int, context: int):
    """OLMoE-1B-7B's attention widths: 16 query and 16 KV heads of 128 (a
    2048-lane page slab, one query head a KV head), page 128."""
    pages_per_seq = context // 128
    cache = sds((batch * pages_per_seq + 1, 128, 16 * 128), jnp.bfloat16)
    return (sds((batch, t_q, 16, 128), jnp.bfloat16), cache, cache,
            sds((batch, pages_per_seq), jnp.int32), sds((batch, t_q), jnp.int32))


@pytest.mark.parametrize("model", ["olmoe", "mellum2-windowed"])
def test_chunked_kernel_takes_one_query_rows(sds, model):
    """The decode slots of a split chunk step: ``paged_prefill_attention`` with
    T = 1 (``start = kv_len - 1``; the query block is the whole one-token
    array), 64 rows of one query head a KV head and 8 rows of a group of 8."""
    from dynamo_tpu.ops.pallas_prefill import paged_prefill_attention

    if model == "olmoe":
        fn = lambda *a: paged_prefill_attention(*a, scale=128 ** -0.5)  # noqa: E731
        operands = _olmoe_operands(sds, batch=64, t_q=1, context=1024)
    else:
        fn = lambda *a: paged_prefill_attention(*a, scale=128 ** -0.5, window=a[-1][0, 0] * 0 + 1024)  # noqa: E731
        operands = _mellum2_operands(sds, batch=8, t_q=1, context=8192)
    text = _compiled_text(fn, *operands)
    assert "tpu_custom_call" in text and "paged_prefill_attention" in text


def _split_step_text(sds, cfg, nd: int, tc: int, pages_per_seq: int, monkeypatch, nc: int = 1) -> str:
    """``llama.forward`` on a split token axis (``nd`` decode slots, ``nc``
    chunk slots of ``tc``), int8, lowered for the described chip."""
    import functools

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.quant import init_params_quantized
    from dynamo_tpu.parallel import moe

    monkeypatch.setattr(moe, "_kernel_platform", lambda: True)  # the described chip, not this CPU
    like = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    params = like(jax.eval_shape(lambda: init_params_quantized(cfg, 0, mode="int8")))
    k_cache, v_cache = like(jax.eval_shape(lambda: llama.init_kv_cache(cfg, 385, 128)))
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    toks = nd + nc * tc
    return jax.jit(functools.partial(llama.forward, cfg=cfg, attn_impl="pallas", split=(nd, nc, tc))).lower(
        params=params, tokens=i32(toks), positions=i32(toks), k_cache=k_cache, v_cache=v_cache,
        block_tables=i32(nd + nc, pages_per_seq), slot_mapping=i32(toks), last_token_index=i32(nd + nc),
    ).compile().as_text()


def _no_rectangle(text: str, nd: int, tc: int) -> None:
    """No activation of the padded step is left: nothing shaped [rows, chunk, ...]."""
    import re

    left = sorted(set(re.findall(rf"\b(?:bf16|f32|s32)\[{nd},{tc},[0-9,]*\]", text)))
    assert not left, left


@pytest.mark.parametrize("nc", [1, 2], ids=["one-chunk-slot", "two-chunk-slots"])
def test_split_mixed_step_olmoe_largest_corner(sds, monkeypatch, nc):
    """The saturated cell's largest mixed step on a split token axis: 64
    decode slots + one 64-token chunk slot over 8 pages, two layers of OLMoE
    at its widths. Both attention calls are the chunked kernel (the
    benchmark's prefill roofline sums that kernel's events inside a mixed
    step's program), the decode kernel is not in the program, and no
    [64, 64, ...] activation is left. With two chunk slots (a prompt's tail
    and the next one's head in one step: no cell runs it) the same holds."""
    import dataclasses

    cfg = dataclasses.replace(PRESETS["olmoe-1b-7b"], num_layers=2)
    text = _split_step_text(sds, cfg, nd=64, tc=64, pages_per_seq=8, monkeypatch=monkeypatch, nc=nc)
    assert text.count('custom_call_target="tpu_custom_call"') == 4  # one layer body: 2 attention + 2 expert calls
    assert text.count("paged_prefill_attention") >= 2 and "paged_decode_attention" not in text
    assert text.count("moe_grouped_matmul_int8") >= 2
    _no_rectangle(text, 64, 64)


def test_split_mixed_step_mellum2_largest_corner(sds, monkeypatch):
    """longctx-decode's largest mixed step: 8 decode slots + one 64-token
    chunk slot over 64 pages, one period (sliding x 3, full) of Mellum2 at its
    widths, the window a per-layer runtime scalar in both calls."""
    import json
    import pathlib

    from dynamo_tpu.models.config import ModelConfig

    doc = json.loads((pathlib.Path(__file__).parents[1] / "benchmark/configs/mellum2-12b-a2.5b-int8.json").read_text())
    hf = {k: v for k, v in doc.items() if k not in ("serve", "rehearsal", "assumed", "reduced_why")}
    hf.update(num_hidden_layers=4, layer_types=hf["layer_types"][:4], mlp_layer_types=hf["mlp_layer_types"][:4])
    cfg = ModelConfig.from_hf(hf, name="mellum2-one-period")
    text = _split_step_text(sds, cfg, nd=8, tc=64, pages_per_seq=64, monkeypatch=monkeypatch)
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert text.count("paged_prefill_attention") >= 2 and "paged_decode_attention" not in text
    _no_rectangle(text, 8, 64)


@pytest.mark.parametrize("split", [None, (64, 1, 64)], ids=["decode", "mixed-chunk"])
def test_shortcut_moe_step_longcat_largest_corners(sds, monkeypatch, split):
    """reason-saturated's largest steps at LongCat-Flash's widths (two of its
    double layers, this chip's 16 of 512 experts): 64 decode rows, and 64
    decode slots + one 64-token chunk slot, over 16 pages. One layer body: the
    two MLA sublayers' attention through the MLA kernel (a chunk's 64 queries
    in tiles of 8: 16 overran the scoped VMEM), the held experts through the
    grouped int8 kernel at its 6144 x 2048 tiles in each arm of the layer's
    ``cond`` (the usual pass; every copy's rows), and no expert array copied
    outside it."""
    import functools
    import json
    import pathlib
    import re

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.parallel import moe

    monkeypatch.setattr(moe, "_kernel_platform", lambda: True)  # the described chip, not this CPU
    doc = json.loads((pathlib.Path(__file__).parents[1] / "benchmark/configs/longcat-flash-chat-ep32-int8.json").read_text())
    hf = {k: v for k, v in doc.items() if k not in ("serve", "rehearsal", "assumed", "reduced_why", "deployment")}
    cfg = ModelConfig.from_hf({**hf, "num_layers": 2}, name="longcat-two-layers")
    like = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    params = _served_params(sds, cfg)
    k_cache, v_cache = like(jax.eval_shape(lambda: llama.init_kv_cache(cfg, 1025, 128)))
    assert k_cache.shape == (4, 1025, 128, 512) and v_cache.shape == (4, 1025, 128, 128)
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    if split is None:
        toks, slots = (64, 1), 64
    else:
        toks, slots = (split[0] + split[1] * split[2],), split[0] + split[1]
    text = jax.jit(functools.partial(llama.forward, cfg=cfg, attn_impl="pallas", split=split, moe_counts=True)).lower(
        params=params, tokens=i32(*toks), positions=i32(*toks), k_cache=k_cache, v_cache=v_cache,
        block_tables=i32(slots, 16), slot_mapping=i32(*toks), last_token_index=i32(slots),
    ).compile().as_text()
    # One layer body: an MLA call per sublayer (two per sublayer on the split axis) and the expert FFN's two an arm.
    assert text.count('custom_call_target="tpu_custom_call"') == (6 if split is None else 8)
    assert "mla_paged_decode_attention" in text and text.count("moe_grouped_matmul_int8") >= 2
    # The held experts stay where they are: no [16, 6144, 2048] array is produced outside the kernel.
    made = re.findall(r"= s8\[(?:1,)?16,(?:6144,2048|2048,6144)\]\S* (?!parameter|get-tuple-element|bitcast)(\w[\w-]*)\(", text)
    assert not made, made


@pytest.mark.parametrize("split", [None, (64, 1, 64)], ids=["decode", "mixed-chunk"])
def test_plain_held_share_step_joyai_largest_corners(sds, monkeypatch, split):
    """reason-saturated's largest steps at JoyAI-LLM-Flash's widths (its dense
    layer and two of its MoE layers, this chip's 32 of 256 experts, the whole
    vocabulary left out): 64 decode rows, and 64 decode slots + one 64-token
    chunk slot, over 16 pages. The dual scan: every layer's attention through
    the MLA kernel at 32 heads, the held experts through the grouped int8
    kernel at its 2048 x 768 tiles in each arm of the layer's ``cond`` by a
    layer index counted from the first MoE layer, no expert array copied
    outside it, and the five counters beside the outputs."""
    import functools
    import re

    from dynamo_tpu.models import llama
    from dynamo_tpu.parallel import moe

    monkeypatch.setattr(moe, "_kernel_platform", lambda: True)  # the described chip, not this CPU
    cfg = _benchmark_config("joyai-llm-flash-ep8-int8", layers=2)
    assert (cfg.num_layers, cfg.first_k_dense, cfg.num_experts, cfg.routed_experts) == (3, 1, 32, 256)
    like = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    params = _served_params(sds, cfg)
    k_cache, v_cache = like(jax.eval_shape(lambda: llama.init_kv_cache(cfg, 1025, 128)))
    assert k_cache.shape == (3, 1025, 128, 512) and v_cache.shape == (3, 1025, 128, 128)
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    if split is None:
        toks, slots = (64, 1), 64
    else:
        toks, slots = (split[0] + split[1] * split[2],), split[0] + split[1]
    compiled = jax.jit(functools.partial(llama.forward, cfg=cfg, attn_impl="pallas", split=split, moe_counts=True)).lower(
        params=params, tokens=i32(*toks), positions=i32(*toks), k_cache=k_cache, v_cache=v_cache,
        block_tables=i32(slots, 16), slot_mapping=i32(*toks), last_token_index=i32(slots),
    ).compile()
    text = compiled.as_text()
    assert "mla_paged_decode_attention" in text and text.count("moe_grouped_matmul_int8") >= 2
    assert "ragged-dot" not in text and "ragged_dot" not in text
    # The held experts stay where they are: no [32, 2048, 768] array is produced outside the kernel.
    made = re.findall(r"= s8\[(?:1,)?32,(?:2048,768|768,2048)\]\S* (?!parameter|get-tuple-element|bitcast)(\w[\w-]*)\(", text)
    assert not made, made
    assert [tuple(o.shape) for o in jax.tree.leaves(compiled.out_info)][-1] == (5,)  # HELD_COUNTS beside the outputs


# -- weights read where they lie (ISSUE 35, models/quant.held_flat) -----------

def _benchmark_config(name: str, layers: int, vocab: int = 8192):
    """A benchmark configuration at its published widths, cut to ``layers``
    (after its leading dense ones, where it has them) and a small vocabulary
    (the layer scan's body depends on neither)."""
    import pathlib

    from benchmark import serving

    conf = serving.load_config(pathlib.Path(__file__).parents[1] / f"benchmark/configs/{name}.json")
    hf = conf["hf"]
    layers += hf.get("first_k_dense_replace", 0)  # leading dense layers are a scan of their own
    hf.update({"num_layers" if "num_layers" in hf else "num_hidden_layers": layers, "vocab_size": vocab})
    hf.pop("num_hidden_layers_published", None)  # (a file that states a pipeline stage: the cut is this helper's)
    for per_layer in ("layer_types", "mlp_layer_types"):
        if per_layer in hf:
            hf[per_layer] = hf[per_layer][:layers]
    return serving.model_config(conf)


_STEP_TEXTS: dict = {}


def _two_layer_step_text(sds, monkeypatch, config: str, rows: int, mixed: bool, held: bool = True, laid: bool = True) -> str:
    """Two layers of a benchmark configuration at its published widths, the
    decode step or the chunk step as served (``rows`` decode slots + one
    64-token chunk slot), compiled for the described chip; a text is compiled
    once for the tests of this file."""
    key = (config, rows, mixed, held, laid)
    if key in _STEP_TEXTS:
        return _STEP_TEXTS[key]

    import functools

    from dynamo_tpu.models import llama, mla
    from dynamo_tpu.parallel import moe

    monkeypatch.setattr(moe, "_kernel_platform", lambda: True)  # the described chip, not this CPU
    if not held:
        monkeypatch.setattr(llama, "held_flat", lambda y: y)
        monkeypatch.setattr(mla, "held_flat", lambda y: y)
    cfg = _benchmark_config(config, layers=2)
    like = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    params = _served_params(sds, cfg, laid=laid)
    pages_per_seq = 16
    k_cache, v_cache = like(jax.eval_shape(lambda: llama.init_kv_cache(cfg, (rows + 1) * pages_per_seq + 1, 128)))
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    split = (rows, 1, 64) if mixed else None
    toks, slots = ((rows + 64,), rows + 1) if mixed else ((rows, 1), rows)
    counted = {"moe_counts": True} if cfg.moe_held_share else {}
    _STEP_TEXTS[key] = jax.jit(functools.partial(llama.forward, cfg=cfg, attn_impl="pallas", split=split, **counted)).lower(
        params=params, tokens=i32(*toks), positions=i32(*toks), k_cache=k_cache, v_cache=v_cache,
        block_tables=i32(slots, pages_per_seq), slot_mapping=i32(*toks), last_token_index=i32(slots),
    ).compile().as_text()
    return _STEP_TEXTS[key]


@pytest.mark.parametrize("config, rows, mixed, held", [
    ("mellum2-12b-a2.5b-int8", 8, False, True),
    ("mellum2-12b-a2.5b-int8", 8, True, True),
    ("mellum2-12b-a2.5b-int8", 8, False, False),  # the barrier taken out: the assertion has something to miss
    ("longcat-flash-chat-ep32-int8", 64, False, True),
    ("olmoe-1b-7b-int8", 64, False, True),  # the control: a flat q/k norm stands between, no barrier, no copy
    ("olmoe-1b-7b-int8", 64, True, True),
    ("joyai-llm-flash-ep8-int8", 64, False, True),  # a dense scan, then the plain body with a held share
    ("joyai-llm-flash-ep8-int8", 64, True, True),
], ids=lambda v: str(v))
def test_step_programs_relay_no_int8_weight(sds, monkeypatch, config, rows, mixed, held):
    """Two layers of each benchmark configuration at its published widths, the
    decode step and the chunk step as served (``rows`` decode slots + one
    64-token chunk slot), lowered for the described chip: the layer scan's body
    holds no copy, transposition or stand-alone slice of an ``s8`` array of
    1 MiB or more. The q (and k) projection reads its int8 weight from the
    stack by the layer's index, in the layout it is stored in. Without
    ``held_flat`` Mellum2's body slices ``wq`` and ``wk`` out of the stack and
    copies each transposed: 21 MB a layer."""
    from tests.test_step_relayouts import load_tool

    text = _two_layer_step_text(sds, monkeypatch, config, rows, mixed, held)
    assert text.count("moe_grouped_matmul_int8") >= 2  # the chip's program, not the CPU's widened one
    relaid = [(op["name"], op["shape"], op["reads"], op["writes"]) for op in load_tool().relayouts(text) if op["dtype"] == "s8"]
    if held:
        assert not relaid, relaid
    else:
        shapes = {shape for _, shape, _, _ in relaid}
        assert {"s8[1,2304,4096]", "s8[1,2304,512]"} <= shapes, relaid
        assert any(reads and "{2,1,0}" in reads[0] and writes == "{1,2,0}" for _, _, reads, writes in relaid), relaid



def _up_projection_moves(text: str, heads: int, r_kv: int = 512, d: int = 128) -> list:
    """What the layer scan's body slices out of a stack or copies that is shaped
    like a layer's ``w_uk`` / ``w_uv`` (bf16, ``r_kv x heads x d`` in any order):
    ``tools/step_relayouts.relayouts`` lists every copy, transposition or
    stand-alone slice of 1 MiB or more; a weight's carry the scan's own
    ``dynamic_slice`` as their ``op_name`` (an activation of as many values, a
    chunk step's ``[128, heads, 512]`` latent queries, carries its einsum's)."""
    import re

    from tests.test_step_relayouts import load_tool

    def dims(shape: str) -> list:
        return sorted(n for n in map(int, re.findall(r"\d+", shape.split("[", 1)[1])) if n != 1)

    return [(op["name"], op["shape"], op["op_name"]) for op in load_tool().relayouts(text)
            if op["dtype"] == "bf16" and dims(op["shape"]) == sorted((r_kv, heads, d)) and op["op_name"].endswith("dynamic_slice")]


@pytest.mark.parametrize("config, heads, mixed, laid", [
    ("joyai-llm-flash-ep8-int8", 32, False, True),
    ("joyai-llm-flash-ep8-int8", 32, True, True),
    ("joyai-llm-flash-ep8-int8", 32, False, False),  # as published: the assertion has something to miss
    ("longcat-flash-chat-ep32-int8", 64, False, True),
    ("longcat-flash-chat-ep32-int8", 64, True, True),
], ids=lambda v: str(v))
def test_step_programs_slice_no_up_projection_out_of_its_stack(sds, monkeypatch, config, heads, mixed, laid):
    """The two reason-saturated cells' largest decode and chunk steps (64 decode
    slots; + one 64-token chunk slot), two layers at the published widths,
    compiled for the described chip on the tree an unsharded runner serves: no
    stand-alone ``dynamic-slice`` of ``w_uk`` / ``w_uv`` into VMEM and no copy
    of either (heads-major, the stack's slice fuses into the contraction that
    uses it: ``models/mla.lay_heads_major``). On the published tree the decode
    step makes two such slices a layer of 4.19 MB (ISSUE 41: 18.5 us a layer,
    and the contractions that then read them 17.4). Ling's corners are held to
    the same in ``test_hybrid_step_ling_largest_corners``."""
    moves = _up_projection_moves(_two_layer_step_text(sds, monkeypatch, config, 64, mixed, laid=laid), heads)
    if laid:
        assert not moves, moves
    else:
        assert len(moves) == 2 and all(name.startswith("constant_dynamic-slice_fusion") for name, _, _ in moves), moves


@pytest.mark.parametrize("config, held_share, at_most", [
    # 176 here: 151 until ISSUE 41, whose laid tree frees the 8.4 MB of VMEM that w_uk / w_uv took, and a
    # two-layer stack's small leaves then ride 25 more async copies into it (40 layers: 126 -> 122 by
    # ``--count``); 124 in the body + 41 in its loop before ISSUE 39
    ("joyai-llm-flash-ep8-int8", True, 180),  # 130 since ISSUE 43 (eight passes for the sort and the gather)
    # 248 here since ISSUE 43 (twelve passes are eleven instructions more than the sort and the gather they
    # replace, and 12 us a layer less on the chip: a count is not a cost); 237 before it; 215 + 42 before ISSUE 39
    ("longcat-flash-chat-ep32-int8", True, 252),
    ("olmoe-1b-7b-int8", False, 0),  # the control: the dropless layer sorts its copies and scatters them back
], ids=lambda v: str(v))
def test_held_layer_routes_a_decode_step_without_sort_scatter_or_loop(sds, monkeypatch, config, held_share, at_most):
    """The 64-row decode step's layer body, two layers, compiled for the
    described chip (``tools/step_relayouts.body_counts``): a layer that holds a
    share of its experts sorts nothing (since ISSUE 43 the router of 256 or 768
    outputs takes a full step's experts by passes of ``max``: ``parallel/moe.
    router_select``; one sort, its ``top_k``, before), scatters nothing
    under a ``moe.`` scope, nests no loop in the layer scan, takes the usual
    pass and the all-rows pass as the two arms of one conditional, and
    executes at most ``at_most`` instructions a layer (the body and one arm;
    two layers compile to a longer body than the benchmark's 40 or 7:
    ``tools/step_relayouts.py --count`` reads 124 and 204 there, 153 and 223
    before).
    OLMoE's dropless layer is what the assertions would miss: two sorts (its
    router of 64 outputs keeps ``top_k``) and two scatters."""
    from tests.test_step_relayouts import load_tool

    counts = load_tool().body_counts(_two_layer_step_text(sds, monkeypatch, config, 64, False))
    if not held_share:
        assert len(counts["sorts"]) == 2 and len(counts["moe_scatters"]) == 2, counts
        return
    assert not counts["sorts"] and not counts["moe_scatters"], counts
    assert counts["nested"]["while"] == 0 and len(counts["arms"]) == 1 and len(counts["arms"][0]) == 2, counts
    assert counts["by_scope"].get("moe.experts/cond") and "moe.experts" not in counts["by_scope"], counts
    assert 0 < counts["executed"] <= at_most, counts


# -- a padding position routes to no expert in the whole-expert layer (ISSUE 50) ------------------

def test_the_padding_mask_adds_nothing_that_moves_data_to_mellum2s_decode_layer(sds, monkeypatch):
    """The 8-row decode step of Mellum2 (six rows decode in it in the
    longctx-decode cell), two layers at the published widths, compiled for the
    described chip: ``moe_mlp_dropless`` takes ``slot_mapping != 0`` there, and
    the layer scan's body holds the copies, transpositions, sorts and gathers
    it held on the parent (commit a9667e6: the counts pinned here) and no
    instruction more than the parent's 100: the mask is made once before the
    scan and its two selects ride fusions that were there (the keys' flatten,
    the residual's add)."""
    from tests.test_step_relayouts import load_tool

    counts = load_tool().body_counts(_two_layer_step_text(sds, monkeypatch, "mellum2-12b-a2.5b-int8", 8, False))
    assert all(counts["moving"][op] <= n for op, n in {"copy": 10, "transpose": 18, "sort": 2, "gather": 5}.items()), counts
    assert len(counts["sorts"]) == 2 and len(counts["moe_scatters"]) == 2, counts
    assert 0 < counts["executed"] <= 100 and not counts["arms"] and not counts["nested"]["while"], counts


# -- a hybrid stack: KDA layers in slots beside the paged latent cache (ISSUE 40) ----------------

@pytest.mark.parametrize("budget_mib, block", [(None, 32), (4, 16), (2, 8)], ids=["served", "4MiB", "2MiB"])
def test_kda_decode_kernel_compiles(sds, monkeypatch, budget_mib, block):
    """The decode step of the KDA recurrence at Ling-3.0-flash's widths: 64
    rows x 32 heads of 128 x 128 over 15 layers x 65 slots, the state aliased
    to the kernel's output (updated where it lies: no second 2 GB buffer). At
    the budget the tree ships the block is all 32 heads (2 MiB in, 2 MiB out,
    double-buffered: the whole budget) and the grid runs over rows only; a
    smaller budget takes a smaller block. Mosaic holds the kernel to its
    ``vmem_limit_bytes``, twice the budget. The wrapper lays out nothing: no
    ``[rows, blocks, key, 4 x block]`` column array reaches the kernel."""
    from dynamo_tpu.ops import pallas_kda

    if budget_mib:
        monkeypatch.setattr(pallas_kda, "STATE_VMEM", budget_mib << 20)
    assert pallas_kda.heads_block(32, 4 * 128 * 128) == block and 4 * block * 4 * 128 * 128 <= pallas_kda.STATE_VMEM
    f32 = lambda *shape: sds(shape, jnp.float32)  # noqa: E731
    compiled = jax.jit(lambda *a: pallas_kda.kda_decode_step.__wrapped__(*a), donate_argnums=(0,)).lower(
        f32(15 * 65, 32, 128, 128), sds((64,), jnp.int32), sds((64,), jnp.bool_),
        f32(64, 32, 128), f32(64, 32, 128), f32(64, 32, 128), f32(64, 32, 128), f32(64, 32)).compile()
    text = compiled.as_text()
    assert "kda_decode_step" in text and "f32[64,1,128,128]" not in text and "f32[64,4,128,32]" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 15 * 65 * 32 * 128 * 128 * 4 and mem.temp_size_in_bytes < 1 << 20


def test_kda_decode_kernel_compiles_at_sixty_four_heads(sds):
    """The same kernel at Solar-Open2-250B's widths (ISSUE 53): 64 rows x 64
    heads of 128 x 128 over 6 layers x 65 slots, a slot-layer of 4.19 MB. The
    budget holds 32 heads a block, so the grid is 64 rows x 2 blocks; the 4,096
    write strengths ride SMEM beside the slot ids; the 1.64 GB state is
    aliased to the kernel's output."""
    from dynamo_tpu.ops import pallas_kda

    assert pallas_kda.heads_block(64, 4 * 128 * 128) == 32
    f32 = lambda *shape: sds(shape, jnp.float32)  # noqa: E731
    compiled = jax.jit(lambda *a: pallas_kda.kda_decode_step.__wrapped__(*a), donate_argnums=(0,)).lower(
        f32(6 * 65, 64, 128, 128), sds((64,), jnp.int32), sds((64,), jnp.bool_),
        f32(64, 64, 128), f32(64, 64, 128), f32(64, 64, 128), f32(64, 64, 128), f32(64, 64)).compile()
    text = compiled.as_text()
    assert "kda_decode_step" in text and "f32[64,1,128,128]" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 6 * 65 * 64 * 128 * 128 * 4 and mem.temp_size_in_bytes < 1 << 20


def _conv_buffer_stays(text: str, conv, chunk_row_in_xla: bool = False) -> None:
    """The step program takes the conv buffer, hands it to ``slot_conv_step``
    where it lies (pinned to HBM) and hands it back: no instruction makes a
    buffer of its shape (a ``copy`` to another layout or memory, a scatter, a
    ``dynamic-update-slice``), no fusion slices or gathers from it, and (the
    callers count them) no loop is left but the layer loops (the parent's gather and scatter by slot id
    were a ``while`` over the 64 rows each; PERF.md, PR 48). ``chunk_row_in_xla``: a
    chunk too wide for the kernel (``pallas_conv.supported``) reads its one
    row's slot by a slice and writes it back where it lies
    (``dynamic-update-slice`` in the buffer's own layout): still no copy."""
    import re

    shape = re.escape(f"bf16[{','.join(map(str, conv.shape))}]")
    made = set(re.findall(rf"= {shape}\S* ([\w-]+)\(", text))
    assert made <= {"parameter", "get-tuple-element"} | ({"dynamic-update-slice", "fusion"} if chunk_row_in_xla else set()), made
    if not chunk_row_in_xla:
        assert not re.search(rf"^%fused_computation\S* \(.*{shape}", text, re.M)  # no fusion takes it: nothing gathers or slices from it
    assert not re.search(rf"{shape}\S*, u32\[\]\S*\) copy-start\(", text)  # nor moves it to another memory
    assert "slot_conv_step" in text and '"output_memory_colors":["0","-1"]' in text


@pytest.mark.parametrize("slots, c, bias, rows, tokens", [
    (15 * 65, 96, False, 64, 1), (9 * 65, 40, True, 64, 1), (15 * 65, 96, False, 1, 64), (9 * 65, 40, True, 2, 64),
    (9 * 65, 72, True, 64, 1), (9 * 65, 72, True, 1, 64), (6 * 65, 192, False, 64, 1), (6 * 65, 192, False, 1, 32),
], ids=["ling-decode", "falcon-h1-decode", "ling-chunk", "falcon-h1-chunks", "granite-decode", "granite-chunk",
        "solar-open2-decode", "solar-open2-half-chunk"])
def test_slot_conv_kernel_compiles(sds, slots, c, bias, rows, tokens):
    """The conv rows' step at both cells' widths (Ling-3.0-flash's 12,288
    channels in 96 rows of lanes, no bias; Falcon-H1-34B's 5,120 in 40, whose
    last bfloat16 tile is half full, with one; granite-4.0-h-small's 8,448 in
    66, held in 72: a 66-row buffer the device would lay out with the slots on
    the sublanes; Solar-Open2-250B's 24,576 in 192, whose 64-token chunk is 25
    MiB of blocks against ``ROWS_VMEM``'s 16 and keeps the XLA path: 32 tokens
    are the most the kernel takes at that width): 64 one-token rows and a
    64-token chunk, the buffer aliased to the kernel's output, pinned to HBM
    and taken in the layout it is allocated in (row-major, ``(8, 128)(2, 1)``
    tiles over the last two axes): nothing but the kernel in the program."""
    from dynamo_tpu.ops import pallas_conv

    assert pallas_conv.supported(tokens, c, 128) and not pallas_conv.supported(128, 96, 128) and not pallas_conv.supported(1, 66, 128)
    assert not pallas_conv.supported(64, 192, 128)
    args = [sds((slots, 3, c, 128), jnp.bfloat16), sds((rows,), jnp.int32), sds((rows,), jnp.bool_), sds((rows,), jnp.int32),
            sds((rows, tokens, c, 128), jnp.float32), sds((4, c, 128), jnp.float32)] + ([sds((c, 128), jnp.float32)] if bias else [])
    compiled = jax.jit(lambda *a: pallas_conv.slot_conv_step.__wrapped__(*a), donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    assert "slot_conv_step" in text and f"bf16[{slots},3,{c},128]{{3,2,1,0:T(8,128)(2,1)}} parameter(0)" in text
    assert '"output_memory_colors":["0","-1"]' in text and " fusion(" not in text and "= bf16" not in text.replace(
        f"= bf16[{slots},3,{c},128]{{3,2,1,0:T(8,128)(2,1)}} parameter(0)", "").replace(
        f"= bf16[{slots},3,{c},128]{{3,2,1,0:T(8,128)(2,1)}} get-tuple-element(", "")  # nothing else makes a bfloat16 array
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= slots * 3 * c * 128 * 2 and mem.temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("split", [None, (64, 1, 64)], ids=["decode", "mixed-chunk"])
def test_hybrid_step_ling_largest_corners(sds, monkeypatch, split):
    """reason-saturated's largest steps at Ling-3.0-flash's widths, one period
    (its two dense FFNs, three routed KDA layers and the MLA layer, this
    chip's 64 of 512 experts in 4 of 8 groups, a small vocabulary): 64 decode
    rows, and 64 decode slots + one 64-token chunk slot, over 16 pages. The KDA
    layers' decode rows through ``kda_decode_step``, the MLA layer through the
    MLA kernel, the held experts through the grouped int8 kernel by a layer
    index counted from the first routed layer; the state buffers come back as
    the last two outputs and are updated where they lie; no int8 weight is
    re-laid inside the loops."""
    import functools

    from dynamo_tpu.models import kda, llama
    from dynamo_tpu.parallel import moe
    from tests.test_step_relayouts import load_tool

    monkeypatch.setattr(moe, "_kernel_platform", lambda: True)  # the described chip, not this CPU
    cfg = _benchmark_config("ling-3.0-flash-ep8-int8", layers=4)  # + 2 dense = one period of 6
    assert (cfg.num_layers, cfg.recurrent_layers, cfg.cache_layers, cfg.first_k_dense) == (6, 5, 1, 2)
    assert (cfg.num_experts, cfg.routed_experts, cfg.moe_n_group, cfg.moe_topk_group) == (64, 512, 8, 4)
    like = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    params = _served_params(sds, cfg)
    k_cache, v_cache = like(jax.eval_shape(lambda: llama.init_kv_cache(cfg, 1025, 128)))
    state, conv = like(jax.eval_shape(lambda: kda.init_state(cfg, 65)))
    assert state.shape == (5 * 65, 32, 128, 128) and conv.shape == (5 * 65, 3, 96, 128)
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    if split is None:
        toks, slots = (64, 1), 64
    else:
        toks, slots = (split[0] + split[1] * split[2],), split[0] + split[1]

    def step(params, tokens, positions, k_cache, v_cache, block_tables, slot_mapping, last_token_index, state, conv, slot_ids):
        return llama.forward(params, cfg, tokens, positions, k_cache, v_cache, block_tables, slot_mapping, last_token_index,
                             attn_impl="pallas", split=split, moe_counts=True, recurrent=(state, conv, slot_ids))

    compiled = jax.jit(step, donate_argnums=(3, 4, 8, 9)).lower(
        params, i32(*toks), i32(*toks), k_cache, v_cache, i32(slots, 16), i32(*toks), i32(slots), state, conv, i32(slots),
    ).compile()
    text = compiled.as_text()
    assert "kda_decode_step" in text and "mla_paged_decode_attention" in text and text.count("moe_grouped_matmul_int8") >= 2
    # The kernel takes q, k, g as the projections leave them: no stack of four, no column array laid out for it.
    assert "f32[64,4,4,8,128]" not in text and "f32[64,4,128,32]" not in text and "f32[64,1,128,128]" not in text
    assert "ragged-dot" not in text and "ragged_dot" not in text
    _conv_buffer_stays(text, conv)
    assert text.count(" while(") == 2  # the dense layers and the period's KDA layers (one period is no loop): none over rows
    shapes = [tuple(o.shape) for o in jax.tree.leaves(compiled.out_info)]
    assert shapes[-3:] == [(5,), state.shape, conv.shape]  # HELD_COUNTS, then the state buffers
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state.size * 4 and mem.temp_size_in_bytes < 1 << 30  # no second copy of the state
    relaid = [(op["name"], op["shape"]) for op in load_tool().relayouts(text) if op["dtype"] == "s8"]
    assert not relaid, relaid
    assert not _up_projection_moves(text, heads=32)  # the MLA layer's w_uk / w_uv are read where they lie


# -- a Mamba-2 mixer beside GQA attention in every layer: pages and a slot a layer (ISSUE 46) ------------

@pytest.mark.parametrize("heads, groups, n, p, budget_mib, block", [
    (32, 2, 256, 128, None, 16), (32, 2, 256, 128, 16, 32), (32, 2, 256, 128, 4, 8),
    (128, 1, 128, 64, None, 32), (128, 1, 128, 64, 4, 16), (128, 2, 128, 64, None, 32),
], ids=["served", "16MiB", "4MiB", "narrow-served", "narrow-4MiB", "narrow-two-groups"])
def test_mamba_decode_kernel_compiles(sds, monkeypatch, heads, groups, n, p, budget_mib, block):
    """The decode step of the Mamba-2 recurrence at Falcon-H1-34B's widths: 64
    rows x 32 heads of 256 x 128 in 2 groups over 9 layers x 65 slots, the
    state aliased to the kernel's output (updated where it lies: no second
    2.45 GB buffer). At the budget the tree ships a block is a group's 16
    heads (2 MiB in, 2 MiB out, double-buffered: the whole budget); twice the
    budget takes both groups in one block and the grid over rows only, half
    takes half a group. The wrapper lays out nothing: no lane-wide decay or
    ``dt x`` and no ``[rows, groups, N, 2]`` columns reach the kernel, only
    the ``[rows x heads]`` scalars. And at granite-4.0-h-small's: 128 heads
    of 128 x 64 in one group (and in two), two heads side by side in a buffer
    row, ``[slots, 64, 128, 128]`` at 4,194,304 B a slot a layer with no lane
    padding; a block is 32 rows (the same 2 MiB), the decay and ``dt`` still
    the ``[rows x heads]`` scalars (8,192 of them)."""
    from dynamo_tpu.ops import pallas_kda, pallas_mamba

    if budget_mib:
        monkeypatch.setattr(pallas_kda, "STATE_VMEM", budget_mib << 20)
    side = 128 // p if p < 128 else 1
    rows_of, lanes = heads // side, side * p  # the buffer's rows: a head, or two side by side
    assert pallas_kda.heads_block(rows_of, 4 * n * lanes, rows_of // groups) == block and 4 * block * 4 * n * lanes <= pallas_kda.STATE_VMEM
    f32 = lambda *shape: sds(shape, jnp.float32)  # noqa: E731
    compiled = jax.jit(lambda *a: pallas_mamba.mamba_decode_step.__wrapped__(*a), donate_argnums=(0,)).lower(
        f32(9 * 65, rows_of, n, lanes), sds((64,), jnp.int32), sds((64,), jnp.bool_),
        f32(64, heads, p), f32(64, groups, n), f32(64, groups, n), f32(64, heads), f32(heads)).compile()
    text = compiled.as_text()
    assert "mamba_decode_step" in text and f"f32[64,{groups},{n},2]" not in text and f"f32[{64 * heads}]" in text
    assert f"f32[{9 * 65},{rows_of},{n},{lanes}]{{3,2,1,0:T(8,128)}}" in text  # row-major, whole tiles: nothing padded
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 9 * 65 * heads * n * p * 4 and mem.temp_size_in_bytes < 1 << 20
    assert mem.argument_size_in_bytes < 9 * 65 * heads * n * p * 4 + (8 << 20)  # the buffer at its bytes, not twice them


@pytest.mark.parametrize("split", [None, (64, 1, 64)], ids=["decode", "mixed-chunk"])
def test_parallel_mixer_step_falcon_h1_largest_corners(sds, split):
    """reason-saturated's largest steps at Falcon-H1-34B's widths, two layers
    and a small vocabulary: 64 decode rows, and 64 decode slots + one 64-token
    chunk slot, over 16 pages. The mixer is a term of the plain layer body:
    its decode rows go through ``mamba_decode_step``, the layer's attention
    (20 query heads over 4 KV heads) through the paged GQA kernels on the
    layer's own slab; the state buffers come back as the last two outputs and
    are updated where they lie (aliased, no copy of the state buffer in the
    step program); no int8 weight is re-laid inside the scan."""
    from dynamo_tpu.models import kda, llama
    from tests.test_step_relayouts import load_tool

    cfg = _benchmark_config("falcon-h1-34b-pp8-int8", layers=2)
    assert (cfg.num_layers, cfg.recurrent_layers, cfg.cache_layers, cfg.ssm_heads, cfg.num_heads, cfg.num_kv_heads) == (2, 2, 2, 32, 20, 4)
    like = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    params = _served_params(sds, cfg)
    assert params["layers"]["w_ssm_in"].dtype == jnp.bfloat16 and params["layers"]["wq"]["qw"].dtype == jnp.int8
    k_cache, v_cache = like(jax.eval_shape(lambda: llama.init_kv_cache(cfg, 1025, 128)))
    state, conv = like(jax.eval_shape(lambda: kda.init_state(cfg, 65)))
    assert state.shape == (2 * 65, 32, 256, 128) and conv.shape == (2 * 65, 3, 40, 128) and conv.dtype == jnp.bfloat16
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    if split is None:
        toks, slots = (64, 1), 64
    else:
        toks, slots = (split[0] + split[1] * split[2],), split[0] + split[1]

    def step(params, tokens, positions, k_cache, v_cache, block_tables, slot_mapping, last_token_index, state, conv, slot_ids):
        return llama.forward(params, cfg, tokens, positions, k_cache, v_cache, block_tables, slot_mapping, last_token_index,
                             attn_impl="pallas", split=split, recurrent=(state, conv, slot_ids))

    compiled = jax.jit(step, donate_argnums=(3, 4, 8, 9)).lower(
        params, i32(*toks), i32(*toks), k_cache, v_cache, i32(slots, 16), i32(*toks), i32(slots), state, conv, i32(slots),
    ).compile()
    text = compiled.as_text()
    assert "mamba_decode_step" in text and "kda_decode_step" not in text
    assert "f32[64,2,256,2]" not in text  # B and C reach the kernel as the conv leaves them, not as padded columns
    assert ("paged_prefill_attention" if split else "paged_decode_attention") in text
    _conv_buffer_stays(text, conv)
    assert text.count(" while(") == 1  # the layer scan: no loop over rows
    shapes = [tuple(o.shape) for o in jax.tree.leaves(compiled.out_info)]
    assert shapes[-2:] == [state.shape, conv.shape] and len(shapes) == 5  # logits, the caches, the state buffers
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state.size * 4 + 2 * k_cache.size * 2 and mem.temp_size_in_bytes < 1 << 30
    ops = load_tool().relayouts(text)
    assert not [(op["name"], op["shape"]) for op in ops if op["dtype"] == "s8"]
    assert not [op for op in ops if op["bytes"] >= state.size * 4 // 65]  # nothing the size of a layer's rows of state moves


@pytest.mark.parametrize("config, rows", [("olmoe-1b-7b-int8", 48), ("joyai-llm-flash-ep8-int8", 64)])
def test_a_model_without_a_mixer_compiles_nothing_of_it(sds, monkeypatch, config, rows):
    """The mixer sits in the plain layer body under a static predicate of the
    configuration: the decode step of a model without one holds no operation
    of it, no multiplier and no state buffer (against the parent commit the
    two texts are equal but for the source lines the kernels embed: PERF.md,
    PR 46, by ``tools/step_relayouts.py --dump`` on both trees)."""
    text = _two_layer_step_text(sds, monkeypatch, config, rows, mixed=False)
    assert "attn.ssm" not in text and "mamba_decode_step" not in text and "softplus" not in text
    cfg = _benchmark_config(config, layers=2)
    assert not cfg.ssm_heads and not cfg.recurrent_layers
    assert {cfg.embed_multiplier, cfg.lm_head_multiplier, cfg.attn_in_multiplier, cfg.attn_out_multiplier, cfg.key_multiplier,
            cfg.mlp_gate_multiplier, cfg.mlp_down_multiplier} == {1.0}


# -- Mamba-2 layers that stand alone, one GQA layer inside the period, experts in every layer, a tied head (ISSUE 49) ------


@pytest.mark.parametrize("split", [None, (64, 1, 64)], ids=["decode", "mixed-chunk"])
def test_period_step_granite_largest_corners(sds, monkeypatch, split):
    """reason-saturated's largest steps of granite-4.0-h-small's stage as it is
    served: the whole period of ten layers (Mamba x5, attention, Mamba x4), 72
    experts a layer, the whole 100,352-id vocabulary under the tied head; 64
    decode rows, and 64 decode slots + one 64-token chunk slot, over 16 pages.
    The period scan is two loops over the Mamba layers round the one layer
    that attends: ``mamba_decode_step`` in each loop's body (all nine Mamba
    layers), the attention layer through the paged GQA kernels on the one
    slab, the experts through the grouped int8 kernel in all three bodies.
    The state buffer is ``65 x 9 x 4,194,304`` B (two heads of 64 side by side:
    no lane padding), handed in, stepped where it lies and handed back
    (aliased: no copy, no gather and no scatter of it; in the chunk step the
    one chunk row's state alone moves); the conv buffer stays where it lies;
    **no transposed copy of the embedding**: the head contracts the 822 MB
    array as the gather reads it; no int8 weight is re-laid inside the loops."""
    import re

    from dynamo_tpu.models import kda, llama
    from dynamo_tpu.parallel import moe
    from tests.test_step_relayouts import load_tool

    monkeypatch.setattr(moe, "_kernel_platform", lambda: True)  # the described chip, not this CPU
    cfg = _benchmark_config("granite-4.0-h-small-pp4-int8", layers=10, vocab=100352)
    assert (cfg.num_layers, cfg.recurrent_layers, cfg.cache_layers, cfg.layer_group_size, cfg.period_attn_index) == (10, 9, 1, 10, 5)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_heads_per_row, cfg.num_experts, cfg.tie_embeddings) == (128, 64, 2, 72, True)
    like = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    params = _served_params(sds, cfg)
    assert params["ssm_layers"]["w_ssm_in"].dtype == jnp.bfloat16 and params["attn_layers"]["wq"]["qw"].dtype == jnp.int8
    assert params["embed"].shape == (100352, 4096) and "lm_head" not in params
    k_cache, v_cache = like(jax.eval_shape(lambda: llama.init_kv_cache(cfg, 1025, 128)))
    state, conv = like(jax.eval_shape(lambda: kda.init_state(cfg, 65)))
    assert state.shape == (9 * 65, 64, 128, 128) and state.size * 4 == 65 * 9 * 4_194_304
    assert conv.shape == (9 * 65, 3, 72, 128) and conv.dtype == jnp.bfloat16 and k_cache.shape == (1, 1025, 128, 1024)
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    if split is None:
        toks, slots = (64, 1), 64
    else:
        toks, slots = (split[0] + split[1] * split[2],), split[0] + split[1]

    def step(params, tokens, positions, k_cache, v_cache, block_tables, slot_mapping, last_token_index, state, conv, slot_ids):
        return llama.forward(params, cfg, tokens, positions, k_cache, v_cache, block_tables, slot_mapping, last_token_index,
                             attn_impl="pallas", split=split, recurrent=(state, conv, slot_ids))

    compiled = jax.jit(step, donate_argnums=(3, 4, 8, 9)).lower(
        params, i32(*toks), i32(*toks), k_cache, v_cache, i32(slots, 16), i32(*toks), i32(slots), state, conv, i32(slots),
    ).compile()
    text = compiled.as_text()
    assert text.count(" while(") == 2  # the Mamba layers before and after the one that attends (one period is no loop): none over rows
    bodies = [body for body in re.split(r"\n(?=%?[\w.\-]+ \(.*\) -> .* \{\n)", text) if " custom-call(" in body and "mamba_decode_step" in body]
    assert len(bodies) == 2 and all(body.count('custom_call_target="tpu_custom_call"') >= 1 for body in bodies)  # both loops' bodies
    assert "kda_decode_step" not in text and ("paged_prefill_attention" if split else "paged_decode_attention") in text
    assert text.count("moe_grouped_matmul_int8") >= 3 and "ragged-dot" not in text and "ragged_dot" not in text
    _conv_buffer_stays(text, conv)
    # The state buffer: a parameter, carried through the loops and the kernel, handed back: nothing else makes one.
    shape = re.escape("f32[585,64,128,128]")
    made = set(re.findall(rf"= (?:\()?{shape}\S*(?:, [^)]*\))? ([\w-]+)\(", text))
    assert made <= {"parameter", "get-tuple-element", "custom-call", "while", "tuple"} | ({"dynamic-update-slice", "fusion"} if split else set()), made
    assert f"f32[585,64,128,128]{{3,2,1,0:T(8,128)}} parameter(" in text
    shapes = [tuple(o.shape) for o in jax.tree.leaves(compiled.out_info)]
    assert shapes[-2:] == [state.shape, conv.shape] and len(shapes) == 5  # logits, the caches, the state buffers
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state.size * 4 + 2 * k_cache.size * 2 and mem.temp_size_in_bytes < 1 << 30
    # The embedding: one array, read by the gather and contracted by the head where it lies.
    assert load_tool().embedding_copies(text, 100352, 4096) == [] and "bf16[100352,4096]" in text
    wrong = text.replace("bf16[100352,4096]{1,0:T(8,128)(2,1)} bitcast(", "bf16[4096,100352]{1,0:T(8,128)(2,1)} transpose(", 1)
    # (the tool sees one where there is one: the transposition, and the fusion round it that is a bitcast no more)
    assert [c["opcode"] for c in load_tool().embedding_copies(wrong, 100352, 4096)] == ["transpose", "fusion"]
    ops = load_tool().relayouts(text)
    assert not [(op["name"], op["shape"]) for op in ops if op["dtype"] == "s8"]
    if split is None:
        assert not [op for op in ops if op["bytes"] >= state.size * 4 // 65 // 9]  # nothing the size of a row's state moves


# -- KDA layers (64 heads, low-rank gates) round a gated GQA layer that opens the period, a held share of 40 (ISSUE 53) ------


@pytest.mark.parametrize("split", [None, (64, 1, 64)], ids=["decode", "mixed-chunk"])
def test_period_step_solar_open2_largest_corners(sds, monkeypatch, split):
    """reason-saturated's largest steps at Solar-Open2-250B's widths, one period
    ([GQA, KDA, KDA, KDA], this chip's 40 of 320 experts, a small vocabulary):
    64 decode rows, and 64 decode slots + one 64-token chunk slot, over 16
    pages. The layer that attends opens the period, so the period scan is the
    GQA layer (the paged GQA kernels at 64 / 8 heads, its gate's 67 MB bf16
    weight read where it lies) and *one* loop over the KDA layers behind it:
    ``kda_decode_step`` at 64 heads and ``slot_conv_step`` at 192 rows of lanes
    in its body, the held experts through the grouped int8 kernel in both
    bodies by a layer index, the 320-output router without a sort. The state
    buffers come back as the last two outputs, updated where they lie; no int8
    weight is re-laid inside the loops and nothing the size of a gate or of a
    low-rank pair is copied."""
    from dynamo_tpu.models import kda, llama
    from dynamo_tpu.parallel import moe
    from tests.test_step_relayouts import load_tool

    monkeypatch.setattr(moe, "_kernel_platform", lambda: True)  # the described chip, not this CPU
    cfg = _benchmark_config("solar-open2-250b-ep8-int8", layers=4)
    assert (cfg.num_layers, cfg.recurrent_layers, cfg.cache_layers, cfg.layer_group_size, cfg.period_attn_index) == (4, 3, 1, 4, 0)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.num_experts, cfg.routed_experts, cfg.kda_low_rank, cfg.attn_out_gate) == (
        64, 8, 40, 320, 128, True)
    assert moe.router_select(64, 320, 8) == "passes" and moe.router_select(1, 320, 8) == "sort" and moe.held_rows_cap(512, 40, 320) == 128
    like = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    params = _served_params(sds, cfg)
    assert params["attn_layers"]["w_out_gate"].dtype == jnp.bfloat16 and params["attn_layers"]["wq"]["qw"].dtype == jnp.int8
    assert params["kda_layers"]["w_decay_b"].shape == (3, 128, 8192) and params["kda_layers"]["wq"]["qw"].dtype == jnp.int8
    k_cache, v_cache = like(jax.eval_shape(lambda: llama.init_kv_cache(cfg, 1025, 128)))
    state, conv = like(jax.eval_shape(lambda: kda.init_state(cfg, 65)))
    assert state.shape == (3 * 65, 64, 128, 128) and conv.shape == (3 * 65, 3, 192, 128) and k_cache.shape == (1, 1025, 128, 1024)
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    if split is None:
        toks, slots = (64, 1), 64
    else:
        toks, slots = (split[0] + split[1] * split[2],), split[0] + split[1]

    def step(params, tokens, positions, k_cache, v_cache, block_tables, slot_mapping, last_token_index, state, conv, slot_ids):
        return llama.forward(params, cfg, tokens, positions, k_cache, v_cache, block_tables, slot_mapping, last_token_index,
                             attn_impl="pallas", split=split, moe_counts=True, recurrent=(state, conv, slot_ids))

    compiled = jax.jit(step, donate_argnums=(3, 4, 8, 9)).lower(
        params, i32(*toks), i32(*toks), k_cache, v_cache, i32(slots, 16), i32(*toks), i32(slots), state, conv, i32(slots),
    ).compile()
    text = compiled.as_text()
    assert "kda_decode_step" in text and ("paged_prefill_attention" if split else "paged_decode_attention") in text
    assert "mla_paged_decode_attention" not in text and "mamba_decode_step" not in text
    assert text.count("moe_grouped_matmul_int8") >= 2 and "ragged-dot" not in text and "ragged_dot" not in text
    # (the decode rows' conv through the kernel in both steps; the chunk slot's 64 tokens x 24,576 channels are
    # over the kernel's VMEM budget and take the XLA path, one slot sliced out and written back in place)
    _conv_buffer_stays(text, conv, chunk_row_in_xla=split is not None)
    assert text.count(" while(") == 1  # the period's KDA layers behind the one that attends (one period is no loop): none over rows
    assert " sort(" not in text  # a full step's router takes its 8 of 320 by passes of max
    shapes = [tuple(o.shape) for o in jax.tree.leaves(compiled.out_info)]
    assert shapes[-3:] == [(5,), state.shape, conv.shape]  # HELD_COUNTS, then the state buffers
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state.size * 4 and mem.temp_size_in_bytes < 1 << 30  # no second copy of the state
    ops = load_tool().relayouts(text)
    assert not [(op["name"], op["shape"]) for op in ops if op["dtype"] == "s8"]
    assert not [(op["name"], op["shape"]) for op in ops if op["dtype"] == "bf16" and op["bytes"] >= 128 * 8192 * 2]  # no gate, no pair
