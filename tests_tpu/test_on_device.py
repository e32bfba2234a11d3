"""Mosaic-compiled kernel parity on the real chip.

The interpret-mode tests in ``tests/test_pallas_*.py`` pin the math; this
tier pins the *lowering*: scoped-VMEM fit, DMA semantics, the per-KV-head
tuple carry, lane-strip slicing at head_dim 64 and 128 — everything that
only exists once Mosaic compiles the kernel for hardware.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.attention import paged_attention_reference
from dynamo_tpu.ops.pallas_paged import paged_decode_attention
from dynamo_tpu.ops.pallas_prefill import paged_prefill_attention


def _case(rng, *, b, t, n_heads, n_kv, head_dim, page_size, pages_per_seq, starts):
    width = n_kv * head_dim
    num_pages = b * pages_per_seq + 1
    k = jnp.asarray(rng.standard_normal((num_pages, page_size, width)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((num_pages, page_size, width)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((b, t, n_heads, head_dim)), jnp.bfloat16)
    tables = jnp.asarray(
        1 + rng.permutation(num_pages - 1)[: b * pages_per_seq].reshape(b, pages_per_seq),
        jnp.int32,
    )
    positions = jnp.asarray(np.asarray(starts)[:, None] + np.arange(t)[None, :], jnp.int32)
    return q, k, v, tables, positions


@pytest.mark.parametrize(
    "n_heads,n_kv,head_dim",
    [(32, 8, 64), (32, 8, 128), (16, 16, 128)],  # 1B GQA, 8B GQA, MHA
)
def test_prefill_kernel_on_device(n_heads, n_kv, head_dim):
    rng = np.random.default_rng(0)
    q, k, v, tables, positions = _case(
        rng, b=2, t=256, n_heads=n_heads, n_kv=n_kv, head_dim=head_dim,
        page_size=128, pages_per_seq=6, starts=[256, 128],
    )
    scale = head_dim**-0.5
    want = paged_attention_reference(q, k, v, tables, positions, scale=scale)
    got = paged_prefill_attention(q, k, v, tables, positions, scale=scale)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=5e-2, rtol=5e-2
    )


@pytest.mark.parametrize("head_dim", [64, 128])
def test_decode_kernel_on_device(head_dim):
    rng = np.random.default_rng(1)
    q, k, v, tables, positions = _case(
        rng, b=8, t=1, n_heads=32, n_kv=8, head_dim=head_dim,
        page_size=128, pages_per_seq=8, starts=[int(x) for x in rng.integers(0, 1000, 8)],
    )
    scale = head_dim**-0.5
    want = paged_attention_reference(q, k, v, tables, positions, scale=scale)
    got = paged_decode_attention(q, k, v, tables, positions, scale=scale)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=5e-2, rtol=5e-2
    )


def test_prefill_faster_than_reference_long_context():
    """The kernel must beat the gather formulation at ISL >= 1024."""
    import time

    rng = np.random.default_rng(2)
    q, k, v, tables, positions = _case(
        rng, b=4, t=2048, n_heads=32, n_kv=8, head_dim=128,
        page_size=128, pages_per_seq=17, starts=[0, 0, 0, 0],
    )
    scale = 128**-0.5
    ref = jax.jit(lambda *a: paged_attention_reference(*a, scale=scale))
    ker = jax.jit(lambda *a: paged_prefill_attention(*a, scale=scale))

    def bench(f):
        f(q, k, v, tables, positions).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(5):
            r = f(q, k, v, tables, positions)
        r.block_until_ready()
        return (time.perf_counter() - t0) / 5

    t_ref, t_ker = bench(ref), bench(ker)
    print(f"prefill kernel {t_ker*1e3:.2f} ms, reference {t_ref*1e3:.2f} ms, "
          f"reference/kernel = {t_ref / t_ker:.2f}x")
    assert t_ker < t_ref, f"kernel {t_ker*1e3:.1f} ms !< reference {t_ref*1e3:.1f} ms"


def test_mla_decode_kernel_on_device():
    """MLA decode kernel at DeepSeek-V3 geometry (r_kv 512, rope 64 padded
    to a 128-lane tile), Mosaic-compiled, vs the gather formulation."""
    from dynamo_tpu.ops.pallas_mla import mla_paged_decode

    rng = np.random.default_rng(7)
    b, page_size, pages_per_seq = 8, 128, 5
    r_kv, r_width, dr = 512, 128, 64
    n_heads = 32
    num_pages = 1 + b * pages_per_seq
    c_cache = jnp.asarray(rng.standard_normal((num_pages, page_size, r_kv)) * 0.3, jnp.bfloat16)
    r_host = np.zeros((num_pages, page_size, r_width), np.float32)
    r_host[..., :dr] = rng.standard_normal((num_pages, page_size, dr)) * 0.3
    r_cache = jnp.asarray(r_host, jnp.bfloat16)
    tables = jnp.asarray(
        1 + rng.permutation(num_pages - 1).reshape(b, pages_per_seq), jnp.int32
    )
    lengths = rng.integers(100, page_size * pages_per_seq, size=b)
    positions = jnp.asarray(lengths[:, None] - 1, jnp.int32)
    q_lat = jnp.asarray(rng.standard_normal((b, n_heads, r_kv)) * 0.2, jnp.bfloat16)
    q_rope_host = np.zeros((b, n_heads, r_width), np.float32)
    q_rope_host[..., :dr] = rng.standard_normal((b, n_heads, dr)) * 0.2
    q_rope = jnp.asarray(q_rope_host, jnp.bfloat16)
    scale = (128 + 64) ** -0.5

    got = np.asarray(mla_paged_decode(
        q_lat, q_rope, c_cache, r_cache, tables, positions, scale=scale
    ))

    s = pages_per_seq * page_size
    c_pages = c_cache[tables.reshape(-1)].reshape(b, s, r_kv).astype(jnp.float32)
    r_pages = r_cache[tables.reshape(-1)].reshape(b, s, r_width).astype(jnp.float32)
    logits = (
        jnp.einsum("bhr,bsr->bhs", q_lat.astype(jnp.float32), c_pages)
        + jnp.einsum("bhr,bsr->bhs", q_rope.astype(jnp.float32), r_pages)
    ) * scale
    key_pos = jnp.arange(s)[None, None, :]
    logits = jnp.where(key_pos <= positions[:, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    want = np.asarray(jnp.einsum("bhs,bsr->bhr", probs, c_pages))
    np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)
