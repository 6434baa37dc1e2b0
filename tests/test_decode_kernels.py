"""Decode-path kernels (VERDICT r2 item 5; reference: PHI
fusion/gpu/masked_multihead_attention + weight_only_linear_kernel.cu).
Pallas kernels run in interpret mode on CPU; numerics must match the
dense/XLA references exactly (same fp32 softmax/accumulate math)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.attention import decode_attention, dense_attention

pytestmark = pytest.mark.usefixtures("_interpret_pallas")


@pytest.fixture
def _interpret_pallas(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


def _dense_reference(q, ck, cv, cache_index):
    """Masked dense attention over the full cache (the old decode path)."""
    T = ck.shape[1]
    kpos = jnp.arange(T)[None, :]
    qpos = cache_index + jnp.arange(1)[:, None]
    mask = (kpos <= qpos)[None, None]
    return dense_attention(q, ck, cv, attn_mask=mask)


@pytest.mark.parametrize("h,kv", [(8, 4), (4, 4), (16, 2)])
@pytest.mark.parametrize("cache_index", [0, 5, 127, 200, 255])
def test_decode_dispatch_matches_dense(h, kv, cache_index):
    """Interpret mode routes through the Pallas kernel dispatch glue
    (T=256 tiles); the T=192 case exercises the grouped-einsum fallback."""
    rs = np.random.RandomState(0)
    for T in (256, 192):
        b, d = 2, 64
        q = jnp.asarray(rs.randn(b, 1, h, d), jnp.float32)
        ck = jnp.asarray(rs.randn(b, T, kv, d), jnp.float32)
        cv = jnp.asarray(rs.randn(b, T, kv, d), jnp.float32)
        ci = jnp.int32(min(cache_index, T - 1))
        got = decode_attention(q, ck, cv, ci)
        ref = _dense_reference(q, ck, cv, ci)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5, err_msg=f"T={T}")


@pytest.mark.parametrize("h,kv,d", [(8, 4, 64), (16, 2, 128)])
@pytest.mark.parametrize("cache_index", [0, 100, 255])
def test_pallas_decode_kernel_matches_dense(h, kv, d, cache_index):
    from paddle_tpu.ops.pallas.decode_attention import decode_attention_pallas
    rs = np.random.RandomState(1)
    b, T = 2, 256
    q = jnp.asarray(rs.randn(b, h, d), jnp.float32)
    ck = jnp.asarray(rs.randn(b, T, kv, d), jnp.float32)
    cv = jnp.asarray(rs.randn(b, T, kv, d), jnp.float32)
    got = decode_attention_pallas(q, ck, cv, jnp.int32(cache_index),
                                  scale=1.0 / np.sqrt(d), block_t=128)
    ref = _dense_reference(q[:, None], ck, cv, jnp.int32(cache_index))[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_pallas_decode_bf16():
    from paddle_tpu.ops.pallas.decode_attention import decode_attention_pallas
    rs = np.random.RandomState(2)
    b, T, h, kv, d = 1, 128, 8, 4, 64
    q = jnp.asarray(rs.randn(b, h, d), jnp.bfloat16)
    ck = jnp.asarray(rs.randn(b, T, kv, d), jnp.bfloat16)
    cv = jnp.asarray(rs.randn(b, T, kv, d), jnp.bfloat16)
    got = decode_attention_pallas(q, ck, cv, jnp.int32(50),
                                  scale=1.0 / np.sqrt(d), block_t=128)
    ref = _dense_reference(q[:, None].astype(jnp.float32),
                           ck.astype(jnp.float32), cv.astype(jnp.float32),
                           jnp.int32(50))[:, 0]
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(ref),
                               atol=3e-2, rtol=3e-2)


def test_generation_uses_decode_path():
    """End-to-end: generate() with the new decode branch still produces
    the same tokens as before (greedy, tiny llama)."""
    import paddle_tpu as pt
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny
    pt.seed(0)
    model = LlamaForCausalLM(llama_tiny(max_position_embeddings=128))
    ids = jnp.asarray(np.random.RandomState(3).randint(0, 256, (2, 8)))
    out = model.generate(ids, max_new_tokens=8, temperature=0.0)
    assert out.shape[1] == 16
    # decode must be deterministic and stable across calls
    out2 = model.generate(ids, max_new_tokens=8, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


# ------------------------------------------------------- fused dequant mm
@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("m", [1, 8, 17])
def test_quant_matmul_kernel_matches_dequant(bits, m):
    from paddle_tpu.ops.pallas.quant_matmul import quant_matmul_pallas
    from paddle_tpu.quant import dequantize_weight, quantize_blockwise
    rs = np.random.RandomState(4)
    din, dout = 256, 384
    w = jnp.asarray(rs.randn(din, dout) * 0.1, jnp.float32)
    qw, sc = quantize_blockwise(w, bits=bits)
    x = jnp.asarray(rs.randn(m, din), jnp.float32)
    got = quant_matmul_pallas(x, qw, sc, bits=bits)
    ref = x @ dequantize_weight(qw, sc, bits=bits, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("bits", [8, 4])
def test_weight_only_linear_routes_to_kernel(bits):
    """With interpret mode on, decode-sized calls go through the Pallas
    kernel and must agree with the XLA dequant path."""
    from paddle_tpu.quant import weight_only_linear, quantize_blockwise
    rs = np.random.RandomState(5)
    w = jnp.asarray(rs.randn(256, 128) * 0.1, jnp.float32)
    qw, sc = quantize_blockwise(w, bits=bits)
    x = jnp.asarray(rs.randn(2, 4, 256), jnp.float32)  # [b, s, din]
    bias = jnp.asarray(rs.randn(128), jnp.float32)
    got = weight_only_linear(x, qw, sc, bias, bits=bits)
    os.environ["PADDLE_TPU_DISABLE_QUANT_KERNEL"] = "1"
    try:
        del os.environ["PADDLE_TPU_PALLAS_INTERPRET"]
        ref = weight_only_linear(x, qw, sc, bias, bits=bits)
    finally:
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
        del os.environ["PADDLE_TPU_DISABLE_QUANT_KERNEL"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)

def test_pallas_decode_group_not_multiple_of_8():
    """GQA group 12 (h=24, kv=2): gp must round up to 16, not sit at 12."""
    from paddle_tpu.ops.pallas.decode_attention import decode_attention_pallas
    rs = np.random.RandomState(6)
    b, T, h, kv, d = 1, 128, 24, 2, 64
    q = jnp.asarray(rs.randn(b, h, d), jnp.float32)
    ck = jnp.asarray(rs.randn(b, T, kv, d), jnp.float32)
    cv = jnp.asarray(rs.randn(b, T, kv, d), jnp.float32)
    got = decode_attention_pallas(q, ck, cv, jnp.int32(60),
                                  scale=1.0 / np.sqrt(d), block_t=128)
    ref = _dense_reference(q[:, None], ck, cv, jnp.int32(60))[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def _flat(pool):
    """A [P, B, kvh, d] pool as the engine holds it: [P, B, kvh*d]."""
    return pool.reshape(pool.shape[:2] + (-1,))


def _dense_paged_reference(q, kp, vp, tables, lens, window=None):
    """The dense whole-table gather path (generation/paged.py fallback)."""
    R = q.shape[0]
    kvh, d = kp.shape[2], kp.shape[3]
    ks = kp[tables].reshape(R, -1, kvh, d)
    vs = vp[tables].reshape(R, -1, kvh, d)
    kpos = jnp.arange(ks.shape[1])[None, :]
    keep = kpos <= lens[:, None]
    if window is not None:
        keep &= kpos > lens[:, None] - window
    return dense_attention(q[:, None], ks, vs,
                           attn_mask=keep[:, None, None, :])[:, 0]


@pytest.mark.parametrize("h,kvh,d", [(8, 4, 64), (16, 2, 128), (4, 4, 64)])
@pytest.mark.parametrize("window", [None, 20])
def test_pallas_paged_kernel_matches_dense_gather(h, kvh, d, window):
    """VERDICT-r4 missing #2: the paged kernel (the ragged one: each
    row walks its own pages) must be exact vs the dense whole-pool
    gather on ragged rows — including rows whose tables hold garbage
    beyond their live blocks — at head shapes ``TestRaggedKernel`` does
    not hold: group 2 of 64, group 8 of 128, no grouping."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        ragged_paged_attention_pallas
    rs = np.random.RandomState(2)
    R, P, B, M = 4, 32, 16, 8
    q = jnp.asarray(rs.randn(R, h, d), jnp.float32)
    kp = jnp.asarray(rs.randn(P, B, kvh, d), jnp.float32)
    vp = jnp.asarray(rs.randn(P, B, kvh, d), jnp.float32)
    # ragged: rows own different numbers of blocks; dead table slots
    # point at garbage blocks with RANDOM contents (they must not leak)
    lens = np.asarray([0, 17, 63, 127], np.int32)
    tables = rs.permutation(np.arange(P)).reshape(1, -1)[0][:R * M] \
        .reshape(R, M).astype(np.int32)
    got = ragged_paged_attention_pallas(
        q, _flat(kp), _flat(vp), jnp.asarray(tables), jnp.asarray(lens),
        1.0 / np.sqrt(d), kvh, window=window)
    ref = _dense_paged_reference(q, kp, vp, jnp.asarray(tables),
                                 jnp.asarray(lens), window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_decode_attention_routes_to_kernel():
    """generation/paged.py dispatch: interpret mode must route through
    the Pallas kernel and agree with the explicit fallback."""
    from paddle_tpu.ops.paged_cache import (PagedKV, paged_decode_attention,
                                            paged_decode_route)
    rs = np.random.RandomState(3)
    R, P, B, M, kvh, h, d = 3, 16, 16, 4, 2, 4, 64
    kp = jnp.asarray(rs.randn(P, B, kvh, d), jnp.float32)
    vp = jnp.asarray(rs.randn(P, B, kvh, d), jnp.float32)
    pk = PagedKV(_flat(kp), _flat(vp),
                 jnp.asarray(rs.randint(0, P, (R, M)), jnp.int32),
                 jnp.asarray([3, 30, 60], jnp.int32), kvh)
    q = jnp.asarray(rs.randn(R, 1, h, d), jnp.float32)
    assert paged_decode_route(q, pk.kp, kvh) == "ragged"
    got = paged_decode_attention(q, pk)
    ref = _dense_paged_reference(q[:, 0], kp, vp, pk.block_tables,
                                 pk.seq_lens)
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# --------------------------------------------- VMEM budget-cap regression
def test_pick_block_t_budget_cap_falls_back_to_128():
    """ADVICE r5 medium: halving a non-power-of-two preferred size (the
    384-row VMEM budget cap, kv*d in (1024,1365]) strands on sizes that
    don't divide T and used to return 0, tripping `assert bt` even
    though T % 128 == 0 guarantees a legal tile."""
    from paddle_tpu.ops.pallas.decode_attention import pick_block_t
    assert pick_block_t(2048, 384) == 128      # was 0: 384->192->96
    assert pick_block_t(640, 384) == 128       # was 0
    # untouched behavior: power-of-two ladders and exact totals
    assert pick_block_t(2048, 512) == 512
    assert pick_block_t(256, 512) == 256
    assert pick_block_t(192, 512) == 192
    assert pick_block_t(100, 512) == 100       # exact total: full block


@pytest.mark.parametrize("kv,d", [(10, 128), (5, 256), (20, 64)])
def test_budget_cap_shapes_run_and_match_dense(kv, d):
    """kv*d = 1280 puts budget_rows at exactly 384; the kernel must run
    (128-row fallback tile) and match the dense reference."""
    from paddle_tpu.ops.pallas.decode_attention import decode_attention_pallas
    rs = np.random.RandomState(6)
    b, T, h = 1, 640, 2 * kv                   # T%384 != 0, T%128 == 0
    q = jnp.asarray(rs.randn(b, h, d), jnp.float32)
    ck = jnp.asarray(rs.randn(b, T, kv, d), jnp.float32)
    cv = jnp.asarray(rs.randn(b, T, kv, d), jnp.float32)
    got = decode_attention_pallas(q, ck, cv, jnp.int32(200),
                                  scale=1.0 / np.sqrt(d))
    ref = _dense_reference(q[:, None], ck, cv, jnp.int32(200))[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
