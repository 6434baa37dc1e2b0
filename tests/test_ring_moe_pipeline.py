"""SURVEY.md §4 parallel correctness: ring attention == full attention,
ulysses == full attention, MoE dispatch conservation, pipeline == sequential."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu.distributed import env
from paddle_tpu.ops.attention import dense_attention
from paddle_tpu.parallel import (MoEMLP, pipeline_apply, ring_attention,
                                 stack_stage_params, top_k_routing,
                                 ulysses_attention)
from jax import shard_map


@pytest.fixture
def sp_mesh():
    mesh = env.init_parallel_env({"sp": 4, "dp": 2})
    yield mesh
    env.init_parallel_env({})


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(sp_mesh, causal):
    b, s, h, d = 2, 64, 4, 16
    kvh = 2  # GQA
    q = jnp.asarray(np.random.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(np.random.randn(b, s, kvh, d), jnp.float32)
    v = jnp.asarray(np.random.randn(b, s, kvh, d), jnp.float32)
    ref = dense_attention(q, k, v, causal=causal)

    ring = shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=causal),
        mesh=sp_mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False)
    out = jax.jit(ring)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_grads_match(sp_mesh):
    b, s, h, d = 1, 32, 2, 8
    q = jnp.asarray(np.random.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(np.random.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(np.random.randn(b, s, h, d), jnp.float32)

    ring = shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=True),
        mesh=sp_mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False)
    g_ring = jax.jit(jax.grad(lambda q, k, v: ring(q, k, v).sum(),
                              argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: dense_attention(q, k, v, causal=True).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(sp_mesh, causal):
    b, s, h, d = 2, 64, 8, 16
    q = jnp.asarray(np.random.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(np.random.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(np.random.randn(b, s, h, d), jnp.float32)
    ref = dense_attention(q, k, v, causal=causal)
    uly = shard_map(
        functools.partial(ulysses_attention, axis_name="sp", causal=causal),
        mesh=sp_mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False)
    out = jax.jit(uly)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_topk_routing_conservation():
    T, E, k = 64, 8, 2
    logits = jnp.asarray(np.random.randn(T, E), jnp.float32)
    C = 32  # ample capacity: nothing dropped
    dispatch, combine, aux = top_k_routing(logits, k, C)
    # each token dispatched exactly k times
    np.testing.assert_allclose(np.asarray(dispatch.sum(axis=(1, 2))), k)
    # no slot double-booked
    assert float(dispatch.sum(axis=0).max()) <= 1.0 + 1e-6
    # combine weights = the top-k softmax probs
    probs = jax.nn.softmax(logits, axis=-1)
    topk = jnp.sort(probs, axis=-1)[:, -k:].sum(-1)
    np.testing.assert_allclose(np.asarray(combine.sum(axis=(1, 2))),
                               np.asarray(topk), rtol=1e-5)
    assert np.isfinite(float(aux))


def test_moe_mlp_forward_and_ep_sharding():
    env.init_parallel_env({"ep": 4, "dp": 2})
    try:
        pt.seed(0)
        moe = MoEMLP(hidden_size=32, intermediate_size=64, num_experts=8,
                     top_k=2, num_shared_experts=1)
        from paddle_tpu.parallel.sharding import shard_layer
        sh = shard_layer(moe)
        assert "ep" in str(sh["w_gate"].spec)
        x = jnp.asarray(np.random.randn(4, 16, 32), jnp.float32)
        fn, params = moe.functional()
        y, aux = jax.jit(lambda p, x: fn(p, x, return_aux=True))(params, x)
        assert y.shape == x.shape
        assert np.isfinite(np.asarray(y)).all()
        assert float(aux) > 0
        # gradients flow to expert weights
        g = jax.grad(lambda p: fn(p, x).sum())(params)
        assert float(jnp.abs(g["w_down"]).sum()) > 0
    finally:
        env.init_parallel_env({})


def test_moe_matches_dense_single_expert():
    """E=1, k=1, ample capacity: MoE == its one expert's SwiGLU."""
    pt.seed(1)
    moe = MoEMLP(hidden_size=16, intermediate_size=32, num_experts=1,
                 top_k=1, capacity_factor=2.0)
    x = jnp.asarray(np.random.randn(2, 8, 16), jnp.float32)
    y = moe(x)
    import paddle_tpu.nn.functional as F
    w_g, w_u, w_d = moe.w_gate[0], moe.w_up[0], moe.w_down[0]
    ref = (F.silu(x @ w_g) * (x @ w_u)) @ w_d  # gate prob == 1 when E==1
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-4,
                               atol=1e-5)


def test_pipeline_matches_sequential():
    mesh = env.init_parallel_env({"pp": 4, "dp": 2})
    try:
        pt.seed(0)
        dim, n_micro, mb = 16, 8, 4
        stages = [{"w": jnp.asarray(np.random.randn(dim, dim) * 0.3, jnp.float32),
                   "b": jnp.zeros((dim,))} for _ in range(4)]

        def stage_fn(params, x):
            return jnp.tanh(x @ params["w"] + params["b"])

        stacked = stack_stage_params(stages)
        microbatches = jnp.asarray(np.random.randn(n_micro, mb, dim), jnp.float32)

        out = jax.jit(lambda sp, m: pipeline_apply(stage_fn, sp, m))(
            stacked, microbatches)

        ref = microbatches
        for p in stages:
            ref = jax.vmap(lambda x, p=p: stage_fn(p, x))(ref)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
    finally:
        env.init_parallel_env({})


def test_pipeline_differentiable():
    mesh = env.init_parallel_env({"pp": 4, "dp": 2})
    try:
        dim = 8
        stages = [{"w": jnp.asarray(np.random.randn(dim, dim) * 0.3, jnp.float32)}
                  for _ in range(4)]

        def stage_fn(params, x):
            return jnp.tanh(x @ params["w"])

        stacked = stack_stage_params(stages)
        mbs = jnp.asarray(np.random.randn(4, 2, dim), jnp.float32)

        def loss_pp(sp):
            return jnp.sum(pipeline_apply(stage_fn, sp, mbs) ** 2)

        def loss_seq(stages_list):
            x = mbs
            for p in stages_list:
                x = jax.vmap(lambda xx, p=p: stage_fn(p, xx))(x)
            return jnp.sum(x ** 2)

        g_pp = jax.jit(jax.grad(loss_pp))(stacked)
        g_seq = jax.grad(loss_seq)(stages)
        for i in range(4):
            np.testing.assert_allclose(np.asarray(g_pp["w"][i]),
                                       np.asarray(g_seq[i]["w"]),
                                       rtol=1e-3, atol=1e-4)
    finally:
        env.init_parallel_env({})


class TestRingFlash:
    def test_matches_full_attention(self, monkeypatch):
        """ring_flash == single-device full attention (8-way sp mesh,
        pallas kernels in interpret mode on CPU)."""
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from paddle_tpu.parallel.ring import ring_flash_attention
        from paddle_tpu.ops.attention import dense_attention

        # interpret-mode pallas is slow: 4 shards x 128 is the smallest
        # shape that still tiles the kernel and rotates a real ring
        n = 4
        B, S, H, D = 1, 4 * 128, 1, 32
        q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D))
        mesh = Mesh(np.array(jax.devices()[:n]), ("sp",))
        for causal in (False, True):
            ring = shard_map(
                lambda q, k, v: ring_flash_attention(q, k, v, "sp",
                                                     causal=causal),
                mesh=mesh,
                in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
                out_specs=P(None, "sp"), check_vma=False)
            out = ring(q, k, v)
            ref = dense_attention(q, k, v, causal=causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-5, rtol=1e-4)

    def test_gradients_flow(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from paddle_tpu.parallel.ring import ring_flash_attention
        from paddle_tpu.ops.attention import dense_attention

        n = 2
        B, S, H, D = 1, 2 * 128, 1, 32
        q = jax.random.normal(jax.random.PRNGKey(3), (B, S, H, D))
        k = jax.random.normal(jax.random.PRNGKey(4), (B, S, H, D))
        v = jax.random.normal(jax.random.PRNGKey(5), (B, S, H, D))
        mesh = Mesh(np.array(jax.devices()[:n]), ("sp",))
        ring = shard_map(
            lambda q, k, v: ring_flash_attention(q, k, v, "sp", causal=True),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"), check_vma=False)
        g1 = jax.grad(lambda q, k, v: jnp.sum(ring(q, k, v) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(
            lambda q, k, v: jnp.sum(
                dense_attention(q, k, v, causal=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=1e-3)


def test_norm_topk_prob_routing():
    """norm_topk_prob renormalizes the selected gates to sum to 1 per
    token (Qwen2-57B-A14B semantics); combine weights prove it."""
    import numpy as np
    from paddle_tpu.parallel.moe import top_k_routing

    rs = np.random.RandomState(0)
    logits = jnp.asarray(rs.randn(16, 8), jnp.float32)
    _, combine_raw, _ = top_k_routing(logits, 2, capacity=16)
    _, combine_norm, _ = top_k_routing(logits, 2, capacity=16,
                                       norm_topk_prob=True)
    raw_sums = np.asarray(combine_raw.sum(axis=(1, 2)))
    norm_sums = np.asarray(combine_norm.sum(axis=(1, 2)))
    assert (raw_sums < 0.999).any()       # raw softmax mass < 1 over top-k
    np.testing.assert_allclose(norm_sums, 1.0, atol=1e-5)


def _mk_segments(rng, b, s, n_seg=3):
    """Random packed-sequence ids: contiguous runs 1..n_seg then 0-pad."""
    import numpy as _np
    out = _np.zeros((b, s), _np.int32)
    for r in range(b):
        cuts = sorted(rng.choice(_np.arange(4, s - 4), n_seg - 1,
                                 replace=False))
        bounds = [0] + list(cuts) + [s - 4]  # last 4 positions = pad (0)
        for i in range(n_seg):
            out[r, bounds[i]:bounds[i + 1]] = i + 1
    return out


def test_ring_attention_segments_match_dense(sp_mesh):
    """Packed SFT under context parallelism (VERDICT r3 weak #4): the
    segment ids rotate with the KV blocks; result must equal dense
    block-causal attention over the full sequence."""
    from paddle_tpu.ops.attention import segment_mask
    b, s, h, d = 2, 64, 4, 16
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, 2, d), jnp.float32)  # GQA
    v = jnp.asarray(rng.randn(b, s, 2, d), jnp.float32)
    seg = jnp.asarray(_mk_segments(rng, b, s))
    ref = dense_attention(q, k, v, causal=True, attn_mask=segment_mask(seg))

    ring = shard_map(
        lambda q, k, v, sg: ring_attention(q, k, v, axis_name="sp",
                                           causal=True, segment_ids=sg),
        mesh=sp_mesh, in_specs=(P(None, "sp"),) * 3 + (P(None, "sp"),),
        out_specs=P(None, "sp"), check_vma=False)
    out = jax.jit(ring)(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window", [8, 24])
def test_ring_attention_window_matches_dense(sp_mesh, window):
    """Sliding-window attention under sp: global positions make the band
    exact across shard boundaries."""
    b, s, h, d = 2, 64, 4, 16
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    ref = dense_attention(q, k, v, causal=True, window=window)

    ring = shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=True,
                          window=window),
        mesh=sp_mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False)
    out = jax.jit(ring)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_segments_window_grads(sp_mesh):
    """Both masks at once, and grads flow (packed + SWA under sp)."""
    from paddle_tpu.ops.attention import segment_mask
    b, s, h, d = 1, 32, 2, 8
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    seg = jnp.asarray(_mk_segments(rng, b, s, n_seg=2))
    window = 6

    ring = shard_map(
        lambda q, k, v, sg: ring_attention(q, k, v, axis_name="sp",
                                           causal=True, segment_ids=sg,
                                           window=window),
        mesh=sp_mesh, in_specs=(P(None, "sp"),) * 3 + (P(None, "sp"),),
        out_specs=P(None, "sp"), check_vma=False)
    out = jax.jit(ring)(q, k, v, seg)
    ref = dense_attention(q, k, v, causal=True, window=window,
                          attn_mask=segment_mask(seg))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    g_ring = jax.jit(jax.grad(lambda q, k, v: ring(q, k, v, seg).sum(),
                              argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: dense_attention(
            q, k, v, causal=True, window=window,
            attn_mask=segment_mask(seg)).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-3, atol=1e-4)


def test_ulysses_segments_window_match_dense(sp_mesh):
    """Ulysses path: local segment shard all-gathers to the full mask."""
    from paddle_tpu.ops.attention import segment_mask
    b, s, h, d = 2, 64, 4, 16
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    seg = jnp.asarray(_mk_segments(rng, b, s))
    window = 16
    ref = dense_attention(q, k, v, causal=True, window=window,
                          attn_mask=segment_mask(seg))

    uly = shard_map(
        lambda q, k, v, sg: ulysses_attention(q, k, v, axis_name="sp",
                                              causal=True, segment_ids=sg,
                                              window=window),
        mesh=sp_mesh, in_specs=(P(None, "sp"),) * 3 + (P(None, "sp"),),
        out_specs=P(None, "sp"), check_vma=False)
    out = jax.jit(uly)(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_flash_masked_delegates(sp_mesh):
    """ring_flash_attention with masks routes to the exact block path."""
    from paddle_tpu.parallel.ring import ring_flash_attention
    b, s, h, d = 1, 64, 2, 16
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    ref = dense_attention(q, k, v, causal=True, window=12)
    ring = shard_map(
        functools.partial(ring_flash_attention, axis_name="sp",
                          causal=True, window=12),
        mesh=sp_mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False)
    out = jax.jit(ring)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
