"""The serving path's decode-attention kernel through the chip's own
compiler, at the widths it is served at, with no chip attached.

The interpreter accepts slices, tilings and VMEM sizes that Mosaic
refuses; the TPU compiler is installed here and compiles for a v5e that
is described, not attached (nothing runs: no result, no time). All such
compiles live in THIS file, inside fixtures: one process may hold the
TPU library, and every xdist worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without the chip: the next
    run would warn and compile again."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("R,M,B,kvh,group,d,P,T,window", [
    (8, 128, 16, 4, 7, 128, 2049, 1, None),     # qwen2-7b-d16, a tick
    (8, 128, 16, 4, 7, 128, 2049, 4, None),     # its speculative verify
    (8, 128, 16, 4, 7, 128, 2049, 1, 1024),     # a sliding window
    (32, 128, 16, 2, 6, 128, 4096, 1, None),    # qwen2-1.5b
    (8, 32, 8, 2, 4, 256, 513, 1, None),        # 8-token pages, head 256
])
def test_ragged_kernel_compiles_for_a_v5e(one_chip, no_persistent_cache,
                                          monkeypatch, R, M, B, kvh, group,
                                          d, P, T, window):
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        ragged_paged_attention_pallas
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    h = kvh * group
    q = arr((R, h, d) if T == 1 else (R, T, h, d))
    compiled = jax.jit(
        lambda q, kp, vp, tbl, lens: ragged_paged_attention_pallas(
            q, kp, vp, tbl, lens, d ** -0.5, window=window)).lower(
        q, arr((P, B, kvh, d)), arr((P, B, kvh, d)),
        arr((R, M), jnp.int32), arr((R,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("R,T", [(64, 1), (64, 2)])
def test_latent_kernel_compiles_for_a_v5e(one_chip, no_persistent_cache,
                                          monkeypatch, R, T):
    """The latent mode at GigaChat3.1 / DeepSeek-V3's geometry: 64 query
    heads over one 640-column row a token (512 latent + 64 roped + 64
    zeros), values the first 512 columns, 8193 pages of 16."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        ragged_paged_attention_pallas
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    q = arr((R, 64, 640) if T == 1 else (R, T, 64, 640))
    compiled = jax.jit(
        lambda q, kp, tbl, lens: ragged_paged_attention_pallas(
            q, kp, None, tbl, lens, 192 ** -0.5, v_width=512)).lower(
        q, arr((8193, 16, 1, 640)), arr((R, 128), jnp.int32),
        arr((R,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
