"""Negligible-compute reference CausalLM for the paged-serving tick
machinery (ISSUE 9): embed -> paged KV write -> paged attention ->
vocab projection, one layer, one head. Engine/gateway benchmarks and
tests that drive it measure scheduling, dispatch and transport — not
model FLOPs. Shared by ``tools/serve_loadgen.py --model stub`` and
``tests/test_gateway.py`` so the paged-cache calling convention lives
in ONE place (the multi-chunk global-positions contract below was
once fixed in two copies at once; see CHANGES PR 7).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .paged import (paged_chunk_attention, paged_decode_attention,
                    paged_decode_write, paged_packed_attention,
                    paged_prefill_write)

__all__ = ["TickStubConfig", "TickStubModel"]


class TickStubConfig:
    vocab_size = 128
    num_hidden_layers = 1
    num_key_value_heads = 1
    head_dim = 8
    dtype = jnp.float32


class TickStubModel:
    """Minimal CausalLM contract (``config`` + ``functional()``). The
    returned fn is a PURE closure over its own params — unlike
    ``Layer.functional()`` it never binds onto a shared layer tree, so
    replicas sharing one instance may tick concurrently."""

    config = TickStubConfig()

    def functional(self):
        d, V = self.config.head_dim, self.config.vocab_size
        k = jax.random.PRNGKey(0)
        params = dict(emb=jax.random.normal(k, (V, d)),
                      out=jax.random.normal(k, (d, V)))

        def fn(params, tokens, kv_caches=None, positions=None,
               paged_chunk=False, paged_decode=False, segment_ids=None):
            x = params["emb"][tokens]              # [R, s, d]
            kv = x[:, :, None, :]                  # [R, s, 1, d]
            pk = kv_caches[0]
            if paged_decode or tokens.shape[1] == 1:
                # decode tick — including the speculative multi-query
                # verify (paged_decode=True, [R, k+1]): the paged
                # write/attention helpers handle T >= 1 natively
                pk = paged_decode_write(pk, kv, kv)
                o = paged_decode_attention(x[:, :, None, :], pk)[:, :, 0]
            elif segment_ids is not None:
                # a packed call: several prompts from position 0, each
                # token into its own prompt's row, attention over the
                # call's own rows
                pk = paged_prefill_write(pk, kv, kv,
                                         positions=positions[0],
                                         segments=segment_ids[0])
                o = paged_packed_attention(kv, kv, kv,
                                           segment_ids)[:, :, 0]
            else:                                  # (chunk) prefill
                # chunk K/V lands at its GLOBAL positions — a chunk at
                # start > 0 written at 0..s-1 reads stale data later
                pk = paged_prefill_write(pk, kv, kv,
                                         positions=positions[0])
                o = paged_chunk_attention(x[:1, :, None, :], pk,
                                          positions)[:, :, 0]
            return o @ params["out"], [pk]

        return fn, params
