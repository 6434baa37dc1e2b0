"""Negligible-compute reference CausalLM for the paged-serving tick
machinery (ISSUE 9): embed -> paged KV write -> paged attention ->
vocab projection, one layer, one head. Engine/gateway benchmarks and
tests that drive it measure scheduling, dispatch and transport — not
model FLOPs. Shared by ``tools/serve_loadgen.py --model stub`` and
``tests/test_gateway.py``. It is the whole of what an engine asks of a
model: a ``config`` its pools are sized from, and a forward that hands
each layer's view to ``ops.paged_cache.write_and_attend`` (or reads
``view.call`` in a mixer of its own) and returns the views it got back.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.paged_cache import write_and_attend

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
               segment_ids=None):
            x = params["emb"][tokens]              # [R, s, d]
            kv = x[:, :, None, :]                  # [R, s, 1, d]
            o, pk = write_and_attend(kv_caches[0], kv, kv, kv, positions,
                                     segment_ids)
            o = o[:, :, 0]
            return o @ params["out"], [pk]

        return fn, params
