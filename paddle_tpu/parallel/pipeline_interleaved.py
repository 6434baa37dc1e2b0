"""Interleaved 1F1B pipeline — virtual pipeline stages (reference:
paddle/distributed/fleet/meta_parallel/pipeline_parallel.py, the
``virtual_pp_degree`` interleaved schedule; Megatron-LM's
"interleaved 1F1B").

Each device holds ``v`` model chunks instead of one contiguous stage:
global stage ``g`` (of S = v*pp) lives on device ``g % pp``, chunk
``g // pp``. A microbatch therefore visits every device v times, and the
pipeline bubble shrinks from (pp-1) full-stage units to (pp-1)
chunk-units — v times smaller, the whole point of interleaving.

TPU-native realisation: like the non-interleaved 1F1B in
``pipeline.py``, this is ONE SPMD program inside `shard_map` manual over
``pp`` (tp/fsdp/dp stay GSPMD-auto inside the chunk fns). What is new:

- Consecutive global stages sit on consecutive devices, so EVERY tick's
  handoff is the same ring `lax.ppermute` (+1 forward, -1 backward) —
  the interleaving needs no special routing, just more ticks.
- The who-does-what-when problem is solved OUTSIDE the program: the
  schedule (microbatch m, chunk c, live?) per (tick, device) is computed
  on the host as static int32 tables and streamed through the
  `lax.scan` as xs; each device picks its row with `lax.axis_index`.
  Collision-freedom is *asserted* during table construction, not hoped
  for: the tick formula
      fwd(m, g)  = (m // pp) * S + (m % pp) + g
      bwd(m, g)  = S + (m // pp) * S + (m % pp) + (S - 1 - g)
  assigns each device at most one forward and one backward per tick
  (unique (m, c) recovery mod pp — see _build_schedule), and
  bwd(m, S-1) = fwd(m, S-1) + 1: the backward chases the forward at the
  1F1B distance, so saved activations stay O(pp), not O(M).
- Per-chunk state: chunk params are stacked on a local leading [v] dim
  (dynamic-indexed by the scheduled chunk), activations live in a
  [v, K] ring whose K is the exact max-in-flight computed from the
  tables, and chunk grads scatter-add into [v, ...] accumulators.

Embedding and loss head run only where they live (device 0 chunk 0 /
device pp-1 chunk v-1) behind device-varying `lax.cond`s, as in
pipeline.py.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..distributed.env import get_mesh
from .pipeline import _tree_add_where, validate_pp_mesh


def _build_schedule(pp: int, v: int, M: int):
    """Static (tick, device) -> (m, chunk, live) tables for fwd and bwd.

    Returns (fwd_m, fwd_c, fwd_live, bwd_m, bwd_c, bwd_live) as [T, pp]
    int32/bool arrays, plus K, the max activations in flight per chunk.
    """
    S = v * pp

    def fwd_tick(m, g):
        return (m // pp) * S + (m % pp) + g

    def bwd_tick(m, g):
        return S + (m // pp) * S + (m % pp) + (S - 1 - g)

    T = bwd_tick(M - 1, 0) + 1
    shape = (T, pp)
    fwd_m = np.zeros(shape, np.int32)
    fwd_c = np.zeros(shape, np.int32)
    fwd_live = np.zeros(shape, bool)
    bwd_m = np.zeros(shape, np.int32)
    bwd_c = np.zeros(shape, np.int32)
    bwd_live = np.zeros(shape, bool)
    for m in range(M):
        for g in range(S):
            d, c = g % pp, g // pp
            t = fwd_tick(m, g)
            assert not fwd_live[t, d], "fwd schedule collision"
            fwd_m[t, d], fwd_c[t, d], fwd_live[t, d] = m, c, True
            t = bwd_tick(m, g)
            assert not bwd_live[t, d], "bwd schedule collision"
            bwd_m[t, d], bwd_c[t, d], bwd_live[t, d] = m, c, True

    # exact ring size: max, over (device, chunk), of activations whose
    # forward has run but whose backward has not
    K = 1
    for g in range(S):
        events = [(fwd_tick(m, g), 1) for m in range(M)] + \
                 [(bwd_tick(m, g), -1) for m in range(M)]
        live = peak = 0
        for _, delta in sorted(events):
            live += delta
            peak = max(peak, live)
        K = max(K, peak)
    return (fwd_m, fwd_c, fwd_live, bwd_m, bwd_c, bwd_live), T, K


def interleaved_pipeline_value_and_grad(
        embed_fn: Callable, chunk_fn: Callable, head_loss_fn: Callable,
        n_stages: int, v: int, axis_name: str = "pp", mesh=None):
    """Interleaved-1F1B analogue of ``pipeline_value_and_grad``.

    Args:
      embed_fn(embed_params, tokens[mb, s]) -> x [mb, s, h]
      chunk_fn(chunk_params, x) -> y (same shape; one chunk = L/(v*pp)
        decoder layers; called with the scheduled chunk's params)
      head_loss_fn(head_params, y, labels[mb, s]) -> scalar mean loss
      n_stages: pp degree; v: virtual chunks per device (v=1 degenerates
        to the plain schedule — use pipeline.py then, it is cheaper).

    Returns fn(params, tokens, labels) -> (loss, grads) with
      params = {"embed":…, "stages": pytree with leading [v, pp, …],
                "head":…};  tokens/labels [n_micro, micro_b, seq].
    """

    def run(params, tokens, labels):
        m = mesh or get_mesh()
        validate_pp_mesh(m, axis_name)
        pp = n_stages
        stage_specs = jax.tree.map(lambda _: P(None, axis_name),
                                   params["stages"])
        in_specs = ({"embed": jax.tree.map(lambda _: P(), params["embed"]),
                     "stages": stage_specs,
                     "head": jax.tree.map(lambda _: P(), params["head"])},
                    P(), P())
        out_specs = (P(), in_specs[0])

        M = tokens.shape[0]
        tables, T, K = _build_schedule(pp, v, M)
        xs = tuple(jnp.asarray(t) for t in tables)

        def body(prm, toks, labs, *sched):
            # local chunk params: [v, 1, ...] -> [v, ...]
            cparams = jax.tree.map(lambda p: p[:, 0], prm["stages"])
            eparams, hparams = prm["embed"], prm["head"]
            d = lax.axis_index(axis_name)
            is_dev0, is_last_dev = d == 0, d == pp - 1

            x_sd = jax.eval_shape(embed_fn, eparams, toks[0])
            xdt = x_sd.dtype
            # MoE chunks return (y, aux): same per-stage aux seeding as
            # pipeline_value_and_grad (pp x ep composition)
            out_sd = jax.eval_shape(
                chunk_fn, jax.tree.map(lambda p: p[0], cparams),
                jax.ShapeDtypeStruct(x_sd.shape, xdt))
            has_aux = isinstance(out_sd, (tuple, list))
            zeros_h = jax.tree.map(jnp.zeros_like, hparams)
            zeros_e = jax.tree.map(jnp.zeros_like, eparams)

            def chunk_at(c):
                return jax.tree.map(
                    lambda p: lax.dynamic_index_in_dim(p, c, 0,
                                                       keepdims=False),
                    cparams)

            def tick(c, row):
                fm, fc, flive, bm, bc, blive = (r[d] for r in row)
                # ---------------------------------------------- forward
                fm_c = jnp.clip(fm, 0, M - 1)
                tok_f = lax.dynamic_index_in_dim(toks, fm_c, 0,
                                                 keepdims=False)
                first_stage = is_dev0 & (fc == 0)
                x0 = lax.cond(
                    first_stage,
                    lambda: embed_fn(eparams, tok_f).astype(xdt),
                    lambda: jnp.zeros(x_sd.shape, xdt))
                x_in = jnp.where(first_stage, x0, c["recv_f"])
                y = chunk_fn(chunk_at(fc), x_in)
                if has_aux:
                    y = y[0]
                y = jnp.where(flive, y, jnp.zeros_like(y))
                slot_f = fm_c % K
                old = c["xbuf"][fc, slot_f]
                xbuf = c["xbuf"].at[fc, slot_f].set(
                    jnp.where(flive, x_in, old))

                # ---------------------------------------------- backward
                bm_c = jnp.clip(bm, 0, M - 1)
                x_sv = xbuf[bc, bm_c % K]
                lab_b = lax.dynamic_index_in_dim(labs, bm_c, 0,
                                                 keepdims=False)
                if has_aux:
                    (y_b, aux_b), chunk_vjp = jax.vjp(chunk_fn,
                                                      chunk_at(bc), x_sv)
                else:
                    y_b, chunk_vjp = jax.vjp(chunk_fn, chunk_at(bc), x_sv)
                    aux_b = jnp.float32(0.0)

                last_stage = is_last_dev & (bc == v - 1)

                def head_branch():
                    loss_m, head_vjp = jax.vjp(
                        lambda hp, yy: head_loss_fn(hp, yy, lab_b),
                        hparams, y_b)
                    g_h_m, dy_head = head_vjp(jnp.ones((), loss_m.dtype))
                    return loss_m.astype(jnp.float32), g_h_m, \
                        dy_head.astype(xdt)

                loss_m, g_h_m, dy_head = lax.cond(
                    last_stage, head_branch,
                    lambda: (jnp.float32(0.0), zeros_h,
                             jnp.zeros(x_sd.shape, xdt)))
                dy = jnp.where(last_stage, dy_head, c["recv_b"])
                if has_aux:
                    g_ch_m, dx = chunk_vjp((dy, jnp.ones((), aux_b.dtype)))
                else:
                    g_ch_m, dx = chunk_vjp(dy)

                first_bwd = is_dev0 & (bc == 0)

                def embed_branch():
                    tok_b = lax.dynamic_index_in_dim(toks, bm_c, 0,
                                                     keepdims=False)
                    _, embed_vjp = jax.vjp(embed_fn, eparams, tok_b)
                    return embed_vjp(dx.astype(x_sd.dtype))[0]

                g_e_m = lax.cond(first_bwd, embed_branch, lambda: zeros_e)

                g_st = jax.tree.map(
                    lambda acc, g: acc.at[bc].add(
                        jnp.where(blive, g, jnp.zeros_like(g)).astype(
                            acc.dtype)),
                    c["g_st"], g_ch_m)
                c = dict(
                    xbuf=xbuf,
                    g_st=g_st,
                    g_h=_tree_add_where(blive & last_stage, c["g_h"], g_h_m),
                    g_e=_tree_add_where(blive & first_bwd, c["g_e"], g_e_m),
                    loss=c["loss"] + jnp.where(blive & last_stage, loss_m,
                                               0.0)
                    + jnp.where(blive, aux_b.astype(jnp.float32), 0.0),
                    recv_f=lax.ppermute(
                        y, axis_name,
                        [(i, (i + 1) % pp) for i in range(pp)]),
                    recv_b=lax.ppermute(
                        jnp.where(blive, dx, jnp.zeros_like(dx)),
                        axis_name,
                        [(i, (i - 1) % pp) for i in range(pp)]),
                )
                return c, None

            carry0 = dict(
                xbuf=jnp.zeros((v, K) + x_sd.shape, xdt),
                g_st=jax.tree.map(jnp.zeros_like, cparams),
                g_h=zeros_h,
                g_e=zeros_e,
                loss=jnp.float32(0.0),
                recv_f=jnp.zeros(x_sd.shape, xdt),
                recv_b=jnp.zeros(x_sd.shape, xdt),
            )
            c, _ = lax.scan(tick, carry0, sched)

            grads = {
                "stages": jax.tree.map(lambda g: (g / M)[:, None],
                                       c["g_st"]),
                "head": jax.tree.map(
                    lambda g: lax.psum(g, axis_name) / M, c["g_h"]),
                "embed": jax.tree.map(
                    lambda g: lax.psum(g, axis_name) / M, c["g_e"]),
            }
            loss = lax.psum(c["loss"], axis_name) / M
            return loss, grads

        from jax import shard_map
        return shard_map(body, mesh=m, in_specs=in_specs + (P(),) * 6,
                         out_specs=out_specs, axis_names={axis_name},
                         check_vma=False)(params, tokens, labels, *xs)

    return run
