"""Mixture-of-Experts with expert parallelism (reference: Paddle's
incubate.distributed.models.moe + PaddleNLP Qwen2-MoE/DeepSeekMoE recipes —
top-k gating, capacity dispatch, NCCL all_to_all over the expert group).

TPU-native (GShard-style): experts live as *stacked* weights
[E, in, out] sharded over the ``ep`` mesh axis; dispatch/combine are
einsums against a capacity-bucketed one-hot, so XLA lowers the routing to
all_to_all collectives over ICI — no hand-written NCCL plumbing, fully
static shapes (dropped tokens beyond capacity, GShard semantics).

Balancing: switch-style aux loss (mean router prob x mean token fraction
x E) plus optional router z-loss; or "loss-free" bias balancing
(DeepSeek-V3 style) via `update_loss_free_bias`.
"""
from __future__ import annotations

import contextlib
import math
import threading
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer, Parameter
from ..utils.rng import next_key
from .sharding import constraint


def _select_topk(router_logits, k, bias, n_group, topk_group, scoring,
                 group_score_mode):
    """The ONE definition of DeepSeek-family expert selection (scores,
    bias correction, group limiting, top-k) — shared by the dispatch and
    by ``update_loss_free_bias`` so the bias is always updated against
    the loads the real router produces."""
    T, E = router_logits.shape
    if scoring == "sigmoid":   # DeepSeek-V3: independent expert scores
        probs = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    else:
        probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    select_scores = probs if bias is None else probs + bias[None, :]
    if n_group > 1:
        g = select_scores.reshape(T, n_group, E // n_group)
        if group_score_mode == "top2_sum":   # DeepSeek-V3 group score
            top2, _ = jax.lax.top_k(g, 2)
            group_scores = jnp.sum(top2, axis=-1)             # [T, G]
        else:
            group_scores = jnp.max(g, axis=-1)                # [T, G]
        _, top_groups = jax.lax.top_k(group_scores, topk_group)
        group_ok = jnp.any(
            jnp.arange(n_group)[None, :, None] == top_groups[:, None, :],
            axis=-1)                                          # [T, G]
        # -inf, not 0: a loss-free-balancing bias can push eligible
        # scores negative, and a 0-masked ineligible expert must never
        # outrank them in top_k (gates come from the unmasked probs, so
        # -inf never reaches the combine weights)
        select_scores = jnp.where(
            jnp.repeat(group_ok, E // n_group, axis=1), select_scores,
            -jnp.inf)
    _, expert_ids = jax.lax.top_k(select_scores, k)          # [T, k]
    return probs, expert_ids


def top_k_routing(router_logits, k: int, capacity: int,
                  bias: Optional[jax.Array] = None,
                  norm_topk_prob: bool = False,
                  n_group: int = 1, topk_group: int = 1,
                  scoring: str = "softmax",
                  group_score_mode: str = "max"):
    """router_logits [T, E] -> (dispatch [T, E, C] bool, combine [T, E, C],
    aux_loss scalar). GShard top-k with per-expert capacity C.
    ``norm_topk_prob`` renormalizes the selected gates to sum to 1
    (Qwen2-57B-A14B-style); False keeps raw softmax-over-all probs.
    ``n_group > 1`` is DeepSeek's group-limited-greedy: experts split
    into n_group groups, only the top ``topk_group`` groups (by max
    member prob) stay eligible before the per-token top-k."""
    T, E = router_logits.shape
    probs, expert_ids = _select_topk(router_logits, k, bias, n_group,
                                     topk_group, scoring,
                                     group_score_mode)
    onehot = jax.nn.one_hot(expert_ids, E, dtype=jnp.float32)  # [T, k, E]
    gates = probs[:, None, :] * onehot                        # gate per choice
    if norm_topk_prob:
        total = jnp.sum(gates, axis=(1, 2), keepdims=True)
        gates = gates / jnp.maximum(total, 1e-9)
    # position of each token within its expert's bucket (over T*k choices,
    # priority by choice rank then token order — GShard's policy)
    flat = onehot.transpose(1, 0, 2).reshape(k * T, E)        # choice-major
    pos = (jnp.cumsum(flat, axis=0) - flat)                   # [kT, E]
    pos = pos.reshape(k, T, E).transpose(1, 0, 2)             # [T, k, E]
    keep = (pos < capacity) * onehot                          # drop overflow
    pos = jnp.minimum(pos, capacity - 1).astype(jnp.int32)
    pos_onehot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)  # [T,k,E,C]
    dispatch = jnp.einsum("tke,tkec->tec", keep, pos_onehot)
    combine = jnp.einsum("tke,tkec->tec", gates * keep, pos_onehot)
    # switch aux loss: E * sum_e mean_prob_e * mean_frac_e. Sigmoid
    # scores normalize first (DeepSeek's seq-aux does the same) — the raw
    # product would be minimized by driving EVERY score to 0, collapsing
    # the router instead of balancing it.
    frac = jnp.mean(onehot[:, 0, :], axis=0)   # fraction routed (top-1 choice)
    pn = probs / jnp.maximum(jnp.sum(probs, axis=-1, keepdims=True), 1e-9) \
        if scoring == "sigmoid" else probs
    mean_prob = jnp.mean(pn, axis=0)
    aux = E * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


class MoEMLP(Layer):
    """Drop-in replacement for a dense FFN: k-of-E expert SwiGLU MLPs with
    optional always-on shared experts (Qwen2-MoE/DeepSeekMoE pattern)."""

    def __init__(self, hidden_size: int, intermediate_size: int,
                 num_experts: int, top_k: int = 2,
                 capacity_factor: float = 1.25,
                 num_shared_experts: int = 0,
                 shared_intermediate_size: Optional[int] = None,
                 aux_loss_weight: float = 0.01,
                 use_shared_expert_gate: bool = False,
                 norm_topk_prob: bool = False,
                 routed_scaling_factor: float = 1.0,
                 n_group: int = 1, topk_group: int = 1,
                 scoring: str = "softmax",
                 group_score_mode: str = "max", name=None):
        super().__init__(name)
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.aux_loss_weight = aux_loss_weight
        self.norm_topk_prob = norm_topk_prob
        # DeepSeek-V2/V3: the routed (not shared) output is scaled
        self.routed_scaling_factor = routed_scaling_factor
        self.n_group, self.topk_group = n_group, topk_group
        self.scoring, self.group_score_mode = scoring, group_score_mode
        E, h, m = num_experts, hidden_size, intermediate_size
        init = I.XavierNormal()
        self.gate = Parameter(init(next_key(), (h, E)))  # router, replicated
        self.w_gate = Parameter(init(next_key(), (E, h, m)),
                                partition=("ep", None, None))
        self.w_up = Parameter(init(next_key(), (E, h, m)),
                              partition=("ep", None, None))
        self.w_down = Parameter(init(next_key(), (E, m, h)),
                                partition=("ep", None, None))
        # loss-free balancing bias (buffer: updated outside the grad path)
        self.register_buffer("expert_bias", jnp.zeros((E,)), persistable=True)
        self.shared = None
        self.has_shared_gate = False
        if num_shared_experts:
            sm = shared_intermediate_size or m * num_shared_experts
            self.shared_gate_proj = Parameter(init(next_key(), (h, sm)))
            self.shared_up_proj = Parameter(init(next_key(), (h, sm)))
            self.shared_down_proj = Parameter(init(next_key(), (sm, h)))
            self.shared = True
            if use_shared_expert_gate:
                # Qwen2-MoE: the shared expert's output is scaled by a
                # learned sigmoid gate on the token
                self.shared_expert_gate = Parameter(
                    init(next_key(), (h, 1)))
                self.has_shared_gate = True

    def capacity(self, tokens: int) -> int:
        c = int(math.ceil(self.capacity_factor * tokens * self.top_k
                          / self.num_experts))
        return max(c, 4)

    def forward(self, x, return_aux: bool = False):
        orig_shape = x.shape
        h = self.hidden_size
        xt = x.reshape(-1, h)                          # [T, h]
        T = xt.shape[0]
        C = self.capacity(T)
        logits = xt.astype(jnp.float32) @ self.gate.astype(jnp.float32)
        dispatch, combine, aux = top_k_routing(
            logits, self.top_k, C, bias=self.expert_bias,
            norm_topk_prob=self.norm_topk_prob,
            n_group=self.n_group, topk_group=self.topk_group,
            scoring=self.scoring, group_score_mode=self.group_score_mode)
        # dispatch to expert buckets: [E, C, h], sharded over ep
        xe = jnp.einsum("tec,th->ech", dispatch.astype(x.dtype), xt)
        xe = constraint(xe, "ep", None, None)
        # per-expert SwiGLU, batched over E on the MXU
        g = jnp.einsum("ech,ehm->ecm", xe, self.w_gate)
        u = jnp.einsum("ech,ehm->ecm", xe, self.w_up)
        ye = jnp.einsum("ecm,emh->ech", F.silu(g) * u, self.w_down)
        ye = constraint(ye, "ep", None, None)
        y = jnp.einsum("tec,ech->th", combine.astype(x.dtype), ye)
        if self.routed_scaling_factor != 1.0:
            y = y * self.routed_scaling_factor
        if self.shared:
            sg = F.silu(xt @ self.shared_gate_proj) * (xt @ self.shared_up_proj)
            so = sg @ self.shared_down_proj
            if self.has_shared_gate:
                so = jax.nn.sigmoid(
                    xt.astype(jnp.float32) @
                    self.shared_expert_gate.astype(jnp.float32)
                ).astype(so.dtype) * so
            y = y + so
        y = y.reshape(orig_shape)
        if return_aux:
            return y, self.aux_loss_weight * aux
        return y

    def update_loss_free_bias(self, router_logits, lr: float = 1e-3):
        """DeepSeek-V3 loss-free balancing: nudge per-expert bias opposite
        to its load error (host-side, outside the gradient path). Uses
        the SAME selection path as dispatch (scoring/group limiting), so
        the measured load is the load the router actually produces."""
        _, ids = _select_topk(router_logits, self.top_k, self.expert_bias,
                              self.n_group, self.topk_group, self.scoring,
                              self.group_score_mode)
        load = jnp.mean(jax.nn.one_hot(ids, self.num_experts).sum(1), axis=0)
        err = load - self.top_k / self.num_experts
        self._buffers["expert_bias"] = self.expert_bias - lr * jnp.sign(err)
        return self.expert_bias


# ------------------------------------------------------------------ serving
# what an ExpertShareMLP counts inside a serving tick, in this order
# (experts_read: the held experts whose weights the forward READ, the
# kernel's list of experts hit by any row or, on the einsums, all held)
SERVING_COUNTERS = ("moe_layer_ticks", "moe_local_assignments",
                    "moe_experts_hit", "moe_experts_read")
# and, after those, where the router has zero-compute columns: the live
# rows' choices (rows x top_k, a layer and tick) and those of them that
# fell on a zero column
ZERO_COUNTERS = ("moe_live_choices", "moe_zero_choices")
# and, last, where the layer is asked to (``count_rows_routed``): the
# live rows of which at least one choice fell on a HELD expert, a layer
# and tick. Group-limited routing sends the others past this rank
# altogether: a grouped product over sorted tokens would skip them
ROUTED_COUNTERS = ("moe_rows_routed_here",)
_collecting = threading.local()     # engines trace on threads of their own


class _Counts:
    def __init__(self, rows):
        self.rows, self.total = rows, None

    def add(self, v):
        self.total = v if self.total is None else self.total + v


@contextlib.contextmanager
def collect_counts(rows):
    """Sum, over the expert layers traced inside the ``with``, their
    ``SERVING_COUNTERS`` of this forward, then the ``ZERO_COUNTERS`` and
    ``ROUTED_COUNTERS`` of layers that count them (``.total``: an int32
    vector, or None where no such layer ran). ``rows`` [b] says which
    rows of the batch are live; the others are computed and not
    counted."""
    prev = getattr(_collecting, "box", None)
    box = _collecting.box = _Counts(rows)
    try:
        yield box
    finally:
        _collecting.box = prev


class ExpertShareMLP(Layer):
    """``experts_held`` routed experts, ``first_expert`` onwards, of a
    layer of ``num_experts``, plus the shared experts: what one rank of
    an expert-parallel deployment holds. Parameter names are
    ``MoEMLP``'s; the router and its selection bias keep all
    ``num_experts`` columns, the stacked expert weights hold the share.

    ``routed`` is the sum over each token's chosen experts THAT ARE HELD,
    gated as the whole layer gates them (normalised over all ``top_k``
    chosen, held or not). What the absent experts add is left out:
    summed over the ranks' ``routed`` parts, plus ``shared_out`` once,
    it is the whole layer (tests/test_expert_share.py).

    ``zero_experts`` more router columns, after the experts', are
    LongCat-Flash's zero-compute experts of the identity kind: a choice
    that falls on one adds ``gate * x`` and reads no weight. In a
    deployment the token's own rank computes that part, so every rank
    computes ``zero_out`` alike and, like ``shared_out``, it counts
    once in the sum over shares."""

    def __init__(self, hidden_size: int, intermediate_size: int,
                 num_experts: int, top_k: int, first_expert: int,
                 experts_held: int, num_shared_experts: int = 0,
                 shared_intermediate_size: Optional[int] = None,
                 norm_topk_prob: bool = False,
                 routed_scaling_factor: float = 1.0,
                 n_group: int = 1, topk_group: int = 1,
                 scoring: str = "softmax",
                 group_score_mode: str = "max", zero_experts: int = 0,
                 count_rows_routed: bool = False, name=None):
        super().__init__(name)
        if not 0 <= first_expert <= num_experts - experts_held:
            raise ValueError(
                f"experts {first_expert}..{first_expert + experts_held - 1}"
                f" are not inside a layer of {num_experts}")
        self.hidden_size = hidden_size
        self.num_experts, self.top_k = num_experts, top_k
        self.first_expert, self.experts_held = first_expert, experts_held
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.n_group, self.topk_group = n_group, topk_group
        self.scoring, self.group_score_mode = scoring, group_score_mode
        self.zero_experts = zero_experts
        self.count_rows_routed = count_rows_routed
        E, n, h, m = num_experts + zero_experts, experts_held, \
            hidden_size, intermediate_size
        init = I.XavierNormal()
        self.gate = Parameter(init(next_key(), (h, E)))
        self.w_gate = Parameter(init(next_key(), (n, h, m)))
        self.w_up = Parameter(init(next_key(), (n, h, m)))
        self.w_down = Parameter(init(next_key(), (n, m, h)))
        # a parameter here (MoEMLP's buffer of the same name): serving
        # has no gradient path to keep it out of, and the served
        # weights are then ONE dict, selection bias included
        self.expert_bias = Parameter(jnp.zeros((E,)))
        self.shared = bool(num_shared_experts)
        if self.shared:
            sm = shared_intermediate_size or m * num_shared_experts
            self.shared_gate_proj = Parameter(init(next_key(), (h, sm)))
            self.shared_up_proj = Parameter(init(next_key(), (h, sm)))
            self.shared_down_proj = Parameter(init(next_key(), (sm, h)))

    def route(self, xt):
        """xt [T, h] -> (expert ids [T, k], gates [T, k] float32). The
        router runs in float32 at the highest matmul precision from the
        hidden state it is given: a token's 8th and 9th scores are often
        closer than a bfloat16 product resolves."""
        logits = jnp.dot(xt.astype(jnp.float32),
                         self.gate.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs, ids = _select_topk(
            logits, self.top_k, self.expert_bias.astype(jnp.float32),
            self.n_group, self.topk_group, self.scoring,
            self.group_score_mode)
        gates = jnp.take_along_axis(probs, ids, axis=-1)
        if self.norm_topk_prob:
            gates = gates / jnp.maximum(
                jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
        return ids, gates * self.routed_scaling_factor

    def routed(self, xt, ids, gates):
        """The held experts' part for tokens xt [T, h]. Where the
        kernels run, a forward of few tokens (``use_expert_kernel``: a
        tick's rows) puts every row through the experts that a row of it
        chose, and a forward of more (``use_grouped_kernel``: a prompt
        call's positions) sorts its (position, held expert) pairs by
        expert and multiplies those alone; either reads the weights of
        the experts hit and of no other. Any other forward multiplies
        every token through every held expert: the einsums, which are
        the definition. The same sum whichever way: every chosen held
        expert of every row, live or not."""
        held = jnp.arange(self.first_expert,
                          self.first_expert + self.experts_held)
        chose = ids[:, :, None] == held[None, None, :]       # [T, k, n]
        from ..ops.pallas import expert_mlp
        kernel = expert_mlp.use_expert_kernel(xt, self.w_gate)
        grouped = expert_mlp.use_grouped_kernel(xt, self.w_gate)
        if not grouped:
            w = jnp.sum(jnp.where(chose, gates[:, :, None], 0.0), axis=1)
        if kernel:
            order, read = expert_mlp.hit_list(jnp.any(chose, axis=(0, 1)))
        box = getattr(_collecting, "box", None)
        if box is not None:
            if grouped:     # it visits the experts that have a pair
                read = jnp.sum(jnp.any(chose, axis=(0, 1)), dtype=jnp.int32)
            elif not kernel:
                read = jnp.int32(self.experts_held)
            live = jnp.repeat(box.rows, xt.shape[0] // box.rows.shape[0])
            chose = chose & live[:, None, None]
            counts = [
                jnp.int32(1), jnp.sum(chose, dtype=jnp.int32),
                jnp.sum(jnp.any(chose, axis=(0, 1)), dtype=jnp.int32),
                read]
            if self.zero_experts:       # ZERO_COUNTERS
                counts += [
                    jnp.sum(live, dtype=jnp.int32) * self.top_k,
                    jnp.sum((ids >= self.num_experts) & live[:, None],
                            dtype=jnp.int32)]
            if self.count_rows_routed:  # ROUTED_COUNTERS
                counts.append(jnp.sum(jnp.any(chose, axis=(1, 2)),
                                      dtype=jnp.int32))
            box.add(jnp.stack(counts))
        if grouped:
            return expert_mlp.grouped_expert_mlp_pallas(
                xt, ids, gates, self.first_expert, self.w_gate, self.w_up,
                self.w_down)
        if kernel:
            return expert_mlp.expert_share_mlp_pallas(
                xt, w, order, read, self.w_gate, self.w_up, self.w_down)
        g = jnp.einsum("th,nhm->ntm", xt, self.w_gate)
        u = jnp.einsum("th,nhm->ntm", xt, self.w_up)
        a = (F.silu(g) * u).astype(jnp.float32) * w.T[:, :, None]
        return jnp.einsum("ntm,nmh->th", a.astype(xt.dtype), self.w_down)

    def shared_out(self, xt):
        sg = F.silu(xt @ self.shared_gate_proj) * (xt @ self.shared_up_proj)
        return sg @ self.shared_down_proj

    def zero_out(self, xt, ids, gates):
        """The identity experts' part: each token times the sum of its
        gates that fell on a zero column."""
        w = jnp.sum(jnp.where(ids >= self.num_experts, gates, 0.0), axis=-1)
        return (xt.astype(jnp.float32) * w[:, None]).astype(xt.dtype)

    def forward(self, x):
        # the scopes are obs.TICK_SCOPES
        xt = x.reshape(-1, self.hidden_size)
        with jax.named_scope("router"):
            ids, gates = self.route(xt)
        with jax.named_scope("experts"):
            y = self.routed(xt, ids, gates)
        if self.zero_experts:
            with jax.named_scope("zero_experts"):
                y = y + self.zero_out(xt, ids, gates)
        if self.shared:
            with jax.named_scope("shared_expert"):
                y = y + self.shared_out(xt)
        return y.reshape(x.shape)
