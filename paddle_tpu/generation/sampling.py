"""Logits processors for autoregressive decoding (reference: PaddleNLP
paddlenlp/generation/logits_process.py — TopKProcess, TopPProcess,
temperature, repetition penalty).

All processors are pure jnp on static shapes so the whole decode loop
compiles into one XLA program (`lax.while_loop`), never re-tracing per
token. Filtering uses mask-to--inf (no dynamic shapes from sorting)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def apply_temperature(logits, temperature):
    t = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-6)
    return logits / t


def top_k_filter(logits, k: int):
    """Keep the k highest logits per row; mask the rest to -inf. Static k."""
    if k <= 0:
        return logits
    kth = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, NEG_INF, logits)


def top_p_filter(logits, p: float):
    """Nucleus sampling: keep the smallest prefix of the sorted distribution
    with cumulative prob >= p (always keeps the argmax)."""
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # mask sorted positions whose *previous* cumulative already reached p
    keep_sorted = (cum - probs) < p
    # threshold = smallest kept logit
    thresh = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf),
                     axis=-1, keepdims=True)
    return jnp.where(logits < thresh, NEG_INF, logits)


def repetition_penalty(logits, generated_mask, penalty: float):
    """Divide (positive) / multiply (negative) logits of seen tokens
    (generated_mask [b, vocab] counts>0)."""
    if penalty == 1.0:
        return logits
    seen = generated_mask > 0
    penalized = jnp.where(logits > 0, logits / penalty, logits * penalty)
    return jnp.where(seen, penalized, logits)


def filter_logits_rows(logits, temperature, top_k, top_p):
    """Per-row temperature / top-k / top-p filtering on [R, V] fp32
    logits with TRACED per-row params (k <= 0 / p >= 1 disable) —
    the processor half of :func:`sample_token_rows`, factored out so
    the rejection-sampled speculative verify
    (:func:`residual_resample_rows`) filters each verify position with
    EXACTLY the ops the plain sampled tick uses. Returns the filtered
    logits (kept entries divided by temperature, rest NEG_INF)."""
    raw = logits.astype(jnp.float32)
    V = raw.shape[-1]
    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    lt = raw / jnp.maximum(temperature, 1e-6)[:, None]
    # per-row top-k: k-th largest value as threshold (k <= 0: keep all)
    sd = jnp.sort(lt, axis=-1)[..., ::-1]
    kth = jnp.take_along_axis(
        sd, jnp.clip(top_k - 1, 0, V - 1)[:, None], axis=-1)
    lt = jnp.where((top_k[:, None] > 0) & (lt < kth), NEG_INF, lt)
    # the top-k-filtered logits in sorted order, derived from the ONE
    # sort: rank >= k is masked (ties at the k-th value are all kept by
    # the filter above but counted once in the top-p cumsum)
    rank = jnp.arange(V)[None, :]
    sd2 = jnp.where((top_k[:, None] <= 0) | (rank < top_k[:, None]),
                    sd, NEG_INF)
    probs = jax.nn.softmax(sd2, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (cum - probs) < top_p[:, None]   # always keeps argmax
    thresh = jnp.min(jnp.where(keep_sorted, sd2, jnp.inf), axis=-1,
                     keepdims=True)
    return jnp.where((top_p[:, None] < 1.0) & (lt < thresh), NEG_INF, lt)


def sample_token_rows(logits, keys, temperature, top_k, top_p):
    """Per-ROW sampling for continuous batching: every parameter is an
    array over rows, so one jitted decode step serves a mixed stream of
    greedy and sampled requests (reference: PaddleNLP llm predictor's
    per-request sampling config).

    logits [R, V] (raw); keys [R, 2] uint32 per-row PRNG states;
    temperature [R] f32 (<= 0 means greedy — BIT-exact argmax of the raw
    fp32 logits, the same op the all-greedy step used); top_k [R] i32
    (<= 0 disables); top_p [R] f32 (>= 1 disables). Unlike the static
    processors above, k and p are traced values: top-k thresholds via
    take_along_axis on the sorted row, not lax.top_k.

    Returns (tokens [R] i32, logprobs [R] f32, new_keys [R, 2]).
    Logprobs are of the CHOSEN token under the unfiltered softmax (what
    serving APIs report), greedy rows included."""
    with jax.named_scope("sample"):     # obs.TICK_SCOPES
        raw = logits.astype(jnp.float32)
        temperature = jnp.asarray(temperature, jnp.float32)
        lt = filter_logits_rows(raw, temperature, top_k, top_p)

        keys = jnp.asarray(keys, jnp.uint32)
        pairs = jax.vmap(lambda k: jax.random.split(
            jax.random.wrap_key_data(k, impl="threefry2x32")))(keys)
        carry = jax.vmap(jax.random.key_data)(pairs[:, 0])
        sampled = jax.vmap(
            lambda k, l: jax.random.categorical(k, l))(pairs[:, 1], lt)
        tokens = jnp.where(temperature <= 0.0, jnp.argmax(raw, axis=-1),
                           sampled).astype(jnp.int32)
        logprobs = jnp.take_along_axis(jax.nn.log_softmax(raw, axis=-1),
                                       tokens[:, None].astype(jnp.int32),
                                       axis=-1)[:, 0]
    return tokens, logprobs, carry


def sample_token_segments(logits, keys, temperature, top_k, top_p, live):
    """:func:`sample_token_rows` for the rows of a packed prefill call,
    of which few are live and fewer sample: the same tokens, logprobs
    and key chain, but the filter (a sort of the whole row) and the draw
    run one row at a time under a ``lax.cond``, so what a call pays
    follows its live sampled rows and not their number (``live`` [R]
    bool; a dead or greedy row's ``sampled`` entry is never looked
    at). Greedy rows are the argmax of the raw fp32 row, bit-exact."""
    with jax.named_scope("sample"):     # obs.TICK_SCOPES
        raw = logits.astype(jnp.float32)
        temperature = jnp.asarray(temperature, jnp.float32)
        carry, sub = split_key_rows(keys)

        def draw(row):
            lt = filter_logits_rows(*(x[None] for x in row[:4]))[0]
            return jax.random.categorical(
                jax.random.wrap_key_data(row[4], impl="threefry2x32"),
                lt).astype(jnp.int32)

        sampled = jax.lax.map(
            lambda row: jax.lax.cond(row[-1], draw,
                                     lambda row: jnp.int32(0), row[:-1]),
            (raw, temperature, jnp.asarray(top_k, jnp.int32),
             jnp.asarray(top_p, jnp.float32), sub,
             live & (temperature > 0.0)))
        tokens = jnp.where(temperature <= 0.0, jnp.argmax(raw, axis=-1),
                           sampled).astype(jnp.int32)
        logprobs = jnp.take_along_axis(jax.nn.log_softmax(raw, axis=-1),
                                       tokens[:, None], axis=-1)[:, 0]
    return tokens, logprobs, carry


def seed_key_row(seed: int):
    """The [2] uint32 raw key data for ONE row's PRNG stream, seeded by
    ``seed`` — the row-scoped key init shared by ``PagedEngine.submit``
    and the delta-transition descriptor packing (ISSUE 14): an admitted
    row's device key is byte-identical whether it rides a full mirror
    rebuild or a one-row patch, because both start from this value."""
    import numpy as np
    return np.asarray(jax.random.key_data(jax.random.PRNGKey(seed)),
                      np.uint32)


def override_key_rows(keys, rows, new_keys, flags):
    """Scatter per-row PRNG key OVERRIDES into the [R, 2] uint32 key
    state: row ``rows[j]`` takes ``new_keys[j]`` iff ``flags[j] != 0``;
    every other row keeps its current (device) stream untouched. The
    key-override rule of the delta-transition descriptors (ISSUE 14),
    shared by the one-row patch program and the fused patch-queue
    scatter (ISSUE 19) so the two transition paths cannot drift: a key
    is authoritative only when the HOST re-keyed the row (fresh admit,
    chunk-final) — for every other descriptor the device key stream,
    possibly advanced by sampled ticks since the last upload, must
    survive the patch. Non-override (and out-of-range padding) rows
    are routed to the out-of-bounds index R and dropped by the
    scatter, which also makes the all-masked case a bitwise no-op —
    the property that lets the fused scatter ride EVERY tick."""
    keys = jnp.asarray(keys, jnp.uint32)
    R = keys.shape[0]
    target = jnp.where(jnp.asarray(flags) != 0,
                       jnp.asarray(rows, jnp.int32), R)
    return keys.at[target].set(jnp.asarray(new_keys, jnp.uint32),
                               mode="drop")


def split_key_rows(keys):
    """Advance [R, 2] uint32 per-row PRNG states one split: returns
    (carry [R, 2], sub [R, 2]) raw key data. The carry chain is the
    same one :func:`sample_token_rows` advances — one split per tick —
    so a rejection-sampled speculative tick consumes the row stream at
    the same rate as the plain sampled tick."""
    pairs = jax.vmap(lambda k: jax.random.split(
        jax.random.wrap_key_data(k, impl="threefry2x32")))(
        jnp.asarray(keys, jnp.uint32))
    carry = jax.vmap(jax.random.key_data)(pairs[:, 0])
    sub = jax.vmap(jax.random.key_data)(pairs[:, 1])
    return carry, sub


def fold_in_rows(keys, j):
    """fold_in over [R, 2] raw key data: the per-position subkey
    derivation of the rejection-sampled verify (position j of a tick's
    sub key)."""
    return jax.vmap(lambda k: jax.random.key_data(jax.random.fold_in(
        jax.random.wrap_key_data(k, impl="threefry2x32"), j)))(
        jnp.asarray(keys, jnp.uint32))


def residual_resample_rows(logits, draft, keys, temperature, top_k,
                           top_p):
    """ONE verify position of rejection-sampled speculative decoding
    with a DETERMINISTIC (one-hot) draft distribution, row-batched
    (Leviathan et al. 2023, specialized: the draft proposes token d
    with probability 1, so accept happens with prob p(d) and the
    residual norm(max(0, p - q)) is p with d removed, renormalized).

    logits [R, V] fp32 — the SAME (penalty-applied, unfiltered) logits
    the plain tick would hand to :func:`sample_token_rows`; draft [R]
    i32 proposed token ids (< 0 = no draft for this row/position: the
    accept test always fails and the residual is the full filtered
    distribution — i.e. a plain sample); keys [R, 2] uint32
    PER-POSITION subkeys (callers fold the row's tick key by position,
    :func:`fold_in_rows`); temperature/top_k/top_p as
    :func:`sample_token_rows`. Rows with temperature <= 0 are greedy:
    token = argmax(logits), accepted = (token == draft) — exactly the
    longest-argmax-prefix rule the greedy speculative tick pins
    bitwise, no RNG consumed.

    Returns (tokens [R] i32, accepted [R] bool, logprobs [R] f32 of
    the chosen token under the unfiltered softmax of ``logits``).

    Distribution preservation (the reason sampled rows may ride
    speculative ticks at all): with p the filtered per-row
    distribution and q = onehot(d),
    P(emit y) = p(d)·[y==d] + (1-p(d)) · p(y)·[y!=d] / (1-p(d)) = p(y)
    — every position's marginal equals the plain tick's, whatever the
    drafter proposed (pinned statistically in tests/test_ring_spec.py).
    """
    raw = logits.astype(jnp.float32)
    R, V = raw.shape
    temperature = jnp.asarray(temperature, jnp.float32)
    d = jnp.asarray(draft, jnp.int32)
    dc = jnp.clip(d, 0, V - 1)
    has = d >= 0
    lt = filter_logits_rows(raw, temperature, top_k, top_p)
    keys = jnp.asarray(keys, jnp.uint32)
    pairs = jax.vmap(lambda k: jax.random.split(
        jax.random.wrap_key_data(k, impl="threefry2x32")))(keys)
    # accept test: u < p(draft) under the FILTERED distribution
    u = jax.vmap(lambda k: jax.random.uniform(k))(pairs[:, 0])
    p_d = jnp.take_along_axis(jax.nn.softmax(lt, axis=-1),
                              dc[:, None], axis=-1)[:, 0]
    acc_s = has & (u < p_d)
    # residual: mask the draft token to -inf; categorical renormalizes
    lt_res = jnp.where((jnp.arange(V)[None, :] == dc[:, None])
                       & has[:, None], NEG_INF, lt)
    res = jax.vmap(lambda k, l: jax.random.categorical(k, l))(
        pairs[:, 1], lt_res)
    samp = jnp.where(acc_s, dc, res).astype(jnp.int32)
    g = jnp.argmax(raw, axis=-1).astype(jnp.int32)
    greedy = temperature <= 0.0
    tokens = jnp.where(greedy, g, samp)
    accepted = jnp.where(greedy, has & (g == d), acc_s)
    logprobs = jnp.take_along_axis(jax.nn.log_softmax(raw, axis=-1),
                                   tokens[:, None], axis=-1)[:, 0]
    return tokens, accepted, logprobs


def sample_token(logits, key, temperature=1.0, top_k=0, top_p=1.0,
                 do_sample=True):
    """logits [b, vocab] -> token ids [b]."""
    logits = logits.astype(jnp.float32)
    if not do_sample:
        return jnp.argmax(logits, axis=-1)
    logits = apply_temperature(logits, temperature)
    if top_k and top_k > 0:
        logits = top_k_filter(logits, top_k)
    if top_p < 1.0:
        logits = top_p_filter(logits, top_p)
    return jax.random.categorical(key, logits, axis=-1)


def suffix_window_hits(seq, cur, g):
    """[L] bool: window ``seq[p : p+g]`` equals the last ``g`` committed
    tokens ``seq[cur-g : cur]``, restricted to windows STRICTLY earlier
    than that suffix. Shared match kernel for n-gram drafting
    (speculative prompt-lookup) and no-repeat-ngram banning — O(L*g)
    integer compares on static shapes. ``g == 0`` matches every
    committed position (the degenerate 1-gram case)."""
    L = seq.shape[0]
    last = jax.lax.dynamic_slice(seq, (jnp.maximum(cur - g, 0),), (g,))
    starts = jnp.arange(L)
    win = seq[jnp.clip(starts[:, None] + jnp.arange(g)[None, :],
                       0, L - 1)]                           # [L, g]
    hit = jnp.all(win == last[None, :], axis=1)
    return hit & (starts <= cur - g - 1) & (cur >= g)


def repetition_penalty_rows(logits, seen, penalties):
    """Per-ROW repetition penalty for continuous batching: logits
    [R, V], seen [R, V] bool membership of each row's running sequence,
    penalties [R] (1.0 = off). Rows at 1.0 pass through BIT-exactly
    (jnp.where with a false mask), preserving the engine's greedy
    exactness guarantee."""
    with jax.named_scope("penalty"):    # obs.TICK_SCOPES
        p = jnp.asarray(penalties, jnp.float32)[:, None]
        pen = jnp.where(logits > 0, logits / p, logits * p)
        return jnp.where(seen & (p != 1.0), pen, logits)
