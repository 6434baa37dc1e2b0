"""The seam between the models and the paged cache (ISSUE 45).

- WHICH CALL THIS IS rides on the view (``PagedKV.call``,
  ``SlotState.call``), not down the models' signatures: no ``forward``
  of a served family (nor the stub's ``fn``) takes ``paged_chunk`` or
  ``paged_decode``, and nothing under ``paddle_tpu/models`` or
  ``paddle_tpu/ops`` imports the engine's package.
- ``write_and_attend`` is the ONE write-then-attend over a K/V view:
  in each of the four calls it equals, bit for bit, the write and the
  attention composed by hand, with and without a window.
- ``call`` is structure: a view that crosses ``jax.checkpoint`` or
  ``jax.lax.scan`` keeps it a Python string.
"""
import ast
import importlib
import inspect
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.attention import dense_attention
from paddle_tpu.ops.paged_cache import (PagedKV, SlotState,
                                        paged_chunk_attention,
                                        paged_decode_attention,
                                        paged_decode_write,
                                        paged_packed_attention,
                                        paged_prefill_write,
                                        write_and_attend)

PKG = pathlib.Path(__file__).resolve().parents[1] / "paddle_tpu"
SERVED = ("llama", "deepseek_v2", "longcat_flash", "mimo_v2",
          "olmo_hybrid", "ling_hybrid")
FLAGS = {"paged_chunk", "paged_decode"}


# ------------------------------------------------------------ signatures
@pytest.mark.parametrize("module", SERVED + ("stub",))
def test_no_forward_is_told_the_call_by_a_flag(module):
    if module == "stub":
        from paddle_tpu.generation.stub import TickStubModel
        takes = {"TickStubModel.fn": TickStubModel().functional()[0]}
    else:
        mod = importlib.import_module(f"paddle_tpu.models.{module}")
        takes = {f"{name}.{fn}": getattr(cls, fn)
                 for name, cls in vars(mod).items()
                 if inspect.isclass(cls) and cls.__module__ == mod.__name__
                 for fn in ("forward", "attend", "_paged")
                 if fn in vars(cls)}
        assert any(name.endswith("ForCausalLM.forward") for name in takes)
    told = {name: sorted(FLAGS & set(inspect.signature(fn).parameters))
            for name, fn in takes.items()}
    assert not {name: flags for name, flags in told.items() if flags}


def _imports(path):
    """The absolute module every import statement of ``path`` names."""
    package = ("paddle_tpu",) + path.relative_to(PKG).parts[:-1]
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            yield from (alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level \
                else ()
            stem = ".".join(base + ((node.module,) if node.module else ()))
            yield from (f"{stem}.{alias.name}" for alias in node.names)


def test_models_and_ops_import_nothing_of_the_engines_package():
    """The layers run serving -> engine -> model -> ops -> kernels. The
    one call upward that stays is ``CausalLMBase.generate``, the public
    ``model.generate(...)``, which hands the model to
    ``paddle_tpu.generation.generate``."""
    upward = sorted(
        (str(path.relative_to(PKG)), name)
        for sub in ("models", "ops") for path in (PKG / sub).rglob("*.py")
        for name in _imports(path)
        if name.startswith("paddle_tpu.generation"))
    assert upward == [("models/base.py", "paddle_tpu.generation.generate")]


# ------------------------------------------------- the one write-and-attend
B, M, P, KVH, H, D = 4, 4, 16, 2, 4, 8


def _case(call, seed=0):
    """(view, q, k, v, positions, segment_ids) of a small call of each
    kind over pools that already hold something."""
    rs = np.random.RandomState(seed)
    pools = [jnp.asarray(rs.randn(P, B, KVH * D), jnp.float32)
             for _ in range(2)]

    def rows(b, s):
        return (jnp.asarray(rs.randn(b, s, H, D), jnp.float32),
                jnp.asarray(rs.randn(b, s, KVH, D), jnp.float32),
                jnp.asarray(rs.randn(b, s, KVH, D), jnp.float32))
    if call == "decode":            # three rows, two positions a row
        tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0], [7, 8, 9, 0]])
        lens = jnp.asarray([9, 3, 6], jnp.int32)
        positions = lens[:, None] + jnp.arange(2)[None, :]
        return (PagedKV(*pools, tables, lens, KVH, False, call),
                *rows(3, 2), positions, None)
    if call == "packed":            # prompts of 3 and 4 tokens and a pad
        tables = jnp.asarray([[1, 0, 0, 0], [2, 3, 0, 0]])
        lens = jnp.asarray([3, 4], jnp.int32)
        seg = jnp.asarray([[0, 0, 0, 1, 1, 1, 1, 1]])
        positions = jnp.asarray([[0, 1, 2, 0, 1, 2, 3, 4]])
        return (PagedKV(*pools, tables, lens, KVH, False, call),
                *rows(1, 8), positions, seg)
    tables = jnp.asarray([[4, 5, 6, 0]])
    if call == "chunk":             # 8 positions behind 5 cached, 6 live
        lens, positions = jnp.asarray([11], jnp.int32), 5 + jnp.arange(8)
    else:                           # a whole prompt of 5 in a bucket of 8
        lens, positions = jnp.asarray([5], jnp.int32), jnp.arange(8)
    return (PagedKV(*pools, tables, lens, KVH, False, call), *rows(1, 8),
            positions[None], None)


def _by_hand(pk, q, k, v, positions, segment_ids, window):
    """The write and the attention of each call, one after the other."""
    if pk.call == "decode":
        new = paged_decode_write(pk, k, v)
        return paged_decode_attention(q, new, window=window), new
    if pk.call == "packed":
        new = paged_prefill_write(pk, k, v, positions=positions[0],
                                  segments=segment_ids[0])
        return paged_packed_attention(q, k.astype(pk.kp.dtype),
                                      v.astype(pk.vp.dtype), segment_ids,
                                      window=window), new
    if pk.call == "chunk":
        new = paged_prefill_write(pk, k, v, positions=positions[0])
        return paged_chunk_attention(q, new, positions, window=window), new
    new = paged_prefill_write(pk, k, v)
    return dense_attention(q, k, v, causal=True, window=window), new


@pytest.mark.parametrize("window", [None, 3])
@pytest.mark.parametrize("call", ["decode", "packed", "chunk", "prompt"])
def test_write_and_attend_is_the_two_helpers_composed(call, window):
    pk, q, k, v, positions, seg = _case(call)
    out, new = write_and_attend(pk, q, k, v, positions, seg, window=window)
    want, want_view = _by_hand(pk, q, k, v, positions, seg, window)
    assert new.call == call and out.shape == q.shape
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    for got, held, before in zip(new.pool, want_view.pool, pk.pool):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(held))
        assert not np.array_equal(np.asarray(got), np.asarray(before))
    # the view's call decides and nothing else does: the same rows as
    # the other lone-prompt call give another result
    if call in ("chunk", "prompt"):
        other, _ = write_and_attend(
            pk._replace(call="prompt" if call == "chunk" else "chunk"),
            q, k, v, positions, seg, window=window)
        assert not np.array_equal(np.asarray(other), np.asarray(out))


def test_a_delta_mixer_refuses_decode_rows_of_several_positions():
    """A speculative verify's rows cannot be taken back out of a
    recurrent state: asked of the view's call, not of a flag."""
    from paddle_tpu.models.olmo_hybrid import GatedDeltaNet, olmo_hybrid_tiny
    cfg = olmo_hybrid_tiny()
    mixer = GatedDeltaNet(cfg)
    view = SlotState(tuple(jnp.zeros((2,) + tuple(shape), dtype)
                           for shape, dtype in mixer.state_arrays()),
                     seq_lens=jnp.zeros((2,), jnp.int32),
                     live=jnp.ones((2,), bool), call="decode")
    x = jnp.zeros((2, 2, cfg.hidden_size), cfg.dtype)
    with pytest.raises(NotImplementedError, match="multi-position"):
        mixer(x, jnp.zeros((2, 2), jnp.int32), kv_cache=view)
    out, new = mixer(x[:, :1], jnp.zeros((2, 1), jnp.int32), kv_cache=view)
    assert out.shape == (2, 1, cfg.hidden_size) and new.call == "decode"


# ------------------------------------------------------- call is structure
def _views():
    return {
        "PagedKV": PagedKV(jnp.zeros((4, 2, 8)), jnp.zeros((4, 2, 8)),
                           jnp.zeros((1, 2), jnp.int32),
                           jnp.zeros((1,), jnp.int32), 1, False, "chunk"),
        "SlotState": SlotState((jnp.zeros((2, 3)), jnp.ones((2, 4))),
                               seq_lens=jnp.arange(2),
                               fresh=jnp.ones((2,), bool), call="packed"),
    }


@pytest.mark.parametrize("transform", ["checkpoint", "scan"])
@pytest.mark.parametrize("kind", ["PagedKV", "SlotState"])
def test_call_crosses_a_transform_as_a_python_string(kind, transform):
    view = _views()[kind]
    inside = []

    def layer(v):
        inside.append(v.call)
        return jax.tree_util.tree_map(lambda a: ~a if a.dtype == bool
                                      else a + 1, v)
    if transform == "checkpoint":
        out = jax.checkpoint(layer)(view)
    else:
        out, _ = jax.lax.scan(lambda v, _: (layer(v), None), view, None,
                              length=2)
    assert inside and all(type(c) is str and c == view.call for c in inside)
    assert type(out) is type(view) and out.call == view.call
    # four arrays each: ``call`` (and ``heads``, ``ring``) is no leaf
    assert len(jax.tree_util.tree_leaves(view)) == 4
