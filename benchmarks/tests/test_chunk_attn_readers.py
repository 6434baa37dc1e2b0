"""The readers of a prompt chunk's attention over its row's pages
(``harness/readers_chunk_attn.py``) on synthetic sources with known
totals, and the cases in which they must report nothing."""
import os

import pytest

from benchmarks.harness import cell
from benchmarks.tests import tiny

BENCH = os.path.join(tiny.ROOT, "benchmarks")


def reader(name):
    return cell.load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"),
        "t_chunk_attn_" + name.replace(".", "_"))


def sources(layers=9, kernel=9, calls=40, spans=None):
    """A window of ``calls`` prompt chunks with cached context behind
    them over ``layers`` K/V layers, ``kernel`` of which took the
    kernel; the counters start far from 0. ``spans``: what
    ``chunk_spans_of`` found in the trace."""
    w0 = {"chunk_attn_layer_calls": 900, "chunk_attn_kernel_calls": 600,
          "prefill_chunks": 100}
    w1 = {"chunk_attn_layer_calls": 900 + layers * calls,
          "chunk_attn_kernel_calls": 600 + kernel * calls,
          "prefill_chunks": 100 + calls + 8}
    return {"snaps": {"w0": {"engines": [w0]}, "w1": {"engines": [w1]}},
            "_chunk_spans": spans}


@pytest.mark.parametrize("kernel,want", [(9, 100.0), (0, 0.0), (3, 100 / 3)])
def test_the_share_is_the_kernels_layers_of_all(kernel, want):
    got = reader("chunk_attn_kernel_share.sat").reduce(
        sources(kernel=kernel))
    assert got == pytest.approx(want)


def test_two_replicas_counters_add_up():
    src = sources()
    for snap in src["snaps"].values():
        snap["engines"] = snap["engines"] * 2
    src["snaps"]["w1"]["engines"][1] = dict(
        src["snaps"]["w1"]["engines"][1], chunk_attn_kernel_calls=600)
    assert reader("chunk_attn_kernel_share.sat").reduce(src) \
        == pytest.approx(50.0)


def test_a_window_without_such_a_call_reports_no_share():
    assert reader("chunk_attn_kernel_share.sat").reduce(
        sources(calls=0)) is None


def test_the_parents_engine_reports_no_share():
    src = sources()
    for snap in src["snaps"].values():
        for e in snap["engines"]:
            del e["chunk_attn_kernel_calls"], e["chunk_attn_layer_calls"]
    assert reader("chunk_attn_kernel_share.sat").reduce(src) is None


def test_the_window_layers_ms_is_the_scopes_time_over_the_calls():
    spans = {"calls": 4, "by_scope": {"chunk_attn_window": 12e-3,
                                      "chunk_attn": 8e-3, "experts": 16e-3}}
    assert reader("chunk_window_attn_ms.sat").reduce(
        sources(spans=spans)) == pytest.approx(3.0)


@pytest.mark.parametrize("spans", [
    None,                                               # no trace
    {"calls": 0, "by_scope": {}},                       # no prompt call
    {"calls": 3, "by_scope": {"chunk_attn": 8e-3}},     # no band layer
], ids=["untraced", "no-prompt-call", "no-window-layer"])
def test_without_the_scope_in_the_span_the_ms_reports_nothing(spans):
    assert reader("chunk_window_attn_ms.sat").reduce(
        sources(spans=spans)) is None
