"""The reduction from a device trace to numbers, on a recorded trace:
three decode ticks of ``qwen2-7b-d16.batch-decode`` on a TPU v5e (PR 23;
cut by ``make_trace_fixture.py``)."""
import os

import pytest

from benchmarks.harness import peaks, readers, trace
from benchmarks.tests import tiny

FIXTURE = os.path.join(tiny.DATA, "v5e_ticks.xplane.pb")


def test_union_counts_overlapping_intervals_once():
    ev = [("a", 0.0, 2.0), ("b", 1.0, 2.0), ("c", 5.0, 1.0), ("d", 5.2, 0.1)]
    assert trace.union_seconds(ev) == pytest.approx(4.0)
    assert trace.union_seconds([]) == 0.0


def test_names_are_cut_to_what_stays_the_same():
    assert trace.module_name("jit__fused_tick_greedy(116863208895)") == \
        "_fused_tick_greedy"
    raw = ('%_fused_tick_greedy.29 = bf16[8,4,8,128]{3,2,1,0:T(8,128)} '
           'custom-call(s32[8,128]{1,0} %fusion.5), '
           'custom_call_target="tpu_custom_call"')
    assert trace.is_kernel(raw)
    assert trace.op_key(raw) == "pallas_kernel bf16[8,4,8,128]"
    assert trace.op_key("%fusion.606 = s32[1024]{0:T(1024)S(1)} "
                        "fusion(s32[8]{0} %p), kind=kLoop") == \
        "fusion s32[1024]"
    assert trace.op_key("%copy-start.28 = (bf16[3584]{0}, bf16[3584]{0}, "
                        "u32[]{:S(2)}) copy-start(bf16[3584]{0} %p)") == \
        "copy-start bf16[3584]"


def test_the_recorded_ticks_reduce_to_their_numbers():
    r = trace.reduce_trace(FIXTURE)
    assert r["chips"] == 1
    assert r["modules"] == {"_fused_tick_greedy": {
        "n": 3, "s": pytest.approx(0.07097, abs=1e-4)}}
    # three ticks of 23.65 ms in an 80.7 ms span: the gaps between them
    # are the host's
    assert r["window_s"] == pytest.approx(0.08071, abs=1e-4)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] == pytest.approx(0.07096, abs=1e-4)
    assert r["idle_gaps"] == [["_fused_tick_greedy -> _fused_tick_greedy",
                               pytest.approx(0.009744, abs=1e-5)]]
    # one Pallas kernel a layer, 16 layers, three ticks: 0.6 ms a call
    k = r["tick_kernels"]
    assert k["n"] == 48 and k["s"] / k["n"] == pytest.approx(6.0e-4, rel=0.1)
    assert len(r["device_ops"]) == 10
    assert r["device_ops"][0][0] == "pallas_kernel bf16[8,4,8,128]"
    assert all(len(name) < 80 for name, _ in r["device_ops"])

    src = {"trace": r, "device_kind": "TPU v5 lite",
           "config": {"hidden_size": 3584, "intermediate_size": 18944,
                      "num_attention_heads": 28, "num_key_value_heads": 4,
                      "num_hidden_layers": 16, "vocab_size": 152064,
                      "dtype": "bfloat16"},
           "trace_times": {"ta": 0.0, "tb": 1.0},
           # 8 rows at 300 tokens of context, a token each tick
           "records": [{"prompt": [0] * 299, "token_times": [-1.0, 0.1,
                                                             0.2, 0.3]}] * 8}
    assert readers.tick_device_ms(src) == pytest.approx(23.66, abs=0.05)
    assert readers.device_idle_share(src) == pytest.approx(12.1, abs=0.2)
    # 8.55 GB of weights + 8 x 300 x 32 KiB of K/V over 819 GB/s is
    # 10.5 ms of a 23.66 ms tick
    assert readers.tick_membw_roofline(src) == pytest.approx(44.5, abs=0.5)
    # the kernel: 79 MB of K/V a tick is 0.1 ms against 9.6 ms
    assert readers.ragged_attn_roofline(src) == pytest.approx(1.0, abs=0.1)
    assert readers.prefill_device_share(src) == 0.0
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9")


def test_a_trace_without_a_device_plane_is_refused(tmp_path):
    p = tmp_path / "empty.xplane.pb"
    p.write_bytes(b"")
    with pytest.raises(ValueError):
        trace.reduce_trace(str(p))
