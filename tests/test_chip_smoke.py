"""ISSUE 21: ``chip_smoke.py`` — the chip-side proof that the serving
path starts — kept honest on the CPU.

- the smoke's own build / serve / verify functions run end to end at
  ``qwen2_tiny`` with the Pallas kernels in interpret mode (an explicit
  test-only choice: ``chip_smoke.main`` refuses interpret mode);
- ``main()`` refuses a CPU backend, and interpret mode, with a non-zero
  exit and no result line; a run that passed ends stdout with exactly
  the result object the accelerator check reads;
- on the 8-virtual-device CPU platform four engines land on four
  distinct devices, and a supervisor rebuild lands on its
  predecessor's;
- the compile-cache resolver: ``$JAX_COMPILATION_CACHE_DIR`` over every
  argument, else the fixed in-checkout path.
"""
import os
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from paddle_tpu.models.qwen2 import qwen2_tiny  # noqa: E402
from paddle_tpu.utils import compile_cache  # noqa: E402

TINY_GEOMETRY = dict(max_slots=4, block_size=8, max_blocks_per_seq=16,
                     num_blocks=65, chunk_prefill_tokens=16,
                     enable_prefix_cache=True)


def test_smoke_at_tiny_size_and_four_engines_on_four_devices(monkeypatch):
    """Build, serve over real HTTP, verify — the same functions the chip
    runs, at a size the CPU can hold, kernels in interpret mode, on a
    device that is NOT the process default. float32 weights, so the
    engine and the plain reference forward agree far inside the chip's
    bf16 tolerance. Then four engines on four virtual devices: each
    lives where its weights live, and supervisor rebuilds (factory or
    in place) stay on their predecessor's device."""
    import paddle_tpu as pt
    from paddle_tpu.generation.paged import PagedEngine
    from paddle_tpu.models.qwen2 import Qwen2ForCausalLM
    from paddle_tpu.serving import Gateway
    from paddle_tpu.utils import observability as obs
    # the smoke reads process-wide totals ("failovers are 0") as a fresh
    # process has them; whichever test files ran before in this worker
    # (the order follows the set of files) left theirs in the registry
    obs.reset()
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    cfg = qwen2_tiny(num_hidden_layers=1)
    devices = jax.devices()[:4]
    warmed = chip_smoke.build_engines(cfg, TINY_GEOMETRY, devices[1:2])
    # jit keys its caches on the thread-local default device; the
    # engine pins that to its own device on every entry, so the
    # builder thread's scope and the gateway tick thread's lack of one
    # share ONE trace of each program
    jits = (warmed[0]._chunk_jit, warmed[0]._tick_greedy_jit,
            warmed[0]._tick_jit)
    # two chunk programs: the packed call from position 0 and the
    # continuation that runs alone
    assert [j._cache_size() for j in jits] == [2, 1, 1]
    facts = chip_smoke.serve_and_verify(cfg, TINY_GEOMETRY, warmed,
                                        devices[1:2], atol=1e-3)
    assert facts["decode_route"] == "ragged"
    assert facts["requests"] == 6
    assert facts["tokens_per_replica"] == [32 + 24 + 24 + 32 + 24 + 24]
    # prefix-b adopts both shared chunks of prefix-a
    assert facts["prefix_hit_tokens"] == 32
    assert facts["max_logprob_diff"] < 1e-3
    assert [j._cache_size() for j in jits] == [2, 1, 1]
    with pytest.raises(chip_smoke.SmokeFailure, match="replica 0: params"):
        chip_smoke.check_placement(warmed, devices[:1])

    def cold_engine():
        # what an ``engine_factory`` does: it names no device
        pt.seed(chip_smoke.SEED)
        return PagedEngine(Qwen2ForCausalLM(cfg), **TINY_GEOMETRY)

    def cold_engine_on(device):
        with jax.default_device(device):
            return cold_engine()

    engines = [cold_engine_on(devices[0]), warmed[0],
               cold_engine_on(devices[2]), cold_engine_on(devices[3])]
    assert [e.device for e in engines] == devices
    chip_smoke.check_same_weights(engines)

    def on(engine, device):
        return all(leaf.devices() == {device}
                   for leaf in jax.tree_util.tree_leaves(
                       (engine.params, engine.pools, engine.seen)))

    assert all(on(e, d) for e, d in zip(engines, devices))
    # the supervisor runs the factory inside the dead replica's device
    # scope, so one that names no device lands where its predecessor was
    gw = Gateway(engines, engine_factory=cold_engine)
    dead = gw._workers[2]
    gw._supervisor._rebuild(dead, "crash")
    new = gw._workers[2]
    try:
        assert new is not dead and new.engine is not engines[2]
        assert new.engine.device == devices[2] and on(new.engine,
                                                      devices[2])
    finally:
        new.draining = True
        new.wake()
        new.join(10)
    assert not new.is_alive()
    engines[3].hard_reset()     # the in-place rebuild allocates there too
    assert on(engines[3], devices[3])


def test_main_refuses_cpu_and_interpret_mode(monkeypatch, capsys):
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert out == ""                       # no result line
    assert "platform 'cpu'" in err
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert out == "" and "PADDLE_TPU_PALLAS_INTERPRET" in err


def test_result_is_the_last_stdout_line_with_exactly_its_keys(capsys):
    """The accelerator check reads the LAST stdout line and wants exactly
    ``{"ok", "device": {"platform", "kind", "count"}}``; the facts of the
    run go on the line before it."""
    import json
    devices = jax.devices()[:2]
    chip_smoke.report(devices, {"setup_s": 1.5, "requests": 6})
    facts, result = map(json.loads, capsys.readouterr().out.splitlines())
    assert result == {"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": 2}}
    assert type(result["device"]["count"]) is int
    assert facts["facts"]["device"] == result["device"]
    assert facts["facts"]["requests"] == 6


def test_a_failed_check_names_its_reason():
    with pytest.raises(chip_smoke.SmokeFailure, match="HTTP 503"):
        chip_smoke.check_answers([dict(name="r", status=503, error="x")])
    metrics = ('gateway_watchdog_fires_total{gateway="g"} 1\n'
               'gateway_watchdog_fires_total_created 5\n')
    assert chip_smoke.metric_total(
        metrics, "gateway_watchdog_fires_total") == 1


def test_cache_resolver_env_over_every_argument(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/from/env")
    assert compile_cache.resolve_dir() == "/from/env"
    assert compile_cache.child_env("/an/argument")[
        compile_cache.ENV_VAR] == "/from/env"
    monkeypatch.delenv(compile_cache.ENV_VAR)
    assert compile_cache.resolve_dir() == compile_cache.DEFAULT_DIR
    assert compile_cache.DEFAULT_DIR == os.path.join(ROOT, ".jax_cache")
    assert compile_cache.child_env("/an/argument")[
        compile_cache.ENV_VAR] == "/an/argument"
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
