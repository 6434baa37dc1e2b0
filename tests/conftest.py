"""Test config: force an 8-virtual-device CPU platform so mesh/sharding
tests run without TPU hardware (SURVEY.md §4)."""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
if "xla_backend_optimization_level" not in flags:
    # tests are compile-bound on this image's single CPU core; O0 cuts
    # XLA:CPU compile ~2-3x and every numerics tolerance still holds
    # (fast-math stays off). Production TPU compiles are untouched.
    flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = flags

# JAX_PLATFORMS=cpu is all it takes to hold jax to the CPU; set before
# jax is imported, and inherited by the subprocesses tests spawn.
os.environ["JAX_PLATFORMS"] = "cpu"

# Persistent compilation cache: many test files compile byte-identical
# tiny-model programs in fresh closures; jit's in-process cache can't
# dedupe those (different callables), the HLO-keyed persistent cache can —
# both within one suite run and across runs/subprocess children. The
# suite keeps its own fixed directory but yields to the standard
# variable, which jax reads at import (utils/compile_cache.py resolves
# the same variable, so Trainer.train & co. stay on this directory).
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      "/tmp/paddle_tpu_test_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")
# compile_cache.enable() would put op metadata (source lines, named
# scopes) into the cache key; the sharing above needs it left out, and
# no test reads a name off a compiled program
os.environ.setdefault("JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY", "0")

import jax

import numpy as np
import pytest

# ---------------------------------------------------------------- tiers
# The heavy tier (see pytest.ini): exhaustive variants whose subsystem
# keeps a fast representative in the default run. One central list, not
# per-file markers, so the split stays reviewable.
_HEAVY = (
    # pipeline 1F1B: the tp+dp composition test subsumes these grad-match
    # variants (same machinery, wider mesh)
    "test_1f1b_matches_sequential[4-2]",
    "test_1f1b_single_microbatch",
    "test_trainer_pp_path_runs_and_learns",
    # HF interop: llama logits parity + round-trip stay; the rest are
    # per-family repeats of the same converter machinery
    "test_hf_interop.py::test_llama_greedy_decode_matches",
    "test_hf_interop.py::test_qwen2_logits_match",
    "test_hf_interop.py::test_llama_tied_embeddings",
    "test_hf_interop.py::test_bert_hidden_states_match",
    "test_hf_interop.py::test_bert_pretraining_heads_load",
    "test_hf_interop.py::test_ernie_mlm_logits_match",
    "test_hf_interop.py::test_sharded_index_checkpoint",
    # ring flash: both composition variants are heavy since the round-5
    # pass (see below) — the default tier keeps the plain ring exactness
    # tests (segments/window vs dense) + the flash kernel suite
    "TestRingFlash::test_gradients_flow",
    # elastic: kill/resume (the r2 deliverable) stays; the hang path is a
    # second full subprocess cycle
    "test_hang_checkpoints_exits_and_supervisor_finishes",
    # dataloader: order/speedup/exception stay (each spawn pool costs
    # seconds); these exercise secondary pool semantics
    "test_get_worker_info_and_distribution",
    "test_worker_init_fn_controls_rng",
    "test_persistent_pool_reused",
    "test_consumer_early_break_then_reuse",
    "test_concurrent_iterators_rejected",
    # model zoo: one overfit + one kv-decode parity per backbone family
    # stays (gpt); qwen2/moe/bert/ernie reuse the identical Llama/Bert
    # machinery verified elsewhere
    "test_gpt_forward_and_overfit",
    "test_qwen2_kv_cache_decode_parity",
    "test_qwen2_moe_forward_aux_and_overfit",
    "test_qwen2_moe_kv_cache_decode",
    "test_bert_classifier_overfit",
    # vision/diffusion/pipelines: shape/math smoke stays; grads + image
    # pipelines are compile-heavy conv/attention repeats
    "TestResNet::test_forward_and_grad",
    "TestResNet::test_bottleneck_variant_d",
    "TestCLIP::test_grad_through_both_towers",
    "TestDiT::test_dit_grad",
    "TestDiT::test_mmdit_joint_stream",
    "TestVAE::test_roundtrip_shapes",
    "TestPPOCR::test_svtr_ctc",
    "TestPPOCR::test_dbnet_maps",
    "TestLoopAndLoss::test_diffusion_loss_with_dit",
    "TestDiTPipeline::test_vae_decode_stage",
    "TestDiTPipeline::test_guidance_changes_output",
    "TestSD3Pipeline::test_flow_sampling",
    "TestPredictor::test_quantized_predictor",
    # generation: beam internals stay via beam1==greedy; this reruns
    # the whole beam program (sampling e2e stays default)
    "test_beam_search_beats_greedy_logprob",
    # second-tier variants added after the first timing pass: each line's
    # subsystem keeps the named cheaper representative
    "test_1f1b_matches_sequential[2-1]",   # <- compose_with_tp_dp
    "test_dead_worker_raises_not_hangs",   # <- worker_exception_propagates
    "TestVAE::test_kl_and_loss",           # <- vae sample_stochastic
    "test_text_pipeline.py::test_pipeline_bucket_reuse",  # <- left_padded
    "test_text_pipeline.py::test_pipeline_single_and_batch",
    # decode kernels: keep a diagonal of the parametrized cross-product
    "test_decode_dispatch_matches_dense[5-",
    "test_decode_dispatch_matches_dense[127-",
    "test_decode_dispatch_matches_dense[200-",
    "test_pallas_decode_kernel_matches_dense[100-",
    # trainer/llama: exhaustive repeats of the jitted-step machinery
    "test_grad_accumulation_matches_big_batch",
    # interleaved pipeline: [3] (microbatches % pp != 0, the harder
    # schedule) stays default; [4] and the tp-composition variant rerun
    # the same table machinery the non-interleaved compose test covers
    "test_interleaved_vpp_matches_sequential[4]",
    "test_interleaved_vpp_composes_with_tp",
    # ernie45-moe: forward+grad (incl. dense/MoE layer split) stays; the
    # generate path is the same CausalLMBase while_loop as llama/qwen
    "TestErnie45Moe::test_generate",
    # deepseek-v2: torch parity + absorbed-decode proofs stay; generate
    # rides the shared while_loop machinery
    "TestMLADecode::test_generate_runs",
    # round-4 timing pass: subsystems keep the named cheaper/stronger
    # representative in the default tier
    "test_speedup_4_workers",            # <- order_matches_serial
    "TestCLIP::test_contrastive_roundtrip",  # <- interop clip parity
    "TestPPOCR::test_db_loss",           # <- heavy dbnet_maps/svtr
    "TestResNet::test_feature_pyramid",  # <- vit/resnet interop + heavy
    "test_custom_logits_loss_under_pp",  # <- compose_with_tp_dp (same
    # machinery; the logits_loss hook itself is 5 lines re-verified there)
    "TestDPO::test_sequence_logps_and_precompute",  # <- dpo_trainer test
    "test_packed_fallback_for_models_without_segment_ids",  # <- packing
    "test_round3_flat_ops",              # <- per-op coverage in test_nn
    "test_mtp_module_does_not_shift_trunk_init",  # <- shapes_and_parity
    # round-5 timing pass (suite was 540s standalone; VERDICT r4 item 9):
    # each demotion names the default-tier representative that exercises
    # the same machinery
    "test_interleaved_vpp_matches_sequential[3]",  # <- composes_with_ep_moe
    # (interleaved tables + harder ep composition in one test)
    "TestDeepseekV2Parity::test_logits_match_torch",  # <- v3_logits_match
    # (V3 parity is the superset: same converter/MLA plus sigmoid router)
    "TestRingFlash::test_matches_full_attention",  # <- plain ring
    # exactness tests (segments/window vs dense) + flash kernel suite
    "TestMTP::test_mtp_shapes_and_main_parity",  # <- mtp_training_decreases
    # + TestMTPSpeculative exactness (MTP modules e2e in decode)
    "test_vae_diffusers_roundtrip",     # <- dit/sd3 roundtrips (dispatch)
    "test_model_pass_swaps_and_generates[awq_quantize_model]",  # <- [gptq]
    "test_fuse_attention_only",         # <- full fuse + mesh exactness
)


# The slow tier: tier-1 verify runs `-m 'not slow'` (which, unlike the
# default addopts, INCLUDES heavy) against a hard wall-clock cap — these
# multi-subprocess e2e tests are its biggest line items (~80s combined)
# and each keeps a faster default-tier representative of the same
# machinery:
#   kill/resume e2e        <- test_preemption.py in-process preempt e2e
#                             (sampler-exact resume, a strict superset)
#   hang+supervisor e2e    <- test_supervise_uses_shared_backoff +
#                             preempt free-restart supervisor test
#   nan rollback converges <- test_rollbacks_bounded_then_reraise
_SLOW = (
    "test_kill_mid_run_then_resume_continues_trajectory",
    "test_hang_checkpoints_exits_and_supervisor_finishes",
    "test_nan_window_rolls_back_and_converges",
    # ISSUE 11 tier-budget pass: the tier-1 suite was within one sweep
    # of the 870s cap, so the top duration offenders (compile-bound
    # exhaustive variants, each already in _HEAVY with a named cheaper
    # tier-1 representative of the same machinery) move to the slow
    # tier. Representatives staying in tier-1:
    #   resnet fwd+grad / bottleneck  <- TestResNet::test_feature_pyramid
    #   deepseek-v2 torch parity      <- TestDeepseekV3::v3_logits_match
    #   ring-flash composition pair   <- plain ring exactness + flash suite
    #   clip tower grads              <- TestCLIP::contrastive_roundtrip
    #   mtp shapes+parity             <- mtp_training_decreases + spec e2e
    #   dit diffusion loss            <- TestLoopAndLoss flow/ddpm losses
    #   dataloader worker-info/rng    <- order_matches_serial + exceptions
    #   vae diffusers roundtrip       <- dit/sd3 pipeline roundtrips
    # Enforced by tools/marker_audit.py --check (pattern sync) and
    # --budget-log (per-test wall-clock ceilings).
    "TestResNet::test_forward_and_grad",
    "TestResNet::test_bottleneck_variant_d",
    "TestDeepseekV2Parity::test_logits_match_torch",
    "TestRingFlash::test_matches_full_attention",
    "TestRingFlash::test_gradients_flow",
    "TestCLIP::test_grad_through_both_towers",
    "TestMTP::test_mtp_shapes_and_main_parity",
    "TestLoopAndLoss::test_diffusion_loss_with_dit",
    "test_get_worker_info_and_distribution",
    "test_worker_init_fn_controls_rng",
    "test_vae_diffusers_roundtrip",
)


def pytest_collection_modifyitems(items):
    for item in items:
        if any(key in item.nodeid for key in _HEAVY):
            item.add_marker(pytest.mark.heavy)
        if any(key in item.nodeid for key in _SLOW):
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as pt
    from paddle_tpu.distributed import env
    pt.seed(0)
    np.random.seed(0)
    yield
    env.clear_mesh()  # tests that install a mesh must not leak it
