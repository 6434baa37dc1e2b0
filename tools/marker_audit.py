#!/usr/bin/env python
"""Tier-budget marker audit (ISSUE 6 satellite; sibling of
``fault_sites.py --check``).

The tier-1 verify runs ``pytest -m 'not slow'`` against a hard 870s
wall clock that currently has only ~duration-of-one-sweep headroom, so
a single dropped ``@pytest.mark.slow`` on a bench or sweep test can
blow the whole budget. ``--check`` collects the suite twice with
``pytest --collect-only`` (once ``-m slow``, once ``-m 'not slow'``)
and fails if:

- any MUST_BE_SLOW pattern (wall-clock benches, sweep-style parity
  matrices, multi-subprocess e2e) matches a test in the tier-1
  collection, or
- a pattern matches nothing at all (stale policy entry — the test was
  renamed or deleted and the guard is no longer guarding anything).

``--budget-log LOG`` (ISSUE 11 satellite) additionally parses a pytest
``--durations=N`` report out of LOG (e.g. the tier-1 verify's tee'd
output) and fails if any single tier-1 test exceeded its declared
wall-clock budget: ``DEFAULT_BUDGET_S`` for everything, with explicit
(pattern, seconds) rows in ``BUDGETS`` for the few known-heavy tests
that are allowed more. A new test that quietly costs 20s therefore
fails CI-style review instead of silently eating the cap. Budgets are
calibrated for the tier-1 verify's normal condition — the suite
running ALONE on the machine (same as its 870s cap); a log from a run
that shared the CPU with a bench/profiler inflates durations 2-8x and
will false-positive.

Run without flags for the marker census only.
"""
import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# --- per-test tier-1 wall-clock budgets (seconds) ----------------------
# Any single `call` duration above its covering budget fails the audit.
# Keep DEFAULT tight: the suite holds ~740 tests under a 870s cap, so
# the sustainable average is ~1s/test — 12s outliers need a named row
# and a reason.
DEFAULT_BUDGET_S = 12.0
BUDGETS = (
    # torch-parity converters pay a one-off HF model build + save
    (r"test_deepseek_v2\.py", 16.0),
    (r"test_hf_interop\.py", 16.0),
    # conv/attention-tower grads are compile-bound on 1 CPU core
    (r"test_vision_models\.py", 16.0),
    # 2s solo; in-suite it pays the mixed spec/sampled/penalized tick
    # program's compile whose cache state depends on suite order
    # (ISSUE 13's test_fleet.py sorting ahead of it shifted the bill)
    (r"test_mixed_spec_sampled_penalized_slots_one_tick", 16.0),
    # ~12s in-suite: the llama spec-tick twin pays the k+1 verify
    # forward's compile; suite-order cache shifts (which test files
    # sort ahead of test_paged_spec.py) push it over the default by a
    # hair
    (r"test_llama_tokens_exact_logprobs_close", 16.0),
)


def _parse_durations(lines):
    """Yield (seconds, nodeid) from pytest --durations report lines
    (``  7.96s call     tests/test_x.py::test_y``). Only `call` rows
    count — setup/teardown are fixture costs shared across tests."""
    rx = re.compile(r"^\s*(\d+\.\d+)s\s+call\s+(\S+)")
    for ln in lines:
        m = rx.match(ln)
        if m:
            yield float(m.group(1)), m.group(2)


def audit_durations(lines):
    """Return budget-violation strings for a durations report."""
    bad = []
    for secs, node in _parse_durations(lines):
        budget = DEFAULT_BUDGET_S
        for pat, cap in BUDGETS:
            if re.search(pat, node):
                budget = cap
                break
        if secs > budget:
            bad.append(f"{node}: {secs:.2f}s > budget {budget:.0f}s")
    return bad

# Patterns (regex, matched against pytest node ids) that must stay OUT
# of the tier-1 run. Keep in sync with tests/conftest.py's _SLOW list
# and per-test @pytest.mark.slow decorations.
MUST_BE_SLOW = (
    # ISSUE 6: sweep matrices + the 14s full-batch interpret parity
    # (each keeps a tier-1 representative; the scan's wall-clock
    # micro-bench went with the scan, PR 29)
    r"test_fused_tick\.py.*parity_sweep",
    r"test_fused_tick\.py.*full_batch",
    # ISSUE 7: spec k/ngram + multi-query kernel sweeps and the
    # tokens-per-forward micro-bench (bitwise k=4/g=2 cases, the
    # boundary-lens kernel case, and the dispatch pins stay tier-1)
    r"test_paged_spec\.py.*sweep",
    r"test_paged_spec\.py.*microbench",
    # PR 2: multi-subprocess preemption/elastic e2e (conftest _SLOW)
    r"test_kill_mid_run_then_resume_continues_trajectory",
    r"test_hang_checkpoints_exits_and_supervisor_finishes",
    r"test_nan_window_rolls_back_and_converges",
    # ISSUE 9: open-loop gateway rate sweeps + the subprocess loadgen
    # CLI e2e (each keeps a tier-1 in-process representative:
    # test_loadgen_inprocess_smoke + the single-shot gateway e2e tests)
    r"test_gateway\.py.*open_loop",
    r"test_gateway\.py.*loadgen_cli",
    # ISSUE 10: the many-request trace retention/attribution sweep
    # (tier-1 keeps the single-shot propagation + retention pins)
    r"test_reqtrace\.py.*sweep",
    # ISSUE 7 sweep: the 4-worker speedup wall-clock bench was tier-1's
    # one pre-policy bench (flipped at 2.56x/3.0 under full-suite load;
    # the rest of test_dataloader_mp.py keeps the correctness coverage)
    r"test_dataloader_mp\.py.*speedup",
    # ISSUE 12: the seeded chaos sweep — multi-seed open-loop loadgen
    # runs with mid-run replica kills + full reference replays (tier-1
    # keeps the single-kill failover e2e pins in test_failover.py:
    # test_failover_stream_bitwise_vs_uninterrupted and friends)
    r"test_failover\.py.*chaos",
    # ISSUE 13: the multi-process fleet e2e — spawns real gateway
    # SUBPROCESSES (cold jax import per process) behind the fleet
    # frontend, kills one mid-run, rides an autoscaled diurnal trace
    # (tier-1 keeps the in-process remote-adapter/failover/autoscaler
    # units in test_fleet.py: proxy parity, peer-kill bitwise resume,
    # breaker rejoin, scaler hysteresis)
    r"test_fleet\.py.*multiproc",
    # ISSUE 16: the 1000-stub fleet-sim acceptance runs (tens of
    # seconds of discrete-event CPU each; tier-1 keeps the small
    # 12-16 replica scenario pins in test_fleet_sim.py) and the live
    # two-frontend HA kill e2e (real replica subprocesses + sibling
    # frontends — matched by the multiproc pattern above)
    r"test_fleet_sim\.py.*thousand",
    # ISSUE 11: the seeded sampled-spec distribution sweep (~190s of
    # engine runs; tier-1 keeps the residual-resample marginal unit +
    # the decisive-logits exact pin), and the ISSUE-11 tier-budget
    # pass's conftest _SLOW demotions (each names its surviving tier-1
    # representative in conftest.py)
    r"test_ring_spec\.py.*distribution_parity_sweep",
    # ISSUE 14: the staged-transition chunk x spec parity matrix
    # against the host tick (tier-1 keeps the single-combination
    # transition-matrix, scoped-drain and upload-counter pins in
    # test_staged_transitions.py)
    r"test_staged_transitions\.py.*parity_sweep",
    # ISSUE 15: the multi-window burn-rate sweep (seeded outcome
    # streams x window scales x thresholds), the multi-PROCESS fleet
    # federation e2e (real replica subprocesses, cold jax import
    # each), and the chaos-alert loadgen e2e (full chaos harness run
    # + bitwise replay). Tier-1 keeps the injected-clock burn units,
    # the in-process federation pin and the sampler-on/off bitwise
    # stream pins in test_telemetry.py.
    r"test_telemetry\.py.*burn_sweep",
    r"test_telemetry\.py.*multiproc",
    r"test_telemetry\.py.*chaos",
    # ISSUE 17: the spill-tier chaos sweep — full chaos loadgen run
    # with the host-RAM KV arena attached (kill -> supervisor rebuild
    # -> warm restore) + bitwise replay gate. Tier-1 keeps the arena
    # units, the spill-on/off bitwise parity pins and the corrupt-
    # fallback pin in test_kvspill.py.
    r"test_kvspill\.py.*chaos",
    # ISSUE 18: the migrate chaos e2e — full chaos loadgen run with
    # kills PLUS the two-gateway drain-migration A/B probe (migrate
    # vs re-prefill control) and its bitwise replay gates. Tier-1
    # keeps the wire-ladder units, the drain-migration bitwise parity
    # pins and the corrupted-transfer-never-emits pin in
    # test_kvxfer.py.
    r"test_kvxfer\.py.*chaos",
    # ISSUE 20: the /profilez capture e2e — real HTTP gateway + fleet
    # frontend federation around a wall-clock capture window (tier-1
    # keeps the injected-clock phase math, the profile-on/off bitwise
    # pins and the reset-flush unit in test_tick_profile.py)
    r"test_tick_profile\.py.*profilez.*e2e",
    r"test_vision_models\.py.*(forward_and_grad|bottleneck_variant"
    r"|grad_through_both_towers)",
    r"TestDeepseekV2Parity.*logits_match_torch",
    r"TestMTP::test_mtp_shapes_and_main_parity",
    r"TestRingFlash",
    r"test_diffusion\.py.*diffusion_loss_with_dit",
    r"test_dataloader_mp\.py.*(worker_info_and_distribution"
    r"|worker_init_fn)",
    r"test_vae_diffusers_roundtrip",
)


def _collect(marker_expr):
    cmd = [sys.executable, "-m", "pytest", "tests/", "--collect-only",
           "-q", "-m", marker_expr, "-p", "no:cacheprovider",
           "--continue-on-collection-errors"]
    out = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                         timeout=300,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    nodes = [ln.strip() for ln in out.stdout.splitlines()
             if "::" in ln and not ln.startswith(("=", "<", " "))]
    return nodes


def check(budget_log=None) -> int:
    slow = _collect("slow")
    tier1 = _collect("not slow")
    bad, stale = [], []
    for pat in MUST_BE_SLOW:
        rx = re.compile(pat)
        leaked = [n for n in tier1 if rx.search(n)]
        if leaked:
            bad.extend(f"{pat}: IN TIER-1 -> {n}" for n in leaked[:3])
        elif not any(rx.search(n) for n in slow):
            stale.append(pat)
    over = []
    if budget_log:
        with open(budget_log) as f:
            over = audit_durations(f)
    census = (f"tier-1 {len(tier1)} tests, slow {len(slow)} "
              f"(cap 870s; see ROADMAP 'Tier-1 verify')")
    if bad or stale or over:
        print("marker audit FAILED:", file=sys.stderr)
        for line in bad:
            print(f"  budget leak  {line}", file=sys.stderr)
        for pat in stale:
            print(f"  stale policy {pat}: matches no collected test",
                  file=sys.stderr)
        for line in over:
            print(f"  over budget  {line}", file=sys.stderr)
        print(census, file=sys.stderr)
        return 1
    print(f"marker audit OK: {census}; "
          f"{len(MUST_BE_SLOW)} slow-policy patterns enforced"
          + (f"; durations within budget ({budget_log})"
             if budget_log else ""))
    return 0


if __name__ == "__main__":
    log = None
    argv = sys.argv[1:]
    if "--budget-log" in argv:
        i = argv.index("--budget-log")
        if i + 1 >= len(argv):
            print("usage: marker_audit.py [--budget-log "
                  "DURATIONS_LOG]", file=sys.stderr)
            sys.exit(2)
        log = argv[i + 1]
    sys.exit(check(budget_log=log))
