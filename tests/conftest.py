"""Test harness: an 8-device virtual CPU mesh.

The reference ran its suite under ``mpirun -np N`` so the same tests covered
size 1 and size N (reference test/common.py:25-58). The TPU-native
equivalent: force the JAX host platform to expose 8 virtual CPU devices and
run every SPMD test over that mesh — sharding semantics (psum, all_gather,
shard_map partitioning) are platform-independent, so what compiles and
passes here compiles on a v5e slice.
"""

import os

# The suite runs on the CPU, on 8 virtual devices.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402

import pytest  # noqa: E402

# Two-lane suite strategy. The full suite (default) is the CI gate; on a
# single-CPU box it runs ~25 min, dominated by whole-program integration
# tests (subprocess launches, example smokes, big-model compiles).
# `pytest -m "not slow"` is the fast iteration lane — measured
# 2026-07-31 (round 4): 9.8 min / 255 tests on the 1-core box (17.9 min
# before the round-4 re-budget) — that keeps per-op/per-kernel
# closed-form and exactness tests and skips whole-program wrappers and
# whole-MODEL composition pins whose internals those tests already
# cover (each demotion below names its faster stand-ins; the full lane
# still runs everything). Auto-marked here (one registry) instead of
# per-file decorators.
_SLOW_TESTS = {
    "test_chip_smoke.py::test_rehearsal_passes",
    "test_examples_models.py::TestExamples::test_flax_imagenet_resnet50_smoke",
    "test_examples_models.py::TestExamples::test_jax_transformer_zero_smoke",
    "test_examples_models.py::TestExamples::test_jax_gpt_parallel_smoke",
    "test_examples_models.py::TestExamples::test_long_context_ring_attention_smoke",
    "test_examples_models.py::TestExamples::test_jax_mnist",
    "test_examples_models.py::TestExamples::test_torch_mnist_via_launcher",
    "test_examples_models.py::TestExamples::test_tf_keras_mnist_via_launcher",
    "test_examples_models.py::TestExamples::test_torch_synthetic_benchmark_via_launcher",
    "test_examples_models.py::TestModelZoo::test_forward_shapes[inception_v3-shape1]",
    "test_conv_bn.py::TestFusedResNet::test_inception_fused_matches_unfused",
    "test_examples_models.py::TestModelZoo::test_vgg16_train_step_runs",
    "test_models.py::test_graft_entry_multichip_subprocess",
    "test_multiprocess_spmd.py::test_two_process_global_mesh_end_to_end",
    "test_multiprocess_spmd.py::test_two_process_hierarchical_ladder",
    "test_multiprocess_spmd.py::test_four_process_global_mesh_end_to_end",
    "test_multiprocess_spmd.py::test_four_process_hierarchical_ladder",
    "test_multiprocess_spmd.py::test_eight_process_asymmetric_ladder_and_ulysses",
    "test_tf_binding.py::TestMultiProcess::test_ops",
    "test_tf_binding.py::TestMultiProcess::test_distributed_gradient_tape_converges",
    "test_tf_binding.py::TestMultiProcess::test_keras_callbacks",
    "test_launcher.py::TestCLI::test_restarts_relaunches_until_success",
    "test_launcher.py::TestCLI::test_restarts_exhausted_returns_failure",
    "test_examples_models.py::TestExamples::test_jax_word2vec_smoke",
    # Whole-program serving bench wrappers (subprocess, ~15-20s each);
    # stand-ins: tests/test_serve_engine.py exactness/lifecycle pins
    # (fast) + the tools/check.sh serve smoke lane runs the contract.
    "test_serve_bench.py::TestServeBenchContract::test_continuous_record_contract",
    "test_serve_bench.py::TestServeBenchContract::test_ab_record_carries_both_sides",
    # ~10s, same subprocess shape; stand-in: the in-process
    # test_serve_engine.py::TestLifecycle::test_hard_reject_when_never_fits
    "test_serve_bench.py::TestServeBenchContract::test_require_finished_fails_loudly",
    # Round-4 re-budget (fast lane had crept to 17.9 min): whole-model
    # composition pins whose per-op internals have fast stand-ins.
    # 41s; stand-ins: test_train_step_matches_dense + decode_composes_with_tp
    "test_parallel_lm.py::test_decode_matches_naive_recompute",
    # 28s; stand-ins: the per-axis exactness pins in the same file
    "test_parallel_lm.py::test_bf16_composed_step_and_decode",
    # 26s; stand-ins: test_zero.py equivalence + ring-attention exactness
    "test_parallel_lm.py::test_zero_composes_with_sequence_parallel",
    # 30s (two full-model compiles); stand-in: LM lane contract (slow)
    "test_models.py::test_scan_layers_matches_unrolled",
    # 25s (two full training runs); numerics covered by optax contract
    "test_models.py::test_bf16_momentum_tracks_fp32",
    # 29s whole-ResNet step; stand-ins: the kernel-level exactness tests
    # (test_fused_equals_unfused_f32, *_grads_equal_*) in the same file
    "test_conv_bn.py::TestFusedResNet::test_resnet50_style_step_fused_vs_unfused",
    # 42s public-API wrapper; mechanism covered by the native-lane
    # TestSubCommunicator tests (fast)
    "test_torch_binding.py::TestMultiProcess::test_init_comm_subworld",
    # np=2 variants stay fast; the larger sizes are integration depth
    "test_torch_binding.py::TestMultiProcess::test_ops[3]",
    "test_native_core.py::TestMultiProcess::test_collectives[4]",
    # 20s whole-ViT step; stand-in: vit forward-shape test
    "test_examples_models.py::TestModelZoo::test_vit_spmd_train_step",
    # Sanitizer builds recompile all of csrc/ (~60s each) and rerun the
    # stress binary under TSAN/ASAN; the plain stress test (fast lane)
    # covers deadlock/corruption, these cover races/memory. Run via
    # tools/check.sh --sanitize or pytest -m slow.
    "test_native_stress.py::test_stress_clean_under_tsan",
    "test_native_stress.py::test_stress_clean_under_asan",
    # The windowed elastic e2e repeats the whole-job kill/relaunch wrapper
    # at k=3; the k=1 variant (fast lane) covers the same supervision
    # path, and TestRunElastic::test_resume_is_bit_exact_windowed pins
    # the windowed resume numerics in-process.
    "test_elastic.py::TestEndToEnd::test_kill_rank1_resumes_bit_exact[3]",
    # ~25s: traces the FULL hvdverify registry (9 big-model gate lanes).
    # Fast stand-in: test_repo_sweep_core_is_clean covers the
    # optimizer/parallel/elastic programs; the gate lanes run here and
    # in tools/check.sh --verify.
    "test_hvdverify.py::test_repo_sweep_is_clean",
    # ~35s: three 24-step LM trainings (fp32 / fp8+EF / fp8 no-EF).
    # Fast stand-ins: test_error_feedback_time_average_converges pins
    # the EF mechanics and test_ef_exact_codec_leaves_zero_residual the
    # Average composition; the LM trajectory pin runs in the CI gate
    # and tools/check.sh's full lane.
    "test_hierarchical.py::test_ef_convergence_small_lm",
    # Round-10 re-budget: the fast lane had grown to ~18 min on the
    # 1-core box (the 870 s tier-1 window truncated it mid-suite, which
    # is worse than demoting — a timeout drops ~170 later tests
    # arbitrarily). Same discipline as round 4: whole-program
    # subprocess wrappers whose internals have fast in-process
    # stand-ins move to the slow lane (still in the full CI gate).
    # 42s TF keras multi-process wrapper; its three TestMultiProcess
    # siblings are already slow-marked with the same justification
    # (single-process keras coverage stays fast).
    "test_tf_binding.py::TestMultiProcess::test_keras_lr_callbacks_and_load_model",
    # 30s + 20s: the even-vocab (32/8) vocab-parallel xent pair (the
    # ragged 28/8 pair joined them in round 17 — see below).
    "test_xent.py::TestVocabParallel::test_loss_and_grads_match_dense[32-8]",
    "test_xent.py::TestVocabParallel::test_loss_and_grads_match_dense_in_region[32-8]",
    # 30s + 24s torch multi-process integration depth; test_ops[2] and
    # the single-process optimizer tests stay fast (test_ops[3] was
    # already slow-marked on the same grounds).
    "test_torch_binding.py::TestMultiProcess::test_distributed_optimizer_converges",
    "test_torch_binding.py::TestMultiProcess::test_optimizer_features",
    # 22s + 11s serving-bench subprocess wrappers: their two sibling
    # contract tests are already slow-marked (stand-ins:
    # test_serve_engine exactness matrix + the check.sh serve smoke,
    # which runs BOTH attention modes end-to-end).
    "test_serve_bench.py::TestServeBenchContract::test_attention_paged_record_contract",
    "test_serve_bench.py::TestServeBenchContract::test_ab_attention_record_carries_both_sides",
    # 25s + 10s fleet-bench subprocess wrappers (each runs whole
    # clean/faulted fleets): stand-ins are the in-process
    # TestKillRedispatch::test_greedy_bit_identical_to_fault_free_run
    # pin (fast) and the check.sh fleet smoke, which runs the exact
    # acceptance command end-to-end. Arg-validation stays fast.
    "test_serve_bench.py::TestFleetBenchContract::test_fleet_fault_ab_record_contract",
    "test_serve_bench.py::TestFleetBenchContract::test_fleet_clean_record_contract",
    # ~90s: whole clean+faulted PROCESS fleets (4 real worker spawns,
    # each paying the jax import + compile). Stand-ins: the fast
    # test_serve_worker.py::TestStubFleet matrix + the synthetic
    # fleet_cell pin; the check.sh process-fleet smoke runs this exact
    # command end-to-end.
    "test_serve_bench.py::TestFleetBenchContract::test_fleet_process_transport_record_contract",
    # 11s + 8s + 7s fleet composition depth: the fast greedy kill pin
    # already runs a clean fleet (== lm_decode per request) AND a
    # faulted fleet on the same submissions; the sampled variant
    # re-runs the same machinery at temperature>0 (engine-level
    # sampling recompute exactness is pinned fast in
    # test_serve_engine), and the stall e2e needs real wall-clock
    # heartbeat aging (watchdog unit pins + TestRestartPolicy stay
    # fast).
    "test_serve_fleet.py::TestFleetBasics::test_all_finish_and_match_lm_decode",
    "test_serve_fleet.py::TestKillRedispatch::test_sampled_requests_resume_exact_stream",
    "test_serve_fleet.py::TestStallWatchdog::test_stall_watchdog_classified_relaunch",
    # 14s whole-CLI launch wrapper; the TestRunFn in-process launcher
    # tests (identity env, collectives through the launcher) stay fast,
    # and the restart-path CLI tests were already slow-marked.
    "test_launcher.py::TestCLI::test_launch_command_success",
    # Round-17 re-budget (fast lane at ~900s > the 870s window): the
    # ragged 28/8 pair joins its even 32/8 twin — the through-boundary
    # variant had grown to 55s — so the whole vocab-parallel grads
    # matrix is slow-lane/CI-gate. Fast stand-ins:
    # test_loss_identical_on_every_rank (the vocab-parallel loss pin,
    # every rank, stays fast) and the dense fused-CE matrix incl. the
    # ragged 60/16 pad path (test_fused_ce_matches_dense).
    "test_xent.py::TestVocabParallel::test_loss_and_grads_match_dense[28-8]",
    "test_xent.py::TestVocabParallel::test_loss_and_grads_match_dense_in_region[28-8]",
    # 12s 4-process launcher collective round-trip; test_identity_env
    # pins the in-process launcher plumbing fast, and the elastic e2e
    # lanes drive launch_job with real collectives every run.
    "test_launcher.py::TestRunFn::test_collectives_through_launcher",
    # 14s: the longest serve-engine exactness matrix entry; the other
    # exactness classes (eviction-recompute, chunk-invariance, single
    # request, max_new=1) stay fast in both attention modes, and the
    # check.sh serve smoke re-pins greedy==lm_decode end-to-end.
    "test_serve_engine.py::TestGreedyExactness::test_staggered_joins_bit_identical[gather-tp1]",
    # Round-17 re-budget: the paged twin joins it on the same grounds
    # — the other exactness classes keep both attention modes fast.
    "test_serve_engine.py::TestGreedyExactness::test_staggered_joins_bit_identical[paged-tp1]",
    # The tp=4 staggered twins (6s each: SPMD compile + 6 lm_decode
    # refs) follow their tp1 parents to the slow lane; fast stand-ins
    # for staggered-under-TP are the tp4 single-request/eviction/
    # max_new exactness cells plus the check.sh TP smoke, which runs
    # a multi-request tp=4-vs-tp=1 A/B end-to-end.
    "test_serve_engine.py::TestGreedyExactness::test_staggered_joins_bit_identical[gather-tp4]",
    "test_serve_engine.py::TestGreedyExactness::test_staggered_joins_bit_identical[paged-tp4]",
    # Chunk-invariance under tp=4: chunk=4 (the ragged non-divisor)
    # stays fast in BOTH attention modes as the named stand-in; the
    # 1/3/16 tp4 cells (~3s each, 6 tests) are slow-lane — chunking
    # itself is pinned fast by the full tp1 chunk matrix, and the tp4
    # concern (SPMD prefill rows == lm_prefill rows) is chunk-size-
    # independent by construction.
    "test_serve_engine.py::TestGreedyExactness::test_chunked_prefill_is_chunk_invariant[1-gather-tp4]",
    "test_serve_engine.py::TestGreedyExactness::test_chunked_prefill_is_chunk_invariant[1-paged-tp4]",
    "test_serve_engine.py::TestGreedyExactness::test_chunked_prefill_is_chunk_invariant[3-gather-tp4]",
    "test_serve_engine.py::TestGreedyExactness::test_chunked_prefill_is_chunk_invariant[3-paged-tp4]",
    "test_serve_engine.py::TestGreedyExactness::test_chunked_prefill_is_chunk_invariant[16-gather-tp4]",
    "test_serve_engine.py::TestGreedyExactness::test_chunked_prefill_is_chunk_invariant[16-paged-tp4]",
    # 35s + 38s whole-bench ab-prefix subprocess wrappers (each runs a
    # cold AND a warm serve/fleet bench): stand-ins are the fast
    # in-process prefix pins — test_serve_prefix.py TestEngineHits
    # hit==cold==lm_decode and TestFleetPrefix co-location /
    # redispatch-savings — and the check.sh prefix smoke, which runs
    # the single-engine --ab-prefix contract end-to-end.
    "test_serve_bench.py::TestServeBenchContract::test_ab_prefix_record_contract",
    "test_serve_bench.py::TestFleetBenchContract::test_fleet_ab_prefix_record_contract",
    # ~20s whole-bench --ab-tp subprocess wrapper (tp=1 AND tp=4 SPMD
    # compiles): stand-ins are the fast in-process tp4 exactness cells
    # (test_serve_engine.py TestGreedyExactness mesh matrix +
    # TestTPSharding per-chip pins) and the check.sh TP smoke, which
    # runs the --ab-tp contract end-to-end; the cheap
    # test_ab_tp_arg_validation stays fast.
    "test_serve_bench.py::TestServeBenchContract::test_ab_tp_record_contract",
    # 13s np=2 torch multi-process ops: the torch TestMultiProcess
    # matrix goes fully slow-lane, matching the tf-binding precedent
    # (its whole TestMultiProcess class has been slow-marked for
    # rounds) — single-process torch op/optimizer tests stay fast.
    "test_torch_binding.py::TestMultiProcess::test_ops[2]",
    # 8s: the lazy-admission hit-stream twin; the reserve variant stays
    # fast and pins the same hit==cold==lm_decode exactness, and
    # test_admission_counts_only_missed_pages keeps the lazy-path
    # accounting fast.
    "test_serve_prefix.py::TestEngineHits::test_hit_stream_bit_identical_to_cold_and_lm_decode[lazy]",
    # 8s real wall-clock stall e2e (whole-job relaunch wrapper): the
    # kill[1] e2e stays fast covering the supervision path, and
    # test_native_core.py::TestStallDetection pins the watchdog
    # mechanics fast.
    "test_elastic.py::TestEndToEnd::test_stall_fault_terminates_via_watchdog",
    # 8s + 7s + 6s + 6s rolling-update/stall composition depth: the
    # core roll pin test_update_rolls_fleet_streams_stay_single_version
    # stays fast (clean roll, per-stream single-version), the stranded/
    # rebase/draining variants and the bounded-stall resume move to the
    # slow lane with the real-worker and tcp variants already there;
    # version-eligibility unit pins (TestRouter/TestRebase) stay fast.
    "test_serve_fleet.py::TestVersionedRollingUpdate::test_stranded_version_restarts_from_scratch",
    "test_serve_fleet.py::TestVersionedRollingUpdate::test_redispatch_rebases_only_onto_same_version",
    "test_serve_fleet.py::TestVersionedRollingUpdate::test_updating_replica_stops_accepting_but_fleet_serves",
    "test_serve_fleet.py::TestStallWatchdog::test_bounded_stall_resumes_without_watchdog",
    # 12s whole-tf.keras rewrap wrapper; the settings plumbing it pins
    # is asserted by the fast native-core knob tests, full run in CI.
    "test_review_regressions.py::test_tf_keras_rewrap_honors_new_settings",
    # 6s each native-lane forked-rank hierarchical variants; the core
    # ladder exactness (4ranks_2groups) and the degrade rules stay
    # fast, auth is covered by TestTransportAuth.
    "test_native_core.py::TestHierarchical::test_hierarchical_authenticated",
    "test_native_core.py::TestHierarchical::test_group_size_defaults_to_local_size",
    # ~20s each: real `python -m horovod_tpu.serve.worker` processes
    # (every spawn pays the jax import + first-step
    # compile). Fast stand-ins: test_serve_worker.py::TestStubFleet
    # runs the SAME fleet/transport code paths against real OS
    # processes via the no-jax protocol stub (~4s for the whole
    # recovery matrix incl. SIGKILL-classify, torn-frame, watchdog
    # stall, close-escalation), test_serve_transport.py pins the codec,
    # and the tools/check.sh process-fleet smoke runs the real-worker
    # kill e2e end to end.
    "test_serve_worker.py::TestRealWorkerE2E::test_kill_redispatch_bit_exact_vs_lm_decode",
    "test_serve_worker.py::TestRealWorkerE2E::test_stall_watchdog_classified_relaunch",
    "test_serve_worker.py::TestRealWorkerE2E::test_kill_mid_write_torn_frame_redispatch_exact",
    # Real-worker loopback-TCP partition e2e (round-14): same jax-spawn
    # cost as the others; fast stand-ins are
    # test_serve_fleet_tcp.py::TestStubTcpFleet (the whole host-domain
    # recovery matrix over real TCP via the no-jax stub) and the
    # tools/check.sh loopback-TCP fleet smoke.
    "test_serve_worker.py::TestRealWorkerE2E::test_tcp_partition_host_down_bit_exact_vs_lm_decode",
    # Round-15 rolling-update e2e (2 real tcp workers + an update push
    # = 4 jax imports + compiles). Fast stand-ins:
    # TestStubRollingUpdate (the full drain/push/tear/resume matrix on
    # the protocol stub) + TestVersionedRollingUpdate (inproc version
    # pinning vs lm_decode) + the check.sh rolling-update smoke.
    "test_serve_worker.py::TestRealWorkerE2E::test_tcp_rolling_update_torn_push_bit_exact_vs_lm_decode",
    # Round-19 speculative decoding (each spec cell pays the draft-
    # scan + verify-window compile, ~6-7s): the k=2 cells of the
    # exactness matrix stay fast in ALL FOUR attention×mesh
    # combinations as the named stand-ins — window math is
    # k-independent (the k=7 > steps clamp is pinned fast at the
    # model level by test_parallel_lm spec tests and at the engine
    # level by test_budget_clamp_never_overshoots).
    "test_serve_engine.py::TestSpeculativeExactness::test_spec_stream_bit_identical[1-gather-tp1]",
    "test_serve_engine.py::TestSpeculativeExactness::test_spec_stream_bit_identical[1-paged-tp1]",
    "test_serve_engine.py::TestSpeculativeExactness::test_spec_stream_bit_identical[1-gather-tp4]",
    "test_serve_engine.py::TestSpeculativeExactness::test_spec_stream_bit_identical[1-paged-tp4]",
    "test_serve_engine.py::TestSpeculativeExactness::test_spec_stream_bit_identical[4-gather-tp1]",
    "test_serve_engine.py::TestSpeculativeExactness::test_spec_stream_bit_identical[4-paged-tp1]",
    "test_serve_engine.py::TestSpeculativeExactness::test_spec_stream_bit_identical[4-gather-tp4]",
    "test_serve_engine.py::TestSpeculativeExactness::test_spec_stream_bit_identical[4-paged-tp4]",
    # 10s + 8s spec-composition depth: eviction-recompute and prefix/
    # COW under speculation re-run machinery whose non-spec twins
    # (TestGreedyExactness eviction matrix, TestTPSharding prefix/COW)
    # and spec twins (the k=2 matrix above, which exercises the SAME
    # widened page-grant/_cow_guard arithmetic every tick) stay fast;
    # the check.sh spec smoke runs the full contract end-to-end.
    "test_serve_engine.py::TestSpeculativeLifecycle::test_eviction_recompute_stays_exact_under_spec",
    "test_serve_engine.py::TestSpeculativeLifecycle::test_prefix_cow_stays_exact_under_spec",
    # 9s + 5s: two more spec engine compiles; fast stand-ins are the
    # host-side TestSpeculativeAcceptUnit rejection-sampling pins
    # (same speculative_accept code path, no compile) and the
    # non-spec TestSampling determinism/neighbor tests.
    "test_serve_engine.py::TestSpeculativeLifecycle::test_temperature_same_seed_deterministic",
    "test_serve_engine.py::TestSpeculativeLifecycle::test_greedy_neighbor_unaffected_by_sampling_slot",
    # ~3s each model-level spec windows at larger k: the [1-1]/[2-1]
    # cells stay fast and pin the same lm_decode_spec == lm_decode
    # equality; k=4/k=7 add only window width (and the k > steps
    # clamp, re-pinned fast by the engine budget-clamp test).
    "test_parallel_lm.py::test_spec_decode_matches_lm_decode[4-2]",
    "test_parallel_lm.py::test_spec_decode_matches_lm_decode[7-2]",
    # ~30s whole-bench --ab-spec subprocess wrapper (an OFF and an ON
    # serve lane + the bit-identity pin): stand-ins are the fast
    # test_ab_spec_arg_validation + the in-process spec exactness
    # matrix, and the check.sh spec smoke runs this exact command
    # (incl. the accept_rate==1.0 / tokens_per_step>1 record pins)
    # end-to-end.
    "test_serve_bench.py::TestServeBenchContract::test_ab_spec_record_contract",
    # ~26s clean+faulted fleet pair under speculation: the fast
    # TestKillRedispatch greedy pin covers drain/redispatch and the
    # spec matrix covers speculative exactness; this composition test
    # (redispatch resumes MID-STREAM under speculative windows) runs
    # in the CI gate.
    "test_serve_fleet.py::TestSpeculativeFleet::test_kill_redispatch_bit_exact_under_spec",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: whole-program integration wrapper; skipped by the fast "
        "iteration lane (pytest -m 'not slow'), always in the CI gate")


def pytest_collection_modifyitems(config, items):
    matched = set()
    for item in items:
        rel = item.nodeid.split("/")[-1]
        if rel in _SLOW_TESTS:
            matched.add(rel)
            item.add_marker(pytest.mark.slow)
    # Fail loudly on registry drift: a renamed/removed test would
    # otherwise silently rejoin the fast lane. Only enforced on full
    # collections (running a single file legitimately misses entries).
    stale = _SLOW_TESTS - matched
    if stale and len(items) > 200:
        raise pytest.UsageError(
            f"tests/conftest.py _SLOW_TESTS has stale entries: {stale}")


@pytest.fixture(scope="session")
def hvd():
    import horovod_tpu.jax as hvd

    hvd.init()
    return hvd
