"""Central heavy-test marker table (the HPU fork's marker-table pattern,
reference tests/unit/ci_promote_marker.py — per-tier status tracked
centrally, test bodies untouched).

Tests listed here get ``@pytest.mark.heavy`` at collection time
(tests/conftest.py) and are EXCLUDED from the default run, keeping the
default tier under ~3 minutes.  Run everything with::

    pytest tests/ -m "heavy or not heavy"

The list was generated from a measured full run (--durations): every
test whose call took >= 4s.  When adding a slow test (engine
construction, HF parity, multi-second compiles), add it here.
Durations in comments are from the generating run (8-dev CPU mesh).
"""

# Tier-1 (the ROADMAP verify command) runs ``-m 'not slow'`` — heavy
# tests INCLUDED.  SLOW_TESTS is the tier above heavy: multi-engine
# builds with multi-minute aggregate compile cost whose coverage is
# redundant with a cheaper sibling in tier-1.  Every entry here was
# either newly added or failing-at-seed when demoted (never demote a
# passing tier-1 test to make the clock).  Run them with ``-m slow``.
SLOW_TESTS = frozenset([
    "tests/test_models.py::test_ring_sp_mode_matches_ulysses",  # 20.8s, 2 engines x 2 meshes
    "tests/test_models.py::TestTraining::test_llama_tp_sp_mesh",  # 19.5s
    "tests/test_pipeline.py::test_pipeline_engine_matches_dense_alibi",  # 12.0s (matches_dense covers the path)
    "tests/test_pipeline.py::test_pipeline_moe_matches_dense",  # 12.4s
    "tests/test_pipeline.py::test_pipeline_respects_per_microbatch_mask",  # 11.1s
    "tests/test_pipeline.py::test_1f1b_schedule_uses_less_memory_than_gpipe",  # 6.6s
    "tests/test_pipeline.py::test_pipeline_1f1b_matches_gpipe_loss",  # 6.4s
    "tests/test_pipeline.py::test_pipeline_engine_with_zero_and_data",  # 11.5s
    "tests/test_collective_scheduler.py::TestAutoAxesMeshes::test_tp_llama_direct_leaves_and_training",  # ~25s, 2 TP llama engines
    "tests/test_collective_scheduler.py::TestObservability::test_profile_buckets",  # ~5s, per-bucket recompiles
    "tests/test_collective_scheduler.py::TestQuantizedWire::test_no_error_feedback_still_converges",  # ~10s, 2 engines
    "tests/test_collective_scheduler.py::TestBucketing::test_overlap_off_matches_tolerance",  # ~12s, 3 engines
    "tests/test_multiprocess.py::TestMultiProcess::test_zero3_param_sharding_across_processes",  # ~13s, 2-proc rendezvous
    "tests/test_fused_serving.py::TestSamplingLattice::test_precompiled_lattice_covers_fused_serving_under_strict",  # ~50s, full sample/chain lattice AOT (newly added; strict coverage of the lattice itself is in tier-1 via TestPrecompileLattice)
    "tests/test_fused_serving.py::TestAsyncScheduling::test_preemption_and_restore_under_async_loop",  # 11.5s, newly added; tier-1 keeps preemption-under-async via test_inference_v2's seed preemption test (default serving is fused+async)
    "tests/test_fused_serving.py::TestSamplingLattice::test_strict_lattice_without_sampling_falls_back_to_split",  # 8.2s, newly added strict-mode fallback
    "tests/test_fused_serving.py::TestSamplingLattice::test_strict_prefill_superbucket_outside_lattice_serves_split",  # ~87s, full sampling-lattice AOT (newly added strict superbucket regression)
    "tests/test_fused_serving.py::TestFusedSplitParity::test_prefill_only_step",  # 6.6s, newly added (mixed-step parity stays in tier-1)
    "tests/test_fused_serving.py::TestFusedSplitParity::test_decode_only_step",  # 4.9s, newly added (mixed-step parity stays in tier-1)
    "tests/test_fused_serving.py::TestAsyncScheduling::test_async_matches_sync_fused_greedy",  # 4.2s, newly added (async==split parity stays in tier-1)
])

# The chaos tier (ISSUE 7): every test in tests/test_chaos.py is
# `chaos`-marked at collection (conftest), plus any entry here.  Run the
# tier alone with ``-m chaos``.  The whole suite currently runs in
# ~16s (shared module-scoped engines), so it stays inside tier-1 and
# every injection site fires there; if a chaos test grows a multi-engine
# build, add it to SLOW_TESTS as well so tier-1's clock is protected.
CHAOS_TESTS = frozenset([
    # ISSUE 8: the drain->snapshot->restore preemption path is driven by
    # injected faults (serving.preempt, ckpt.io_error) — part of the
    # chaos tier alongside tests/test_chaos.py
    "tests/test_serving_snapshot.py::TestBundleFormat::test_atomic_write_crash_leaves_previous_bundle",
    "tests/test_serving_snapshot.py::TestPreemptionTrigger::test_serving_preempt_site_interrupts_between_steps",
    "tests/test_serving_snapshot.py::TestPreemptionTrigger::test_grace_budget_expiry_migrates_with_partial_tokens",
    "tests/test_serving_snapshot.py::TestPreemptionTrigger::test_snapshot_failure_migrates_instead_of_vanishing",
    # ISSUE 11: the two-replica federation demo kills a live replica
    # through the serving.preempt chaos site mid-replay
    "tests/test_fleet_observatory.py::TestTwoReplicaKillDemo::test_fleet_coherent_and_evaluator_pages_through_replica_kill",
    # ISSUE 12: the replica pool replays the captured trace while the
    # serving.preempt site kills a replica mid-replay; the pool absorbs
    # the death and a scale_up restores capacity with zero lost requests
    "tests/test_replica_pool.py::TestPoolKillAddReplay::test_replayed_kill_add_loses_nothing",
    # ISSUE 20: the injected kv.alloc_oom walks the degrade ladder and
    # must leave a mem.breakdown forensics event with per-rung
    # pages-freed accounting
    "tests/test_memory_observatory.py::TestOOMForensics::test_injected_oom_leaves_breakdown_with_rungs",
])

HEAVY_TESTS = frozenset([
    "tests/test_disagg.py::TestHandoffParity::test_parity_with_staggered_arrivals_and_dedup",  # 7.1s, 3 engines (newly added)
    "tests/test_disagg.py::TestKeyedSampling::test_schedule_invariance",  # 6.3s, 2 engines (newly added)
    "tests/test_disagg.py::TestHandoffParity::test_threaded_serve_matches_fused",  # 6.1s, 3 engines + threads (newly added)
    "tests/test_spec_decoding.py::TestStrictSpec::test_strict_spec_lattice",  # 16.7s, full sampling+spec lattice AOT (newly added)
    "tests/test_spec_decoding.py::TestStrictSpec::test_strict_without_spec_buckets_latches_off",  # ~14s, full sampling lattice AOT (newly added)
    "tests/test_spec_decoding.py::TestSpecParity::test_mixed_workload_parity",  # 6.7s, 3 serving variants (newly added)
    "tests/test_spec_decoding.py::TestSpecParity::test_preemption_mid_spec",  # 4.2s, tiny-pool engines (newly added)
    "tests/test_serving_snapshot.py::TestSnapshotRestoreParity::test_interrupt_every_step_ordinal_speculative",  # ~10s, ordinal sweep with spec on (newly added)
    "tests/test_workload_trace.py::TestCostAccounting::test_precompiled_and_on_path_costs_agree",  # 6.5s, 2 engine builds + small precompile lattice (newly added)
    "tests/test_prefix_cache.py::TestServingParity::test_parity_under_preemption",  # 11.5s, small-pool engine build (newly added)
    "tests/test_prefix_cache.py::TestServingParity::test_parity_sliding_window_model",  # 4.0s, windowed engine build (newly added)
    "tests/test_autotuning.py::test_end_to_end_tune_picks_best",  # 7.01s
    "tests/test_checkpoint.py::TestHFImport::test_build_hf_engine_generates",  # 7.78s
    "tests/test_checkpoint.py::TestHFImport::test_llama_logits_parity",  # 15.90s
    "tests/test_checkpoint.py::TestHFImportBloomGPTJ::test_bloom_v2_greedy_matches_hf",  # 6.25s
    "tests/test_checkpoint.py::TestHFImportBloomGPTJ::test_generate_smoke[_tiny_hf_bloom]",  # 6.20s
    "tests/test_checkpoint.py::TestHFImportBloomGPTJ::test_generate_smoke[_tiny_hf_gptj]",  # 6.11s
    "tests/test_checkpoint.py::TestHFImportBreadth::test_generate_smoke[_tiny_hf_mixtral]",  # 7.42s
    "tests/test_checkpoint.py::TestHFImportBreadth::test_generate_smoke[_tiny_hf_neox]",  # 6.04s
    "tests/test_checkpoint.py::TestHFImportBreadth::test_generate_smoke[_tiny_hf_qwen2]",  # 5.97s
    "tests/test_checkpoint.py::TestHFImportBreadth::test_mixtral_v1_init_inference_generates",  # 10.35s
    "tests/test_checkpoint.py::TestHFImportBreadthFalconOptPhi::test_generate_smoke[_tiny_hf_phi3]",  # 5.71s
    "tests/test_checkpoint.py::TestHFImportBreadthFalconOptPhi::test_generate_smoke[_tiny_hf_phi]",  # 6.17s
    "tests/test_checkpoint.py::TestHFImportBreadthFalconOptPhi::test_phi_v2_engine_applies_lm_head_bias",  # 6.24s
    "tests/test_checkpoint.py::TestMistralParity::test_arch_invariants_guard_mismapped_checkpoints",  # 7.54s
    "tests/test_checkpoint.py::TestTopologyReshape::test_reshape_roundtrip[save_mesh0-load_mesh0]",  # 6.06s
    "tests/test_compression.py::test_engine_integration_prunes_params",  # 4.27s
    "tests/test_engine.py::TestActivationCheckpointing::test_cpu_checkpointing_offloads_and_trains",  # 24.51s
    "tests/test_engine.py::TestActivationCheckpointing::test_partition_activations_trains_on_mp_mesh",  # 23.93s
    "tests/test_engine.py::TestActivationCheckpointing::test_policy_name_mapping",  # 26.31s
    "tests/test_engine.py::test_checkpoint_reshard_topology",  # 4.73s
    "tests/test_engine.py::test_checkpoint_resume_training_trajectory",  # 5.96s
    "tests/test_engine.py::test_checkpoint_save_load_roundtrip",  # 5.55s
    "tests/test_engine.py::test_reference_compat_accessors",  # 4.08s
    "tests/test_engine.py::test_zero_stages_converge[0]",  # 4.39s
    "tests/test_engine.py::test_zero_stages_match_numerically",  # 12.65s
    "tests/test_inference_v1.py::test_hybrid_engine_train_and_generate",  # 23.83s
    "tests/test_inference_v1.py::test_init_inference_generate_and_forward",  # 9.00s
    "tests/test_fused_serving.py::TestAsyncScheduling::test_stop_token_misprediction_rolls_back",  # 8.2s
    "tests/test_fused_serving.py::TestAsyncScheduling::test_async_matches_split_greedy",  # 4.6s
    "tests/test_inference_v2.py::TestEndToEnd::test_chunked_prefill_then_decode_matches_full",  # 5.95s
    "tests/test_inference_v2.py::TestEndToEnd::test_generate_matches_engine_greedy",  # 20.82s
    "tests/test_inference_v2.py::TestPrecompileLattice::test_precompile_covers_serving_and_strict_catches_misses",  # 147.61s
    "tests/test_inference_v2.py::TestQuantizedInference::test_quantized_generate_close_to_full_precision[fp8_e4m3]",  # 19.42s
    "tests/test_inference_v2.py::TestQuantizedInference::test_quantized_generate_close_to_full_precision[int8]",  # 11.40s
    "tests/test_inference_v2.py::TestQuantizedInference::test_quantized_moe_generates",  # 14.32s
    "tests/test_inference_v2.py::TestScheduler::test_mixed_sampling_params_respected",  # 10.55s
    "tests/test_inference_v2.py::TestSlidingWindowServing::test_ragged_model_matches_core_forward",  # 9.32s
    "tests/test_inference_v2.py::TestTensorParallelInference::test_tp_sharded_matches_single_device",  # 7.15s
    "tests/test_launcher_elasticity.py::test_launch_propagates_child_failure",  # 23.23s
    "tests/test_launcher_elasticity.py::test_launch_runs_script_per_rank",  # 22.38s
    "tests/test_lora_universal.py::test_lora_adapter_changes_output_and_merge",  # 4.05s
    "tests/test_lora_universal.py::test_universal_pipe_tp_to_fsdp_bitwise",  # 80.73s
    "tests/test_lora_universal.py::test_universal_roundtrip_across_topologies",  # 10.22s
    "tests/test_lora_universal.py::test_universal_strict_missing_atom",  # 7.60s
    "tests/test_models.py::TestForward::test_bert_not_causal",  # 8.93s
    "tests/test_models.py::TestForward::test_causal_masking",  # 5.70s
    "tests/test_models.py::TestForward::test_llama_logits_shape",  # 6.01s
    "tests/test_models.py::TestForward::test_scan_matches_unrolled",  # 14.00s
    "tests/test_models.py::TestTraining::test_bert_mlm_trains",  # 16.58s
    "tests/test_models.py::TestTraining::test_gpt_trains",  # 13.37s
    "tests/test_models.py::TestTraining::test_llama_tp_sp_mesh",  # 45.41s
    "tests/test_models.py::TestTraining::test_llama_zero_trains[0]",  # 27.53s
    "tests/test_models.py::TestTraining::test_llama_zero_trains[3]",  # 32.38s
    "tests/test_models.py::test_learned_positions_ignore_padding",  # 5.97s
    "tests/test_models.py::test_save_attn_out_remat_policy[einsum-save_attn_out]",  # 5.3s (the path's first case compiles its reference too)
    "tests/test_moe_sp.py::TestMixtral::test_expert_params_sharded",  # 6.00s
    "tests/test_moe_sp.py::TestMixtral::test_mixtral_trains",  # 17.35s
    "tests/test_moe_sp.py::TestMoELayer::test_expert_parallel_matches_single",  # 7.22s
    "tests/test_moe_sp.py::TestMoELayer::test_forward_shape_and_aux",  # 5.47s
    "tests/test_moe_sp.py::TestUlysses::test_distributed_attention_matches_local",  # 5.65s
    "tests/test_multiprocess.py::TestMultiProcess::test_init_and_cross_process_psum",  # 9.24s
    "tests/test_multiprocess.py::TestMultiProcess::test_zero1_training_across_processes",  # 14.83s
    "tests/test_multiprocess.py::TestMultiProcess::test_zero3_param_sharding_across_processes",  # 13.66s
    "tests/test_ops.py::TestFlashAttention::test_backward_matches_reference",  # 4.08s
    "tests/test_ops.py::TestFusedLionLamb::test_lamb_matches_reference_math",  # 4.29s
    "tests/test_ops.py::TestFusedLionLamb::test_lamb_transform_trains",  # 7.40s
    "tests/test_ops.py::TestQuantization::test_quantized_psum_scatter",  # 9.14s
    "tests/test_ops.py::TestSlidingWindow::test_kernel_bwd_matches_reference",  # 4.87s
    "tests/test_pipeline.py::test_1f1b_schedule_uses_less_memory_than_gpipe",  # 31.94s
    "tests/test_pipeline.py::test_gpipe_matches_sequential[2]",  # 4.23s
    "tests/test_pipeline.py::test_pipeline_1f1b_matches_gpipe_loss",  # 35.15s
    "tests/test_pipeline.py::test_pipeline_engine_matches_dense",  # 21.23s
    "tests/test_pipeline.py::test_pipeline_engine_matches_dense_alibi",  # 19.40s
    "tests/test_pipeline.py::test_pipeline_engine_with_zero_and_data",  # 18.37s
    "tests/test_pipeline.py::test_pipeline_moe_matches_dense",  # 27.20s
    "tests/test_pipeline.py::test_pipeline_respects_per_microbatch_mask",  # 17.19s
    "tests/test_sparse_grads.py::TestEngineSparseGradients::test_llama_trains_with_sparse_gradients",  # 12.71s
    "tests/test_sparse_grads.py::TestEngineSparseGradients::test_sparse_matches_dense_training",  # 24.38s
    "tests/test_tensor_logger.py::TestEngineIntegration::test_engine_records_inputs_and_loss",  # 26.48s
    "tests/test_zeropp.py::TestQgzWire::test_hlo_moves_int8_collectives",  # 7.57s
    "tests/test_zeropp.py::TestQgzWire::test_replicated_leaf_reduces_over_all_batch_axes",  # 22.59s
    "tests/test_zeropp.py::TestQgzWire::test_training_converges_close_to_exact",  # 12.62s
    "tests/test_zeropp.py::test_hpz_training_matches_plain_stage3",  # 9.50s
    "tests/test_zeropp.py::test_mics_matches_plain_stage3",  # 9.51s
    "tests/test_zeropp.py::test_mics_topology_mapping",  # 6.04s
    "tests/test_zeropp.py::test_quantized_all_gather_st_grad",  # 12.18s
    "tests/test_zeropp.py::test_qwz_trains_and_quantizes",  # 8.11s
    "tests/test_checkpoint.py::TestHFImportBreadth::test_mixtral_logits_parity",  # 3.10s
    "tests/test_checkpoint.py::TestMistralParity::test_sliding_window_logits_match_hf",  # 3.44s
    "tests/test_checkpoint.py::TestTopologyReshape::test_reshape_roundtrip[save_mesh1-load_mesh1]",  # 3.44s
    "tests/test_data_pipeline.py::test_eigenvalue_quadratic_exact",  # 3.17s
    "tests/test_engine.py::test_forward_backward_step_compat",  # 3.60s
    "tests/test_engine.py::test_gradient_accumulation_equivalence",  # 3.16s
    "tests/test_engine.py::test_zero_stages_converge[1]",  # 3.53s
    "tests/test_engine.py::test_zero_stages_converge[2]",  # 3.16s
    "tests/test_engine.py::test_zero_stages_converge[3]",  # 3.18s
    "tests/test_engine.py::test_zero_state_is_sharded[1]",  # 3.15s
    "tests/test_engine.py::test_zero_state_is_sharded[3]",  # 3.53s
    "tests/test_inference_v2.py::TestEngineV2::test_put_and_kv_accounting",  # 3.23s
    "tests/test_lora_universal.py::test_lora_starts_as_identity_adapter",  # 3.94s
    "tests/test_offload.py::test_cpu_offload_matches_device_path",  # 3.06s
    "tests/test_offload.py::test_module_only_load_resyncs_masters",  # 3.08s
    "tests/test_offload.py::test_nvme_matches_cpu_offload",  # 3.02s
    "tests/test_ops.py::TestFPQuantizer::test_optimized_linear_fp8_base",  # 3.13s
    "tests/test_ops.py::TestFusedAdam::test_transform_multi_step",  # 3.94s
    "tests/test_inference_v1.py::TestPerArchTPInference::test_tp2_matches_unsharded[bloom]",  # HF build + tp=2 engine
    "tests/test_inference_v1.py::TestPerArchTPInference::test_tp2_matches_unsharded[falcon]",  # HF build + tp=2 engine
    "tests/test_inference_v1.py::TestPerArchTPInference::test_tp2_matches_unsharded[opt]",  # HF build + tp=2 engine
    "tests/test_inference_v1.py::TestPerArchTPInference::test_tp2_matches_unsharded[gpt_neox]",  # HF build + tp=2 engine
    "tests/test_inference_v2.py::TestSlidingWindowServing::test_window_eviction_bounds_live_kv",  # engine + 31 puts
    "tests/test_checkpoint.py::TestMistralParity::test_sliding_window_logits_match_hf",  # HF parity
    "tests/test_checkpoint.py::TestMistralParity::test_factory_picks_arch_implementation",  # two HF engine builds
    "tests/test_zeropp.py::TestQgzWire::test_training_converges_close_to_exact",  # two engines x 6 steps
    "tests/test_zeropp.py::TestQgzWire::test_replicated_leaf_reduces_over_all_batch_axes",  # shard_map compiles
    "tests/test_engine.py::test_destroyed_engine_raises_clearly",  # engine construction
    "tests/test_models.py::test_ring_sp_mode_matches_ulysses",  # 2 engines x 2 meshes
    "tests/test_lora_universal.py::test_load_universal_config_flag",  # 2 engines + ckpt io
    "tests/test_inference_v2.py::TestKVOffloadRestore::test_preempt_and_resume_matches_uninterrupted",  # 2 engines
    "tests/test_inference_v2.py::TestKVOffloadRestore::test_scheduler_preempts_and_resumes_under_kv_pressure",  # engine + long run
    "tests/test_inference_v2.py::TestFreshPrefillFlash::test_fresh_bucket_uses_flash_and_matches_paged",  # 2 engines
    "tests/test_foundation.py::TestConfigHonesty::test_matmul_precision_and_bf16_accumulation_knobs",  # engine build
    "tests/test_feature_matrix.py::test_qgz_wire_with_fp16_overflow_skip",  # engine + 5 steps
    "tests/test_feature_matrix.py::test_sliding_window_with_ring_sequence_parallel",  # 2 engines
    "tests/test_feature_matrix.py::test_cpu_checkpointing_with_zero3_and_host_offload",  # 2 engines + ckpt
    "tests/test_feature_matrix.py::test_moe_with_sequence_parallel_ulysses",  # moe engine
    "tests/test_feature_matrix.py::test_sliding_window_eviction_with_scheduler_preemption",  # 2 engines
])
