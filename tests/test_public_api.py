"""Public-API surface stability: everything the docs promise imports.

Guards against accidental export regressions between rounds; update this
list deliberately alongside docs/api.md.
"""
import importlib

import pytest

TOP_LEVEL = [
    "transform", "transform_batched", "transform_hybrid",
    "transform_with_model_load", "transform_dense",
    "WorkerLogic", "ParameterServerLogic", "ParameterServerClient",
    "ParameterServer", "SimplePSLogic", "add_pull_limiter",
    "BatchedWorkerLogic", "PushRequest",
    "ShardedParamStore", "StoreSpec", "DenseParameterServer",
    "TransformResult", "make_mesh", "DP_AXIS", "PS_AXIS",
    "StreamingDriver", "DriverConfig",
    "Pull", "Push", "PullAnswer", "WorkerToPS", "PSToWorker",
    "ServingService", "ServingClient", "ServingServer", "QueryEngine",
    "SnapshotManager",
    "MetricsRegistry", "SpanTracer", "TelemetryServer", "get_registry",
    "get_tracer", "prometheus_text", "build_run_report", "write_run_report",
    "HotRowCache", "LeasePolicy", "CachedLookupService",
]

MODULE_SYMBOLS = {
    "flink_parameter_server_tpu.core.store": [
        "StoreSpec", "ShardedParamStore", "pull", "push", "push_counted",
        "Arms", "arms", "step_counts", "publish_counts"],
    "flink_parameter_server_tpu.core.senders": ["SenderPolicy"],
    "flink_parameter_server_tpu.parallel.ring_attention": [
        "ring_attention", "reference_attention"],
    "flink_parameter_server_tpu.parallel.pipeline": [
        "pipeline_apply", "stack_stage_params"],
    "flink_parameter_server_tpu.parallel.multihost": [
        "initialize", "make_multihost_mesh", "process_local_batch_slice"],
    "flink_parameter_server_tpu.training.checkpoint": [
        "save", "restore", "load_model", "JobCheckpointManager"],
    "flink_parameter_server_tpu.training.metrics": ["StepMetrics"],
    "flink_parameter_server_tpu.training.tracing": [
        "profile_trace", "scope", "mesh_scope", "mesh_moved", "mesh_tally",
        "device_memory_stats",
        "register_device_memory_gauges"],
    "flink_parameter_server_tpu.telemetry.registry": [
        "Counter", "Gauge", "Histogram", "MetricsRegistry", "json_line",
        "get_registry", "set_registry"],
    "flink_parameter_server_tpu.telemetry.spans": [
        "SpanTracer", "get_tracer", "set_tracer", "span"],
    "flink_parameter_server_tpu.telemetry.exporter": [
        "prometheus_text", "TelemetryServer", "scrape"],
    "flink_parameter_server_tpu.telemetry.report": [
        "build_run_report", "render_markdown", "write_run_report"],
    "flink_parameter_server_tpu.telemetry.distributed": [
        "TraceContext", "TraceCollector", "new_trace", "parse_token",
        "format_token"],
    "flink_parameter_server_tpu.telemetry.hotkeys": [
        "CountMinSketch", "SpaceSavingTopK", "HotKeySketch",
        "HotKeyAggregator", "get_aggregator", "set_aggregator"],
    "flink_parameter_server_tpu.telemetry.flightrec": [
        "FlightRecorder", "StormDetector", "get_recorder",
        "set_recorder"],
    "flink_parameter_server_tpu.telemetry.slo": [
        "SLOEngine", "SLOSpec", "default_slos", "pull_latency_slo",
        "serving_latency_slo", "staleness_slo", "recovery_time_slo",
        "failover_slo"],
    "flink_parameter_server_tpu.telemetry.profiler": [
        "PhaseProfiler", "StackSampler", "PHASES", "get_profiler",
        "set_profiler", "resolve_profiler"],
    "flink_parameter_server_tpu.utils.net": [
        "LineServer", "NetMeter", "ConnStats", "client_meter",
        "request_lines", "PeerHalfClosed", "count_half_closed"],
    "flink_parameter_server_tpu.nemesis": [
        "ChaosProxy", "ProxiedServer", "NemesisOp", "Scenario",
        "BUILTIN_SCENARIOS", "ScenarioReport", "Verdict",
        "NemesisElasticDriver", "NemesisReplicatedDriver",
        "run_scenario", "search_scenarios", "shrink", "load_corpus",
        "replay_corpus"],
    "flink_parameter_server_tpu.hotcache": [
        "HotRowCache", "LeaseBoard", "LeasePolicy", "StaticHotSet",
        "CachedLookupService", "CachedLookupResult",
        "register_cache", "unregister_cache", "cache_snapshots",
        "split_response_options", "parse_inv_token"],
    "flink_parameter_server_tpu.nemesis.invariants": [
        "check_lease_staleness", "check_parity_bitwise",
        "check_count_parity"],
    "flink_parameter_server_tpu.training.driver": ["TrainingDiverged"],
    "flink_parameter_server_tpu.models.matrix_factorization": [
        "SGDUpdater", "OnlineMatrixFactorization", "MFWorkerLogic",
        "ps_online_mf", "worker_block_rows"],
    "flink_parameter_server_tpu.models.topk_recommender": [
        "query_topk", "make_mf_topk_step"],
    "flink_parameter_server_tpu.models.passive_aggressive": [
        "PARule", "transform_binary", "transform_multiclass",
        "PABinaryWorkerLogic"],
    "flink_parameter_server_tpu.models.word2vec": [
        "SkipGramNS", "train_skipgram", "sample_negatives"],
    "flink_parameter_server_tpu.models.factorization_machine": [
        "FMConfig", "train_fm"],
    "flink_parameter_server_tpu.models.sketches": [
        "CountMinSketch", "BloomCooccurrence", "TugOfWarSketch", "decay"],
    "flink_parameter_server_tpu.models.transformer": [
        "TransformerConfig", "init_params", "forward", "forward_pipelined",
        "lm_loss", "next_token_xent", "param_shardings"],
    "flink_parameter_server_tpu.models.moe": [
        "MoEConfig", "init_moe_params", "moe_apply", "moe_dense"],
    "flink_parameter_server_tpu.ops.topk": ["dense_topk", "sharded_topk"],
    "flink_parameter_server_tpu.ops.packed": [
        "pack_table", "unpack_table", "packed_pull", "lane_shift_deltas",
        "packed_phys_ids"],
    "flink_parameter_server_tpu.ops.row_update": [
        "row_add", "sorted_row_update", "refusal", "refusal_count",
        "scatter_add", "sorted_tile_add", "tile_refusal"],
    "flink_parameter_server_tpu.ops.hashing": [
        "hash_params", "bucket_hash", "sign_hash", "pair_key", "permute_ids"],
    "flink_parameter_server_tpu.ops.dedup": [
        "occurrence_counts", "occurrence_scale"],
    "flink_parameter_server_tpu.data.streams": [
        "microbatches", "partitioned_microbatches", "sparse_feature_batches",
        "prefetch", "from_collection"],
    "flink_parameter_server_tpu.cluster": [
        "ClusterClient", "ClusterConfig", "ClusterDriver",
        "ConsistentHashPartitioner", "RangePartitioner", "ParamShard",
        "ShardServer", "StalenessClock", "StaleEpoch", "FrozenKeys",
        "ShardProcess", "ShardProcSpec"],
    "flink_parameter_server_tpu.utils.frames": [
        "Frame", "FrameError", "encode_request", "encode_response",
        "decode", "rows_to_payload", "rows_from_payload",
        "HELLO_LINE", "VERB_IDS", "ENC_Q8", "WIRE_ENCS",
        "hello_ok_line", "hello_encs"],
    "flink_parameter_server_tpu.compression": [
        "DeltaCompressor", "PushAggregator", "ResidualStore",
        "quantize_q8", "dequantize_q8", "q8_payload",
        "q8_from_payload", "bf16_roundtrip", "record_deltas",
        "compress_record_payload"],
    "flink_parameter_server_tpu.elastic": [
        "ElasticClusterConfig", "ElasticClusterDriver",
        "ElasticController", "ScalePolicy", "MembershipService",
        "PartitionEpoch", "plan_moves", "execute_moves", "Hedger",
        "HedgeBudget"],
    "flink_parameter_server_tpu.replication": [
        "ReplicatedClusterConfig", "ReplicatedClusterDriver",
        "ReplicaShard", "ReplicaChain", "ChainManager", "WALShipper",
        "ReplHub", "PromoteReport", "promote"],
    "flink_parameter_server_tpu.replication.failover": [
        "salvage_records", "verify_against_log"],
    "flink_parameter_server_tpu.resilience.wal": [
        "UpdateWAL", "WALRecord", "encode_frame", "decode_frame",
        "encode_frame_bytes", "decode_frame_bytes"],
    "flink_parameter_server_tpu.serving.follower": [
        "FollowerLookupService", "ChainLookupResult"],
    "flink_parameter_server_tpu.data.movielens": [
        "synthetic_ratings", "load_movielens"],
    "flink_parameter_server_tpu.data.text": [
        "synthetic_corpus", "skipgram_batches", "cooccurrence_pairs"],
    "flink_parameter_server_tpu.data.native_loader": [
        "load_ratings", "stream_batches", "NativeUnavailable"],
    "flink_parameter_server_tpu.utils.initializers": [
        "ranged_random_factor", "normal_factor", "zeros"],
    "flink_parameter_server_tpu.utils.config": ["Parameters"],
    "flink_parameter_server_tpu.serving.snapshot": [
        "TableSnapshot", "SnapshotManager"],
    "flink_parameter_server_tpu.serving.batcher": [
        "RequestBatcher", "QueueFull"],
    "flink_parameter_server_tpu.serving.engine": [
        "QueryEngine", "TopKResult", "LookupResult", "NoSnapshotError"],
    "flink_parameter_server_tpu.serving.server": [
        "ServingService", "ServingClient", "ServingServer",
        "tcp_request", "parse_response", "format_response"],
    "flink_parameter_server_tpu.serving.metrics": ["ServingMetrics"],
    "flink_parameter_server_tpu.workloads": [
        "Workload", "WorkloadParams", "WorkloadRegistry",
        "DenseCombineLogic", "create_workload", "workload_names",
        "get_workload_registry", "build_cluster_driver",
        "resolve_workload", "serve_workload", "workload_table",
        "run_streaming", "WorkloadServingServer",
        "WorkloadServingClient"],
}


def test_top_level_exports():
    import flink_parameter_server_tpu as fps

    missing = [n for n in TOP_LEVEL if not hasattr(fps, n)]
    assert not missing, missing


@pytest.mark.parametrize("module", sorted(MODULE_SYMBOLS))
def test_module_symbols(module):
    mod = importlib.import_module(module)
    missing = [n for n in MODULE_SYMBOLS[module] if not hasattr(mod, n)]
    assert not missing, (module, missing)


# -- the seam between the store and the two layers above it -------------------
# Which arm a pull or a push takes is `core/store.arms`' to read, and a count
# is published by whoever made it (`core/store.publish_counts`, a logic's
# `publish_counts`): the step and the driver name neither.
COUNTER_PREFIXES = ("ps_", "bag_", "fm_", "dlrm_", "keyed_")


@pytest.mark.parametrize("module", ["training/driver.py", "core/transform.py"])
def test_the_driver_and_the_step_name_no_counter_and_no_private_of_the_store(
        module):
    import ast
    import os

    import flink_parameter_server_tpu as fps

    path = os.path.join(os.path.dirname(fps.__file__), module)
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    named = sorted({
        node.value for node in ast.walk(tree)
        if isinstance(node, ast.Constant) and isinstance(node.value, str)
        and node.value.startswith(COUNTER_PREFIXES)
        # (the reference's knob, an argument of `transform`: no counter)
        and node.value != "ps_parallelism"})
    assert not named, named
    private = sorted({
        node.attr for node in ast.walk(tree)
        if isinstance(node, ast.Attribute) and node.attr.startswith("_")
        and not node.attr.startswith("__")
        and isinstance(node.value, ast.Name)
        and node.value.id in ("store_mod", "store")})
    assert not private, private
    takes = sorted({
        node.attr if isinstance(node, ast.Attribute) else node.id
        for node in ast.walk(tree)
        if isinstance(node, (ast.Attribute, ast.Name))
        and (node.attr if isinstance(node, ast.Attribute) else node.id
             ).endswith("_takes")})
    assert not takes, takes


@pytest.mark.parametrize("steps_per_call", [1, 2])
def test_a_logic_defined_here_publishes_its_own_count_through_the_driver(
        steps_per_call):
    """A new family's counter costs its own file: this logic counts one
    thing in its step, names its gauge in ``publish_counts``, and the
    StreamingDriver sets it (the total over a scanned dispatch) with no
    edit anywhere else."""
    import jax.numpy as jnp
    import numpy as np

    from flink_parameter_server_tpu import (
        BatchedWorkerLogic, DriverConfig, PushRequest, ShardedParamStore,
        StreamingDriver)
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry

    class Toy(BatchedWorkerLogic):
        def init_state(self, rng):
            return ()

        def keys(self, batch):
            return batch["ids"]

        def step(self, state, batch, pulled):
            out = {"toy_even_keys": jnp.sum(
                batch["ids"] % 2 == 0, dtype=jnp.int32)}
            return state, PushRequest(batch["ids"], jnp.ones_like(pulled)), out

        def publish_counts(self, outs, registry, total, peak):
            registry.gauge("toy_even_keys", component="train").set(
                total(outs["toy_even_keys"]))

    registry = MetricsRegistry()
    batches = [{"ids": np.arange(i, i + 8, dtype=np.int32)} for i in (0, 3, 4, 9)]
    driver = StreamingDriver(
        Toy(), ShardedParamStore.create(64, (4,)), registry=registry,
        config=DriverConfig(dump_model=False, steps_per_call=steps_per_call))
    result = driver.run(iter(batches))
    gauges = registry.snapshot()
    # the newest dispatch: the last batch, or the last two of a scanned one
    assert gauges["toy_even_keys"][0]["value"] == 4 * steps_per_call, gauges
    table = np.asarray(result.store.values())
    assert table[:17].sum() == 4 * 8 * 4 and not table[17:].any()
