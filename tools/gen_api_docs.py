"""Generate docs/api.md from the package's public docstrings.

Run from the repo root: ``python tools/gen_api_docs.py``. Kept as a script
(not a build step) so the committed docs/api.md is reviewable; CI checks it
is in sync via tests/test_examples.py.
"""

from __future__ import annotations

import importlib
import inspect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

SECTIONS = [
    ("Lifecycle & topology", "horovod_tpu", [
        "init", "shutdown", "is_initialized", "rank", "size", "local_rank",
        "local_size", "cross_rank", "cross_size", "is_homogeneous", "mesh"]),
    ("Collectives (sync)", "horovod_tpu", [
        "allreduce", "grouped_allreduce", "allgather", "broadcast",
        "alltoall", "reducescatter", "barrier", "join"]),
    ("Collectives (async handles)", "horovod_tpu", [
        "allreduce_async", "grouped_allreduce_async", "allgather_async",
        "broadcast_async", "alltoall_async", "reducescatter_async", "poll",
        "synchronize"]),
    ("Step-capture replay", "horovod_tpu", [
        "step_begin", "step_end", "step"]),
    ("", "horovod_tpu.core.replay", []),
    ("Fault injection & robustness", "horovod_tpu.faults", [
        "failpoint", "arm", "disarm", "break_hangs", "hits", "arm_from_kv",
        "enabled", "FaultRegistry", "DROP"]),
    ("", "horovod_tpu.common.retry", ["retrying", "backoff_delays"]),
    ("Metrics & telemetry", "horovod_tpu", ["metrics_snapshot"]),
    ("", "horovod_tpu.metrics", [
        "registry", "Registry", "Counter", "Gauge", "Histogram", "EventLog",
        "MetricsEmitter", "render_prometheus", "render_prometheus_cluster",
        "publish_snapshot"]),
    ("Step health & anomaly detection", "horovod_tpu.observability", [
        "StepDigest", "RollingBaseline", "AnomalyDetector", "Anomaly",
        "StepHealthMonitor", "FlightDumper", "HBMSampler",
        "ANOMALY_CLASSES"]),
    ("State synchronization", "horovod_tpu", [
        "broadcast_parameters", "broadcast_optimizer_state",
        "broadcast_object", "allgather_object", "allreduce_sparse"]),
    ("Optimizers & compression", "horovod_tpu", [
        "DistributedOptimizer", "DistributedDeltaAdasumOptimizer",
        "Compression"]),
    ("Gradient wire codecs", "horovod_tpu.ops.compression", [
        "resolve_codec", "wire_itemsize", "encode", "decode", "decode_sum",
        "ef_encode", "FP8Compressor", "Int8Compressor"]),
    ("", "horovod_tpu.ops.collectives", [
        "build_codec_allreduce", "codec_residual_elems", "ef_allreduce_p",
        "replay_residual_layout"]),
    ("Functional optimizer API", "horovod_tpu.optimizer", [
        "distributed", "DistributedState", "DistributedEagerOptimizer",
        "ShardedEagerState", "zero1_state_specs",
        "distributed_delta_adasum"]),
    ("Sharded (ZeRO-1) collective builders", "horovod_tpu.ops.collectives", [
        "build_grouped_reducescatter", "build_grouped_allgather",
        "build_sharded_step", "build_sharded_update", "build_replay_step",
        "shard_spec"]),
    ("Topology & algorithm selection", "horovod_tpu.parallel.mesh", [
        "Topology", "detect_topology", "world_mesh", "hierarchical_mesh",
        "training_mesh", "multislice_mesh"]),
    ("", "horovod_tpu.ops.collectives", [
        "choose_algorithm", "validate_algorithm", "link_split",
        "tree_groups", "build_tree_allreduce",
        "build_hierarchical_allreduce", "build_hierarchical_allgather"]),
    ("Comm/compute overlap", "horovod_tpu.common.env", ["apply_xla_lhs"]),
    ("Reduce ops & exceptions", "horovod_tpu", [
        "ReduceOp", "HorovodInternalError", "HostsUpdatedInterrupt",
        "DuplicateNameError"]),
    ("Elastic training", "horovod_tpu.elastic", [
        "run", "State", "ObjectState", "TPUState"]),
    ("Checkpointing", "horovod_tpu.checkpoint", [
        "CheckpointManager", "RestoreResult", "CheckpointRestoreError",
        "build_manifest", "validate_manifest", "generation_complete",
        "checksum", "reshard_ranges", "zero1_reshard"]),
    ("Cluster run API", "horovod_tpu.runner", [
        "run", "run_elastic"]),
    ("Replicated control plane", "horovod_tpu.runner.replication", [
        "ReplicaCoordinator", "ReplicationConfig"]),
    ("", "horovod_tpu.runner.http_client", [
        "Endpoints", "resolve_endpoints", "parse_endpoint_spec",
        "KVBackpressure"]),
    ("Hierarchical telemetry", "horovod_tpu.runner.aggregator", [
        "SliceAggregator", "TelemetryRoute"]),
    ("Estimator & store", "horovod_tpu", []),
    ("Models", "horovod_tpu.models.transformer", [
        "TransformerConfig", "LayerKind", "init_params", "forward_block",
        "mamba_mix", "lean_lm_loss", "lm_loss_terms",
        "make_train_step", "make_spmd_loss", "shard_params",
        "forward_exits", "exit_distribution", "forward_routes",
        "forward_heads"]),
    ("", "horovod_tpu.models.vit", ["ViT", "ViT_B16", "ViT_S16"]),
    ("", "horovod_tpu.models.resnet", ["ResNet50", "ResNet101", "ResNet152"]),
    ("Parallelism kernels", "horovod_tpu.parallel.ring_attention", [
        "ring_attention_p", "local_attention"]),
    ("", "horovod_tpu.parallel.ulysses", ["ulysses_attention_p"]),
    ("", "horovod_tpu.parallel.flash_attention", ["flash_attention_local",
                                                   "attention_kernel"]),
    ("", "horovod_tpu.parallel.ssd", ["ssd_chunked"]),
    ("", "horovod_tpu.parallel.gated_delta", ["gated_delta_chunked"]),
    ("", "horovod_tpu.parallel.moe", ["moe_layer_p", "MoEParams"]),
    ("", "horovod_tpu.parallel.pipeline", []),
    ("Ops", "horovod_tpu.ops.sync_batch_norm", []),
    ("", "horovod_tpu.ops.fused_batch_norm", ["FusedBatchNorm",
                                              "batch_norm_train"]),
    ("", "horovod_tpu.ops.adasum", ["adasum_combine"]),
    ("Callbacks", "horovod_tpu.callbacks", []),
    ("Observability", "horovod_tpu.timeline", []),
    ("", "horovod_tpu.stall_inspector", []),
    ("Cross-rank tracing", "horovod_tpu.trace", [
        "TraceRecorder", "TracePublisher", "publish_segment",
        "merge_segments", "collective_skew", "modal_straggler",
        "observe_skew",
        "render_cluster_trace", "clock_offset", "load_trace_events",
        "load_trace_file", "make_corr", "parse_corr"]),
    ("Autotuning", "horovod_tpu.autotune.parameter_manager", []),
    ("", "horovod_tpu.autotune.calibration", [
        "fit_alpha_beta", "derived_tree_threshold_bytes",
        "derived_hier_threshold_bytes", "probe_link_times",
        "agree_times", "fit_measured_topology", "derived_thresholds",
        "calibrate_engine"]),
    ("", "horovod_tpu.autotune.persistence", []),
    ("Static analysis", "horovod_tpu.analysis", []),
    ("", "horovod_tpu.analysis.lockcheck", []),
    ("", "horovod_tpu.analysis.divcheck", []),
    ("", "horovod_tpu.analysis.knobcheck", []),
    ("", "horovod_tpu.analysis.errflow", []),
    ("", "horovod_tpu.analysis.faultcheck", []),
    ("", "horovod_tpu.analysis.metriccheck", []),
    ("", "horovod_tpu.common.knobs", []),
]


def _first_para(doc: str) -> str:
    import re
    doc = inspect.cleandoc(doc or "")
    para = doc.split("\n\n")[0].replace("\n", " ").strip()
    # dataclass-generated docstrings embed default-object reprs with
    # per-process memory addresses — strip them for reproducibility
    return re.sub(r" at 0x[0-9a-f]+", "", para)


def _sig(obj) -> str:
    import re
    try:
        sig = str(inspect.signature(obj))
    except (TypeError, ValueError):
        return ""
    # default-value reprs can embed per-process memory addresses (e.g. flax
    # sentinel objects) — strip them so the output is reproducible
    return re.sub(r" at 0x[0-9a-f]+", "", sig)


def _knob_rows(specs, internal):
    rows = []
    for name in sorted(specs):
        spec = specs[name]
        if bool(spec.get("internal")) is not internal:
            continue
        typ = spec["type"]
        if typ == "choice" and spec.get("choices"):
            typ = "choice: " + "/".join(spec["choices"])
        default = str(spec.get("default", "")) or "(unset)"
        help_str = " ".join(spec["help"].split())
        rows.append(f"| `{name}` | {typ} | `{default}` | {help_str} |")
    return rows


def knob_section():
    """The generated "Configuration knobs" section: rendered from
    horovod_tpu.common.knobs.KNOB_SPECS (the registry the knob lint in
    tools/check.py keeps in sync with the code's actual env reads)."""
    from horovod_tpu.common.knobs import KNOB_SPECS
    out = ["## Configuration knobs",
           "",
           "Generated from `horovod_tpu.common.knobs.KNOB_SPECS` — the "
           "central registry of every environment variable the framework "
           "reads. `python tools/check.py --only knobs` fails on knobs "
           "read but not declared here, and on declared knobs nothing "
           "reads (see docs/static_analysis.md).",
           "",
           "| knob | type | default | description |",
           "| --- | --- | --- | --- |"]
    out += _knob_rows(KNOB_SPECS, internal=False)
    out += ["",
            "Launcher/rendezvous plumbing (set by `tpurun` and the "
            "elastic driver; users rarely set these directly):",
            "",
            "| variable | type | default | description |",
            "| --- | --- | --- | --- |"]
    out += _knob_rows(KNOB_SPECS, internal=True)
    out.append("")
    return out


def main():
    out = ["# API reference",
           "",
           "Generated by `python tools/gen_api_docs.py` from the public "
           "docstrings. The import surface is `import horovod_tpu as hvd` "
           "(drop-in for the reference's `import horovod.torch as hvd` "
           "call sites — see docs/migrate.md for the mapping).",
           ""]
    out.extend(knob_section())
    for title, modname, names in SECTIONS:
        mod = importlib.import_module(modname)
        if title:
            out.append(f"## {title}")
            out.append("")
        if not names:
            para = _first_para(mod.__doc__)
            out.append(f"**module `{modname}`** — {para}")
            out.append("")
            continue
        out.append(f"*module `{modname}`*")
        out.append("")
        for n in names:
            obj = getattr(mod, n, None)
            if obj is None:
                continue
            doc = _first_para(getattr(obj, "__doc__", "") or "")
            if inspect.isclass(obj):
                out.append(f"- **`{n}`** (class) — {doc}")
            else:
                out.append(f"- **`{n}{_sig(obj)}`** — {doc}")
        out.append("")
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "api.md")
    with open(path, "w") as f:
        f.write("\n".join(out))
    print(f"wrote {path} ({len(out)} lines)")


if __name__ == "__main__":
    main()
