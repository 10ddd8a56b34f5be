#!/usr/bin/env python
"""Regenerate ballista_pb2.py without protoc.

The container image carries no protoc / grpc_tools, so the generated
module cannot be rebuilt from ballista.proto the normal way. This script
instead treats the CHECKED-IN generated module as the carrier of the
serialized FileDescriptorProto, mutates that descriptor programmatically
(google.protobuf.descriptor_pb2 is a full in-memory model of a .proto
file), and re-emits the module. ballista.proto remains the human-readable
source of truth: every mutation made here must be mirrored there by hand.

Idempotent: additions are keyed by message/field name and skipped when
already present, so re-running is safe.

Usage: python dev/gen_proto_patch.py
"""

from __future__ import annotations

import ast
import os
import re

from google.protobuf import descriptor_pb2 as dp

HERE = os.path.dirname(os.path.abspath(__file__))
PB2 = os.path.join(HERE, "..", "ballista_tpu", "proto", "ballista_pb2.py")

F = dp.FieldDescriptorProto


def load_serialized_blob(path: str) -> bytes:
    """Pull the AddSerializedFile(b'...') literal out of the generated
    module WITHOUT importing it (importing would register the old file in
    the default descriptor pool and block re-registration)."""
    text = open(path).read()
    m = re.search(r"AddSerializedFile\(\s*(b(?:'|\").*?(?:'|\"))\s*\)", text,
                  re.DOTALL)
    if m is None:
        raise SystemExit(f"no AddSerializedFile literal in {path}")
    return ast.literal_eval(m.group(1))


def get_message(fdp: dp.FileDescriptorProto, name: str) -> dp.DescriptorProto:
    for msg in fdp.message_type:
        if msg.name == name:
            return msg
    raise SystemExit(f"message {name} not found")


def has_field(msg: dp.DescriptorProto, name: str) -> bool:
    return any(f.name == name for f in msg.field)


def has_message(fdp: dp.FileDescriptorProto, name: str) -> bool:
    return any(m.name == name for m in fdp.message_type)


def add_field(msg, name, number, ftype, *, type_name=None, repeated=False,
              oneof=None):
    if has_field(msg, name):
        return
    f = msg.field.add(
        name=name, number=number, type=ftype,
        label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL,
    )
    if type_name is not None:
        f.type_name = type_name
    if oneof is not None:
        f.oneof_index = next(
            i for i, o in enumerate(msg.oneof_decl) if o.name == oneof
        )


def apply_observability(fdp: dp.FileDescriptorProto) -> None:
    # -- EXPLAIN ANALYZE -----------------------------------------------------
    add_field(get_message(fdp, "ExplainNode"), "analyze", 3, F.TYPE_BOOL)

    if not has_message(fdp, "PhysicalExplainAnalyzeNode"):
        m = fdp.message_type.add(name="PhysicalExplainAnalyzeNode")
        add_field(m, "input", 1, F.TYPE_MESSAGE,
                  type_name=".ballista_tpu.PhysicalPlanNode")
        add_field(m, "verbose", 2, F.TYPE_BOOL)
        add_field(m, "logical_text", 3, F.TYPE_STRING)
    add_field(get_message(fdp, "PhysicalPlanNode"), "explain_analyze", 17,
              F.TYPE_MESSAGE,
              type_name=".ballista_tpu.PhysicalExplainAnalyzeNode",
              oneof="plan_type")

    # -- per-task / per-stage metrics ---------------------------------------
    if not has_message(fdp, "MetricValue"):
        m = fdp.message_type.add(name="MetricValue")
        m.oneof_decl.add(name="value")
        add_field(m, "name", 1, F.TYPE_STRING)
        add_field(m, "counter", 2, F.TYPE_INT64, oneof="value")
        add_field(m, "elapsed_secs", 3, F.TYPE_DOUBLE, oneof="value")
        add_field(m, "gauge", 4, F.TYPE_DOUBLE, oneof="value")

    if not has_message(fdp, "OperatorMetrics"):
        m = fdp.message_type.add(name="OperatorMetrics")
        add_field(m, "operator", 1, F.TYPE_STRING)
        add_field(m, "depth", 2, F.TYPE_UINT32)
        add_field(m, "metrics", 3, F.TYPE_MESSAGE,
                  type_name=".ballista_tpu.MetricValue", repeated=True)

    if not has_message(fdp, "TaskMetrics"):
        m = fdp.message_type.add(name="TaskMetrics")
        add_field(m, "operators", 1, F.TYPE_MESSAGE,
                  type_name=".ballista_tpu.OperatorMetrics", repeated=True)
        add_field(m, "elapsed_total_secs", 2, F.TYPE_DOUBLE)

    if not has_message(fdp, "StageMetrics"):
        m = fdp.message_type.add(name="StageMetrics")
        add_field(m, "stage_id", 1, F.TYPE_UINT32)
        add_field(m, "num_tasks", 2, F.TYPE_UINT32)
        add_field(m, "metrics", 3, F.TYPE_MESSAGE,
                  type_name=".ballista_tpu.TaskMetrics")

    add_field(get_message(fdp, "CompletedTask"), "metrics", 4,
              F.TYPE_MESSAGE, type_name=".ballista_tpu.TaskMetrics")
    add_field(get_message(fdp, "CompletedJob"), "stage_metrics", 2,
              F.TYPE_MESSAGE, type_name=".ballista_tpu.StageMetrics",
              repeated=True)


def apply_adaptive(fdp: dp.FileDescriptorProto) -> None:
    """PR 2: adaptive query execution wire fields (mirrored by hand in
    ballista.proto — keep the two in sync; dev/check_proto_sync.py
    guards the drift)."""
    # per-output-partition shuffle byte histogram on task stats
    add_field(get_message(fdp, "PartitionStats"), "shuffle_partition_bytes",
              5, F.TYPE_INT64, repeated=True)

    # adaptive reader layout on ShuffleReaderNode
    if not has_message(fdp, "ShuffleReadRange"):
        m = fdp.message_type.add(name="ShuffleReadRange")
        add_field(m, "output_lo", 1, F.TYPE_UINT32)
        add_field(m, "output_hi", 2, F.TYPE_UINT32)
        add_field(m, "producer_lo", 3, F.TYPE_UINT32)
        add_field(m, "producer_hi", 4, F.TYPE_UINT32)
    if not has_message(fdp, "ShuffleReadPartition"):
        m = fdp.message_type.add(name="ShuffleReadPartition")
        add_field(m, "ranges", 1, F.TYPE_MESSAGE,
                  type_name=".ballista_tpu.ShuffleReadRange", repeated=True)
    reader = get_message(fdp, "ShuffleReaderNode")
    add_field(reader, "read_partitions", 3, F.TYPE_MESSAGE,
              type_name=".ballista_tpu.ShuffleReadPartition", repeated=True)
    add_field(reader, "hash_columns", 4, F.TYPE_STRING, repeated=True)
    add_field(reader, "original_partitions", 5, F.TYPE_UINT32)

    # join demotion annotation
    add_field(get_message(fdp, "PhysicalJoinNode"), "adaptive_note", 7,
              F.TYPE_STRING)

    # stage versioning: definitions carry it, status reports echo it
    add_field(get_message(fdp, "TaskDefinition"), "stage_version", 5,
              F.TYPE_UINT32)
    add_field(get_message(fdp, "TaskStatus"), "stage_version", 5,
              F.TYPE_UINT32)


def apply_health(fdp: dp.FileDescriptorProto) -> None:
    """PR 5: executor heartbeats carry resource gauges for the
    scheduler's health plane (mirrored by hand in ballista.proto;
    dev/check_proto_sync.py guards the drift)."""
    if not has_message(fdp, "ExecutorResources"):
        m = fdp.message_type.add(name="ExecutorResources")
        add_field(m, "rss_bytes", 1, F.TYPE_UINT64)
        add_field(m, "device_bytes", 2, F.TYPE_UINT64)
        add_field(m, "inflight_tasks", 3, F.TYPE_UINT32)
        add_field(m, "ingest_pool_depth", 4, F.TYPE_UINT32)
        add_field(m, "peak_host_bytes", 5, F.TYPE_UINT64)
    add_field(get_message(fdp, "ExecutorMetadata"), "resources", 5,
              F.TYPE_MESSAGE, type_name=".ballista_tpu.ExecutorResources")


def apply_profiler(fdp: dp.FileDescriptorProto) -> None:
    """PR 7: distributed profiler wire fields (mirrored by hand in
    ballista.proto; dev/check_proto_sync.py guards the drift) — the
    per-task profile window riding CompletedTask, and the GetJobProfile
    RPC messages serving merged per-job artifacts to clients."""
    if not has_message(fdp, "TaskProfile"):
        m = fdp.message_type.add(name="TaskProfile")
        add_field(m, "t0", 1, F.TYPE_DOUBLE)
        add_field(m, "wall_seconds", 2, F.TYPE_DOUBLE)
        add_field(m, "pid", 3, F.TYPE_UINT32)
        add_field(m, "role", 4, F.TYPE_STRING)
        add_field(m, "executor_id", 5, F.TYPE_STRING)
        add_field(m, "records_json", 6, F.TYPE_BYTES)
        add_field(m, "phases_json", 7, F.TYPE_BYTES)
        add_field(m, "compile_json", 8, F.TYPE_BYTES)
        add_field(m, "memory_json", 9, F.TYPE_BYTES)
    add_field(get_message(fdp, "CompletedTask"), "profile", 5,
              F.TYPE_MESSAGE, type_name=".ballista_tpu.TaskProfile")

    if not has_message(fdp, "GetJobProfileParams"):
        m = fdp.message_type.add(name="GetJobProfileParams")
        add_field(m, "job_id", 1, F.TYPE_STRING)
    if not has_message(fdp, "GetJobProfileResult"):
        m = fdp.message_type.add(name="GetJobProfileResult")
        add_field(m, "artifact_json", 1, F.TYPE_BYTES)
        add_field(m, "error", 2, F.TYPE_STRING)


def apply_systables(fdp: dp.FileDescriptorProto) -> None:
    """PR 8: SQL-queryable system.* tables (mirrored by hand in
    ballista.proto; dev/check_proto_sync.py guards the drift) — the
    serialized-snapshot payload on TableSourceDesc and the
    GetSystemTable RPC serving scheduler snapshots to remote scans."""
    add_field(get_message(fdp, "TableSourceDesc"), "payload", 8,
              F.TYPE_BYTES)

    if not has_message(fdp, "GetSystemTableParams"):
        m = fdp.message_type.add(name="GetSystemTableParams")
        add_field(m, "table", 1, F.TYPE_STRING)
    if not has_message(fdp, "GetSystemTableResult"):
        m = fdp.message_type.add(name="GetSystemTableResult")
        add_field(m, "rows_json", 1, F.TYPE_BYTES)
        add_field(m, "error", 2, F.TYPE_STRING)


def apply_lifecycle(fdp: dp.FileDescriptorProto) -> None:
    """PR 9: query lifecycle control plane (mirrored by hand in
    ballista.proto; dev/check_proto_sync.py guards the drift) — the
    CancelJob RPC messages, the terminal CancelledJob status, the
    server-side deadline on ExecuteQueryParams, and the cancelled-job
    piggyback on PollWorkResult."""
    if not has_message(fdp, "CancelledJob"):
        m = fdp.message_type.add(name="CancelledJob")
        add_field(m, "reason", 1, F.TYPE_STRING)
    add_field(get_message(fdp, "JobStatus"), "cancelled", 5,
              F.TYPE_MESSAGE, type_name=".ballista_tpu.CancelledJob",
              oneof="status")

    add_field(get_message(fdp, "PollWorkResult"), "cancelled_jobs", 2,
              F.TYPE_STRING, repeated=True)
    add_field(get_message(fdp, "ExecuteQueryParams"), "deadline_secs", 5,
              F.TYPE_DOUBLE)

    if not has_message(fdp, "CancelJobParams"):
        m = fdp.message_type.add(name="CancelJobParams")
        add_field(m, "job_id", 1, F.TYPE_STRING)
        add_field(m, "reason", 2, F.TYPE_STRING)
    if not has_message(fdp, "CancelJobResult"):
        m = fdp.message_type.add(name="CancelJobResult")
        add_field(m, "cancelled", 1, F.TYPE_BOOL)
        add_field(m, "state", 2, F.TYPE_STRING)


def apply_progress(fdp: dp.FileDescriptorProto) -> None:
    """PR 10: live query progress plane (mirrored by hand in
    ballista.proto; dev/check_proto_sync.py guards the drift) — compact
    per-task progress samples piggybacked on the PollWork heartbeat,
    and the live job progress model served through GetJobStatus."""
    if not has_message(fdp, "TaskProgress"):
        m = fdp.message_type.add(name="TaskProgress")
        add_field(m, "partition_id", 1, F.TYPE_MESSAGE,
                  type_name=".ballista_tpu.PartitionId")
        add_field(m, "stage_version", 2, F.TYPE_UINT32)
        add_field(m, "operator", 3, F.TYPE_STRING)
        add_field(m, "rows_so_far", 4, F.TYPE_UINT64)
        add_field(m, "input_rows_total", 5, F.TYPE_UINT64)
        add_field(m, "bytes_so_far", 6, F.TYPE_UINT64)
        add_field(m, "elapsed_seconds", 7, F.TYPE_DOUBLE)
    add_field(get_message(fdp, "PollWorkParams"), "task_progress", 4,
              F.TYPE_MESSAGE, type_name=".ballista_tpu.TaskProgress",
              repeated=True)

    if not has_message(fdp, "StageProgress"):
        m = fdp.message_type.add(name="StageProgress")
        add_field(m, "stage_id", 1, F.TYPE_UINT32)
        add_field(m, "tasks_total", 2, F.TYPE_UINT32)
        add_field(m, "tasks_running", 3, F.TYPE_UINT32)
        add_field(m, "tasks_completed", 4, F.TYPE_UINT32)
        add_field(m, "fraction", 5, F.TYPE_DOUBLE)
        add_field(m, "eta_seconds", 6, F.TYPE_DOUBLE)
        add_field(m, "rows_so_far", 7, F.TYPE_UINT64)
        add_field(m, "bytes_so_far", 8, F.TYPE_UINT64)
    if not has_message(fdp, "JobProgress"):
        m = fdp.message_type.add(name="JobProgress")
        add_field(m, "fraction", 1, F.TYPE_DOUBLE)
        add_field(m, "eta_seconds", 2, F.TYPE_DOUBLE)
        add_field(m, "wall_seconds", 3, F.TYPE_DOUBLE)
        add_field(m, "tasks_total", 4, F.TYPE_UINT32)
        add_field(m, "tasks_running", 5, F.TYPE_UINT32)
        add_field(m, "tasks_queued", 6, F.TYPE_UINT32)
        add_field(m, "tasks_completed", 7, F.TYPE_UINT32)
        add_field(m, "stages", 8, F.TYPE_MESSAGE,
                  type_name=".ballista_tpu.StageProgress", repeated=True)
    add_field(get_message(fdp, "GetJobStatusResult"), "progress", 2,
              F.TYPE_MESSAGE, type_name=".ballista_tpu.JobProgress")


def apply_spill(fdp: dp.FileDescriptorProto) -> None:
    """PR 12: memory-governed streaming shuffle (mirrored by hand in
    ballista.proto; dev/check_proto_sync.py guards the drift) — the
    data-plane chunk-stream negotiation field on Action and the shuffle
    governor gauges riding the executor heartbeat."""
    add_field(get_message(fdp, "Action"), "stream_window", 11,
              F.TYPE_UINT64)
    add_field(get_message(fdp, "Action"), "stream_chunk", 12,
              F.TYPE_UINT64)
    res = get_message(fdp, "ExecutorResources")
    add_field(res, "shuffle_inflight_bytes", 6, F.TYPE_UINT64)
    add_field(res, "spill_bytes_total", 7, F.TYPE_UINT64)


def apply_admission(fdp: dp.FileDescriptorProto) -> None:
    """PR 15: multi-tenant admission plane (mirrored by hand in
    ballista.proto; dev/check_proto_sync.py guards the drift) — the
    structured shed on ExecuteQueryResult, queue position/reason on the
    queued JobStatus, and the retryable retry-after on FailedJob
    (queue-timeout sheds travel as a terminal failed status)."""
    res = get_message(fdp, "ExecuteQueryResult")
    add_field(res, "error", 2, F.TYPE_STRING)
    add_field(res, "retry_after_secs", 3, F.TYPE_DOUBLE)

    q = get_message(fdp, "QueuedJob")
    add_field(q, "queue_position", 1, F.TYPE_UINT32)
    add_field(q, "reason", 2, F.TYPE_STRING)
    add_field(q, "queued_seconds", 3, F.TYPE_DOUBLE)

    add_field(get_message(fdp, "FailedJob"), "retry_after_secs", 2,
              F.TYPE_DOUBLE)


def apply_controlplane(fdp: dp.FileDescriptorProto) -> None:
    """PR 17: durable elastic control plane (mirrored by hand in
    ballista.proto; dev/check_proto_sync.py guards the drift) — the
    recovered marker on the queued JobStatus (the entry was rebuilt
    from the journal by a restarted scheduler) and the autoscaler's
    graceful-drain piggyback on PollWorkResult (the executor stops
    accepting tasks and exits once its in-flight work completes)."""
    add_field(get_message(fdp, "QueuedJob"), "recovered", 4,
              F.TYPE_BOOL)
    add_field(get_message(fdp, "PollWorkResult"), "drain", 3,
              F.TYPE_BOOL)


def apply_handoff(fdp: dp.FileDescriptorProto) -> None:
    """PR 26: event-driven hand-off (mirrored by hand in
    ballista.proto) — how long the CALLER lets the scheduler hold the
    call for the event it waits for: a ready task (PollWork) or the
    job's terminal status (GetJobStatus). 0, and every caller that
    predates the field, is answered at once."""
    add_field(get_message(fdp, "PollWorkParams"), "wait_secs", 5,
              F.TYPE_DOUBLE)
    add_field(get_message(fdp, "GetJobStatusParams"), "wait_secs", 2,
              F.TYPE_DOUBLE)


def apply_join_columns(fdp: dp.FileDescriptorProto) -> None:
    """PR 42: a physical join carries the columns it emits (mirrored by
    hand in ballista.proto). Empty = everything, as before the field."""
    add_field(get_message(fdp, "PhysicalJoinNode"), "out_columns", 8,
              F.TYPE_STRING, repeated=True)
    add_field(get_message(fdp, "PhysicalMeshJoinNode"), "out_columns", 7,
              F.TYPE_STRING, repeated=True)


def apply_release(fdp: dp.FileDescriptorProto) -> None:
    """PR 43: a finished job's files go once its client has fetched the
    result (mirrored by hand in ballista.proto): the client says so on a
    last GetJobStatus, and the scheduler passes the id to each executor
    once, on its next poll."""
    add_field(get_message(fdp, "GetJobStatusParams"), "fetched", 3,
              F.TYPE_BOOL)
    add_field(get_message(fdp, "PollWorkResult"), "released_jobs", 4,
              F.TYPE_STRING, repeated=True)


TEMPLATE = '''# -*- coding: utf-8 -*-
# Generated by dev/gen_proto_patch.py (no protoc in this image). DO NOT EDIT!
# source: ballista.proto
"""Generated protocol buffer code."""
from google.protobuf.internal import builder as _builder
from google.protobuf import descriptor as _descriptor
from google.protobuf import descriptor_pool as _descriptor_pool
from google.protobuf import symbol_database as _symbol_database
# @@protoc_insertion_point(imports)

_sym_db = _symbol_database.Default()




DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile({blob!r})

_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())
_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, \'ballista_pb2\', globals())
# @@protoc_insertion_point(module_scope)
'''


def main() -> None:
    blob = load_serialized_blob(PB2)
    fdp = dp.FileDescriptorProto.FromString(blob)
    apply_observability(fdp)
    apply_adaptive(fdp)
    apply_health(fdp)
    apply_profiler(fdp)
    apply_systables(fdp)
    apply_lifecycle(fdp)
    apply_progress(fdp)
    apply_spill(fdp)
    apply_admission(fdp)
    apply_controlplane(fdp)
    apply_handoff(fdp)
    apply_join_columns(fdp)
    apply_release(fdp)
    out = TEMPLATE.format(blob=fdp.SerializeToString())
    with open(PB2, "w") as f:
        f.write(out)
    print(f"wrote {os.path.normpath(PB2)} "
          f"({len(fdp.message_type)} messages)")


if __name__ == "__main__":
    main()
