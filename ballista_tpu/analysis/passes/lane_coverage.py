"""lane-coverage: every span name must be known to an attribution map.

The profiler's lane decomposition (``export.compute_lanes`` via
``export.LANE_SPANS``) and the latency ledger's span-derived phases
(``ledger.LEDGER_SPANS``) both attribute query wall time by SPAN NAME.
A new ``trace_span("foo.bar", ...)`` that neither map knows about
silently lands in whatever residual lane encloses it ("other" for the
profiler, ``device_execute``/``unattributed`` for the ledger) — the
attribution drifts without any test failing. This pass closes the
loop: every constant span/event name emitted anywhere in the package
must be either

- mapped by ``export.LANE_SPANS`` or ``ledger.LEDGER_SPANS``,
- covered by a mapped PREFIX (``ingest.*`` — compute_lanes folds the
  whole ingest family into the parse/h2d lanes by prefix), or
- on the explicit :data:`UNMAPPED_ALLOWLIST` with a justification.

Dynamic names (f-strings, concatenation — e.g. the admission plane's
``admission.{action}`` events) are structurally invisible to an AST
constant scan and are exercised by the runtime tests instead.

The registries import lazily inside ``run`` (live-package rule, same
as metric-names) so the pure-AST rules stay usable standalone.
"""

from __future__ import annotations

import ast
from typing import List

from ..engine import Finding, Package, Rule, make_finding

# span names that are DELIBERATELY unmapped: control-plane envelopes
# and markers that never represent attributable query wall time. Every
# entry carries its justification — an unexplained span name belongs in
# a map, not here.
UNMAPPED_ALLOWLIST = {
    # structural task envelope: compute_lanes keys per-process tracks
    # and flow arrows off it, and capture_task_profile bounds task
    # windows with it — its children are the attributed spans
    "executor.task",
    # scheduler-side planning envelope; planning wall time reaches the
    # ledger through the scheduler's explicit planning STAMP, and the
    # span exists for the merged artifact's scheduler track
    "scheduler.plan_job",
    # scheduler dispatch bookkeeping: control-plane time, not part of
    # any single query's attributable wall
    "scheduler.task_dispatch",
    # the served hand-off's polls and the sleeps between them: the
    # threads are asleep or in a control-plane round trip, so the spans
    # name idle gaps in a device trace (perfbench/xplane.py) and count
    # in tracing.span_totals(); the wall time they cost a query reaches
    # the ledger as dispatch_wait / report_wait / client_poll_wait,
    # which the scheduler accumulates itself (ledger.HANDOFF_PHASES)
    "executor.poll_wait",
    "executor.poll",
    "client.poll_wait",
    "client.poll",
    # the same hand-off where the scheduler holds a call for the event
    # it waits for (a ready task, a terminal status): a thread waiting
    # on a condition, named for the device trace and counted; a refused
    # hold is a dur=0 marker kept out of the ring
    "scheduler.poll_held",
    "scheduler.status_held",
    "scheduler.hold_refused",
    # marker events (dur=0) counting how often each wait of the
    # hand-off ended on its event: a poll sent because a task ended, a
    # poll sent because a slot was still free, a report that sat out a
    # timer, a held call the event ended
    "executor.report_now",
    "executor.refill",
    "executor.report_waited",
    "scheduler.poll_woken",
    "scheduler.status_woken",
    # marker events (dur=0) read as counts from tracing.span_totals():
    # a straggler duplicated (ballista_tasks_speculated_total) and a
    # completion report sent without its profile window
    "scheduler.speculate",
    "executor.profile_dropped",
    # marker event (dur=0) counting compactions (physical/base.py
    # maybe_compact); the time is the device's, under jit_batch_compact
    # in a device trace
    "compact.search",
    # marker events (dur=0), one a probe batch of a join with a fused
    # probe chain (physical/join.py _probe_inputs): compacted before
    # its probe, or handed to the single fused program
    "join.probe_compacted",
    "join.probe_fused",
    # marker event (dur=0), one a probe launch that searches the sorted
    # build keys in steps of 128 (physical/join.py _note_search); the
    # time is the device's, under jit_join_ranges / jit_join_unique
    "join.search",
    # marker event (dur=0), one a second-half launch of an expanding
    # probe, after its count was read (physical/join.py _expand_run);
    # the time is the device's, under jit_join_expand
    "join.expand",
    # marker event (dur=0), one a partition an aggregate executed: how
    # its input batches reached the program (physical/aggregate.py
    # _execute_over: single | in_program | host_concat); the time is
    # the device's, under jit_agg_grouped / jit_agg_mixed /
    # jit_agg_scalar
    "agg.inputs",
    # the mesh exchange (physical/mesh_input.py, mesh_agg.py,
    # distributed/scheduler.py _fuse_mesh_stages): marker events (dur=0)
    # for a side exchanged over the mesh and for a join or aggregate the
    # fusion pass fused or left alone, and the span around a fused
    # stage's stacked input, which runs inside the executor's task
    # window; the device's time is under jit_mesh_join_spmd and
    # jit_mesh_agg_spmd in a device trace
    "mesh.exchange",
    "mesh.assemble",
    "mesh.fused",
    "mesh.unfused",
    # every governed launch (compile/governor.py call_with): the host's
    # time from the call to the return of the jitted function, counted
    # in tracing.span_totals() under launch:jit_<family> and annotated
    # in a device trace, never a ring record, so no lane or ledger
    # window ever holds one; a call that compiled is renamed launch.cold
    # before it ends (compile.jit is the mapped record of that time)
    "launch",
    "launch.cold",
    # cancellation marker event (dur=0): lifecycle, not latency
    "lifecycle.cancel",
    # adaptive re-planning marker (dur=0), one a REWRITE of a plan
    # (adaptive/rules.py note_rule, from the standalone pass and the
    # cluster replanner alike): it fires INSIDE windows that are
    # already attributed (standalone collect / scheduler stage report)
    "adaptive.rule",
    # what a cached plan builds once and then keeps (physical/join.py
    # _materialize_build, physical/operators.py RepartitionExec): the
    # spans of a build side made and of a repartition's sources sorted
    # by destination run inside the collect / task window and hold
    # device.block children of their own; the dur=0 markers count a
    # build or sorted sources found again, and one a destination
    # partition gathered (the time is the device's, under
    # jit_repart_take)
    "join.build",
    "join.build_reused",
    "repart.materialize",
    "repart.reused",
    "repart.take",
    # marker event (dur=0), one a scan partition executed
    # (physical/operators.py ScanExec): its rows and how they were
    # served (resident | filled | streamed); parse and upload time is
    # the ingest spans'
    "scan.serve",
    # the shuffle data plane at a task's edges (distributed/executor.py
    # _write_shuffled, physical/shuffle.py _load_group, executor.py
    # _cleanup_job_outputs): a marker event (dur=0) a task that wrote
    # shuffled output, with its fan-out, batches and slices (the write's
    # time is dataplane.write's); the span around a reader's partition
    # loaded (its producers' files decoded and the upload enqueued),
    # which runs inside the executor's task window and holds the
    # shuffle.fetch spans of the pieces that came over the data plane;
    # and the span around a released or cancelled job's files removed
    # (a released job's on a thread of its own, after the query ended)
    "shuffle.write",
    "shuffle.read",
    "dataplane.release",
    # whole-stage fusion runs inside the planning phase, which both
    # paths stamp wholesale (client ledger_phase / scheduler stamp)
    "compile.fuse",
    # control-plane events: restart recovery, degraded-mode entry,
    # cost-feedback persistence, autoscaler decisions — scheduler
    # lifetime, no per-query wall time to attribute
    "controlplane.recover",
    "controlplane.degraded",
    "controlplane.costs",
    "controlplane.autoscale",
}

# name prefixes an attribution surface handles wholesale:
# compute_lanes folds every ``ingest.*`` span into parse/h2d by prefix
MAPPED_PREFIXES = ("ingest.",)


class LaneCoverageRule(Rule):
    id = "lane-coverage"
    description = ("span names every attribution map ignores (lane/"
                   "ledger coverage drift)")

    def run(self, package: Package) -> List[Finding]:
        from ballista_tpu.observability.export import LANE_SPANS
        from ballista_tpu.observability.ledger import LEDGER_SPANS

        mapped = set(LANE_SPANS) | set(LEDGER_SPANS)
        findings: List[Finding] = []
        for sf in package.files:
            for node in ast.walk(sf.tree):
                name = _span_name(node)
                if name is None or name in mapped or \
                        name in UNMAPPED_ALLOWLIST or \
                        name.startswith(MAPPED_PREFIXES):
                    continue
                findings.append(make_finding(
                    self.id, sf, node.lineno,
                    f"span {name!r} is unknown to export.LANE_SPANS, "
                    "ledger.LEDGER_SPANS and the unmapped allowlist — "
                    "its wall time silently lands in a residual lane "
                    "(map it, or allowlist it with a justification)"))
        return findings


def _span_name(node: ast.AST):
    """The constant first argument of a trace_span/trace_event call,
    else None."""
    if not isinstance(node, ast.Call) or not node.args:
        return None
    f = node.func
    fname = (f.id if isinstance(f, ast.Name)
             else f.attr if isinstance(f, ast.Attribute) else "")
    if fname not in ("trace_span", "trace_event"):
        return None
    first = node.args[0]
    if isinstance(first, ast.Constant) and isinstance(first.value, str):
        return first.value
    return None
