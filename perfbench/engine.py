"""The system under test, as the benchmark takes it: contexts built from a
configuration file and the tables' files, and the counters and per-query
records that the harness itself or several readers need. Nothing here
measures or decides: the readers in ``metrics/`` do, and a reader that needs
a counter or a span of its own brings it (``snapshot`` and ``after_query``
in README.md)."""

from __future__ import annotations

import os

import datagen


class Engine:
    """The deployment a configuration file describes: ``standalone`` (one
    context) or ``served`` (an in-process ``LocalCluster`` behind remote
    contexts). ``context()`` gives each traffic stream a client of its own."""

    def __init__(self, config: dict, data_dir: str, tables):
        self.config, self.data_dir, self.tables = config, data_dir, tables
        self.cluster = None
        mode = config["mode"]
        if mode == "served":
            from ballista_tpu.distributed.dataplane import NativeDataPlane
            from ballista_tpu.distributed.executor import LocalCluster

            self.cluster = LocalCluster(
                num_executors=config["executors"],
                concurrent_tasks=config["slots"],
                num_devices=config["devices"])
            planes = [type(e._data_plane).__name__
                      for e in self.cluster.executors]
            if not all(isinstance(e._data_plane, NativeDataPlane)
                       for e in self.cluster.executors):
                self.close()
                raise RuntimeError(
                    f"executors serve shuffle data with {planes}: the "
                    "configuration requires the native data plane")
        elif mode != "standalone":
            raise ValueError(f"mode {mode!r}: 'standalone' or 'served'")

    def context(self):
        from ballista_tpu.client import BallistaContext

        if self.cluster is None:
            ctx = BallistaContext.standalone()
        else:
            ctx = BallistaContext.remote(
                "localhost", self.cluster.port,
                **self.config.get("client_settings", {}))
        for name in self.tables:
            table = datagen.table(name)
            ctx.register_parquet(name, os.path.join(self.data_dir, name),
                                 table.program_schema(),
                                 primary_key=table.PRIMARY_KEY)
        return ctx

    def close(self) -> None:
        if self.cluster is not None:
            self.cluster.shutdown()
            self.cluster = None


def counters() -> dict:
    """Process-wide counters the program keeps, as one flat dict (a copy of
    ``chip_smoke.py``'s ``read_counters``, which stays where it is): those
    the warm-up rule and more than one reader need."""
    from ballista_tpu.compile import compile_stats
    from ballista_tpu.ingest import phase_bytes, phase_totals

    st, ph = compile_stats(), phase_totals()
    return {
        # jax reports a read from the disk cache as a (short) backend
        # compile too, so this counts both
        "backend_compiles": int(st["backend_compiles"]),
        "persistent_cache_hits": int(st["persistent_cache_hits"]),
        "compile_seconds": float(st["compile_seconds"]),
        "parse_seconds": float(ph["parse"]),
        "h2d_bytes": int(phase_bytes().get("h2d", 0)),
    }


def delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def query_record(ctx) -> dict:
    """What the program recorded about the query ``ctx`` just ran: its
    latency ledger (``phases`` among its keys) and its stages' metrics, as
    the program gives them, and from the stages its tasks and the bytes the
    non-final stages wrote into the data plane. Taken between queries of a
    traced run only: the served ledger is a round trip to the scheduler."""
    ledger = ctx.last_query_ledger() or {}
    metrics = ctx.last_query_metrics()
    stages = dict(metrics.stages) if metrics is not None else {}
    tasks = sum(int(st.get("num_tasks", 0)) for st in stages.values())
    # as the scheduler's own cost feedback counts it (controlplane/costs.py)
    final = max(stages) if stages else None
    shuffled = sum(
        int((op.get("metrics") or {}).get("bytes_written", 0))
        for sid, st in stages.items() if sid != final
        for op in st.get("operators") or []
        if op.get("operator") in ("ShuffleWrite", "PartitionWrite"))
    return {"phases": dict(ledger.get("phases") or {}), "tasks": tasks,
            "shuffle_bytes": shuffled, "ledger": ledger, "stages": stages}
