"""Executor binary: ``python -m ballista_tpu.distributed.executor_main``.

(reference: rust/executor/src/main.rs:55-164 + executor_config_spec.toml
— layered config: defaults < /etc/ballista-tpu/executor.toml <
--config-file < env BALLISTA_EXECUTOR_* < CLI flags; ``--local`` embeds
a standalone scheduler in-process like the reference's local mode.)
"""

from __future__ import annotations

import argparse
import logging
import signal
import sys

from .config import install_stop_signals, layered_config

DEFAULTS = {
    "namespace": "default",
    "scheduler_host": "localhost",
    "scheduler_port": 50050,
    "bind_host": "localhost",
    "external_host": "",
    "port": 0,  # data-plane port (0 = ephemeral)
    "work_dir": "",
    "concurrent_tasks": 4,
    "num_devices": 0,  # 0 = autodetect
    # -- mesh group: executors on several hosts forming ONE device mesh --
    "mesh_group_size": 0,  # processes in the group; 0 = no group
    "mesh_group_rank": 0,  # this process's rank (0 = leader)
    "mesh_group_coordinator": "",  # jax.distributed coordinator host:port
    "mesh_group_channel": "",  # leader's task channel (host:port);
    #                            leader binds it, followers dial it
    "mesh_local_devices": 0,  # virtual CPU devices per process (tests)
    # C++ shuffle-server daemon serves the data plane (GIL-free); "off"
    # keeps the in-process Python server (also the automatic fallback)
    "native_dataplane": "on",
    "metrics_port": 0,  # health plane (/healthz, /metrics); -1 = off
    "log_level": "INFO",
}


def main(argv=None) -> int:
    wait_for_stop = install_stop_signals()
    ap = argparse.ArgumentParser(description="ballista-tpu executor")
    ap.add_argument("--config-file", default=None)
    ap.add_argument("--local", action="store_true",
                    help="embed a standalone scheduler in-process")
    for key in DEFAULTS:
        ap.add_argument("--" + key.replace("_", "-"), default=None)
    args = ap.parse_args(argv)

    cfg = layered_config(
        "executor", DEFAULTS, args.config_file,
        cli={k: getattr(args, k) for k in DEFAULTS},
    )

    logging.basicConfig(
        level=cfg["log_level"].upper(),
        format="%(asctime)s %(levelname)s %(name)s %(message)s",
    )

    from .dataplane import native_dataplane_enabled as _native_enabled
    from .executor import Executor, ExecutorConfig

    group_size = int(cfg["mesh_group_size"])
    group_rank = int(cfg["mesh_group_rank"])
    leader = None
    if group_size > 1:
        # join the shared jax.distributed runtime BEFORE anything
        # touches the backend, so every member sees the global mesh
        from ..parallel import multihost

        multihost.init_group(
            cfg["mesh_group_coordinator"], group_size, group_rank,
            local_device_count=int(cfg["mesh_local_devices"]) or None,
        )
        # backend init is ITSELF a cross-process rendezvous (each
        # process registers its local devices with the coordinator):
        # every member must do it now, or the first member to call
        # jax.devices() later hangs waiting for the rest
        import jax

        n_global = len(jax.devices())
        print(f"mesh group rank {group_rank}: global mesh has "
              f"{n_global} devices", flush=True)
        host, _, port_s = cfg["mesh_group_channel"].rpartition(":")
        from . import mesh_group

        if group_rank == 0:
            leader = mesh_group.GroupLeader(
                cfg["bind_host"], int(port_s), group_size - 1
            )
            print(f"mesh group leader channel on "
                  f"{cfg['bind_host']}:{leader.port}; waiting for "
                  f"{group_size - 1} follower(s)", flush=True)
            leader.wait_members()
        else:
            print(f"mesh group follower rank {group_rank} joining "
                  f"{host}:{port_s}", flush=True)
            mesh_group.run_follower(host or "localhost", int(port_s))
            return 0  # leader closed the channel: group is done

    scheduler_port = cfg["scheduler_port"]
    if args.local:
        from .scheduler import serve_scheduler
        from .state import MemoryBackend, SchedulerState

        state = SchedulerState(MemoryBackend(), cfg["namespace"])
        _server, _svc, scheduler_port = serve_scheduler(
            state, "localhost", 0
        )
        print(f"embedded scheduler on localhost:{scheduler_port}", flush=True)

    num_devices = cfg["num_devices"]
    if not num_devices:
        import jax

        num_devices = len(jax.devices())
    exec_cfg = ExecutorConfig(
        host=cfg["external_host"] or cfg["bind_host"],
        bind_host=cfg["bind_host"],
        port=cfg["port"],
        work_dir=cfg["work_dir"] or None,
        concurrent_tasks=cfg["concurrent_tasks"],
        scheduler_host="localhost" if args.local else cfg["scheduler_host"],
        scheduler_port=scheduler_port,
        num_devices=num_devices,
        native_dataplane=_native_enabled(cfg["native_dataplane"]),
        metrics_port=int(cfg["metrics_port"]),
    )
    executor = Executor(exec_cfg, mesh_group=leader)
    executor.start()
    print(
        f"ballista-tpu executor {executor.id[:8]} polling "
        f"{exec_cfg.scheduler_host}:{exec_cfg.scheduler_port}, data plane on "
        f"{exec_cfg.host}:{executor.port}, work_dir={exec_cfg.work_dir}"
        + (f", mesh group of {group_size} x "
           f"{num_devices // group_size} devices" if leader else ""),
        flush=True,
    )
    if executor.health_port is not None:
        print(f"ballista-tpu executor health plane on "
              f"127.0.0.1:{executor.health_port}", flush=True)
    stop = wait_for_stop()
    drain = stop == signal.SIGTERM
    print(f"signal {stop}; shutting down"
          + (" (graceful drain)" if drain else ""), flush=True)
    if leader is not None:
        leader.close()
    # SIGTERM (the orchestrator's polite stop) drains: stop accepting,
    # let in-flight tasks finish within the bound, flush pending status
    # reports. SIGINT (ctrl-C) keeps the immediate shutdown.
    executor.stop(drain=drain)
    return 0


if __name__ == "__main__":
    sys.exit(main())
