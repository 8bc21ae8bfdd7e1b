"""``dtpu-worker``: run worker process(es) (reference cli/dask_worker.py).

    python -m distributed_tpu.cli.worker tcp://127.0.0.1:8786 \
        --nworkers 2 --nthreads 1 --nanny
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import signal
import sys


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dtpu-worker", description="distributed_tpu worker"
    )
    p.add_argument("scheduler", help="scheduler address (tcp://host:port)")
    p.add_argument("--nthreads", type=int, default=1, help="threads per worker")
    p.add_argument("--host", default=None,
                   help="interface to bind (default: loopback); a name/IP "
                        "reachable from other hosts, or 'auto' to bind the "
                        "interface this host uses to reach the scheduler")
    p.add_argument("--nworkers", default="1",
                   help="number of worker processes ('auto' = cpu count)")
    p.add_argument("--name", default=None, help="worker name prefix")
    p.add_argument("--memory-limit", default="0",
                   help="memory per worker before spilling: bytes ('4GiB'), "
                        "fraction of host memory (0.5), or 'auto' "
                        "(host/cgroup limit split across --nworkers)")
    p.add_argument("--resources", default=None,
                   help='JSON dict of abstract resources, e.g. \'{"GPU": 2}\'')
    p.add_argument("--nanny", action="store_true", default=False,
                   help="run each worker under a nanny (auto-restart)")
    p.add_argument("--no-nanny", dest="nanny", action="store_false")
    p.add_argument("--preload", action="append", default=[],
                   help="module to import (dtpu_setup hook) at startup")
    p.add_argument("--lifetime", default=None,
                   help="retire the worker gracefully after this long "
                        "(e.g. '1 hour'); for bounded-preemption hosts")
    p.add_argument("--lifetime-stagger", default=None,
                   help="uniform +/- jitter on --lifetime so a fleet "
                        "doesn't cycle in lock-step (default: config)")
    p.add_argument("--lifetime-restart", action="store_true", default=None,
                   help="with --nanny: start a fresh worker after each "
                        "lifetime instead of shutting down (default: config)")
    p.add_argument("--no-lifetime-restart", dest="lifetime_restart",
                   action="store_false",
                   help="override a config-enabled lifetime restart")
    p.add_argument("--tls-ca-file", default=None,
                   help="CA certificate for TLS (tls:// scheduler address)")
    p.add_argument("--tls-cert", default=None, help="worker TLS certificate")
    p.add_argument("--tls-key", default=None, help="worker TLS private key")
    p.add_argument("--jax-coordinator", default=None,
                   help="host:port of the jax.distributed coordination "
                        "service — joins this process to a pod-wide jax "
                        "runtime for the device data plane")
    p.add_argument("--jax-process-id", type=int, default=None,
                   help="this process's index in the pod (0..n-1)")
    p.add_argument("--jax-num-processes", type=int, default=None,
                   help="total jax processes in the pod")
    p.add_argument("--jax-cpu-devices", type=int, default=None,
                   help="virtual CPU devices per process (testing)")
    p.add_argument("--log-level", default="INFO")
    p.add_argument("--version", action="store_true")
    return p


async def run(args: argparse.Namespace) -> int:
    import os

    if args.jax_coordinator and not args.nanny:
        # join the pod-wide jax runtime FIRST: both the device-count
        # config and jax.distributed.initialize must run before ANY
        # backend query in this process (imports below may touch jax).
        # Under --nanny the PARENT must NOT join — the nanny-spawned
        # worker child joins with this process_id; a double-join wedges
        # the coordination service (the child gets the kwargs below).
        import jax

        if args.jax_cpu_devices:
            jax.config.update("jax_num_cpu_devices", args.jax_cpu_devices)
        from distributed_tpu.parallel import multihost

        multihost.maybe_initialize(
            args.jax_coordinator,
            process_id=args.jax_process_id,
            num_processes=args.jax_num_processes,
        )

    from distributed_tpu.preloading import process_preloads
    from distributed_tpu.utils.system import parse_memory_limit
    from distributed_tpu.worker.nanny import Nanny
    from distributed_tpu.worker.server import Worker

    nworkers = (
        os.cpu_count() or 1 if args.nworkers == "auto" else int(args.nworkers)
    )
    if args.jax_coordinator and nworkers != 1:
        # one pod process id maps to ONE worker process: several workers
        # sharing a process id either double-join the coordination
        # service (--nanny) or report overlapping device ownership,
        # breaking the device plane in confusing ways downstream
        raise SystemExit(
            "--jax-coordinator requires --nworkers 1 (one worker process "
            "per pod process id); start one dtpu-worker per chip group"
        )
    from distributed_tpu import config

    resources = json.loads(args.resources) if args.resources else None
    memory_limit = parse_memory_limit(args.memory_limit, nworkers)
    # None = defer to the worker.lifetime.* config keys
    lifetime = config.parse_timedelta(args.lifetime) if args.lifetime else None
    lifetime_stagger = (
        config.parse_timedelta(args.lifetime_stagger)
        if args.lifetime_stagger is not None else None
    )
    host = args.host
    if host == "auto":
        # the interface this host routes to the scheduler through: works
        # for ssh aliases / jump hosts where the ssh destination name is
        # not resolvable on the worker machine itself
        from distributed_tpu.utils.system import outbound_ip

        host = outbound_ip(args.scheduler)
    # match the scheduler's transport: a tls:// control plane means the
    # worker must serve its peers over tls too
    proto = args.scheduler.split("://", 1)[0] if "://" in args.scheduler else "tcp"
    if (args.tls_ca_file or args.tls_cert) and proto == "tcp":
        # mirror the scheduler CLI: supplying TLS credentials means an
        # encrypted cluster — silently running the whole data plane in
        # plaintext because the address said tcp:// is a foot-gun
        rest = args.scheduler.split("://", 1)[-1]
        args.scheduler = f"tls://{rest}"
        proto = "tls"
        logging.getLogger("distributed_tpu.cli").info(
            "TLS credentials provided: scheduler address upgraded to %s",
            args.scheduler,
        )
    if host:
        listen_addr = f"{proto}://{host}:0"
    elif proto != "tcp":
        listen_addr = f"{proto}://127.0.0.1:0"
    else:
        listen_addr = None
    security = None
    if args.tls_ca_file or args.tls_cert:
        from distributed_tpu.security import Security

        security = Security(
            tls_ca_file=args.tls_ca_file,
            tls_worker_cert=args.tls_cert,
            tls_worker_key=args.tls_key,
            require_encryption=True,
        )
        if proto != "tls":
            logging.getLogger("distributed_tpu.cli").warning(
                "TLS credentials given but the scheduler address is %s://"
                " — traffic will NOT be encrypted; use a tls:// address",
                proto,
            )

    servers = []
    all_preloads = []
    for i in range(nworkers):
        name = (
            f"{args.name}-{i}" if args.name and nworkers > 1
            else args.name or None
        )
        worker_kwargs = {}
        if resources:
            worker_kwargs["resources"] = resources
        if args.jax_coordinator:
            worker_kwargs.update(
                jax_coordinator=args.jax_coordinator,
                jax_process_id=args.jax_process_id,
                jax_num_processes=args.jax_num_processes,
                jax_cpu_devices=args.jax_cpu_devices,
            )
        if listen_addr:
            worker_kwargs["listen_addr"] = listen_addr
        if security is not None:
            worker_kwargs["security"] = security
        if args.nanny:
            server = Nanny(
                args.scheduler,
                nthreads=args.nthreads,
                name=name,
                memory_limit=memory_limit,
                worker_kwargs=worker_kwargs,
                lifetime=lifetime,
                lifetime_stagger=lifetime_stagger,
                lifetime_restart=args.lifetime_restart,
                security=security,
            )
        else:
            server = Worker(
                args.scheduler,
                nthreads=args.nthreads,
                name=name,
                memory_limit=memory_limit,
                lifetime=lifetime,
                lifetime_stagger=lifetime_stagger,
                **worker_kwargs,
            )
        await server.start()
        # preloads run with the server live (dtpu_setup may read .address)
        preloads = process_preloads(server, args.preload)
        for preload in preloads:
            await preload.start()
        all_preloads.extend(preloads)
        servers.append(server)
        addr = getattr(server, "worker_address", None) or server.address
        print(f"Worker at: {addr}", flush=True)

    loop = asyncio.get_running_loop()
    stop = asyncio.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    waiters = [asyncio.ensure_future(s.finished()) for s in servers]
    stopper = asyncio.ensure_future(stop.wait())
    await asyncio.wait({*waiters, stopper}, return_when=asyncio.FIRST_COMPLETED)
    for preload in all_preloads:
        await preload.teardown()
    for s in servers:
        await s.close()
    stopper.cancel()
    return 0


def main(argv: list[str] | None = None) -> int:
    args = make_parser().parse_args(argv)
    if args.version:
        from distributed_tpu import __version__

        print(__version__)
        return 0
    logging.basicConfig(
        level=args.log_level.upper(),
        format="%(asctime)s %(levelname)s %(name)s %(message)s",
    )
    return asyncio.run(run(args))


if __name__ == "__main__":
    sys.exit(main())
