"""``pio deploy --fleet N`` glue: supervisor + gateway in one process.

Topology: the gateway binds the requested ``--port``; worker i is a
child ``pio deploy`` process on ``port + 1 + i`` bound to localhost
(only the gateway faces traffic). Workers inherit every deploy flag the
operator passed except ``--fleet`` and ``--port``, and get a registry
sync interval so rollout state changes propagate fleet-wide.

SIGTERM to the parent is a zero-downtime stop: the gateway drains
(listener closed, in-flight answered), then the supervisor SIGTERMs the
workers — which drain too (``create_server`` drain path) — escalating
to SIGKILL only past the grace window.

The fleet observability plane (``--obs-dir``, default ``pio_obs``) also
lives here: worker stderr/stdout captured into per-replica rotating tail
files (:mod:`.worklog`), a durable telemetry ring the gateway appends
fleet snapshots into (:mod:`obs.tsring`), and the incident flight
recorder (:mod:`obs.incidents`) whose sources — merged traces, ring
tail, supervisor ladder, registry state — are wired up so a worker
crash, breaker trip, or fleet SLO alert leaves an inspectable bundle
(``pio incidents list``).
"""

from __future__ import annotations

import asyncio
import logging
import os
import signal
import subprocess
import sys

from predictionio_tpu.fleet.gateway import Gateway, GatewayConfig, GatewayGroup
from predictionio_tpu.fleet.hostrt import (
    DRIVER_CONTAINER,
    DRIVER_SSH,
    HostRuntime,
    assign_hosts,
    parse_hosts,
)
from predictionio_tpu.fleet.supervisor import (
    REPLICA_CLASS_CPU,
    Supervisor,
    SupervisorConfig,
    WorkerSpec,
)
from predictionio_tpu.fleet.worklog import WorkerLogBook, spawn_with_log
from predictionio_tpu.obs.incidents import IncidentRecorder
from predictionio_tpu.obs.metrics import MetricsRegistry
from predictionio_tpu.obs.tsring import TelemetryRing

logger = logging.getLogger(__name__)

# flags that must not leak from the operator's command line into worker
# argv: the fleet topology flags (value-taking unless noted)
_STRIP_FLAGS = {
    "--fleet": True,
    "--port": True,
    "--ip": True,
    "--fleet-probe-interval": True,
    "--registry-sync-interval": True,
    "--obs-dir": True,
    # elasticity flags are parent-only too (a worker recursively
    # autoscaling would be a fork bomb with extra steps)
    "--autoscale": False,
    "--fleet-min": True,
    "--fleet-max": True,
    "--cpu-fallback-max": True,
    "--autoscale-interval": True,
    # multi-host / multi-gateway topology flags are parent-only too
    "--hosts": True,
    "--gateways": True,
    # the lifecycle controller lives in the fleet parent only (a worker
    # running its own retune loop would grid-search once per replica)
    "--lifecycle": True,
    "--lifecycle-cadence": True,
    "--lifecycle-cooldown": True,
    "--lifecycle-workers": True,
    "--lifecycle-nice": True,
    "--lifecycle-warm-limit": True,
    "--lifecycle-app": True,
}


def worker_argv(
    cli_argv: list[str],
    port: int,
    sync_interval_s: float,
    bind_ip: str = "127.0.0.1",
) -> list[str]:
    """Child process argv for one worker, derived from the parent's CLI
    argv (everything after the program name, i.e. starting at the
    ``deploy`` subcommand). Strips the fleet/port flags (both
    ``--flag value`` and ``--flag=value`` spellings) and appends the
    worker's own port + registry sync cadence. Workers on a REMOTE host
    bind all interfaces (the gateway dials them across the wire);
    same-box workers stay loopback-only."""
    out: list[str] = [sys.executable, "-m", "predictionio_tpu.tools.cli"]
    skip = False
    for arg in cli_argv:
        if skip:
            skip = False
            continue
        flag = arg.split("=", 1)[0]
        if flag in _STRIP_FLAGS:
            skip = _STRIP_FLAGS[flag] and "=" not in arg
            continue
        out.append(arg)
    out += [
        "--ip",
        bind_ip,
        "--port",
        str(port),
        "--registry-sync-interval",
        str(sync_interval_s),
    ]
    return out


def _refuse_shared_chips(device_workers: int, args) -> None:
    """A chip belongs to one process at a time, and every device-class
    worker is a process that sees (and claims) every chip of its host: a
    second one on the same box fails or hangs at backend start-up.
    Refused here instead. With ``--hosts`` placement is per box and is
    the operator's to size; cpu-class workers never want the chip."""
    if (
        device_workers > 1
        and not getattr(args, "hosts", None)
        and os.environ.get("JAX_PLATFORMS") != "cpu"
    ):
        raise ValueError(
            f"{device_workers} device-class workers on this host would each "
            "claim every chip, and a chip belongs to one process: use "
            "--fleet 1 (with --fleet-max 1 under --autoscale; overflow "
            "goes to --cpu-fallback-max workers), place workers with "
            "--hosts, or run the fleet on the CPU with JAX_PLATFORMS=cpu"
        )


def run_fleet(args, cli_argv: list[str]) -> int:
    """Blocking fleet entry point for ``cmd_deploy``. ``cli_argv`` is the
    raw CLI argument vector (sys.argv[1:]) the workers are derived from."""
    n = int(args.fleet)
    if n < 1:
        raise ValueError("--fleet needs at least 1 replica")
    _refuse_shared_chips(n, args)
    if getattr(args, "ssl_certfile", None) or getattr(args, "ssl_keyfile", None):
        # workers would inherit the TLS flags and serve HTTPS, but the
        # gateway probes/forwards plain HTTP on loopback — every replica
        # would fail its handshake and the fleet would serve nothing.
        # Terminate TLS in front of the gateway instead.
        raise ValueError(
            "--fleet does not support --ssl-certfile/--ssl-keyfile: workers "
            "bind loopback behind the plain-HTTP gateway; terminate TLS at a "
            "front proxy"
        )
    # None = flag unset (fleet workers default to 1 s); an EXPLICIT 0
    # disables the sync loop, exactly as the help text promises
    sync_arg = getattr(args, "registry_sync_interval", None)
    sync_s = 1.0 if sync_arg is None else float(sync_arg)
    n_gateways = int(getattr(args, "gateways", 1) or 1)
    if n_gateways < 1:
        raise ValueError("--gateways needs at least 1 gateway")
    metrics = MetricsRegistry()
    obs = build_obs_plane(
        getattr(args, "obs_dir", "pio_obs"),
        metrics,
        registry_dir=getattr(args, "registry_dir", None),
    )
    logbook = obs.get("logbook")

    # host inventory (--hosts): the declared boxes workers place across;
    # unset collapses to the classic single-box deploy (no runtime, no
    # probes — byte-for-byte the pre-multi-host behavior)
    hosts_arg = getattr(args, "hosts", None)
    runtime = None
    if hosts_arg:
        host_specs = parse_hosts(hosts_arg)
        runtime = HostRuntime(host_specs, logbook=logbook)
        placement = assign_hosts(n, host_specs)
        specs = [
            WorkerSpec(
                name=f"w{i}",
                port=args.port + n_gateways + i,
                host=placement[i],
                addr=runtime.host(placement[i]).connect_ip,
            )
            for i in range(n)
        ]
    else:
        # gateways occupy ports base..base+G-1; workers follow. With the
        # default single gateway that is exactly the old port+1+i scheme.
        specs = [
            WorkerSpec(name=f"w{i}", port=args.port + n_gateways + i)
            for i in range(n)
        ]

    def spawn(spec: WorkerSpec):
        cpu = spec.worker_class == REPLICA_CLASS_CPU
        if runtime is not None:
            host = runtime.host(spec.host)
            remote = host.driver in (DRIVER_SSH, DRIVER_CONTAINER)
            argv = worker_argv(
                cli_argv,
                spec.port,
                sync_s,
                bind_ip="0.0.0.0" if remote else "127.0.0.1",
            )
            if remote:
                # remote spawns export ONLY what the worker needs; the
                # parent's whole environment does not belong on the wire
                env = {"JAX_PLATFORMS": "cpu"} if cpu else None
            else:
                env = {**os.environ, "JAX_PLATFORMS": "cpu"} if cpu else None
            return runtime.spawn_worker(spec.host, spec.name, argv, env)
        argv = worker_argv(cli_argv, spec.port, sync_s)
        env = None
        if cpu:
            # the cpu-fallback class IS the cheap tier: same server
            # stack, CPU backend — overflow degrades to slower answers
            # instead of competing for the accelerator
            env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        if logbook is not None:
            return spawn_with_log(argv, logbook, spec.name, env=env)
        return subprocess.Popen(argv, env=env)

    def on_host_down(info: dict) -> None:
        # ONE bundle per host death (the supervisor already folded every
        # resident worker into this single transition); each dead
        # worker's log tail lands as its own text part
        incidents = obs.get("incidents")
        if incidents is None:
            return
        texts = {}
        for winfo in info.get("workers", []):
            tail = winfo.pop("logTail", "")
            if tail:
                texts[f"log_tail_{winfo['replica']}"] = tail
        incidents.trigger("host-death", context=info, texts=texts)

    supervisor = Supervisor(
        spawn=spawn,
        specs=specs,
        config=SupervisorConfig(),
        metrics=metrics,
        logbook=logbook,
        on_crash=obs.get("on_crash"),
        runtime=runtime,
        on_host_down=on_host_down,
    )

    # scale-out slot allocator: names/ports after the boot-time range,
    # monotonic so a retired slot is never reused while its old process
    # could still be draining. Placement is host-aware: the supervisor
    # picks the UP host with the most free slots (how the autoscaler
    # restores capacity on the survivor after a host death).
    next_slot = [n]

    def spec_factory(worker_class: str) -> WorkerSpec:
        i = next_slot[0]
        next_slot[0] += 1
        prefix = "c" if worker_class == REPLICA_CLASS_CPU else "w"
        kw = {}
        if runtime is not None:
            host = supervisor.pick_host()
            if host is None:
                raise RuntimeError(
                    "scale-out wanted but no live host has a free slot "
                    "(grow --hosts)"
                )
            kw = {"host": host, "addr": runtime.host(host).connect_ip}
        return WorkerSpec(
            name=f"{prefix}{i}",
            port=args.port + n_gateways + i,
            worker_class=worker_class,
            **kw,
        )

    gateways: list[Gateway] = []
    rings = [obs.get("telemetry")]
    for g in range(n_gateways):
        if g == 0:
            ring_g = obs.get("telemetry")
        elif obs.get("dir"):
            # peer gateways write the SAME ring directory under their own
            # writer namespace — never interleaving a segment file
            ring_g = TelemetryRing(
                os.path.join(obs["dir"], "telemetry"), writer_id=f"g{g}"
            )
            rings.append(ring_g)
        else:
            ring_g = None
        gateways.append(
            Gateway(
                GatewayConfig(
                    ip=args.ip,
                    port=args.port + g,
                    replica_urls=tuple(s.url for s in specs),
                    probe_interval_s=getattr(args, "fleet_probe_interval", 1.0),
                    request_timeout_s=args.request_timeout,
                    breaker_threshold=args.breaker_threshold,
                    breaker_recovery_s=args.breaker_recovery,
                    sticky_key_field=args.sticky_key,
                    gateway_id=f"g{g}",
                    peer_urls=tuple(
                        f"http://127.0.0.1:{args.port + p}"
                        for p in range(n_gateways)
                        if p != g
                    ),
                ),
                # one registry for the primary: supervisor counters
                # federate through it exactly as before. Peers are
                # shared-nothing — their own registries, their own
                # /metrics (the balancer's scrape view per member).
                metrics=metrics if g == 0 else MetricsRegistry(),
                telemetry=ring_g,
                incidents=obs.get("incidents") if g == 0 else None,
            )
        )
    gateway = gateways[0]
    wire_incident_sources(obs.get("incidents"), gateway, supervisor)

    autoscaler = None
    if getattr(args, "autoscale", False):
        ring = obs.get("telemetry")
        if ring is None:
            raise ValueError(
                "--autoscale reads the telemetry ring; it cannot run with "
                "the flight recorder disabled (--obs-dir '')"
            )
        # membership changes (add/retire) must land on EVERY gateway —
        # the group fans those two calls out and reads from the primary
        scale_target = (
            GatewayGroup(gateways) if len(gateways) > 1 else gateway
        )
        autoscaler = build_autoscaler(
            args, supervisor, scale_target, spec_factory, ring, metrics, obs
        )

    lifecycle = None
    if getattr(args, "lifecycle", None):
        if obs.get("telemetry") is None:
            raise ValueError(
                "--lifecycle reads drift signals off the telemetry ring; "
                "it cannot run with the flight recorder disabled "
                "(--obs-dir '')"
            )
        lifecycle = build_lifecycle(
            args, metrics, obs, serve_url=f"http://127.0.0.1:{args.port}"
        )

    async def main() -> None:
        supervisor.start()
        loop = asyncio.get_running_loop()
        sup_task = asyncio.ensure_future(supervisor.run())
        auto_task = (
            asyncio.ensure_future(autoscaler.run())
            if autoscaler is not None
            else None
        )
        life_task = (
            asyncio.ensure_future(lifecycle.run())
            if lifecycle is not None
            else None
        )

        def drain_all() -> None:
            for gw in gateways:
                gw.begin_drain()

        try:
            loop.add_signal_handler(signal.SIGTERM, drain_all)
        except (NotImplementedError, RuntimeError):
            pass  # non-POSIX loop: Ctrl-C still stops via KeyboardInterrupt
        # peers first (g1..gN-1 on port+1..), then the primary's serve
        # loop blocks until drain; each peer is its own shared-nothing
        # listener over the identical replica set
        for gw in gateways[1:]:
            await gw.start()
        try:
            await gateway.run_until_stopped()
        finally:
            for gw in gateways[1:]:
                try:
                    await gw.stop()
                except Exception:  # noqa: BLE001 - best-effort teardown
                    logger.exception("peer gateway stop failed")
            tasks = [
                t for t in (sup_task, auto_task, life_task) if t is not None
            ]
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            # workers drain on SIGTERM (create_server drain path); the
            # supervisor escalates to SIGKILL only past the grace window
            await loop.run_in_executor(None, supervisor.stop)

    if n_gateways > 1:
        print(
            f"Fleet gateways starting on {args.ip}:{args.port}-"
            f"{args.port + n_gateways - 1} ({n_gateways} shared-nothing "
            f"listeners; put any TCP balancer in front) "
            f"({n} workers on ports {specs[0].port}-{specs[-1].port}) ..."
        )
    else:
        print(
            f"Fleet gateway starting on {args.ip}:{args.port} "
            f"({n} workers on ports {specs[0].port}-{specs[-1].port}) ..."
        )
    if runtime is not None:
        census = ", ".join(
            f"{h.name}[{h.driver}]x{h.slots}" for h in runtime.hosts()
        )
        print(f"Host inventory: {census} (docs/fleet.md §Multi-host)")
    if obs.get("dir"):
        print(
            f"Fleet flight recorder in {obs['dir']} "
            "(telemetry ring, worker logs, incident bundles; "
            "`pio incidents list`, `pio top --history`)"
        )
    if autoscaler is not None:
        cfg = autoscaler.policy.config
        print(
            f"Autoscaler on: device envelope [{cfg.min_replicas}.."
            f"{cfg.max_replicas}], cpu-fallback max {cfg.cpu_fallback_max}, "
            f"tick {cfg.tick_interval_s:g}s (docs/fleet.md §Autoscaling)"
        )
    if lifecycle is not None:
        lcfg = lifecycle.policy.config
        triggers = (
            f"drift + cadence {lcfg.cadence_s:g}s"
            if lcfg.cadence_s
            else "drift/manual"
        )
        print(
            f"Lifecycle controller on: {triggers}, state "
            f"{lifecycle.state_dir} (`pio lifecycle status`, "
            "docs/lifecycle.md)"
        )
    try:
        asyncio.run(main())
    finally:
        for ring in rings:
            if ring is not None:
                ring.close()
    return 0


def build_autoscaler(
    args,
    supervisor: Supervisor,
    gateway: Gateway,
    spec_factory,
    ring,
    metrics: MetricsRegistry,
    obs: dict,
):
    """Assemble the elasticity loop from the deploy flags: policy
    envelope (``--fleet-min/--fleet-max/--cpu-fallback-max``), the
    telemetry ring as the single signal path, the registry as the
    mid-bake gate, and the incident recorder for envelope saturation."""
    from predictionio_tpu.fleet.autoscaler import (
        Autoscaler,
        AutoscalerConfig,
        ScalingPolicy,
        registry_rollout_probe,
    )

    n = int(args.fleet)

    def flag(name, default, cast):
        # None = unset -> default; an EXPLICIT value is honored verbatim
        # and validated below (`or` would silently turn an explicit 0
        # into the default — the unset-vs-zero bug PR 9 fixed for
        # --registry-sync-interval)
        value = getattr(args, name, None)
        return default if value is None else cast(value)

    config = AutoscalerConfig(
        min_replicas=flag("fleet_min", 1, int),
        # default headroom: twice the boot size (an envelope equal to N
        # would make --autoscale a no-op outward)
        max_replicas=flag("fleet_max", max(1, 2 * n), int),
        cpu_fallback_max=flag("cpu_fallback_max", 0, int),
        tick_interval_s=flag("autoscale_interval", 5.0, float),
    )
    if config.min_replicas < 1:
        raise ValueError("--fleet-min must be >= 1 (0 would drain the fleet)")
    if config.min_replicas > config.max_replicas:
        raise ValueError("--fleet-min cannot exceed --fleet-max")
    if config.max_replicas < n:
        # booting above the ceiling would pin every pressured tick on
        # "saturated" (bundle spam) while the operator believes the
        # envelope bounds the fleet
        raise ValueError(
            f"--fleet-max ({config.max_replicas}) must be >= the --fleet "
            f"boot size ({n})"
        )
    _refuse_shared_chips(config.max_replicas, args)
    if config.cpu_fallback_max < 0:
        raise ValueError("--cpu-fallback-max must be >= 0")
    if config.tick_interval_s <= 0:
        raise ValueError("--autoscale-interval must be > 0")
    registry_dir = getattr(args, "registry_dir", None)
    return Autoscaler(
        ScalingPolicy(config),
        supervisor,
        gateway,
        spec_factory,
        ring=ring,
        rollout_probe=(
            registry_rollout_probe(registry_dir) if registry_dir else None
        ),
        metrics=metrics,
        incidents=obs.get("incidents"),
    )


def build_lifecycle(args, metrics: MetricsRegistry, obs: dict, serve_url: str):
    """Assemble the lifecycle controller from the deploy flags
    (docs/lifecycle.md): the fleet's own telemetry ring is the drift
    sensor AND the transition log, its incident recorder snapshots
    aborts/rollbacks, its metrics registry exports ``pio_lifecycle_*``
    through the gateway's federated /metrics, and the gateway itself is
    the cache-warm target (warm queries take the same least-loaded route
    production traffic does)."""
    from predictionio_tpu.lifecycle import (
        LifecycleConfig,
        LifecycleController,
        LifecyclePolicy,
        build_grid_tuner,
        build_warmer,
    )
    from predictionio_tpu.registry.probe import registry_rollout_probe
    from predictionio_tpu.workflow.engine_loader import load_manifest

    registry_dir = getattr(args, "registry_dir", None) or os.environ.get(
        "PIO_REGISTRY_DIR"
    )
    if not registry_dir:
        raise ValueError(
            "--lifecycle stages and promotes through the registry; it "
            "needs --registry-dir (or $PIO_REGISTRY_DIR)"
        )
    manifest = load_manifest(
        getattr(args, "engine_dir", "."), getattr(args, "variant", None)
    )

    def flag(name, default, cast):
        value = getattr(args, name, None)
        return default if value is None else cast(value)

    config = LifecycleConfig(
        cadence_s=flag("lifecycle_cadence", 0.0, float),
        cooldown_s=flag("lifecycle_cooldown", 600.0, float),
        warm_limit=flag("lifecycle_warm_limit", 256, int),
    )
    state_dir = os.path.join(obs["dir"], "lifecycle")
    cwd = os.getcwd()
    if cwd not in sys.path:
        sys.path.insert(0, cwd)
    tuner = build_grid_tuner(
        args.lifecycle,
        workdir=os.path.join(state_dir, "grid"),
        engine_manifest=manifest,
        registry_dir=registry_dir,
        workers=flag("lifecycle_workers", 2, int),
        nice=flag("lifecycle_nice", 10, int),
        cwd=cwd,
        env={k: v for k, v in os.environ.items() if k.startswith("PIO_")},
    )
    warmer = None
    app_name = getattr(args, "lifecycle_app", None)
    if app_name and config.warm_limit > 0:
        from predictionio_tpu.lifecycle.warm import event_store_queries

        def query_source():
            # storage resolves lazily at warm time: the event store may
            # not even exist when the fleet boots
            from predictionio_tpu.data.storage import Storage
            from predictionio_tpu.data.store.event_store import resolve_app

            storage = Storage.instance()
            app_id, _ = resolve_app(storage, app_name, None)
            return event_store_queries(
                storage, app_id, limit=config.warm_limit
            )

        warmer = build_warmer(serve_url, query_source, limit=config.warm_limit)
    return LifecycleController(
        LifecyclePolicy(config),
        state_dir=state_dir,
        engine_id=manifest.engine_id,
        registry_dir=registry_dir,
        tune=tuner,
        warm=warmer,
        rollout_probe=registry_rollout_probe(registry_dir),
        # the SHARED ring object: drift records written by replicas/obs
        # plane land where the controller reads, and its transitions land
        # where `pio top --history` renders
        ring=obs.get("telemetry"),
        incidents=obs.get("incidents"),
        metrics=metrics,
    )


def build_obs_plane(
    obs_dir: str | None,
    metrics: MetricsRegistry,
    registry_dir: str | None = None,
) -> dict:
    """The fleet flight-recorder wiring: worker logbook, telemetry ring,
    incident recorder (all under ``obs_dir``; empty/None disables).
    Returns the pieces keyed by role plus the supervisor ``on_crash``
    hook. Split out of :func:`run_fleet` so tests and the chaos e2e can
    assemble the identical plane around in-process fleets."""
    if not obs_dir:
        return {}
    obs_dir = os.path.abspath(obs_dir)
    logbook = WorkerLogBook(os.path.join(obs_dir, "logs"))
    telemetry = TelemetryRing(os.path.join(obs_dir, "telemetry"))
    incidents = IncidentRecorder(
        os.path.join(obs_dir, "incidents"), metrics=metrics
    )
    if registry_dir:

        def registry_state() -> dict:
            # lazy import: the launcher must not pay the registry import
            # unless an incident actually captures
            from predictionio_tpu.registry.store import ArtifactStore

            store = ArtifactStore(registry_dir)
            out: dict = {}
            for engine_key in store.engines():
                state = store.state_by_key(engine_key)
                out[engine_key] = {
                    "generation": state.generation,
                    "stable": state.stable,
                    "candidate": state.candidate,
                    "mode": state.mode,
                    "fraction": state.fraction,
                }
            return out

        incidents.add_source("registry", registry_state)
    incidents.add_source(
        "telemetry", lambda: telemetry.tail(120)
    )

    def on_crash(info: dict) -> None:
        texts = {}
        tail = info.pop("stderrTail", None)
        if tail:
            texts["stderr_tail"] = tail
        incidents.trigger(
            "worker-park" if info.get("parked") else "worker-crash",
            context=info,
            texts=texts,
        )

    return {
        "dir": obs_dir,
        "logbook": logbook,
        "telemetry": telemetry,
        "incidents": incidents,
        "on_crash": on_crash,
    }


def wire_incident_sources(
    incidents, gateway: Gateway, supervisor: Supervisor
) -> None:
    """Attach the live-state evidence sources once both tiers exist: the
    gateway's merged trace snapshot (its own ring + the per-tick replica
    caches — a SIGKILLed worker's final spans survive in the cache) and
    the supervisor's restart ladder."""
    if incidents is None:
        return
    incidents.add_source("traces", lambda: gateway.cached_spans()[:400])
    incidents.add_source("fleet", gateway.fleet_snapshot)
    incidents.add_source("supervisor", supervisor.snapshot)
    # profile-on-alert (obs/sampler): every incident kind — not just the
    # gateway's own slo-alert path — carries the gateway host-stack view
    incidents.add_source("hoststacks", gateway.sampler.snapshot)


__all__ = [
    "build_autoscaler",
    "build_lifecycle",
    "build_obs_plane",
    "run_fleet",
    "wire_incident_sources",
    "worker_argv",
]
