"""Fleet chaos harness: randomized fault-injection scenarios against a
live multi-engine server, with fleet invariants checked after every one.

distlint guards the CODE's invariants; this guards the FLEET's
(ROADMAP "Multi-host control plane + fleet chaos harness"). Each
scenario builds (or reuses) a tiny-model fleet on the CPU backend, arms
a seeded FaultSet (serving/faults.py), drives real requests through the
full spine — dispatcher → scheduler → runners → disagg controller —
and then asserts the promises docs/RESILIENCE.md makes:

- **exactly-once termination**: every accepted request resolves its sink
  with on_done XOR on_error, exactly once, and never streams a token
  after a terminal event;
- **no leaked KV pages**: every engine's allocator passes the
  free/cached/live conservation audit (``LLMEngine.audit_pages``);
- **no wedged drains**: runner inflight maps, the migration queue, and
  the admission queue all empty out;
- **scheduler reconvergence**: with auto-restart on, every replica is
  healthy again once the faults are disarmed.

Scenario matrix: runner crash with zero-token in-flight (redispatch),
crash-mid-handoff (source decodes in place), crash-mid-import (no page
leak), channel truncation, degradation-ladder flapping, and
warm-replica death under cache-aware routing.

    python tools/chaos_fleet.py [minutes]            # time-budgeted soak
    python tools/chaos_fleet.py --seeds 20           # N fresh seeds/scenario
    python tools/chaos_fleet.py --seed 7 --scenarios redispatch  # repro
    python tools/chaos_fleet.py --list

Exit 0 = clean; exit 1 = violation (scenario + seed printed — commit it
as a regression in tests/test_chaos.py, which runs fixed seeds of the
same scenarios in tier-1).

A CPU tool, by construction: ``JAX_PLATFORMS=cpu`` is pinned in this
process's environment before jax is touched, so nothing it starts can
inherit an unset platform and reach a TPU.
"""

from __future__ import annotations

import argparse
import os
import random
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

DEFAULT_SCENARIOS = (
    "redispatch",
    "crash_mid_handoff",
    "crash_mid_import",
    "channel_truncation",
    "degradation_flap",
    "warm_replica_death",
    "warm_peer_fetch_death",
    "registry_partition",
    "remote_runner_crash_mid_request",
    "registry_failover",
    "registry_split_brain",
    "rerole_flap",
    "cross_host_handoff_death",
    "remote_fetch_source_death",
    "slow_member_brownout",
    "breaker_flap",
    "overload_shed",
    "mesh_peer_wire_death",
)

_PROMPT = "chaos is a ladder, resilience is a lattice"


def _env_setup() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


class ChaosSink:
    """Result sink that records the stream contract instead of text:
    terminal events, ordering violations, and codes."""

    def __init__(self, rid: str):
        self.rid = rid
        self.tokens = 0
        self.dones = 0
        self.errors = []  # (message, code)
        self.violations = []
        self.ev = threading.Event()
        self._lock = threading.Lock()

    def _terminal(self, kind: str) -> None:
        with self._lock:
            if self.ev.is_set():
                self.violations.append(
                    f"{self.rid}: second terminal event ({kind}) after "
                    f"{self.dones} done / {len(self.errors)} error"
                )
            self.ev.set()

    def on_token(self, token_id, text, token_index, logprob=None):
        with self._lock:
            if self.ev.is_set():
                self.violations.append(
                    f"{self.rid}: token streamed after a terminal event"
                )
            self.tokens += 1

    def on_done(self, finish_reason, usage):
        self._terminal("done")
        self.dones += 1

    def on_error(self, message, code):
        self._terminal(f"error:{code}")
        self.errors.append((message, code))

    @property
    def terminal_count(self) -> int:
        return self.dones + len(self.errors)


_PARAMS = None


def _tiny_params():
    global _PARAMS
    if _PARAMS is None:
        import jax
        import jax.numpy as jnp

        from distributed_inference_server_tpu.models import llama
        from distributed_inference_server_tpu.models.configs import TINY

        _PARAMS = llama.init_params(jax.random.PRNGKey(0), TINY,
                                    dtype=jnp.float32)
    return _PARAMS


def build_fleet(roles=("unified", "unified"), strategy="least_loaded",
                channel="inproc", auto_restart=True, warmup=False,
                handoff_timeout_s=20.0, engine_kwargs=None,
                fleet=False, rerole=False, member_roles=("unified",),
                health=None, admission=None, slo=None, mesh=False,
                ha=False):
    """A tiny-model fleet wired exactly like production (the
    disagg_smoke.py topology, sans HTTP): real engines, real runners,
    real dispatcher/scheduler/controller. Health loop runs hot
    (100 ms sweeps, 200 ms restart backoff) so chaos iterations stay
    fast.

    ``fleet=True`` adds the multi-host control plane (docs/FLEET.md):
    the server becomes a registry host and a second InferenceServer
    joins as a fleet member over a REAL localhost TCP connection
    through a FleetWorker — the wire is real (KV data channel
    included, serving/fleet_kv.py) even though the processes share an
    interpreter (tools/fleet_smoke.py covers the true 2-process path).
    ``member_roles`` sets the member's replica roles — ``("decode",)``
    makes it a cross-host handoff target. ``rerole=True`` arms the
    RoleBalancer with a short cooldown, its poll thread stopped so
    scenarios drive ``evaluate()`` deterministically.

    ``health`` / ``admission`` / ``slo`` (serving/health.py /
    serving/teledigest.py settings objects) arm the gray-failure
    defense scenarios: a chaos-paced HealthScorer (scenarios drive
    ``evaluate()`` themselves — set a long interval), deadline-aware
    admission, and short SLO digest windows so latency evidence decays
    inside a scenario. ``slo`` is applied to the member server too —
    digest epochs must agree or the host drops the member's telemetry
    frames as foreign.

    ``mesh=True`` (implies ``fleet``) turns on the member<->member KV
    mesh (docs/FLEET.md "KV mesh") and joins a SECOND member
    (``chaos-w2``, same roles) so the registry has a pair to introduce
    — three schedulers, three allocators, one real localhost wire per
    member plus the brokered member->member data wire.

    ``ha=True`` (implies ``fleet``) arms registry HA (docs/FLEET.md
    "Registry HA"): TWO registry InferenceServers on pre-picked fixed
    localhost ports share an ordered ``fleet.registries`` list, elect
    ``registries[0]`` (``srv``) primary, and the member dual-heartbeats
    both over real wires. The standby rides on ``srv._ha_standby_srv``;
    chaos-fast lease windows (lease_s=1.2) keep failover inside a
    scenario. Scenarios kill/partition the primary IN-PROCESS (stop its
    listener + HA loop) — the true SIGKILL path is tools/fleet_smoke.py
    ``--ha``."""
    import jax.numpy as jnp

    from distributed_inference_server_tpu.engine.engine import (
        EngineConfig,
        LLMEngine,
    )
    from distributed_inference_server_tpu.engine.kv_cache import (
        PagedCacheConfig,
    )
    from distributed_inference_server_tpu.models.configs import TINY
    from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer
    from distributed_inference_server_tpu.serving.disagg import DisaggSettings
    from distributed_inference_server_tpu.serving.fleet import FleetSettings
    from distributed_inference_server_tpu.serving.scheduler import (
        SchedulingStrategy,
    )
    from distributed_inference_server_tpu.serving.server import InferenceServer

    params = _tiny_params()
    paged = PagedCacheConfig(num_pages=192, page_size=8, max_pages_per_seq=32)

    def factory():
        return LLMEngine(
            params, TINY, ByteTokenizer(),
            EngineConfig(max_batch=4, prefill_buckets=(16, 64), paged=paged,
                         warmup_compile=warmup, **(engine_kwargs or {})),
            dtype=jnp.float32,
        )

    # aging windows sized for LOADED runners: a GIL stall from a
    # concurrent engine compile must read as jitter, not death
    fleet = fleet or mesh or ha
    ha_registries = ()
    ha_ports = ()
    if ha:
        # pre-pick two free fixed ports: the ordered fleet.registries
        # list must name both listeners BEFORE either server starts
        import socket as _socket

        picked = []
        for _ in range(2):
            s = _socket.socket()
            s.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", 0))
            picked.append(s)
        ha_ports = tuple(s.getsockname()[1] for s in picked)
        for s in picked:
            s.close()
        ha_registries = tuple(f"127.0.0.1:{p}" for p in ha_ports)
    fleet_settings = FleetSettings(
        enabled=fleet, heartbeat_interval_s=0.1, suspect_after_s=0.6,
        dead_after_s=1.5, rerole=rerole, rerole_high_ratio=2.0,
        rerole_low_ratio=0.5, rerole_cooldown_s=0.3,
        rerole_interval_s=60.0,  # scenarios drive evaluate() themselves
        mesh_enabled=mesh,
        # chaos-fast lease windows: failover resolves inside a scenario
        port=ha_ports[0] if ha else 0, registries=ha_registries,
        lease_s=1.2, lease_suspect_s=0.6,
    )
    srv = InferenceServer(
        factory, ByteTokenizer(), model_name="tiny-chaos",
        num_engines=len(roles), engine_roles=list(roles),
        strategy=SchedulingStrategy.parse(strategy),
        auto_restart=auto_restart, health_check_interval_s=0.1,
        restart_backoff_s=0.2, restart_backoff_max_s=2.0,
        disagg_settings=DisaggSettings(channel=channel,
                                       handoff_timeout_s=handoff_timeout_s),
        fleet_settings=fleet_settings,
        health_settings=health,
        admission_settings=admission,
        slo_settings=slo,
    )
    srv.start()
    srv._fleet_worker = None
    srv._fleet_worker_srv = None
    srv._fleet_worker2 = None
    srv._fleet_worker2_srv = None
    srv._ha_standby_srv = None
    if ha:
        import dataclasses

        standby_srv = InferenceServer(
            factory, ByteTokenizer(), model_name="tiny-chaos-standby",
            num_engines=len(roles), engine_roles=list(roles),
            strategy=SchedulingStrategy.parse(strategy),
            auto_restart=auto_restart, health_check_interval_s=0.1,
            restart_backoff_s=0.2, restart_backoff_max_s=2.0,
            fleet_settings=dataclasses.replace(fleet_settings,
                                               port=ha_ports[1]),
            slo_settings=slo,
        )
        standby_srv.start()
        srv._ha_standby_srv = standby_srv
        # initial election: registries[0] (srv) wins after the boot
        # grace (one lease window); the standby defers to it
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            if srv.fleet_ha.is_primary():
                break
            time.sleep(0.05)
        else:
            raise RuntimeError(
                f"HA fleet never elected a primary: "
                f"{srv.fleet_ha.stats()} / {standby_srv.fleet_ha.stats()}")
    if fleet:
        worker_srv = InferenceServer(
            factory, ByteTokenizer(), model_name="tiny-chaos-member",
            num_engines=len(member_roles),
            engine_roles=list(member_roles),
            auto_restart=auto_restart,
            health_check_interval_s=0.1,
            slo_settings=slo,
        )
        worker_srv.start()
        srv._fleet_worker_srv = worker_srv
        srv._fleet_worker_settings = FleetSettings(
            connect=f"127.0.0.1:{srv.fleet_server.bound_port}",
            heartbeat_interval_s=0.1,
            mesh_enabled=mesh,
            # dual-heartbeat: the member keeps a live wire to BOTH
            # registries, so the standby's member table stays warm
            registries=ha_registries,
        )
        if mesh:
            worker2_srv = InferenceServer(
                factory, ByteTokenizer(), model_name="tiny-chaos-member2",
                num_engines=len(member_roles),
                engine_roles=list(member_roles),
                auto_restart=auto_restart,
                health_check_interval_s=0.1,
                slo_settings=slo,
            )
            worker2_srv.start()
            srv._fleet_worker2_srv = worker2_srv
        _ensure_worker(srv)
        if mesh:
            _ensure_worker2(srv)
        orig_shutdown = srv.shutdown

        def _shutdown(drain_timeout_s=30.0):
            if srv._fleet_worker is not None:
                srv._fleet_worker.stop()
            if srv._fleet_worker2 is not None:
                srv._fleet_worker2.stop()
            if srv._fleet_worker2_srv is not None:
                srv._fleet_worker2_srv.shutdown(drain_timeout_s)
            if srv._ha_standby_srv is not None:
                srv._ha_standby_srv.shutdown(drain_timeout_s)
            worker_srv.shutdown(drain_timeout_s)
            orig_shutdown(drain_timeout_s)

        srv.shutdown = _shutdown
    return srv


def _ensure_member(srv, member_id: str, member_srv, worker_attr: str,
                   timeout_s: float = 20.0):
    """Make sure a chaos member is connected, alive in the registry,
    and its remote proxy is registered + healthy (a crashed member from
    a previous seed rejoins under the same member id)."""
    from distributed_inference_server_tpu.serving.remote_runner import (
        FleetWorker,
    )

    fw = getattr(srv, worker_attr)
    if fw is None or fw._crashed or not fw.is_connected():
        if fw is not None:
            fw.stop()
        fw = FleetWorker(member_srv.scheduler,
                         srv._fleet_worker_settings, member_id=member_id,
                         metrics=member_srv.metrics,
                         tracer=member_srv.tracer)
        fw.start()
        setattr(srv, worker_attr, fw)
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if srv.fleet_registry.member_state(member_id) == "alive" and any(
            getattr(r, "is_remote", False) and r.is_healthy()
            and r.engine_id.startswith(member_id + ":")
            for r in srv.scheduler.engines()
        ):
            return fw
        time.sleep(0.03)
    raise RuntimeError(f"chaos fleet member {member_id} failed to join")


def _ensure_worker(srv, timeout_s: float = 20.0):
    return _ensure_member(srv, "chaos-w1", srv._fleet_worker_srv,
                          "_fleet_worker", timeout_s)


def _ensure_worker2(srv, timeout_s: float = 20.0):
    return _ensure_member(srv, "chaos-w2", srv._fleet_worker2_srv,
                          "_fleet_worker2", timeout_s)


def _wait_member_state(srv, state: str, timeout_s: float = 10.0) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if srv.fleet_registry.member_state("chaos-w1") == state:
            return True
        time.sleep(0.03)
    return False


def _wait_until(pred, timeout_s: float, interval_s: float = 0.05) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval_s)
    return False


def submit(srv, rid: str, prompt: str = _PROMPT, max_tokens: int = 16,
           sinks=None):
    """Submit one request; returns its ChaosSink, or None if admission
    rejected it (backpressure/degradation — not a violation)."""
    from distributed_inference_server_tpu.core.errors import QueueFull
    from distributed_inference_server_tpu.engine.engine import SamplingParams
    from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer
    from distributed_inference_server_tpu.serving.runner import ServerRequest

    sink = ChaosSink(rid)
    try:
        srv.dispatcher.submit(ServerRequest(
            rid, ByteTokenizer().encode(prompt),
            SamplingParams(max_tokens=max_tokens, temperature=0.0), sink,
        ))
    except QueueFull:
        return None
    if sinks is not None:
        sinks.append(sink)
    return sink


def wait_terminal(sinks, timeout_s: float = 60.0):
    """Wait until every sink saw a terminal event; returns the ids that
    did not (wedged requests — an invariant violation)."""
    deadline = time.monotonic() + timeout_s
    wedged = []
    for s in sinks:
        if not s.ev.wait(max(0.0, deadline - time.monotonic())):
            wedged.append(s.rid)
    return wedged


def check_invariants(srv, sinks, require_success=False,
                     converge_timeout_s: float = 30.0):
    """The fleet invariants (module docstring); returns violation
    strings, empty = clean. Call with faults already disarmed."""
    violations = []
    for s in sinks:
        violations.extend(s.violations)
        if s.terminal_count != 1:
            violations.append(
                f"{s.rid}: {s.terminal_count} terminal events "
                f"({s.dones} done, {len(s.errors)} error) — want exactly 1"
            )
        if require_success and s.errors:
            violations.append(f"{s.rid}: expected success, got {s.errors}")
    member_srvs = [m for m in (getattr(srv, "_fleet_worker_srv", None),
                               getattr(srv, "_fleet_worker2_srv", None),
                               getattr(srv, "_ha_standby_srv", None))
                   if m is not None]
    deadline = time.monotonic() + converge_timeout_s
    auto = srv.scheduler._auto_restart
    while time.monotonic() < deadline:
        runners = srv.scheduler.engines()
        for m in member_srvs:
            runners = runners + m.scheduler.engines()
        healthy = all(r.is_healthy() for r in runners)
        fetcher = getattr(srv.dispatcher, "prefix_fetcher", None)
        drained = (
            (healthy or not auto)
            and all(r.active_count() == 0 for r in runners)
            and srv.dispatcher.queue.is_empty()
            and srv.dispatcher.batcher.pending_count() == 0
            and (srv.disagg is None or srv.disagg.pending_count() == 0)
            and (fetcher is None or fetcher.pending_count() == 0)
        )
        if drained and (healthy or not auto):
            break
        time.sleep(0.05)
    else:
        state = {
            r.engine_id: (r.is_healthy(), r.active_count())
            for r in srv.scheduler.engines()
        }
        violations.append(
            "fleet did not reconverge/drain within "
            f"{converge_timeout_s}s: engines={state}, "
            f"queue_empty={srv.dispatcher.queue.is_empty()}, "
            f"migrations={srv.disagg.pending_count() if srv.disagg else 0}"
        )
    for r in srv.scheduler.engines():
        violations.extend(r.audit())
    for m in member_srvs:
        # zero page leak on EVERY side of the data plane: a torn
        # cross-host (or member->member mesh) stream must release the
        # member's reserved pages too
        for r in m.scheduler.engines():
            violations.extend(r.audit())
    return violations


# ---------------------------------------------------------------------------
# Scenarios — each installs a seeded FaultSet, drives traffic, disarms,
# and returns (sinks, require_success)
# ---------------------------------------------------------------------------


def _arm(spec: str, seed: int):
    from distributed_inference_server_tpu.serving import faults

    faults.install(faults.parse_spec(spec, seed))


def scenario_redispatch(srv, seed: int):
    """A runner crashes between submit and inbox drain: its zero-token
    in-flight requests must complete on the other replica, invisibly."""
    rng = random.Random(seed)
    sinks = []
    _arm(f"runner.inbox:nth={rng.randint(1, 2)}", seed)
    for i in range(rng.randint(1, 3)):
        submit(srv, f"rd-{seed}-{i}", sinks=sinks)
    wedged = wait_terminal(sinks)
    return sinks, True, [f"{r}: no terminal event (wedged)" for r in wedged]


def scenario_crash_mid_handoff(srv, seed: int):
    """The handoff dies mid-flight — switchover commit dropped, or the
    decode runner crashes while the import session is open. The source
    keeps decoding in place; the client never notices."""
    rng = random.Random(seed)
    spec = rng.choice([
        "disagg.commit:nth=1",
        # inbox hit 1 is the prefill's submit; hits 2+ land on the
        # decode runner's import open/commit commands
        f"runner.inbox:nth={rng.randint(2, 3)}",
        "disagg.slow_peer:prob=1.0,delay_ms=30;disagg.commit:nth=1",
    ])
    sinks = []
    _arm(spec, seed)
    submit(srv, f"hof-{seed}", max_tokens=rng.randint(24, 48), sinks=sinks)
    wedged = wait_terminal(sinks)
    return sinks, True, [f"{r}: no terminal event (wedged)" for r in wedged]


def scenario_crash_mid_import(srv, seed: int):
    """Import-side chunk validation fails: the session aborts, every
    reserved page is released (the audit proves it), and the source
    decodes in place."""
    rng = random.Random(seed)
    sinks = []
    _arm(f"kv.import_chunk:nth={rng.randint(1, 3)}", seed)
    submit(srv, f"imp-{seed}", max_tokens=rng.randint(24, 48), sinks=sinks)
    wedged = wait_terminal(sinks)
    return sinks, True, [f"{r}: no terminal event (wedged)" for r in wedged]


def scenario_channel_truncation(srv, seed: int):
    """The streamed channel errors on the Nth chunk (truncation): phase-1
    failure costs nothing, the sequence never left the source."""
    rng = random.Random(seed)
    sinks = []
    _arm(f"disagg.chunk:nth={rng.randint(1, 5)},times={rng.randint(1, 2)}",
         seed)
    for i in range(2):
        submit(srv, f"tr-{seed}-{i}", max_tokens=32, sinks=sinks)
    wedged = wait_terminal(sinks)
    return sinks, True, [f"{r}: no terminal event (wedged)" for r in wedged]


def scenario_degradation_flap(srv, seed: int):
    """The degradation ladder slams to EMERGENCY and back while traffic
    flows, the health loop restarts healthy replicas on injected flaps,
    and caches evict mid-decode. Success is not promised here — bounded
    failure is: exactly-once termination, no leaks, reconvergence."""
    rng = random.Random(seed)
    sinks = []
    _arm("sched.health_flap:prob=0.3,times=2", seed)
    for i in range(3):
        submit(srv, f"flap-{seed}-{i}", max_tokens=24, sinks=sinks)
        srv.degradation.evaluate(pressure=rng.choice([0.97, 0.92, 0.85]))
        time.sleep(rng.uniform(0.0, 0.05))
        for r in srv.scheduler.engines():
            if r.is_healthy() and rng.random() < 0.5:
                r.evict_cache(rng.uniform(0.3, 0.8),
                              drop_host_tier=rng.random() < 0.5)
        srv.degradation.evaluate(pressure=0.1)
    wedged = wait_terminal(sinks)
    srv.degradation.evaluate(pressure=0.1)  # ladder back to NORMAL
    extra = [f"{r}: no terminal event (wedged)" for r in wedged]
    if srv.dispatcher.reject_all or srv.dispatcher.reject_low_priority:
        extra.append("degradation ladder stuck above NORMAL after "
                     "pressure dropped")
    return sinks, False, extra


def scenario_warm_replica_death(srv, seed: int):
    """Cache-aware routing sends repeated-prefix traffic to the warm
    replica; the warm replica dies with the request in flight before its
    first token. Redispatch lands it on the cold replica — slower, but
    correct and invisible."""
    rng = random.Random(seed)
    sinks = []
    prompt = _PROMPT + " warm" * rng.randint(1, 3)
    # warm a replica's prefix cache and let its digest publish
    warm = [submit(srv, f"warm-{seed}-{i}", prompt=prompt, max_tokens=8)
            for i in range(2)]
    wait_terminal([s for s in warm if s is not None])
    time.sleep(0.35)  # digest refresh is rate-limited to 250 ms
    _arm("runner.inbox:nth=1", seed)
    submit(srv, f"wrd-{seed}", prompt=prompt, max_tokens=16, sinks=sinks)
    wedged = wait_terminal(sinks)
    return sinks, True, [f"{r}: no terminal event (wedged)" for r in wedged]


def scenario_warm_peer_fetch_death(srv, seed: int):
    """Fleet prefix sharing (docs/CACHING.md): the cost model picks
    fetch-to-cold (forced deterministic by the sched.fetch_decision
    flag) and the warm peer dies mid-fetch — on the wire (kv.peer_fetch
    drops a chunk) or outright (runner.inbox crashes the peer before it
    serves the export). The request must degrade to recompute on its
    target, terminate exactly once, and leak zero pages."""
    rng = random.Random(seed)
    sinks = []
    prompt = _PROMPT + " fetch" * rng.randint(1, 3)
    # warm one replica's prefix cache (cache_aware routes the repeats
    # together) and let its rolling digest publish
    warm = [submit(srv, f"pfw-{seed}-{i}", prompt=prompt, max_tokens=8)
            for i in range(2)]
    wait_terminal([s for s in warm if s is not None])
    time.sleep(0.35)  # digest refresh is rate-limited to 250 ms
    spec = rng.choice([
        # the export dies on the wire at the Nth chunk
        f"sched.fetch_decision:nth=1;kv.peer_fetch:nth={rng.randint(1, 2)}",
        # the peer runner itself crashes before serving the export
        "sched.fetch_decision:nth=1;runner.inbox:nth=1",
    ])
    _arm(spec, seed)
    submit(srv, f"pf-{seed}", prompt=prompt, max_tokens=16, sinks=sinks)
    wedged = wait_terminal(sinks)
    return sinks, True, [f"{r}: no terminal event (wedged)" for r in wedged]


def scenario_registry_partition(srv, seed: int):
    """Fleet control plane (docs/FLEET.md): heartbeats are dropped at
    the registry (fleet.heartbeat) while the member process lives on —
    the member must age alive -> suspect -> dead (its in-flight requests
    taking the redispatch path, its proxies leaving the routing set),
    then REJOIN on the first beat after the partition heals, with fresh
    proxies serving again."""
    rng = random.Random(seed)
    sinks = []
    _ensure_worker(srv)
    # drop enough consecutive beats to cross dead_after_s (1.5s at a
    # 100 ms beat), with headroom
    _arm(f"fleet.heartbeat:nth=1,times={rng.randint(22, 30)}", seed)
    extra = []
    # traffic keeps flowing during the partition (routes to whatever is
    # healthy; a zero-token request caught on the dying member must
    # redispatch invisibly — one that already STREAMED on it may fail
    # fast as engine_crashed, which is the documented bounded-failure
    # contract, so success is not required here, only exactly-once)
    for i in range(rng.randint(1, 3)):
        submit(srv, f"part-{seed}-{i}", sinks=sinks)
    if not _wait_member_state(srv, "dead", timeout_s=12.0):
        extra.append("member never aged out to dead under dropped beats")
    from distributed_inference_server_tpu.serving import faults as _faults

    _faults.clear()  # heal the partition
    if not _wait_member_state(srv, "alive", timeout_s=12.0):
        extra.append("member never rejoined after the partition healed")
    else:
        _ensure_worker(srv)  # proxy re-registered and healthy
        # the rejoined fleet MUST serve cleanly again, token-stream
        # and all — reconvergence means service, not just state
        rejoin_sink = submit(srv, f"part-{seed}-rejoin", sinks=sinks)
        if rejoin_sink is not None:
            rejoin_sink.ev.wait(60)
            if rejoin_sink.errors:
                extra.append(
                    f"post-rejoin request failed: {rejoin_sink.errors}")
    for s in sinks:
        for _msg, code in s.errors:
            if code != "engine_crashed":
                extra.append(f"{s.rid}: unexpected failure code {code!r} "
                             "(only mid-stream engine_crashed is a legal "
                             "partition casualty)")
    wedged = wait_terminal(sinks)
    extra += [f"{r}: no terminal event (wedged)" for r in wedged]
    return sinks, False, extra


def scenario_remote_runner_crash_mid_request(srv, seed: int):
    """A request is forwarded to a remote member and the member dies
    with it in flight, zero tokens streamed — on the registry host's
    wire (fleet.submit hit 1: the send itself fails) or as a worker
    crash on receipt (hit 2: the frame lands, the member drops the
    connection and serves nothing). Either way the request must complete
    via crash-safe redispatch, exactly once, token-identically."""
    rng = random.Random(seed)
    from distributed_inference_server_tpu.engine.engine import SamplingParams
    from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer
    from distributed_inference_server_tpu.serving.runner import ServerRequest

    _ensure_worker(srv)
    remote = next(r for r in srv.scheduler.engines()
                  if getattr(r, "is_remote", False))
    # hit 1 = RemoteRunner.submit (the wire), hit 2 = the worker's
    # executor (crash on receipt)
    _arm(f"fleet.submit:nth={rng.randint(1, 2)}", seed)
    sinks = []
    sink = ChaosSink(f"rrc-{seed}")
    sinks.append(sink)
    remote.submit([ServerRequest(
        sink.rid, ByteTokenizer().encode(_PROMPT),
        SamplingParams(max_tokens=16, temperature=0.0), sink,
    )])
    wedged = wait_terminal(sinks, timeout_s=60.0)
    return sinks, True, [f"{r}: no terminal event (wedged)" for r in wedged]


def scenario_rerole_flap(srv, seed: int):
    """Hysteresis under an oscillating queue: the sched.rerole flag
    forces the rebalance signal high on a random ~half of evaluations
    (seeded), so the DESIRED role flips every few ticks — the cooldown
    must bound the ACTUAL flips, traffic must keep completing, and the
    fleet must converge back to its configured all-unified admission
    topology once the oscillation stops."""
    rng = random.Random(seed)
    bal = srv.role_balancer
    bal.stop()  # scenarios drive evaluate() deterministically
    before = srv.metrics.fleet_counters()["reroles"]
    sinks = []
    _arm("sched.rerole:prob=0.5,times=1000", seed)
    t0 = time.monotonic()
    evals = rng.randint(30, 45)
    for i in range(evals):
        bal.evaluate()
        if i % 10 == 0:
            submit(srv, f"flapr-{seed}-{i}", max_tokens=8, sinks=sinks)
        time.sleep(0.02)
    from distributed_inference_server_tpu.serving import faults as _faults

    _faults.clear()
    elapsed = time.monotonic() - t0
    # converge back: with the flag gone the real signal is low, so the
    # balancer restores every engine it flipped (cooldown-paced)
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and bal.stats()["flipped"]:
        bal.evaluate()
        time.sleep(0.05)
    after = srv.metrics.fleet_counters()["reroles"]
    flips = (after.get("to_prefill", 0) - before.get("to_prefill", 0)) + (
        after.get("to_unified", 0) - before.get("to_unified", 0))
    # the hysteresis bound: at most one flip per cooldown window (plus
    # the first and the final restores, with slack for timer jitter)
    bound = int((elapsed + 10.0) / bal.settings.rerole_cooldown_s) + 2
    extra = []
    if flips > bound:
        extra.append(f"role flapping: {flips} flips in {elapsed:.1f}s "
                     f"(cooldown {bal.settings.rerole_cooldown_s}s, "
                     f"bound {bound})")
    if flips < 2:
        extra.append(f"rerole never exercised (flips={flips}) — the "
                     "sched.rerole lever did not drive a flip cycle")
    if bal.stats()["flipped"]:
        extra.append(f"balancer did not restore flipped engines: "
                     f"{bal.stats()['flipped']}")
    roles = {r.engine_id: r.role for r in srv.scheduler.engines()
             if not getattr(r, "is_remote", False)}
    if "prefill" in roles.values():
        extra.append(f"fleet did not converge back to unified: {roles}")
    wedged = wait_terminal(sinks)
    extra += [f"{r}: no terminal event (wedged)" for r in wedged]
    return sinks, True, extra


def scenario_cross_host_handoff_death(srv, seed: int):
    """Fleet KV data plane (docs/FLEET.md "KV data plane"): the host's
    prefill engine migrates every sequence to the member's decode
    replica over the data channel — and the stream dies mid-flight: the
    dial fails (fleet.kv_connect), the wire tears at the Nth chunk
    (fleet.kv_chunk), or the member crashes on the import command
    (runner.inbox). Every death is PRE-switchover, so the request must
    complete by decoding in place on the host, exactly once, with zero
    pages leaked on either side."""
    rng = random.Random(seed)
    _ensure_worker(srv)
    sinks = []
    spec = rng.choice([
        "fleet.kv_connect:nth=1",
        f"fleet.kv_chunk:nth={rng.randint(1, 3)}",
        # inbox hit 1 is the host prefill's submit; hits 2+ land on the
        # member runner's import open/commit commands
        f"runner.inbox:nth={rng.randint(2, 3)}",
    ])
    _arm(spec, seed)
    submit(srv, f"xh-{seed}", max_tokens=rng.randint(32, 48), sinks=sinks)
    wedged = wait_terminal(sinks, timeout_s=90.0)
    return sinks, True, [f"{r}: no terminal event (wedged)" for r in wedged]


def scenario_remote_fetch_source_death(srv, seed: int):
    """Fleet KV data plane: the cost model picks a REMOTE warm peer as
    the fetch source (forced deterministic via sched.fetch_decision)
    and the data channel dies under the fetch — dial failure or a chunk
    torn off the response stream. The request must degrade to plain
    recompute on its local target, terminate exactly once, and leak
    zero pages on either side."""
    rng = random.Random(seed)
    from distributed_inference_server_tpu.engine.engine import SamplingParams
    from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer
    from distributed_inference_server_tpu.serving.runner import ServerRequest

    _ensure_worker(srv)
    remote = next(r for r in srv.scheduler.engines()
                  if getattr(r, "is_remote", False))
    prompt = _PROMPT + " remote" * rng.randint(2, 3)
    # warm the MEMBER's prefix cache through the control wire, then
    # wait for its digest to ride a heartbeat into the routing snapshot
    warm = []
    for i in range(2):
        sink = ChaosSink(f"rfw-{seed}-{i}")
        remote.submit([ServerRequest(
            sink.rid, ByteTokenizer().encode(prompt),
            SamplingParams(max_tokens=8, temperature=0.0), sink,
        )])
        warm.append(sink)
    wait_terminal(warm)
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        s = remote.status()
        if s.prefix_digest and getattr(s, "data_plane", False):
            break
        time.sleep(0.05)
    sinks = []
    spec = rng.choice([
        "sched.fetch_decision:nth=1;fleet.kv_connect:nth=1",
        f"sched.fetch_decision:nth=1;fleet.kv_chunk:nth={rng.randint(1, 2)}",
    ])
    _arm(spec, seed)
    submit(srv, f"rf-{seed}", prompt=prompt, max_tokens=16, sinks=sinks)
    wedged = wait_terminal(sinks, timeout_s=90.0)
    return sinks, True, [f"{r}: no terminal event (wedged)" for r in wedged]


def _drive_remote(srv, rid: str, prompt: str = _PROMPT,
                  max_tokens: int = 8, sinks=None):
    """Submit one request straight at the member's remote proxy (the
    deterministic way to put TTFT samples in the MEMBER's digests)."""
    from distributed_inference_server_tpu.engine.engine import SamplingParams
    from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer
    from distributed_inference_server_tpu.serving.runner import ServerRequest

    remote = next(r for r in srv.scheduler.engines()
                  if getattr(r, "is_remote", False))
    sink = ChaosSink(rid)
    remote.submit([ServerRequest(
        rid, ByteTokenizer().encode(prompt),
        SamplingParams(max_tokens=max_tokens, temperature=0.0), sink,
    )])
    if sinks is not None:
        sinks.append(sink)
    return sink


def _remote_health(srv) -> str:
    remote = next(r for r in srv.scheduler.engines()
                  if getattr(r, "is_remote", False))
    return srv.health.state(remote.engine_id)


def scenario_slow_member_brownout(srv, seed: int):
    """The gray failure itself (docs/RESILIENCE.md "Gray failures and
    overload"): a member serves every forwarded request through a
    fleet.slow_member delay while heartbeating healthily. Its own TTFT
    telemetry carries the slowness to the host, whose HealthScorer must
    demote it (healthy -> degraded) so routing drains it WITHOUT a
    single client error — and once the delay clears and the windowed
    evidence decays, promote it back to healthy."""
    rng = random.Random(seed)
    _ensure_worker(srv)
    sinks = []
    extra = []

    def traffic(tag, n_local, n_remote, wait=True):
        batch = []
        for i in range(n_local):
            s = submit(srv, f"smb-{seed}-{tag}-l{i}", max_tokens=8,
                       sinks=sinks)
            if s is not None:
                batch.append(s)
        for i in range(n_remote):
            batch.append(_drive_remote(srv, f"smb-{seed}-{tag}-r{i}",
                                       sinks=sinks))
        if wait:
            wait_terminal(batch, timeout_s=90.0)
        return batch

    # phase 1: both sources collect windowed TTFT samples while the
    # member is SLOW (delay >> the tiny model's local TTFT)
    _arm(f"fleet.slow_member:prob=1.0,delay_ms={rng.randint(350, 450)},"
         "times=1000", seed)
    traffic("warm", 4, 4)
    # the member's digests ride its next heartbeat; demotion needs
    # demote_after consecutive bad evaluations on fresh telemetry
    deadline = time.monotonic() + 20.0
    while (time.monotonic() < deadline
           and _remote_health(srv) != "degraded"):
        traffic("evid", 1, 1)
        srv.health.evaluate()
        time.sleep(0.15)
    if _remote_health(srv) != "degraded":
        extra.append(
            f"slow member never demoted (health={_remote_health(srv)}, "
            f"stats={srv.health.stats()})")
    else:
        # degraded member drained: new admissions must complete clean
        # (routing tiers them onto the healthy local replica)
        traffic("drain", 3, 0)
    from distributed_inference_server_tpu.serving import faults as _faults

    _faults.clear()  # the member is fast again
    # recovery: fresh fast samples push the member's windowed p99 back
    # under recover_ratio x the median as the slow epochs fall out of
    # the short chaos window; recover_after clean evals promote it
    deadline = time.monotonic() + 30.0
    while (time.monotonic() < deadline
           and _remote_health(srv) != "healthy"):
        traffic("recov", 1, 1)
        srv.health.evaluate()
        time.sleep(0.25)
    if _remote_health(srv) != "healthy":
        extra.append(
            f"member never recovered (health={_remote_health(srv)}, "
            f"stats={srv.health.stats()})")
    wedged = wait_terminal(sinks, timeout_s=90.0)
    extra += [f"{r}: no terminal event (wedged)" for r in wedged]
    return sinks, True, extra


def scenario_breaker_flap(srv, seed: int):
    """A flapping KV data wire (fleet.wire_timeout) under cross-host
    handoffs: the channel's circuit breaker must open after
    health.wire_failures consecutive failures (handoffs degrade to
    decode-in-place, exactly once — and ELECTION skips the member, so
    streams stop being attempted at all), re-probe after
    breaker_open_s, and close once the wire heals. The flip count must
    stay bounded by the cooldown — a flapping wire must not flap the
    breaker faster than its hysteresis allows."""
    rng = random.Random(seed)
    _ensure_worker(srv)
    sinks = []
    extra = []

    def breaker():
        stats = srv.fleet_server.kv_stats().get("chaos-w1", {})
        return stats.get("breaker", {})

    def breaker_history():
        with srv.fleet_server._lock:
            sessions = list(srv.fleet_server._sessions)
        for session in sessions:
            with session._lock:
                ch = session.kv_channel
            if ch is not None and session.member_id == "chaos-w1":
                return ch.breaker.history()
        return []

    fires = rng.randint(4, 6)
    _arm(f"fleet.wire_timeout:prob=1.0,times={fires}", seed)
    # every admission wants a cross-host migration (host prefill ->
    # member decode); each failed stream walks the breaker toward open
    for i in range(4):
        submit(srv, f"bf-{seed}-{i}", max_tokens=rng.randint(24, 40),
               sinks=sinks)
        wait_terminal(sinks[-1:], timeout_s=90.0)
        if breaker().get("state") == "open":
            break
    if breaker().get("state") != "open":
        extra.append(f"breaker never opened: {breaker()}")
    from distributed_inference_server_tpu.serving import faults as _faults

    _faults.clear()  # the wire heals
    # half-open probe: after the cooldown the next handoff is allowed
    # through and must close the breaker
    open_s = srv.health_settings.breaker_open_s
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline and breaker().get("state") != "closed":
        time.sleep(max(0.05, open_s / 4))
        submit(srv, f"bf-{seed}-p{int(time.monotonic() * 1000)}",
               max_tokens=16, sinks=sinks)
        wait_terminal(sinks[-1:], timeout_s=90.0)
    stats = breaker()
    if stats.get("state") != "closed":
        extra.append(f"breaker never re-closed after heal: {stats}")
    # THE hysteresis property: no half-open probe window opens before
    # the cooldown elapsed since the breaker opened (flip rate is
    # bounded by open_s, however hard the wire flaps)
    history = breaker_history()
    last_open = None
    probes = 0
    for t, state in history:
        if state == "open":
            last_open = t
        elif state == "half_open":
            probes += 1
            if last_open is not None and t - last_open < open_s * 0.85:
                extra.append(
                    f"breaker half-opened {t - last_open:.3f}s after "
                    f"opening (cooldown {open_s}s) — hysteresis broken")
    if probes < 1:
        extra.append(f"breaker never probed half-open: {history}")
    wedged = wait_terminal(sinks, timeout_s=90.0)
    extra += [f"{r}: no terminal event (wedged)" for r in wedged]
    return sinks, True, extra


def scenario_overload_shed(srv, seed: int):
    """Deadline-aware admission under synthetic overload: with the
    windowed queue-wait estimate blown past the TTFT-SLO deadline,
    new submissions must shed AT ADMISSION — AdmissionShed (503 +
    Retry-After upstream), decided fast, with the distinct terminal in
    the flight recorder and requests_shed_total counted — while already
    admitted traffic completes and, once the short window decays,
    admission recovers. Shed requests never touch an engine: the page
    audit proves zero leak."""
    rng = random.Random(seed)
    from distributed_inference_server_tpu.engine.engine import SamplingParams
    from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer
    from distributed_inference_server_tpu.serving.health import AdmissionShed
    from distributed_inference_server_tpu.serving.runner import ServerRequest

    sinks = []
    extra = []
    # phase 1: normal service
    for i in range(2):
        submit(srv, f"os-{seed}-a{i}", max_tokens=8, sinks=sinks)
    wait_terminal(sinks, timeout_s=90.0)
    # phase 2: synthetic overload — the queue-wait digest reads like a
    # fleet whose backlog already exceeds every deadline (the organic
    # feeder is flightrec's phase partition; the digest is the contract)
    for _ in range(12):
        srv.metrics.perf_store().observe("queue_wait_ms",
                                         rng.uniform(1500, 2500))
    time.sleep(0.35)  # the admission estimator caches ~250 ms
    shed = 0
    for i in range(3):
        sink = ChaosSink(f"os-{seed}-s{i}")
        t0 = time.monotonic()
        try:
            srv.dispatcher.submit(ServerRequest(
                sink.rid, ByteTokenizer().encode(_PROMPT),
                SamplingParams(max_tokens=8, temperature=0.0), sink,
            ))
        except AdmissionShed as e:
            shed += 1
            decide_ms = (time.monotonic() - t0) * 1000.0
            if decide_ms > 50.0:
                extra.append(f"shed decision took {decide_ms:.1f}ms "
                             "(want < 50ms)")
            if e.retry_after_s < 1.0:
                extra.append(f"Retry-After hint {e.retry_after_s} < 1s")
            tl = srv.recorder.timeline(sink.rid)
            if tl is None or tl.get("code") != "admission_shed":
                extra.append(f"{sink.rid}: no admission_shed terminal "
                             f"in the flight recorder (got {tl})")
        else:
            # admitted against a blown estimate: a violation — but the
            # request is live, so track its sink for exactly-once
            sinks.append(sink)
            extra.append(f"{sink.rid}: admitted despite overload")
    if shed == 0:
        extra.append("no requests shed under synthetic overload")
    snap = srv.metrics.snapshot().to_dict()
    shed_counts = (snap.get("resilience") or {}).get("requests_shed", {})
    if not shed_counts:
        extra.append("requests_shed_total never counted")
    # phase 3: the short chaos SLO window decays; admission recovers
    deadline = time.monotonic() + 15.0
    recovered = None
    while time.monotonic() < deadline and recovered is None:
        time.sleep(0.5)
        s = submit(srv, f"os-{seed}-r{int(time.monotonic() * 1000)}",
                   max_tokens=8, sinks=sinks)
        recovered = s
    if recovered is None:
        extra.append("admission never recovered after the window decayed")
    wedged = wait_terminal(sinks, timeout_s=90.0)
    extra += [f"{r}: no terminal event (wedged)" for r in wedged]
    return sinks, True, extra


def scenario_mesh_peer_wire_death(srv, seed: int):
    """The KV mesh (docs/FLEET.md "KV mesh"): the cost model picks a
    REMOTE fetch target (chaos-w2) against a remote warm peer
    (chaos-w1) — admissible only because the registry introduced the
    pair — so the host ships a fetch HINT and w2 pulls the chunks
    directly from w1 over its own data wire. Then that wire dies: the
    peer dial fails (fleet.kv_peer_dial), a chunk tears off the
    response stream (fleet.kv_chunk), or w2's import session rejects a
    chunk (kv.import_chunk). Every death must degrade the hinted
    request to plain recompute ON THE MEMBER, exactly once, with zero
    pages leaked on any of the three processes."""
    rng = random.Random(seed)
    from distributed_inference_server_tpu.engine.engine import SamplingParams
    from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer
    from distributed_inference_server_tpu.serving.runner import ServerRequest

    _ensure_worker(srv)
    _ensure_worker2(srv)
    w1 = next(r for r in srv.scheduler.engines()
              if r.engine_id.startswith("chaos-w1:"))
    # seed-unique from the FIRST page: chain hashes are cumulative, so
    # a shared head (the previous seed's recompute left _PROMPT's pages
    # on w2) would leave w2 within min_pages of the peer's depth and
    # cost it its fetch option on a reused fleet
    prompt = f"mesh{seed} " * rng.randint(2, 3) + _PROMPT
    # warm the prefix on MEMBER w1 through the control wire, then wait
    # for its digest to ride a heartbeat AND for the registry to have
    # both data endpoints (the introduction precondition)
    warm = []
    for i in range(2):
        sink = ChaosSink(f"mw-{seed}-{i}")
        w1.submit([ServerRequest(
            sink.rid, ByteTokenizer().encode(prompt),
            SamplingParams(max_tokens=8, temperature=0.0), sink,
        )])
        warm.append(sink)
    wait_terminal(warm)
    from distributed_inference_server_tpu.engine.kv_cache import chain_hashes
    from distributed_inference_server_tpu.serving.scheduler import (
        prefix_match_depth,
    )
    toks = ByteTokenizer().encode(prompt)
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        s = w1.status()
        # the digest must cover THIS seed's prompt (a reused fleet's
        # digest is already non-empty from the previous seed — waiting
        # on mere truthiness would race the heartbeat carrying the new
        # chain and leave plan_route with no fetch option to force)
        hashes = chain_hashes(toks, max(1, getattr(s, "page_size", 0) or 1))
        if (hashes and prefix_match_depth(s, hashes) == len(hashes)
                and getattr(s, "data_plane", False)
                and srv.fleet_server.mesh_route("chaos-w2", "chaos-w1")):
            break
        time.sleep(0.05)

    def delegated_count():
        cache = srv.metrics.snapshot().to_dict().get("cache") or {}
        return (cache.get("peer_fetch") or {}).get("delegated", 0)

    before = delegated_count()
    sinks = []
    spec = rng.choice([
        "sched.fetch_decision:nth=1;fleet.kv_peer_dial:nth=1",
        f"sched.fetch_decision:nth=1;fleet.kv_chunk:nth={rng.randint(1, 2)}",
        "sched.fetch_decision:nth=1;kv.import_chunk:nth=1",
    ])
    # the local engine is unregistered for the one faulted decision, so
    # the mesh pair is the ONLY fetch option the flag can force: a
    # previous seed's transfer leaves a (correctly) terrible learned
    # rate on the mesh wire, and pricing the relay against it would
    # route the fetch through the host — sound routing, wrong scenario
    local = next(r for r in srv.scheduler.engines()
                 if not getattr(r, "is_remote", False))
    srv.scheduler.unregister(local.engine_id)
    try:
        _arm(spec, seed)
        submit(srv, f"mesh-{seed}", prompt=prompt, max_tokens=16,
               sinks=sinks)
        wedged = wait_terminal(sinks, timeout_s=90.0)
    finally:
        srv.scheduler.register(local)
    extra = [f"{r}: no terminal event (wedged)" for r in wedged]
    if delegated_count() <= before:
        extra.append("fetch was never delegated to the mesh "
                     "(no fetch hint left the host)")
    return sinks, True, extra


def _registry_serving(reg_srv) -> bool:
    """A registry's federated view is LIVE: the member alive in its
    table and a healthy remote proxy in its routing set."""
    return (reg_srv.fleet_registry.member_state("chaos-w1") == "alive"
            and any(getattr(r, "is_remote", False) and r.is_healthy()
                    for r in reg_srv.scheduler.engines()))


def scenario_registry_failover(srv, seed: int):
    """Registry HA (docs/FLEET.md "Registry HA"): the PRIMARY registry
    dies in-process — lease loop and member listener stopped cold — and
    the warm standby must promote within the lease window at a bumped
    epoch, its member table and proxies already live from the dual
    heartbeat, and serve traffic through its OWN ingress. Then the old
    primary restarts on the same port and must rejoin as a STANDBY (it
    boots at epoch 0, learns the cluster epoch from the new primary's
    lease, and never splits the brain). Odd seeds crash the standby's
    first promotion attempt (fleet.takeover) — the takeover must be
    atomic-or-absent, the election simply re-running next tick."""
    rng = random.Random(seed)
    from distributed_inference_server_tpu.serving import faults as _faults

    _ensure_worker(srv)
    # the fleet is reused across seeds and each iteration SWAPS the
    # roles — find the current primary instead of assuming srv holds it
    a, b = srv, srv._ha_standby_srv
    pri, stb = (a, b) if a.fleet_ha.is_primary() else (b, a)
    lease_s = srv.fleet_settings.lease_s
    pri_epoch = pri.fleet_ha.epoch
    sinks = []
    extra = []
    # pre-kill reference traffic through the primary
    for i in range(rng.randint(1, 2)):
        submit(pri, f"fo-{seed}-a{i}", sinks=sinks)
    wedged = wait_terminal(sinks)
    extra += [f"{r}: no terminal event (wedged)" for r in wedged]
    if seed % 2:
        # crash the standby at the start of its first promotion: the
        # fault fires before any state changed, so the next tick must
        # simply re-run the election (atomic-or-absent)
        _arm("fleet.takeover:nth=1", seed)
    # the primary dies in-process: listener + HA loop gone, engines
    # orphaned (the true SIGKILL path is fleet_smoke --ha)
    pri.fleet_ha.stop()
    pri.fleet_server.stop()
    if not _wait_until(stb.fleet_ha.is_primary,
                       timeout_s=lease_s * 4 + 5.0):
        extra.append(f"standby never promoted: {stb.fleet_ha.stats()}")
    _faults.clear()
    takeovers = stb.fleet_ha.stats()["takeovers"]
    if stb.fleet_ha.is_primary() and not takeovers.get("lease_expired"):
        extra.append(f"promotion not counted as lease_expired: {takeovers}")
    if stb.fleet_ha.is_primary() and stb.fleet_ha.epoch <= pri_epoch:
        extra.append(
            f"promotion did not bump the epoch past the old primary's: "
            f"{stb.fleet_ha.epoch} <= {pri_epoch}")
    # the standby was WARM: its member table and proxies must go (stay)
    # live without the member doing anything but its usual beats
    if not _wait_until(lambda: _registry_serving(stb), timeout_s=10.0):
        extra.append("standby's warm member table never went live after "
                     "takeover")
    else:
        post = submit(stb, f"fo-{seed}-post", sinks=sinks)
        if post is None:
            extra.append("post-takeover submit rejected at the new primary")
    wedged = wait_terminal(sinks, timeout_s=90.0)
    extra += [f"{r}: no terminal event (wedged)" for r in wedged]
    # the old primary restarts on the SAME port: it must come back
    # standby, learn the new epoch from the lease, and NOT fight
    pri.fleet_server.start()
    pri.fleet_ha.start(f"127.0.0.1:{pri.fleet_server.bound_port}")
    if not _wait_until(
            lambda: (not pri.fleet_ha.is_primary()
                     and pri.fleet_ha.epoch == stb.fleet_ha.epoch),
            timeout_s=lease_s * 4 + 5.0):
        extra.append(
            f"old primary did not rejoin as standby at the new epoch: "
            f"{pri.fleet_ha.stats()} vs {stb.fleet_ha.stats()}")
    if stb.fleet_ha.is_primary() == pri.fleet_ha.is_primary():
        extra.append(
            f"not exactly one primary after rejoin: "
            f"{pri.fleet_ha.stats()} / {stb.fleet_ha.stats()}")
    # the member's wire to the restarted listener reconnects and the
    # old primary's (now standby) view warms back up — reconvergence
    # means every front door serves again
    if not _wait_until(lambda: _registry_serving(pri), timeout_s=15.0):
        extra.append("restarted registry's member table never re-warmed")
    return sinks, False, extra


def scenario_registry_split_brain(srv, seed: int):
    """Registry HA fencing (docs/FLEET.md "Registry HA"): a
    registry<->registry partition (fleet.lease_beat drops every lease
    beat before the wire) while BOTH registries live. The standby's
    lease expires, it promotes at a higher epoch — two primaries exist.
    The member, having executed one control frame from the new primary,
    must bounce the OLD primary's submits as stale-epoch failures
    (which redispatch on the old primary's own local engine, invisibly
    to the client). When the partition heals, the old primary sees the
    higher-epoch lease and demotes — fenced, exactly one primary."""
    rng = random.Random(seed)  # noqa: F841 — seed selects the FaultSet RNG
    from distributed_inference_server_tpu.serving import faults as _faults

    worker = _ensure_worker(srv)
    # the fleet is reused across seeds and each iteration SWAPS the
    # roles — find the current primary instead of assuming srv holds it
    a, b = srv, srv._ha_standby_srv
    pri, stb = (a, b) if a.fleet_ha.is_primary() else (b, a)
    lease_s = srv.fleet_settings.lease_s
    fenced_before = pri.fleet_ha.stats()["takeovers"].get("fenced", 0)
    sinks = []
    extra = []
    # the partition: every lease beat drops before the wire (the point
    # fires on the PRIMARY's send path only; RegistryState echoes and
    # member heartbeats still flow — a pure registry<->registry split)
    _arm("fleet.lease_beat:prob=1.0,times=100000", seed)
    if not _wait_until(stb.fleet_ha.is_primary,
                       timeout_s=lease_s * 4 + 5.0):
        extra.append(f"standby never promoted under the partition: "
                     f"{stb.fleet_ha.stats()}")
    split = pri.fleet_ha.is_primary() and stb.fleet_ha.is_primary()
    if not split:
        extra.append(
            f"no split-brain manufactured: {pri.fleet_ha.stats()} / "
            f"{stb.fleet_ha.stats()}")
    # teach the member the NEW epoch: one request through the new
    # primary's remote proxy puts its epoch on a FleetSubmit frame
    if _wait_until(lambda: _registry_serving(stb), timeout_s=10.0):
        _drive_remote(stb, f"sb-{seed}-new", sinks=sinks)
        wait_terminal(sinks[-1:], timeout_s=60.0)
        if worker._fleet_epoch != stb.fleet_ha.epoch:
            extra.append(
                f"member never learned the new primary's epoch: "
                f"{worker._fleet_epoch} != {stb.fleet_ha.epoch}")
    else:
        extra.append("new primary's member view never went live")
    # the OLD primary (still primary, lower epoch) forwards a request
    # straight at its remote proxy: the member must fence it (stale
    # epoch -> worker_failure event). The old primary redispatches on
    # its side — usually completing on its local engine, but the
    # documented bounded-failure contract allows the budget to exhaust
    # as worker_failure if routing keeps re-picking the fenced proxy;
    # what is NEVER legal is the member executing the stale control
    if split and _registry_serving(pri):
        fenced = _drive_remote(pri, f"sb-{seed}-old", sinks=sinks)
        fenced.ev.wait(60.0)
        for _msg, code in fenced.errors:
            if code != "worker_failure":
                extra.append(
                    f"fenced submit failed with {code!r} (want a clean "
                    "redispatch completion or worker_failure)")
    # heal: the surviving lease beats reach the old primary, which must
    # demote (fenced) — exactly one primary again
    _faults.clear()
    if not _wait_until(
            lambda: (not pri.fleet_ha.is_primary()
                     and stb.fleet_ha.is_primary()),
            timeout_s=lease_s * 4 + 5.0):
        extra.append(
            f"old primary never fenced after the partition healed: "
            f"{pri.fleet_ha.stats()} / {stb.fleet_ha.stats()}")
    else:
        fenced_after = pri.fleet_ha.stats()["takeovers"].get("fenced", 0)
        if fenced_after <= fenced_before:
            extra.append(
                f"demotion not counted as fenced: {pri.fleet_ha.stats()}")
        if pri.fleet_ha.epoch != stb.fleet_ha.epoch:
            extra.append(
                f"epochs never converged: {pri.fleet_ha.epoch} != "
                f"{stb.fleet_ha.epoch}")
    wedged = wait_terminal(sinks, timeout_s=90.0)
    extra += [f"{r}: no terminal event (wedged)" for r in wedged]
    return sinks, False, extra


#: chaos-paced gray-failure settings (serving/health.py): scenarios
#: drive evaluate() themselves (interval_s=60), evidence windows short
#: enough to decay inside one scenario, thresholds low enough for a
#: tiny CPU fleet's jitter
def _chaos_health():
    from distributed_inference_server_tpu.serving.health import (
        HealthSettings,
    )

    return HealthSettings(
        interval_s=60.0, stall_s=10.0, latency_ratio=2.5,
        recover_ratio=1.2, demote_after=2, recover_after=2,
        min_window_requests=3, wire_failures=2, breaker_open_s=0.4,
    )


def _chaos_slo():
    from distributed_inference_server_tpu.serving.teledigest import (
        SloSettings,
    )

    return SloSettings(ttft_ms=300.0, window_s=4.0, epoch_s=0.5)


def _chaos_admission():
    from distributed_inference_server_tpu.serving.health import (
        AdmissionSettings,
    )

    return AdmissionSettings(min_window_requests=4)


#: scenario -> (fn, fleet kwargs)
SCENARIOS = {
    "redispatch": (scenario_redispatch, {}),
    "crash_mid_handoff": (scenario_crash_mid_handoff,
                          {"roles": ("prefill", "decode")}),
    "crash_mid_import": (scenario_crash_mid_import,
                         {"roles": ("prefill", "decode")}),
    "channel_truncation": (scenario_channel_truncation,
                           {"roles": ("prefill", "decode"),
                            "channel": "protowire"}),
    "degradation_flap": (scenario_degradation_flap, {}),
    "warm_replica_death": (scenario_warm_replica_death,
                           {"strategy": "cache_aware"}),
    # fleet prefix sharing: digests need the Python allocator tier (the
    # native allocator has no digest surface → no warm peer to fetch
    # from), and protowire exercises the KvPrefixFetch/KvChunk framing
    "warm_peer_fetch_death": (scenario_warm_peer_fetch_death,
                              {"strategy": "cache_aware",
                               "channel": "protowire",
                               "engine_kwargs": {
                                   "native_allocator": False}}),
    # fleet control plane (docs/FLEET.md): one registry host (one local
    # unified engine) + one member (one unified engine) over a real
    # localhost fleet-wire connection
    "registry_partition": (scenario_registry_partition,
                           {"roles": ("unified",), "fleet": True}),
    "remote_runner_crash_mid_request": (
        scenario_remote_runner_crash_mid_request,
        {"roles": ("unified",), "fleet": True}),
    # registry HA (docs/FLEET.md "Registry HA"): two registry hosts on
    # an ordered fleet.registries list + one dual-heartbeating member;
    # the primary dies in-process / is partitioned and the warm standby
    # takes over lease-fenced
    "registry_failover": (scenario_registry_failover,
                          {"roles": ("unified",), "ha": True}),
    "registry_split_brain": (scenario_registry_split_brain,
                             {"roles": ("unified",), "ha": True}),
    # role rebalancing: one unified admission engine + one decode target
    # (list-form roles skip parse_roles's static-topology check — the
    # balancer IS the prefill source here)
    "rerole_flap": (scenario_rerole_flap,
                    {"roles": ("unified", "decode"), "rerole": True}),
    # fleet KV data plane (docs/FLEET.md "KV data plane"): the host's
    # only engine is prefill-role, the member's only engine decode-role
    # — every admission wants a cross-host migration over the data
    # channel (list-form roles skip the static-topology check: the
    # decode capacity lives on the member)
    "cross_host_handoff_death": (scenario_cross_host_handoff_death,
                                 {"roles": ("prefill",), "fleet": True,
                                  "member_roles": ("decode",)}),
    # remote fetch source: digests need the Python allocator tier (no
    # digest surface on the native allocator — same constraint as
    # warm_peer_fetch_death)
    "remote_fetch_source_death": (scenario_remote_fetch_source_death,
                                  {"roles": ("unified",), "fleet": True,
                                   "strategy": "cache_aware",
                                   "member_roles": ("unified",),
                                   "engine_kwargs": {
                                       "native_allocator": False}}),
    # gray-failure defense (docs/RESILIENCE.md "Gray failures and
    # overload"): a slow-but-alive member demoted and drained by the
    # latency-scored HealthScorer, then recovered (the two-sided
    # hysteresis); short SLO windows so the evidence decays in-scenario
    "slow_member_brownout": (scenario_slow_member_brownout,
                             {"roles": ("unified",), "fleet": True,
                              "member_roles": ("unified",),
                              "health": _chaos_health(),
                              "slo": _chaos_slo()}),
    # the data-channel circuit breaker under a flapping wire: host
    # prefill -> member decode, every admission wants a cross-host
    # migration stream (the cross_host_handoff_death topology)
    "breaker_flap": (scenario_breaker_flap,
                     {"roles": ("prefill",), "fleet": True,
                      "member_roles": ("decode",),
                      "health": _chaos_health()}),
    # deadline-aware admission shedding under synthetic overload: TTFT
    # SLO armed so requests HAVE a deadline, short windows so the
    # overload evidence decays and admission recovers in-scenario
    "overload_shed": (scenario_overload_shed,
                      {"roles": ("unified",),
                       "health": _chaos_health(),
                       "slo": _chaos_slo(),
                       "admission": _chaos_admission()}),
    # the KV mesh (docs/FLEET.md "KV mesh"): registry + TWO members,
    # the fetch delegated member->member over the brokered wire, and
    # the wire killed under it. Digests need the Python allocator tier
    # (same constraint as warm_peer_fetch_death).
    "mesh_peer_wire_death": (scenario_mesh_peer_wire_death,
                             {"roles": ("unified",), "mesh": True,
                              "strategy": "cache_aware",
                              "member_roles": ("unified",),
                              "engine_kwargs": {
                                  "native_allocator": False}}),
}


def dump_postmortems(srv, sinks, violations) -> None:
    """The violating requests' stories (docs/OBSERVABILITY.md): each
    implicated request's flight-recorder timeline + stitched trace —
    a seeded repro now starts from a narrative, not just a seed.
    Requests named in a violation dump first; if none are named (e.g.
    a reconvergence failure), the scenario's requests dump instead,
    capped so a wide scenario stays readable."""
    from tools.fleet_smoke import dump_postmortem

    named = [s.rid for s in sinks
             if any(s.rid in v for v in violations)]
    rids = (named or [s.rid for s in sinks])[:5]
    for rid in rids:
        dump_postmortem(srv, rid)


def run_scenario(name: str, seed: int, srv=None):
    """One scenario iteration on a fresh seed; returns (violations,
    srv) — the fleet is reusable across seeds of the same scenario
    (auto-restart heals crash damage between iterations). Faults are
    ALWAYS disarmed before the invariant check. A violation dumps the
    implicated requests' flight-recorder timelines + stitched traces
    before returning (docs/OBSERVABILITY.md postmortems)."""
    from distributed_inference_server_tpu.serving import faults

    fn, fleet_kwargs = SCENARIOS[name]
    if srv is None:
        srv = build_fleet(**fleet_kwargs)
    try:
        sinks, require_success, extra = fn(srv, seed)
    finally:
        faults.clear()
    violations = list(extra)
    violations += check_invariants(srv, sinks,
                                   require_success=require_success)
    if violations:
        dump_postmortems(srv, sinks, violations)
    return violations, srv


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument("minutes", nargs="?", type=float, default=None,
                    help="time budget: loop fresh seeds until it runs out")
    ap.add_argument("--seeds", type=int, default=20,
                    help="fresh seeds per scenario (ignored with a time "
                    "budget or --seed)")
    ap.add_argument("--seed", type=int, default=None,
                    help="run exactly this seed (reproduction)")
    ap.add_argument("--base-seed", type=int, default=None,
                    help="first seed of the sweep (default: wall clock)")
    ap.add_argument("--scenarios",
                    default=",".join(DEFAULT_SCENARIOS),
                    help="comma-separated subset of: "
                    + ", ".join(DEFAULT_SCENARIOS))
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()
    if args.list:
        for name in SCENARIOS:
            print(name)
        return 0
    _env_setup()
    names = [s for s in args.scenarios.split(",") if s.strip()]
    for n in names:
        if n not in SCENARIOS:
            print(f"unknown scenario {n!r} (see --list)", file=sys.stderr)
            return 2

    if args.seed is not None:
        seeds = [args.seed]
    else:
        base = (args.base_seed if args.base_seed is not None
                else int(time.time()) % 1_000_000)
        seeds = [base + i for i in range(args.seeds)]
    deadline = (time.monotonic() + args.minutes * 60
                if args.minutes else None)

    total = 0
    t_start = time.monotonic()
    for name in names:
        srv = None
        try:
            i = 0
            while True:
                if deadline is None:
                    if i >= len(seeds):
                        break
                    seed = seeds[i]
                else:
                    if time.monotonic() >= deadline:
                        break
                    seed = (args.base_seed or int(t_start)) * 1000 + total
                i += 1
                total += 1
                violations, srv = run_scenario(name, seed, srv=srv)
                if violations:
                    print(f"VIOLATION scenario={name} seed={seed}:")
                    for v in violations:
                        print(f"  - {v}")
                    print(f"\nreproduce: python tools/chaos_fleet.py "
                          f"--seed {seed} --scenarios {name}")
                    return 1
                print(f"ok scenario={name} seed={seed}", flush=True)
        finally:
            from distributed_inference_server_tpu.serving import faults

            faults.clear()
            if srv is not None:
                srv.shutdown(drain_timeout_s=5.0)
    print(f"chaos clean: {total} iterations across {names} in "
          f"{time.monotonic() - t_start:.0f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
