"""Multi-host fleet control plane: federated engine registry, the fleet
wire, and dynamic role rebalancing (docs/FLEET.md).

Everything below the serving spine so far scaled within one process:
``server.engine_roles`` builds local runners and the dispatcher routes
against one in-process fleet snapshot. This subsystem federates it:

- **FleetRegistry** — membership truth for the whole fleet. Worker
  processes join by dialing the registry host and heartbeating
  (``FleetHeartbeat`` = member id + its full ``EngineStatus`` replica
  set, digests included, over the protowire codec); the registry ages
  members out on missed beats through an ``alive -> suspect -> dead``
  state machine and feeds every consumer — scheduler routing, metrics,
  ``/server/stats`` — the merged local+remote snapshot.
- **the fleet wire** — one duplex TCP connection per member carrying
  length-delimited protowire frames (u32 payload length, u8 kind, the
  encoded message): ``FleetHeartbeat`` and ``FleetEvent`` flow worker →
  registry host, ``FleetSubmit`` flows back. ``FleetServer`` owns the
  listener and one reader thread per member session; each heartbeat
  registers/refreshes a ``RemoteRunner`` proxy per remote engine
  (serving/remote_runner.py) in the scheduler, so the entire existing
  dispatch spine — strategies, cache_aware cost model, redispatch —
  routes remote replicas with zero special cases.
- **RoleBalancer** — dynamic role rebalancing: when the fleet's prompt
  queue deepens past ``fleet.rerole_high_ratio`` (queued + waiting
  prompts per admission-capable replica), one ``unified`` engine
  re-roles to ``prefill`` (the disagg machinery makes the flip a single
  attribute write — the next admission batch simply parks its prefills
  for migration); it flips back once the signal drops below
  ``fleet.rerole_low_ratio``. Two-sided hysteresis (signal band + a
  flip cooldown) keeps an oscillating queue from flapping roles — the
  ``rerole_flap`` chaos scenario pins that. The balancer only restores
  engines IT flipped, so an operator's static topology is never
  rewritten.

Failure semantics (docs/RESILIENCE.md): a dead member's ``RemoteRunner``
proxies map remote death onto the existing crash-safe redispatch path —
zero-token in-flight requests re-dispatch exactly once onto healthy
replicas, mid-stream requests fail fast as ``engine_crashed``. Fault
points: ``fleet.heartbeat`` (registry ingest drops the beat — the
partition model), ``fleet.submit`` (the forwarded submit dies on the
wire / the worker crashes on receipt), ``sched.rerole`` (flag: forces
the rebalance signal high for one evaluation — the chaos lever that
drives reroles deterministically).
"""

from __future__ import annotations

import json
import logging
import socket
import struct
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

from distributed_inference_server_tpu.core.errors import ConfigError
from distributed_inference_server_tpu.serving import faults, protowire
from distributed_inference_server_tpu.serving.metrics import (
    EngineStatus,
    MetricsCollector,
)
from distributed_inference_server_tpu.utils.tracing import Span

logger = logging.getLogger(__name__)

MEMBER_ALIVE = "alive"
MEMBER_SUSPECT = "suspect"
MEMBER_DEAD = "dead"
MEMBER_STATES = (MEMBER_ALIVE, MEMBER_SUSPECT, MEMBER_DEAD)


@dataclass(frozen=True)
class FleetSettings:
    """Knobs of the fleet control plane (config section ``fleet``)."""

    enabled: bool = False
    host: str = "127.0.0.1"
    port: int = 0  # registry listener; 0 = ephemeral (tests/smoke)
    connect: str = ""  # worker mode: "host:port" of the registry host
    member_id: str = ""  # worker identity; "" = derived host:pid
    heartbeat_interval_s: float = 0.5
    suspect_after_s: float = 2.0
    dead_after_s: float = 5.0
    rerole: bool = False
    rerole_high_ratio: float = 4.0
    rerole_low_ratio: float = 1.0
    rerole_cooldown_s: float = 10.0
    rerole_interval_s: float = 0.5
    # dead members are kept for observability, then pruned: every worker
    # restart mints a new host:pid identity, so without eviction the
    # member table (and fleet_members{state="dead"}) grows forever
    dead_retention_s: float = 300.0
    # fleet KV data plane (serving/fleet_kv.py; docs/FLEET.md "KV data
    # plane"): workers bind a KV data listener (kv_data_port; 0 =
    # ephemeral) and advertise it per heartbeat; the registry host
    # dials it lazily for cross-host handoff and peer prefix fetch,
    # with at most kv_max_streams bulk streams in flight per member.
    # kv_enabled=False keeps a worker control-plane-only.
    kv_enabled: bool = True
    kv_data_port: int = 0
    kv_max_streams: int = 4
    kv_connect_timeout_s: float = 5.0
    # KV mesh (serving/fleet_mesh.py; docs/FLEET.md "KV mesh"): the
    # registry brokers member endpoints over KvIntro frames and members
    # dial each other directly — bulk fetch bytes skip the registry.
    # Off by default: the relay topology is the compatible baseline.
    mesh_enabled: bool = False
    # learned wire-rate window and prior (serving/fleet_mesh.py): rates
    # older than the window are forgotten; kv_rate_prior (bytes/s) is
    # the rate kv_page_cost is assumed to price — a wire measured at
    # the prior costs exactly the constant. <= 0 disables learned
    # pricing (every wire charges the constant).
    kv_rate_window_s: float = 30.0
    kv_rate_prior: float = 125000000.0
    # Registry HA (serving/fleet_ha.py; docs/FLEET.md "Registry HA"):
    # ordered registry endpoint list shared by every process. Workers
    # dial ALL of them (dual-heartbeat); registries heartbeat each
    # other and elect a lease-fenced primary (list order breaks ties).
    # () = single-registry fleet, HA machinery entirely dormant.
    registries: Tuple[str, ...] = ()
    # lease aging on the PRIMARY itself: a standby marks the lease
    # suspect after lease_suspect_s without a beat and takes over after
    # lease_s (the same alive->suspect->dead machinery used on members)
    lease_s: float = 3.0
    lease_suspect_s: float = 1.5
    # multi-ingress: standbys serve HTTP against their own federated
    # view. False = a standby's dispatcher rejects ingress (QueueFull)
    # until it holds the lease — single-front-door deployments.
    standby_http: bool = True


# ---------------------------------------------------------------------------
# The fleet wire: length-delimited protowire frames over one TCP stream
# ---------------------------------------------------------------------------

FRAME_KINDS: Dict[int, str] = {
    1: "FleetHeartbeat",
    2: "FleetSubmit",
    3: "FleetEvent",
    # fleet-stitched tracing (docs/OBSERVABILITY.md): finished member
    # spans, batched at heartbeat cadence, worker -> registry host
    4: "FleetSpans",
    # fleet-federated performance telemetry (serving/teledigest.py):
    # member digests + step-clock counters, heartbeat-piggybacked
    5: "FleetTelemetry",
    # KV mesh introduction (serving/fleet_mesh.py): registry host ->
    # worker, brokering member-to-member data-plane endpoints
    6: "KvIntro",
    # registry HA (serving/fleet_ha.py): primary -> standby lease beat
    # and standby -> primary state echo, registry <-> registry
    7: "RegistryLease",
    8: "RegistryState",
}
_KIND_BY_NAME = {name: kind for kind, name in FRAME_KINDS.items()}

#: a fleet frame is control-plane small (statuses, token events, prompt
#: ids) — anything bigger is a torn/foreign stream, not a real frame
MAX_FRAME_BYTES = 16 * 1024 * 1024


class FleetWireError(RuntimeError):
    """A malformed frame on the fleet wire (foreign protocol, torn
    stream, oversized payload). The session treats it as member death."""


def send_frame(sock: socket.socket, name: str, obj: Dict[str, Any]) -> None:
    """Encode ``obj`` as message ``name`` and write one frame. Callers
    serialize sends per socket themselves (one lock per session)."""
    payload = protowire.encode(name, obj)
    sock.sendall(struct.pack(">IB", len(payload), _KIND_BY_NAME[name])
                 + payload)


def _recv_exact(sock: socket.socket, n: int) -> Optional[bytes]:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None  # orderly EOF
        buf += chunk
    return bytes(buf)


def recv_frame(sock: socket.socket) -> Optional[Tuple[str, Dict[str, Any]]]:
    """Read one frame; returns ``(message_name, decoded_dict)`` or None
    on EOF. Raises FleetWireError on a malformed frame."""
    header = _recv_exact(sock, 5)
    if header is None:
        return None
    length, kind = struct.unpack(">IB", header)
    name = FRAME_KINDS.get(kind)
    if name is None or length > MAX_FRAME_BYTES:
        raise FleetWireError(f"bad fleet frame (kind={kind}, len={length})")
    payload = _recv_exact(sock, length)
    if payload is None:
        return None
    try:
        return name, protowire.decode(name, payload)
    except Exception as e:  # noqa: BLE001 — wire fault domain
        raise FleetWireError(f"undecodable {name} frame: {e}") from e


def status_to_wire(s: EngineStatus) -> Dict[str, Any]:
    """EngineStatus -> FleetHeartbeat wire dict (the digest travels so
    the registry host can score remote prefix matches)."""
    host = s.host_tier or {}
    return {
        "engine_id": s.engine_id,
        "healthy": s.healthy,
        "active_requests": s.active_requests,
        "waiting_requests": s.waiting_requests,
        "total_processed": s.total_processed,
        "memory_used_pages": s.memory_used_pages,
        "memory_total_pages": s.memory_total_pages,
        "role": s.role or "unified",
        "pages_cached": s.pages_cached,
        "prefix_digest": sorted(int(h) for h in (s.prefix_digest or ())),
        "page_size": s.page_size,
        "digest_depth": s.digest_depth,
        "host_tier_bytes": host.get("bytes", 0),
        "host_tier_pages": host.get("pages", 0),
    }


def status_from_wire(d: Dict[str, Any], member_id: str) -> EngineStatus:
    """Wire dict -> EngineStatus namespaced under ``member_id`` (the
    proxy id the scheduler routes on: ``<member>:<engine>``)."""
    host = None
    if d.get("host_tier_bytes") or d.get("host_tier_pages"):
        host = {"bytes": d.get("host_tier_bytes", 0),
                "pages": d.get("host_tier_pages", 0), "hit_pages": 0}
    return EngineStatus(
        engine_id=f"{member_id}:{d.get('engine_id', '')}",
        healthy=bool(d.get("healthy")),
        active_requests=d.get("active_requests", 0),
        waiting_requests=d.get("waiting_requests", 0),
        total_processed=d.get("total_processed", 0),
        memory_used_pages=d.get("memory_used_pages", 0),
        memory_total_pages=d.get("memory_total_pages", 0),
        pages_cached=d.get("pages_cached", 0),
        role=d.get("role") or "unified",
        prefix_digest=frozenset(d.get("prefix_digest") or ()),
        page_size=d.get("page_size", 0),
        digest_depth=d.get("digest_depth", 0),
        host_tier=host,
        remote=True,
    )


def _attrs_to_json(attrs: Dict[str, Any]) -> str:
    if not attrs:
        return ""
    try:
        return json.dumps(attrs, default=str)
    except (TypeError, ValueError):
        return json.dumps({k: str(v) for k, v in attrs.items()})


def _attrs_from_json(blob: str) -> Dict[str, Any]:
    if not blob:
        return {}
    try:
        obj = json.loads(blob)
        return obj if isinstance(obj, dict) else {}
    except ValueError:
        return {}


def span_to_wire(s: Span, epoch_offset_ns: int) -> Dict[str, Any]:
    """Span -> TraceSpan wire dict. Timestamps go out as EPOCH ns
    (``epoch_offset_ns`` = time_ns() - monotonic_ns() of the SENDER), so
    the receiver can re-base into its own monotonic domain — the only
    residual error is wall-clock skew between hosts, same as OTLP."""
    start = s.start_ns + epoch_offset_ns
    return {
        "name": s.name,
        "trace_id": s.trace_id,
        "span_id": s.span_id,
        "parent_id": s.parent_id or "",
        "start_unix_ns": max(0, start),
        "duration_ns": max(0, (s.end_ns or s.start_ns) - s.start_ns),
        "status": s.status or "ok",
        "attrs_json": _attrs_to_json(s.attributes),
        "events": [
            {"offset_ns": max(0, t - s.start_ns), "name": n,
             "attrs_json": _attrs_to_json(a)}
            for t, n, a in s.events
        ],
    }


def span_from_wire(d: Dict[str, Any], epoch_offset_ns: int,
                   member_id: str = "") -> Span:
    """TraceSpan wire dict -> Span in the RECEIVER's monotonic domain.
    ``member_id`` is stamped as a ``member`` attribute so a stitched
    trace shows which process each span ran in."""
    start = max(0, d.get("start_unix_ns", 0) - epoch_offset_ns)
    duration = max(0, d.get("duration_ns", 0))
    attrs = _attrs_from_json(d.get("attrs_json", ""))
    if member_id:
        attrs.setdefault("member", member_id)
    return Span(
        name=d.get("name", ""),
        trace_id=d.get("trace_id", ""),
        span_id=d.get("span_id", ""),
        parent_id=d.get("parent_id") or None,
        start_ns=start,
        end_ns=start + duration,
        attributes=attrs,
        events=[
            (start + e.get("offset_ns", 0), e.get("name", ""),
             _attrs_from_json(e.get("attrs_json", "")))
            for e in d.get("events", [])
        ],
        status=d.get("status") or "ok",
    )


# ---------------------------------------------------------------------------
# Federated engine registry
# ---------------------------------------------------------------------------


@dataclass
class FleetMember:
    """One worker process as the registry sees it. Mutated only under
    the registry's lock; ``snapshot()`` hands out copies."""

    member_id: str
    state: str = MEMBER_ALIVE
    last_beat: float = field(default_factory=time.monotonic)
    beats: int = 0
    engines: List[EngineStatus] = field(default_factory=list)

    def snapshot(self, now: float) -> Dict[str, Any]:
        return {
            "member_id": self.member_id,
            "state": self.state,
            "last_beat_age_s": round(now - self.last_beat, 3),
            "beats": self.beats,
            "engines": {s.engine_id: s.role for s in self.engines},
        }


class FleetRegistry:
    """Membership truth: heartbeat ingest + the alive/suspect/dead state
    machine. Thread-safe — beats arrive on member-session reader
    threads, the sweeper ages members out, and routing snapshots read
    from the dispatcher thread. State-change callbacks run OUTSIDE the
    lock (they unregister runners / fail requests — lock-heavy work)."""

    def __init__(
        self,
        settings: Optional[FleetSettings] = None,
        metrics: Optional[MetricsCollector] = None,
        on_state_change: Optional[Callable[[str, str, str], None]] = None,
    ):
        self.settings = settings or FleetSettings()
        self.metrics = metrics
        self.on_state_change = on_state_change
        self._members: Dict[str, FleetMember] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- ingest (member-session reader threads) ----------------------------

    def observe(self, member_id: str,
                engines: List[EngineStatus]) -> Optional[str]:
        """Ingest one heartbeat. Returns the member's PREVIOUS state (so
        the caller can re-register runners on a rejoin), or None when the
        beat was dropped by the ``fleet.heartbeat`` fault point — the
        partition model: the wire delivered it, the registry never saw
        it."""
        try:
            faults.fire("fleet.heartbeat")
        except faults.InjectedFault:
            if self.metrics:
                self.metrics.record_fleet_heartbeat("dropped")
            return None
        transition = None
        created = False
        with self._lock:
            member = self._members.get(member_id)
            if member is None:
                member = self._members[member_id] = FleetMember(member_id)
                created = True
                # the session treats a first join like a rejoin (fresh
                # proxies, clean slate), but it is NOT a revival for
                # metrics/callbacks — nothing existed to revive
                prev = MEMBER_DEAD
            else:
                prev = member.state
            member.last_beat = time.monotonic()
            member.beats += 1
            member.engines = list(engines)
            member.state = MEMBER_ALIVE
            if not created and prev != MEMBER_ALIVE:
                transition = (member_id, prev, MEMBER_ALIVE)
        if self.metrics:
            self.metrics.record_fleet_heartbeat(
                "rejoin" if transition else "ok")
            self._publish_gauge()
        if transition and self.on_state_change:
            self.on_state_change(*transition)
        return prev

    def disconnect(self, member_id: str) -> None:
        """Connection death: faster truth than beat aging — the member
        is dead NOW (its in-flight requests must redispatch, not wait
        out the suspect window)."""
        self._transition(member_id, MEMBER_DEAD)

    # -- aging (sweeper thread) --------------------------------------------

    def sweep(self, now: Optional[float] = None) -> List[Tuple[str, str, str]]:
        """Age members on missed beats: alive -> suspect after
        ``suspect_after_s``, suspect -> dead after ``dead_after_s``.
        Returns the transitions applied."""
        now = time.monotonic() if now is None else now
        transitions: List[Tuple[str, str, str]] = []
        pruned = False
        with self._lock:
            for member in list(self._members.values()):
                age = now - member.last_beat
                if (member.state == MEMBER_ALIVE
                        and age > self.settings.suspect_after_s):
                    transitions.append(
                        (member.member_id, member.state, MEMBER_SUSPECT))
                    member.state = MEMBER_SUSPECT
                if (member.state == MEMBER_SUSPECT
                        and age > self.settings.dead_after_s):
                    transitions.append(
                        (member.member_id, member.state, MEMBER_DEAD))
                    member.state = MEMBER_DEAD
                if (member.state == MEMBER_DEAD
                        and age > (self.settings.dead_after_s
                                   + self.settings.dead_retention_s)):
                    # restarted workers mint fresh host:pid identities;
                    # without eviction the dead set grows forever
                    del self._members[member.member_id]
                    pruned = True
        if (transitions or pruned) and self.metrics:
            self._publish_gauge()
        if self.on_state_change:
            for t in transitions:
                self.on_state_change(*t)
        return transitions

    def _transition(self, member_id: str, new_state: str) -> None:
        with self._lock:
            member = self._members.get(member_id)
            if member is None or member.state == new_state:
                return
            prev = member.state
            member.state = new_state
        if self.metrics:
            self._publish_gauge()
        if self.on_state_change:
            self.on_state_change(member_id, prev, new_state)

    def _publish_gauge(self) -> None:
        with self._lock:
            counts = {state: 0 for state in MEMBER_STATES}
            for member in self._members.values():
                counts[member.state] += 1
        self.metrics.set_fleet_members(counts)

    # -- snapshots (any thread) --------------------------------------------

    def member_state(self, member_id: str) -> Optional[str]:
        with self._lock:
            member = self._members.get(member_id)
            return member.state if member else None

    def members(self) -> List[Dict[str, Any]]:
        now = time.monotonic()
        with self._lock:
            return [m.snapshot(now) for m in self._members.values()]

    def stats(self) -> Dict[str, Any]:
        """The ``fleet`` block of ``/server/stats``: members with state
        and last-beat age (the role map and rebalance history ride in
        from the server's balancer)."""
        members = self.members()
        counts = {state: 0 for state in MEMBER_STATES}
        for m in members:
            counts[m["state"]] += 1
        return {"members": members, "member_counts": counts}

    # -- sweeper lifecycle -------------------------------------------------

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        # lifecycle handle: start/stop are orchestrator calls
        # distlint: ignore[DL008]
        self._thread = threading.Thread(
            target=self._sweep_loop, name="fleet-registry-sweep", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(5.0)
            self._thread = None

    def _sweep_loop(self) -> None:
        # sweep at heartbeat cadence: aging resolution finer than the
        # suspect window costs nothing and keeps detection < 1 interval
        while not self._stop.wait(self.settings.heartbeat_interval_s):
            try:
                self.sweep()
            except Exception:  # noqa: BLE001 — sweeper must stay alive
                logger.exception("fleet registry sweep failed; retrying")


# ---------------------------------------------------------------------------
# Registry-host listener: member sessions feeding the registry
# ---------------------------------------------------------------------------


class _MemberSession:
    """One accepted member connection on the registry host. The reader
    thread owns the inbound half (heartbeats, events); sends are
    serialized by ``_send_lock`` (RemoteRunner submits arrive from the
    dispatcher and redispatch paths concurrently)."""

    def __init__(self, server: "FleetServer", sock: socket.socket,
                 peer: str):
        self.server = server
        self.sock = sock
        self.peer = peer
        self.member_id: Optional[str] = None
        # engine_id (member-local) -> RemoteRunner proxy; written on the
        # reader thread, read by close/detach paths — guarded by _lock
        self.runners: Dict[str, Any] = {}
        # fleet KV data plane (serving/fleet_kv.py): the member's
        # lazily-dialed data channel, created when a heartbeat
        # advertises a data_port; guarded by _lock
        self.kv_channel: Any = None
        self._lock = threading.Lock()
        self._send_lock = threading.Lock()
        self._closed = False

    def send(self, name: str, obj: Dict[str, Any]) -> None:
        with self._send_lock:
            if self._closed:
                raise FleetWireError("member session closed")
            send_frame(self.sock, name, obj)

    # host->member kinds never arrive here: this loop reads what MEMBERS
    # send (heartbeats, events, spans, telemetry); submits and KvIntro
    # travel the other direction, on FleetWorker's reader
    # distlint: wire-ignores[FleetSubmit, KvIntro]
    def run(self) -> None:
        """Reader loop (one thread per session)."""
        try:
            while True:
                frame = recv_frame(self.sock)
                if frame is None:
                    break
                name, obj = frame
                if name == "FleetHeartbeat":
                    self._on_heartbeat(obj)
                elif name == "FleetEvent":
                    self._on_event(obj)
                elif name == "FleetSpans":
                    # finished member spans: merge into the host tracer
                    # (even from a member the registry has aged out — a
                    # dying member's last spans are exactly the ones a
                    # postmortem needs)
                    self.server.ingest_spans(
                        obj, self.member_id or obj.get("member_id", ""))
                elif name == "FleetTelemetry":
                    # member perf digests + step-clock counters: stored
                    # per member, merged on demand at GET /server/perf
                    self.server.ingest_telemetry(
                        obj, self.member_id or obj.get("member_id", ""))
                elif name in ("RegistryLease", "RegistryState"):
                    # registry HA (serving/fleet_ha.py): a peer
                    # registry's lease beat / state echo arriving on
                    # our member listener — routed to the HA module;
                    # the session stays member-less (close is a no-op
                    # detach), so peer wires never fabricate members
                    self.server.on_registry_frame(name, obj)
                # FleetSubmit frames only flow host -> worker; one
                # arriving here is a confused peer — ignore it
        except (OSError, FleetWireError) as e:
            logger.debug("fleet session %s reader ended: %s", self.peer, e)
        finally:
            self.close("fleet member connection lost")

    def _on_heartbeat(self, obj: Dict[str, Any]) -> None:
        member_id = obj.get("member_id") or self.peer
        if self.member_id is None:
            self.member_id = member_id
            superseded = self.server._claim_member(member_id, self)
            if superseded is not None:
                # a reconnect replaced a half-dead session: fail the old
                # proxies' in-flight (their connection cannot deliver
                # events anymore) without killing the member
                superseded.detach_runners(
                    f"fleet member {member_id} reconnected on a new "
                    "session")
            logger.info("fleet member %s joined from %s", member_id,
                        self.peer)
        statuses = [status_from_wire(d, member_id)
                    for d in obj.get("engines", [])]
        prev = self.server.registry.observe(member_id, statuses)
        if prev is None:
            return  # beat dropped (fleet.heartbeat fault) — no refresh
        self.server._ensure_kv_channel(self, member_id,
                                       obj.get("data_port", 0))
        self.server._broker_intros(self, member_id,
                                   obj.get("data_port", 0))
        self.server._refresh_runners(self, member_id, obj.get("engines", []),
                                     statuses, rejoined=prev == MEMBER_DEAD)

    def _on_event(self, obj: Dict[str, Any]) -> None:
        with self._lock:
            runner = self.runners.get(obj.get("engine_id", ""))
        if runner is not None:
            runner.on_event(obj)

    def detach_runners(self, message: str) -> None:
        """Unregister this member's proxies from the scheduler and fail
        their in-flight requests onto the redispatch path. Two phases on
        purpose: EVERY proxy leaves the routing set (and is marked
        detached) before ANY request is failed — redispatching the first
        proxy's requests must not land them on a dead sibling proxy of
        the same member and burn the bounded redispatch budget there."""
        with self._lock:
            runners = list(self.runners.values())
            self.runners.clear()
            kv_channel, self.kv_channel = self.kv_channel, None
        if kv_channel is not None:
            # fails every in-flight KV stream (handoffs fall back to
            # decode-in-place, fetches to recompute) and the migrated
            # requests whose events rode it (engine_crashed)
            kv_channel.close(message)
        for runner in runners:
            # identity-checked: a reconnect's fresh proxy registered
            # under the same id must survive this session's late detach
            self.server.scheduler.unregister_if(runner.engine_id, runner)
            runner.mark_detached(message)
        for runner in runners:
            runner.fail_inflight(message)

    def close(self, reason: str) -> None:
        with self._send_lock:
            if self._closed:
                return
            self._closed = True
            try:
                self.sock.close()
            except OSError:
                pass
        member = self.member_id
        logger.info("fleet session %s (%s) closed: %s", self.peer,
                    member or "pre-join", reason)
        self.detach_runners(reason)
        if member is not None and self.server._is_current(member, self):
            # only the member's CURRENT session's death kills it — a
            # superseded session's late EOF is just cleanup
            self.server.registry.disconnect(member)
        self.server._drop_session(self)


class FleetServer:
    """The registry host's listener: accepts member connections, feeds
    heartbeats to the registry, and materializes one RemoteRunner proxy
    per remote engine in the scheduler so the whole dispatch spine
    routes the federated fleet with no special cases."""

    def __init__(
        self,
        registry: FleetRegistry,
        scheduler,
        settings: Optional[FleetSettings] = None,
        metrics: Optional[MetricsCollector] = None,
        redispatch: Optional[Callable] = None,
        tracer=None,
        recorder=None,
        health_settings=None,
        retry_budget=None,
    ):
        """``tracer``: the host Tracer — remote members' FleetSpans
        frames merge into it (one stitched cross-process trace per
        request, docs/OBSERVABILITY.md). ``recorder``: the host
        FlightRecorder — RemoteRunner proxies note token/terminal
        events into per-request timelines. ``health_settings``
        (serving/health.py HealthSettings) shapes each member data
        channel's circuit breaker; ``retry_budget`` (health.RetryBudget)
        budgets its reconnect attempts (docs/RESILIENCE.md "Gray
        failures and overload")."""
        from distributed_inference_server_tpu.serving.health import (
            HealthSettings,
        )

        self.registry = registry
        self.scheduler = scheduler
        self.settings = settings or FleetSettings()
        self.metrics = metrics
        self.redispatch = redispatch
        self.tracer = tracer
        self.recorder = recorder
        self.health_settings = health_settings or HealthSettings()
        self.retry_budget = retry_budget
        # monotonic <-> epoch re-basing for ingested remote spans
        self._epoch_offset_ns = time.time_ns() - time.monotonic_ns()
        self._sessions: List[_MemberSession] = []
        # member_id -> its CURRENT session: a reconnect replaces the
        # entry, so the superseded session's late EOF can neither kill
        # the member nor detach the new session's runners
        self._by_member: Dict[str, _MemberSession] = {}
        # member_id -> last ingested FleetTelemetry frame (digests +
        # counters, serving/teledigest.py), merged at GET /server/perf;
        # guarded by _lock, pruned by age at snapshot time
        self._telemetry: Dict[str, Dict[str, Any]] = {}
        # learned per-wire transfer rates (serving/fleet_mesh.py): the
        # host's own channels observe locally; member-to-member wires
        # arrive as cumulative kvwire counters on fleet telemetry.
        # Always on — cold wires price at the configured constant, so
        # nothing changes until bytes actually flow.
        from distributed_inference_server_tpu.serving.fleet_mesh import (
            MeshWireRates,
        )

        self.mesh_rates = MeshWireRates(
            window_s=self.settings.kv_rate_window_s,
            prior_rate=self.settings.kv_rate_prior,
            metrics=metrics,
        )
        # KV mesh broker state (guarded by _lock): member_id -> its
        # last-published (host, data_port) endpoint, and the cumulative
        # kvwire counter values last seen per (member, src, dst) so the
        # telemetry ingest can feed DELTAS into the rate window
        self._intro_endpoints: Dict[str, Tuple[str, int]] = {}
        self._kvwire_last: Dict[Tuple[str, str, str],
                                Tuple[float, float, float]] = {}
        self._lock = threading.Lock()
        self._sock: Optional[socket.socket] = None
        self._thread: Optional[threading.Thread] = None
        self._stopping = False
        self.bound_port: int = 0
        # registry HA (serving/fleet_ha.py): set by the server when
        # fleet.registries is configured. None = single-registry fleet
        # — every HA hook below degrades to the pre-HA behavior.
        self.ha = None
        registry.on_state_change = self._on_member_state

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        sock.bind((self.settings.host, self.settings.port))
        sock.listen(16)
        self._sock = sock
        self.bound_port = sock.getsockname()[1]
        self._stopping = False
        # lifecycle handle  # distlint: ignore[DL008]
        self._thread = threading.Thread(
            target=self._accept_loop, name="fleet-accept", daemon=True
        )
        self._thread.start()
        self.registry.start()
        logger.info("fleet registry listening on %s:%d", self.settings.host,
                    self.bound_port)

    def stop(self) -> None:
        self._stopping = True
        self.registry.stop()
        if self._sock is not None:
            try:
                # close() alone does not wake a thread blocked in
                # accept() on Linux — the join below then sat out its
                # full timeout on every stop; shutdown() does
                self._sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None
        with self._lock:
            sessions = list(self._sessions)
        for session in sessions:
            session.close("fleet server shutting down")
        if self._thread is not None:
            self._thread.join(5.0)
            self._thread = None

    def _accept_loop(self) -> None:
        while not self._stopping:
            try:
                conn, addr = self._sock.accept()
            except OSError:
                return  # listener closed
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            session = _MemberSession(self, conn, f"{addr[0]}:{addr[1]}")
            with self._lock:
                self._sessions.append(session)
            threading.Thread(
                target=session.run,
                name=f"fleet-session-{addr[0]}:{addr[1]}", daemon=True,
            ).start()

    def _claim_member(self, member_id: str,
                      session: _MemberSession) -> Optional[_MemberSession]:
        """Make ``session`` the member's current session; returns the
        session it superseded (a reconnect), if any."""
        with self._lock:
            prev = self._by_member.get(member_id)
            self._by_member[member_id] = session
            return prev if prev is not session else None

    def _is_current(self, member_id: str, session: _MemberSession) -> bool:
        with self._lock:
            return self._by_member.get(member_id) is session

    def _drop_session(self, session: _MemberSession) -> None:
        with self._lock:
            try:
                self._sessions.remove(session)
            except ValueError:
                pass
            if (session.member_id is not None
                    and self._by_member.get(session.member_id) is session):
                self._by_member.pop(session.member_id, None)

    # -- registry HA hooks (serving/fleet_ha.py) ---------------------------

    def control_epoch(self) -> int:
        """The epoch stamped on every control frame this registry sends
        (submits, aborts, KvIntros). 0 = no HA configured — members
        treat 0 as unfenced (legacy single-registry behavior)."""
        ha = self.ha
        return ha.epoch if ha is not None else 0

    def on_registry_frame(self, name: str, obj: Dict[str, Any]) -> None:
        """A peer registry's RegistryLease / RegistryState frame,
        arriving on a member session's reader thread."""
        ha = self.ha
        if ha is not None:
            ha.on_peer_frame(name, obj)

    def on_ha_promote(self) -> None:
        """Takeover re-arm: this registry just won the lease. The member
        table, proxies, and learned rates are already warm (the dual
        heartbeat kept them live) — what needs re-arming is the intro
        broker: re-publish every known endpoint at the NEW epoch so
        members fence out any stale intros from the old primary."""
        if not self.settings.mesh_enabled:
            return
        with self._lock:
            endpoints = dict(self._intro_endpoints)
            sessions = dict(self._by_member)
        grant = self.settings.kv_max_streams
        for member_id, session in sessions.items():
            for other_id, ep in endpoints.items():
                if other_id == member_id:
                    continue
                self._send_intro(session, {
                    "member_id": other_id, "host": ep[0],
                    "data_port": ep[1], "max_streams": grant,
                })

    # -- span ingest (session reader threads) ------------------------------

    def ingest_spans(self, obj: Dict[str, Any], member_id: str) -> None:
        """Merge one FleetSpans frame into the host tracer: each span is
        re-based into this host's monotonic domain and stamped with its
        member id, then exported through every sink (ring + OTLP) with
        its original trace/span/parent ids intact — the operator's
        ``/server/trace?trace_id=`` and the OTLP backend both see ONE
        correctly-parented cross-process tree. Spans the member shed
        before shipping count as wire drops."""
        if self.tracer is None:
            return
        member = member_id or obj.get("member_id", "")
        dropped = obj.get("dropped", 0)
        if dropped:
            self.tracer.record_drop("wire", int(dropped))
        for d in obj.get("spans", []):
            try:
                self.tracer.ingest(
                    span_from_wire(d, self._epoch_offset_ns, member))
            except Exception:  # noqa: BLE001 — one bad span must not
                # drop its whole batch
                logger.debug("undecodable remote span from %s", member,
                             exc_info=True)
                self.tracer.record_drop("wire")

    # -- telemetry ingest (session reader threads) --------------------------

    def ingest_telemetry(self, obj: Dict[str, Any], member_id: str) -> None:
        """Store one FleetTelemetry frame (replacing the member's
        previous one — digests are cumulative windows, not deltas, so
        last-frame-wins is exact) and publish the fleet_*{member}
        series: cumulative step-clock tokens per dispatch kind and the
        member's windowed TTFT p99 (docs/OBSERVABILITY.md)."""
        from distributed_inference_server_tpu.serving import teledigest

        member = member_id or obj.get("member_id", "")
        if not member:
            return
        digests = {d.get("name", ""): d for d in obj.get("digests", [])
                   if d.get("name")}
        foreign: List[str] = []
        if self.metrics is not None:
            # epoch geometry is part of the merge key space: a member
            # configured with a different slo.epoch_s ships epoch
            # indices in a different time unit — merging them would
            # silently corrupt the fleet windows, so drop them LOUDLY
            local_epoch_s = self.metrics.perf_epoch_s()
            foreign = [n for n, d in digests.items()
                       if float(d.get("epoch_s", 0.0)) != local_epoch_s]
            if foreign:
                logger.warning(
                    "fleet telemetry from %s dropped %d digest(s) with "
                    "foreign epoch_s (member slo.epoch_s disagrees with "
                    "this host's %.3gs): %s", member, len(foreign),
                    local_epoch_s, sorted(foreign),
                )
                for name in foreign:
                    del digests[name]
        counters = {c.get("name", ""): c.get("value", 0.0)
                    for c in obj.get("counters", []) if c.get("name")}
        with self._lock:
            self._telemetry[member] = {
                "digests": digests,
                "counters": counters,
                "at": time.monotonic(),
            }
            pruned = self._prune_telemetry_locked(time.monotonic())
        self._drop_member_series(pruned)
        self._ingest_wire_counters(member, counters)
        if self.metrics is not None:
            # exactly ONE outcome per frame: a frame that lost digests
            # to the epoch guard must not also read as cleanly ingested
            # (sum-over-outcomes == frames, and the mismatch stays loud)
            self.metrics.record_telemetry_frame(
                "epoch_mismatch" if foreign else "ingested")
            step_tokens: Dict[str, float] = {}
            for name, value in counters.items():
                parts = name.split(".")
                if (parts[0] == "step" and len(parts) == 4
                        and parts[3] == "tokens"):
                    step_tokens[parts[2]] = (
                        step_tokens.get(parts[2], 0.0) + value
                    )
            ttft_p99 = None
            ttft = digests.get("ttft_ms")
            if ttft is not None:
                stats = teledigest.window_stats(
                    ttft, self.metrics.perf_window_s())
                ttft_p99 = stats.get("p99")
            self.metrics.set_member_telemetry(member, step_tokens,
                                              ttft_p99)

    def _ingest_wire_counters(self, member: str,
                              counters: Dict[str, float]) -> None:
        """Feed the member's cumulative ``kvwire|src|dst|*`` counters
        (serving/fleet_mesh.py — its mesh channels' observed bulk
        bytes/seconds/chunks) into the host's learned-rate windows as
        DELTAS against the last frame. A counter running backwards
        means the member's telemetry restarted: the current value IS
        the delta then (same reasoning as any cumulative-counter
        scrape)."""
        wires: Dict[Tuple[str, str], Dict[str, float]] = {}
        from distributed_inference_server_tpu.serving.fleet_mesh import (
            WIRE_COUNTER_PREFIX,
        )

        for name, value in counters.items():
            if not name.startswith(WIRE_COUNTER_PREFIX):
                continue
            parts = name.split("|")
            if len(parts) != 4 or parts[3] not in ("bytes", "seconds",
                                                   "chunks"):
                continue
            wires.setdefault((parts[1], parts[2]), {})[parts[3]] = value
        if not wires:
            return
        for (src, dst), vals in wires.items():
            cur = (vals.get("bytes", 0.0), vals.get("seconds", 0.0),
                   vals.get("chunks", 0.0))
            key = (member, src, dst)
            with self._lock:
                last = self._kvwire_last.get(key, (0.0, 0.0, 0.0))
                self._kvwire_last[key] = cur
            if any(c < p for c, p in zip(cur, last)):
                last = (0.0, 0.0, 0.0)  # member telemetry restarted
            d_bytes, d_secs, d_chunks = (c - p
                                         for c, p in zip(cur, last))
            if d_bytes > 0 and d_secs > 0:
                self.mesh_rates.observe(src, dst, int(d_bytes), d_secs,
                                        chunks=int(d_chunks))

    def _prune_telemetry_locked(self, now: float) -> List[str]:
        """Drop members silent past the dead-retention window (a
        restarted worker mints a fresh id, same rationale as the
        registry's member table). Runs on every ingest — an unpolled
        registry host must not grow one digest frame per dead worker
        forever. Returns the pruned member ids (caller drops their
        gauge series outside the lock)."""
        horizon = self.settings.dead_after_s + self.settings.dead_retention_s
        stale = [m for m, v in self._telemetry.items()
                 if now - v["at"] > horizon]
        for member in stale:
            del self._telemetry[member]
        return stale

    def _drop_member_series(self, members: List[str]) -> None:
        """Remove pruned members' fleet_member_* gauge series: a dead
        member's last TTFT p99 must stop reading as live, and per-
        restart member ids must not grow /metrics without bound (same
        policy as the tenant-depth gauge)."""
        if self.metrics is None:
            if members:
                self._forget_wires(members)
            return
        for member in members:
            self.metrics.remove_member_telemetry(member)
        self._forget_wires(members)

    def _forget_wires(self, members: List[str]) -> None:
        """Drop pruned/dead members' learned-rate state: their wire
        series leave the gauge (bounded label sets) and their stored
        cumulative counters leave the delta table."""
        for member in members:
            self.mesh_rates.drop_member(member)
        with self._lock:
            for member in members:
                self._intro_endpoints.pop(member, None)
            gone = [k for k in self._kvwire_last
                    if k[0] in members or k[1] in members
                    or k[2] in members]
            for key in gone:
                del self._kvwire_last[key]

    def telemetry_snapshot(self) -> Dict[str, Dict[str, Any]]:
        """Per-member telemetry for GET /server/perf: last frame per
        member with its age (stale members pruned here too, so a quiet
        control plane still converges on read)."""
        now = time.monotonic()
        with self._lock:
            pruned = self._prune_telemetry_locked(now)
            out = {
                member: {
                    "digests": dict(v["digests"]),
                    "counters": dict(v["counters"]),
                    "age_s": now - v["at"],
                }
                for member, v in self._telemetry.items()
            }
        self._drop_member_series(pruned)
        return out

    # -- KV data plane (session reader threads) -----------------------------

    def _ensure_kv_channel(self, session: _MemberSession, member_id: str,
                           data_port: int) -> None:
        """Create (or retire) the member's KV data channel to match its
        advertised ``data_port``. The channel itself dials lazily — the
        first cross-host handoff/fetch pays the connect, not the
        heartbeat path."""
        from distributed_inference_server_tpu.serving.fleet_kv import (
            KvDataChannel,
        )

        host = session.peer.rsplit(":", 1)[0]
        with session._lock:
            if session._closed:
                return
            current = session.kv_channel
            if data_port <= 0:
                session.kv_channel = None
                stale = current
            elif (current is not None
                    and current.address == (host, data_port)):
                return
            else:
                stale = current
                session.kv_channel = KvDataChannel(
                    member_id, host, data_port,
                    max_streams=self.settings.kv_max_streams,
                    connect_timeout_s=self.settings.kv_connect_timeout_s,
                    metrics=self.metrics,
                    on_event=session._on_event,
                    on_lost_requests=lambda rids, reason,
                    s=session: self._fail_kv_requests(s, rids, reason),
                    # gray-failure defense (serving/health.py): the
                    # wire's circuit breaker + budgeted reconnects
                    breaker_threshold=self.health_settings.wire_failures,
                    breaker_open_s=self.health_settings.breaker_open_s,
                    retry_budget=self.retry_budget,
                    # learned wire-rate model (serving/fleet_mesh.py):
                    # the host's channels are the "registry" -> member
                    # wires in the (src, dst) rate key space
                    rate_estimator=self.mesh_rates.estimator(
                        "registry", member_id),
                )
            for runner in session.runners.values():
                runner.kv_channel = session.kv_channel
        if stale is not None:
            stale.close("member advertised a new kv data port")

    def _fail_kv_requests(self, session: _MemberSession,
                          request_ids: List[str], reason: str) -> None:
        """The data channel died with migrated requests mid-decode:
        fail exactly those, fast (they streamed tokens — engine_crashed,
        never silently re-run)."""
        with session._lock:
            runners = list(session.runners.values())
        for runner in runners:
            runner.fail_requests(request_ids, reason)

    def kv_stats(self) -> Dict[str, Any]:
        """Per-member data-channel state for the ``/server/stats``
        fleet block (connected / in-flight streams / bytes)."""
        with self._lock:
            sessions = [(s.member_id, s) for s in self._sessions
                        if s.member_id is not None]
        out: Dict[str, Any] = {}
        for member_id, session in sessions:
            with session._lock:
                channel = session.kv_channel
            if channel is not None:
                out[member_id] = channel.stats()
        return out

    # -- KV mesh introduction broker (session reader threads) ---------------

    def _broker_intros(self, session: _MemberSession, member_id: str,
                       data_port: int) -> None:
        """Keep every member introduced to every other member's
        advertised data-plane endpoint (serving/fleet_mesh.py). Called
        per heartbeat, but intros only cross the wire when an endpoint
        is NEW or CHANGED — plus a full catch-up of the existing fleet
        to a member whose endpoint just (re)appeared, covering both a
        fresh joiner and a reconnect after the registry bounced."""
        if not self.settings.mesh_enabled:
            return
        if self.ha is not None and not self.ha.is_primary():
            # standby: track endpoints (warm state) but never broker —
            # only the lease holder publishes intros; on takeover
            # on_ha_promote() re-publishes everything at the new epoch
            endpoint_ = None
            host_ = session.peer.rsplit(":", 1)[0]
            if data_port > 0:
                endpoint_ = (host_, int(data_port))
            with self._lock:
                if endpoint_ is None:
                    self._intro_endpoints.pop(member_id, None)
                else:
                    self._intro_endpoints[member_id] = endpoint_
            return
        host = session.peer.rsplit(":", 1)[0]
        endpoint = (host, int(data_port)) if data_port > 0 else None
        with self._lock:
            prev = self._intro_endpoints.get(member_id)
            if endpoint == prev:
                return
            if endpoint is None:
                self._intro_endpoints.pop(member_id, None)
            else:
                self._intro_endpoints[member_id] = endpoint
            others = [(m, s, self._intro_endpoints.get(m))
                      for m, s in self._by_member.items()
                      if m != member_id]
        if endpoint is None:
            # the member stopped advertising a data plane: retract it
            for other_id, other_session, _ep in others:
                self._send_intro(other_session,
                                 {"member_id": member_id, "gone": True})
            return
        grant = self.settings.kv_max_streams
        for other_id, other_session, other_ep in others:
            # both directions: the fleet learns the (new) endpoint...
            self._send_intro(other_session, {
                "member_id": member_id, "host": endpoint[0],
                "data_port": endpoint[1], "max_streams": grant,
            })
            # ...and the (re)joiner learns the existing fleet
            if other_ep is not None:
                self._send_intro(session, {
                    "member_id": other_id, "host": other_ep[0],
                    "data_port": other_ep[1], "max_streams": grant,
                })

    def _send_intro(self, session: _MemberSession,
                    obj: Dict[str, Any]) -> None:
        """One KvIntro send, outcome-counted: the broker is best-effort
        by design (a dropped intro only costs the mesh route — the
        fetch degrades to recompute, never to an error)."""
        ha = getattr(self, "ha", None)
        if ha is not None and ha.epoch:
            # registry HA fence: members ignore intros older than the
            # highest epoch they have seen (serving/fleet_ha.py)
            obj = dict(obj, epoch=ha.epoch)
        try:
            # injected broker drop (docs/RESILIENCE.md fleet.kv_intro)
            faults.fire("fleet.kv_intro")
            session.send("KvIntro", obj)
            outcome = "gone" if obj.get("gone") else "sent"
        except faults.InjectedFault:
            outcome = "dropped"
        except (FleetWireError, OSError) as e:
            logger.debug("kv intro to %s failed: %s", session.member_id, e)
            outcome = "failed"
        if self.metrics is not None:
            self.metrics.record_kv_intro(outcome)

    def mesh_route(self, target_member: str, peer_member: str) -> bool:
        """True when the mesh has (or will have, via the per-heartbeat
        broker) introduced ``target_member`` to ``peer_member`` — the
        gate for delegating a remote-target/remote-peer fetch to the
        member instead of relaying chunk bytes through this host."""
        if not self.settings.mesh_enabled or target_member == peer_member:
            return False
        with self._lock:
            return (target_member in self._intro_endpoints
                    and peer_member in self._intro_endpoints)

    def kv_wire_stats(self) -> List[Dict[str, Any]]:
        """The ``kv_wires`` table of ``/server/stats``: one row per
        directed wire with its learned rate and lifetime bytes/chunks
        (serving/fleet_mesh.py). Registry-owned wires carry live
        connectivity + breaker state from their channel; member-to-
        member wires carry whether the pair is currently introduced
        (their sockets live in the members — the rows' rates arrive via
        telemetry)."""
        rows: Dict[Tuple[str, str], Dict[str, Any]] = {
            (r["src"], r["dst"]): r for r in self.mesh_rates.snapshot()
        }
        for member_id, st in self.kv_stats().items():
            row = rows.setdefault(("registry", member_id), {
                "src": "registry", "dst": member_id,
                "rate_bytes_per_s": None, "bytes": 0, "chunks": 0,
            })
            row["connected"] = st.get("connected", False)
            row["breaker"] = st.get("breaker")
        with self._lock:
            introduced = set(self._intro_endpoints)
        for (src, dst), row in rows.items():
            if "connected" not in row:
                row["introduced"] = (src in introduced
                                     and dst in introduced)
        return [rows[k] for k in sorted(rows)]

    # -- runner materialization (session reader threads) -------------------

    def _refresh_runners(self, session: _MemberSession, member_id: str,
                         wire_engines: List[Dict[str, Any]],
                         statuses: List[EngineStatus],
                         rejoined: bool) -> None:
        from distributed_inference_server_tpu.serving.remote_runner import (
            RemoteRunner,
        )

        by_local_id = {d.get("engine_id", ""): s
                       for d, s in zip(wire_engines, statuses)}
        with session._lock:
            if session._closed:
                return
            stale = set(session.runners) - set(by_local_id)
            if rejoined:
                # dead->alive: the death path detached the old proxies;
                # fresh ones own a clean in-flight map
                stale |= set(session.runners)
            gone = [(eid, session.runners.pop(eid)) for eid in stale]
            for local_id, status in by_local_id.items():
                runner = session.runners.get(local_id)
                if runner is None:
                    runner = RemoteRunner(
                        engine_id=status.engine_id,
                        local_engine_id=local_id,
                        send=session.send,
                        metrics=self.metrics,
                        recorder=self.recorder,
                    )
                    runner.redispatch = self.redispatch
                    runner.kv_channel = session.kv_channel
                    # registry HA: stamp submits/aborts with this
                    # registry's control epoch (0 = unfenced)
                    runner.epoch_fn = self.control_epoch
                    session.runners[local_id] = runner
                    self.scheduler.register(runner)
                    logger.info("fleet: registered remote engine %s "
                                "(role=%s)", status.engine_id, status.role)
                elif self.scheduler.get(runner.engine_id) is not runner:
                    # a superseded session's late detach (or anything
                    # else) evicted our registration — heal it, or the
                    # engine silently takes no traffic while alive
                    self.scheduler.register(runner)
                runner.update_status(status)
        for _eid, runner in gone:
            self.scheduler.unregister_if(runner.engine_id, runner)
            runner.detach("remote engine left the member's heartbeat")

    # -- member state transitions (sweeper / reader threads) ---------------

    def _on_member_state(self, member_id: str, old: str, new: str) -> None:
        logger.warning("fleet member %s: %s -> %s", member_id, old, new)
        with self._lock:
            session = self._by_member.get(member_id)
        if session is None:
            return
        if new == MEMBER_DEAD:
            # remote death maps onto the crash-safe redispatch path:
            # zero-token in-flight requests move to healthy replicas
            # exactly once, mid-stream ones fail fast (RESILIENCE.md)
            session.detach_runners(
                f"fleet member {member_id} dead (missed heartbeats)")
            # KV mesh: retract the dead member's endpoint from the
            # fleet (each receiver closes its wire) and drop its
            # learned-rate series — dead host:pid identities must not
            # pin gauge labels (serving/fleet_mesh.py)
            if self.settings.mesh_enabled:
                with self._lock:
                    known = member_id in self._intro_endpoints
                    others = [s for m, s in self._by_member.items()
                              if m != member_id]
                if known:
                    for other in others:
                        self._send_intro(other, {"member_id": member_id,
                                                 "gone": True})
            self._forget_wires([member_id])
        elif new == MEMBER_SUSPECT:
            with session._lock:
                runners = list(session.runners.values())
            for runner in runners:
                runner.set_member_state(MEMBER_SUSPECT)
        elif new == MEMBER_ALIVE and old == MEMBER_SUSPECT:
            with session._lock:
                runners = list(session.runners.values())
            for runner in runners:
                runner.set_member_state(MEMBER_ALIVE)
        # dead -> alive rejoin is handled by the heartbeat path, which
        # materializes fresh proxies (rejoined=True)


# ---------------------------------------------------------------------------
# Dynamic role rebalancing
# ---------------------------------------------------------------------------


class RoleBalancer:
    """Flips ``unified`` engines to ``prefill`` when the fleet's prompt
    queue deepens, and back when it drains — with two-sided hysteresis
    (a signal band plus a flip cooldown) so an oscillating queue cannot
    flap roles. Only engines the balancer itself flipped are ever
    restored; operator-configured roles are never rewritten."""

    def __init__(self, scheduler, dispatcher,
                 settings: Optional[FleetSettings] = None,
                 metrics: Optional[MetricsCollector] = None,
                 recorder=None):
        """``recorder`` (serving/flightrec.py): role flips land in the
        flight recorder's fleet-event window, so a request's timeline
        shows a rerole that happened mid-flight."""
        self.scheduler = scheduler
        self.dispatcher = dispatcher
        self.settings = settings or FleetSettings()
        self.metrics = metrics
        self.recorder = recorder
        self._lock = threading.Lock()
        self._flipped: Dict[str, float] = {}  # engine_id -> flip time
        self._last_flip = 0.0
        self._last_signal = 0.0
        self._history: Deque[Dict[str, Any]] = deque(maxlen=64)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # registry HA (serving/fleet_ha.py): only the lease-holding
        # primary balances roles — the server wires this to
        # RegistryHA.is_primary. None = always active (no HA).
        self.active_fn: Optional[Callable[[], bool]] = None

    # -- the decision ------------------------------------------------------

    def signal(self) -> float:
        """Fleet prompt pressure: queued + engine-waiting prompts per
        healthy admission-capable (prefill/unified) replica."""
        statuses = self.scheduler.statuses()
        admission = [s for s in statuses if s.healthy
                     and s.role in ("prefill", "unified")]
        waiting = sum(s.waiting_requests for s in admission)
        depth = self.dispatcher.queue.total_depth()
        return (depth + waiting) / max(1, len(admission))

    def evaluate(self, now: Optional[float] = None) -> Optional[str]:
        """One rebalance decision; returns the flip direction applied
        ("to_prefill" / "to_unified") or None. At most one engine flips
        per evaluation, and never within ``rerole_cooldown_s`` of the
        previous flip — that cooldown IS the temporal hysteresis the
        ``rerole_flap`` chaos scenario pins."""
        if not self.settings.rerole:
            return None
        if self.active_fn is not None and not self.active_fn():
            # registry HA: a standby's balancer stays armed but quiet —
            # two balancers flipping the same fleet would fight
            return None
        now = time.monotonic() if now is None else now
        statuses = self.scheduler.statuses()
        # gates the to_prefill direction ONLY: restores must still run
        # with the decode fleet gone, or a balancer-flipped engine would
        # be stuck in the prefill role forever. LOCAL decode only:
        # remote replicas are not KV handoff targets (disagg.py), so
        # remote decode capacity cannot make a flip pay
        has_decode = any(
            s.healthy and s.role == "decode"
            and not getattr(s, "remote", False)
            for s in statuses
        )
        sig = self.signal()
        if faults.flag("sched.rerole"):
            # chaos lever: force the raw signal high for one evaluation
            # (drives the flip DESIRE deterministically; hysteresis and
            # cooldown still bound the actual flips)
            sig = max(sig, self.settings.rerole_high_ratio)
        direction = None
        with self._lock:
            self._last_signal = sig
            if now - self._last_flip < self.settings.rerole_cooldown_s:
                return None
            if sig >= self.settings.rerole_high_ratio and has_decode:
                runner = self._pick_unified()
                if runner is not None:
                    runner.set_role("prefill")
                    self._flipped[runner.engine_id] = now
                    self._last_flip = now
                    direction = "to_prefill"
                    self._record(runner.engine_id, direction, sig)
            elif sig <= self.settings.rerole_low_ratio and self._flipped:
                runner = self._pick_flipped_locked()
                if runner is not None:
                    runner.set_role("unified")
                    self._flipped.pop(runner.engine_id, None)
                    self._last_flip = now
                    direction = "to_unified"
                    self._record(runner.engine_id, direction, sig)
        if direction:
            logger.info("fleet rerole %s (signal %.2f)", direction, sig)
            if self.recorder is not None:
                self.recorder.note_global("rerole", direction=direction,
                                          signal=round(sig, 3))
            if self.metrics:
                self.metrics.record_rerole(direction)
                self.metrics.set_engines_by_role(self._role_counts())
        return direction

    def _pick_unified(self):
        candidates = [
            r for r in self.scheduler.engines()
            if r.role == "unified" and r.is_healthy()
            and not getattr(r, "is_remote", False)
        ]
        if not candidates:
            return None
        return min(candidates, key=lambda r: r.engine_id)

    def _pick_flipped_locked(self):
        for engine_id in sorted(self._flipped):
            runner = self.scheduler.get(engine_id)
            if runner is not None and runner.role == "prefill":
                return runner
            self._flipped.pop(engine_id, None)  # unregistered/re-roled
        return None

    def _record(self, engine_id: str, direction: str, sig: float) -> None:
        self._history.append({
            "engine_id": engine_id, "direction": direction,
            "signal": round(sig, 3), "t": round(time.time(), 3),
        })

    def _role_counts(self) -> Dict[str, int]:
        # LOCAL replicas only, matching the boot-time publisher
        # (server.py uses DisaggController.role_counts over the static
        # role list) — the gauge's meaning must not depend on which
        # publisher wrote last
        counts: Dict[str, int] = {}
        for r in self.scheduler.engines():
            if not getattr(r, "is_remote", False):
                counts[r.role] = counts.get(r.role, 0) + 1
        return counts

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "signal": round(self._last_signal, 3),
                "flipped": sorted(self._flipped),
                "history": list(self._history),
            }

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        # lifecycle handle  # distlint: ignore[DL008]
        self._thread = threading.Thread(
            target=self._loop, name="fleet-rerole", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(5.0)
            self._thread = None

    def _loop(self) -> None:
        while not self._stop.wait(self.settings.rerole_interval_s):
            try:
                self.evaluate()
            except Exception:  # noqa: BLE001 — balancer must stay alive
                logger.exception("role rebalance evaluation failed")


def parse_connect(connect: str) -> Tuple[str, int]:
    """Parse ``fleet.connect`` ("host:port") for worker mode."""
    host, sep, port = connect.rpartition(":")
    if not sep or not host or not port.isdigit():
        raise ConfigError(
            f"fleet.connect must be host:port, got {connect!r}"
        )
    return host, int(port)
