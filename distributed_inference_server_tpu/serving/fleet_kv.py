"""Fleet KV data plane: cross-host handoff and peer prefix fetch over
per-member data channels (docs/FLEET.md "KV data plane").

The fleet control plane (serving/fleet.py) federated routing, but both
KV byte paths — the disagg prefill→decode handoff and the peer prefix
fetch — stayed in-process: remote members were excluded from handoff
targets and fetch sources because the import session and the chunk
channel needed a local engine object on both ends. This module is the
missing data plane:

- **KvDataChannel** (registry-host side, one per member): a SECOND
  protowire TCP connection, dialed lazily at the member's heartbeat-
  advertised ``data_port`` and kept apart from the heartbeat wire on
  purpose — a multi-megabyte chunk stream must never head-of-line-block
  control frames (heartbeats aging members, submit/event traffic). It
  carries ``KvHandoffHeader``/``KvChunk``/``KvHandoff``/``KvPrefixFetch``
  streams host→member and chunk/``KvStreamResult``/``FleetEvent`` frames
  back, with a bounded in-flight stream window
  (``fleet.kv_max_streams`` — the (N+1)th concurrent stream fails fast
  to its local fallback instead of queueing unboundedly behind bulk
  transfers), per-stream exactly-once resolution, and lazy
  reconnect-with-backoff after a connection death.
- **KvDataServer** (member side): a listener the ``FleetWorker`` binds
  at startup and advertises in every heartbeat. Each accepted
  connection gets a reader thread (stream reassembly → local runner
  calls) and a writer thread (bounded queue → socket), so an engine
  thread's export callback only ever ENQUEUES frames — serializing a
  chunk chain must not stall the decode loop of exactly the replica
  that was picked as a fetch source because it is warm (and therefore
  busy). Migrated sequences decode on the member with a sink that
  encodes ``FleetEvent`` frames back over the data channel; the host's
  RemoteRunner proxy pumps them into the request's real sink — the
  same exactly-once event path remote submits already use.

Failure semantics (docs/RESILIENCE.md): every stream resolves exactly
once. A dial failure (``fleet.kv_connect``), a frame death mid-stream
(``fleet.kv_chunk``, one hit per chunk), a torn connection, or a crc/
validation reject on the member all resolve the stream as failed on the
host — which degrades a handoff to decode-in-place and a fetch to
recompute, exactly as the in-process paths do. A member-side crash
resolves the pending runner callbacks through the runner's ``_fail_all``
(the same ``_pending_opens``/``_pending_fetches`` pop-first protocol),
so the failure ships back as a ``KvStreamResult`` instead of wedging the
host. A data-channel death AFTER a commit fails the migrated requests
fast (``engine_crashed`` — they already streamed tokens and can never be
silently re-run) and aborts the member-side orphans.
"""

from __future__ import annotations

import logging
import queue
import socket
import struct
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from distributed_inference_server_tpu.engine.engine import SequenceExport
from distributed_inference_server_tpu.engine.kv_cache import KvChunk
from distributed_inference_server_tpu.serving import faults, protowire
from distributed_inference_server_tpu.serving.metrics import MetricsCollector

logger = logging.getLogger(__name__)

#: data-channel frame kinds — a table of its own so the bulk wire can
#: never be confused with (or parsed as) the heartbeat wire
KV_FRAME_KINDS: Dict[int, str] = {
    1: "KvHandoffHeader",
    2: "KvChunk",
    3: "KvHandoff",
    4: "KvPrefixFetch",
    5: "KvStreamResult",
    # decode tokens of a cross-host-migrated request, member -> host
    6: "FleetEvent",
}
_KV_KIND_BY_NAME = {name: kind for kind, name in KV_FRAME_KINDS.items()}

#: a KvChunk payload is chunk_pages full KV pages — tens of MB at large
#: geometries; anything bigger than this is a torn/foreign stream
MAX_KV_FRAME_BYTES = 256 * 1024 * 1024


class KvWireError(RuntimeError):
    """A malformed frame on a KV data channel; the connection dies and
    every in-flight stream resolves as failed."""


def send_kv_frame(sock: socket.socket, name: str,
                  obj: Dict[str, Any]) -> int:
    """Encode and write one data-channel frame; returns bytes written.
    Callers serialize sends per socket (one writer thread per side)."""
    payload = protowire.encode(name, obj)
    frame = struct.pack(">IB", len(payload), _KV_KIND_BY_NAME[name]) + payload
    sock.sendall(frame)
    return len(frame)


def _recv_exact(sock: socket.socket, n: int) -> Optional[bytes]:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(min(n - len(buf), 1 << 20))
        if not chunk:
            return None  # orderly EOF
        buf += chunk
    return bytes(buf)


def recv_kv_frame(sock: socket.socket
                  ) -> Optional[Tuple[str, Dict[str, Any]]]:
    """Read one frame; None on EOF, KvWireError on a malformed frame."""
    header = _recv_exact(sock, 5)
    if header is None:
        return None
    length, kind = struct.unpack(">IB", header)
    name = KV_FRAME_KINDS.get(kind)
    if name is None or length > MAX_KV_FRAME_BYTES:
        raise KvWireError(f"bad kv data frame (kind={kind}, len={length})")
    payload = _recv_exact(sock, length)
    if payload is None:
        return None
    try:
        return name, protowire.decode(name, payload)
    except Exception as e:  # noqa: BLE001 — wire fault domain
        raise KvWireError(f"undecodable {name} frame: {e}") from e


def chunk_to_wire(handoff_id: str, c: KvChunk) -> Dict[str, Any]:
    return {
        "handoff_id": handoff_id,
        "index": c.index,
        "total": c.total,
        "page_start": c.page_start,
        "page_count": c.page_count,
        "crc32": c.crc32,
        "payload": c.payload,
    }


def chunk_from_wire(d: Dict[str, Any]) -> KvChunk:
    return KvChunk(
        index=d["index"], total=d["total"], page_start=d["page_start"],
        page_count=d["page_count"], payload=d["payload"], crc32=d["crc32"],
    )


def _export_state_to_wire(exp: SequenceExport) -> Dict[str, Any]:
    """SequenceExport host state -> KvHandoff wire dict (the chunks
    travel as their own frames; ``kv`` carries the monolithic payload
    only when there are no chunks)."""
    obj: Dict[str, Any] = {
        "request_id": str(exp.request_id),
        "token_ids": [int(t) for t in exp.token_ids],
        "prompt_len": exp.prompt_len,
        "seq_len": exp.seq_len,
        "next_token": int(exp.next_token),
        "emitted_tokens": exp.emitted_tokens,
        "output_text": exp.output_text,
        "emitted_upto": exp.emitted_upto,
        "pending_ids": [int(t) for t in exp.pending_ids],
        "max_tokens": exp.params.max_tokens,
        "temperature": exp.params.temperature,
        "top_p": exp.params.top_p,
        "stop_sequences": list(exp.params.stop_sequences),
        "kv": exp.kv if exp.kv_chunks is None else b"",
        "source_engine": exp.source_engine,
    }
    if exp.draft_kv is not None:
        obj["draft_kv"] = exp.draft_kv
    return obj


def _export_state_from_wire(d: Dict[str, Any]) -> SequenceExport:
    from distributed_inference_server_tpu.engine.engine import SamplingParams

    return SequenceExport(
        request_id=d["request_id"],
        token_ids=list(d["token_ids"]),
        prompt_len=d["prompt_len"],
        seq_len=d["seq_len"],
        next_token=d["next_token"],
        params=SamplingParams(
            max_tokens=d["max_tokens"],
            temperature=d["temperature"],
            top_p=d["top_p"],
            stop_sequences=tuple(d["stop_sequences"]),
        ),
        output_text=d["output_text"],
        emitted_upto=d["emitted_upto"],
        emitted_tokens=d["emitted_tokens"],
        pending_ids=list(d["pending_ids"]),
        kv=d["kv"],
        draft_kv=d.get("draft_kv"),
        source_engine=d["source_engine"],
    )


# ---------------------------------------------------------------------------
# Host side: one lazily-dialed data channel per member
# ---------------------------------------------------------------------------


class _KvStream:
    """One in-flight host-side stream: registered before the first frame
    goes out, resolved exactly once — by its KvStreamResult, by a send/
    connect failure, or by the connection dying under it."""

    __slots__ = ("key", "op", "rid", "cb", "chunks", "started_at",
                 "result_depth", "wire_bytes", "wire_chunks")

    def __init__(self, op: str, rid: str, cb: Callable):
        self.key = f"{op}:{rid}"
        self.op = op
        self.rid = rid
        self.cb = cb
        self.chunks: List[KvChunk] = []  # fetch-response reassembly
        self.started_at = time.monotonic()
        self.result_depth = 0  # fetch: depth the member actually served
        # bulk payload accounting for the learned wire-rate model
        # (serving/fleet_mesh.py): bytes/chunks this stream moved in
        # EITHER direction — sent (handoff/import) or received (fetch)
        self.wire_bytes = 0
        self.wire_chunks = 0


class KvDataChannel:
    """Registry-host end of one member's KV data channel.

    Thread-shape: public ops arrive from the disagg worker, the
    dispatcher (fetch routing), and runner callbacks; they register the
    stream and enqueue a send job. ONE wire worker thread owns the
    socket's send half (dial-on-first-use included — a lazy connect may
    block up to ``kv_connect_timeout_s`` and must never run on a
    dispatch path); one reader thread per live connection owns the
    receive half. Stream resolution is exactly-once by pop-first on
    ``_streams`` under ``_lock``."""

    def __init__(
        self,
        member_id: str,
        host: str,
        port: int,
        max_streams: int = 4,
        connect_timeout_s: float = 5.0,
        metrics: Optional[MetricsCollector] = None,
        on_event: Optional[Callable[[Dict[str, Any]], None]] = None,
        on_lost_requests: Optional[Callable[[List[str], str], None]] = None,
        breaker_threshold: int = 3,
        breaker_open_s: float = 5.0,
        retry_budget=None,
        rate_estimator=None,
        peer_wire: bool = False,
    ):
        """``on_event(obj)`` receives FleetEvent frames (decode tokens
        of migrated requests) on the reader thread. ``on_lost_requests``
        fires when the connection dies with migrated requests still
        streaming — the caller fails them fast (engine_crashed).
        ``breaker_threshold``/``breaker_open_s`` (serving/health.py
        CircuitBreaker; config ``health.wire_failures`` /
        ``health.breaker_open_s``): consecutive wire failures open the
        breaker — new streams fail fast and handoff/fetch election
        skips this member (``wire_available``) until a half-open probe
        succeeds. ``retry_budget`` (health.RetryBudget): reconnects
        after a failure draw from the shared budget, so a fleet of
        broken wires cannot amplify dial load. ``rate_estimator``
        (serving/fleet_mesh.py WireRateEstimator): each completed
        stream's bulk bytes/seconds feed the learned per-wire transfer
        rate the routing cost model prices fetches with; None = no
        observation (the wire stays priced at the configured prior).
        ``peer_wire`` marks a member-to-member mesh channel (dialed
        from a KvIntro, not by the registry host): the dial-death
        fault point is then ``fleet.kv_peer_dial`` instead of
        ``fleet.kv_connect`` (docs/RESILIENCE.md)."""
        from distributed_inference_server_tpu.serving.health import (
            CircuitBreaker,
        )

        self.member_id = member_id
        self.address = (host, port)
        self.max_streams = max(1, max_streams)
        self.connect_timeout_s = connect_timeout_s
        self.metrics = metrics
        self.on_event = on_event
        self.on_lost_requests = on_lost_requests
        self.retry_budget = retry_budget
        self.rate_estimator = rate_estimator
        self.peer_wire = peer_wire
        self.breaker = CircuitBreaker(
            threshold=breaker_threshold, open_s=breaker_open_s,
            on_transition=(metrics.record_breaker_transition
                           if metrics is not None else None),
        )
        # a failed dial/send happened since the last good connect: the
        # NEXT dial is a retry and must draw from the shared budget.
        # GIL-atomic bool, wire-worker-owned  # distlint: ignore[DL008]
        self._reconnecting = False
        self._lock = threading.Lock()
        # distlint: registry
        self._streams: Dict[str, _KvStream] = {}
        # request ids of migrated sequences whose decode events ride
        # THIS connection; failed fast if the channel dies under them
        self._event_rids: set = set()
        self._sock: Optional[socket.socket] = None
        self._jobs: "queue.Queue" = queue.Queue()
        self._worker: Optional[threading.Thread] = None
        self._closed = False
        # reconnect backoff after a connection death: the next dial
        # waits out _not_before instead of hammering a dead member
        self._not_before = 0.0
        self._backoff_s = 0.25
        self._bytes_sent = 0
        self._bytes_received = 0

    # -- public ops (any thread) --------------------------------------------

    def fetch_prefix(self, rid, engine_id: str, hashes: Sequence[int],
                     chunk_pages: int, wire_quant: str,
                     trace: Optional[tuple],
                     cb: Callable[[Optional[tuple], Optional[str]], None]
                     ) -> None:
        """Ask the member's ``engine_id`` for its cached prefix chain;
        ``cb((depth, chunks), None)`` or ``cb(None, err)`` exactly once
        (the submit_prefix_export callback contract)."""
        def _resolve(ok: bool, err: Optional[str], s: _KvStream) -> None:
            if not ok:
                cb(None, err or "fetch failed")
                return
            cb((s.result_depth, sorted(s.chunks, key=lambda c: c.index)),
               None)

        stream = _KvStream("fetch", str(rid), _resolve)
        msg = {
            "request_id": str(rid),
            "hashes": [int(h) for h in hashes],
            "chunk_pages": chunk_pages,
            "wire_quant": wire_quant,
            "engine_id": engine_id,
        }
        if trace:
            msg["trace_id"], msg["parent_span_id"] = trace
        self._start_stream(stream, [("KvPrefixFetch", msg)])

    def import_open(self, rid, engine_id: str, prefix_pages: int,
                    wire_quant: str, chunks: Sequence[KvChunk],
                    trace: Optional[tuple],
                    cb: Callable[[bool, Optional[str]], None]) -> None:
        """Phase 1 of a cross-host streamed handoff: ship the prefix
        chunks and open an import session on the member's engine."""
        stream = _KvStream(
            "open", str(rid), lambda ok, err, s: cb(ok, err))
        frames = [("KvHandoffHeader", self._header(
            rid, "open", engine_id, wire_quant, trace,
            prefix_pages=prefix_pages, total_chunks=len(chunks)))]
        frames += [("KvChunk", chunk_to_wire(str(rid), c)) for c in chunks]
        self._start_stream(stream, frames)

    def import_commit(self, exp: SequenceExport, engine_id: str,
                      trace: Optional[tuple],
                      cb: Callable[[bool, Optional[str]], None]) -> None:
        """Phase 2: the switchover tail (``exp.kv_chunks``) plus the
        host state. On ok the member's engine owns the sequence and its
        decode events start riding this channel."""
        self._sequence_stream("commit", exp, engine_id, trace, cb)

    def resume(self, exp: SequenceExport, engine_id: str,
               trace: Optional[tuple],
               cb: Callable[[bool, Optional[str]], None]) -> None:
        """A monolithic cross-host migration: chunks (if the export was
        streamed) or the single ``kv`` payload, plus the host state."""
        self._sequence_stream("resume", exp, engine_id, trace, cb)

    def _sequence_stream(self, op: str, exp: SequenceExport,
                         engine_id: str, trace: Optional[tuple],
                         cb: Callable[[bool, Optional[str]], None]) -> None:
        """Commit and resume share one shape: header + chunks + the
        terminal KvHandoff state frame, and on ok the request's decode
        events start riding this channel (failure-tracked so a channel
        death fails the migrated request fast)."""
        rid = str(exp.request_id)
        chunks = list(exp.kv_chunks or [])

        def _resolve(ok: bool, err: Optional[str], s: _KvStream) -> None:
            if ok:
                with self._lock:
                    self._event_rids.add(rid)
            cb(ok, err)

        stream = _KvStream(op, rid, _resolve)
        frames = [("KvHandoffHeader", self._header(
            rid, op, engine_id, exp.wire_quant, trace,
            total_chunks=len(chunks)))]
        frames += [("KvChunk", chunk_to_wire(rid, c)) for c in chunks]
        frames.append(("KvHandoff", _export_state_to_wire(exp)))
        self._start_stream(stream, frames)

    def import_abort(self, rid, engine_id: str) -> None:
        """Drop an opened-but-uncommitted member import session (stream
        cancelled / client abort): fire-and-forget, no reply."""
        self._enqueue_frames(None, [("KvStreamResult", {
            "stream_id": str(rid), "op": "abort", "ok": True,
            "engine_id": engine_id,
        })])

    def release_request(self, rid) -> None:
        """The migrated request resolved (done/error/abort observed by
        the proxy): stop failure-tracking its events."""
        with self._lock:
            self._event_rids.discard(str(rid))

    def wire_available(self) -> bool:
        """Election gate (serving/health.py): False while the breaker is
        OPEN — handoff targets and fetch sources skip this member
        instead of discovering the broken wire one failed stream at a
        time (RemoteRunner.supports_kv_import / EngineStatus.data_plane)."""
        return self.breaker.available()

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            out = {
                "connected": self._sock is not None,
                "streams": len(self._streams),
                "event_requests": len(self._event_rids),
                "bytes_sent": self._bytes_sent,
                "bytes_received": self._bytes_received,
            }
        out["breaker"] = self.breaker.stats()
        if self.rate_estimator is not None:
            out["rate_bytes_per_s"] = self.rate_estimator.rate()
        return out

    def close(self, reason: str = "channel closed") -> None:
        with self._lock:
            self._closed = True
        self._drop_connection(reason, count_failure=False)
        self._jobs.put(None)  # wake the worker so it can exit

    # -- internals ----------------------------------------------------------

    @staticmethod
    def _header(rid, op: str, engine_id: str, wire_quant: str,
                trace: Optional[tuple], prefix_pages: int = 0,
                total_chunks: int = 0) -> Dict[str, Any]:
        h = {
            "handoff_id": str(rid), "request_id": str(rid),
            "wire_quant": wire_quant or "none", "op": op,
            "engine_id": engine_id, "prefix_pages": prefix_pages,
            "total_chunks": total_chunks,
        }
        if trace:
            h["trace_id"], h["parent_span_id"] = trace
        return h

    def _start_stream(self, stream: _KvStream,
                      frames: List[Tuple[str, Dict[str, Any]]]) -> None:
        if not self.breaker.try_acquire():
            # circuit OPEN (or a half-open probe already in flight):
            # fail fast to the caller's local fallback — the member's
            # wire is judged broken, and hammering it would only delay
            # the fallback the request is going to take anyway
            stream.cb(False, "kv data channel circuit open "
                      f"(member {self.member_id} wire unhealthy)", stream)
            return
        with self._lock:
            if self._closed:
                reject = "kv data channel closed"
            elif len(self._streams) >= self.max_streams:
                # the in-flight window: fail fast to the caller's local
                # fallback instead of queueing bulk transfers behind
                # each other unboundedly
                reject = (f"kv data channel window full "
                          f"({self.max_streams} streams in flight)")
            else:
                reject = None
                self._streams[stream.key] = stream
        if reject is not None:
            # the attempt never ran: hand back a consumed half-open
            # probe, or it would wedge the breaker half-open forever
            self.breaker.release()
            stream.cb(False, reject, stream)
            return
        self._enqueue_frames(stream, frames)

    def _enqueue_frames(self, stream: Optional[_KvStream],
                        frames: List[Tuple[str, Dict[str, Any]]]) -> None:
        with self._lock:
            if self._closed:
                return  # fire-and-forget sends after close just drop
            if self._worker is None:
                # lazy wire worker: nothing is spawned (and nothing is
                # dialed) until the first KV byte actually needs to move
                self._worker = threading.Thread(
                    target=self._run_worker,
                    name=f"kv-wire-{self.member_id}", daemon=True,
                )
                self._worker.start()
        self._jobs.put((stream, frames))

    def _run_worker(self) -> None:
        while True:
            job = self._jobs.get()
            if job is None:
                return
            stream, frames = job
            if stream is not None:
                with self._lock:
                    live = self._streams.get(stream.key) is stream
                if not live:
                    # the stream was already failed (a connection drop
                    # while this job sat queued): transmitting its
                    # frames anyway would make the member do work the
                    # host has abandoned — reserve pages no commit will
                    # ever claim, or decode a ghost duplicate of a
                    # sequence already decoding in place
                    continue
            try:
                sock = self._ensure_connected()
                # the data wire wedges/times out mid-send
                # (docs/RESILIENCE.md fleet.wire_timeout): repeated
                # hits walk the circuit breaker closed -> open
                faults.fire("fleet.wire_timeout")
                for name, obj in frames:
                    if name == "KvChunk":
                        # per-chunk wire death (docs/RESILIENCE.md):
                        # nth=N tears the stream at its Nth chunk
                        faults.fire("fleet.kv_chunk")
                    n = send_kv_frame(sock, name, obj)
                    with self._lock:
                        self._bytes_sent += n
                        if stream is not None:
                            stream.wire_bytes += n
                            if name == "KvChunk":
                                stream.wire_chunks += 1
            except Exception as e:  # noqa: BLE001 — transport fault
                # domain: the stream fails, the connection is torn down
                # (its reader resolves every OTHER in-flight stream)
                logger.debug("kv channel %s: send failed: %s",
                             self.member_id, e)
                if self.metrics:
                    self.metrics.record_error("fleet_kv.send")
                self.breaker.record_failure()
                self._reconnecting = True
                self._resolve_stream(stream, False, str(e))
                # count_failure=False: THIS incident is already recorded
                # above — letting the drop count it again would halve
                # the effective health.wire_failures threshold whenever
                # other streams/event requests are live
                self._drop_connection(f"send failed: {e}",
                                      count_failure=False)

    def _ensure_connected(self) -> socket.socket:
        with self._lock:
            sock = self._sock
        if sock is not None:
            return sock
        now = time.monotonic()
        if now < self._not_before:
            raise OSError(
                f"kv data channel to {self.member_id} backing off "
                f"({self._not_before - now:.2f}s left)"
            )
        if (self._reconnecting and self.retry_budget is not None
                and not self.retry_budget.acquire("kv_reconnect")):
            # a RE-dial after a failure is a retry: the shared budget
            # (serving/health.py) is dry, so degrade this stream to its
            # local fallback instead of amplifying dial load
            raise OSError(
                f"kv data channel to {self.member_id}: retry budget "
                "exhausted"
            )
        # injected dial failure (docs/RESILIENCE.md): member-to-member
        # mesh wires and registry-to-member wires are distinct chaos
        # fault domains, so each gets its own LITERAL point
        if self.peer_wire:
            faults.fire("fleet.kv_peer_dial")
        else:
            faults.fire("fleet.kv_connect")
        try:
            # the channel's dedicated wire worker thread: blocking by
            # design with a bounded timeout; never a dispatch/async path
            sock = socket.create_connection(  # distlint: ignore[DL001]
                self.address, timeout=self.connect_timeout_s)
        except OSError:
            self._not_before = now + self._backoff_s
            self._backoff_s = min(self._backoff_s * 2.0, 5.0)
            self._reconnecting = True
            raise
        try:
            sock.settimeout(None)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except OSError:
            # a socket that dialed but cannot be configured is as dead
            # as a failed dial: close it (else the fd leaks) and take
            # the same backoff the dial failure would have
            sock.close()
            self._not_before = now + self._backoff_s
            self._backoff_s = min(self._backoff_s * 2.0, 5.0)
            self._reconnecting = True
            raise
        self._backoff_s = 0.25
        self._reconnecting = False
        with self._lock:
            if self._closed:
                sock.close()
                raise OSError("kv data channel closed")
            self._sock = sock
        threading.Thread(
            target=self._read_loop, args=(sock,),
            name=f"kv-read-{self.member_id}", daemon=True,
        ).start()
        logger.info("kv data channel to %s dialed %s:%d", self.member_id,
                    *self.address)
        return sock

    # the host half of the data channel only ever *initiates* streams:
    # handoff headers/states and prefix-fetch requests flow host->member
    # and come back as chunks + results, never inbound here
    # distlint: wire-ignores[KvHandoffHeader, KvHandoff, KvPrefixFetch]
    def _read_loop(self, sock: socket.socket) -> None:
        try:
            while True:
                frame = recv_kv_frame(sock)
                if frame is None:
                    break
                name, obj = frame
                if name == "KvChunk":
                    with self._lock:
                        payload_n = len(obj.get("payload", b""))
                        self._bytes_received += payload_n
                        stream = self._streams.get(
                            f"fetch:{obj.get('handoff_id', '')}")
                        if stream is not None:
                            stream.wire_bytes += payload_n
                            stream.wire_chunks += 1
                    if stream is not None:
                        stream.chunks.append(chunk_from_wire(obj))
                elif name == "KvStreamResult":
                    self._on_result(obj)
                elif name == "FleetEvent":
                    rid = obj.get("request_id", "")
                    if obj.get("kind") in ("done", "error"):
                        self.release_request(rid)
                    if self.on_event is not None:
                        self.on_event(obj)
                # headers of fetch responses carry no state the result
                # frame doesn't; chunks key on handoff_id directly
        except (OSError, KvWireError) as e:
            logger.debug("kv channel %s reader ended: %s", self.member_id, e)
        finally:
            self._drop_connection("kv data connection lost")

    def _on_result(self, obj: Dict[str, Any]) -> None:
        key = f"{obj.get('op', '')}:{obj.get('stream_id', '')}"
        with self._lock:
            stream = self._streams.pop(key, None)
        if stream is None:
            return  # already resolved (send failure / channel death)
        # a result frame — ok or not — proves the WIRE round-tripped:
        # member-side rejects (validation, engine unavailable) are not
        # wire failures and must not open the breaker
        self.breaker.record_success()
        if (self.rate_estimator is not None and bool(obj.get("ok"))
                and stream.wire_bytes > 0):
            # feed the learned wire-rate model (serving/fleet_mesh.py):
            # only OK streams with bulk payload count — a reject moved
            # control frames, not pages, and would poison the rate
            self.rate_estimator.observe(
                stream.wire_bytes,
                max(time.monotonic() - stream.started_at, 1e-6),
                chunks=stream.wire_chunks,
            )
        stream.result_depth = obj.get("depth", 0)
        try:
            stream.cb(bool(obj.get("ok")),
                      obj.get("error") or None, stream)
        except Exception as e:  # noqa: BLE001 — callback isolation
            self._absorbed("stream_callback", e)

    def _resolve_stream(self, stream: Optional[_KvStream], ok: bool,
                        err: Optional[str]) -> None:
        if stream is None:
            return
        with self._lock:
            if self._streams.pop(stream.key, None) is None:
                return  # the reader's result beat us to it
        try:
            stream.cb(ok, err, stream)
        except Exception as e:  # noqa: BLE001 — callback isolation
            self._absorbed("stream_callback", e)

    def _drop_connection(self, reason: str,
                         count_failure: bool = True) -> None:
        with self._lock:
            sock, self._sock = self._sock, None
            streams = list(self._streams.values())
            self._streams.clear()
            lost = sorted(self._event_rids)
            self._event_rids.clear()
        if count_failure and (streams or lost):
            # the connection died UNDER work: wire-failure evidence for
            # the breaker (an idle orderly EOF is not)
            self.breaker.record_failure()
            self._reconnecting = True
        if sock is not None:
            try:
                # shutdown BEFORE close: a close() under a reader thread
                # blocked in recv defers the FIN until that syscall
                # returns (the in-flight recv pins the kernel socket) —
                # the peer would never notice the death
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass
        for stream in streams:
            try:
                stream.cb(False, reason, stream)
            except Exception as e:  # noqa: BLE001 — callback isolation
                self._absorbed("stream_callback", e)
        if lost and self.on_lost_requests is not None:
            # migrated requests whose decode events rode this
            # connection: they already streamed tokens, so they fail
            # fast (engine_crashed) — never silently re-run
            try:
                self.on_lost_requests(lost, reason)
            except Exception as e:  # noqa: BLE001 — callback isolation
                self._absorbed("lost_requests", e)

    def _absorbed(self, site: str, exc: BaseException) -> None:
        logger.debug("kv channel %s: absorbed error at %s: %s",
                     self.member_id, site, exc)
        if self.metrics:
            self.metrics.record_error(f"fleet_kv.{site}")


# ---------------------------------------------------------------------------
# Member side: the data listener FleetWorker advertises
# ---------------------------------------------------------------------------


class _DataEventSink:
    """ResultSink of a cross-host-migrated sequence on the MEMBER: every
    token/terminal encodes a FleetEvent frame onto the data connection's
    writer queue. Runs on the member's engine-runner threads; enqueue
    only — the writer thread owns serialization and the socket."""

    def __init__(self, conn: "_KvPeerConn", request_id: str,
                 engine_id: str):
        self._conn = conn
        self._rid = request_id
        self._eid = engine_id

    def _event(self, obj: Dict[str, Any]) -> None:
        obj["request_id"] = self._rid
        obj["engine_id"] = self._eid
        self._conn.enqueue("FleetEvent", obj)

    def on_token(self, token_id, text, token_index, logprob=None) -> None:
        ev = {"kind": "token", "text": text or "",
              "token_index": token_index or 0}
        if token_id is not None:
            ev["token_id"] = int(token_id)
        if logprob is not None:
            ev["logprob"] = float(logprob)
        self._event(ev)

    def on_done(self, finish_reason, usage) -> None:
        self._conn.release(self._rid)
        self._event({
            "kind": "done",
            "finish_reason": getattr(finish_reason, "value",
                                     str(finish_reason)),
            "prompt_tokens": getattr(usage, "prompt_tokens", 0),
            "completion_tokens": getattr(usage, "completion_tokens", 0),
        })

    def on_error(self, message, code) -> None:
        self._conn.release(self._rid)
        self._event({"kind": "error", "message": message or "",
                     "code": code or "inference_failed"})


class _Assembly:
    """Reassembly state of one inbound stream on a member connection
    (owned by the connection's reader thread)."""

    __slots__ = ("header", "chunks")

    def __init__(self, header: Dict[str, Any]):
        self.header = header
        self.chunks: List[KvChunk] = []


class _KvPeerConn:
    """One accepted registry-host connection on the member's data
    listener: a reader thread (frames → stream reassembly → local runner
    calls) and a writer thread (bounded frame queue → socket). Runner
    callbacks only enqueue; a full queue blocks the enqueueing runner
    callback briefly (TCP backpressure shaped) rather than buffering
    unboundedly."""

    def __init__(self, server: "KvDataServer", sock: socket.socket,
                 peer: str):
        self.server = server
        self.sock = sock
        self.peer = peer
        # reader-owned: inbound stream reassembly keyed by handoff id
        # distlint: registry
        self._assemblies: Dict[str, _Assembly] = {}
        self._out: "queue.Queue" = queue.Queue(maxsize=256)
        self._lock = threading.Lock()
        # migrated requests decoding locally whose events ride this
        # connection; aborted if the host vanishes mid-decode
        # distlint: registry
        self._live: Dict[str, str] = {}  # rid -> engine_id
        self._closed = False
        self._writer = threading.Thread(
            target=self._write_loop, name=f"kv-peer-write-{peer}",
            daemon=True,
        )
        self._writer.start()

    # -- outbound (runner threads enqueue, writer thread sends) -------------

    def enqueue(self, name: str, obj: Dict[str, Any]) -> None:
        with self._lock:
            if self._closed:
                return
        try:
            self._out.put((name, obj), timeout=5.0)
        except queue.Full:
            # the host stopped draining: treat the connection as dead
            # rather than stalling runner callbacks forever
            self.close("kv data writer queue wedged")

    def release(self, rid: str) -> None:
        with self._lock:
            self._live.pop(str(rid), None)

    def _write_loop(self) -> None:
        while True:
            item = self._out.get()
            if item is None:
                return
            name, obj = item
            try:
                if name == "KvChunk":
                    # the member half of the per-chunk wire death: a
                    # fetch response can tear mid-stream too
                    faults.fire("fleet.kv_chunk")
                send_kv_frame(self.sock, name, obj)
            except Exception as e:  # noqa: BLE001 — transport fault domain
                logger.debug("kv peer %s: send failed: %s", self.peer, e)
                self.close(f"send failed: {e}")
                return

    # -- inbound (reader thread) --------------------------------------------

    # FleetEvent frames go member->host on this wire (_DataEventSink
    # enqueues them outbound); the peer conn never receives one
    # distlint: wire-ignores[FleetEvent]
    def run(self) -> None:
        try:
            while True:
                frame = recv_kv_frame(self.sock)
                if frame is None:
                    break
                name, obj = frame
                if name == "KvChunk":
                    asm = self._assemblies.get(obj.get("handoff_id", ""))
                    if asm is not None:
                        asm.chunks.append(chunk_from_wire(obj))
                        self._maybe_complete(obj.get("handoff_id", ""))
                elif name == "KvHandoffHeader":
                    hid = obj.get("handoff_id", "")
                    self._assemblies[hid] = _Assembly(obj)
                    self._maybe_complete(hid)
                elif name == "KvHandoff":
                    self._on_state(obj)
                elif name == "KvPrefixFetch":
                    self._on_fetch(obj)
                elif name == "KvStreamResult":
                    if obj.get("op") == "abort":
                        self._on_abort(obj)
        except (OSError, KvWireError) as e:
            logger.debug("kv peer %s reader ended: %s", self.peer, e)
        finally:
            self.close("kv data connection lost")

    def _runner(self, engine_id: str):
        return self.server.scheduler.get(engine_id)

    def _result(self, rid: str, op: str, ok: bool,
                error: Optional[str] = None, depth: int = 0) -> None:
        self.enqueue("KvStreamResult", {
            "stream_id": rid, "op": op, "ok": ok,
            "error": error or "", "depth": depth,
        })

    def _maybe_complete(self, hid: str) -> None:
        """An ``open`` stream acts once its chunk count arrives (commit/
        resume wait for their terminal KvHandoff state frame)."""
        # single-owner: the reader thread is the only resolver of
        # _assemblies (close() never touches it), so get-then-pop
        # cannot race a second resolver
        # distlint: ignore[DL015]
        asm = self._assemblies.get(hid)
        if asm is None or asm.header.get("op") != "open":
            return
        if len(asm.chunks) < asm.header.get("total_chunks", 0):
            return
        self._assemblies.pop(hid, None)
        header = asm.header
        rid = header.get("request_id", "")
        runner = self._runner(header.get("engine_id", ""))
        if runner is None or not runner.is_healthy():
            self._result(rid, "open", False, "remote engine unavailable")
            return
        chunks = sorted(asm.chunks, key=lambda c: c.index)

        def _done(ok: bool, err: Optional[str]) -> None:
            # runner thread: enqueue only
            self._result(rid, "open", ok, err)

        runner.submit_import_open(
            rid, header.get("prefix_pages", 0), chunks, _done)

    def _on_state(self, obj: Dict[str, Any]) -> None:
        """Terminal KvHandoff frame of a commit/resume stream: rebuild
        the SequenceExport, register a local ServerRequest whose sink
        streams FleetEvents back, and hand it to the target runner."""
        from distributed_inference_server_tpu.serving.runner import (
            ServerRequest,
        )

        rid = obj.get("request_id", "")
        # pop-before-submit is safe HERE only because _assemblies is
        # owned by this reader thread alone: no crash sweep races the
        # window, and if the submit dies the wire dies with it — the
        # host settles the stream through connection death
        # distlint: ignore[DL015]
        asm = self._assemblies.pop(rid, None)
        if asm is None:
            return  # state frame with no header: torn stream, ignore
        header = asm.header
        op = header.get("op", "")
        engine_id = header.get("engine_id", "")
        runner = self._runner(engine_id)
        if runner is None or not runner.is_healthy():
            self._result(rid, op, False, "remote engine unavailable")
            return
        exp = _export_state_from_wire(obj)
        if asm.chunks:
            exp.kv_chunks = sorted(asm.chunks, key=lambda c: c.index)
            exp.wire_quant = header.get("wire_quant") or "none"
        sink = _DataEventSink(self, rid, engine_id)
        req = ServerRequest(
            rid, [int(t) for t in exp.token_ids[:exp.prompt_len]],
            exp.params, sink,
        )
        # the sequence streamed its pre-migration tokens on the HOST;
        # marking the first token here keeps member-side accounting from
        # double-counting TTFT for a mid-stream arrival
        req.first_token_at = time.monotonic()

        def _done(ok: bool, err: Optional[str]) -> None:
            if ok and err != "aborted":
                with self._lock:
                    self._live[rid] = engine_id
            self._result(rid, op, ok, err if not ok else None)

        if op == "commit":
            runner.submit_import_commit(exp, req, _done)
        else:
            runner.submit_resume(exp, req, _done)

    def _on_fetch(self, obj: Dict[str, Any]) -> None:
        rid = obj.get("request_id", "")
        runner = self._runner(obj.get("engine_id", ""))
        if runner is None or not runner.is_healthy():
            self._result(rid, "fetch", False, "remote engine unavailable")
            return
        wire_quant = obj.get("wire_quant") or "none"

        def _done(result, err: Optional[str]) -> None:
            # peer runner's thread: enqueue the response frames only —
            # serialization happens on the writer thread
            if result is None:
                self._result(rid, "fetch", False, err)
                return
            depth, chunks = result
            self.enqueue("KvHandoffHeader", {
                "handoff_id": rid, "request_id": rid,
                "wire_quant": wire_quant, "op": "fetch",
                "total_chunks": len(chunks),
            })
            for c in chunks:
                self.enqueue("KvChunk", chunk_to_wire(rid, c))
            self._result(rid, "fetch", True, depth=depth)

        runner.submit_prefix_export(
            rid, list(obj.get("hashes", [])),
            obj.get("chunk_pages", 0) or 8, wire_quant, _done,
        )

    def _on_abort(self, obj: Dict[str, Any]) -> None:
        rid = obj.get("stream_id", "")
        runner = self._runner(obj.get("engine_id", ""))
        if runner is not None:
            runner.submit_import_abort(rid)
        self._assemblies.pop(rid, None)

    def close(self, reason: str) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            live = dict(self._live)
            self._live.clear()
        try:
            # shutdown first: our own reader blocked in recv pins the
            # kernel socket — a bare close would defer the FIN and the
            # host would never see this connection die
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass
        # stop the writer WITHOUT blocking: the queue may be full (a
        # wedged writer is one of the paths into close) and the writer
        # will never drain it — a plain put() here would deadlock the
        # engine-runner thread whose enqueue() triggered the close.
        # Drain the stale frames (the connection is dead; none would be
        # sent) and best-effort the sentinel: if it still doesn't fit,
        # the writer is mid-send and exits via the send-failure arm the
        # shutdown above just armed.
        while True:
            try:
                self._out.get_nowait()
            except queue.Empty:
                break
        try:
            self._out.put_nowait(None)
        except queue.Full:
            pass
        # the host vanished mid-decode: abort the orphaned migrated
        # sequences — nobody is listening for their tokens, and the
        # host's channel death already failed them client-side
        for rid, engine_id in live.items():
            runner = self._runner(engine_id)
            if runner is not None:
                try:
                    runner.abort(rid)
                except Exception as e:  # noqa: BLE001 — cleanup isolation
                    logger.debug("kv peer %s: orphan abort failed: %s",
                                 self.peer, e)
        self.server._drop_conn(self)


class KvDataServer:
    """The member's KV data listener (started by FleetWorker; its bound
    port rides every heartbeat). Serves export/import streams against
    the member's LOCAL runners via the scheduler."""

    def __init__(self, scheduler, host: str = "0.0.0.0", port: int = 0,
                 metrics: Optional[MetricsCollector] = None):
        self.scheduler = scheduler
        self.metrics = metrics
        self._host = host
        self._port = port
        self._sock: Optional[socket.socket] = None
        self._thread: Optional[threading.Thread] = None
        self._conns: List[_KvPeerConn] = []
        self._lock = threading.Lock()
        self._stopping = False
        self.bound_port = 0

    def start(self) -> None:
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        sock.bind((self._host, self._port))
        sock.listen(8)
        self._sock = sock
        self.bound_port = sock.getsockname()[1]
        self._stopping = False
        # lifecycle handle  # distlint: ignore[DL008]
        self._thread = threading.Thread(
            target=self._accept_loop, name="kv-data-accept", daemon=True
        )
        self._thread.start()
        logger.info("kv data listener on %s:%d", self._host, self.bound_port)

    def stop(self) -> None:
        self._stopping = True
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
            conns = list(self._conns)
        for conn in conns:
            conn.close("kv data server stopping")
        if self._thread is not None:
            self._thread.join(5.0)
            self._thread = None

    def _accept_loop(self) -> None:
        while not self._stopping:
            try:
                sock, addr = self._sock.accept()
            except OSError:
                return  # listener closed
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            conn = _KvPeerConn(self, sock, f"{addr[0]}:{addr[1]}")
            with self._lock:
                self._conns.append(conn)
            threading.Thread(
                target=conn.run, name=f"kv-peer-read-{addr[0]}:{addr[1]}",
                daemon=True,
            ).start()

    def _drop_conn(self, conn: _KvPeerConn) -> None:
        with self._lock:
            try:
                self._conns.remove(conn)
            except ValueError:
                pass
