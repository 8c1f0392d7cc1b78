"""Engine runner: a dedicated thread owning one ``LLMEngine`` replica.

The reference's ``InferenceWorker`` (``design.md:335-342`` [spec]) maps to
one runner = one engine replica = one "worker". The engine itself is
single-owner and synchronous (engine/engine.py); every interaction with it
— request admission, aborts, embeddings — goes through a thread-safe inbox
drained on the runner thread between decode steps. Step outputs are fanned
out to per-request ``ResultSink``s, which the HTTP layer bridges onto the
asyncio loop.

Failure semantics (``requirements.md:104-110,130-134``):
- per-request failures surface as ``StepOutput.error`` and poison only that
  request (Property 22);
- an unhandled exception in the step loop marks the runner unhealthy and
  fails all in-flight requests; the scheduler's health checker notices the
  flag within its check interval (<5 s detection, requirements.md:133) and
  can ``restart()`` it (worker self-restart, requirements.md:109).
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Protocol, Sequence

import numpy as np

from distributed_inference_server_tpu.core.models import FinishReason, Usage
from distributed_inference_server_tpu.core.types import RequestId
from distributed_inference_server_tpu.engine.engine import (
    LLMEngine,
    SamplingParams,
    StepOutput,
)
from distributed_inference_server_tpu.serving import faults
from distributed_inference_server_tpu.serving.metrics import (
    EngineStatus,
    MetricsCollector,
)

logger = logging.getLogger(__name__)

# Bound on engine construction + warm-up. On an empty compile cache the
# warm-up compiles every serving program at full width before the runner
# reports ready; 300 s read a slow-but-healthy cold start as "engine
# failed to start". Generous on purpose: it only has to catch a hang.
START_TIMEOUT_S = 1800.0


class ResultSink(Protocol):
    """Receives a request's step outputs. Methods are called on the runner
    thread and must be non-blocking and exception-free; the HTTP layer's
    sinks bounce to the asyncio loop via ``call_soon_threadsafe``."""

    def on_token(self, token_id: int, text: str, token_index: int,
                 logprob=None) -> None: ...

    def on_done(self, finish_reason: FinishReason, usage: Usage) -> None: ...

    def on_error(self, message: str, code: str) -> None: ...


class ServerRequest:
    """A validated, tokenized request handed to the serving spine."""

    __slots__ = ("request_id", "prompt_ids", "params", "sink", "submitted_at",
                 "first_token_at", "span", "engine_span", "redispatches",
                 "tenant")

    def __init__(
        self,
        request_id: RequestId,
        prompt_ids: List[int],
        params: SamplingParams,
        sink: ResultSink,
        span=None,
        tenant: str = "default",
    ):
        self.request_id = request_id
        self.prompt_ids = prompt_ids
        self.params = params
        self.sink = sink
        self.submitted_at = time.monotonic()
        self.first_token_at: Optional[float] = None
        # request-lifecycle tracing (S12): root span owned by the handler,
        # engine child span owned by the runner
        self.span = span
        self.engine_span = None
        # crash-safe redispatch attempts consumed (docs/RESILIENCE.md):
        # bounded by the dispatcher so a systemic crash cannot bounce a
        # request around the fleet forever
        self.redispatches = 0
        # per-tenant fair admission key (core/queue.py DRR; docs/FLEET.md)
        self.tenant = tenant or "default"


class EngineRunner:
    """Runs one engine on a dedicated thread; thread-safe façade."""

    def __init__(
        self,
        engine_id: str,
        engine_factory: Callable[[], LLMEngine],
        metrics: Optional[MetricsCollector] = None,
        tracer=None,
        role: str = "unified",
        disagg=None,
        recorder=None,
    ):
        """``role`` ("prefill" | "decode" | "unified") and ``disagg``
        (the DisaggController) enable disaggregated serving
        (serving/disagg.py): a prefill runner admits requests
        prefill-only and exports each finished prefill to the controller
        for migration; a decode runner receives them via
        ``submit_resume``. Unified (the default) is today's monolithic
        behavior exactly.

        ``recorder`` (serving/flightrec.py): first-token / decode-block
        / terminal events land in the per-request flight-recorder
        timeline. None (the default) keeps the per-token path free of
        recorder work entirely."""
        self.engine_id = engine_id
        self.role = role
        self._disagg = disagg
        self._factory = engine_factory
        self.metrics = metrics
        self.tracer = tracer
        self.recorder = recorder
        # crash-safe redispatch hook (docs/RESILIENCE.md): the server
        # wires this to Dispatcher.redispatch. Called from _fail_all_of
        # for an in-flight request that streamed ZERO tokens; returns
        # True when it took ownership (the request will terminate on
        # another replica — this runner must NOT also resolve its sink).
        self.redispatch: Optional[
            Callable[[ServerRequest, str, str], bool]
        ] = None
        self._inbox: Deque[Callable[[], None]] = deque()
        self._inbox_lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._healthy = False
        self._last_error: Optional[str] = None
        self._total_processed = 0
        # lock-free by design: per-request dict ops are GIL-atomic and
        # the exactly-once protocol is pop-first — every terminal path
        # pops before resolving (docs/RESILIENCE.md)
        # distlint: registry
        self._inflight: Dict[RequestId, ServerRequest] = {}  # distlint: ignore[DL008]
        # submit_resume callbacks not yet run by the engine thread: a
        # crash/shutdown before the inbox drains resolves them from
        # _fail_all (exactly-once via dict.pop), otherwise the migration
        # job would leak in DisaggController._migrating and wedge every
        # future drain on pending_count()
        self._pending_resumes: Dict[RequestId, Callable] = {}
        # streamed handoff exports in flight (engine HandoffExportSession
        # + the request + the controller stream job), advanced by
        # _pump_export_jobs between steps; owned by the runner thread —
        # the only cross-thread touches are GIL-atomic pops at crash/
        # restart time, after the thread died  # distlint: ignore[DL008]
        self._export_jobs: Dict[RequestId, list] = {}
        # phased-import state on a DECODE runner: open sessions awaiting
        # their commit (request_id -> (KvImportSession, engine)), plus
        # un-run open callbacks for crash-time resolution
        self._import_sessions: Dict[RequestId, tuple] = {}
        # token -> callback maps written from submitter threads (disagg
        # worker, dispatcher/fetcher, runner callbacks) and resolved on
        # the runner thread or at crash time: per-token dict ops are
        # GIL-atomic and exactly-once is pop-first by construction
        # (docs/RESILIENCE.md)  # distlint: ignore[DL008]
        self._pending_opens: Dict[str, Callable] = {}
        # un-run peer-fetch EXPORT callbacks (fleet prefix sharing,
        # serving/disagg.py PrefixFetcher): a crash before the inbox
        # drains resolves them from _fail_all — the fetcher then falls
        # back to recompute on the target instead of waiting forever on
        # a dead peer (same GIL-atomic pop-first exactly-once protocol)
        # distlint: ignore[DL008]
        self._pending_fetches: Dict[str, Callable] = {}
        self._pending_embeds: Dict[int, Callable] = {}
        self._embed_seq = 0
        # incremental embeddings jobs, advanced one device batch per
        # runner-loop iteration (owned by the engine thread)
        self._embed_jobs: Deque[dict] = deque()
        self._engine: Optional[LLMEngine] = None
        self._thread: Optional[threading.Thread] = None
        self._cache_seen = {"hits": 0, "misses": 0, "evictions": 0,
                            "host_hit_pages": 0}
        # mixed-step counter watermarks (engine.mixed_stats() reports
        # totals; the collector wants deltas)
        self._mixed_seen = {"prefill_tokens": 0, "decode_tokens": 0}
        # payload-byte watermarks (engine.payload_byte_counters()
        # reports totals by encoding kind; the collector wants deltas)
        self._payload_seen: Dict[str, int] = {}
        # looped-block counter watermarks (engine.loop_stats() reports
        # totals; the collector wants deltas — same shape as the mixed
        # block)
        self._loop_seen: Dict[str, Any] = {"steps": 0, "exits": {}}
        # step-clock watermarks (engine.step_clock_stats() reports
        # cumulative kind/event counters; the collector wants deltas —
        # same shape as the mixed block, docs/OBSERVABILITY.md)
        self._sc_seen: Dict[str, Dict] = {"kinds": {}, "events": {}}
        # rolling prefix digest for cache-aware routing (ISSUE 5):
        # refreshed on the engine thread (allocator state is single-
        # owner), read as an immutable snapshot by status() from any
        # thread
        self._prefix_digest: frozenset = frozenset()
        self._digest_ts = 0.0
        # old engines still finishing their in-flight requests after a
        # model hot-swap (Req 13.3: in-flight completes on the old model)
        self._draining: List[LLMEngine] = []

    # -- lifecycle ---------------------------------------------------------

    def start(self, wait_ready: bool = True,
              timeout: float = START_TIMEOUT_S) -> None:
        """Spawn the runner thread; optionally block until the engine is
        constructed (model loaded) and the runner reports ready
        (reference Req 7.2: worker reports ready before serving)."""
        ready = threading.Event()
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, args=(ready,), name=f"engine-{self.engine_id}",
            daemon=True,
        )
        self._thread.start()
        if wait_ready and not ready.wait(timeout):
            raise TimeoutError(f"engine {self.engine_id} failed to start in {timeout}s")
        if wait_ready and not self._healthy:
            raise RuntimeError(
                f"engine {self.engine_id} failed to initialize: {self._last_error}"
            )

    def shutdown(self, timeout: float = 30.0) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout)
        # health flag: GIL-atomic bool; writers are the runner thread and
        # lifecycle callers, readers tolerate one stale check (the health
        # loop re-reads every sweep)  # distlint: ignore[DL008]
        self._healthy = False
        if self.metrics:
            self.metrics.set_engine_up(self.engine_id, False)
        # anything still in flight will never complete — tell the clients
        self._fail_all("engine shut down before request completion")

    def restart(self, wait_ready: bool = True,
                timeout: float = START_TIMEOUT_S) -> None:
        """Tear down and bring the engine back (worker self-restart,
        requirements.md:109)."""
        self.shutdown()
        # under the lock even though the runner thread is joined: submit()
        # may still race in from the dispatcher thread (distlint DL002)
        with self._inbox_lock:
            self._inbox.clear()
        self._inflight.clear()
        self._export_jobs.clear()
        self.start(wait_ready=wait_ready, timeout=timeout)

    def set_role(self, role: str) -> None:
        """Re-role this runner at runtime (fleet role rebalancing,
        serving/fleet.py RoleBalancer). The flip is one attribute write:
        ``submit`` reads the role per batch, so the NEXT admission batch
        follows the new role while in-flight requests finish under the
        old one (a unified→prefill flip never strands a decode)."""
        self.role = role

    # -- submission (any thread) -------------------------------------------

    def submit(self, requests: Sequence[ServerRequest]) -> None:
        reqs = list(requests)
        # register in _inflight immediately (not inside the closure) so a
        # crash between submit and inbox-drain still fails these sinks
        for r in reqs:
            self._inflight[r.request_id] = r
        if not self._healthy:
            self._fail_all_of(reqs, self._last_error or "engine unavailable")
            return

        # admit unified when the decode fleet is gone (e.g. scaled away):
        # prefill-only admission would pay a KV serialize + retry +
        # in-place fallback on every request, forever
        prefill_only = (
            self.role == "prefill"
            and self._disagg is not None
            and self._disagg.has_decode_targets()
        )

        def _do() -> None:
            for r in reqs:
                if r.request_id in self._inflight:  # not aborted meanwhile
                    if self.tracer and r.span is not None:
                        r.engine_span = self.tracer.start(
                            "engine.infer", parent=r.span.context(),
                            engine_id=self.engine_id,
                            request_id=str(r.request_id),
                            prompt_tokens=len(r.prompt_ids),
                        )
                    self._engine.add_request(r.request_id, r.prompt_ids,
                                             r.params,
                                             prefill_only=prefill_only)

        self._post(_do)

    def abort(self, request_id: RequestId) -> None:
        def _do() -> None:
            if not self._engine.abort(request_id):
                for eng in self._draining:
                    if eng.abort(request_id):
                        break
            self._inflight.pop(request_id, None)

        self._post(_do)

    def submit_resume(self, exp, req: ServerRequest,
                      on_done: Callable[[bool, Optional[str]], None]) -> None:
        """Resume a migrated sequence on this runner's engine (KV handoff
        import, serving/disagg.py). ``on_done(ok, err)`` fires exactly
        once from the runner thread — or here, if the engine is already
        down. On ok=False the request has been deregistered again and the
        caller (the DisaggController) owns its fate (fallback)."""
        # register BEFORE the health check (same crash-safe ordering as
        # submit_embed): a crash between check and registration would
        # otherwise strand on_done un-called and leak the migration job.
        # _pending_resumes FIRST: a concurrent _fail_all that saw
        # _inflight but not the callback would sink-fail the request AND
        # let the fallback resume it — two contradictory terminal paths.
        # Cross-thread by design: GIL-atomic dict ops + exactly-once via
        # dict.pop  # distlint: ignore[DL008]
        self._pending_resumes[req.request_id] = on_done
        self._inflight[req.request_id] = req
        if not self._healthy:
            self._inflight.pop(req.request_id, None)
            cb = self._pending_resumes.pop(req.request_id, None)
            if cb is not None:  # None: _fail_all already resolved it
                cb(False, self._last_error or "engine unavailable")
            return

        def _do() -> None:
            cb = self._pending_resumes.pop(req.request_id, None)
            if cb is None:
                return  # already resolved by _fail_all (crash/shutdown)
            if req.request_id not in self._inflight:
                # aborted between registration and import: resolved (no
                # fallback wanted), but NOT a real transfer — the
                # "aborted" marker keeps the handoff metrics honest
                cb(True, "aborted")
                return
            try:
                self._engine.import_sequence(exp)
            except Exception as e:  # noqa: BLE001 — import fault domain
                self._inflight.pop(req.request_id, None)
                cb(False, str(e))
                return
            cb(True, None)

        self._post(_do)

    def submit_import_open(self, request_id: RequestId, prefix_pages: int,
                           chunks, on_done: Callable[[bool, Optional[str]],
                                                     None]) -> None:
        """Phase 1 of a streamed handoff on the TARGET runner: open an
        incremental import session, reserve the prefix pages, and absorb
        the prefix chunks — all while the source sequence is still
        decoding in place. ``on_done(ok, err)`` fires exactly once (from
        the runner thread, or here if the engine is down); ok=True means
        the target is ready for the switchover commit."""
        token = f"open:{request_id}"
        self._pending_opens[token] = on_done
        if not self._healthy:
            cb = self._pending_opens.pop(token, None)
            if cb is not None:
                cb(False, self._last_error or "engine unavailable")
            return

        def _do() -> None:
            cb = self._pending_opens.pop(token, None)
            if cb is None:
                return  # resolved by _fail_all
            engine = self._engine
            session = None
            try:
                session = engine.import_stream_open(request_id, prefix_pages)
                engine.import_stream_add(session, chunks)
            except Exception as e:  # noqa: BLE001 — import fault domain
                if session is not None:
                    # the open reserved pages; a chunk-validation failure
                    # (crc, shape, duplicate) must hand them back or the
                    # decode engine bleeds capacity on every bad stream
                    try:
                        engine.import_stream_abort(session)
                    except Exception as abort_exc:  # noqa: BLE001
                        self._absorbed("import_abort", abort_exc)
                cb(False, str(e))
                return
            # bind the session to ITS engine: a hot-swap between open
            # and commit must not scatter into the new model's pool
            self._import_sessions[request_id] = (session, engine)
            cb(True, None)

        self._post(_do)

    def submit_import_commit(self, exp, req: ServerRequest,
                             on_done: Callable[[bool, Optional[str]],
                                               None]) -> None:
        """Phase 2: absorb the tail delta, validate, publish, and seat —
        the part of the import that sits inside the migrated sequence's
        stall window. Same registration/crash-safety contract as
        submit_resume (on_done exactly once; ok=False hands the request
        back to the controller's fallback)."""
        self._pending_resumes[req.request_id] = on_done
        self._inflight[req.request_id] = req
        if not self._healthy:
            self._inflight.pop(req.request_id, None)
            cb = self._pending_resumes.pop(req.request_id, None)
            self._drop_import_session(req.request_id)
            if cb is not None:
                cb(False, self._last_error or "engine unavailable")
            return

        def _do() -> None:
            cb = self._pending_resumes.pop(req.request_id, None)
            if cb is None:
                return  # already resolved by _fail_all (crash/shutdown)
            entry = self._import_sessions.pop(req.request_id, None)
            if req.request_id not in self._inflight:
                # aborted between registration and commit
                if entry is not None:
                    entry[1].import_stream_abort(entry[0])
                cb(True, "aborted")
                return
            if entry is None:
                self._inflight.pop(req.request_id, None)
                cb(False, "no open import session (engine restarted?)")
                return
            session, engine = entry
            if engine is not self._engine:
                # hot-swapped since open: the reserved pages belong to
                # the OLD pool; abort there and reject the commit
                engine.import_stream_abort(session)
                self._inflight.pop(req.request_id, None)
                cb(False, "engine swapped mid-import")
                return
            try:
                engine.import_stream_commit(session, exp)
            except Exception as e:  # noqa: BLE001 — import fault domain
                self._inflight.pop(req.request_id, None)
                cb(False, str(e))
                return
            cb(True, None)

        self._post(_do)

    def submit_import_abort(self, request_id: RequestId) -> None:
        """Drop an opened-but-uncommitted import (source cancelled the
        stream / client disconnect): release the reserved pages."""
        self._post(lambda: self._drop_import_session(request_id))

    # -- fleet prefix sharing (peer fetch, serving/disagg.py) --------------

    def submit_prefix_export(
        self, request_id: RequestId, hashes: Sequence[int],
        chunk_pages: int, wire_quant: str,
        on_done: Callable[[Optional[tuple], Optional[str]], None],
        trace=None,
    ) -> None:
        """Peer-fetch SOURCE side: serialize this engine's cached prefix
        chain for ``hashes`` (engine.export_prefix_chunks — HBM and
        host tier, consecutive from the head) on the engine thread.
        ``on_done((depth, chunks), None)`` or ``on_done(None, err)``
        fires exactly once — from the runner thread, or here/at crash
        time if the engine is (or becomes) unavailable, so a peer dying
        mid-fetch degrades the caller to recompute instead of wedging
        the request (docs/RESILIENCE.md). ``trace`` exists for surface
        parity with RemoteRunner (serving/fleet_kv.py carries it on the
        wire); an in-process export has nowhere to ship it."""
        token = f"pfx:{request_id}"
        self._pending_fetches[token] = on_done
        if not self._healthy:
            cb = self._pending_fetches.pop(token, None)
            if cb is not None:
                cb(None, self._last_error or "engine unavailable")
            return

        def _do() -> None:
            cb = self._pending_fetches.pop(token, None)
            if cb is None:
                return  # resolved by _fail_all (crash/shutdown)
            try:
                depth, chunks = self._engine.export_prefix_chunks(
                    hashes, chunk_pages=chunk_pages, wire_quant=wire_quant
                )
            except Exception as e:  # noqa: BLE001 — export fault domain
                cb(None, str(e))
                return
            cb((depth, chunks), None)

        self._post(_do)

    def submit_prefix_import(
        self, request_id: RequestId, tokens: Sequence[int], chunks,
        on_done: Callable[[bool, Optional[str]], None],
    ) -> None:
        """Peer-fetch TARGET side: validate-and-scatter the fetched
        prefix chunks into this engine's prefix cache
        (engine.import_prefix) so the request submitted right after
        matches them. Same exactly-once callback contract as
        submit_import_open (ok=False → the fetcher falls back to plain
        recompute; the pages were released by the aborted session)."""
        token = f"pfx-import:{request_id}"
        self._pending_opens[token] = on_done
        if not self._healthy:
            cb = self._pending_opens.pop(token, None)
            if cb is not None:
                cb(False, self._last_error or "engine unavailable")
            return

        def _do() -> None:
            cb = self._pending_opens.pop(token, None)
            if cb is None:
                return  # resolved by _fail_all
            try:
                self._engine.import_prefix(tokens, chunks)
            except Exception as e:  # noqa: BLE001 — import fault domain
                cb(False, str(e))
                return
            cb(True, None)

        self._post(_do)

    def _drop_import_session(self, request_id: RequestId) -> None:
        entry = self._import_sessions.pop(request_id, None)
        if entry is not None:
            try:
                entry[1].import_stream_abort(entry[0])
            except Exception as e:  # noqa: BLE001 — cleanup isolation
                self._absorbed("import_abort", e)

    def _drain_handoffs(self) -> bool:
        """Export finished prefills parked by the engine and queue their
        migration (prefill-role runners only). Runs on the runner thread
        between steps; returns True if it moved anything.

        With ``disagg.stream`` on (the default) each export runs as a
        STREAMED job: the sequence resumes decoding in place while its
        immutable prefix pages serialize (engine.export_handoff_begin),
        and one runner-loop iteration later the switchover drains the
        pipeline, serializes only the tail delta, and enqueues the
        migration — the request's decode pause is O(tail), not
        O(seq_len). Draft-model engines and too-short completions take
        the monolithic path (engine.export_handoff)."""
        if self._disagg is None or self._engine is None:
            return False
        worked = self._pump_export_jobs()
        ids = self._engine.handoff_ready_ids()
        if not ids:
            return worked
        settings = self._disagg.settings
        stream = settings.stream and self._engine.draft_state is None
        for rid in ids:
            # pop-tolerant engine-thread read: only crash sweeps pop
            # concurrently, and the None arm below handles that winner
            # distlint: ignore[DL015]
            req = self._inflight.get(rid)
            if req is None:
                # aborted after readiness: clear the engine-side state
                self._engine.abort(rid)
                continue
            try:
                if stream:
                    session = self._engine.export_handoff_begin(
                        rid, chunk_pages=settings.chunk_pages,
                        wire_quant=settings.wire_quant,
                    )
                    if session is not None:
                        entry = [session, req, None]
                        self._export_jobs[rid] = entry
                        # serialize + open the target NOW (the pulls
                        # overlap the in-flight decode pipeline) so the
                        # overlap window stays a couple of blocks wide
                        self._advance_export_job(rid, entry)
                        self._advance_export_job(rid, entry)
                        continue
                    # not worth streaming (tiny prefix / short budget)
                stalled_at = time.monotonic()
                exp = self._engine.export_handoff(
                    rid, wire_quant=settings.wire_quant)
            except Exception as e:  # noqa: BLE001 — per-request isolation
                # the engine may still hold the sequence (and its pages);
                # abort releases them and clears has_work, or the runner
                # loop would busy-spin on a zombie forever
                self._engine.abort(rid)
                self._inflight.pop(rid, None)
                if self.recorder is not None:
                    self.recorder.finish(rid, "error",
                                         code="handoff_failed")
                try:
                    req.sink.on_error(f"KV export failed: {e}",
                                      "handoff_failed")
                except Exception as sink_exc:  # noqa: BLE001
                    self._absorbed("sink_error", sink_exc)
                continue
            if exp is None:
                continue
            exp.source_engine = self.engine_id
            exp.stalled_at = stalled_at
            self._inflight.pop(rid, None)
            self._disagg.enqueue(exp, req, self)
        return True

    def _pump_export_jobs(self) -> bool:
        """Advance streamed exports one stage per runner-loop iteration
        (the sequence decodes a block between stages — that is the
        overlap window): serialize the prefix, open the target through
        the controller (phase 1), poll until the target is ready, then
        switch over — export only the tail delta and commit (phase 2).
        Any failure before the switchover costs nothing: the sequence
        just keeps decoding in place."""
        if not self._export_jobs:
            return False
        for rid, entry in list(self._export_jobs.items()):
            self._advance_export_job(rid, entry)
        return True

    def _advance_export_job(self, rid, entry) -> None:
        """One stage of one streamed export; exceptions are contained to
        the request (per-request isolation)."""
        session, req, job = entry
        try:
            if session.dead:
                self._drop_export_job(rid, job, record=False)
                return
            if not session.prefix_done:
                self._engine.export_handoff_pump(session)
                return  # target opens while the next block decodes
            if job is None:
                job = self._disagg.open_stream(
                    rid, session.chunks, len(session.prefix_pages),
                    session.wire_quant, req, self,
                )
                if job is None:  # controller not accepting
                    self._cancel_export(rid, session, None, record=False)
                    return
                entry[2] = job
                return
            if job.status == "opening":
                if time.monotonic() > job.deadline:
                    self._cancel_export(rid, session, job, record=True)
                return
            if job.status in ("failed", "cancelled"):
                self._cancel_export(rid, session, job,
                                    record=job.status == "failed")
                return
            # target ready -> switchover
            exp, outputs = self._engine.export_handoff_finish(session)
            self._dispatch(outputs)
            self._export_jobs.pop(rid, None)
            # pop-tolerant engine-thread read (absent entry = resolved)
            # distlint: ignore[DL015]
            if exp is None or rid not in self._inflight:
                # finished/aborted/preempted in place during the
                # overlap: no migration, nothing to fall back from
                logger.debug(
                    "%s: streamed export of %s cancelled "
                    "(sequence resolved in place)", self.engine_id, rid,
                )
                self._disagg.cancel_stream(job, record=False)
                return
            exp.source_engine = self.engine_id
            self._inflight.pop(rid, None)
            self._disagg.commit_stream(job, exp)
        except Exception as e:  # noqa: BLE001 — per-request isolation
            self._drop_export_job(rid, job, record=False)
            self._engine.abort(rid)
            self._inflight.pop(rid, None)
            if self.recorder is not None:
                self.recorder.finish(rid, "error", code="handoff_failed")
            try:
                req.sink.on_error(f"KV export failed: {e}",
                                  "handoff_failed")
            except Exception as sink_exc:  # noqa: BLE001
                self._absorbed("sink_error", sink_exc)

    def _cancel_export(self, rid, session, job, record: bool) -> None:
        """Abandon a streamed export BEFORE the switchover: the sequence
        keeps decoding in place (that is the whole fallback), the
        target's reserved pages are released via the controller."""
        self._engine.export_handoff_cancel(session)
        self._drop_export_job(rid, job, record=record)

    def _drop_export_job(self, rid, job, record: bool) -> None:
        self._export_jobs.pop(rid, None)
        if job is not None and self._disagg is not None:
            self._disagg.cancel_stream(job, record=record)

    def evict_cache(self, target_frac: float,
                    drop_host_tier: bool = False) -> None:
        """Evict cached (refcount-0) prefix pages until used/total <=
        target_frac (degradation ladder, design.md:937 [spec]). Evicted
        pages DEMOTE to the host tier when one is configured;
        ``drop_host_tier`` (the ladder's most severe rung) skips the
        demotion and clears the host tier too."""

        def _do() -> None:
            self._engine.evict_cache(target_frac,
                                     drop_host_tier=drop_host_tier)
            self._refresh_digest(force=True)

        self._post(_do)

    def submit_embed(
        self,
        ids_list: List[List[int]],
        on_result: Callable[[Optional[np.ndarray], Optional[str]], None],
    ) -> None:
        """Queue an embeddings computation; ``on_result(array, error)`` is
        called exactly once — on the runner thread, or here/at crash time if
        the engine is (or becomes) unavailable.

        The computation runs as an incremental job: the runner loop
        processes ONE device batch per iteration between decode steps
        (engine.embed_step), so a large embeddings request never stalls
        the in-flight generations on this replica."""
        # register BEFORE the health check (same crash-safe ordering as
        # submit): a crash between check and registration would otherwise
        # strand the callback un-called forever
        self._embed_seq += 1
        token = self._embed_seq
        self._pending_embeds[token] = on_result
        if not self._healthy:
            cb = self._pending_embeds.pop(token, None)
            if cb is not None:
                cb(None, self._last_error or "engine unavailable")
            return

        def _enqueue() -> None:
            # bind the CURRENT engine: a hot-swap mid-job must not mix
            # two models' hidden states in one accumulator
            engine = self._engine
            try:
                state = engine.embed_start(ids_list)
            except Exception as e:  # noqa: BLE001 — called-exactly-once
                cb = self._pending_embeds.pop(token, None)
                if cb is not None:
                    cb(None, str(e))
                return
            self._embed_jobs.append(
                {"token": token, "engine": engine, "state": state}
            )

        self._post(_enqueue)

    def _embed_quantum(self) -> bool:
        """Advance the oldest embeddings job by one device batch (runner
        loop calls this between decode steps). Returns True if it did
        work."""
        if not self._embed_jobs:
            return False
        job = self._embed_jobs[0]
        # pop-tolerant engine-thread read: a crash handler popping the
        # token is exactly the case the branch below retires
        # distlint: ignore[DL015]
        if job["token"] not in self._pending_embeds:
            self._embed_jobs.popleft()  # failed by a crash handler
            return True
        result = error = None
        try:
            if job["engine"].embed_step(job["state"]):
                result = job["engine"].embed_finish(job["state"])
        except Exception as e:  # noqa: BLE001 — isolation boundary
            error = str(e)
        if result is not None or error is not None:
            self._embed_jobs.popleft()
            cb = self._pending_embeds.pop(job["token"], None)
            if cb is not None:
                cb(result, error)
        return True

    def set_mixed_prefill_frac(self, frac: float) -> None:
        """Degradation-ladder hook: shrink the mixed step's prefill
        share under memory pressure (engine.set_mixed_prefill_frac on
        the engine thread; a no-op when the mixed step is off)."""

        def _do() -> None:
            self._engine.set_mixed_prefill_frac(frac)

        self._post(_do)

    def set_loop_cap_frac(self, frac: float) -> None:
        """Degradation-ladder hook: shrink the looped-block iteration
        cap under pressure so run-to-completion blocks hand control
        back to the host sooner (engine.set_loop_cap_frac on the
        engine thread; a no-op when loop_to_completion is off)."""

        def _do() -> None:
            self._engine.set_loop_cap_frac(frac)

        self._post(_do)

    def reset_speculation(self) -> None:
        """Clear every pattern's acceptance tracker (Req 12.5 explicit
        reset — e.g. the operator knows the request pattern changed);
        re-enables speculation immediately with fresh measurement
        windows."""

        def _do() -> None:
            if self._engine.spec_trackers is not None:
                self._engine.spec_trackers.reset()

        self._post(_do)

    def profile_steps(self, n: int, timeout_s: float = 30.0) -> dict:
        """Capture a device trace over the next ``n`` engine steps
        (utils/profiler.py; SURVEY §5 device-tracing bar). Blocks up to
        ``timeout_s`` for the capture to finish — an idle engine only
        captures once work arrives. Returns the trace summary dict, or a
        dict with an ``error`` key."""
        if not self._healthy:
            return {"error": self._last_error or "engine unavailable"}
        box: dict = {}
        armed = threading.Event()

        def _do() -> None:
            box["ev"], box["holder"] = self._engine.profile_steps(n)
            armed.set()

        self._post(_do)
        if not armed.wait(timeout_s):
            return {"error": "engine thread did not arm the capture in time"}
        if not box["ev"].wait(timeout_s):
            self._post(lambda: self._engine.cancel_profile(box["holder"]))
            return {
                "error": f"capture did not complete within {timeout_s}s "
                "(engine idle? send traffic while profiling)"
            }
        return dict(box["holder"])

    def _post(self, fn: Callable[[], None]) -> None:
        with self._inbox_lock:
            self._inbox.append(fn)
        self._wake.set()

    def _absorbed(self, site: str, exc: BaseException) -> None:
        """An isolation boundary deliberately ate ``exc``; make that
        observable — debug log + ``errors_total{site=...}`` — instead of
        silent (distlint DL004). Must never raise itself."""
        logger.debug("%s: absorbed error at %s: %s: %s", self.engine_id,
                     site, type(exc).__name__, exc)
        if self.metrics:
            self.metrics.record_error(f"runner.{site}")

    # -- model hot-swap (Req 13, requirements.md:178-182) ------------------

    def swap_model(
        self,
        factory: Callable[[], LLMEngine],
        on_done: Optional[Callable[[bool, Optional[str]], None]] = None,
        cancelled: Optional[threading.Event] = None,
    ) -> None:
        """Hot-swap the model: build the new engine on a background thread
        (serving continues on the old model, Req 13.1-13.2), then switch
        atomically at an inbox-drain point — new requests hit the new
        engine, in-flight ones finish on the old (Req 13.3). On load
        failure the old model stays (Req 13.4). The new engine starts with
        an empty KV cache and fresh cache stats (Req 13.5).

        ``cancelled`` (checked right before the switch, on the runner
        thread) lets an orchestrator abandon a swap that exceeded its
        deadline without a late install sneaking in afterwards."""

        def _build() -> None:
            try:
                eng = factory()
                if eng.ecfg.warmup_compile:
                    # the new model must not serve cold after the switch
                    eng.warmup()
            except Exception as e:  # noqa: BLE001 — keep old model
                self._last_error = f"model swap failed: {e}"
                if on_done:
                    on_done(False, str(e))
                return

            def _install() -> None:
                if cancelled is not None and cancelled.is_set():
                    if on_done:
                        on_done(False, "swap cancelled")
                    return
                old = self._engine
                self._engine = eng
                # restarts must come back on the swapped model
                self._factory = factory
                if old is not None and old.has_work():
                    self._draining.append(old)
                # fresh stats baseline for the new model (Req 13.5)
                self._cache_seen = {"hits": 0, "misses": 0, "evictions": 0}
                self._mixed_seen = {"prefill_tokens": 0,
                                    "decode_tokens": 0}
                self._loop_seen = {"steps": 0, "exits": {}}
                self._payload_seen = {}
                self._sc_seen = {"kinds": {}, "events": {}}
                if on_done:
                    on_done(True, None)

            self._post(_install)

        threading.Thread(
            target=_build, name=f"swap-{self.engine_id}", daemon=True
        ).start()

    # -- introspection (any thread) ---------------------------------------

    def tokenizer(self):
        """Tokenizer of the currently-installed engine (None until ready).
        A plain reference read — safe from other threads; the server uses
        it to retarget the handler's tokenizer after a model swap."""
        eng = self._engine
        return eng.tok if eng is not None else None

    def is_healthy(self) -> bool:
        return self._healthy

    def audit(self, timeout_s: float = 30.0) -> List[str]:
        """KV-page conservation audit (docs/RESILIENCE.md): run
        ``LLMEngine.audit_pages`` on the engine thread (allocator state
        is single-owner), counting open import sessions' reserved pages
        as live holders. Returns inconsistency strings — empty = clean.
        Unhealthy engines audit vacuously clean (their pool died with
        them and is rebuilt on restart)."""
        if not self._healthy:
            return []
        box: Dict[str, List[str]] = {}
        done = threading.Event()

        def _do() -> None:
            extra = [p for (session, _eng) in self._import_sessions.values()
                     for p in session.pages]
            box["issues"] = self._engine.audit_pages(extra)
            done.set()

        self._post(_do)
        if not done.wait(timeout_s):
            return [f"{self.engine_id}: audit timed out after {timeout_s}s "
                    "(engine thread wedged?)"]
        return box["issues"]

    def last_error(self) -> Optional[str]:
        return self._last_error

    def active_count(self) -> int:
        return len(self._inflight)

    def status(self) -> EngineStatus:
        eng = self._engine
        used = total = cached = page_size = digest_depth = 0
        waiting = 0
        speculation = host_tier = mixed = loop = latent = None
        if eng is not None:
            try:
                s = eng.cache_stats()
                # RAW occupancy (pages off the free list) with the cached
                # share broken out: cached (refcount-0 prefix) pages are
                # effectively free capacity — allocate() reclaims them
                # LRU on demand — so consumers score live pressure as
                # used - cached (scheduler memory_aware, degradation
                # ladder); counting cache as live pressure would drive
                # the ladder to EMERGENCY on a pool merely FULL OF CACHE.
                total = s.pages_total
                cached = s.pages_cached
                used = total - s.pages_free
                page_size = eng.pcfg.page_size
                digest_depth = eng.ecfg.digest_depth
                waiting = eng.num_waiting()
                host_tier = eng.host_tier_stats()
                latent = eng.latent_stats()
                mixed = eng.mixed_stats()
                loop = eng.loop_stats()
                speculation = eng.spec_stats()
                if speculation is not None and self.metrics:
                    self.metrics.set_speculation(self.engine_id, speculation)
            except Exception as e:  # noqa: BLE001 — status must never raise
                self._absorbed("status", e)
        return EngineStatus(
            engine_id=self.engine_id,
            role=self.role,
            healthy=self._healthy,
            active_requests=len(self._inflight),
            waiting_requests=waiting,
            total_processed=self._total_processed,
            memory_used_pages=used,
            memory_total_pages=total,
            pages_cached=cached,
            speculation=speculation,
            prefix_digest=self._prefix_digest,
            page_size=page_size,
            digest_depth=digest_depth,
            host_tier=host_tier,
            latent=latent,
            mixed=mixed,
            loop=loop,
        )

    def placement(self) -> Dict[str, object]:
        """The engine's device ids and resolved attention pair
        (``LLMEngine.placement``) for ``/health``; empty until the
        engine is constructed. Local runners only — it does not ride the
        fleet wire."""
        eng = self._engine
        return eng.placement() if eng is not None else {}

    # -- runner thread ----------------------------------------------------

    def _run(self, ready: threading.Event) -> None:
        try:
            self._engine = self._factory()
            if self._engine.ecfg.warmup_compile:
                # compile all serving programs before reporting ready
                # (first-request TTFT must not pay XLA compile)
                self._engine.warmup()
            self._healthy = True
        except Exception as e:  # noqa: BLE001 — startup failure isolation
            logger.exception("engine %s failed to start", self.engine_id)
            self._last_error = str(e)
            self._healthy = False
            ready.set()
            return
        finally:
            if self.metrics:
                self.metrics.set_engine_up(self.engine_id, self._healthy)
        ready.set()

        try:
            self._refresh_digest(force=True)
            while not self._stop.is_set():
                self._drain_inbox()
                worked = False
                if self._engine.has_work():
                    worked = True
                    t0 = time.monotonic()
                    outputs = self._engine.step()
                    # crash mid-step (docs/RESILIENCE.md): outputs were
                    # computed but none reached a sink — the nastiest
                    # window for the exactly-once termination contract
                    faults.fire("runner.step")
                    dt = time.monotonic() - t0
                    if self.metrics:
                        self.metrics.record_inference(dt)
                    self._dispatch(outputs)
                    self._report_cache_deltas()
                    # force on the busy→idle transition: a request's
                    # FINAL step is what publishes its prefix chain
                    # (_release_seq), and with no further steps the
                    # rate-limited refresh would never snapshot it —
                    # the fleet registry (cache_aware routing + peer
                    # fetch) would stay blind to a drained replica's
                    # freshly warmed cache
                    self._refresh_digest(force=not self._engine.has_work())
                if self._drain_handoffs():
                    # a handoff export moves payload bytes without a
                    # step — flush the per-kind byte counters now, or
                    # an otherwise-idle prefill replica's export never
                    # reaches kv_payload_bytes_total
                    self._report_cache_deltas()
                    worked = True
                worked |= self._step_draining()
                worked |= self._embed_quantum()
                if not worked:
                    self._wake.wait(0.005)
                    self._wake.clear()
        except Exception as e:  # noqa: BLE001 — engine-level crash
            self._last_error = str(e)
            self._healthy = False
            if self.metrics:
                self.metrics.set_engine_up(self.engine_id, False)
            self._fail_all(str(e))

    def _step_draining(self) -> bool:
        """Step old engines still finishing in-flight work after a swap.
        A crash in a draining engine fails only its own requests — the new
        engine keeps serving."""
        worked = False
        for eng in list(self._draining):
            if not eng.has_work():
                self._draining.remove(eng)
                continue
            worked = True
            try:
                self._dispatch(eng.step())
            except Exception as e:  # noqa: BLE001 — old-model isolation
                ids = list(getattr(eng, "_by_id", {}).keys())
                self._fail_all_of(
                    [r for r in self._inflight.values()
                     if r.request_id in ids],
                    f"old model failed during drain: {e}",
                )
                self._draining.remove(eng)
        return worked

    def _drain_inbox(self) -> None:
        while True:
            with self._inbox_lock:
                if not self._inbox:
                    return
                fn = self._inbox.popleft()
            # crash between submit and drain (docs/RESILIENCE.md):
            # requests sit in _inflight but the engine never saw them —
            # zero tokens streamed, so they are redispatchable. Fired
            # OUTSIDE the per-command try: an injected fault here kills
            # the runner, it is not a per-request failure.
            faults.fire("runner.inbox")
            try:
                fn()
            except Exception as e:  # noqa: BLE001 — command isolation
                self._last_error = str(e)

    def _dispatch(self, outputs: List[StepOutput]) -> None:
        tokens = 0
        for out in outputs:
            # pop-tolerant engine-thread read: only crash sweeps pop
            # concurrently, and the None arm below handles that winner
            # distlint: ignore[DL015]
            req = self._inflight.get(out.request_id)
            if req is None:
                continue
            # a terminal event (done OR error) already reached the sink:
            # the stream is resolved, so the except arm must not send a
            # second terminal event and must still count the request
            terminal_delivered = False
            try:
                if out.error is not None:
                    if self.recorder is not None:
                        self.recorder.finish(out.request_id, "error",
                                             code="inference_failed")
                    req.sink.on_error(out.error, "inference_failed")
                    terminal_delivered = True
                elif out.token_id is not None or out.text:
                    if req.first_token_at is None:
                        req.first_token_at = time.monotonic()
                        if self.metrics:
                            self.metrics.record_ttft(
                                req.first_token_at - req.submitted_at
                            )
                        if req.engine_span is not None:
                            req.engine_span.event("first_token")
                    if out.token_id is not None:
                        tokens += 1
                        if self.recorder is not None:
                            self.recorder.token(out.request_id)
                    if not out.finished:
                        req.sink.on_token(out.token_id, out.text,
                                          out.token_index, out.logprob)
                if out.finished:
                    if out.error is None:
                        # flush any final delta carried on the done event
                        if out.text:
                            req.sink.on_token(None, out.text, out.token_index)
                        req.sink.on_done(
                            out.finish_reason or FinishReason.STOP,
                            out.usage or Usage(),
                        )
                        terminal_delivered = True
                        if self.recorder is not None:
                            self.recorder.finish(out.request_id, "ok")
                    if self.tracer and req.engine_span is not None:
                        if out.usage is not None:
                            req.engine_span.set(
                                completion_tokens=out.usage.completion_tokens
                            )
                        self.tracer.finish(
                            req.engine_span,
                            status="ok" if out.error is None else "error",
                        )
                    self._inflight.pop(out.request_id, None)
                    self._total_processed += 1
            except Exception as e:  # noqa: BLE001 — sink isolation
                self._last_error = f"sink error: {e}"
                # best-effort: resolve the waiter before dropping, or the
                # client's future waits forever on a request the runner
                # no longer tracks (on_error is a different method — it
                # may well work even when on_token just raised). But if
                # a terminal event already succeeded (e.g. tracer.finish
                # raised after on_done/on_error), the request IS resolved
                # — a second terminal event would contradict the stream
                # contract.
                if not terminal_delivered:
                    if self.recorder is not None:
                        self.recorder.finish(out.request_id, "error",
                                             code="server_error")
                    try:
                        req.sink.on_error(f"sink failure: {e}",
                                          "server_error")
                    except Exception as err_exc:  # noqa: BLE001
                        self._absorbed("sink_error", err_exc)
                elif out.finished:
                    # the request DID resolve — only post-terminal
                    # bookkeeping raised; keep the count honest
                    self._total_processed += 1
                self._inflight.pop(out.request_id, None)
        if self.metrics and tokens:
            self.metrics.record_tokens(tokens)

    def _refresh_digest(self, force: bool = False,
                        min_interval_s: float = 0.25) -> None:
        """Snapshot the engine's prefix digest for cache-aware routing
        (engine thread only; rate-limited — the digest is advisory)."""
        now = time.monotonic()
        if not force and now - self._digest_ts < min_interval_s:
            return
        try:
            self._prefix_digest = self._engine.prefix_digest()
            self._digest_ts = now
        except Exception as e:  # noqa: BLE001 — digest is best-effort
            self._absorbed("prefix_digest", e)

    def _report_cache_deltas(self) -> None:
        if not self.metrics or self._engine is None:
            return
        try:
            s = self._engine.cache_stats()
            host = self._engine.host_tier_stats()
            payload = self._engine.payload_byte_counters()
            reloads = self._engine.drain_reload_durations()
            mixed = self._engine.mixed_stats()
            loop = self._engine.loop_stats()
            step_clock = self._engine.step_clock_stats()
            step_samples = self._engine.drain_step_samples()
        except Exception as e:  # noqa: BLE001
            self._absorbed("cache_stats", e)
            return
        self._report_step_clock(step_clock, step_samples)
        if mixed is not None:
            seen_m = self._mixed_seen
            dp = max(0, mixed["prefill_tokens"] - seen_m["prefill_tokens"])
            dd = max(0, mixed["decode_tokens"] - seen_m["decode_tokens"])
            if dp or dd:
                self.metrics.record_mixed_step(prefill_tokens=dp,
                                               decode_tokens=dd)
            self.metrics.set_mixed_density(self.engine_id,
                                           mixed["batch_density"])
            self._mixed_seen = {
                "prefill_tokens": mixed["prefill_tokens"],
                "decode_tokens": mixed["decode_tokens"],
            }
        if loop is not None:
            seen_l = self._loop_seen
            d_steps = max(0, loop["steps"] - seen_l["steps"])
            d_exits = {
                reason: max(0, n - seen_l["exits"].get(reason, 0))
                for reason, n in loop["exits"].items()
            }
            if d_steps or any(d_exits.values()):
                self.metrics.record_loop_block(steps=d_steps,
                                               exits=d_exits)
            self._loop_seen = {"steps": loop["steps"],
                               "exits": dict(loop["exits"])}
        # payload bytes by encoding kind (kv_payload_bytes_total): the
        # engine reports totals, the collector wants deltas
        payload_deltas = {
            kind: max(0, n - self._payload_seen.get(kind, 0))
            for kind, n in payload.items()
        }
        if any(payload_deltas.values()):
            self.metrics.record_kv_payload(payload_deltas)
        self._payload_seen = dict(payload)
        seen = self._cache_seen
        hits = max(0, s.hits - seen["hits"])
        self.metrics.record_cache(
            hits=hits,
            misses=max(0, s.misses - seen["misses"]),
            evictions=max(0, s.evictions - seen["evictions"]),
        )
        host_hit_pages = 0
        if host is not None:
            host_hit_pages = max(
                0, host["hit_pages"] - seen.get("host_hit_pages", 0)
            )
            self.metrics.set_host_tier(self.engine_id, host["bytes"],
                                       host["pages"])
        if hits or host_hit_pages:
            self.metrics.record_prefix_hits(hbm=hits, host=host_hit_pages)
        for dur in reloads:
            self.metrics.record_prefix_reload(dur)
        self._cache_seen = {
            "hits": s.hits, "misses": s.misses, "evictions": s.evictions,
            "host_hit_pages": host["hit_pages"] if host is not None else 0,
        }

    def _report_step_clock(self, step_clock: Dict, samples) -> None:
        """Delta-report the engine step clock into the collector
        (docs/OBSERVABILITY.md "Performance telemetry"): cumulative
        kind/event counters diffed against the last report, per-segment
        wall-time samples fed to the step_ms.<kind> windowed digests."""
        seen_kinds = self._sc_seen.get("kinds", {})
        for kind, cur in step_clock["kinds"].items():
            prev = seen_kinds.get(kind, {})
            d_disp = int(cur["dispatches"] - prev.get("dispatches", 0))
            d_wall = cur["wall_s"] - prev.get("wall_s", 0.0)
            d_tok = int(cur["tokens"] - prev.get("tokens", 0))
            d_rows = int(cur["rows"] - prev.get("rows", 0))
            if d_disp > 0 or d_wall > 0 or d_tok > 0:
                self.metrics.record_step_clock(
                    self.engine_id, kind, dispatches=max(0, d_disp),
                    wall_s=max(0.0, d_wall), tokens=max(0, d_tok),
                    rows=max(0, d_rows),
                )
        seen_events = self._sc_seen.get("events", {})
        deltas = {
            event: int(total - seen_events.get(event, 0))
            for event, total in step_clock["events"].items()
        }
        if any(n > 0 for n in deltas.values()):
            self.metrics.record_step_events(self.engine_id, deltas)
        self._sc_seen = step_clock
        for kind, wall_s in samples:
            self.metrics.observe_step(kind, wall_s)

    def _fail_all(self, message: str) -> None:
        # streamed exports die with the engine: cancel their stream jobs
        # so any target-side reserved pages are released (the requests
        # themselves are sink-failed below with the rest of _inflight)
        for rid, entry in list(self._export_jobs.items()):
            self._drop_export_job(rid, entry[2], record=False)
        self._export_jobs.clear()
        # resolve un-run resume imports FIRST, dropping them from
        # _inflight so they are not also sink-failed below: on_done(False)
        # hands the request back to the DisaggController, whose in-place
        # fallback owns its fate (a sink error here would be a second,
        # contradictory terminal event)
        for rid in list(self._pending_resumes):
            cb = self._pending_resumes.pop(rid, None)
            if cb is None:
                continue
            self._inflight.pop(rid, None)
            try:
                cb(False, message)
            except Exception as e:  # noqa: BLE001 — callback isolation
                self._absorbed("resume_callback", e)
        # phased-import state dies with the engine: resolve un-run open
        # callbacks (the controller's stream job falls back to in-place
        # decode on the source) and drop reserved pages — the pool is
        # gone with the engine anyway, but the allocator bookkeeping
        # must not leak across a restart()
        for token in list(self._pending_opens):
            cb = self._pending_opens.pop(token, None)
            if cb is not None:
                try:
                    cb(False, message)
                except Exception as e:  # noqa: BLE001 — callback isolation
                    self._absorbed("open_callback", e)
        # peer-fetch exports die with the engine: the fetcher falls back
        # to recompute on its target (the request never lived here)
        for token in list(self._pending_fetches):
            cb = self._pending_fetches.pop(token, None)
            if cb is not None:
                try:
                    cb(None, message)
                except Exception as e:  # noqa: BLE001 — callback isolation
                    self._absorbed("fetch_callback", e)
        for rid in list(self._import_sessions):
            self._drop_import_session(rid)
        self._fail_all_of(list(self._inflight.values()), message)
        self._inflight.clear()
        for token in list(self._pending_embeds):
            cb = self._pending_embeds.pop(token, None)
            if cb is not None:
                try:
                    cb(None, message)
                except Exception as e:  # noqa: BLE001
                    self._absorbed("embed_callback", e)

    def _fail_all_of(self, reqs: Sequence[ServerRequest], message: str) -> None:
        """Resolve dead in-flight requests, exactly once each, by
        construction: every request is popped from ``_inflight`` FIRST
        (this runner can never resolve it twice), then takes exactly one
        of two terminal paths —

        - **redispatch** (zero streamed tokens only): the dispatcher
          takes ownership and the request terminates on another replica
          — or fails there, once, if the fleet is really out of capacity;
        - **sink failure**: ``worker_failure`` for zero-token requests
          the dispatcher declined (shutdown / attempts exhausted / no
          healthy replica), ``engine_crashed`` — a distinct, client-
          distinguishable code — for requests that already streamed
          tokens, which can never be transparently re-run (a re-run
          could emit a diverging continuation mid-stream)."""
        for req in reqs:
            if self._inflight.pop(req.request_id, None) is None:
                # another failure path already owns this request (e.g.
                # submit() raced the engine thread's crash and both
                # reached here) — resolving it again would double-
                # terminate or double-redispatch
                continue
            if self.tracer and req.engine_span is not None:
                self.tracer.finish(req.engine_span, status="error")
                # the request has exactly one owner (popped above)
                req.engine_span = None  # distlint: ignore[DL008]
            if req.first_token_at is None and self.redispatch is not None:
                try:
                    if self.redispatch(req, self.engine_id, message):
                        continue  # the new owner resolves the sink
                except Exception as e:  # noqa: BLE001 — hook isolation
                    self._absorbed("redispatch", e)
            code = ("worker_failure" if req.first_token_at is None
                    else "engine_crashed")
            if self.recorder is not None:
                self.recorder.finish(req.request_id, "error", code=code)
            try:
                req.sink.on_error(message, code)
            except Exception as e:  # noqa: BLE001
                self._absorbed("sink_error", e)
