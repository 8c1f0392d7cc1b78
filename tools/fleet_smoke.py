"""Two-process fleet smoke (docs/FLEET.md; CI job ``fleet-smoke``).

Boots a REAL two-process fleet — a registry host with one local engine
and a worker process that joins over the fleet wire (TCP + protowire
frames) — then proves the control plane end to end:

1. **remote serving**: a request submitted on the registry host through
   the worker's RemoteRunner proxy completes token-identically to a
   local run (both processes build the same seeded tiny model, greedy
   sampling — the wire must not perturb a single token);
2. **stitched tracing + flight recorder** (docs/OBSERVABILITY.md): a
   remote-served request driven through the REAL HTTP surface yields
   ONE trace_id whose ``/server/trace?trace_id=`` tree contains spans
   from BOTH processes with intact parent links (the worker's
   ``fleet.serve``/``engine.infer`` spans arrive over FleetSpans frames
   and parent under the host's root span), and
   ``GET /server/requests/<id>`` returns a timeline whose phase
   attribution sums to within 10% of the request's wall clock;
2b. **performance telemetry** (docs/OBSERVABILITY.md "Performance
   telemetry"): the registry host's ``GET /server/perf`` shows the
   member's step-clock counters MOVING as it serves, its fleet-merged
   TTFT p99 EXACTLY equals an offline re-merge of the member digests
   fetched from each process, and ``fleet_*{member}`` series appear in
   host ``/metrics``;
2c. **registry HA** (docs/FLEET.md "Registry HA"): a three-process
   fleet — a primary registry child, a warm-standby registry (this
   process), and a dual-heartbeating worker. The primary child is
   SIGKILLed mid-fleet; the standby must promote itself within its
   lease window, serve a ``/generate`` through its own front door that
   routes over its ALREADY-WARM member proxy token-identically to the
   dead primary's pre-kill reference, and when the old primary reboots
   it must rejoin FENCED: standby, at the learned (higher) epoch;
2d. **KV mesh** (docs/FLEET.md "KV mesh"): a three-process fleet —
   registry + two mesh members — where a forced fetch moves the warm
   member's chunks DIRECTLY to the cold member over the
   registry-introduced wire, token-identically, while the registry's
   own data-channel byte counters do NOT move (the broker never
   relays), and the puller's observed transfer surfaces as a learned
   wire-rate row in the host's ``kv_wires`` stats table;
3. **remote death**: the worker process is SIGKILLed with a zero-token
   request in flight; the request must complete via crash-safe
   redispatch on the local engine — token-identically, exactly once,
   invisibly — with ``fleet_members{state="dead"}`` reflecting the loss
   and the local allocator passing a clean page audit.

Any failed assertion exits 1 with the violation, after dumping the
implicated request's flight-recorder timeline + stitched trace (the
postmortem story, docs/OBSERVABILITY.md).

    JAX_PLATFORMS=cpu python tools/fleet_smoke.py
    python tools/fleet_smoke.py --worker --connect 127.0.0.1:PORT  # child

A CPU tool, by construction: the parent pins ``JAX_PLATFORMS=cpu`` before
it touches jax and every child is spawned with that pin in its
environment. It must stay one — the parent holds jax while its children
run, and a chip belongs to one process (the chip is reached only through
``chip_smoke.py``, whose parent stays off jax).
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Optional

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

MEMBER_ID = "smoke-w1"
_PROMPT = "the fleet is one machine with many rooms"


def _env_setup() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def _smoke_slo():
    """One SLO digest geometry for EVERY smoke process: the host drops
    member telemetry whose epoch_s disagrees, and the degrade-and-
    recover leg needs a window short enough for latency evidence to
    decay inside the smoke."""
    from distributed_inference_server_tpu.serving.teledigest import (
        SloSettings,
    )

    return SloSettings(window_s=8.0, epoch_s=1.0)


def _build_server(fleet_settings=None, engine_roles=None, health=None,
                  strategy=None, engine_kwargs=None):
    """One-engine InferenceServer on the seeded tiny model (both
    processes build identical params: PRNGKey(0) is deterministic).
    ``engine_roles`` (a LIST, e.g. ``["prefill"]`` / ``["decode"]``)
    shapes the cross-host-handoff leg: the host prefills, a decode-role
    worker is the migration target over the KV data channel. ``health``
    (serving/health.py HealthSettings) paces the host's gray-failure
    scorer for the degrade-and-recover leg. ``strategy`` (a string,
    e.g. "cache_aware") and ``engine_kwargs`` (EngineConfig overrides —
    the mesh leg needs ``native_allocator=False`` for the prefix-digest
    surface) shape the KV-mesh leg's routing."""
    import jax
    import jax.numpy as jnp

    from distributed_inference_server_tpu.engine.engine import (
        EngineConfig,
        LLMEngine,
    )
    from distributed_inference_server_tpu.engine.kv_cache import (
        PagedCacheConfig,
    )
    from distributed_inference_server_tpu.models import llama
    from distributed_inference_server_tpu.models.configs import TINY
    from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer
    from distributed_inference_server_tpu.serving.scheduler import (
        SchedulingStrategy,
    )
    from distributed_inference_server_tpu.serving.server import InferenceServer

    params = llama.init_params(jax.random.PRNGKey(0), TINY,
                               dtype=jnp.float32)
    paged = PagedCacheConfig(num_pages=192, page_size=8,
                             max_pages_per_seq=32)

    def factory():
        return LLMEngine(
            params, TINY, ByteTokenizer(),
            EngineConfig(max_batch=4, prefill_buckets=(16, 64), paged=paged,
                         warmup_compile=False, **(engine_kwargs or {})),
            dtype=jnp.float32,
        )

    srv = InferenceServer(
        factory, ByteTokenizer(), model_name="tiny-fleet-smoke",
        num_engines=len(engine_roles) if engine_roles else 1,
        engine_roles=engine_roles,
        strategy=(SchedulingStrategy.parse(strategy) if strategy
                  else SchedulingStrategy.LEAST_LOADED),
        auto_restart=False, fleet_settings=fleet_settings,
        slo_settings=_smoke_slo(), health_settings=health,
    )
    srv.start()
    return srv


class _Sink:
    def __init__(self):
        self.toks, self.text = [], ""
        self.errors = []
        self.dones = 0
        self.ev = threading.Event()

    def on_token(self, token_id, text, token_index, logprob=None):
        if token_id is not None:
            self.toks.append(int(token_id))
        self.text += text

    def on_done(self, finish_reason, usage):
        self.dones += 1
        self.ev.set()

    def on_error(self, message, code):
        self.errors.append((message, code))
        self.ev.set()


def _request(rid: str):
    from distributed_inference_server_tpu.engine.engine import SamplingParams
    from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer
    from distributed_inference_server_tpu.serving.runner import ServerRequest

    sink = _Sink()
    req = ServerRequest(
        rid, ByteTokenizer().encode(_PROMPT),
        SamplingParams(max_tokens=16, temperature=0.0), sink,
    )
    return req, sink


def run_worker(connect: str, role: str = "",
               member_id: str = MEMBER_ID, http_port: int = 0,
               fault_spec: str = "", mesh: bool = False,
               registries: str = "") -> int:
    """Child process: one engine + a FleetWorker joined to ``connect``;
    serves until killed. ``role`` ("decode") makes this member the
    cross-host handoff target over its KV data channel. ``http_port``
    > 0 serves the member's own HTTP surface there (the perf leg
    fetches its /server/perf digests). ``fault_spec`` arms a seeded
    FaultSet in THIS process (the degrade-and-recover leg's
    fleet.slow_member delay; a bounded ``times=`` makes the fault
    self-clearing). ``mesh`` joins the member<->member KV mesh
    (docs/FLEET.md "KV mesh"): registry KvIntro frames are honored,
    fetch hints pull directly from peer members, and the engine keeps
    the Python allocator tier so its prefix digests have a surface.
    ``registries`` (comma-separated endpoints) dual-heartbeats EVERY
    registry (docs/FLEET.md "Registry HA") — the HA leg's worker.
    SIGTERM runs a page-conservation audit and exits
    with its verdict — the host's "clean audits both sides" check."""
    _env_setup()
    from distributed_inference_server_tpu.serving import faults
    from distributed_inference_server_tpu.serving.fleet import FleetSettings
    from distributed_inference_server_tpu.serving.remote_runner import (
        FleetWorker,
    )

    srv = _build_server(
        engine_roles=[role] if role else None,
        engine_kwargs={"native_allocator": False} if mesh else None,
    )
    if fault_spec:
        faults.install(faults.parse_spec(fault_spec, seed=0))
    regs = tuple(r.strip() for r in registries.split(",") if r.strip())
    worker = FleetWorker(
        srv.scheduler,
        FleetSettings(connect=connect, registries=regs,
                      heartbeat_interval_s=0.2, mesh_enabled=mesh),
        member_id=member_id,
        # fleet-stitched tracing: fleet.serve/engine.infer spans ship
        # back to the registry host (docs/OBSERVABILITY.md)
        tracer=srv.tracer,
        # performance telemetry: digests + step-clock counters ship as
        # heartbeat-piggybacked FleetTelemetry frames
        metrics=srv.metrics,
    )
    worker.start(connect_timeout_s=30.0)
    if http_port:
        _start_http(srv, port=http_port)
    print(f"fleet-smoke worker: joined {connect or ','.join(regs)} "
          f"(role={role or 'unified'})", flush=True)

    def _on_term(_sig, _frame):
        issues = []
        for runner in srv.scheduler.engines():
            issues.extend(runner.audit())
        if issues:
            print(f"fleet-smoke worker AUDIT VIOLATION: {issues}",
                  file=sys.stderr, flush=True)
            os._exit(3)
        print("fleet-smoke worker: audit clean, exiting", flush=True)
        os._exit(0)

    signal.signal(signal.SIGTERM, _on_term)
    while True:  # serve until the parent kills us
        time.sleep(1.0)


def _fail(msg: str) -> int:
    print(f"FLEET SMOKE VIOLATION: {msg}", file=sys.stderr, flush=True)
    return 1


def dump_postmortem(srv, request_id) -> None:
    """The violating request's story (docs/OBSERVABILITY.md): its
    flight-recorder timeline and its stitched trace, so a red run reads
    as a narrative instead of a seed."""
    import json

    print(f"--- postmortem for request {request_id} ---", file=sys.stderr)
    tl = srv.recorder.timeline(request_id)
    print("timeline:", json.dumps(tl, indent=2, default=str),
          file=sys.stderr)
    spans = srv.tracer.recent(500, request_id=str(request_id))
    trace_ids = {s.trace_id for s in spans}
    for tid in trace_ids:
        tree = srv.tracer.recent(500, trace_id=tid)
        print(f"trace {tid}:", json.dumps(
            [s.to_dict() for s in tree], indent=2, default=str),
            file=sys.stderr)
    if tl is None and not spans:
        print("(no timeline or spans recorded)", file=sys.stderr)
    print("--- end postmortem ---", file=sys.stderr, flush=True)


def _start_http(srv, port: int = 0):
    """Serve a server's real HTTP app from a background event loop;
    returns (loop, runner, port)."""
    import asyncio

    from aiohttp import web

    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    async def _up():
        runner = web.AppRunner(srv.build_app())
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", port)
        await site.start()
        bound = site._server.sockets[0].getsockname()[1]
        return runner, bound

    fut = asyncio.run_coroutine_threadsafe(_up(), loop)
    runner, bound = fut.result(60)
    return loop, runner, bound


def _free_port() -> int:
    """Pick an ephemeral port for a child's HTTP surface (bind/close:
    a tiny race is acceptable for a smoke)."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _http_json(method: str, url: str, body=None, timeout: float = 120.0):
    import json
    import urllib.request

    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"},
        method=method,
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _trace_leg(srv, port: int) -> Optional[str]:
    """The stitched-trace + flight-recorder acceptance (step 2 of the
    module docstring). Returns a violation string or None. The local
    engine is temporarily unregistered so the HTTP request MUST route
    to the remote member."""
    local = next(r for r in srv.scheduler.engines()
                 if not getattr(r, "is_remote", False))
    srv.scheduler.unregister(local.engine_id)
    try:
        resp = _http_json(
            "POST", f"http://127.0.0.1:{port}/generate",
            {"prompt": _PROMPT, "max_tokens": 12, "temperature": 0.0},
        )
    finally:
        srv.scheduler.register(local)
    rid = resp.get("id", "").split("-", 1)[-1]
    if not rid:
        return f"HTTP /generate returned no id: {resp}"

    # member spans arrive at heartbeat cadence — wait for the stitch
    deadline = time.monotonic() + 30.0
    spans = []
    while time.monotonic() < deadline:
        spans = _http_json(
            "GET", f"http://127.0.0.1:{port}/server/trace"
            f"?request_id={rid}&n=500")["spans"]
        if any(s["attributes"].get("member") == MEMBER_ID for s in spans):
            break
        time.sleep(0.2)
    by_member = [s for s in spans
                 if s["attributes"].get("member") == MEMBER_ID]
    if not by_member:
        dump_postmortem(srv, rid)
        return "no remote-member span ever merged into the host trace"
    trace_ids = {s["trace_id"] for s in spans}
    if len(trace_ids) != 1:
        dump_postmortem(srv, rid)
        return f"request produced {len(trace_ids)} trace ids: {trace_ids}"
    trace_id = trace_ids.pop()

    tree = _http_json(
        "GET", f"http://127.0.0.1:{port}/server/trace"
        f"?trace_id={trace_id}&n=500")["spans"]
    by_name = {s["name"]: s for s in tree}
    root = by_name.get("request.generate")
    serve = by_name.get("fleet.serve")
    if root is None or serve is None:
        dump_postmortem(srv, rid)
        return (f"stitched trace missing spans: have {sorted(by_name)} "
                "(want request.generate + fleet.serve)")
    if serve["parent_id"] != root["span_id"]:
        dump_postmortem(srv, rid)
        return ("parent link broken: fleet.serve.parent="
                f"{serve['parent_id']} != root span {root['span_id']}")
    if "member" in root["attributes"]:
        return "host root span claims a member attribute"
    infer = by_name.get("engine.infer")
    if infer is not None and infer["parent_id"] != serve["span_id"]:
        dump_postmortem(srv, rid)
        return ("parent link broken: engine.infer.parent="
                f"{infer['parent_id']} != fleet.serve {serve['span_id']}")
    print(f"fleet-smoke: one stitched trace {trace_id} with "
          f"{len(by_member)} remote span(s) OK", flush=True)

    tl = _http_json("GET",
                    f"http://127.0.0.1:{port}/server/requests/{rid}")
    phases = tl.get("phases", {})
    wall = tl.get("wall_s", 0.0)
    total = sum(phases.values())
    if wall <= 0:
        dump_postmortem(srv, rid)
        return f"timeline has no wall clock: {tl}"
    if abs(total - wall) > 0.10 * wall:
        dump_postmortem(srv, rid)
        return (f"phase attribution does not sum to the wall clock: "
                f"sum={total:.4f}s wall={wall:.4f}s phases={phases}")
    if tl.get("status") != "ok" or tl.get("tokens", 0) < 1:
        dump_postmortem(srv, rid)
        return f"timeline did not record a served request: {tl}"
    print(f"fleet-smoke: flight recorder phases sum {total:.3f}s vs "
          f"wall {wall:.3f}s OK", flush=True)
    return None


def _member_step_tokens(perf: dict, member: str) -> float:
    """Total step-clock tokens the host's /server/perf reports for one
    member (summed over its engines and dispatch kinds)."""
    counters = (perf.get("fleet", {}).get("members", {})
                .get(member, {}).get("counters", {}))
    return sum(v for name, v in counters.items()
               if name.startswith("step.") and name.endswith(".tokens"))


def _perf_leg(srv, port: int, worker_port: int) -> Optional[str]:
    """The performance-telemetry acceptance (docs/OBSERVABILITY.md
    "Performance telemetry"): the registry host's /server/perf shows
    the member's step-clock counters MOVING as it serves, its
    fleet-merged TTFT p99 EXACTLY equals re-merging the member digests
    fetched from each process (host + worker, one merge code path), and
    the fleet_*{member} series are present in host /metrics. Returns a
    violation string or None."""
    import re

    from distributed_inference_server_tpu.serving import teledigest

    # -- step-clock counters present, then moving under traffic --------
    deadline = time.monotonic() + 30.0
    before = 0.0
    while time.monotonic() < deadline:
        p = _http_json("GET", f"http://127.0.0.1:{port}/server/perf")
        before = _member_step_tokens(p, MEMBER_ID)
        if before > 0:
            break
        time.sleep(0.2)
    if before <= 0:
        return ("host /server/perf never showed member step-clock "
                "counters")
    # drive one more remote request (local engine unregistered so the
    # member must serve it), then the counters must advance
    local = next(r for r in srv.scheduler.engines()
                 if not getattr(r, "is_remote", False))
    srv.scheduler.unregister(local.engine_id)
    try:
        _http_json("POST", f"http://127.0.0.1:{port}/generate",
                   {"prompt": _PROMPT, "max_tokens": 8,
                    "temperature": 0.0})
    finally:
        srv.scheduler.register(local)
    deadline = time.monotonic() + 30.0
    after = before
    while time.monotonic() < deadline:
        p = _http_json("GET", f"http://127.0.0.1:{port}/server/perf")
        after = _member_step_tokens(p, MEMBER_ID)
        if after > before:
            break
        time.sleep(0.2)
    if after <= before:
        return (f"member step-clock counters never moved "
                f"({before} -> {after})")
    print(f"fleet-smoke: member step-clock counters moving "
          f"({before:.0f} -> {after:.0f} tokens) OK", flush=True)

    # -- merge identity: host merged p99 == re-merge of fetched digests
    # (idle first so the member's last shipped frame equals its live
    # digest; retried — an observation landing mid-leg re-races it)
    violation = "merge-identity leg never ran"
    for _attempt in range(5):
        time.sleep(1.0)  # ~5 heartbeat intervals of idle
        host_perf = _http_json("GET",
                               f"http://127.0.0.1:{port}/server/perf")
        member_perf = _http_json(
            "GET", f"http://127.0.0.1:{worker_port}/server/perf")
        merged_reported = (host_perf.get("fleet", {})
                           .get("merged", {}).get("ttft_ms"))
        member_ttft = member_perf.get("digests", {}).get("ttft_ms")
        host_ttft = host_perf.get("digests", {}).get("ttft_ms")
        if not merged_reported or not member_ttft or not host_ttft:
            violation = (f"missing ttft digests: merged="
                         f"{merged_reported} member={bool(member_ttft)} "
                         f"host={bool(host_ttft)}")
            continue
        remerged = teledigest.merge_digests([host_ttft, member_ttft])
        expect = teledigest.window_stats(
            remerged, host_perf["window_s"], host_perf["as_of_epoch"])
        if expect == merged_reported:
            violation = None
            break
        violation = (f"fleet-merged ttft p99 != re-merge of member "
                     f"digests: reported={merged_reported} "
                     f"remerged={expect}")
    if violation is not None:
        return violation
    print(f"fleet-smoke: fleet-merged TTFT p99 "
          f"{merged_reported.get('p99', 0):.2f}ms == offline re-merge "
          "(bit-equal) OK", flush=True)

    # -- fleet_*{member} series in host /metrics -----------------------
    prom = srv.metrics.prometheus_text().decode()
    if not re.search(
            r'fleet_member_step_tokens\{.*member="' + MEMBER_ID + '"',
            prom):
        return "fleet_member_step_tokens{member=...} missing in /metrics"
    if ('fleet_member_ttft_p99_ms{member="' + MEMBER_ID + '"') not in prom:
        return "fleet_member_ttft_p99_ms{member=...} missing in /metrics"
    print("fleet-smoke: fleet_*{member} series present in /metrics OK",
          flush=True)
    return None


def _handoff_leg(srv, port: int, registry_port: int,
                 ref_text: str) -> Optional[str]:
    """The cross-host-handoff acceptance (docs/FLEET.md "KV data
    plane"): a SECOND worker joins with a decode-role engine, so the
    host's prefill engine migrates the next HTTP request's live KV to
    it over the member's data channel — token-identically, with
    ``kv_handoff_chunks_total{scope="remote"}`` moving and clean page
    audits on BOTH processes (the worker audits on SIGTERM). Returns a
    violation string or None."""
    import re

    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker",
         "--connect", f"127.0.0.1:{registry_port}", "--role", "decode",
         "--member-id", "smoke-w2"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    try:
        deadline = time.monotonic() + 240.0
        while time.monotonic() < deadline:
            decode_remote = next(
                (r for r in srv.scheduler.engines()
                 if getattr(r, "is_remote", False)
                 and r.is_healthy() and r.role == "decode"
                 and getattr(r, "supports_kv_import", False)), None)
            if decode_remote is not None:
                break
            if child.poll() is not None:
                return "decode worker died before joining"
            time.sleep(0.1)
        else:
            return "decode worker never joined with a kv data channel"
        if not srv.disagg.has_decode_targets():
            return ("remote decode replica not counted as a handoff "
                    "target")
        # a short completion can finish decoding in place during the
        # cross-process open window (which is the CORRECT degradation,
        # not a failure) — so every attempt asserts token identity, and
        # the leg passes once a migration actually lands on the member
        migrated = False
        for attempt in range(5):
            resp = _http_json(
                "POST", f"http://127.0.0.1:{port}/generate",
                {"prompt": _PROMPT, "max_tokens": 96, "temperature": 0.0},
            )
            text = resp.get("choices", [{}])[0].get("text", "")
            if text != ref_text:
                rid = resp.get("id", "").split("-", 1)[-1]
                dump_postmortem(srv, rid)
                return (f"cross-host-migrated stream diverged (attempt "
                        f"{attempt}): {text!r} != {ref_text!r}")
            prom = srv.metrics.prometheus_text().decode()
            m = re.search(
                r'kv_handoff_chunks_total\{scope="remote"\} ([0-9.]+)',
                prom)
            if m is not None and float(m.group(1)) > 0:
                migrated = True
                break
        if not migrated:
            return ("kv_handoff_chunks_total{scope=remote} never moved "
                    "across 5 token-identical attempts")
        m = re.search(r'kv_handoff_total\{outcome="ok"\} ([0-9.]+)', prom)
        if m is None or float(m.group(1)) < 1:
            return "no successful handoff recorded"
        local = next(r for r in srv.scheduler.engines()
                     if not getattr(r, "is_remote", False))
        issues = local.audit()
        if issues:
            return f"host page audit after cross-host handoff: {issues}"
        # the worker side of "clean audits both sides": SIGTERM makes
        # it audit its runners and exit 0 (clean) or 3 (violation)
        child.terminate()
        rc = child.wait(timeout=30)
        if rc != 0:
            return f"decode worker audit exited {rc}"
        print("fleet-smoke: cross-host handoff token-identical, "
              "chunks{scope=remote} moved, audits clean both sides OK",
              flush=True)
        return None
    finally:
        if child.poll() is None:
            child.kill()
            child.wait(timeout=10)


def _degrade_leg(srv, port: int, registry_port: int) -> Optional[str]:
    """The gray-failure degrade-and-recover acceptance
    (docs/RESILIENCE.md "Gray failures and overload"): a THIRD worker
    joins with ``fleet.slow_member`` armed (every serve delayed 300 ms,
    self-clearing after a bounded ``times=``). The host must DEMOTE it
    on its own shipped latency telemetry — visible in the
    ``/server/stats`` health block — concurrent HTTP traffic must stay
    within 2× the healthy-fleet p99 baseline while the slow member is
    routed around (vs unbounded if it kept taking traffic), and once
    the delay exhausts and the windowed evidence decays the member must
    return to healthy routing. Returns a violation string or None."""
    slow_id = "smoke-w3"
    delay_fires = 16

    def lat_of(n):
        """Client-observed wall times of n serial HTTP /generate calls
        (the 'concurrent traffic' the acceptance bounds)."""
        out = []
        for _ in range(n):
            t = time.monotonic()
            _http_json("POST", f"http://127.0.0.1:{port}/generate",
                       {"prompt": _PROMPT, "max_tokens": 8,
                        "temperature": 0.0})
            out.append(time.monotonic() - t)
        return out

    def slow_state():
        stats = _http_json("GET",
                           f"http://127.0.0.1:{port}/server/stats")
        engines = (stats.get("health") or {}).get("engines", {})
        return engines.get(f"{slow_id}:engine-0", {}).get("state")

    # healthy-fleet baseline BEFORE the slow member exists
    baseline = sorted(lat_of(6))
    base_p99 = baseline[-1]
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker",
         "--connect", f"127.0.0.1:{registry_port}",
         "--member-id", slow_id,
         "--fault-spec",
         f"fleet.slow_member:prob=1.0,delay_ms=300,times={delay_fires}"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    try:
        deadline = time.monotonic() + 240.0
        slow = None
        while time.monotonic() < deadline:
            slow = next(
                (r for r in srv.scheduler.engines()
                 if getattr(r, "is_remote", False) and r.is_healthy()
                 and r.engine_id.startswith(slow_id + ":")), None)
            if slow is not None:
                break
            if child.poll() is not None:
                return "slow worker died before joining"
            time.sleep(0.1)
        if slow is None:
            return "slow worker never joined the registry"

        # evidence: the slow member serves (delayed) requests so its
        # shipped TTFT digest carries the slowness; local traffic keeps
        # the host's own digest warm for the median comparison
        fires = 0
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline and slow_state() != "degraded":
            req, sink = _request(f"smoke-slow-{fires}")
            slow.submit([req])
            sink.ev.wait(30.0)
            fires += 1
            lat_of(1)
        if slow_state() != "degraded":
            stats = _http_json("GET",
                               f"http://127.0.0.1:{port}/server/stats")
            return ("slow member never demoted; health block = "
                    f"{stats.get('health')}")
        print(f"fleet-smoke: slow member demoted to degraded after "
              f"{fires} slow serves (visible in /server/stats) OK",
              flush=True)

        # concurrent traffic routes AROUND the degraded member: p99
        # stays within 2x the healthy baseline (a round through the
        # 300 ms-delayed member would blow it)
        degraded = sorted(lat_of(6))
        if degraded[-1] > 2.0 * max(base_p99, 0.05):
            return (f"p99 under a degraded member {degraded[-1]:.3f}s "
                    f"> 2x healthy baseline {base_p99:.3f}s — traffic "
                    "was not routed around it")
        print(f"fleet-smoke: degraded-fleet p99 {degraded[-1]:.3f}s "
              f"within 2x baseline {base_p99:.3f}s OK", flush=True)

        # recovery: burn the remaining delay fires (the fault is
        # self-clearing), then fast serves + window decay promote the
        # member back to healthy routing
        deadline = time.monotonic() + 90.0
        i = 0
        while time.monotonic() < deadline and slow_state() != "healthy":
            req, sink = _request(f"smoke-recov-{i}")
            slow.submit([req])
            sink.ev.wait(30.0)
            i += 1
            lat_of(1)
        if slow_state() != "healthy":
            stats = _http_json("GET",
                               f"http://127.0.0.1:{port}/server/stats")
            return ("slow member never recovered after the fault "
                    f"cleared; health block = {stats.get('health')}")
        print("fleet-smoke: member recovered to healthy routing after "
              "the fault cleared OK", flush=True)
        child.terminate()
        rc = child.wait(timeout=30)
        if rc != 0:
            return f"slow worker audit exited {rc}"
        return None
    finally:
        if child.poll() is None:
            child.kill()
            child.wait(timeout=10)


def run_registry(fleet_port: int, registries: str, http_port: int) -> int:
    """Child process: a FULL registry — its own engine, a fleet
    listener on ``fleet_port``, the HA lease election over the
    ``registries`` list, and its own HTTP front door (multi-ingress).
    The HA leg SIGKILLs this process while it holds the lease, then
    reboots it to watch it rejoin fenced."""
    _env_setup()
    from distributed_inference_server_tpu.serving.fleet import FleetSettings

    regs = tuple(r.strip() for r in registries.split(",") if r.strip())
    srv = _build_server(FleetSettings(
        enabled=True, port=fleet_port, registries=regs,
        heartbeat_interval_s=0.2, suspect_after_s=1.0, dead_after_s=2.0,
        lease_s=1.2, lease_suspect_s=0.6,
    ))
    _start_http(srv, port=http_port)
    print(f"fleet-smoke registry: fleet :{fleet_port} http :{http_port}",
          flush=True)
    while True:  # serve until the parent kills us
        time.sleep(1.0)


def _reg_stats(http_port: int) -> Optional[dict]:
    """A registry child's /server/stats ``registry`` block, or None
    while its HTTP surface is still booting."""
    try:
        return _http_json(
            "GET", f"http://127.0.0.1:{http_port}/server/stats",
            timeout=5.0)["fleet"]["registry"]
    except Exception:  # noqa: BLE001 — child still booting
        return None


def _ha_leg() -> Optional[str]:
    """The registry-HA acceptance (docs/FLEET.md "Registry HA", step 2c
    of the module docstring), on its OWN three-process fleet: a primary
    registry child at registries[0], THIS process as the warm standby
    at registries[1], and one worker dual-heartbeating both. Asserts:
    the child wins the boot election (list order); SIGKILLing it
    promotes the standby within ITS lease window with a
    ``lease_expired`` takeover and a higher epoch; a ``/generate``
    through the standby's own front door — with its local engine
    unregistered, so the request MUST ride the already-warm remote
    proxy — is token-identical to the dead primary's pre-kill
    reference; and the rebooted old primary rejoins FENCED (standby, at
    the learned epoch) while the new primary keeps the lease. Returns a
    violation string or None."""
    from distributed_inference_server_tpu.serving.fleet import FleetSettings

    port_a, port_b = _free_port(), _free_port()
    http_a = _free_port()
    regs = (f"127.0.0.1:{port_a}", f"127.0.0.1:{port_b}")

    def _spawn_registry():
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--registry",
             "--fleet-port", str(port_a),
             "--registries", ",".join(regs),
             "--http-port", str(http_a)],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )

    # the child boots FIRST and must already hold the lease before the
    # standby exists: the leg's election claim is about list order, not
    # about who booted first
    child = _spawn_registry()
    srv = None
    worker = None
    try:
        deadline = time.monotonic() + 240.0
        while time.monotonic() < deadline:
            reg = _reg_stats(http_a)
            if reg is not None and reg["role"] == "primary":
                break
            if child.poll() is not None:
                return "primary registry child died before electing"
            time.sleep(0.2)
        else:
            return "registry child never won the boot election"

        # the standby: lease_s=3.0 keeps its boot grace longer than the
        # child's worst-case peer-redial backoff (2s), so the standby
        # never transiently self-promotes while joining a live primary
        srv = _build_server(FleetSettings(
            enabled=True, port=port_b, registries=regs,
            heartbeat_interval_s=0.2, suspect_after_s=1.0,
            dead_after_s=2.0, lease_s=3.0, lease_suspect_s=1.0,
        ))
        worker = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             "--registries", ",".join(regs), "--member-id", "ha-w1"],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )

        # warm standby: BOTH registries must hold the member before the
        # kill — the child as lease holder, the standby via its own
        # dual-heartbeat wire
        deadline = time.monotonic() + 240.0
        proxy = None
        while time.monotonic() < deadline:
            proxy = next((r for r in srv.scheduler.engines()
                          if getattr(r, "is_remote", False)
                          and r.is_healthy()), None)
            lease = srv.fleet_ha.stats()["lease"]
            if proxy is not None and lease["holder"] == regs[0]:
                break
            if worker.poll() is not None:
                return "HA worker died before joining"
            time.sleep(0.2)
        if proxy is None:
            return "the standby never materialized a warm member proxy"
        if srv.fleet_ha.is_primary():
            return "the standby won an election over a live registries[0]"
        epoch_before = srv.fleet_ha.epoch
        takeovers_before = dict(srv.fleet_ha.stats()["takeovers"])

        # reference through the PRIMARY's front door, pre-kill
        ref = _http_json(
            "POST", f"http://127.0.0.1:{http_a}/generate",
            {"prompt": _PROMPT, "max_tokens": 24, "temperature": 0.0})
        ref_text = ref.get("choices", [{}])[0].get("text", "")
        if not ref_text:
            return f"primary /generate returned no text: {ref}"

        # SIGKILL the lease holder; the standby must take over within
        # its OWN lease window (plus scheduler slack)
        os.kill(child.pid, signal.SIGKILL)
        child.wait(timeout=10)
        t_kill = time.monotonic()
        lease_s = srv.fleet_ha.settings.lease_s
        while (time.monotonic() - t_kill < lease_s + 5.0
               and not srv.fleet_ha.is_primary()):
            time.sleep(0.05)
        took = time.monotonic() - t_kill
        if not srv.fleet_ha.is_primary():
            return (f"standby never took over ({took:.1f}s > lease "
                    f"{lease_s}s + slack): {srv.fleet_ha.stats()}")
        st = srv.fleet_ha.stats()
        if (st["takeovers"].get("lease_expired", 0)
                <= takeovers_before.get("lease_expired", 0)):
            return f"takeover not recorded as lease_expired: {st}"
        if st["epoch"] <= epoch_before:
            return (f"promotion did not advance the epoch: "
                    f"{epoch_before} -> {st['epoch']}")
        print(f"fleet-smoke: standby promoted in {took:.2f}s "
              f"(lease {lease_s}s, epoch {st['epoch']}) OK", flush=True)

        # multi-ingress through the NEW primary's own front door; its
        # local engine is unregistered so the request MUST ride the
        # warm remote proxy it learned while still a standby
        _loop, _runner, http_b = _start_http(srv)
        local = next(r for r in srv.scheduler.engines()
                     if not getattr(r, "is_remote", False))
        srv.scheduler.unregister(local.engine_id)
        try:
            resp = _http_json(
                "POST", f"http://127.0.0.1:{http_b}/generate",
                {"prompt": _PROMPT, "max_tokens": 24, "temperature": 0.0})
        finally:
            srv.scheduler.register(local)
        text = resp.get("choices", [{}])[0].get("text", "")
        if text != ref_text:
            return (f"failover stream diverged over the warm proxy: "
                    f"{text!r} != {ref_text!r}")
        print("fleet-smoke: failover /generate over the warm member "
              "proxy token-identical OK", flush=True)

        # reboot the old primary: it must rejoin FENCED — standby, at
        # the cluster epoch it learns from the new primary's lease
        child = _spawn_registry()
        deadline = time.monotonic() + 240.0
        reg = None
        while time.monotonic() < deadline:
            reg = _reg_stats(http_a)
            if (reg is not None and reg["role"] == "standby"
                    and reg["epoch"] == srv.fleet_ha.epoch):
                break
            if child.poll() is not None:
                return "rebooted old primary died while rejoining"
            time.sleep(0.2)
        else:
            return (f"old primary never rejoined fenced: {reg} vs "
                    f"epoch {srv.fleet_ha.epoch}")
        if not srv.fleet_ha.is_primary():
            return "the new primary lost the lease during the rejoin"
        print(f"fleet-smoke: old primary rejoined fenced (standby, "
              f"epoch {reg['epoch']}) OK", flush=True)
        return None
    finally:
        for c in (child, worker):
            if c is not None and c.poll() is None:
                c.kill()
                c.wait(timeout=10)
        if srv is not None:
            srv.shutdown(drain_timeout_s=5.0)


def _mesh_leg() -> Optional[str]:
    """The KV-mesh acceptance (docs/FLEET.md "KV mesh", step 2d of the
    module docstring), on its OWN three-process fleet: a cache_aware
    registry with mesh introductions on, plus two ``--mesh`` members.
    amesh-1 is warmed; a forced fetch (the ``sched.fetch_decision``
    flag, exactly one routing decision) must then land on the cold
    member — the ids sort before the local ``engine-0`` so the
    cheapest-fetch tie-break is deterministic — making amesh-2 pull the
    chunks DIRECTLY from amesh-1 over the registry-introduced wire.
    Asserts: the stream is token-identical to the warm run, the
    delegated-fetch counter moved, the REGISTRY's own data-channel byte
    counters did NOT move (the broker introduces, it never relays), the
    puller's observed transfer comes back via telemetry as a
    (src=amesh-2, dst=amesh-1) ``kv_wires`` row with bytes, and page
    audits are clean on all three processes. Returns a violation string
    or None."""
    from distributed_inference_server_tpu.engine.engine import SamplingParams
    from distributed_inference_server_tpu.engine.kv_cache import chain_hashes
    from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer
    from distributed_inference_server_tpu.serving import faults
    from distributed_inference_server_tpu.serving.fleet import FleetSettings
    from distributed_inference_server_tpu.serving.runner import ServerRequest
    from distributed_inference_server_tpu.serving.scheduler import (
        prefix_match_depth,
    )

    prompt = "the mesh moves pages between rooms " + _PROMPT
    srv = _build_server(
        FleetSettings(enabled=True, heartbeat_interval_s=0.2,
                      suspect_after_s=1.0, dead_after_s=2.0,
                      mesh_enabled=True),
        strategy="cache_aware",
        engine_kwargs={"native_allocator": False},
    )
    port = srv.fleet_server.bound_port
    children = []
    try:
        for member in ("amesh-1", "amesh-2"):
            children.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--worker",
                 "--connect", f"127.0.0.1:{port}",
                 "--member-id", member, "--mesh"],
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
            ))
        deadline = time.monotonic() + 240.0
        proxies = {}
        while time.monotonic() < deadline and len(proxies) < 2:
            for r in srv.scheduler.engines():
                if getattr(r, "is_remote", False) and r.is_healthy():
                    proxies[r.engine_id.rsplit(":", 1)[0]] = r
            if any(c.poll() is not None for c in children):
                return "a mesh worker died before joining"
            time.sleep(0.1)
        if len(proxies) < 2:
            return "mesh workers never joined the registry"

        # warm amesh-1; its stream is the reference the mesh-fetched
        # run must reproduce byte-for-byte
        ref = _Sink()
        proxies["amesh-1"].submit([ServerRequest(
            "mesh-warm", ByteTokenizer().encode(prompt),
            SamplingParams(max_tokens=24, temperature=0.0), ref)])
        if not ref.ev.wait(120.0) or ref.errors:
            return f"mesh warm run failed: {ref.errors}"

        # fetch-admissibility: amesh-1's digest covers the prompt's
        # chain to the published depth (it rides a heartbeat), its data
        # plane is up, and the registry has introduced the pair. The
        # chain is capped to the digest depth exactly like the
        # scheduler's own hashing — the raw prompt can outrun it.
        toks = ByteTokenizer().encode(prompt)
        deadline = time.monotonic() + 30.0
        ready = False
        while time.monotonic() < deadline and not ready:
            s = proxies["amesh-1"].status()
            ps = max(1, getattr(s, "page_size", 0) or 1)
            hashes = chain_hashes(
                toks, ps,
                max_pages=min(getattr(s, "digest_depth", 0) or 8,
                              (len(toks) - 1) // ps))
            ready = bool(
                hashes and prefix_match_depth(s, hashes) == len(hashes)
                and getattr(s, "data_plane", False)
                and srv.fleet_server.mesh_route("amesh-2", "amesh-1"))
            if not ready:
                time.sleep(0.1)
        if not ready:
            s = proxies["amesh-1"].status()
            return ("mesh pair never became fetch-admissible: "
                    f"depth={prefix_match_depth(s, hashes)}"
                    f"/{len(hashes)} "
                    f"data_plane={getattr(s, 'data_plane', False)} "
                    "introduced="
                    f"{srv.fleet_server.mesh_route('amesh-2', 'amesh-1')}")

        reg_bytes_before = {
            m: (st.get("bytes_sent", 0), st.get("bytes_received", 0))
            for m, st in srv.fleet_server.kv_stats().items()}
        snap = srv.metrics.snapshot().to_dict()
        delegated_before = ((snap.get("cache") or {})
                            .get("peer_fetch") or {}).get("delegated", 0)

        sink = _Sink()
        faults.install(faults.parse_spec("sched.fetch_decision:nth=1", 0))
        try:
            srv.dispatcher.submit(ServerRequest(
                "mesh-fetch", ByteTokenizer().encode(prompt),
                SamplingParams(max_tokens=24, temperature=0.0), sink))
            if not sink.ev.wait(120.0):
                dump_postmortem(srv, "mesh-fetch")
                return "mesh-fetched request never terminated"
        finally:
            faults.clear()
        if sink.errors:
            dump_postmortem(srv, "mesh-fetch")
            return f"mesh-fetched request errored: {sink.errors}"
        if sink.toks != ref.toks:
            dump_postmortem(srv, "mesh-fetch")
            return (f"mesh-fetched stream diverged: "
                    f"{sink.toks} != {ref.toks}")

        snap = srv.metrics.snapshot().to_dict()
        delegated = ((snap.get("cache") or {})
                     .get("peer_fetch") or {}).get("delegated", 0)
        if delegated <= delegated_before:
            dump_postmortem(srv, "mesh-fetch")
            return ("fetch was never delegated to the mesh "
                    "(no fetch hint left the host)")
        print("fleet-smoke: mesh fetch delegated, stream "
              "token-identical OK", flush=True)

        reg_bytes_after = {
            m: (st.get("bytes_sent", 0), st.get("bytes_received", 0))
            for m, st in srv.fleet_server.kv_stats().items()}
        if reg_bytes_after != reg_bytes_before:
            return ("registry data-channel bytes moved during a mesh "
                    f"fetch (broker must not relay): {reg_bytes_before} "
                    f"-> {reg_bytes_after}")

        # the puller's kvwire counters ride heartbeats back: the host's
        # kv_wires table must grow the (amesh-2 <- amesh-1) row
        deadline = time.monotonic() + 20.0
        wire = None
        while time.monotonic() < deadline and wire is None:
            wire = next(
                (r for r in srv.fleet_server.kv_wire_stats()
                 if r["src"] == "amesh-2" and r["dst"] == "amesh-1"
                 and r.get("bytes", 0) > 0), None)
            if wire is None:
                time.sleep(0.2)
        if wire is None:
            return ("kv_wires never learned the amesh-2<-amesh-1 "
                    "transfer (rows: "
                    f"{srv.fleet_server.kv_wire_stats()})")
        rate = wire.get("rate_bytes_per_s")
        print(f"fleet-smoke: registry bytes unmoved, learned wire rate "
              f"{'cold' if rate is None else f'{rate / 1e6:.1f}MB/s'} "
              f"over {wire['bytes']}B OK", flush=True)

        # clean audits all three processes: members audit on SIGTERM
        for c in children:
            c.terminate()
        rcs = [c.wait(timeout=30) for c in children]
        if any(rc != 0 for rc in rcs):
            return f"mesh worker audits exited {rcs}"
        issues = next(r for r in srv.scheduler.engines()
                      if not getattr(r, "is_remote", False)).audit()
        if issues:
            return f"mesh host page audit: {issues}"
        print("fleet-smoke: mesh audits clean on all three processes OK",
              flush=True)
        return None
    finally:
        for c in children:
            if c.poll() is None:
                c.kill()
                c.wait(timeout=10)
        srv.shutdown(drain_timeout_s=5.0)


def run_host() -> int:
    _env_setup()
    from distributed_inference_server_tpu.serving.fleet import FleetSettings
    from distributed_inference_server_tpu.serving.health import (
        HealthSettings,
    )
    t0 = time.monotonic()
    # the host's engine is PREFILL-role: once a decode-role member
    # joins (the handoff leg), every admission migrates cross-host;
    # until then prefill admits unified — the earlier legs see exactly
    # the old behavior. The health scorer runs smoke-paced (fast
    # evaluations, small windows) for the degrade-and-recover leg.
    srv = _build_server(FleetSettings(
        enabled=True, heartbeat_interval_s=0.2, suspect_after_s=1.0,
        dead_after_s=2.0,
    ), engine_roles=["prefill"], health=HealthSettings(
        interval_s=0.25, demote_after=2, recover_after=2,
        min_window_requests=4, latency_ratio=2.5, recover_ratio=1.2,
    ))
    port = srv.fleet_server.bound_port
    print(f"fleet-smoke host: registry on 127.0.0.1:{port}", flush=True)

    # the worker serves its own HTTP surface too: the perf leg fetches
    # its /server/perf digests for the merge-identity acceptance
    worker_http_port = _free_port()
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker",
         "--connect", f"127.0.0.1:{port}",
         "--http-port", str(worker_http_port)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    try:
        # -- join: wait for the member and its healthy proxy ------------
        deadline = time.monotonic() + 240.0
        remote = None
        while time.monotonic() < deadline:
            remote = next((r for r in srv.scheduler.engines()
                           if getattr(r, "is_remote", False)
                           and r.is_healthy()), None)
            if remote is not None:
                break
            if child.poll() is not None:
                return _fail("worker process died before joining")
            time.sleep(0.1)
        if remote is None:
            return _fail("worker never joined the registry")
        print(f"fleet-smoke: member joined as {remote.engine_id} "
              f"({time.monotonic() - t0:.1f}s)", flush=True)

        # -- local reference run ---------------------------------------
        local = next(r for r in srv.scheduler.engines()
                     if not getattr(r, "is_remote", False))
        ref_req, ref = _request("smoke-ref")
        local.submit([ref_req])
        if not ref.ev.wait(120.0) or ref.errors:
            return _fail(f"local reference failed: {ref.errors}")

        # -- 1. remote serving, token-identical ------------------------
        r1_req, r1 = _request("smoke-remote")
        remote.submit([r1_req])
        if not r1.ev.wait(120.0):
            return _fail("remote request never terminated")
        if r1.errors:
            return _fail(f"remote request errored: {r1.errors}")
        if r1.toks != ref.toks or r1.text != ref.text:
            return _fail(
                f"remote stream diverged: {r1.toks} != {ref.toks}")
        print("fleet-smoke: remote serving token-identical OK", flush=True)

        # -- 2. stitched trace + flight recorder over real HTTP ---------
        _loop, _http_runner, http_port = _start_http(srv)
        violation = _trace_leg(srv, http_port)
        if violation is not None:
            return _fail(violation)

        # -- 2.2 performance telemetry: step clock + merge identity -----
        violation = _perf_leg(srv, http_port, worker_http_port)
        if violation is not None:
            return _fail(violation)

        # -- 2.5 cross-host handoff over the KV data plane --------------
        # HTTP reference FIRST, while no decode replica exists anywhere:
        # the prefill engine decodes in place — the baseline the
        # migrated run must match byte-for-byte
        ref_resp = _http_json(
            "POST", f"http://127.0.0.1:{http_port}/generate",
            {"prompt": _PROMPT, "max_tokens": 96, "temperature": 0.0},
        )
        ref_text = ref_resp.get("choices", [{}])[0].get("text", "")
        if not ref_text:
            return _fail(f"HTTP reference returned no text: {ref_resp}")
        violation = _handoff_leg(srv, http_port, port, ref_text)
        if violation is not None:
            return _fail(violation)

        # -- 2.7 gray-failure degrade-and-recover -----------------------
        violation = _degrade_leg(srv, http_port, port)
        if violation is not None:
            return _fail(violation)

        # -- 2.8 registry HA failover (own three-process fleet) ---------
        violation = _ha_leg()
        if violation is not None:
            return _fail(violation)

        # -- 2.9 member<->member KV mesh (own three-process fleet) ------
        violation = _mesh_leg()
        if violation is not None:
            return _fail(violation)

        # -- 3. kill the worker mid-zero-token-request ------------------
        r2_req, r2 = _request("smoke-kill")
        remote.submit([r2_req])
        os.kill(child.pid, signal.SIGKILL)  # mid-request, pre-first-token
        if not r2.ev.wait(120.0):
            dump_postmortem(srv, "smoke-kill")
            return _fail("killed request never terminated")
        if r2.errors:
            dump_postmortem(srv, "smoke-kill")
            return _fail(f"killed request errored (redispatch should be "
                         f"invisible): {r2.errors}")
        if r2.dones != 1:
            dump_postmortem(srv, "smoke-kill")
            return _fail(f"killed request saw {r2.dones} done events")
        if r2.toks != ref.toks:
            dump_postmortem(srv, "smoke-kill")
            return _fail(f"redispatched stream diverged: {r2.toks} != "
                         f"{ref.toks}")
        snap = srv.metrics.snapshot().to_dict()
        redisp = (snap.get("resilience") or {}).get("redispatched", {})
        if redisp.get("ok", 0) < 1:
            return _fail(f"no redispatch recorded: {redisp}")
        print("fleet-smoke: kill -> redispatch token-identical OK",
              flush=True)

        # -- registry convergence + metrics -----------------------------
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            if srv.fleet_registry.member_state(MEMBER_ID) == "dead":
                break
            time.sleep(0.1)
        else:
            return _fail("registry never marked the killed member dead")
        import re

        prom = srv.metrics.prometheus_text().decode()
        m = re.search(r'fleet_members\{state="dead"\} ([0-9.]+)', prom)
        # >= 1: the SIGKILLed worker (the terminated decode worker of
        # the handoff leg may count too, depending on prune timing)
        if m is None or float(m.group(1)) < 1:
            return _fail("fleet_members{state=dead} gauge does not "
                         "reflect the loss")
        stats = srv._fleet_stats()
        if stats["member_counts"]["dead"] < 1:
            return _fail(f"/server/stats fleet block wrong: {stats}")

        # -- page audit --------------------------------------------------
        issues = local.audit()
        if issues:
            return _fail(f"page audit: {issues}")
        print(f"fleet-smoke clean in {time.monotonic() - t0:.1f}s",
              flush=True)
        return 0
    finally:
        if child.poll() is None:
            child.kill()
        child.wait(timeout=10)
        srv.shutdown(drain_timeout_s=5.0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--worker", action="store_true",
                    help="run as the joining worker process")
    ap.add_argument("--connect", default="",
                    help="registry host:port (worker mode)")
    ap.add_argument("--role", default="",
                    help="worker engine role ('' = unified; 'decode' "
                    "makes it a cross-host handoff target)")
    ap.add_argument("--member-id", default=MEMBER_ID,
                    help="worker member identity")
    ap.add_argument("--http-port", type=int, default=0,
                    help="worker mode: serve the member's HTTP surface "
                    "on this port (0 = none; the perf leg fetches its "
                    "/server/perf)")
    ap.add_argument("--fault-spec", default="",
                    help="worker mode: arm this fault spec in the "
                    "worker process (the degrade-and-recover leg's "
                    "fleet.slow_member delay)")
    ap.add_argument("--mesh", action="store_true",
                    help="worker mode: join the member<->member KV "
                    "mesh (honor KvIntro frames, pull fetch hints "
                    "directly from peer members)")
    ap.add_argument("--registry", action="store_true",
                    help="run as an HA registry child (the HA leg's "
                    "killable primary)")
    ap.add_argument("--fleet-port", type=int, default=0,
                    help="registry mode: bind the fleet listener here")
    ap.add_argument("--registries", default="",
                    help="comma-separated fleet.registries list "
                    "(registry mode: the election peers; worker mode: "
                    "dual-heartbeat every one of them)")
    args = ap.parse_args()
    if args.registry:
        return run_registry(args.fleet_port, args.registries,
                            args.http_port)
    if args.worker:
        return run_worker(args.connect, role=args.role,
                          member_id=args.member_id,
                          http_port=args.http_port,
                          fault_spec=args.fault_spec,
                          mesh=args.mesh,
                          registries=args.registries)
    return run_host()


if __name__ == "__main__":
    sys.exit(main())
