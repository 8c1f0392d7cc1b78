"""HTTP transport: aiohttp application over the inference handler.

Realizes the reference's spec'd ``ApiServer`` (``design.md:139-145`` [spec];
endpoints ``requirements.md:32-38,118-119``):

- POST ``/generate`` ``/chat`` — JSON, or SSE when ``stream: true``
  (Req 1.6); client disconnect mid-stream aborts generation (Req 5.4);
- POST ``/embeddings``;
- GET ``/server/stats`` — ``MetricsSnapshot`` JSON;
- GET ``/metrics`` — Prometheus text;
- GET ``/health`` — liveness + per-engine health;
- errors → ``ErrorResponse`` JSON with the reference's status mapping
  (400/503/408/500, error.rs:39-56 semantics via core.errors.ApiError).

The axum/tower stack maps to aiohttp; SSE framing is hand-rolled (the wire
format is just ``data: {json}\\n\\n`` frames, streamer.sse_encode).
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Optional

from aiohttp import web

from distributed_inference_server_tpu.core.errors import ApiError
from distributed_inference_server_tpu.core.models import ErrorResponse
from distributed_inference_server_tpu.serving.handler import InferenceHandler
from distributed_inference_server_tpu.serving.metrics import MetricsCollector
from distributed_inference_server_tpu.serving.streamer import SSE_DONE, sse_encode


def _error_response(err: ApiError) -> web.Response:
    body = ErrorResponse.of(str(err), err.error_type(), err.code())
    headers = None
    retry_after = getattr(err, "retry_after_s", None)
    if retry_after is not None:
        # deadline-aware admission shed (serving/health.py): the
        # standard backoff hint rides the 503 so well-behaved clients
        # retry after the backlog drains instead of hammering it
        headers = {"Retry-After": str(int(max(1, round(retry_after))))}
    return web.json_response(
        body.to_dict(), status=err.status_code(), dumps=json.dumps,
        headers=headers,
    )


def build_app(
    handler: InferenceHandler,
    metrics: Optional[MetricsCollector] = None,
    swap_fn=None,
    scale_fn=None,
    fleet_fn=None,
    perf_fn=None,
    health_fn=None,
) -> web.Application:
    """``swap_fn(model_name) -> (ok, error)`` enables the admin model-swap
    endpoint (Req 13.1: admin-API-triggered); ``scale_fn(n) -> (ok,
    error)`` enables the admin replica-scaling endpoint (runtime scale
    up/down, requirements.md:110). Both are blocking — they run in the
    default executor. ``fleet_fn() -> dict`` adds the fleet control-plane
    block (members, role map, rebalance history; serving/fleet.py) to
    ``/server/stats``. ``perf_fn() -> dict`` serves ``GET /server/perf``
    (per-engine step clock, windowed percentiles, SLO burn, and the
    fleet-merged digest view; docs/OBSERVABILITY.md). ``health_fn() ->
    dict`` adds the gray-failure ``health`` block (per-engine scored
    state, breaker states, retry budget, admission estimator;
    serving/health.py) to ``/server/stats``."""
    app = web.Application()
    app["handler"] = handler
    app["metrics"] = metrics

    @web.middleware
    async def observe(request: web.Request, handler):  # noqa: A002 — aiohttp
        # requires the parameter name "handler" (shadows the InferenceHandler)
        t0 = time.monotonic()
        code = 500
        try:
            resp = await handler(request)
            code = resp.status
            return resp
        except ApiError as e:
            resp = _error_response(e)
            code = resp.status
            return resp
        finally:
            if metrics and request.method == "POST":
                metrics.record_request(request.path, code, time.monotonic() - t0)

    app.middlewares.append(observe)

    class ApiErrorJson(ApiError):
        def __init__(self, msg: str):
            super().__init__(f"Validation error: {msg}")

        def status_code(self) -> int:
            return 400

        def error_type(self) -> str:
            return "invalid_request_error"

        def code(self) -> str:
            return "invalid_json"

    async def _json_body(request: web.Request) -> dict:
        try:
            obj = await request.json()
        except Exception:  # noqa: BLE001 — malformed body
            raise ApiErrorJson("request body is not valid JSON") from None
        if not isinstance(obj, dict):
            raise ApiErrorJson("request body must be a JSON object")
        return obj

    async def _stream_response(request: web.Request, request_id, events,
                               encode=sse_encode):
        """One SSE scaffold for every stream (native TokenEvent frames
        and the /v1 OpenAI-chunk encoding differ only in ``encode``) —
        the Req 5.4 abort-on-disconnect logic exists exactly once.
        ``request_id`` may be a single id or the list of fanned-out ids
        (/v1 with n > 1): every live sequence is aborted on disconnect."""
        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "Connection": "keep-alive",
            },
        )
        consuming = False
        try:
            await resp.prepare(request)
            consuming = True  # past here the generator is entered, and a
            # cancellation lands inside its frame — its finally then owns
            # the per-request metrics/span bookkeeping
            async for event in events:
                await resp.write(encode(event))
            await resp.write(SSE_DONE)
        except (ConnectionResetError, asyncio.CancelledError):
            # client went away: abort generation (Req 5.4)
            rids = (request_id if isinstance(request_id, (list, tuple))
                    else (request_id,))
            if consuming:
                for rid in rids:
                    handler.dispatcher.abort(rid)
            else:
                # disconnect during prepare: the generator never started,
                # its finally will never run, and abort drops requests
                # with no sink callback — so do the abort AND the
                # bookkeeping the stream's finally would have done
                handler.release_unstarted(rids)
            raise
        await resp.write_eof()
        return resp

    async def _serve_completion(request, *, chat: bool, v1: bool):
        """Shared stream-or-JSON dispatch for /generate, /chat and their
        /v1 aliases — one copy of the negotiation, with the OpenAI field
        translation and wire mapping applied only on the v1 paths."""
        obj = await _json_body(request)
        if v1:
            obj, opts = _openai_fields(obj, chat=chat)
            if obj.get("stream") is True:
                rids, events = await handler.stream_many(
                    obj, chat=chat, n=opts.n
                )
                return await _stream_response_v1(
                    request, rids, events, chat=chat, opts=opts
                )
            rid, choices, usage = await handler.complete_many(
                obj, chat=chat, n=opts.n
            )
            return web.json_response(
                _v1_response(rid, choices, usage, chat=chat, opts=opts)
            )
        stream_fn = handler.chat_stream if chat else handler.generate_stream
        call_fn = handler.chat if chat else handler.generate
        if obj.get("stream") is True:
            request_id, events = await stream_fn(obj)
            return await _stream_response(request, request_id, events)
        result = await call_fn(obj)
        return web.json_response(result.to_dict())

    async def generate(request: web.Request) -> web.StreamResponse:
        return await _serve_completion(request, chat=False, v1=False)

    async def chat(request: web.Request) -> web.StreamResponse:
        return await _serve_completion(request, chat=True, v1=False)

    async def embeddings(request: web.Request) -> web.Response:
        obj = await _json_body(request)
        result = await handler.embeddings(obj)
        return web.json_response(result.to_dict())

    # -- OpenAI-compatible aliases -----------------------------------------
    # The non-stream response envelopes already follow the OpenAI shapes
    # (Req 11). The /v1/* aliases close the remaining wire gaps so
    # off-the-shelf OpenAI clients work: the "stop" request field,
    # finish_reason vocabulary ("stop_sequence" is not OpenAI's), and
    # streaming as text_completion / chat.completion.chunk objects with
    # choices[].text / choices[].delta instead of internal TokenEvents.

    class _V1Opts:
        """Parsed OpenAI-only request options (everything the native
        schema doesn't carry)."""

        __slots__ = ("n", "include_usage", "logprobs")

        def __init__(self, n=1, include_usage=False, logprobs=False):
            self.n = n
            self.include_usage = include_usage
            self.logprobs = logprobs

    # fan-out bound: each choice is a full engine sequence admitted
    # through the same queue, so one request must not be able to claim
    # an unbounded slice of capacity (OpenAI itself caps n at 128)
    _MAX_N = 16

    def _openai_fields(obj: dict, *, chat: bool):
        """Translate/validate the OpenAI request spellings. Returns
        ``(obj, _V1Opts)``. Shape-changing fields we do not implement
        (echo, best_of>n, top-alternative logprobs, suffix) are rejected
        with a clear 400 — a silently wrong response shape is worse than
        an honest error."""
        # _json_body already 400s on non-dict bodies
        n = obj.get("n")
        if n is None:
            n = 1
        elif type(n) is not int or not 1 <= n <= _MAX_N:
            # bool is not an int here: n=true must not pass as 1
            raise ApiErrorJson(
                f'"n" must be an integer in [1, {_MAX_N}]'
            )
        opts = _V1Opts(n=n)

        so = obj.get("stream_options")
        if so is not None:
            if obj.get("stream") is not True:
                raise ApiErrorJson(
                    '"stream_options" requires "stream": true'
                )
            if not isinstance(so, dict):
                raise ApiErrorJson('"stream_options" must be an object')
            iu = so.get("include_usage", False)
            if not isinstance(iu, bool):
                raise ApiErrorJson(
                    '"stream_options.include_usage" must be a boolean'
                )
            opts.include_usage = iu

        lp = obj.get("logprobs")
        if chat:
            if lp is not None and not isinstance(lp, bool):
                raise ApiErrorJson('"logprobs" must be a boolean')
            opts.logprobs = bool(lp)
            tlp = obj.get("top_logprobs")
            if tlp is not None:
                if type(tlp) is not int or not 0 <= tlp <= 20:
                    raise ApiErrorJson(
                        '"top_logprobs" must be an integer in [0, 20]'
                    )
                if not opts.logprobs:
                    raise ApiErrorJson(
                        '"logprobs" must be true when "top_logprobs" '
                        "is used"
                    )
                if tlp > 0:
                    raise ApiErrorJson(
                        '"top_logprobs" > 0 (alternative-token logprobs) '
                        "is not supported; use 0 for sampled-token "
                        "logprobs"
                    )
        else:
            # completions spelling: logprobs is an int — the number of
            # TOP-ALTERNATIVE tokens to return per position. 0 = just the
            # sampled token's logprob (supported); >0 needs per-step
            # top-k alternatives we don't surface.
            if lp is not None:
                if type(lp) is not int or lp < 0:
                    raise ApiErrorJson(
                        '"logprobs" must be a non-negative integer'
                    )
                if lp > 0:
                    raise ApiErrorJson(
                        '"logprobs" > 0 (alternative-token logprobs) is '
                        "not supported; use 0 for sampled-token logprobs"
                    )
                opts.logprobs = True
            if obj.get("echo"):
                raise ApiErrorJson(
                    '"echo" is not supported (the response would have to '
                    "prepend the prompt)"
                )
            if obj.get("suffix") is not None:
                raise ApiErrorJson('"suffix" is not supported')
            bo = obj.get("best_of")
            if bo is not None and (type(bo) is not int or bo != n):
                # best_of == n degenerates to "return all n"; more means
                # server-side reranking we don't do, fewer than n is
                # self-contradictory (OpenAI 400s best_of < n too)
                raise ApiErrorJson(
                    f'"best_of" must equal n (= {n}); server-side '
                    "candidate reranking is not supported"
                )

        # the SDKs' recommended replacement for the deprecated max_tokens
        if "max_completion_tokens" in obj and "max_tokens" not in obj:
            obj["max_tokens"] = obj.pop("max_completion_tokens")
        if "stop" in obj and "stop_sequences" not in obj:
            stop = obj.pop("stop")
            if stop is None:
                stop = []
            elif isinstance(stop, str):
                stop = [stop]
            if not (isinstance(stop, list)
                    and all(isinstance(s, str) for s in stop)):
                # name the field the CLIENT sent, not our internal one
                raise ApiErrorJson('"stop" must be a string or an array '
                                   "of strings")
            if any(s == "" for s in stop):
                # OpenAI rejects empty stop strings; ours would match at
                # position 0 and instantly truncate to an empty output
                raise ApiErrorJson('"stop" strings must be non-empty')
            obj["stop_sequences"] = stop
        return obj, opts

    def _v1_finish(reason) -> Optional[str]:
        fr = getattr(reason, "value", reason)
        return "stop" if fr == "stop_sequence" else fr

    def _lp_completions(token_texts, logprobs) -> dict:
        """OpenAI completions logprobs object (sampled token only).
        text_offset is the cumulative character offset of each token's
        isolated decode within the generated text; tokens held back by
        incremental detok decode to U+FFFD fragments in isolation, same
        as OpenAI's own byte-fragment rendering."""
        offsets, pos = [], 0
        for t in token_texts:
            offsets.append(pos)
            pos += len(t)
        return {
            "tokens": token_texts,
            "token_logprobs": logprobs,
            "top_logprobs": None,
            "text_offset": offsets,
        }

    def _lp_chat(token_texts, logprobs) -> dict:
        """OpenAI chat logprobs object: content[] of per-token entries.
        top_logprobs is always [] — alternative-token logprobs are
        rejected at request parse (top_logprobs > 0). Entries without a
        logprob are dropped rather than emitted with null: the OpenAI
        schema requires a float (a held-back-text flush carries no
        logprob of its own — its tokens' logprobs already streamed)."""
        return {
            "content": [
                {
                    "token": t,
                    "logprob": lp,
                    "bytes": list(t.encode("utf-8")),
                    "top_logprobs": [],
                }
                for t, lp in zip(token_texts, logprobs)
                if lp is not None
            ]
        }

    def _v1_response(request_id, choices, usage, *, chat: bool,
                     opts) -> dict:
        """Non-streaming OpenAI response envelope from the handler's
        fan-out results (one entry per choice, indices 0..n-1)."""
        out = []
        for i, c in enumerate(choices):
            lp_obj = None
            if opts.logprobs:
                texts = [handler.tok.decode_token(t)
                         for t in c["token_ids"]]
                lp_obj = (
                    _lp_chat(texts, c["token_logprobs"]) if chat
                    else _lp_completions(texts, c["token_logprobs"])
                )
            if chat:
                out.append({
                    "index": i,
                    "message": {"role": "assistant",
                                "content": c["text"]},
                    "logprobs": lp_obj,
                    "finish_reason": _v1_finish(c["finish_reason"]),
                })
            else:
                out.append({
                    "text": c["text"],
                    "index": i,
                    "logprobs": lp_obj,
                    "finish_reason": _v1_finish(c["finish_reason"]),
                })
        return {
            "id": ("chatcmpl-" if chat else "cmpl-") + str(request_id),
            "object": "chat.completion" if chat else "text_completion",
            "created": int(time.time()),
            "model": handler.model_name,
            "choices": out,
            "usage": usage.to_dict(),
        }

    async def _stream_response_v1(request, request_ids, events, *,
                                  chat: bool, opts):
        """OpenAI chunk encoding over the merged (choice_index, event)
        stream. Per-choice state: the role appears only in a choice's
        first delta; each choice gets its own finish chunk. With
        stream_options.include_usage every chunk carries "usage": null
        and one final usage-only chunk (empty choices) precedes [DONE]."""
        obj_name = "chat.completion.chunk" if chat else "text_completion"
        rid = ("chatcmpl-" if chat else "cmpl-") + str(request_ids[0])
        created = int(time.time())
        model = handler.model_name
        n = len(request_ids)

        def frame(payload: dict) -> bytes:
            if opts.include_usage and "usage" not in payload:
                payload["usage"] = None
            return b"data: " + json.dumps(payload).encode() + b"\n\n"

        def envelope(choice: dict, usage=None) -> bytes:
            payload = {"id": rid, "object": obj_name, "created": created,
                       "model": model, "choices": [choice]}
            if usage is not None:
                payload["usage"] = usage
            return frame(payload)

        first = [True] * n  # role only in each choice's 1st delta
        offset = [0] * n  # per-choice char offset for completions logprobs
        observed = [0] * n  # sampled tokens seen per choice (usage
        # fallback for choices that error mid-generation: their done
        # event — the authoritative usage carrier — never arrives)
        prompt_tokens = [0]
        completion_tokens = [0]
        remaining = [n]

        def chunk(pair) -> bytes:
            idx, ev = pair
            if ev.type == "token":
                text = ev.token or ""
                if ev.logprob is not None:
                    # real sampled token (flushes carry no logprob)
                    observed[idx] += 1
                lp_obj = None
                if opts.logprobs:
                    # a held-back-text flush (no logprob of its own) gets
                    # a null logprobs object, matching the non-stream
                    # path which records sampled tokens only; its text
                    # still advances the completions offset so offsets
                    # keep matching the emitted text
                    if chat:
                        lp_obj = (
                            _lp_chat([text], [ev.logprob])
                            if ev.logprob is not None else None
                        )
                    else:
                        if ev.logprob is not None:
                            lp_obj = _lp_completions([text], [ev.logprob])
                            lp_obj["text_offset"] = [offset[idx]]
                        offset[idx] += len(text)
                if chat:
                    delta = {"content": text}
                    if first[idx]:
                        delta = {"role": "assistant", **delta}
                        first[idx] = False
                    choice = {"index": idx, "delta": delta,
                              "logprobs": lp_obj, "finish_reason": None}
                else:
                    choice = {"text": text, "index": idx,
                              "logprobs": lp_obj, "finish_reason": None}
                return envelope(choice)
            if ev.type == "done":
                fr = _v1_finish(ev.finish_reason)
                if ev.usage is not None:
                    prompt_tokens[0] = max(prompt_tokens[0],
                                           ev.usage.prompt_tokens)
                    completion_tokens[0] += ev.usage.completion_tokens
                choice = (
                    {"index": idx, "delta": {}, "logprobs": None,
                     "finish_reason": fr}
                    if chat else
                    {"text": "", "index": idx, "logprobs": None,
                     "finish_reason": fr}
                )
                return envelope(choice) + _maybe_usage_chunk()
            # error: no OpenAI stream-error standard; error object with
            # the choice index so n>1 clients can attribute it (the
            # stream keeps going for the surviving choices). An error
            # TERMINATES its choice (the sink closes after it), so it
            # counts toward stream completion like a done event —
            # otherwise the include_usage final chunk would never fire
            # when any choice errors.
            completion_tokens[0] += observed[idx]
            return frame({"error": {
                "message": ev.messages or "",
                "code": ev.code or "server_error",
                "index": idx,
            }}) + _maybe_usage_chunk()

        def _maybe_usage_chunk() -> bytes:
            """Decrement the live-choice count; on the LAST terminal
            event (done or error), emit the usage-only final chunk when
            stream_options.include_usage asked for it (OpenAI: empty
            choices array, preceding [DONE])."""
            remaining[0] -= 1
            if remaining[0] != 0 or not opts.include_usage:
                return b""
            total = prompt_tokens[0] + completion_tokens[0]
            return frame({
                "id": rid, "object": obj_name,
                "created": created, "model": model,
                "choices": [],
                "usage": {
                    "prompt_tokens": prompt_tokens[0],
                    "completion_tokens": completion_tokens[0],
                    "total_tokens": total,
                },
            })

        return await _stream_response(
            request, request_ids, events, encode=chunk
        )

    async def generate_v1(request: web.Request) -> web.StreamResponse:
        return await _serve_completion(request, chat=False, v1=True)

    async def chat_v1(request: web.Request) -> web.StreamResponse:
        return await _serve_completion(request, chat=True, v1=True)

    async def stats(request: web.Request) -> web.Response:
        statuses = tuple(handler.dispatcher.scheduler.statuses())
        if metrics is None:
            out = {"worker_statuses": [s.to_dict() for s in statuses]}
        else:
            out = metrics.snapshot(statuses).to_dict()
        if fleet_fn is not None:
            out["fleet"] = fleet_fn()
        if health_fn is not None:
            # gray-failure block (serving/health.py): scored per-engine
            # states, data-channel breaker states, the shared retry
            # budget, and the admission estimator
            out["health"] = health_fn()
        recorder = getattr(handler, "recorder", None)
        tracer = getattr(handler, "tracer", None)
        if recorder is not None or tracer is not None:
            blk = out.setdefault("tracing", {})
            if tracer is not None:
                # the tracer's own view (includes drops before metrics
                # wiring); the metrics mirror is spans_dropped above
                blk["tracer_dropped"] = tracer.dropped()
            if recorder is not None:
                blk["flight_recorder"] = recorder.stats()
        return web.json_response(out)

    async def prom(request: web.Request) -> web.Response:
        if metrics is None:
            return web.Response(status=404, text="metrics disabled")
        return web.Response(
            body=metrics.prometheus_text(),
            content_type="text/plain",
            charset="utf-8",
        )

    async def health(request: web.Request) -> web.Response:
        """Liveness plus the device facts an outside checker cannot get
        without touching jax itself (chip_smoke.py, whose process must
        leave the chip to this one): the backend's platform, device kind
        and count; per LOCAL engine the devices it holds and the
        attention kernels it resolved to; whether the native C++ tier is
        loaded; the compile-cache directory."""
        import jax

        from distributed_inference_server_tpu import native

        scheduler = handler.dispatcher.scheduler
        statuses = scheduler.statuses()
        healthy = any(s.healthy for s in statuses)
        # remote (fleet) runners have no placement(): their devices are
        # another process's to report
        placements = {
            r.engine_id: r.placement() for r in scheduler.engines()
            if hasattr(r, "placement")
        }
        devices = jax.devices()
        return web.json_response(
            {
                "status": "ok" if healthy else "unhealthy",
                "accepting": handler.dispatcher.is_accepting(),
                "platform": devices[0].platform,
                "device_kind": devices[0].device_kind,
                "device_count": len(devices),
                "native_tier": native.loaded(),
                "compile_cache_dir": jax.config.jax_compilation_cache_dir,
                "engines": [
                    {**s.to_dict(), **placements.get(s.engine_id, {})}
                    for s in statuses
                ],
            },
            status=200 if healthy else 503,
        )

    async def model_swap(request: web.Request) -> web.Response:
        if swap_fn is None:
            return web.json_response(
                {"error": {"message": "model swap not configured",
                           "error_type": "invalid_request_error",
                           "code": "swap_unavailable"}},
                status=501,
            )
        obj = await _json_body(request)
        name = obj.get("model")
        if not isinstance(name, str) or not name:
            return web.json_response(
                {"error": {"message": "body must contain 'model'",
                           "error_type": "invalid_request_error",
                           "code": "invalid_body"}},
                status=400,
            )
        loop = asyncio.get_running_loop()
        ok, err = await loop.run_in_executor(None, swap_fn, name)
        if not ok:
            return web.json_response(
                {"error": {"message": err, "error_type": "server_error",
                           "code": "swap_failed"}},
                status=500,
            )
        return web.json_response({"status": "ok", "model": name})

    _TRACE_N_MAX = 10_000

    async def trace(request: web.Request) -> web.Response:
        """Finished spans from the in-memory ring, sorted by start time.
        Filters: ``trace_id=`` (one stitched trace — remote members'
        spans included once their FleetSpans frames merged) and
        ``request_id=`` (every span carrying that request_id
        attribute). ``n`` is validated: an integer in [1, 10000]."""
        tracer = getattr(handler, "tracer", None)
        if tracer is None:
            return web.json_response({"spans": []})
        try:
            n = int(request.query.get("n", "100"))
            if not 1 <= n <= _TRACE_N_MAX:
                raise ValueError
        except ValueError:
            return web.json_response(
                {"error": {"message": "query parameter 'n' must be an "
                           f"integer in [1, {_TRACE_N_MAX}]",
                           "error_type": "invalid_request_error",
                           "code": "invalid_parameter"}},
                status=400,
            )
        trace_id = request.query.get("trace_id")
        request_id = request.query.get("request_id")
        spans = tracer.recent(n, trace_id=trace_id, request_id=request_id)
        return web.json_response(
            {"spans": [s.to_dict() for s in spans]}
        )

    async def request_timeline(request: web.Request) -> web.Response:
        """GET /server/requests/<id> — the flight-recorder timeline:
        events, derived phase attribution (phases partition the wall
        clock), and the TTFT/TBT breakdown (docs/OBSERVABILITY.md)."""
        recorder = getattr(handler, "recorder", None)
        if recorder is None:
            return web.json_response(
                {"error": {"message": "flight recorder disabled",
                           "error_type": "invalid_request_error",
                           "code": "recorder_disabled"}},
                status=404,
            )
        tl = recorder.timeline(request.match_info["id"])
        if tl is None:
            return web.json_response(
                {"error": {"message": "no timeline for this request id "
                           "(expired from the bounded recorder, or never "
                           "admitted)",
                           "error_type": "invalid_request_error",
                           "code": "unknown_request"}},
                status=404,
            )
        return web.json_response(tl)

    async def request_list(request: web.Request) -> web.Response:
        recorder = getattr(handler, "recorder", None)
        if recorder is None:
            return web.json_response({"requests": []})
        try:
            n = int(request.query.get("n", "50"))
            if not 1 <= n <= 1000:
                raise ValueError
        except ValueError:
            return web.json_response(
                {"error": {"message": "query parameter 'n' must be an "
                           "integer in [1, 1000]",
                           "error_type": "invalid_request_error",
                           "code": "invalid_parameter"}},
                status=400,
            )
        # SLO triage (docs/OBSERVABILITY.md "Performance telemetry"):
        # ?verdict=violated lists exactly the timelines burning the SLO
        verdict = request.query.get("verdict")
        if verdict is not None and verdict not in ("ok", "violated"):
            return web.json_response(
                {"error": {"message": "query parameter 'verdict' must "
                           "be 'ok' or 'violated'",
                           "error_type": "invalid_request_error",
                           "code": "invalid_parameter"}},
                status=400,
            )
        return web.json_response(
            {"requests": recorder.recent(n, verdict=verdict),
             "stats": recorder.stats()})

    async def perf(request: web.Request) -> web.Response:
        """GET /server/perf — the performance-telemetry surface
        (docs/OBSERVABILITY.md): per-engine step-clock counters,
        windowed TTFT/TBT/queue-wait percentiles, SLO burn, the raw
        mergeable digests, and (registry host) the per-member +
        fleet-merged view."""
        if perf_fn is None:
            return web.json_response(
                {"error": {"message": "performance telemetry not "
                           "configured",
                           "error_type": "invalid_request_error",
                           "code": "perf_unavailable"}},
                status=404,
            )
        return web.json_response(perf_fn())

    async def profile(request: web.Request) -> web.Response:
        """Device-trace capture (SURVEY §5 device-tracing bar;
        utils/profiler.py). Body: {"steps": N} traces the next N engine
        steps on one replica (optional "engine_id"), or
        {"duration_ms": M} traces a wall-clock window process-wide.
        Returns the TensorBoard trace directory."""
        obj = await _json_body(request)
        loop = asyncio.get_running_loop()
        if "steps" in obj:
            steps = obj.get("steps")
            if not isinstance(steps, int) or not 1 <= steps <= 1000:
                return web.json_response(
                    {"error": {"message": "'steps' must be an integer "
                               "in [1, 1000]",
                               "error_type": "invalid_request_error",
                               "code": "invalid_body"}},
                    status=400,
                )
            runners = handler.dispatcher.scheduler.engines()
            engine_id = obj.get("engine_id")
            if engine_id is not None:
                runners = [r for r in runners if r.engine_id == engine_id]
            if not runners:
                return web.json_response(
                    {"error": {"message": "no such engine",
                               "error_type": "invalid_request_error",
                               "code": "invalid_body"}},
                    status=400,
                )
            timeout_s = float(obj.get("timeout_s", 30.0))
            result = await loop.run_in_executor(
                None, runners[0].profile_steps, steps, timeout_s
            )
            result.setdefault("engine_id", runners[0].engine_id)
        else:
            ms = obj.get("duration_ms", 500)
            if not isinstance(ms, (int, float)) or not 0 < ms <= 60_000:
                return web.json_response(
                    {"error": {"message": "'duration_ms' must be in "
                               "(0, 60000]",
                               "error_type": "invalid_request_error",
                               "code": "invalid_body"}},
                    status=400,
                )
            from distributed_inference_server_tpu.utils.profiler import (
                capture_duration,
            )

            def _cap():
                try:
                    return capture_duration(ms / 1000.0)
                except Exception as e:  # noqa: BLE001 — capture busy etc.
                    return {"error": str(e)}

            result = await loop.run_in_executor(None, _cap)
        status = 409 if "error" in result else 200
        return web.json_response(result, status=status)

    async def scale(request: web.Request) -> web.Response:
        """Runtime replica scaling (requirements.md:110): body
        {"num_engines": N}; removal drains in-flight work."""
        if scale_fn is None:
            return web.json_response(
                {"error": {"message": "scaling not configured",
                           "error_type": "invalid_request_error",
                           "code": "scale_unavailable"}},
                status=501,
            )
        obj = await _json_body(request)
        n = obj.get("num_engines")
        if not isinstance(n, int) or not 1 <= n <= 64:
            return web.json_response(
                {"error": {"message": "'num_engines' must be an integer "
                           "in [1, 64]",
                           "error_type": "invalid_request_error",
                           "code": "invalid_body"}},
                status=400,
            )
        loop = asyncio.get_running_loop()
        ok, err = await loop.run_in_executor(None, scale_fn, n)
        if not ok:
            return web.json_response(
                {"error": {"message": err, "error_type": "server_error",
                           "code": "scale_failed"}},
                status=500,
            )
        statuses = handler.dispatcher.scheduler.statuses()
        return web.json_response({
            "status": "ok",
            "num_engines": len(statuses),
            "engines": [s.to_dict() for s in statuses],
        })

    async def speculation(request: web.Request) -> web.Response:
        """Speculation control (Req 12.5): {"action": "reset"} clears the
        acceptance trackers fleet-wide — explicit operator signal that
        the request pattern changed (the automatic probation re-enable
        handles the common case)."""
        obj = await _json_body(request)
        if obj.get("action") != "reset":
            return web.json_response(
                {"error": {"message": "'action' must be 'reset'",
                           "error_type": "invalid_request_error",
                           "code": "invalid_body"}},
                status=400,
            )
        runners = handler.dispatcher.scheduler.engines()
        n = 0
        for r in runners:
            if hasattr(r, "reset_speculation"):
                r.reset_speculation()
                n += 1
        return web.json_response({"status": "ok", "engines_reset": n})

    app.router.add_post("/admin/speculation", speculation)
    app.router.add_post("/admin/scale", scale)
    app.router.add_post("/server/profile", profile)
    app.router.add_get("/server/trace", trace)
    app.router.add_get("/server/perf", perf)
    app.router.add_get("/server/requests", request_list)
    app.router.add_get("/server/requests/{id}", request_timeline)
    app.router.add_post("/admin/model-swap", model_swap)
    app.router.add_post("/generate", generate)
    app.router.add_post("/chat", chat)
    app.router.add_post("/embeddings", embeddings)
    app.router.add_post("/v1/completions", generate_v1)
    app.router.add_post("/v1/chat/completions", chat_v1)
    app.router.add_post("/v1/embeddings", embeddings)
    app.router.add_get("/server/stats", stats)
    app.router.add_get("/metrics", prom)
    app.router.add_get("/health", health)
    return app
