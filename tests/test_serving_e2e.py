"""End-to-end serving tests: HTTP → handler → dispatcher → engine → SSE.

Drives the full spine (SURVEY.md §3.2-3.4 call stacks) against a TINY
Llama-family model on the XLA CPU backend with real continuous batching —
the integration tier the reference spec'd but never built
(``design.md:1046-1053`` [spec]).
"""

from __future__ import annotations

import asyncio
import json

import jax.numpy as jnp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from distributed_inference_server_tpu.core.models import TokenEvent
from distributed_inference_server_tpu.engine.engine import EngineConfig
from distributed_inference_server_tpu.engine.kv_cache import PagedCacheConfig
from distributed_inference_server_tpu.models import llama
from distributed_inference_server_tpu.models.configs import TINY
from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer
from distributed_inference_server_tpu.serving.server import InferenceServer

# engine capacity: 32 pages/seq * 8 = 256 tokens max — small enough that an
# in-validator-range prompt can exceed it (failure-isolation test), big
# enough for the chat template (~180 byte-tokens)
_PAGED = PagedCacheConfig(num_pages=192, page_size=8, max_pages_per_seq=32)


def _engine_factory():
    import jax

    from distributed_inference_server_tpu.engine.engine import LLMEngine

    params = llama.init_params(jax.random.PRNGKey(0), TINY, dtype=jnp.float32)
    return LLMEngine(
        params,
        TINY,
        ByteTokenizer(),
        EngineConfig(max_batch=4, prefill_buckets=(16, 64), paged=_PAGED),
        dtype=jnp.float32,
    )


@pytest.fixture(scope="module")
def server():
    srv = InferenceServer(
        _engine_factory,
        ByteTokenizer(),
        model_name="tiny-test",
        num_engines=1,
        auto_restart=False,
    )
    srv.start()
    yield srv
    srv.shutdown(drain_timeout_s=5.0)


def _run(server: InferenceServer, coro_fn):
    async def main():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            return await coro_fn(client)
        finally:
            await client.close()

    return asyncio.run(main())


def test_generate_roundtrip(server):
    async def go(client):
        resp = await client.post(
            "/generate",
            json={"prompt": "hello world", "max_tokens": 8, "temperature": 0.0},
        )
        assert resp.status == 200
        body = await resp.json()
        assert body["object"] == "text_completion"
        assert body["id"].startswith("cmpl-")
        assert body["model"] == "tiny-test"
        assert len(body["choices"]) == 1
        choice = body["choices"][0]
        assert choice["finish_reason"] in ("stop", "length", "stop_sequence")
        usage = body["usage"]
        assert usage["prompt_tokens"] == len("hello world") + 1  # +BOS
        assert usage["total_tokens"] == (
            usage["prompt_tokens"] + usage["completion_tokens"]
        )
        assert usage["completion_tokens"] <= 8

    _run(server, go)


def test_generate_streaming_sse(server):
    async def go(client):
        resp = await client.post(
            "/generate",
            json={"prompt": "stream me", "max_tokens": 6, "temperature": 0.0,
                  "stream": True},
        )
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/event-stream")
        raw = await resp.read()
        frames = [f for f in raw.decode().split("\n\n") if f]
        assert frames[-1] == "data: [DONE]"
        events = [
            TokenEvent.from_dict(json.loads(f[len("data: "):]))
            for f in frames[:-1]
        ]
        assert events, "no events streamed"
        assert events[-1].type == "done"
        assert events[-1].usage.completion_tokens <= 6
        token_events = [e for e in events[:-1] if e.type == "token"]
        assert all(e.index is not None for e in token_events)
        # every real token event carries the model logprob on the wire
        # (models.rs:272-277's optional field, populated by the engine);
        # held-back text flushes (token_id None) ride without one
        with_lp = [e for e in token_events if e.logprob is not None]
        assert with_lp, "no logprobs streamed"
        assert all(e.logprob <= 0.0 for e in with_lp)

    _run(server, go)


def test_chat_roundtrip(server):
    async def go(client):
        resp = await client.post(
            "/chat",
            json={
                "messages": [
                    {"role": "system", "content": "be brief"},
                    {"role": "user", "content": "hi"},
                ],
                "max_tokens": 4,
                "temperature": 0.0,
            },
        )
        assert resp.status == 200
        body = await resp.json()
        assert body["object"] == "chat.completion"
        assert body["choices"][0]["message"]["role"] == "assistant"

    _run(server, go)


def test_embeddings_roundtrip(server):
    async def go(client):
        resp = await client.post(
            "/embeddings", json={"input": ["alpha", "beta gamma"]}
        )
        assert resp.status == 200
        body = await resp.json()
        assert body["object"] == "list"
        assert len(body["data"]) == 2
        for i, item in enumerate(body["data"]):
            assert item["object"] == "embedding"
            assert item["index"] == i
            norm = sum(x * x for x in item["embedding"]) ** 0.5
            assert abs(norm - 1.0) < 1e-3

    _run(server, go)


def test_embeddings_single_string_input(server):
    async def go(client):
        resp = await client.post("/embeddings", json={"input": "just one"})
        assert resp.status == 200
        body = await resp.json()
        assert len(body["data"]) == 1

    _run(server, go)


def test_validation_errors_400(server):
    async def go(client):
        # empty prompt
        resp = await client.post("/generate", json={"prompt": "   "})
        assert resp.status == 400
        body = await resp.json()
        assert body["error"]["error_type"] == "invalid_request_error"
        # bad temperature
        resp = await client.post(
            "/generate", json={"prompt": "x", "temperature": 9.0}
        )
        assert resp.status == 400
        # malformed JSON
        resp = await client.post(
            "/generate", data=b"{nope", headers={"Content-Type": "application/json"}
        )
        assert resp.status == 400
        # missing field
        resp = await client.post("/generate", json={"max_tokens": 4})
        assert resp.status == 400

    _run(server, go)


def test_oversized_prompt_fails_alone(server):
    """A prompt that passes the validator but exceeds engine capacity
    errors that request only (Property 22) — concurrent request survives."""

    async def go(client):
        big = "x" * 400  # 401 tokens > 256-token engine cap; validator OK
        ok, bad = await asyncio.gather(
            client.post("/generate",
                        json={"prompt": "fine", "max_tokens": 4,
                              "temperature": 0.0}),
            client.post("/generate", json={"prompt": big, "max_tokens": 4}),
        )
        assert ok.status == 200
        assert bad.status == 500
        body = await bad.json()
        assert body["error"]["error_type"] == "server_error"

    _run(server, go)


def test_server_stats(server):
    async def go(client):
        resp = await client.get("/server/stats")
        assert resp.status == 200
        body = await resp.json()
        for key in (
            "total_requests", "active_requests", "tokens_per_second",
            "average_ttft_ms", "p99_latency_ms", "average_batch_size",
            "cache_hit_rate", "queue_depth", "worker_statuses",
        ):
            assert key in body
        assert body["total_requests"] >= 1
        assert len(body["worker_statuses"]) == 1
        assert body["worker_statuses"][0]["healthy"] is True

    _run(server, go)


def test_prometheus_metrics(server):
    async def go(client):
        resp = await client.get("/metrics")
        assert resp.status == 200
        text = await resp.text()
        assert "tokens_generated_total" in text
        assert "request_latency_seconds" in text
        assert "engine_up" in text

    _run(server, go)


def test_health(server):
    async def go(client):
        resp = await client.get("/health")
        assert resp.status == 200
        body = await resp.json()
        assert body["status"] == "ok"
        assert body["accepting"] is True
        # the device facts an outside checker (chip_smoke.py) relies on
        assert body["platform"] == "cpu" and body["device_count"] == 8
        assert body["device_kind"] and "compile_cache_dir" in body
        assert isinstance(body["native_tier"], bool)
        (engine,) = body["engines"]
        assert engine["device_ids"] == [0]
        assert engine["attention"] == {"decode": "xla", "prefill": "xla"}
        assert engine["attention_rejected"] == {}

    _run(server, go)


def test_concurrent_mixed_requests(server):
    """Continuous batching handles interleaved requests with different
    lengths; every request completes with consistent usage."""

    async def go(client):
        async def one(i: int):
            resp = await client.post(
                "/generate",
                json={"prompt": f"request number {i}", "max_tokens": 3 + i,
                      "temperature": 0.0},
            )
            assert resp.status == 200
            return await resp.json()

        bodies = await asyncio.gather(*[one(i) for i in range(6)])
        for i, body in enumerate(bodies):
            assert body["usage"]["completion_tokens"] <= 3 + i

    _run(server, go)


def test_greedy_determinism(server):
    """temperature=0 is greedy argmax: same prompt → same completion."""

    async def go(client):
        async def once():
            resp = await client.post(
                "/generate",
                json={"prompt": "determinism", "max_tokens": 8,
                      "temperature": 0.0},
            )
            return (await resp.json())["choices"][0]["text"]

        first = await once()
        second = await once()
        assert first == second

    _run(server, go)


def test_admin_scale_endpoint(server):
    async def go(client):
        # scale 1 -> 2 replicas
        resp = await client.post("/admin/scale", json={"num_engines": 2})
        body = await resp.json()
        assert resp.status == 200, body
        assert body["num_engines"] == 2
        # generation still works across the scaled fleet
        r = await client.post("/generate", json={
            "prompt": "scaled", "max_tokens": 3, "temperature": 0.0})
        assert r.status == 200
        # scale back down (drains)
        resp = await client.post("/admin/scale", json={"num_engines": 1})
        body = await resp.json()
        assert resp.status == 200 and body["num_engines"] == 1
        # validation
        bad = await client.post("/admin/scale", json={"num_engines": 0})
        assert bad.status == 400
    _run(server, go)


class TestOpenAIAliases:
    """/v1/* aliases accept OpenAI request spellings (notably "stop") and
    serve the same schemas — off-the-shelf OpenAI clients work
    unchanged."""

    def test_v1_completions_with_stop_string(self, server):
        async def go(client):
            ref = await (await client.post(
                "/generate",
                json={"prompt": "hello world", "max_tokens": 8,
                      "temperature": 0.0},
            )).json()
            stop = ref["choices"][0]["text"][2:4]
            resp = await client.post(
                "/v1/completions",
                json={"prompt": "hello world", "max_tokens": 8,
                      "temperature": 0.0, "stop": stop},
            )
            assert resp.status == 200
            body = await resp.json()
            assert body["object"] == "text_completion"
            # OpenAI vocabulary: stop_sequence maps to "stop" on /v1
            assert body["choices"][0]["finish_reason"] == "stop"
            return ref, body, stop

        ref, body, stop = _run(server, go)
        # truncated at the stop's FIRST occurrence in the greedy text
        want = ref["choices"][0]["text"]
        assert body["choices"][0]["text"] == want[: want.find(stop)]

    def test_v1_bad_stop_type_names_the_client_field(self, server):
        async def go(client):
            resp = await client.post(
                "/v1/completions",
                json={"prompt": "x", "stop": 5},
            )
            assert resp.status == 400
            err = (await resp.json())["error"]
            assert '"stop"' in err["message"]
            assert "stop_sequences" not in err["message"]

        _run(server, go)

    def test_v1_chat_and_embeddings(self, server):
        async def go(client):
            chat = await client.post(
                "/v1/chat/completions",
                json={"messages": [{"role": "user", "content": "hi"}],
                      "max_tokens": 4, "stop": ["zzz_never"]},
            )
            assert chat.status == 200
            assert (await chat.json())["object"] == "chat.completion"
            emb = await client.post(
                "/v1/embeddings", json={"input": ["a"]}
            )
            assert emb.status == 200
            assert (await emb.json())["object"] == "list"

        _run(server, go)

    def test_v1_streaming_is_openai_chunks(self, server):
        """/v1 streams OpenAI objects (choices[].text / choices[].delta),
        NOT the internal TokenEvent frames — off-the-shelf SDK chunk
        parsing depends on it."""
        import json as _json

        async def go(client):
            resp = await client.post(
                "/v1/completions",
                json={"prompt": "abc", "max_tokens": 3, "stream": True},
            )
            assert resp.status == 200
            comp = (await resp.read()).decode()
            resp = await client.post(
                "/v1/chat/completions",
                json={"messages": [{"role": "user", "content": "hi"}],
                      "max_tokens": 3, "stream": True},
            )
            assert resp.status == 200
            chat = (await resp.read()).decode()
            return comp, chat

        comp, chat = _run(server, go)
        for body in (comp, chat):
            assert '"type": "token"' not in body  # no internal frames
            assert body.strip().endswith("data: [DONE]")
        frames = [_json.loads(line[6:]) for line in comp.splitlines()
                  if line.startswith("data: {")]
        assert all(f["object"] == "text_completion" for f in frames)
        assert "text" in frames[0]["choices"][0]
        assert frames[-1]["choices"][0]["finish_reason"] == "length"
        cframes = [_json.loads(line[6:]) for line in chat.splitlines()
                   if line.startswith("data: {")]
        assert all(f["object"] == "chat.completion.chunk" for f in cframes)
        assert cframes[0]["choices"][0]["delta"]["role"] == "assistant"
        assert cframes[-1]["choices"][0]["delta"] == {}
        assert cframes[-1]["choices"][0]["finish_reason"] == "length"


    def test_v1_max_completion_tokens_and_empty_stop(self, server):
        async def go(client):
            resp = await client.post(
                "/v1/chat/completions",
                json={"messages": [{"role": "user", "content": "hi"}],
                      "max_completion_tokens": 3},
            )
            assert resp.status == 200
            body = await resp.json()
            assert body["usage"]["completion_tokens"] <= 3
            bad = await client.post(
                "/v1/completions", json={"prompt": "x", "stop": [""]}
            )
            assert bad.status == 400
            assert "non-empty" in (await bad.json())["error"]["message"]
            for bad_n in (True, 0, "2", 17, -1):
                multi = await client.post(
                    "/v1/completions", json={"prompt": "x", "n": bad_n}
                )
                assert multi.status == 400, bad_n  # no silent one-choice
                assert '"n"' in (await multi.json())["error"]["message"]
            ok_n = await client.post(
                "/v1/completions",
                json={"prompt": "x", "n": 1, "max_tokens": 1},
            )
            assert ok_n.status == 200

        _run(server, go)

    def test_v1_chat_role_only_in_first_delta(self, server):
        import json as _json

        async def go(client):
            resp = await client.post(
                "/v1/chat/completions",
                json={"messages": [{"role": "user", "content": "hi"}],
                      "max_tokens": 4, "stream": True},
            )
            return (await resp.read()).decode()

        body = _run(server, go)
        deltas = [
            _json.loads(line[6:])["choices"][0]["delta"]
            for line in body.splitlines() if line.startswith("data: {")
        ]
        token_deltas = [d for d in deltas if d.get("content") is not None]
        assert "role" in token_deltas[0]
        assert all("role" not in d for d in token_deltas[1:])


def _v1_chunks(body: str):
    import json as _json

    return [
        _json.loads(line[6:])
        for line in body.splitlines()
        if line.startswith("data: {")
    ]


class TestV1ParityTail:
    """OpenAI /v1 parity: n>1 fan-out, sampled-token logprobs, and
    stream_options.include_usage (VERDICT r3 missing #5 / next #4;
    multi-choice response schema models.rs:147-171)."""

    def test_n2_completions_nonstream(self, server):
        async def go(client):
            resp = await client.post(
                "/v1/completions",
                json={"prompt": "fan out", "n": 2, "max_tokens": 4,
                      "temperature": 0.0},
            )
            assert resp.status == 200
            body = await resp.json()
            assert [c["index"] for c in body["choices"]] == [0, 1]
            for c in body["choices"]:
                assert c["finish_reason"] in ("stop", "length")
                assert c["logprobs"] is None
            u = body["usage"]
            # prompt counted ONCE; completions summed over both choices
            assert u["prompt_tokens"] == len("fan out") + 1  # +BOS
            assert u["completion_tokens"] <= 8
            assert u["total_tokens"] == (
                u["prompt_tokens"] + u["completion_tokens"]
            )
            # greedy decoding: both choices must agree
            assert body["choices"][0]["text"] == body["choices"][1]["text"]

        _run(server, go)

    def test_n2_chat_stream_interleaves_choices(self, server):
        async def go(client):
            resp = await client.post(
                "/v1/chat/completions",
                json={"messages": [{"role": "user", "content": "hi"}],
                      "n": 2, "max_tokens": 3, "stream": True},
            )
            assert resp.status == 200
            return (await resp.read()).decode()

        body = _run(server, go)
        assert body.rstrip().endswith("data: [DONE]")
        chunks = _v1_chunks(body)
        by_idx = {0: [], 1: []}
        for ch in chunks:
            for c in ch["choices"]:
                by_idx[c["index"]].append(c)
        for idx in (0, 1):
            finishes = [c for c in by_idx[idx]
                        if c["finish_reason"] is not None]
            assert len(finishes) == 1, f"choice {idx} finish chunks"
            deltas = [c["delta"] for c in by_idx[idx]
                      if c["delta"].get("content") is not None]
            assert "role" in deltas[0]
            assert all("role" not in d for d in deltas[1:])

    def test_completions_logprobs_nonstream(self, server):
        async def go(client):
            resp = await client.post(
                "/v1/completions",
                json={"prompt": "lp", "max_tokens": 4, "logprobs": 0,
                      "temperature": 0.0},
            )
            assert resp.status == 200
            body = await resp.json()
            lp = body["choices"][0]["logprobs"]
            assert lp is not None
            k = len(lp["tokens"])
            assert k >= 1
            assert len(lp["token_logprobs"]) == k
            assert len(lp["text_offset"]) == k
            assert lp["top_logprobs"] is None
            assert all(v <= 0.0 for v in lp["token_logprobs"]
                       if v is not None)
            assert lp["text_offset"][0] == 0
            assert lp["text_offset"] == sorted(lp["text_offset"])

        _run(server, go)

    def test_chat_logprobs_nonstream_and_stream(self, server):
        async def go(client):
            resp = await client.post(
                "/v1/chat/completions",
                json={"messages": [{"role": "user", "content": "hi"}],
                      "max_tokens": 3, "logprobs": True},
            )
            assert resp.status == 200
            body = await resp.json()
            content = body["choices"][0]["logprobs"]["content"]
            assert content
            for entry in content:
                assert set(entry) == {"token", "logprob", "bytes",
                                      "top_logprobs"}
                assert entry["top_logprobs"] == []
                assert isinstance(entry["bytes"], list)
            sresp = await client.post(
                "/v1/chat/completions",
                json={"messages": [{"role": "user", "content": "hi"}],
                      "max_tokens": 3, "logprobs": True, "stream": True},
            )
            return (await sresp.read()).decode()

        body = _run(server, go)
        chunks = _v1_chunks(body)
        token_chunks = [
            c for ch in chunks for c in ch["choices"]
            if c.get("delta", {}).get("content") is not None
        ]
        assert token_chunks
        with_lp = [c for c in token_chunks if c["logprobs"] is not None]
        assert with_lp, "no logprobs in stream chunks"
        for c in with_lp:
            for entry in c["logprobs"]["content"]:
                assert "token" in entry and "logprob" in entry

    def test_stream_include_usage(self, server):
        async def go(client):
            resp = await client.post(
                "/v1/completions",
                json={"prompt": "use me", "max_tokens": 3, "stream": True,
                      "stream_options": {"include_usage": True}},
            )
            assert resp.status == 200
            return (await resp.read()).decode()

        body = _run(server, go)
        chunks = _v1_chunks(body)
        # every chunk carries a usage key; all null except the final one
        assert all("usage" in ch for ch in chunks)
        final = chunks[-1]
        assert final["choices"] == []
        u = final["usage"]
        assert u["prompt_tokens"] == len("use me") + 1  # +BOS
        assert 1 <= u["completion_tokens"] <= 3
        assert u["total_tokens"] == (
            u["prompt_tokens"] + u["completion_tokens"]
        )
        assert all(ch["usage"] is None for ch in chunks[:-1])

    def test_stream_error_still_emits_usage_chunk(self, server):
        """An error event terminates its choice, so include_usage's final
        usage chunk must still arrive when a choice errors (review
        finding: remaining was only decremented on done events)."""

        async def go(client):
            big = "x" * 400  # 401 tokens > 256-token engine cap
            resp = await client.post(
                "/v1/completions",
                json={"prompt": big, "max_tokens": 3, "stream": True,
                      "stream_options": {"include_usage": True}},
            )
            assert resp.status == 200
            return (await resp.read()).decode()

        body = _run(server, go)
        assert body.rstrip().endswith("data: [DONE]")
        chunks = _v1_chunks(body)
        assert any("error" in ch for ch in chunks)
        final = chunks[-1]
        assert final["choices"] == []
        assert final["usage"] is not None

    def test_unsupported_shape_fields_rejected(self, server):
        async def go(client):
            cases = [
                ("/v1/completions", {"prompt": "x", "echo": True}),
                ("/v1/completions", {"prompt": "x", "best_of": 3}),
                # best_of < n is self-contradictory (OpenAI 400s it too)
                ("/v1/completions", {"prompt": "x", "n": 4, "best_of": 1}),
                ("/v1/completions", {"prompt": "x", "suffix": "tail"}),
                ("/v1/completions", {"prompt": "x", "logprobs": 3}),
                ("/v1/completions",
                 {"prompt": "x",
                  "stream_options": {"include_usage": True}}),
                ("/v1/chat/completions",
                 {"messages": [{"role": "user", "content": "x"}],
                  "logprobs": True, "top_logprobs": 2}),
                ("/v1/chat/completions",
                 {"messages": [{"role": "user", "content": "x"}],
                  "top_logprobs": 0}),
            ]
            for path, payload in cases:
                resp = await client.post(path, json=payload)
                assert resp.status == 400, (path, payload)
                msg = (await resp.json())["error"]["message"]
                assert msg, (path, payload)
            # best_of == n degenerates to "return all n" and is allowed
            ok = await client.post(
                "/v1/completions",
                json={"prompt": "x", "n": 2, "best_of": 2,
                      "max_tokens": 1},
            )
            assert ok.status == 200
            assert len((await ok.json())["choices"]) == 2

        _run(server, go)


def test_four_replicas_hold_four_devices():
    """``--server-num-engines 4`` through the real entry point: replica i
    is pinned to device i (committed weights and pool), so /health shows
    four engines on four distinct devices — not four replicas stacked on
    ``jax.devices()[0]``. Runs on the 8-virtual-device CPU backend."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import chip_smoke

    args = ["--server-num-engines", "4", "--model-model-name", "tiny",
            "--model-dtype", "float32", "--engine-warmup-compile", "false",
            "--engine-num-pages", "64", "--engine-page-size", "8",
            "--engine-max-pages-per-seq", "16",
            "--engine-prefill-buckets", "16"]
    with chip_smoke.serving(args, platform="cpu",
                            log_name="test_four_replicas.log") as (base, _):
        health = chip_smoke.get_json(base, "/health")
        assert health["device_count"] == 8
        ids = [e["device_ids"] for e in health["engines"]]
        assert sorted(ids) == [[0], [1], [2], [3]], ids
