"""Multi-host backend tests (SURVEY §5 two-plane design; VERDICT r1:
"DCN / multi-host absent entirely"): the jax.distributed wrapper + hybrid
DCN x ICI mesh (data plane) and the cross-host HTTP router (control
plane), driven against two real in-process backend servers."""

from __future__ import annotations

import asyncio
import json

import jax.numpy as jnp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from distributed_inference_server_tpu.engine.engine import EngineConfig
from distributed_inference_server_tpu.engine.kv_cache import PagedCacheConfig
from distributed_inference_server_tpu.models import llama
from distributed_inference_server_tpu.models.configs import TINY
from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer
from distributed_inference_server_tpu.parallel import MeshSpec
from distributed_inference_server_tpu.parallel.distributed import (
    DistributedConfig,
    global_batch_shard,
    hybrid_mesh,
    initialize,
)
from distributed_inference_server_tpu.serving.router import (
    Router,
    RouterConfig,
    build_router_app,
)
from distributed_inference_server_tpu.serving.server import InferenceServer


class TestDataPlane:
    def test_single_process_skips_initialize(self):
        assert initialize(DistributedConfig()) is False
        assert not DistributedConfig().enabled
        assert DistributedConfig(num_processes=4,
                                 coordinator_address="h:1234").enabled

    def test_hybrid_mesh_single_slice_collapses(self):
        mesh = hybrid_mesh(MeshSpec(tensor=2), dcn_spec=MeshSpec(data=4))
        assert mesh.shape["data"] == 4
        assert mesh.shape["tensor"] == 2
        assert mesh.shape["expert"] == 1

    def test_hybrid_mesh_defaults(self):
        mesh = hybrid_mesh(MeshSpec(tensor=4, data=2))
        assert mesh.shape["tensor"] == 4
        assert mesh.shape["data"] == 2

    def test_global_batch_shard_single(self):
        assert global_batch_shard(7) == (7, 0)


_PAGED = PagedCacheConfig(num_pages=64, page_size=8, max_pages_per_seq=8)


def _factory():
    import jax

    from distributed_inference_server_tpu.engine.engine import LLMEngine

    params = llama.init_params(jax.random.PRNGKey(0), TINY, dtype=jnp.float32)
    return LLMEngine(
        params, TINY, ByteTokenizer(),
        EngineConfig(max_batch=2, prefill_buckets=(16,), paged=_PAGED),
        dtype=jnp.float32,
    )


@pytest.fixture(scope="module")
def backends():
    servers = []
    for name in ("host-a", "host-b"):
        srv = InferenceServer(
            _factory, ByteTokenizer(), model_name=name,
            num_engines=1, auto_restart=False,
        )
        srv.start()
        servers.append(srv)
    yield servers
    for srv in servers:
        srv.shutdown(drain_timeout_s=5.0)


def _run_router(backends, coro_fn, **router_kw):
    async def main():
        # two real backend HTTP servers on localhost ports
        test_servers = [TestServer(s.build_app()) for s in backends]
        for ts in test_servers:
            await ts.start_server()
        urls = [str(ts.make_url("/")).rstrip("/") for ts in test_servers]
        router = Router(RouterConfig(
            backends=urls,
            health_check_interval_s=0.2,
            **router_kw,
        ))
        client = TestClient(TestServer(build_router_app(router)))
        await client.start_server()
        try:
            return await coro_fn(client, router, urls)
        finally:
            await client.close()
            for ts in test_servers:
                await ts.close()

    return asyncio.run(main())


class TestRouter:
    def test_v1_alias_via_router(self, backends):
        """The OpenAI /v1 aliases proxy through the router 1:1; the
        backend applies the field translation ("stop" here)."""
        async def go(client, router, urls):
            resp = await client.post("/v1/completions", json={
                "prompt": "hello fleet", "max_tokens": 4,
                "temperature": 0.0, "stop": ["zz_never"],
            })
            assert resp.status == 200
            body = await resp.json()
            assert body["object"] == "text_completion"
            assert body["usage"]["completion_tokens"] == 4
        _run_router(backends, go)

    def test_generate_via_router(self, backends):
        async def go(client, router, urls):
            resp = await client.post("/generate", json={
                "prompt": "hello fleet", "max_tokens": 6,
                "temperature": 0.0,
            })
            assert resp.status == 200
            body = await resp.json()
            assert body["usage"]["completion_tokens"] == 6
            assert sum(b.total for b in router.backends) == 1
        _run_router(backends, go)

    def test_round_robin_spreads_load(self, backends):
        async def go(client, router, urls):
            for _ in range(4):
                resp = await client.post("/generate", json={
                    "prompt": "spread", "max_tokens": 2,
                    "temperature": 0.0,
                })
                assert resp.status == 200
            counts = sorted(b.total for b in router.backends)
            assert counts == [2, 2]
        _run_router(backends, go, strategy="round_robin")

    def test_sse_stream_passthrough(self, backends):
        async def go(client, router, urls):
            resp = await client.post("/generate", json={
                "prompt": "stream me", "max_tokens": 4,
                "temperature": 0.0, "stream": True,
            })
            assert resp.status == 200
            assert resp.content_type == "text/event-stream"
            events = []
            async for line in resp.content:
                line = line.decode().strip()
                if line.startswith("data: ") and line != "data: [DONE]":
                    events.append(json.loads(line[6:]))
            kinds = [e["type"] for e in events]
            # 4 generated tokens arrive as >= 4 token events (the final
            # token is emitted as id + held-back-text flush, same as the
            # direct backend stream) followed by done
            assert kinds.count("token") >= 4
            assert kinds[-1] == "done"
            assert events[-1]["usage"]["completion_tokens"] == 4
        _run_router(backends, go)

    def test_dead_backend_failover(self, backends):
        async def go(client, router, urls):
            # poison one backend with an unreachable address
            router.backends[0].base_url = "http://127.0.0.1:1"
            resp = await client.post("/generate", json={
                "prompt": "failover", "max_tokens": 3,
                "temperature": 0.0,
            })
            assert resp.status == 200  # retried on the healthy backend
            assert not router.backends[0].healthy
            assert router.backends[0].last_error
        _run_router(backends, go)

    def test_all_dead_returns_503(self, backends):
        async def go(client, router, urls):
            for b in router.backends:
                b.healthy = False
            resp = await client.post("/generate", json={
                "prompt": "nope", "max_tokens": 1,
            })
            assert resp.status == 503
            body = await resp.json()
            assert body["error"]["code"] == "no_backend"
        _run_router(backends, go)

    def test_health_aggregation_and_recovery(self, backends):
        async def go(client, router, urls):
            resp = await client.get("/health")
            assert resp.status == 200
            body = await resp.json()
            assert body["status"] == "ok"
            assert len(body["backends"]) == 2
            # mark one unhealthy; the health loop reinstates it
            router.backends[0].healthy = False
            await asyncio.sleep(0.5)
            assert router.backends[0].healthy  # recovered by the loop
        _run_router(backends, go)

    def test_stats_aggregation(self, backends):
        async def go(client, router, urls):
            resp = await client.get("/server/stats")
            assert resp.status == 200
            body = await resp.json()
            assert set(body["backends"]) == set(urls)
            assert len(body["router"]) == 2
        _run_router(backends, go)

    def test_validation_errors_pass_through(self, backends):
        async def go(client, router, urls):
            resp = await client.post("/generate", json={"max_tokens": 1})
            assert resp.status == 400  # backend's validator error
            body = await resp.json()
            assert body["error"]["error_type"] == "invalid_request_error"
        _run_router(backends, go)

    def test_router_config_validation(self):
        with pytest.raises(ValueError):
            Router(RouterConfig(backends=[]))
        with pytest.raises(ValueError):
            Router(RouterConfig(backends=["http://x"], strategy="nope"))


_WORKER_SRC = '''
"""One rank of the two-process jax.distributed smoke test (SURVEY §5:
the comm backend's real multi-process init path, not the single-process
skip). Run: python worker.py <rank> <port>"""
import sys

rank, port = int(sys.argv[1]), sys.argv[2]
import jax

jax.config.update("jax_platforms", "cpu")  # never claim a chip

from distributed_inference_server_tpu.parallel.distributed import (
    DistributedConfig,
    global_batch_shard,
    initialize,
    is_coordinator,
    process_count,
)

cfg = DistributedConfig(
    coordinator_address="127.0.0.1:" + port, num_processes=2,
    process_id=rank,
)
assert initialize(cfg), "initialize returned False"
assert initialize(cfg), "second initialize must be idempotent-True"
assert process_count() == 2
assert is_coordinator() == (rank == 0)
assert global_batch_shard(5) == ((3, 0) if rank == 0 else (2, 3))

import numpy as np
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

devs = jax.devices()
assert len(devs) == 2, devs  # global device view spans both processes
mesh = Mesh(np.array(devs), ("data",))
f = jax.jit(jax.shard_map(
    lambda x: lax.psum(x, "data"), mesh=mesh,
    in_specs=P("data"), out_specs=P(),
))
local = jnp.arange(2, dtype=jnp.float32) + 1  # global [1, 2], one per rank
out = np.asarray(f(local))
assert out.tolist() == [3.0], out  # summed ACROSS processes over the wire
print("WORKER%d OK" % rank)
'''


class TestTwoProcessDataPlane:
    def test_real_initialize_and_cross_process_psum(self, tmp_path):
        """Spawn two local CPU processes with a coordinator on localhost:
        ``initialize()`` really runs (not the single-process skip), the
        global device view spans both processes, and a psum over the
        'data' axis completes ACROSS the process boundary (VERDICT r2
        weak #6: multi-host init was the one piece no test executed)."""
        import os
        import socket
        import subprocess
        import sys

        worker = tmp_path / "dist_worker.py"
        worker.write_text(_WORKER_SRC)
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(
            os.environ,
            PYTHONPATH=repo_root + os.pathsep + os.environ.get("PYTHONPATH", ""),
        )
        # one local CPU device per process, whatever the suite's XLA_FLAGS
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        procs = [
            subprocess.Popen(
                [sys.executable, str(worker), str(r), str(port)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
                env=env,
            )
            for r in range(2)
        ]
        outs = []
        try:
            for p in procs:
                out, _ = p.communicate(timeout=240)
                outs.append(out)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        for r, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {r} failed:\n{out[-2000:]}"
            assert f"WORKER{r} OK" in out
