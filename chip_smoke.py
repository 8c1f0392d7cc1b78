"""Chip smoke: the quickest proof that the serving path still starts,
compiles and answers on the TPU.

    python chip_smoke.py

Starts the server through its normal entry point — ``python -m
distributed_inference_server_tpu --model-model-name llama-3.2-1b``:
Llama-3.2-1B bf16 at full width and depth, random weights from the
server's fixed seed, the DEFAULT engine geometry, nothing shrunk — as a
child process on a spare port, waits for ``/health``, and drives it over
HTTP the way a user would: ``/generate`` twice (greedy: the texts must be
identical), ``/generate`` streamed over SSE twice (greedy: the per-token
logprobs must be finite and identical — with random weights nearly every
sampled id lies outside the byte tokenizer's range and decodes to "", so
the logprobs are the observable fingerprint of the token stream),
``/chat``, ``/v1/completions``, and a concurrent burst whose prompts span
every prefill bucket, so batched prefill and batched decode both run.
Then it reads ``/server/stats`` and ``/metrics``, checks the device facts
the server reports in ``/health`` (platform ``tpu``, a v5e device kind,
decode and prefill attention on the Pallas kernels — what "auto" is
documented to give at this geometry), shuts the server down and checks
its exit code.

This process must NOT import jax: a chip belongs to one process, and it
belongs to the server. Everything known about the device comes from the
server's ``/health``. The child is started with ``JAX_PLATFORMS=tpu``, so
on a machine without a TPU it dies at start-up instead of serving the
1B model from the CPU for an hour first.

The first failed phase raises; nothing is caught and carried past. On
success the last line of stdout is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import json
import math
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
SERVER_ARGS = ["--model-model-name", "llama-3.2-1b"]
# A cold start compiles every serving program (a prefill program per
# bucket and the decode block); the whole smoke must end within 1200 s.
READY_TIMEOUT_S = 1000.0
REQUEST_TIMEOUT_S = 120.0
BURST = 12
GREEDY_PROMPT = "The chip smoke asks twice: "


class SmokeFailure(Exception):
    """A phase of the smoke did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def http(method: str, url: str, body: dict | None = None,
         timeout: float = REQUEST_TIMEOUT_S) -> tuple[int, bytes]:
    """One HTTP exchange; a refused connection or a timeout raises."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def get_json(base: str, path: str) -> dict:
    status, raw = http("GET", base + path)
    check(status == 200, f"GET {path} -> {status}: {raw[:300]!r}")
    return json.loads(raw)


def post_json(base: str, path: str, body: dict) -> dict:
    status, raw = http("POST", base + path, body)
    check(status == 200, f"POST {path} -> {status}: {raw[:300]!r}")
    obj = json.loads(raw)
    n = obj["usage"]["completion_tokens"]
    check(n >= 1, f"POST {path}: completion_tokens={n}")
    return obj


def greedy_stream(base: str, prompt: str, max_tokens: int) -> list[float]:
    """One greedy ``/generate`` over SSE; the per-token logprobs."""
    status, raw = http("POST", base + "/generate", {
        "prompt": prompt, "max_tokens": max_tokens, "temperature": 0,
        "stream": True})
    check(status == 200, f"POST /generate stream -> {status}: {raw[:300]!r}")
    events = [line[len("data: "):] for line in raw.decode().splitlines()
              if line.startswith("data: ")]
    check(bool(events) and events[-1] == "[DONE]",
          f"SSE stream did not end in [DONE]: {events[-2:]}")
    frames = [json.loads(e) for e in events[:-1]]
    done = [f for f in frames if f.get("type") == "done"]
    check(len(done) == 1 and done[0]["usage"]["completion_tokens"] >= 1,
          f"SSE stream carried no completion: {frames[-2:]}")
    logprobs = [f["logprob"] for f in frames
                if f.get("type") == "token" and "logprob" in f]
    check(len(logprobs) == done[0]["usage"]["completion_tokens"]
          and all(math.isfinite(lp) and lp <= 0.0 for lp in logprobs),
          f"SSE token logprobs are not one finite value per token: "
          f"{logprobs}")
    return logprobs


def max_abs_diff(a: list[float], b: list[float]) -> float:
    check(len(a) == len(b), f"streams differ in length: {len(a)} vs {len(b)}")
    return max(abs(x - y) for x, y in zip(a, b))


def request_phases(base: str) -> None:
    """Every request returns 200 with >= 1 completion token; greedy is
    deterministic; the burst fills batched prefill and batched decode."""
    greedy = {"prompt": GREEDY_PROMPT, "max_tokens": 16, "temperature": 0}
    first = post_json(base, "/generate", greedy)["choices"][0]["text"]
    second = post_json(base, "/generate", greedy)["choices"][0]["text"]
    check(first == second,
          f"two temperature-0 requests differ: {first!r} vs {second!r}")
    print(f"PASS /generate x2, greedy texts identical ({first!r})")

    lp1 = greedy_stream(base, GREEDY_PROMPT, 16)
    lp2 = greedy_stream(base, GREEDY_PROMPT, 16)
    diff = max_abs_diff(lp1, lp2)
    check(diff <= 1e-3, f"two greedy streams differ: logprobs {lp1} vs {lp2}")
    print(f"PASS /generate stream x2, {len(lp1)} token logprobs finite and "
          f"identical (max |diff| {diff:.2g})")

    post_json(base, "/chat", {
        "messages": [{"role": "user", "content": "Say something."}],
        "max_tokens": 16, "temperature": 0,
    })
    print("PASS /chat")
    post_json(base, "/v1/completions", {
        "prompt": "Once upon a chip", "max_tokens": 16, "temperature": 0,
    })
    print("PASS /v1/completions")

    # byte-level tokenizer: prompt length in characters ~ tokens, so these
    # land in the 32, 128 and 512 prefill buckets
    bodies = [
        {"prompt": f"burst {i} " + "x" * (20, 100, 400)[i % 3],
         "max_tokens": 24, "temperature": 0.7 if i % 2 else 0}
        for i in range(BURST)
    ]
    with concurrent.futures.ThreadPoolExecutor(BURST) as pool:
        results = list(pool.map(
            lambda b: post_json(base, "/generate", b), bodies))
    tokens = sum(r["usage"]["completion_tokens"] for r in results)
    print(f"PASS burst of {BURST} concurrent /generate ({tokens} tokens)")


REQUESTS_SENT = 6 + BURST  # what request_phases sends


def stats_phase(base: str) -> None:
    stats = get_json(base, "/server/stats")
    check(stats["total_requests"] >= REQUESTS_SENT,
          f"/server/stats counted {stats['total_requests']} of "
          f"{REQUESTS_SENT} requests")
    status, raw = http("GET", base + "/metrics")
    check(status == 200 and b"request_latency_seconds_count" in raw,
          f"GET /metrics -> {status}, no request histogram")
    print(f"PASS /server/stats ({stats['total_requests']} requests) and "
          "/metrics")


def device_phase(health: dict) -> dict:
    """The server must be on the chip, on the kernels it documents."""
    engines = health["engines"]
    print("device: platform {platform}, device_kind {device_kind}, "
          "count {device_count}".format(**health))
    for e in engines:
        print(f"{e['engine_id']}: devices {e['device_ids']} (bytes in use "
              f"{e['device_bytes_in_use']}), attention "
              f"decode={e['attention']['decode']} "
              f"prefill={e['attention']['prefill']}"
              + "".join(f"; {k} kernel rejected: {v}"
                        for k, v in e["attention_rejected"].items()))
    print("native tier:", "loaded (built from the committed sources)"
          if health["native_tier"] else "NOT loaded, Python tier serves")
    print("compile cache:", health["compile_cache_dir"])
    check(health["platform"] == "tpu",
          f"server runs on platform {health['platform']!r}, not 'tpu'")
    kind = health["device_kind"]
    check("v5 lite" in kind,  # jax 0.9 reports a v5e chip as "TPU v5 lite"
          f"device_kind {kind!r} is not a TPU v5e")
    for e in engines:
        # README "Attention kernels": on a TPU "auto" serves decode and
        # prefill on the Pallas paged-attention kernels at this geometry
        check(e["attention"] == {"decode": "pallas", "prefill": "pallas"},
              f"{e['engine_id']} attends on {e['attention']}, not the "
              f"Pallas kernels: {e['attention_rejected']}")
    print("PASS device facts")
    return {"platform": health["platform"], "kind": kind,
            "count": health["device_count"]}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_ready(base: str, child: subprocess.Popen, timeout: float) -> dict:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        check(child.poll() is None,
              f"server exited with code {child.returncode} before it was "
              "ready")
        try:
            status, raw = http("GET", base + "/health", timeout=5.0)
        except OSError:
            time.sleep(1.0)  # not listening yet: still compiling
            continue
        check(status == 200, f"GET /health -> {status}: {raw[:300]!r}")
        return json.loads(raw)
    raise SmokeFailure(f"server not ready after {timeout:.0f} s")


@contextlib.contextmanager
def serving(server_args, platform: str = "tpu",
            log_name: str = "chip_smoke_server.log"):
    """Run the server as a child for the body of the ``with``: yields
    ``(base_url, set_up_seconds)`` once ``/health`` answers. A clean exit
    of the body shuts the server down with SIGINT and requires exit code
    0; a failure prints the end of the server's log. The child never
    outlives the block."""
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    log_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(log_dir, exist_ok=True)
    log_path = os.path.join(log_dir, log_name)
    t0 = time.monotonic()
    with open(log_path, "wb") as log:
        child = subprocess.Popen(
            [sys.executable, "-m", "distributed_inference_server_tpu",
             "--server-host", "127.0.0.1", "--server-port", str(port),
             *server_args],
            cwd=HERE, stdout=log, stderr=subprocess.STDOUT,
            env={**os.environ, "JAX_PLATFORMS": platform},
        )
        try:
            wait_ready(base, child, READY_TIMEOUT_S)
            setup_s = time.monotonic() - t0
            print(f"PASS server ready; set-up (start -> ready) "
                  f"{setup_s:.1f} s")
            yield base, setup_s
            child.send_signal(signal.SIGINT)
            code = child.wait(timeout=90)
            check(code == 0, f"server exited with code {code} on SIGINT")
            print("PASS server shut down, exit code 0")
        except BaseException:
            log.flush()
            with open(log_path, "rb") as f:
                tail = f.read()[-6000:].decode(errors="replace")
            print(f"--- server log tail ({log_path}) ---\n{tail}",
                  file=sys.stderr)
            raise
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()


def main(server_args=SERVER_ARGS, platform: str = "tpu") -> int:
    """``server_args``/``platform`` exist for tests/test_chip_smoke.py,
    which drives this same logic at a tiny size on the CPU — where the
    requests succeed and the device phase must FAIL."""
    t0 = time.monotonic()
    with serving(server_args, platform) as (base, setup_s):
        request_phases(base)
        stats_phase(base)
        device = device_phase(get_json(base, "/health"))
    print(f"set-up seconds: {setup_s:.1f}; total "
          f"{time.monotonic() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"FAIL {e}", file=sys.stderr)
        sys.exit(1)
