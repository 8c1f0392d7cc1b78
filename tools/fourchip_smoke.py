"""Four-chip smoke: replicas on four chips, and one replica over four.

    python tools/fourchip_smoke.py        # on the four-chip host

Two legs, same model and requests as ``chip_smoke.py`` (whose helpers
this reuses; like it, this process never imports jax — the chips belong
to the server child):

(a) ``--server-num-engines 4`` — four one-chip replicas in ONE process.
    ``/health`` must show four engines on four distinct devices with
    memory in use on each, every one on the Pallas kernels; a concurrent
    burst must reach all four (``/server/stats`` ``total_processed``).
(b) ``--engine-tensor-parallel 4`` — one replica sharded over the four
    chips, the Pallas kernels inside ``shard_map``. Its greedy stream
    must equal leg (a)'s — a one-chip replica's — token for token: same
    text, per-token logprobs within bf16 reduction-order noise.

Exits non-zero at the first failed phase; prints one JSON summary line
last and writes it to ``chiprun_out/fourchip_smoke.json``.
"""

from __future__ import annotations

import concurrent.futures
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke as cs  # noqa: E402

# The dispatcher hands each admission batch (<= 32 requests per 50 ms
# window) to ONE replica, the least loaded: it takes four batches in
# flight at once to reach four replicas.
BURST = 128
# TP=4 sums each row-parallel matmul over four shards in another order
# than one chip does; in bf16 that moves a logprob of magnitude ~10 by
# about 1e-2. A different TOKEN moves it by far more.
TP_LOGPROB_TOL = 0.1


def greedy(base: str) -> tuple[str, list[float]]:
    text = cs.post_json(base, "/generate", {
        "prompt": cs.GREEDY_PROMPT, "max_tokens": 16, "temperature": 0,
    })["choices"][0]["text"]
    return text, cs.greedy_stream(base, cs.GREEDY_PROMPT, 16)


def replicas_leg() -> dict:
    args = [*cs.SERVER_ARGS, "--server-num-engines", "4"]
    with cs.serving(args, log_name="fourchip_replicas.log") as (base, setup):
        health = cs.get_json(base, "/health")
        device = cs.device_phase(health)
        engines = health["engines"]
        cs.check(device["count"] == 4 and len(engines) == 4,
                 f"want 4 engines on 4 devices, got {len(engines)} on "
                 f"{device['count']}")
        ids = sorted(e["device_ids"] for e in engines)
        cs.check(ids == [[0], [1], [2], [3]],
                 f"replicas are not on four distinct devices: {ids}")
        in_use = {e["device_ids"][0]: e["device_bytes_in_use"][0]
                  for e in engines}
        cs.check(all(b and b > 0 for b in in_use.values()),
                 f"a chip holds nothing: bytes_in_use {in_use}")
        print(f"PASS four replicas on devices {ids}, bytes in use {in_use}")
        text, logprobs = greedy(base)
        with concurrent.futures.ThreadPoolExecutor(BURST) as pool:
            list(pool.map(lambda i: cs.post_json(base, "/generate", {
                "prompt": f"spread {i} " + "y" * (i % 7) * 20,
                "max_tokens": 32, "temperature": 0}), range(BURST)))
        stats = cs.get_json(base, "/server/stats")
        done = {w["engine_id"]: w["total_processed"]
                for w in stats["worker_statuses"]}
        cs.check(len(done) == 4 and all(n > 0 for n in done.values()),
                 f"requests did not reach all four replicas: {done}")
        print(f"PASS burst of {BURST} spread over all four: {done}")
    return {"setup_s": round(setup, 1), "device_ids": ids,
            "bytes_in_use": in_use, "processed": done, "text": text,
            "logprobs": logprobs}


def tensor_parallel_leg(one_chip: dict) -> dict:
    args = [*cs.SERVER_ARGS, "--engine-tensor-parallel", "4"]
    with cs.serving(args, log_name="fourchip_tp4.log") as (base, setup):
        health = cs.get_json(base, "/health")
        cs.device_phase(health)
        (engine,) = health["engines"]
        cs.check(engine["device_ids"] == [0, 1, 2, 3],
                 f"TP=4 replica holds devices {engine['device_ids']}")
        text, logprobs = greedy(base)
        cs.check(text == one_chip["text"],
                 f"TP=4 greedy text {text!r} != one-chip "
                 f"{one_chip['text']!r}")
        diff = cs.max_abs_diff(logprobs, one_chip["logprobs"])
        cs.check(diff <= TP_LOGPROB_TOL,
                 f"TP=4 greedy stream diverges from one chip: logprobs "
                 f"{logprobs} vs {one_chip['logprobs']}")
        print(f"PASS TP=4 greedy stream equals the one-chip stream "
              f"({len(logprobs)} tokens, max |logprob diff| {diff:.3g})")
        cs.request_phases(base)
    return {"setup_s": round(setup, 1), "device_ids": engine["device_ids"],
            "bytes_in_use": engine["device_bytes_in_use"],
            "attention": engine["attention"], "text": text,
            "logprobs": logprobs,
            "max_logprob_diff_vs_one_chip": round(diff, 4)}


def main() -> int:
    replicas = replicas_leg()
    tp4 = tensor_parallel_leg(replicas)
    summary = {"ok": True, "replicas_x4": replicas, "tensor_parallel_4": tp4}
    line = json.dumps(summary)
    with open(os.path.join(cs.HERE, "chiprun_out", "fourchip_smoke.json"),
              "w") as f:
        f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except cs.SmokeFailure as e:
        print(f"FAIL {e}", file=sys.stderr)
        sys.exit(1)
