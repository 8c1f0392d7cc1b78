"""Differential tests: native C++ components vs the canonical Python tier.

The same randomized operation sequences drive both implementations; every
observable (returned values, depths, stats, backpressure state) must be
identical. This is the conformance story for the native serving layer —
the Python modules carry the reference-derived property tests, and these
prove the C++ twins behave identically."""

import random

import pytest

from distributed_inference_server_tpu import native
from distributed_inference_server_tpu.core.errors import CacheFull, QueueFull
from distributed_inference_server_tpu.core.queue import (
    PriorityQueueManager,
    QueueConfig,
    QueuedRequest,
)
from distributed_inference_server_tpu.core.types import Priority
from distributed_inference_server_tpu.engine.kv_cache import (
    PageAllocator,
    PagedCacheConfig,
)

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native toolchain unavailable"
)


def test_failed_build_never_serves_a_library_left_on_disk(monkeypatch):
    """The tier is built from the committed sources at first use or the
    Python tier serves: when the build fails, a library some earlier
    build left on disk (older than, or unrelated to, today's sources) is
    NOT loaded."""
    import os
    import subprocess

    assert os.path.exists(native._LIB_PATH)  # available() built it

    def failing_make(cmd, **_kw):
        raise subprocess.CalledProcessError(2, cmd)

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_failed", False)
    monkeypatch.setattr(native.subprocess, "run", failing_make)
    assert native._load() is None
    assert not native.loaded() and not native.available()


def test_loaded_library_is_not_older_than_its_sources():
    import glob
    import os

    assert native.loaded()
    built = os.stat(native._LIB_PATH).st_mtime
    for src in glob.glob(os.path.join(native._DIR, "*.cpp")):
        if os.path.basename(src) != "stress.cpp":  # not part of the .so
            assert built >= os.stat(src).st_mtime, src


def _req(i: int, prio: Priority, t: float):
    return QueuedRequest(id=f"r{i}", data=i, priority=prio, enqueued_at=t)


def test_queue_differential_random_ops():
    cfg = QueueConfig(high_watermark=30, low_watermark=15,
                      request_timeout_s=10.0, max_queue_size=60)
    py = PriorityQueueManager(cfg)
    cc = native.NativePriorityQueue(cfg)
    rnd = random.Random(0)
    now = 0.0
    seq = 0
    for _ in range(3000):
        op = rnd.random()
        now += rnd.random() * 0.5
        if op < 0.45:
            seq += 1
            prio = rnd.choice(list(Priority))
            r1 = _req(seq, prio, now)
            r2 = _req(seq, prio, now)
            outcomes = []
            for q, r in ((py, r1), (cc, r2)):
                try:
                    q.enqueue(r)
                    outcomes.append("ok")
                except QueueFull:
                    outcomes.append("full")
            assert outcomes[0] == outcomes[1], f"enqueue diverged at {seq}"
        elif op < 0.7:
            n = rnd.randint(1, 8)
            a = [r.id for r in py.dequeue_batch(n)]
            b = [r.id for r in cc.dequeue_batch(n)]
            assert a == b
        elif op < 0.8:
            a = py.dequeue_one()
            b = cc.dequeue_one()
            assert (a.id if a else None) == (b.id if b else None)
        elif op < 0.9:
            a = sorted(r.id for r in py.remove_expired(now))
            b = sorted(r.id for r in cc.remove_expired(now))
            assert a == b
        else:
            victim = f"r{rnd.randint(max(1, seq - 20), seq + 1)}"
            a = py.cancel(victim)
            b = cc.cancel(victim)
            assert (a.id if a else None) == (b.id if b else None)
        assert py.queue_depth() == cc.queue_depth()
        assert py.is_accepting() == cc.is_accepting()


def test_queue_backpressure_hysteresis_native():
    """Property 7 directly against the native queue."""
    cfg = QueueConfig(high_watermark=10, low_watermark=5,
                      request_timeout_s=30.0, max_queue_size=100)
    q = native.NativePriorityQueue(cfg)
    for i in range(10):
        q.enqueue(_req(i, Priority.NORMAL, 0.0))
    assert q.is_accepting()  # at watermark, not above
    q.enqueue(_req(99, Priority.NORMAL, 0.0))  # 11 > 10
    assert not q.is_accepting()  # crossed high watermark
    with pytest.raises(QueueFull):
        q.enqueue(_req(100, Priority.NORMAL, 0.0))
    while q.total_depth() >= 5:
        q.dequeue_one()
    assert q.is_accepting()  # released below low watermark


def test_allocator_differential_random_ops():
    cfg = PagedCacheConfig(num_pages=24, page_size=4, max_pages_per_seq=8)
    py = PageAllocator(cfg)
    cc = native.NativePageAllocator(cfg)
    rnd = random.Random(1)
    # sequences: token list + page ids currently held, mirrored across impls
    held_py = []  # list of (tokens, pages)
    held_cc = []
    for step in range(2000):
        op = rnd.random()
        if op < 0.35:  # admit a sequence: match prefix then allocate rest
            n_tokens = rnd.randint(1, 28)
            tokens = [rnd.randint(0, 5) for _ in range(n_tokens)]
            res = []
            for impl, held in ((py, held_py), (cc, held_cc)):
                shared, matched = impl.match_prefix(tokens)
                needed = -(-(n_tokens) // cfg.page_size) - len(shared)
                try:
                    fresh = impl.allocate(needed)
                    impl.publish(tokens, shared + fresh)
                    held.append((tokens, shared + fresh))
                    res.append(("ok", shared, matched, fresh))
                except CacheFull:
                    impl.release(shared)
                    res.append(("full", shared, matched, None))
            assert res[0] == res[1], f"admit diverged at step {step}"
        elif op < 0.75 and held_py:  # finish a sequence
            i = rnd.randrange(len(held_py))
            _, pages_py = held_py.pop(i)
            _, pages_cc = held_cc.pop(i)
            py.release(pages_py)
            cc.release(pages_cc)
        elif op < 0.85 and held_py:  # touch
            i = rnd.randrange(len(held_py))
            py.touch(held_py[i][1])
            cc.touch(held_cc[i][1])
        elif op < 0.95:
            frac = rnd.random()
            assert py.evict_below(frac) == cc.evict_below(frac)
        else:
            assert py.num_free() == cc.num_free()
        s_py, s_cc = py.stats(), cc.stats()
        assert (s_py.hits, s_py.misses, s_py.evictions, s_py.pages_free,
                s_py.pages_cached) == (
            s_cc.hits, s_cc.misses, s_cc.evictions, s_cc.pages_free,
            s_cc.pages_cached,
        ), f"stats diverged at step {step}"


def test_allocator_prefix_reuse_native():
    """Property 9 against the native allocator: identical prompts share
    full pages."""
    cfg = PagedCacheConfig(num_pages=16, page_size=4, max_pages_per_seq=8)
    a = native.NativePageAllocator(cfg)
    tokens = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    shared, matched = a.match_prefix(tokens)
    assert (shared, matched) == ([], 0)
    fresh = a.allocate(3)
    a.publish(tokens, fresh)
    shared2, matched2 = a.match_prefix(tokens)
    assert shared2 == fresh[:2]  # two FULL pages (8 of 9 tokens)
    assert matched2 == 8
    a.release(shared2)
    a.release(fresh)
    # all pages released -> cached, reclaimable
    assert a.num_free() == cfg.num_pages


def test_engine_runs_on_native_allocator():
    """End-to-end: the continuous-batching engine with the native page
    allocator produces the same tokens as with the Python allocator."""
    import jax
    import jax.numpy as jnp

    from distributed_inference_server_tpu.engine.engine import (
        EngineConfig,
        LLMEngine,
        SamplingParams,
    )
    from distributed_inference_server_tpu.models import llama
    from distributed_inference_server_tpu.models.configs import TINY
    from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer

    params = llama.init_params(jax.random.PRNGKey(0), TINY, dtype=jnp.float32)
    tok = ByteTokenizer()
    results = {}
    for use_native in (False, True):
        eng = LLMEngine(
            params, TINY, tok,
            EngineConfig(
                max_batch=2, prefill_buckets=(8, 32),
                paged=PagedCacheConfig(num_pages=32, page_size=4,
                                       max_pages_per_seq=8),
                native_allocator=use_native,
            ),
            dtype=jnp.float32,
        )
        assert ("Native" in type(eng.allocator).__name__) == use_native
        eng.add_request("r", tok.encode("native!"),
                        SamplingParams(max_tokens=8, temperature=0.0))
        toks = []
        while eng.has_work():
            for o in eng.step():
                if o.token_id is not None:
                    toks.append(o.token_id)
        results[use_native] = toks
    assert results[True] == results[False]
    assert len(results[True]) == 8


# ---------------------------------------------------------------------------
# admission batcher (native/batcher.cpp vs serving/batcher.py)
# ---------------------------------------------------------------------------


def _batcher_pair(window_ms=50.0, max_batch=4, qcfg=None):
    from distributed_inference_server_tpu.serving.batcher import (
        AdmissionBatcher,
        BatcherConfig,
    )

    qcfg = qcfg or QueueConfig(high_watermark=100, low_watermark=50,
                               request_timeout_s=60.0, max_queue_size=200)
    bcfg = BatcherConfig(window_ms=window_ms, max_batch_size=max_batch)
    pyq = PriorityQueueManager(qcfg)
    ccq = native.NativePriorityQueue(qcfg)
    return (
        (pyq, AdmissionBatcher(pyq, bcfg)),
        (ccq, native.NativeAdmissionBatcher(ccq, bcfg)),
    )


def _ids(batch):
    return [r.id for r in batch.requests] if batch else None


def test_batcher_differential_random_ops():
    (pyq, pyb), (ccq, ccb) = _batcher_pair()
    rnd = random.Random(7)
    now = 0.0
    seq = 0
    for _ in range(2000):
        op = rnd.random()
        now += rnd.random() * 0.02
        if op < 0.5:
            seq += 1
            prio = rnd.choice(list(Priority))
            pyq.enqueue(_req(seq, prio, now))
            ccq.enqueue(_req(seq, prio, now))
        elif op < 0.85:
            assert _ids(pyb.poll(now)) == _ids(ccb.poll(now)), \
                f"poll diverged at step {seq}"
            assert pyb.pending_count() == ccb.pending_count()
        elif op < 0.95 and seq:
            rid = f"r{rnd.randint(max(1, seq - 5), seq)}"
            got = (pyb.cancel(rid) is not None,
                   ccb.cancel(rid) is not None)
            assert got[0] == got[1], f"cancel diverged on {rid}"
        else:
            assert _ids(pyb.flush(now)) == _ids(ccb.flush(now))
    assert _ids(pyb.flush(now)) == _ids(ccb.flush(now))


def test_batcher_window_expiry_native():
    (_, _), (ccq, ccb) = _batcher_pair(window_ms=50.0, max_batch=8)
    ccq.enqueue(_req(1, Priority.NORMAL, 0.0))
    assert ccb.poll(0.0) is None  # window opens, not expired
    assert ccb.pending_count() == 1
    assert ccb.poll(0.049) is None
    batch = ccb.poll(0.051)  # 51ms >= 50ms window
    assert _ids(batch) == ["r1"]
    assert ccb.pending_count() == 0


def test_batcher_size_dispatch_and_priority_order_native():
    (_, _), (ccq, ccb) = _batcher_pair(max_batch=3)
    ccq.enqueue(_req(1, Priority.LOW, 0.0))
    ccq.enqueue(_req(2, Priority.HIGH, 0.0))
    ccq.enqueue(_req(3, Priority.NORMAL, 0.0))
    batch = ccb.poll(0.0)  # size cap reached -> immediate dispatch
    assert _ids(batch) == ["r2", "r3", "r1"]  # strict priority order


def test_batcher_divisor_and_hot_reload_native():
    from distributed_inference_server_tpu.serving.batcher import BatcherConfig

    (_, _), (ccq, ccb) = _batcher_pair(max_batch=4)
    ccb.size_divisor = 2  # degradation ladder: effective cap 2
    for i in range(1, 4):
        ccq.enqueue(_req(i, Priority.NORMAL, 0.0))
    assert _ids(ccb.poll(0.0)) == ["r1", "r2"]
    ccb.size_divisor = 1
    ccb.config = BatcherConfig(window_ms=1.0, max_batch_size=4)
    assert ccb.poll(0.0) is None  # r3 pending, window reopened
    assert _ids(ccb.poll(0.01)) == ["r3"]  # 10ms >= 1ms window


def test_dispatcher_uses_native_batcher_with_native_queue():
    from distributed_inference_server_tpu.serving.dispatcher import (
        _make_batcher,
        _make_queue,
    )

    q = _make_queue(None, True)
    b = _make_batcher(q, None)
    assert isinstance(b, native.NativeAdmissionBatcher)
    q2 = _make_queue(None, False)
    b2 = _make_batcher(q2, None)
    from distributed_inference_server_tpu.serving.batcher import (
        AdmissionBatcher,
    )

    assert isinstance(b2, AdmissionBatcher)


# ---------------------------------------------------------------------------
# race detection (SURVEY §5): TSan-instrumented native stress harness
# ---------------------------------------------------------------------------


def _run_stress(target: str, env_extra=None):
    import os
    import subprocess

    d = os.path.dirname(os.path.abspath(native.__file__))
    build = subprocess.run(["make", "-C", d, target],
                           capture_output=True, timeout=300)
    if build.returncode != 0:
        pytest.skip(f"{target} build unavailable: "
                    f"{build.stderr.decode()[-200:]}")
    env = dict(os.environ, **(env_extra or {}))
    run = subprocess.run([os.path.join(d, target)], capture_output=True,
                         timeout=600, env=env)
    assert run.returncode == 0, (
        f"{target} failed:\n{run.stdout.decode()[-1000:]}\n"
        f"{run.stderr.decode()[-3000:]}"
    )
    assert b"stress OK" in run.stdout


def test_native_stress_tsan():
    """The whole native tier (queue + batcher + allocator) hammered from
    concurrent threads under ThreadSanitizer; any data race aborts."""
    _run_stress("stress_tsan", {"TSAN_OPTIONS": "halt_on_error=1"})


def test_native_stress_plain():
    _run_stress("stress_plain")


# ---------------------------------------------------------------------------
# validator (native/validator.cpp vs core/validator.py)
# ---------------------------------------------------------------------------


def _validators():
    from distributed_inference_server_tpu.core.validator import (
        RequestValidator,
        ValidatorConfig,
    )

    cfg = ValidatorConfig(max_context_tokens=64, max_output_tokens=32)
    return RequestValidator(cfg), native.NativeRequestValidator(cfg)


def _outcome(fn, req):
    try:
        return ("ok", type(fn(req).into_inner()).__name__)
    except Exception as e:  # compared by type AND message
        return (type(e).__name__, str(e))


def test_validator_differential_generate():
    from distributed_inference_server_tpu.core.models import GenerateRequest

    py, nat = _validators()
    rng = random.Random(7)
    texts = [
        "", " ", "\t\n", "ok", "x" * 255, "x" * 256, "x" * 257, "x" * 1000,
        "héllo wörld", "　", "    ", "a b", "🙂" * 70,
        "mixed 🙂 ascii and ünïcode",
    ]
    for _ in range(300):
        req = GenerateRequest(
            prompt=rng.choice(texts),
            max_tokens=rng.choice([-1, 0, 1, 32, 33, 4096]),
            temperature=rng.choice([-0.1, 0.0, 1.0, 2.0, 2.1]),
            top_p=rng.choice([-0.1, 0.0, 0.5, 1.0, 1.01]),
        )
        assert _outcome(py.validate_generate, req) == _outcome(
            nat.validate_generate, req
        ), req


def test_validator_differential_chat_and_embeddings():
    from distributed_inference_server_tpu.core.models import (
        ChatMessage,
        ChatRequest,
        EmbeddingsRequest,
        Role,
    )

    py, nat = _validators()
    rng = random.Random(11)
    contents = ["", "  ", "hello", "x" * 200, "ü" * 100, "　 "]
    for _ in range(200):
        msgs = [
            ChatMessage(role=Role.USER, content=rng.choice(contents))
            for _ in range(rng.randint(0, 4))
        ]
        req = ChatRequest(
            messages=msgs,
            max_tokens=rng.choice([1, 32, 64]),
            temperature=rng.choice([0.0, 1.0, 3.0]),
            top_p=1.0,
        )
        assert _outcome(py.validate_chat, req) == _outcome(
            nat.validate_chat, req
        ), req
    for _ in range(200):
        n = rng.randint(0, 4)
        inputs = [rng.choice(contents) for _ in range(n)]
        req = EmbeddingsRequest(input=inputs if n != 1 else inputs[0])
        assert _outcome(py.validate_embeddings, req) == _outcome(
            nat.validate_embeddings, req
        ), req


def test_validator_token_count_parity_unicode():
    py, nat = _validators()
    for s in ["", "a", "abc", "abcd", "abcde", "héllo", "🙂" * 9,
              "　" * 7, "mixed 🙂 text"]:
        assert py.token_count(s) == nat.token_count(s), s


def test_server_uses_native_validator_when_available():
    from distributed_inference_server_tpu.native import make_validator

    v = make_validator()
    assert type(v).__name__ == "NativeRequestValidator"


def test_validator_huge_max_tokens_not_wrapped():
    """ctypes c_int64 wraps out-of-range ints silently; a 2^64+32
    max_tokens must still be rejected exactly like the Python tier."""
    from distributed_inference_server_tpu.core.models import GenerateRequest

    py, nat = _validators()
    req = GenerateRequest(prompt="ok", max_tokens=2**64 + 32)
    assert _outcome(py.validate_generate, req) == _outcome(
        nat.validate_generate, req
    )


def test_validator_lone_surrogate_delegates():
    """json.loads produces lone-surrogate strings; UTF-8 encoding fails,
    so the native tier must delegate instead of raising
    UnicodeEncodeError (which the HTTP error middleware can't map)."""
    from distributed_inference_server_tpu.core.models import GenerateRequest

    py, nat = _validators()
    req = GenerateRequest(prompt="\ud800 hello", max_tokens=4)
    assert _outcome(py.validate_generate, req) == _outcome(
        nat.validate_generate, req
    )
