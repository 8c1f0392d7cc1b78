"""Native C++ serving-layer components, reached over a C ABI via ctypes.

The reference's serving layer is entirely native (Rust; SURVEY.md §2
language note) — this package is the counterpart tier in our design: the
host-side hot paths (request queue, page allocator) implemented in C++
(native/pqueue.cpp, native/allocator.cpp) behind Python wrappers with the
exact contracts of ``core/queue.py`` and ``engine/kv_cache.py``. The
Python implementations remain the canonical semantics; differential tests
(tests/test_native.py) drive both with the same operation sequences.

The shared library builds on demand with ``make`` (g++, no deps); when a
toolchain is unavailable, ``available()`` is False and callers fall back
to the Python tier.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libdis_tpu_native.so")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        # always run make: its dependency tracking rebuilds the .so from
        # the committed sources after any edit (a no-op when up to date),
        # so a library that loads is never older than its sources. A
        # failed build means the Python tier serves — never a binary left
        # on disk by some earlier build. Running under _lock is
        # deliberate — concurrent first callers must wait for the one
        # build, not race it.
        try:
            subprocess.run(  # distlint: ignore[DL003]
                ["make", "-C", _DIR],
                check=True,
                capture_output=True,
                timeout=120,
            )
            lib = ctypes.CDLL(_LIB_PATH)
            _declare(lib)
        except (OSError, subprocess.SubprocessError, AttributeError) as e:
            logger.warning("native build/load failed (%s); Python tier "
                           "only", e)
            _build_failed = True
            return None
        _lib = lib
        return lib


def _declare(lib: ctypes.CDLL) -> None:
    u64p = ctypes.POINTER(ctypes.c_uint64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    intp = ctypes.POINTER(ctypes.c_int)
    lib.pq_create.restype = ctypes.c_void_p
    lib.pq_create.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_double,
                              ctypes.c_int]
    lib.pq_destroy.argtypes = [ctypes.c_void_p]
    lib.pq_set_config.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
                                  ctypes.c_double, ctypes.c_int]
    lib.pq_enqueue.argtypes = [ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int,
                               ctypes.c_double]
    lib.pq_dequeue_batch.argtypes = [ctypes.c_void_p, u64p, ctypes.c_int]
    lib.pq_dequeue_one.argtypes = [ctypes.c_void_p, u64p]
    lib.pq_depth.argtypes = [ctypes.c_void_p, intp]
    lib.pq_is_accepting.argtypes = [ctypes.c_void_p]
    lib.pq_remove_expired.argtypes = [ctypes.c_void_p, ctypes.c_double, u64p,
                                      ctypes.c_int]
    lib.pq_cancel.argtypes = [ctypes.c_void_p, ctypes.c_uint64]

    lib.pa_create.restype = ctypes.c_void_p
    lib.pa_create.argtypes = [ctypes.c_int, ctypes.c_int]
    lib.pa_destroy.argtypes = [ctypes.c_void_p]
    lib.pa_num_free.argtypes = [ctypes.c_void_p]
    lib.pa_match_prefix.argtypes = [ctypes.c_void_p, i32p, ctypes.c_int, i32p]
    lib.pa_allocate.argtypes = [ctypes.c_void_p, ctypes.c_int, i32p]
    lib.pa_publish.argtypes = [ctypes.c_void_p, i32p, ctypes.c_int, i32p,
                               ctypes.c_int]
    lib.pa_retain.argtypes = [ctypes.c_void_p, i32p, ctypes.c_int]
    lib.pa_release.argtypes = [ctypes.c_void_p, i32p, ctypes.c_int]
    lib.pa_touch.argtypes = [ctypes.c_void_p, i32p, ctypes.c_int]
    lib.pa_evict_below.argtypes = [ctypes.c_void_p, ctypes.c_double]
    lib.pa_stats.argtypes = [ctypes.c_void_p, i64p]

    lib.batcher_create.restype = ctypes.c_void_p
    lib.batcher_create.argtypes = [ctypes.c_void_p, ctypes.c_double,
                                   ctypes.c_int]
    lib.batcher_destroy.argtypes = [ctypes.c_void_p]
    lib.batcher_set_config.argtypes = [ctypes.c_void_p, ctypes.c_double,
                                       ctypes.c_int]
    lib.batcher_set_divisor.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.batcher_pending.argtypes = [ctypes.c_void_p]
    lib.batcher_cancel.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.batcher_poll.argtypes = [ctypes.c_void_p, ctypes.c_double, u64p,
                                 ctypes.c_int]
    lib.batcher_flush.argtypes = [ctypes.c_void_p, u64p, ctypes.c_int]

    u8pp = ctypes.POINTER(ctypes.c_char_p)
    lib.val_token_count.restype = ctypes.c_int64
    lib.val_token_count.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.val_generate.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_double,
        ctypes.c_double, ctypes.c_void_p, i64p,
    ]
    lib.val_chat.argtypes = [
        u8pp, i64p, ctypes.c_int, ctypes.c_int64, ctypes.c_double,
        ctypes.c_double, ctypes.c_void_p, i64p,
    ]
    lib.val_embeddings.argtypes = [
        u8pp, i64p, ctypes.c_int, ctypes.c_void_p, i64p, intp,
    ]


def available() -> bool:
    """True when the native library is built (builds on first call)."""
    return _load() is not None


def loaded() -> bool:
    """True when the native library is serving in this process. Unlike
    ``available()`` it never triggers a build — a status read."""
    return _lib is not None


def _i32arr(vals: Sequence[int]):
    return (ctypes.c_int32 * max(len(vals), 1))(*vals)


class NativePriorityQueue:
    """ctypes façade over native/pqueue.cpp with the exact contract of
    ``core.queue.PriorityQueueManager`` (drop-in for the dispatcher)."""

    def __init__(self, config=None):
        from distributed_inference_server_tpu.core.queue import QueueConfig

        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._config = config or QueueConfig()
        self._ptr = lib.pq_create(
            self._config.high_watermark,
            self._config.low_watermark,
            ctypes.c_double(self._config.request_timeout_s),
            self._config.max_queue_size,
        )
        self._next_handle = 1
        self._by_handle: Dict[int, object] = {}
        self._lock = threading.Lock()

    @property
    def config(self):
        return self._config

    @config.setter
    def config(self, cfg) -> None:
        """Hot-reload (requirements.md:146): pushes the new watermarks/
        timeout/cap down to the native side."""
        self._config = cfg
        self._lib.pq_set_config(
            self._ptr, cfg.high_watermark, cfg.low_watermark,
            ctypes.c_double(cfg.request_timeout_s), cfg.max_queue_size,
        )

    def __del__(self):
        ptr = getattr(self, "_ptr", None)
        if ptr:
            self._lib.pq_destroy(ptr)
            self._ptr = None

    # -- contract ----------------------------------------------------------

    def enqueue(self, request) -> None:
        from distributed_inference_server_tpu.core.errors import QueueFull

        with self._lock:
            handle = self._next_handle
            # Priority is LOW=0..HIGH=2 (types.py); the native queue
            # indexes level 0 = High .. 2 = Low
            rc = self._lib.pq_enqueue(
                self._ptr, handle, 2 - int(request.priority),
                ctypes.c_double(request.enqueued_at),
            )
            if rc != 0:
                raise QueueFull()
            self._next_handle += 1
            self._by_handle[handle] = request

    def dequeue_batch(self, max_count: int) -> List:
        out = (ctypes.c_uint64 * max(max_count, 1))()
        with self._lock:
            n = self._lib.pq_dequeue_batch(self._ptr, out, max_count)
            return [self._by_handle.pop(out[i]) for i in range(n)]

    def dequeue_one(self):
        got = self.dequeue_batch(1)
        return got[0] if got else None

    def queue_depth(self):
        from distributed_inference_server_tpu.core.queue import QueueDepth

        out = (ctypes.c_int * 3)()
        self._lib.pq_depth(self._ptr, out)
        return QueueDepth(high=out[0], normal=out[1], low=out[2],
                          total=out[0] + out[1] + out[2])

    def is_accepting(self) -> bool:
        return bool(self._lib.pq_is_accepting(self._ptr))

    def total_depth(self) -> int:
        return self.queue_depth().total

    def is_empty(self) -> bool:
        return self.total_depth() == 0

    def remove_expired(self, now: Optional[float] = None) -> List:
        now = time.monotonic() if now is None else now
        with self._lock:
            cap = len(self._by_handle) or 1
            out = (ctypes.c_uint64 * cap)()
            n = self._lib.pq_remove_expired(
                self._ptr, ctypes.c_double(now), out, cap
            )
            return [self._by_handle.pop(out[i]) for i in range(min(n, cap))]

    def cancel(self, request_id):
        with self._lock:
            for handle, req in self._by_handle.items():
                if req.id == request_id:
                    if self._lib.pq_cancel(self._ptr, handle):
                        self._by_handle.pop(handle)
                        return req
                    return None
            return None


class NativePageAllocator:
    """ctypes façade over native/allocator.cpp with the contract of
    ``engine.kv_cache.PageAllocator`` (drop-in for the engine)."""

    def __init__(self, cfg):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.cfg = cfg
        self._ptr = lib.pa_create(cfg.num_pages, cfg.page_size)
        # pages drawn onto a device-resident free-list for a looped
        # decode block (kernel looping, docs/PERF.md): tracked Python-
        # side — the native core sees a plain allocate, and returned
        # (never-assigned) pages go back through release()
        self._device_held: set = set()

    def __del__(self):
        ptr = getattr(self, "_ptr", None)
        if ptr:
            self._lib.pa_destroy(ptr)
            self._ptr = None

    def num_free(self) -> int:
        return self._lib.pa_num_free(self._ptr)

    def match_prefix(self, tokens: Sequence[int]) -> Tuple[List[int], int]:
        max_pages = len(tokens) // self.cfg.page_size
        out = (ctypes.c_int32 * max(max_pages, 1))()
        n = self._lib.pa_match_prefix(
            self._ptr, _i32arr(list(tokens)), len(tokens), out
        )
        return [out[i] for i in range(n)], n * self.cfg.page_size

    def allocate(self, n: int) -> List[int]:
        from distributed_inference_server_tpu.core.errors import CacheFull

        out = (ctypes.c_int32 * max(n, 1))()
        if self._lib.pa_allocate(self._ptr, n, out) != 0:
            raise CacheFull()
        return [out[i] for i in range(n)]

    def draw_device(self, n: int) -> List[int]:
        """Contract of ``PageAllocator.draw_device``: move up to ``n``
        pages into the DEVICE-HELD state for a looped decode block's
        on-device free-list; a partial draw never raises. The native
        core has no device-held notion, so the draw is a plain
        allocate() of what fits and the state lives Python-side."""
        from distributed_inference_server_tpu.core.errors import CacheFull

        m = min(n, self.num_free())
        if m <= 0:
            return []
        try:
            pages = self.allocate(m)
        except CacheFull:  # pragma: no cover — num_free() raced
            return []
        self._device_held.update(pages)
        return pages

    def reconcile_device(
        self, claimed: Sequence[int], returned: Sequence[int]
    ) -> None:
        """Contract of ``PageAllocator.reconcile_device``: ``claimed``
        pages joined a row's block table on device and are now plain
        live-held (released later like any allocate()d page);
        ``returned`` pages were never assigned and go back to free."""
        for pid in list(claimed) + list(returned):
            if pid not in self._device_held:
                raise ValueError(
                    f"page {pid} reconciled but not device-held"
                )
            self._device_held.discard(pid)
        if returned:
            self.release(list(returned))

    def device_held(self) -> int:
        return len(self._device_held)

    def publish(self, tokens: Sequence[int], page_ids: Sequence[int]) -> None:
        self._lib.pa_publish(
            self._ptr, _i32arr(list(tokens)), len(tokens),
            _i32arr(list(page_ids)), len(page_ids),
        )

    def retain(self, page_ids: Sequence[int]) -> None:
        self._lib.pa_retain(self._ptr, _i32arr(list(page_ids)), len(page_ids))

    def release(self, page_ids: Sequence[int]) -> None:
        self._lib.pa_release(self._ptr, _i32arr(list(page_ids)), len(page_ids))

    def touch(self, page_ids: Sequence[int]) -> None:
        self._lib.pa_touch(self._ptr, _i32arr(list(page_ids)), len(page_ids))

    def evict_below(self, target_frac: float) -> int:
        return self._lib.pa_evict_below(self._ptr,
                                        ctypes.c_double(target_frac))

    def stats(self):
        from distributed_inference_server_tpu.engine.kv_cache import CacheStats

        out = (ctypes.c_int64 * 6)()
        self._lib.pa_stats(self._ptr, out)
        hits, misses, evictions, total, free, cached = (
            out[0], out[1], out[2], out[3], out[4], out[5],
        )
        return CacheStats(
            hits=int(hits), misses=int(misses), evictions=int(evictions),
            pages_total=int(total), pages_free=int(free),
            pages_cached=int(cached),
            memory_used_frac=1.0 - (free + cached) / total if total else 0.0,
        )

    def hit_rate(self) -> float:
        s = self.stats()
        total = s.hits + s.misses
        return s.hits / total if total else 0.0


__all__ = ["available", "NativePriorityQueue", "NativePageAllocator"]

class NativeAdmissionBatcher:
    """ctypes façade over native/batcher.cpp with the contract of
    ``serving.batcher.AdmissionBatcher`` (drop-in for the dispatcher).
    Requires a ``NativePriorityQueue`` — one native batcher_poll call
    drains the native queue and manages the window with no Python in the
    per-request path; handles resolve back to payloads through the
    queue's handle map only when a batch actually dispatches."""

    def __init__(self, queue: "NativePriorityQueue", config=None):
        from distributed_inference_server_tpu.serving.batcher import (
            BatcherConfig,
        )

        if not isinstance(queue, NativePriorityQueue):
            raise TypeError(
                "NativeAdmissionBatcher requires a NativePriorityQueue"
            )
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.queue = queue
        self._config = config or BatcherConfig()
        self._divisor = 1
        self._ptr = lib.batcher_create(
            queue._ptr, ctypes.c_double(self._config.window_ms),
            self._config.max_batch_size,
        )

    def __del__(self):
        ptr = getattr(self, "_ptr", None)
        if ptr:
            self._lib.batcher_destroy(ptr)
            self._ptr = None

    # -- contract ----------------------------------------------------------

    @property
    def config(self):
        return self._config

    @config.setter
    def config(self, cfg) -> None:
        """Hot-reload (requirements.md:146): window/max apply natively
        from the next poll."""
        self._config = cfg
        self._lib.batcher_set_config(
            self._ptr, ctypes.c_double(cfg.window_ms), cfg.max_batch_size
        )

    @property
    def size_divisor(self) -> int:
        return self._divisor

    @size_divisor.setter
    def size_divisor(self, d: int) -> None:
        self._divisor = d
        self._lib.batcher_set_divisor(self._ptr, int(d))

    def effective_max_batch(self) -> int:
        return max(1, self._config.max_batch_size // max(1, self._divisor))

    def pending_count(self) -> int:
        return self._lib.batcher_pending(self._ptr)

    def cancel(self, request_id):
        """Remove a request still waiting in the batching window
        (Req 5.4). Returns the removed request or None."""
        with self.queue._lock:
            for handle, req in self.queue._by_handle.items():
                if req.id == request_id:
                    if self._lib.batcher_cancel(self._ptr, handle):
                        self.queue._by_handle.pop(handle)
                        return req
                    return None
        return None

    def _resolve(self, out, n):
        with self.queue._lock:
            return [self.queue._by_handle.pop(out[i]) for i in range(n)]

    def poll(self, now: Optional[float] = None):
        from distributed_inference_server_tpu.serving.batcher import (
            AdmissionBatch,
        )
        from distributed_inference_server_tpu.core.types import new_batch_id

        now = time.monotonic() if now is None else now
        cap = max(1, self.effective_max_batch())
        out = (ctypes.c_uint64 * cap)()
        n = self._lib.batcher_poll(
            self._ptr, ctypes.c_double(now), out, cap
        )
        if n <= 0:
            return None
        return AdmissionBatch(new_batch_id(), self._resolve(out, n), now)

    def flush(self, now: Optional[float] = None):
        from distributed_inference_server_tpu.serving.batcher import (
            AdmissionBatch,
        )
        from distributed_inference_server_tpu.core.types import new_batch_id

        now = time.monotonic() if now is None else now
        cap = max(1, self.pending_count())
        out = (ctypes.c_uint64 * cap)()
        n = self._lib.batcher_flush(self._ptr, out, cap)
        if n <= 0:
            return None
        return AdmissionBatch(new_batch_id(), self._resolve(out, n), now)


class _ValLimits(ctypes.Structure):
    _fields_ = [
        ("max_context_tokens", ctypes.c_int64),
        ("max_output_tokens", ctypes.c_int64),
        ("min_temperature", ctypes.c_double),
        ("max_temperature", ctypes.c_double),
        ("min_top_p", ctypes.c_double),
        ("max_top_p", ctypes.c_double),
    ]


class NativeRequestValidator:
    """C++ request validator (native/validator.cpp) with the exact
    decision semantics of ``core/validator.py`` — same check order, same
    ceil(codepoints/4) token estimate, same Unicode-whitespace blank
    rule. The native side handles the hot path (byte scanning + range
    checks on accepted requests); ANY rejection — and any input the C ABI
    cannot represent (lone surrogates, out-of-int64 params) — delegates
    to the Python reference validator, so the raised exceptions are
    identical by construction (differential-tested in
    tests/test_native.py)."""

    def __init__(self, config=None):
        from distributed_inference_server_tpu.core.validator import (
            RequestValidator,
            ValidatorConfig,
        )

        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.config = config or ValidatorConfig()
        self._py = RequestValidator(self.config)
        c = self.config
        self._lim = _ValLimits(
            c.max_context_tokens, c.max_output_tokens,
            c.min_temperature, c.max_temperature,
            c.min_top_p, c.max_top_p,
        )

    @staticmethod
    def _carr(items):
        """(char**, int64*, n) marshalling for a list of UTF-8 strings."""
        n = len(items)
        arr = (ctypes.c_char_p * max(1, n))(*items)
        lens = (ctypes.c_int64 * max(1, n))(*[len(c) for c in items])
        return arr, lens, n

    @staticmethod
    def _clamp64(v: int) -> int:
        # c_int64 marshalling WRAPS out-of-range Python ints (no
        # OverflowError), which could wrap a huge max_tokens into range;
        # clamp so over-limit stays over-limit (rejection path is exact:
        # it re-runs the Python validator on the original value)
        return max(-(2**62), min(int(v), 2**62))

    def token_count(self, text: str) -> int:
        # Python str length IS the codepoint count, so the reference
        # tier's ceil(len/4) is O(1); the native scan only pays off where
        # the blank check rides along (validate_*)
        return self._py.token_count(text)

    def validate_generate(self, request):
        from distributed_inference_server_tpu.core.validator import Validated

        try:
            b = request.prompt.encode("utf-8")
        except UnicodeEncodeError:  # lone surrogates: C ABI can't carry them
            return self._py.validate_generate(request)
        toks = ctypes.c_int64(0)
        rc = self._lib.val_generate(
            b, len(b), self._clamp64(request.max_tokens),
            float(request.temperature), float(request.top_p),
            ctypes.byref(self._lim), ctypes.byref(toks),
        )
        if rc == 0:
            return Validated(request)
        # rejection is the cold path: the Python tier raises the
        # authoritative exception (type AND message) for this request
        return self._py.validate_generate(request)

    def validate_chat(self, request):
        from distributed_inference_server_tpu.core.validator import Validated

        try:
            contents = [m.content.encode("utf-8") for m in request.messages]
        except UnicodeEncodeError:
            return self._py.validate_chat(request)
        arr, lens, n = self._carr(contents)
        toks = ctypes.c_int64(0)
        rc = self._lib.val_chat(
            arr, lens, n, self._clamp64(request.max_tokens),
            float(request.temperature), float(request.top_p),
            ctypes.byref(self._lim), ctypes.byref(toks),
        )
        if rc == 0:
            return Validated(request)
        return self._py.validate_chat(request)

    def validate_embeddings(self, request):
        from distributed_inference_server_tpu.core.validator import Validated

        try:
            inputs = [t.encode("utf-8") for t in request.input_list()]
        except UnicodeEncodeError:
            return self._py.validate_embeddings(request)
        arr, lens, n = self._carr(inputs)
        toks = ctypes.c_int64(0)
        idx = ctypes.c_int(0)
        rc = self._lib.val_embeddings(
            arr, lens, n, ctypes.byref(self._lim), ctypes.byref(toks),
            ctypes.byref(idx),
        )
        if rc == 0:
            return Validated(request)
        return self._py.validate_embeddings(request)


def make_validator(config=None, native: Optional[bool] = None):
    """Pick the validator tier like ``engine._make_allocator``: native C++
    when the library builds (or ``native=True`` forces it), the Python
    reference implementation otherwise."""
    from distributed_inference_server_tpu.core.validator import (
        RequestValidator,
    )

    if native is False:
        return RequestValidator(config)
    if available():
        return NativeRequestValidator(config)
    if native is True:
        raise RuntimeError("native validator forced but library unavailable")
    return RequestValidator(config)
