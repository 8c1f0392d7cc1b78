"""KvHandoff wire-format fuzz: random SequenceExports round-tripped
through the protowire codec (serving/disagg.py export_to_wire /
export_from_wire), plus schema agreement between serving/inference.proto
and the protowire tables — the runtime twin of distlint rule DL005.

Deterministic seeded random (the image ships no hypothesis): failures
reproduce exactly, and the test always runs in tier 1."""

from __future__ import annotations

import random

import pytest

from distributed_inference_server_tpu.engine.engine import (
    SamplingParams,
    SequenceExport,
)
from distributed_inference_server_tpu.engine.kv_cache import (
    KvChunk,
    chunk_crc,
)
from distributed_inference_server_tpu.serving import protowire
from distributed_inference_server_tpu.serving.disagg import (
    HandoffError,
    export_from_wire,
    export_to_wire,
    stream_from_frames,
    stream_to_frames,
)
from tools.lint import proto as protodef
from tools.lint.rules import compare_wire_schema

# code points that exercise 1..4-byte UTF-8, U+FFFD, and ASCII controls
_CHARS = (
    "abc XYZ 0189 \t\n" "äßçñ" "中文日本語" "🙂🚀" "�" "'\"\\{}[]"
)


def _rand_text(rng: random.Random, max_len: int = 40) -> str:
    return "".join(rng.choice(_CHARS) for _ in range(rng.randrange(max_len)))


def _rand_export(rng: random.Random) -> SequenceExport:
    n_tokens = rng.randrange(0, 60)
    token_ids = [rng.randrange(0, 2 ** 32) for _ in range(n_tokens)]
    return SequenceExport(
        request_id=_rand_text(rng, 20) or "req-0",
        token_ids=token_ids,
        prompt_len=rng.randrange(0, 4096),
        seq_len=n_tokens,
        next_token=rng.randrange(0, 2 ** 31),
        params=SamplingParams(
            max_tokens=rng.randrange(1, 8192),
            # full-range doubles: bit-exactness across the handoff is the
            # whole point of the double fields (inference.proto note)
            temperature=rng.choice(
                [0.0, 1.0, rng.random() * 2, 7e-45, 0.6999999999999998]
            ),
            top_p=rng.choice([1.0, rng.random() or 0.5, 0.9]),
            stop_sequences=tuple(
                _rand_text(rng, 8) for _ in range(rng.randrange(3))
            ),
        ),
        output_text=_rand_text(rng, 120),
        emitted_upto=rng.randrange(0, 120),
        emitted_tokens=rng.randrange(0, 8192),
        pending_ids=[rng.randrange(0, 2 ** 20)
                     for _ in range(rng.randrange(4))],
        kv=rng.randbytes(rng.randrange(0, 256)),
        draft_kv=(rng.randbytes(rng.randrange(1, 64))
                  if rng.random() < 0.5 else None),
        source_engine=rng.choice(["", "engine-0", "engine-17"]),
    )


def test_kvhandoff_roundtrip_fuzz():
    rng = random.Random(0xD157)
    for i in range(300):
        exp = _rand_export(rng)
        got = export_from_wire(export_to_wire(exp))
        for attr in ("request_id", "token_ids", "prompt_len", "seq_len",
                     "next_token", "output_text", "emitted_upto",
                     "emitted_tokens", "pending_ids", "kv", "source_engine"):
            assert getattr(got, attr) == getattr(exp, attr), (i, attr)
        # draft_kv is `optional bytes`: absent stays absent (None), never
        # collapses to b""
        assert got.draft_kv == exp.draft_kv, i
        p, q = got.params, exp.params
        assert p.max_tokens == q.max_tokens, i
        # doubles must survive BIT-EXACT (sampled-token identity across
        # the handoff); repr equality catches any float32 truncation
        assert repr(p.temperature) == repr(q.temperature), i
        assert repr(p.top_p) == repr(q.top_p), i
        assert tuple(p.stop_sequences) == tuple(q.stop_sequences), i


def test_kvhandoff_decode_fills_proto3_defaults():
    """An all-defaults frame (zero bytes on the wire) reconstructs the
    full key set with proto3 zero values."""
    d = protowire.decode("KvHandoff", b"")
    assert d["token_ids"] == [] and d["pending_ids"] == []
    assert d["stop_sequences"] == []
    assert d["kv"] == b"" and "draft_kv" not in d
    assert d["temperature"] == 0.0 and d["max_tokens"] == 0
    assert d["request_id"] == "" and d["source_engine"] == ""


def test_kvhandoff_unknown_fields_skipped():
    """Forward compatibility: a frame carrying an unknown field decodes
    cleanly (future senders may extend the message)."""
    base = export_to_wire(_rand_export(random.Random(7)))
    # field 100, length-delimited, 3 payload bytes
    unknown = protowire._key(100, 2) + bytes([3, 1, 2, 3])
    d = protowire.decode("KvHandoff", unknown + base)
    assert d == protowire.decode("KvHandoff", base)


def _rand_chunk(rng: random.Random, index: int, total: int,
                page_start: int) -> KvChunk:
    payload = rng.randbytes(rng.randrange(1, 512))
    return KvChunk(
        index=index, total=total, page_start=page_start,
        page_count=rng.randrange(1, 9), payload=payload,
        crc32=chunk_crc(payload),
    )


def test_kvchunk_and_header_roundtrip_fuzz():
    """Seeded random KvChunk / KvHandoffHeader frames survive the wire
    field-for-field (crc32 is a full-range uint32 varint)."""
    rng = random.Random(0xC4C4)
    for i in range(200):
        c = _rand_chunk(rng, rng.randrange(0, 2 ** 20),
                        rng.randrange(0, 2 ** 20), rng.randrange(0, 2 ** 16))
        d = protowire.decode("KvChunk", protowire.encode("KvChunk", {
            "handoff_id": f"h{i}", "index": c.index, "total": c.total,
            "page_start": c.page_start, "page_count": c.page_count,
            "crc32": c.crc32, "payload": c.payload,
        }))
        assert (d["index"], d["total"], d["page_start"], d["page_count"],
                d["crc32"], d["payload"]) == (
            c.index, c.total, c.page_start, c.page_count, c.crc32,
            c.payload), i
        h = {"handoff_id": f"h{i}", "request_id": _rand_text(rng, 16),
             "wire_quant": rng.choice(["none", "int8"]),
             # trace context (docs/OBSERVABILITY.md): untraced headers
             # keep the fields off the wire; decode fills the defaults
             "trace_id": rng.choice(["", "aabbccdd11223344"]),
             "parent_span_id": rng.choice(["", "5566778899aabbcc"]),
             # fleet KV data plane (serving/fleet_kv.py): stream op tag
             # + geometry; "" / 0 = the legacy in-process framing
             "op": rng.choice(["", "open", "commit", "resume", "fetch"]),
             "engine_id": rng.choice(["", "engine-0"]),
             "prefix_pages": rng.randrange(0, 2 ** 16),
             "total_chunks": rng.randrange(0, 2 ** 12)}
        got = protowire.decode("KvHandoffHeader",
                               protowire.encode("KvHandoffHeader", h))
        assert got == h, i


def _streamed_export(rng: random.Random) -> SequenceExport:
    exp = _rand_export(rng)
    total = rng.randrange(1, 6)
    page_start = 0
    chunks = []
    for i in range(total):
        c = _rand_chunk(rng, i, total, page_start)
        page_start += c.page_count
        chunks.append(c)
    exp.kv_chunks = chunks
    exp.kv = b""
    exp.wire_quant = rng.choice(["none", "int8"])
    return exp


def test_streamed_frames_roundtrip_and_reorder():
    """The header/chunks/state frame sequence reassembles the export
    exactly — including when chunk frames arrive OUT OF ORDER (a real
    transport may reorder per-chunk streams)."""
    rng = random.Random(0x57EA)
    for i in range(50):
        exp = _streamed_export(rng)
        frames = list(stream_to_frames(exp))
        # shuffle the chunk frames only (header first, state anywhere after)
        chunk_frames = frames[1:-1]
        rng.shuffle(chunk_frames)
        got = stream_from_frames(
            [frames[0]] + chunk_frames + [frames[-1]])
        assert [c.index for c in got.kv_chunks] == sorted(
            c.index for c in exp.kv_chunks), i
        assert {c.index: (c.payload, c.crc32, c.page_start, c.page_count,
                          c.total) for c in got.kv_chunks} == {
            c.index: (c.payload, c.crc32, c.page_start, c.page_count,
                      c.total) for c in exp.kv_chunks}, i
        assert got.wire_quant == exp.wire_quant
        assert got.token_ids == exp.token_ids


def test_streamed_frames_truncation_rejected():
    """A stream missing its header or terminal state frame is rejected
    (never silently reassembled), and a truncated chunk frame fails to
    decode."""
    exp = _streamed_export(random.Random(3))
    frames = list(stream_to_frames(exp))
    with pytest.raises(HandoffError):
        stream_from_frames(frames[1:])  # header dropped
    with pytest.raises(HandoffError):
        stream_from_frames(frames[:-1])  # state dropped
    kind, data = frames[1]  # a KvChunk frame cut mid-payload
    with pytest.raises(ValueError):
        protowire.decode("KvChunk", data[: len(data) // 2])


def test_kvchunk_crc_corruption_detected():
    """A flipped payload byte survives protowire (payload is opaque
    bytes) but fails the crc check the import session applies."""
    c = _rand_chunk(random.Random(9), 0, 1, 0)
    wire = protowire.encode("KvChunk", {
        "handoff_id": "h", "index": c.index, "total": c.total,
        "page_start": c.page_start, "page_count": c.page_count,
        "crc32": c.crc32, "payload": c.payload[:-1]
        + bytes([c.payload[-1] ^ 0xFF]),
    })
    d = protowire.decode("KvChunk", wire)
    assert chunk_crc(d["payload"]) != d["crc32"]


def test_kvchunk_unknown_fields_skipped():
    """Forward compatibility for the chunk frame: unknown fields are
    skipped, known fields decode unchanged."""
    c = _rand_chunk(random.Random(11), 2, 4, 8)
    base = protowire.encode("KvChunk", {
        "handoff_id": "h", "index": c.index, "total": c.total,
        "page_start": c.page_start, "page_count": c.page_count,
        "crc32": c.crc32, "payload": c.payload,
    })
    unknown = protowire._key(99, 2) + bytes([4, 9, 9, 9, 9])
    assert protowire.decode("KvChunk", unknown + base) == \
        protowire.decode("KvChunk", base)


def test_kv_stream_result_roundtrip_fuzz():
    """KvStreamResult — the data-channel per-stream terminal status
    frame (serving/fleet_kv.py) — survives the wire field-for-field."""
    rng = random.Random(0xDA7A)
    for i in range(200):
        msg = {
            "stream_id": _rand_text(rng, 24) or f"s{i}",
            "op": rng.choice(["open", "commit", "resume", "fetch",
                              "abort"]),
            "ok": rng.random() < 0.5,
            "error": rng.choice(["", "torn stream", _rand_text(rng, 40)]),
            "depth": rng.randrange(0, 2 ** 20),
            "engine_id": rng.choice(["", "engine-0", "engine-17"]),
        }
        got = protowire.decode("KvStreamResult",
                               protowire.encode("KvStreamResult", msg))
        assert got == msg, i


def test_kv_stream_result_truncation_and_unknown_fields():
    """Data-channel framing hardening: a result frame cut mid-field is
    rejected (never a plausible-but-wrong decode), and unknown fields
    skip cleanly (forward compatibility for future stream ops)."""
    base = protowire.encode("KvStreamResult", {
        "stream_id": "req-77", "op": "fetch", "ok": True,
        "error": "", "depth": 9, "engine_id": "engine-1",
    })
    with pytest.raises(ValueError):
        protowire.decode("KvStreamResult", base[: len(base) - 3])
    unknown = protowire._key(90, 2) + bytes([2, 7, 7])
    assert protowire.decode("KvStreamResult", unknown + base) == \
        protowire.decode("KvStreamResult", base)


def test_kv_stream_result_decode_fills_defaults():
    d = protowire.decode("KvStreamResult", b"")
    assert d == {"stream_id": "", "op": "", "ok": False, "error": "",
                 "depth": 0, "engine_id": ""}


def test_fleet_heartbeat_data_port_roundtrip():
    """The member's KV data listener port rides every heartbeat
    (serving/fleet_kv.py); 0 (no data plane) stays off the wire and
    decodes back as the proto3 default."""
    on = protowire.decode("FleetHeartbeat", protowire.encode(
        "FleetHeartbeat",
        {"member_id": "w1", "seq": 3, "engines": [], "data_port": 40123},
    ))
    assert on["data_port"] == 40123
    off = protowire.decode("FleetHeartbeat", protowire.encode(
        "FleetHeartbeat", {"member_id": "w1", "seq": 4, "engines": []},
    ))
    assert off["data_port"] == 0


def test_kv_prefix_fetch_engine_id_roundtrip():
    """The data-plane fetch request targets a member-local engine;
    legacy (in-process) requests leave the field off the wire."""
    d = protowire.decode("KvPrefixFetch", protowire.encode(
        "KvPrefixFetch",
        {"request_id": "r1", "hashes": [1, 2 ** 63 + 1], "chunk_pages": 8,
         "wire_quant": "int8", "engine_id": "engine-2"},
    ))
    assert d["engine_id"] == "engine-2"
    assert d["hashes"] == [1, 2 ** 63 + 1]


def test_total_processed_uint64_roundtrip():
    """EngineStatus.total_processed is uint64 in inference.proto; counts
    past 2^63 must not decode negative (distlint DL005 fix)."""
    big = 2 ** 63 + 5
    data = protowire.encode("EngineStatus", {
        "engine_id": "e", "healthy": True, "total_processed": big,
    })
    assert protowire.decode("EngineStatus", data)["total_processed"] == big


def test_wire_schema_field_numbers_agree_with_proto():
    """Field-number/type/cardinality agreement between inference.proto
    and the live protowire tables — the runtime half of DL005, pinned
    here so a drift fails even if someone disables the linter."""
    import distributed_inference_server_tpu as pkg
    from pathlib import Path

    proto_path = (Path(pkg.__file__).parent / "serving" / "inference.proto")
    schema = protodef.parse_file(proto_path)
    diffs = compare_wire_schema(schema, protowire.MESSAGES, protowire.ENUMS)
    assert diffs == [], diffs
    # and KvHandoff specifically covers every SequenceExport field
    kv = schema.messages["KvHandoff"]
    names = {f.name for f in kv.fields.values()}
    assert {"request_id", "token_ids", "kv", "draft_kv", "temperature",
            "top_p", "stop_sequences", "source_engine"} <= names


def _rand_telemetry(rng: random.Random) -> dict:
    """A random FleetTelemetry frame in the canonical wire-dict form
    (serving/teledigest.py: sorted epochs, sorted parallel arrays)."""
    digests = []
    for d in range(rng.randrange(0, 5)):
        epochs = []
        base_epoch = rng.randrange(0, 2 ** 40)
        for k in sorted(rng.sample(range(16), rng.randrange(0, 5))):
            buckets = sorted(rng.sample(range(300), rng.randrange(0, 6)))
            counts = [rng.randrange(1, 2 ** 50) for _ in buckets]
            epochs.append({
                "index": base_epoch + k,
                "buckets": buckets,
                "counts": counts,
                "n": sum(counts) + rng.randrange(0, 10),
                "sum_us": rng.randrange(0, 2 ** 60),
            })
        digests.append({
            "name": rng.choice(["ttft_ms", "tbt_ms", "step_ms.mixed",
                                f"series_{d}"]),
            "epoch_s": rng.choice([1.0, 5.0, 30.0]),
            "ring": epochs,
        })
    counters = [
        {"name": f"step.engine-{i}.prefill.tokens",
         "value": rng.random() * 1e12}
        for i in range(rng.randrange(0, 4))
    ]
    return {"member_id": _rand_text(rng, 16) or "m0",
            "digests": digests, "counters": counters}


def test_fleet_telemetry_roundtrip_fuzz():
    """FleetTelemetry — the heartbeat-piggybacked perf-digest frame
    (fleet-wire kind 5, serving/teledigest.py) — survives the wire
    field-for-field: epoch indices, bucket/count arrays, exact sums."""
    rng = random.Random(0x7E1E)
    for i in range(120):
        msg = _rand_telemetry(rng)
        got = protowire.decode("FleetTelemetry",
                               protowire.encode("FleetTelemetry", msg))
        assert got == msg, i


def test_fleet_telemetry_truncation_and_unknown_fields():
    """A telemetry frame cut mid-field is rejected (never a
    plausible-but-wrong digest), and unknown fields skip cleanly."""
    rng = random.Random(0x7E1F)
    msg = _rand_telemetry(rng)
    while not msg["digests"]:
        msg = _rand_telemetry(rng)
    base = protowire.encode("FleetTelemetry", msg)
    with pytest.raises(ValueError):
        protowire.decode("FleetTelemetry", base[: len(base) - 2])
    unknown = protowire._key(88, 2) + bytes([3, 1, 2, 3])
    assert protowire.decode("FleetTelemetry", unknown + base) == \
        protowire.decode("FleetTelemetry", base)


def test_tele_digest_wire_matches_live_digest():
    """A live WindowedDigest's to_wire() dict IS the TeleDigest wire
    message: encode/decode returns it unchanged (canonical sorted
    arrays survive), so merge identity holds across the wire."""
    from distributed_inference_server_tpu.serving.teledigest import (
        WindowedDigest,
        merge_digests,
    )

    rng = random.Random(0x7E20)
    dig = WindowedDigest(epoch_s=5.0, window_s=60.0)
    for _ in range(300):
        dig.observe(rng.random() * 1000.0,
                    now=1_000_000.0 + rng.random() * 40.0)
    wire = dig.to_wire("ttft_ms")
    got = protowire.decode("TeleDigest",
                           protowire.encode("TeleDigest", wire))
    assert got == wire
    # and a wire round-trip is transparent to the merge algebra
    assert merge_digests([got, got]) == merge_digests([wire, wire])


# ---------------------------------------------------------------------------
# KvIntro — the mesh introduction frame (fleet-wire kind 6)
# ---------------------------------------------------------------------------


def _rand_intro(rng: random.Random) -> dict:
    return {
        "member_id": _rand_text(rng, 24) or "m0",
        "host": rng.choice(["127.0.0.1", "10.1.2.3", "fe80::1%eth0",
                            _rand_text(rng, 16)]),
        "data_port": rng.randrange(0, 65536),
        "max_streams": rng.randrange(0, 64),
        "gone": rng.random() < 0.3,
        # registry HA: the broker stamps its fencing epoch on re-brokered
        # intros (serving/fleet_ha.py)
        "epoch": rng.randrange(0, 1 << 31),
    }


def test_kv_intro_roundtrip_fuzz():
    """KvIntro — the registry's mesh introduction broker frame
    (fleet-wire kind 6, serving/fleet_mesh.py) — survives the wire
    field-for-field, including zero ports and gone retractions."""
    rng = random.Random(0x7E21)
    for i in range(200):
        msg = _rand_intro(rng)
        got = protowire.decode("KvIntro",
                               protowire.encode("KvIntro", msg))
        assert got == msg, i


def test_kv_intro_truncation_and_unknown_fields():
    """An intro cut mid-field is rejected — a member must never dial a
    half-parsed endpoint — and unknown fields skip cleanly (newer
    registries can extend the introduction without breaking members)."""
    rng = random.Random(0x7E22)
    msg = _rand_intro(rng)
    msg["gone"] = True  # a trailing one-byte field to cut the value off
    base = protowire.encode("KvIntro", msg)
    with pytest.raises(ValueError):
        protowire.decode("KvIntro", base[: len(base) - 1])
    unknown = protowire._key(77, 2) + bytes([4, 9, 9, 9, 9])
    assert protowire.decode("KvIntro", unknown + base) == \
        protowire.decode("KvIntro", base)


def test_kv_intro_decode_fills_proto3_defaults():
    """A minimal intro (member_id only) decodes with every other field
    at its proto3 default — absent gone reads False, absent port 0, so
    MeshClient's gone-or-invalid-endpoint check is well-defined."""
    got = protowire.decode(
        "KvIntro", protowire.encode("KvIntro", {"member_id": "m1"}))
    assert got == {"member_id": "m1", "host": "", "data_port": 0,
                   "max_streams": 0, "gone": False, "epoch": 0}


def test_latent_kind3_chunk_wire_fuzz():
    """Kind-3 (latent) payloads ride the SAME self-describing KvChunk
    frame (ISSUE 20 — no proto schema change, DL005 untouched): real
    latent chunks round-trip protowire field-for-field in any order, a
    truncated frame fails to decode, and a payload truncated *with a
    recomputed crc* still rejects at the import session (the kind-3
    buffer-length check), releasing every reserved page."""
    import dataclasses
    import zlib

    import jax.numpy as jnp
    import numpy as np

    from distributed_inference_server_tpu.core.errors import (
        CacheDeserializationError,
    )
    from distributed_inference_server_tpu.engine.kv_cache import (
        KvImportSession,
        LatentCodec,
        PageAllocator,
        PagedCacheConfig,
        PagedKVState,
        serialize_kv_chunks,
    )
    from distributed_inference_server_tpu.models.configs import TINY

    cfg = PagedCacheConfig(num_pages=16, page_size=4, max_pages_per_seq=8)
    state = PagedKVState.create(TINY, cfg, dtype=jnp.float32)
    nprng = np.random.default_rng(0x7A13)
    k = nprng.standard_normal(state.k.shape).astype(np.float32)
    v = nprng.standard_normal(state.v.shape).astype(np.float32)
    state.k, state.v = jnp.asarray(k), jnp.asarray(v)
    codec = LatentCodec.calibrate(k, v, rank=4)

    rng = random.Random(0x7A14)
    for wire_quant in ("latent", "latent_int8"):
        pages = rng.sample(range(16), 4)
        chunks = list(serialize_kv_chunks(state, pages, cfg.page_size,
                                          chunk_pages=1,
                                          wire_quant=wire_quant,
                                          codec=codec))
        chunks = [dataclasses.replace(c, total=len(chunks))
                  for c in chunks]
        # protowire round-trip, arbitrary arrival order
        wired = []
        for c in chunks:
            d = protowire.decode("KvChunk", protowire.encode("KvChunk", {
                "handoff_id": "h", "index": c.index, "total": c.total,
                "page_start": c.page_start, "page_count": c.page_count,
                "crc32": c.crc32, "payload": c.payload,
            }))
            assert chunk_crc(d["payload"]) == d["crc32"]
            wired.append(KvChunk(index=d["index"], total=d["total"],
                                 page_start=d["page_start"],
                                 page_count=d["page_count"],
                                 payload=d["payload"], crc32=d["crc32"]))
        rng.shuffle(wired)
        fresh = PagedKVState.create(TINY, cfg, dtype=jnp.float32)
        alloc = PageAllocator(cfg)
        sess = KvImportSession(fresh, alloc, cfg.page_size, codec=codec)
        sess.reserve(len(pages))
        for c in wired:
            sess.add_chunk(c)
        restored, _ = sess.finish(fresh, list(range(len(pages) * 4)))

        # a frame cut mid-payload never decodes
        frame = protowire.encode("KvChunk", {
            "handoff_id": "h", "index": 0, "total": len(chunks),
            "page_start": 0, "page_count": 1,
            "crc32": chunks[0].crc32, "payload": chunks[0].payload,
        })
        with pytest.raises(ValueError):
            protowire.decode("KvChunk", frame[: len(frame) // 2])

        # truncated payload with a RECOMPUTED crc: survives the wire,
        # rejects at the kind-3 decode, zero pages leaked
        cut = chunks[0].payload[: len(chunks[0].payload) - 8]
        bad = dataclasses.replace(chunks[0], payload=cut,
                                  crc32=zlib.crc32(cut) & 0xFFFFFFFF)
        alloc2 = PageAllocator(cfg)
        free0 = alloc2.num_free()
        sess2 = KvImportSession(PagedKVState.create(TINY, cfg,
                                                    dtype=jnp.float32),
                                alloc2, cfg.page_size, codec=codec)
        sess2.reserve(len(pages))
        with pytest.raises(CacheDeserializationError):
            sess2.add_chunk(bad)
        sess2.abort()
        assert alloc2.num_free() == free0
