"""Run-to-completion looped decode blocks (ISSUE 19, kernel looping;
EngineConfig.loop_to_completion): greedy token identity against the
fixed-K path across mixed bursts, mid-block EOS, free-list exhaustion,
aborts and handoff overlap; the on-device page free-list's draw/claim/
reconcile conservation; speculative decoding composed INSIDE the loop;
and the degradation cap hook."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_server_tpu.engine.engine import (
    EngineConfig,
    LLMEngine,
    SamplingParams,
)
from distributed_inference_server_tpu.engine.kv_cache import PagedCacheConfig
from distributed_inference_server_tpu.engine.speculative import SpecConfig
from distributed_inference_server_tpu.models import llama
from distributed_inference_server_tpu.models.configs import TINY
from distributed_inference_server_tpu.models.tokenizer import ByteTokenizer

TOK = ByteTokenizer()


@pytest.fixture(scope="module")
def tiny_params():
    return llama.init_params(jax.random.PRNGKey(0), TINY, dtype=jnp.float32)


@pytest.fixture(scope="module")
def draft_params():
    return llama.init_params(jax.random.PRNGKey(9), TINY, dtype=jnp.float32)


def make_engine(tiny_params, loop=False, loop_max_steps=64, num_pages=64,
                page_size=4, max_pages_per_seq=24, max_batch=4,
                tokenizer=None, draft=None, cfg=TINY, **kw):
    return LLMEngine(
        tiny_params,
        cfg,
        tokenizer or ByteTokenizer(),
        EngineConfig(
            max_batch=max_batch,
            prefill_buckets=(8, 32),
            paged=PagedCacheConfig(
                num_pages=num_pages, page_size=page_size,
                max_pages_per_seq=max_pages_per_seq,
            ),
            decode_block_size=4,
            loop_to_completion=loop,
            loop_max_steps=loop_max_steps,
            **kw,
        ),
        dtype=jnp.float32,
        draft_params=draft,
        draft_cfg=TINY if draft is not None else None,
        spec=SpecConfig(num_draft_tokens=3) if draft is not None else None,
    )


def drain(engine, toks=None, max_steps=800):
    toks = {} if toks is None else toks
    steps = 0
    while engine.has_work():
        steps += 1
        assert steps < max_steps, "engine did not drain"
        for out in engine.step():
            assert out.error is None, (out.request_id, out.error)
            if out.token_id is not None:
                toks.setdefault(out.request_id, []).append(out.token_id)
    return toks, steps


def _diff(got, want):
    return {k: (got.get(k), want.get(k))
            for k in set(got) | set(want) if got.get(k) != want.get(k)}


# ---------------------------------------------------------------------------
# greedy bit-identity: looped blocks vs the fixed-K path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [3, 11, 29])
def test_loop_greedy_identity_fuzz(tiny_params, seed):
    """The acceptance-criteria identity, fuzzed: random prompt lengths
    and budgets decode bit-identically with loop_to_completion on and
    off, and the page books conserve either way."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 200, size=int(n)).tolist()
               for n in rng.integers(3, 20, size=4)]
    budgets = [int(b) for b in rng.integers(2, 16, size=4)]

    def run(loop):
        eng = make_engine(tiny_params, loop=loop)
        for i, (ids, mt) in enumerate(zip(prompts, budgets)):
            eng.add_request(f"r{i}", ids,
                            SamplingParams(max_tokens=mt, temperature=0.0))
        toks, _ = drain(eng)
        assert eng.audit_pages() == []
        return toks, eng

    want, _ = run(False)
    got, eng = run(True)
    assert got == want, _diff(got, want)
    stats = eng.loop_stats()
    assert stats["blocks"] >= 1
    # each request's FIRST token is sampled by prefill, the rest by the
    # looped blocks
    assert stats["decode_tokens"] == (sum(len(v) for v in got.values())
                                      - len(got))
    assert stats["exits"]["budget"] >= 1


def test_loop_collapses_dispatches_and_steps(tiny_params):
    """The perf contract: a pure-decode drain that takes the fixed path
    one block per engine step finishes in far fewer engine steps looped
    — the stop condition runs on-device, not on the host."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 200, size=6).tolist() for _ in range(3)]

    def run(loop):
        eng = make_engine(tiny_params, loop=loop)
        for i, ids in enumerate(prompts):
            eng.add_request(f"r{i}", ids,
                            SamplingParams(max_tokens=24, temperature=0.0))
        toks, steps = drain(eng)
        return toks, steps, eng

    want, steps_off, _ = run(False)
    got, steps_on, eng = run(True)
    assert got == want, _diff(got, want)
    assert steps_on < steps_off
    sc = eng.step_clock_stats()["kinds"]["loop"]
    assert sc["dispatches"] >= 1
    # the looped dispatches carried every token past each row's first
    # (prefill samples that one)
    assert sc["tokens"] == sum(len(v) for v in got.values()) - len(got)
    assert eng.step_clock_stats()["kinds"]["decode_block"]["dispatches"] == 0


def test_loop_stats_none_when_off(tiny_params):
    eng = make_engine(tiny_params, loop=False)
    assert eng.loop_stats() is None


def test_loop_max_steps_validated(tiny_params):
    with pytest.raises(ValueError, match="loop_max_steps"):
        make_engine(tiny_params, loop=True, loop_max_steps=0)


# ---------------------------------------------------------------------------
# stop conditions: EOS, budget, pages, cap
# ---------------------------------------------------------------------------


class _EosTok(ByteTokenizer):
    def __init__(self, eos):
        super().__init__()
        self.eos_ids = (eos,)


COUNT_CFG = TINY.with_overrides(name="tiny-count",
                                tie_word_embeddings=False)


def counting_params():
    """Weights whose greedy stream the test controls, whatever a PRNG
    key happens to emit on this jax: every layer's output projection is
    zero, so the residual stream stays the token's embedding, and the
    head scores token v by embedding v-1 — the model counts upward from
    the last prompt byte."""
    params = llama.init_params(jax.random.PRNGKey(0), COUNT_CFG,
                               dtype=jnp.float32)
    layers = params["layers"]
    layers["wo"] = jnp.zeros_like(layers["wo"])
    layers["w_down"] = jnp.zeros_like(layers["w_down"])
    params["lm_head"] = jnp.roll(params["embed"], 1, axis=0).T
    return params


def test_mid_block_eos_identity():
    """A row that hits EOS mid-loop freezes on-device (exit reason eos)
    and emits exactly the same tokens as the fixed path."""
    params = counting_params()
    prompt = [104, 101, 108, 108, 111]  # "hello", no BOS
    probe = make_engine(params, cfg=COUNT_CFG)
    probe.add_request("p", prompt,
                      SamplingParams(max_tokens=12, temperature=0.0))
    ptoks, _ = drain(probe)
    assert ptoks["p"] == list(range(112, 124))  # the stream counts
    # index 0 is the prefill-sampled token; index 5 is the second step of
    # the second decode block — EOS fires inside the decode loop
    eos = 112 + 5

    def run(loop):
        eng = make_engine(params, loop=loop, tokenizer=_EosTok(eos),
                          cfg=COUNT_CFG)
        eng.add_request("e", prompt,
                        SamplingParams(max_tokens=12, temperature=0.0))
        # a second row keeps the block alive past the EOS row's freeze
        eng.add_request("other", TOK.encode("keep going"),
                        SamplingParams(max_tokens=12, temperature=0.0))
        toks, _ = drain(eng)
        assert eng.audit_pages() == []
        return toks, eng

    want, _ = run(False)
    got, eng = run(True)
    assert got == want, _diff(got, want)
    assert got["e"] == list(range(112, eos))  # cut short at the EOS token
    assert eng.loop_stats()["exits"]["eos"] >= 1


def test_free_list_exhaustion_repages_and_stays_identical(tiny_params):
    """A tight pool starves the device free-list mid-loop: rows freeze
    with exit reason 'pages', re-stage, and the drain still produces
    bit-identical tokens with zero page leaks."""
    rng = np.random.default_rng(13)
    prompts = [rng.integers(1, 200, size=n).tolist() for n in (5, 9, 13)]

    def run(loop):
        eng = make_engine(tiny_params, loop=loop, num_pages=18)
        for i, ids in enumerate(prompts):
            eng.add_request(f"r{i}", ids,
                            SamplingParams(max_tokens=20, temperature=0.0))
        toks, _ = drain(eng)
        assert eng.audit_pages() == []
        assert eng.allocator.device_held() == 0
        return toks, eng

    want, _ = run(False)
    got, eng = run(True)
    assert got == want, _diff(got, want)
    assert eng.loop_stats()["exits"]["pages"] >= 1


def test_cache_full_drain_then_preempt_under_loop(tiny_params):
    """When even the host-side first-write guarantee cannot be met the
    loop path preempts the youngest row exactly like _maybe_launch —
    every request still finishes and the books conserve."""
    rng = np.random.default_rng(17)
    eng = make_engine(tiny_params, loop=True, num_pages=12,
                      max_pages_per_seq=8)
    for i in range(3):
        eng.add_request(f"r{i}", rng.integers(1, 200, size=6).tolist(),
                        SamplingParams(max_tokens=18, temperature=0.0))
    toks, _ = drain(eng)
    assert set(toks) == {"r0", "r1", "r2"}
    assert all(len(v) == 18 for v in toks.values())
    assert eng.audit_pages() == []
    ev = eng.step_clock_stats()["events"]
    assert ev["cache_full"] >= 1 and ev["preempt"] >= 1


def test_cap_exit_resumes_next_step(tiny_params):
    """A block that hits loop_max_steps hands control back with exit
    reason 'cap'; the rows simply resume at the next engine step and
    the tokens stay identical."""
    rng = np.random.default_rng(19)
    prompts = [rng.integers(1, 200, size=7).tolist() for _ in range(2)]

    def run(loop, cap=3):
        eng = make_engine(tiny_params, loop=loop, loop_max_steps=cap)
        for i, ids in enumerate(prompts):
            eng.add_request(f"r{i}", ids,
                            SamplingParams(max_tokens=14, temperature=0.0))
        toks, _ = drain(eng)
        assert eng.audit_pages() == []
        return toks, eng

    want, _ = run(False)
    got, eng = run(True)
    assert got == want, _diff(got, want)
    assert eng.loop_stats()["exits"]["cap"] >= 1
    assert eng.loop_stats()["blocks"] >= 2


def test_set_loop_cap_frac_shrinks_cap(tiny_params):
    """The degradation hook: the effective iteration cap shrinks with
    the frac (floor 1) and restores on the way back down."""
    eng = make_engine(tiny_params, loop=True, loop_max_steps=40)
    assert eng.loop_stats()["cap"] == 40
    eng.set_loop_cap_frac(0.25)
    assert eng.loop_stats()["cap"] == 10
    assert eng.loop_stats()["cap_frac"] == 0.25
    eng.set_loop_cap_frac(0.0)  # floored, never zero
    assert eng.loop_stats()["cap"] >= 1
    eng.set_loop_cap_frac(1.0)
    assert eng.loop_stats()["cap"] == 40


# ---------------------------------------------------------------------------
# aborts and handoff overlap
# ---------------------------------------------------------------------------


def test_abort_mid_block_releases_everything(tiny_params):
    """Aborting between looped launches: the dead row's device appends
    reconcile as orphans, its pages free, and the surviving rows'
    tokens are unaffected (identical to a run that never saw the
    aborted request decode past the same point)."""
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, 200, size=6).tolist() for _ in range(3)]

    eng = make_engine(tiny_params, loop=True, loop_max_steps=2)
    for i, ids in enumerate(prompts):
        eng.add_request(f"r{i}", ids,
                        SamplingParams(max_tokens=16, temperature=0.0))
    toks: dict = {}
    for _ in range(2):  # a couple of capped blocks, everyone mid-decode
        for out in eng.step():
            if out.token_id is not None:
                toks.setdefault(out.request_id, []).append(out.token_id)
    assert eng.abort("r1")
    drain(eng, toks)
    assert eng.audit_pages() == []
    assert eng.allocator.device_held() == 0
    assert len(toks["r0"]) == 16 and len(toks["r2"]) == 16
    assert len(toks.get("r1", [])) < 16


def test_streamed_export_overlap_under_loop(tiny_params):
    """The engine.py streamed-export overlap window with looped decode:
    the sequence keeps decoding through looped blocks while its prefix
    serializes, and the migrated decode is token-identical to in-place
    (the same contract the fixed path proves in test_disagg)."""
    ids = TOK.encode("the quick brown fox jumps over the lazy dog")
    sp = SamplingParams(max_tokens=40, temperature=0.0)

    uni = make_engine(tiny_params, loop=True)
    uni.add_request("r", ids, sp)
    ref, _ = drain(uni)

    # loop cap small so the overlap window spans several looped blocks
    src = make_engine(tiny_params, loop=True, loop_max_steps=2)
    src.add_request("r", ids, sp, prefill_only=True)
    got: dict = {}
    while src.has_work() and not src.handoff_ready_ids():
        for o in src.step():  # prefill + first token, then parked
            assert o.error is None
            if o.token_id is not None:
                got.setdefault(o.request_id, []).append(o.token_id)
    dst = make_engine(tiny_params, loop=True)
    session = src.export_handoff_begin("r", chunk_pages=2)
    assert session is not None

    def collect(outs):
        for o in outs:
            assert o.error is None
            if o.token_id is not None:
                got.setdefault(o.request_id, []).append(o.token_id)

    collect(src.step())  # overlap: looped decode while the prefix moves
    src.export_handoff_pump(session)
    isess = dst.import_stream_open("r", len(session.prefix_pages))
    dst.import_stream_add(isess, session.chunks)
    collect(src.step())  # more overlap
    exp, outputs = src.export_handoff_finish(session)
    assert exp is not None
    collect(outputs)
    assert not src.has_work()
    assert src.audit_pages() == []
    tail = exp.kv_chunks[len(session.chunks):]
    dst.import_stream_commit(isess, dataclasses.replace(exp,
                                                        kv_chunks=tail))
    drain(dst, got)
    assert dst.audit_pages() == []
    assert got == ref, _diff(got, ref)


# ---------------------------------------------------------------------------
# mixed-step K-block fusion
# ---------------------------------------------------------------------------


def test_mixed_burst_identity_and_k_fusion(tiny_params):
    """A long prompt lands mid-decode: with loop_to_completion the
    mixed step advances every decode row decode_block_size tokens per
    dispatch (not one), with bit-identical tokens to the quantum
    baseline."""
    rng = np.random.default_rng(31)
    chats = [rng.integers(1, 200, size=6).tolist() for _ in range(2)]
    long_prompt = rng.integers(1, 200, size=60).tolist()

    def run(loop):
        # loop cap 1 keeps the chats mid-decode when the prompt lands
        eng = make_engine(tiny_params, loop=loop, loop_max_steps=1,
                          mixed_step_tokens=20 if loop else 0)
        toks: dict = {}
        for i, ids in enumerate(chats):
            eng.add_request(f"c{i}", ids,
                            SamplingParams(max_tokens=30, temperature=0.0))
        for _ in range(3):
            for out in eng.step():
                if out.token_id is not None:
                    toks.setdefault(out.request_id, []).append(out.token_id)
        eng.add_request("long", long_prompt,
                        SamplingParams(max_tokens=8, temperature=0.0))
        drain(eng, toks)
        assert eng.audit_pages() == []
        return toks, eng

    want, _ = run(False)
    got, eng = run(True)
    assert got == want, _diff(got, want)
    ms = eng.mixed_stats()
    assert ms["decode_tokens"] > 0
    # K-block fusion: decode tokens advanced per mixed dispatch averages
    # well above the fixed path's 1 (K = decode_block_size = 4, minus
    # rows that hit their budget mid-block)
    assert ms["decode_tokens"] / ms["steps"] > 1.0


def test_mixed_dispatch_count_collapses_k_fold(tiny_params):
    """The dispatch-count contract behind the bench: decoding the same
    burst, the fused mixed path uses ~K x fewer mixed dispatches per
    decode token than the per-token baseline."""
    rng = np.random.default_rng(37)
    chat = rng.integers(1, 200, size=6).tolist()
    long_prompt = rng.integers(1, 200, size=90).tolist()

    def dispatches_per_decode_token(loop):
        eng = make_engine(tiny_params, loop=loop, loop_max_steps=1,
                          mixed_step_tokens=20)
        eng.add_request("chat", chat,
                        SamplingParams(max_tokens=40, temperature=0.0))
        for _ in range(2):
            eng.step()
        eng.add_request("long", long_prompt,
                        SamplingParams(max_tokens=2, temperature=0.0))
        drain(eng)
        ms = eng.mixed_stats()
        sc = eng.step_clock_stats()["kinds"]["mixed"]
        assert sc["dispatches"] == ms["steps"]
        return ms["steps"] / max(1, ms["decode_tokens"])

    base = dispatches_per_decode_token(False)
    fused = dispatches_per_decode_token(True)
    # the fixed path spends one mixed dispatch per decode token; fusion
    # amortizes each dispatch over K=4 decode tokens
    assert base >= 0.99
    assert fused <= base / 2


# ---------------------------------------------------------------------------
# speculation inside the loop
# ---------------------------------------------------------------------------


def test_spec_in_loop_identity(tiny_params, draft_params):
    """Draft+verify composed INSIDE the looped program emits exactly
    the two-dispatch fixed spec path's greedy tokens."""
    rng = np.random.default_rng(41)
    prompts = [rng.integers(1, 200, size=n).tolist() for n in (5, 9, 13)]

    def run(loop):
        eng = make_engine(tiny_params, loop=loop, draft=draft_params)
        for i, ids in enumerate(prompts):
            eng.add_request(f"r{i}", ids,
                            SamplingParams(max_tokens=12, temperature=0.0))
        toks, _ = drain(eng)
        assert eng.audit_pages() == []
        return toks, eng

    want, _ = run(False)
    got, eng = run(True)
    assert got == want, _diff(got, want)
    assert eng.loop_stats()["blocks"] >= 1


def test_spec_composes_with_mixed_under_loop(tiny_params, draft_params):
    """ISSUE 19 lifts the mixed-vs-speculation exclusion: with
    loop_to_completion both knobs construct and the run matches the
    plain engine's greedy tokens (greedy spec == greedy plain)."""
    rng = np.random.default_rng(43)
    chats = [rng.integers(1, 200, size=6).tolist() for _ in range(2)]
    long_prompt = rng.integers(1, 200, size=60).tolist()

    def run(spec_mixed_loop):
        if spec_mixed_loop:
            eng = make_engine(tiny_params, loop=True,
                              mixed_step_tokens=20, draft=draft_params)
        else:
            eng = make_engine(tiny_params)
        toks: dict = {}
        for i, ids in enumerate(chats):
            eng.add_request(f"c{i}", ids,
                            SamplingParams(max_tokens=12, temperature=0.0))
        for _ in range(3):
            for out in eng.step():
                if out.token_id is not None:
                    toks.setdefault(out.request_id, []).append(out.token_id)
        eng.add_request("long", long_prompt,
                        SamplingParams(max_tokens=8, temperature=0.0))
        drain(eng, toks)
        assert eng.audit_pages() == []
        return toks

    want = run(False)
    got = run(True)
    assert got == want, _diff(got, want)


def test_spec_mixed_still_excluded_without_loop(tiny_params, draft_params):
    with pytest.raises(ValueError, match="loop_to_completion"):
        make_engine(tiny_params, mixed_step_tokens=20, draft=draft_params)
