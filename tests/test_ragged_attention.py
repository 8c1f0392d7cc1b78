"""Ragged mixed-batch attention (ISSUE 12): the Pallas kernel
(ops/pallas/paged_attention.py:paged_attention_ragged) and the packed-token
XLA reference (ops/attention.py:ragged_gqa_attention) against each other
and against per-row gqa_attention ground truth — seeded ragged geometries,
page-boundary and chunk-boundary edges, empty-decode and empty-prefill
batches. Runs in Pallas interpret mode on the CPU backend."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_server_tpu.ops.attention import (
    gqa_attention,
    ragged_gqa_attention,
)
from distributed_inference_server_tpu.ops.pallas import paged_attention_ragged

PAGE = 8


def _make_case(seed, S, Bm, H, KV, D, P, q_lens, num_pages=64,
               history=None):
    """Random pool + packed ragged batch: row b contributes q_lens[b] new
    tokens on top of ``history[b]`` resident ones (random when None)."""
    rng = np.random.default_rng(seed)
    pool_k = rng.standard_normal((num_pages * PAGE, KV, D)).astype(np.float32)
    pool_v = rng.standard_normal((num_pages * PAGE, KV, D)).astype(np.float32)
    q = rng.standard_normal((S, H, D)).astype(np.float32)
    tables = rng.permutation(num_pages)[: Bm * P].reshape(Bm, P)
    if history is None:
        history = [
            int(rng.integers(0, P * PAGE - ql + 1)) if ql else 0
            for ql in q_lens
        ]
    valid = np.array(
        [h + ql for h, ql in zip(history, q_lens)], np.int32
    )
    tok_row = np.full((S,), -1, np.int32)
    q_pos = np.zeros((S,), np.int32)
    off = 0
    for b, ql in enumerate(q_lens):
        tok_row[off:off + ql] = b
        q_pos[off:off + ql] = np.arange(history[b], history[b] + ql)
        off += ql
    return q, pool_k, pool_v, tables, tok_row, q_pos, valid


def _gathered(pk, pv, tables):
    Bm, P = tables.shape
    slots = (
        tables[:, :, None] * PAGE + np.arange(PAGE)[None, None, :]
    ).reshape(Bm, P * PAGE)
    return pk[slots], pv[slots]


def _reference(q, pk, pv, tables, tok_row, q_pos, valid, **kw):
    k_seq, v_seq = _gathered(pk, pv, tables)
    return ragged_gqa_attention(
        jnp.asarray(q), jnp.asarray(k_seq), jnp.asarray(v_seq),
        jnp.asarray(tok_row), jnp.asarray(q_pos), jnp.asarray(valid), **kw
    )


def _kernel(q, pk, pv, tables, tok_row, q_pos, valid, q_block=8, **kw):
    return paged_attention_ragged(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
        jnp.asarray(tables), jnp.asarray(tok_row), jnp.asarray(q_pos),
        jnp.asarray(valid), page_size=PAGE, q_block=q_block,
        interpret=True, **kw,
    )


def _assert_match(got, want, tok_row, rtol=2e-5, atol=2e-5):
    m = tok_row >= 0  # padding outputs are garbage by contract
    np.testing.assert_allclose(
        np.asarray(got)[m], np.asarray(want)[m], rtol=rtol, atol=atol
    )


class TestRaggedReference:
    """ragged_gqa_attention vs per-row gqa_attention ground truth."""

    def test_matches_per_row_gqa(self):
        q, pk, pv, tables, tok_row, q_pos, valid = _make_case(
            0, 16, 3, 8, 4, 16, 4, [1, 10, 4]
        )
        k_seq, v_seq = _gathered(pk, pv, tables)
        got = np.asarray(_reference(q, pk, pv, tables, tok_row, q_pos,
                                    valid))
        # ground truth: run each row alone through gqa_attention
        off = 0
        for b, ql in enumerate([1, 10, 4]):
            want = gqa_attention(
                jnp.asarray(q[off:off + ql])[None],
                jnp.asarray(k_seq[b])[None], jnp.asarray(v_seq[b])[None],
                jnp.asarray(q_pos[off:off + ql])[None],
                jnp.asarray(valid[b:b + 1]),
            )[0]
            np.testing.assert_allclose(
                got[off:off + ql], np.asarray(want), rtol=2e-5, atol=2e-5
            )
            off += ql


class TestRaggedKernelVsReference:
    @pytest.mark.parametrize(
        "S,Bm,H,KV,D,P,q_lens",
        [
            # decode rows packed next to one prefill chunk
            (16, 4, 8, 4, 16, 4, [1, 1, 1, 13]),
            # empty-prefill: every row is a decode token, padding tail
            (16, 6, 4, 2, 32, 3, [1, 1, 1, 1, 1, 1]),
            # empty-decode: chunks only, crossing window boundaries
            (32, 3, 8, 4, 16, 4, [9, 17, 2]),
            # one row exactly fills the window (boundary-aligned chunk)
            (8, 2, 16, 2, 64, 2, [8, 0]),
            # MHA-ish KV=8 with a mid-size chunk mix
            (24, 5, 8, 8, 16, 3, [3, 1, 8, 1, 5]),
            # KV*D wider than one 128-lane chunk (two head chunks): each
            # grid step must DMA only its own chunk's lane window
            (16, 3, 8, 4, 64, 3, [1, 1, 14]),
        ],
    )
    def test_seeded_geometries(self, S, Bm, H, KV, D, P, q_lens):
        q, pk, pv, tables, tok_row, q_pos, valid = _make_case(
            S * 31 + Bm, S, Bm, H, KV, D, P, q_lens
        )
        got = _kernel(q, pk, pv, tables, tok_row, q_pos, valid)
        want = _reference(q, pk, pv, tables, tok_row, q_pos, valid)
        _assert_match(got, want, tok_row)

    def test_fuzz_seeded_ragged_mixes(self):
        """Randomized q_len mixes (decode-heavy, chunk-heavy, partial
        budgets) across seeds — the mixed step's real workload shape."""
        for seed in range(6):
            rng = np.random.default_rng(100 + seed)
            S, P = 24, 4
            q_lens, left, Bm = [], S, 0
            while left > 0 and Bm < 8:
                ql = int(rng.integers(1, min(left, 9) + 1))
                if rng.random() < 0.5:
                    ql = 1  # decode-weighted
                q_lens.append(ql)
                left -= ql
                Bm += 1
            q, pk, pv, tables, tok_row, q_pos, valid = _make_case(
                seed, S, Bm, 8, 4, 16, P, q_lens
            )
            got = _kernel(q, pk, pv, tables, tok_row, q_pos, valid)
            want = _reference(q, pk, pv, tables, tok_row, q_pos, valid)
            _assert_match(got, want, tok_row)

    def test_page_boundary_history(self):
        """Chunks starting exactly at page boundaries, and one token
        short of them — the ragged kv_valid edge the mask must honor."""
        for hist in ([PAGE, 2 * PAGE], [PAGE - 1, 2 * PAGE + 1]):
            q, pk, pv, tables, tok_row, q_pos, valid = _make_case(
                7, 16, 2, 8, 4, 16, 4, [6, 10], history=hist
            )
            got = _kernel(q, pk, pv, tables, tok_row, q_pos, valid)
            want = _reference(q, pk, pv, tables, tok_row, q_pos, valid)
            _assert_match(got, want, tok_row)

    def test_sliding_window_and_softcap(self):
        q, pk, pv, tables, tok_row, q_pos, valid = _make_case(
            11, 16, 3, 8, 4, 16, 4, [1, 10, 4]
        )
        got = _kernel(q, pk, pv, tables, tok_row, q_pos, valid,
                      sliding_window=7, attn_softcap=30.0)
        want = _reference(q, pk, pv, tables, tok_row, q_pos, valid,
                          sliding_window=7, attn_softcap=30.0)
        _assert_match(got, want, tok_row)

    def test_bf16_io(self):
        q, pk, pv, tables, tok_row, q_pos, valid = _make_case(
            13, 16, 4, 8, 4, 16, 4, [1, 1, 1, 13]
        )
        got = _kernel(
            q.astype(jnp.bfloat16), pk.astype(jnp.bfloat16),
            pv.astype(jnp.bfloat16), tables, tok_row, q_pos, valid,
        )
        assert got.dtype == jnp.bfloat16
        want = _reference(q, pk, pv, tables, tok_row, q_pos, valid)
        _assert_match(np.asarray(got, np.float32), want, tok_row,
                      rtol=5e-2, atol=5e-2)

    def test_all_padding_batch(self):
        """A fully-padded packed batch (no work at all) must not crash;
        outputs are garbage by contract."""
        q, pk, pv, tables, tok_row, q_pos, valid = _make_case(
            17, 8, 2, 8, 4, 16, 2, [0, 0]
        )
        out = _kernel(q, pk, pv, tables, tok_row, q_pos, valid)
        assert out.shape == q.shape

    def test_subsumes_decode_kernel_contract(self):
        """All-decode packed batch equals paged_attention_decode on the
        same pool — the ONE-kernel subsumption the mixed step relies on."""
        from distributed_inference_server_tpu.ops.pallas import (
            paged_attention_decode,
        )

        q, pk, pv, tables, tok_row, q_pos, valid = _make_case(
            19, 8, 8, 8, 4, 16, 3, [1] * 8
        )
        got = _kernel(q, pk, pv, tables, tok_row, q_pos, valid)
        want = paged_attention_decode(
            jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
            jnp.asarray(tables), jnp.asarray(valid), page_size=PAGE,
            interpret=True,
        )
        # packed order == row order for an all-decode batch
        _assert_match(got, np.asarray(want), tok_row)
