"""Unit tests for the compute ops against naive NumPy references."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from xllm_service_tpu.ops import (
    rms_norm, apply_rope, mha_prefill, paged_decode_attention,
    gather_pages, write_prefill_kv, write_decode_kv, sample_tokens, greedy,
)
from xllm_service_tpu.ops.sampling import SamplingTensors, compute_logprobs


def test_rms_norm_matches_numpy():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 5, 16)).astype(np.float32)
    w = rng.standard_normal(16).astype(np.float32)
    got = np.asarray(rms_norm(jnp.asarray(x), jnp.asarray(w), eps=1e-5))
    ref = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * w
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_rope_identity_at_position_zero_and_norm_preserving():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((1, 3, 2, 8)).astype(np.float32)
    pos = jnp.asarray([[0, 1, 7]], dtype=jnp.int32)
    out = np.asarray(apply_rope(jnp.asarray(x), pos, theta=10000.0))
    np.testing.assert_allclose(out[0, 0], x[0, 0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.linalg.norm(out, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-4)


def test_rope_relative_property():
    # <rope(q, m), rope(k, n)> depends only on m - n.
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((1, 1, 1, 16)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((1, 1, 1, 16)).astype(np.float32))

    def dot_at(m, n):
        qr = apply_rope(q, jnp.asarray([[m]], jnp.int32), 10000.0)
        kr = apply_rope(k, jnp.asarray([[n]], jnp.int32), 10000.0)
        return float(jnp.sum(qr * kr))

    assert dot_at(5, 3) == pytest.approx(dot_at(12, 10), rel=1e-4)
    assert dot_at(5, 3) == pytest.approx(dot_at(2, 0), rel=1e-4)


def _naive_attention(q, k, v, kv_len, q_start):
    """Loop reference: q [T,Hq,D], k/v [S,Hkv,D]."""
    T, Hq, D = q.shape
    S, Hkv, _ = k.shape
    G = Hq // Hkv
    out = np.zeros_like(q)
    for t in range(T):
        for h in range(Hq):
            kv_h = h // G
            scores = (k[:, kv_h] @ q[t, h]) / np.sqrt(D)
            mask = (np.arange(S) <= q_start + t) & (np.arange(S) < kv_len)
            scores = np.where(mask, scores, -1e30)
            p = np.exp(scores - scores.max())
            p /= p.sum()
            out[t, h] = p @ v[:, kv_h]
    return out


def test_mha_prefill_matches_naive():
    rng = np.random.default_rng(3)
    B, T, S, Hq, Hkv, D = 2, 4, 6, 4, 2, 8
    q = rng.standard_normal((B, T, Hq, D)).astype(np.float32)
    k = rng.standard_normal((B, S, Hkv, D)).astype(np.float32)
    v = rng.standard_normal((B, S, Hkv, D)).astype(np.float32)
    q_start = np.array([2, 0], np.int32)   # seq 0 has a 2-token cached prefix
    kv_len = np.array([6, 4], np.int32)
    got = np.asarray(mha_prefill(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), jnp.asarray(kv_len),
                                 jnp.asarray(q_start)))
    for b in range(B):
        ref = _naive_attention(q[b], k[b], v[b], kv_len[b], q_start[b])
        np.testing.assert_allclose(got[b], ref, rtol=1e-4, atol=1e-5)


def test_mha_prefill_chunked_matches_dense():
    """Online-softmax chunked prefill ≡ dense path, incl. cached prefixes,
    padding rows, and S not a multiple of the chunk size."""
    from xllm_service_tpu.ops.attention import mha_prefill_chunked

    rng = np.random.default_rng(7)
    B, T, S, Hq, Hkv, D = 2, 8, 37, 4, 2, 8
    q = jnp.asarray(rng.standard_normal((B, T, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    q_start = jnp.asarray([20, 0], jnp.int32)
    kv_len = jnp.asarray([26, 5], jnp.int32)
    ref = mha_prefill(q, k, v, kv_len, q_start)
    for chunk in (4, 7, 16, 64):
        got = mha_prefill_chunked(q, k, v, kv_len, q_start,
                                  chunk_size=chunk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)


def test_mha_prefill_chunked_soft_cap():
    from xllm_service_tpu.ops.attention import mha_prefill_chunked

    rng = np.random.default_rng(8)
    B, T, S, Hq, Hkv, D = 1, 6, 24, 2, 1, 8
    q = jnp.asarray(rng.standard_normal((B, T, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    q_start = jnp.asarray([18], jnp.int32)
    kv_len = jnp.asarray([24], jnp.int32)
    ref = mha_prefill(q, k, v, kv_len, q_start, logits_soft_cap=30.0)
    got = mha_prefill_chunked(q, k, v, kv_len, q_start,
                              logits_soft_cap=30.0, chunk_size=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_sliding_window_prefill_chunked_matches_dense():
    """SWA: dense mask ≡ a hand mask, and the chunked flash path (with its
    below-window chunk skipping) ≡ dense across chunk sizes, cached
    prefixes, and padding rows."""
    from xllm_service_tpu.ops.attention import mha_prefill_chunked

    rng = np.random.default_rng(11)
    B, T, S, Hq, Hkv, D, W = 2, 8, 37, 4, 2, 8, 5
    q = jnp.asarray(rng.standard_normal((B, T, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    q_start = jnp.asarray([20, 0], jnp.int32)
    kv_len = jnp.asarray([26, 5], jnp.int32)
    ref = mha_prefill(q, k, v, kv_len, q_start, sliding_window=W)
    # The window changes the answer vs full attention (mask is live).
    full = mha_prefill(q, k, v, kv_len, q_start)
    assert not np.allclose(np.asarray(ref), np.asarray(full))
    # Hand-rolled check on one (b, t): only the last W positions attend.
    b, t = 0, 3
    qp = int(q_start[b]) + t
    lo = qp - W + 1
    scores = (np.asarray(q)[b, t].reshape(Hkv, Hq // Hkv, D) @
              np.asarray(k)[b].transpose(1, 2, 0)) / np.sqrt(D)
    allowed = (np.arange(S) >= lo) & (np.arange(S) <= qp) & \
        (np.arange(S) < int(kv_len[b]))
    scores = np.where(allowed[None, None, :], scores, -1e30)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    hand = (p @ np.asarray(v)[b].transpose(1, 0, 2)).reshape(Hq, D)
    np.testing.assert_allclose(np.asarray(ref)[b, t], hand,
                               rtol=1e-4, atol=1e-5)
    for chunk in (4, 7, 16, 64):
        got = mha_prefill_chunked(q, k, v, kv_len, q_start,
                                  chunk_size=chunk, sliding_window=W)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)


def test_sliding_window_decode_paths():
    """Both paged decode variants honor the window: equivalent to dense
    prefill attention restricted to the last W positions."""
    from xllm_service_tpu.ops.attention import (
        paged_decode_attention, paged_decode_attention_current)

    rng = np.random.default_rng(12)
    P, ps, Hkv, D, Hq, B, W = 8, 4, 2, 8, 4, 2, 3
    k_pages = jnp.asarray(rng.standard_normal((P, ps, Hkv, D)), jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal((P, ps, Hkv, D)), jnp.float32)
    pt = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0]], jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    ctx = jnp.asarray([10, 6], jnp.int32)       # includes current token
    got = np.asarray(paged_decode_attention(
        q, k_pages, v_pages, pt, ctx, sliding_window=W))
    from xllm_service_tpu.ops.attention import gather_pages
    k_all = np.asarray(gather_pages(k_pages, pt))
    v_all = np.asarray(gather_pages(v_pages, pt))
    for b in range(B):
        qp = int(ctx[b]) - 1
        allowed = (np.arange(k_all.shape[1]) > qp - W) & \
            (np.arange(k_all.shape[1]) <= qp)
        scores = (np.asarray(q)[b].reshape(Hkv, Hq // Hkv, D) @
                  k_all[b].transpose(1, 2, 0)) / np.sqrt(D)
        scores = np.where(allowed[None, None, :], scores, -1e30)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = (p @ v_all[b].transpose(1, 0, 2)).reshape(Hq, D)
        np.testing.assert_allclose(got[b], ref, rtol=1e-4, atol=1e-5)

    # current-token variant: cache_lens EXcludes the current token whose
    # K/V ride separately; result must equal the full variant after the
    # write. Build the written pool then compare.
    k_cur = jnp.asarray(rng.standard_normal((B, Hkv, D)), jnp.float32)
    v_cur = jnp.asarray(rng.standard_normal((B, Hkv, D)), jnp.float32)
    cache_lens = ctx - 1
    from xllm_service_tpu.ops.attention import write_decode_kv
    k_w, v_w = write_decode_kv(k_pages, v_pages, k_cur, v_cur, pt,
                               cache_lens, jnp.ones((B,), bool))
    want = np.asarray(paged_decode_attention(
        q, k_w, v_w, pt, ctx, sliding_window=W))
    got_cur = np.asarray(paged_decode_attention_current(
        q, k_pages, v_pages, pt, cache_lens, k_cur, v_cur,
        sliding_window=W))
    np.testing.assert_allclose(got_cur, want, rtol=1e-4, atol=1e-5)


def test_paged_kv_roundtrip_and_decode_attention():
    rng = np.random.default_rng(4)
    P, ps, Hkv, D, Hq = 8, 4, 2, 8, 4
    B, T = 2, 6
    k_pages = jnp.zeros((P, ps, Hkv, D), jnp.float32)
    v_pages = jnp.zeros((P, ps, Hkv, D), jnp.float32)
    # seq0 pages [1,2], seq1 pages [3,4]; page 0 is NULL.
    page_table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    k = rng.standard_normal((B, T, Hkv, D)).astype(np.float32)
    v = rng.standard_normal((B, T, Hkv, D)).astype(np.float32)
    lengths = np.array([6, 5], np.int32)
    start = np.zeros(B, np.int32)
    k_pages, v_pages = write_prefill_kv(
        k_pages, v_pages, jnp.asarray(k), jnp.asarray(v), page_table,
        jnp.asarray(start), jnp.asarray(lengths))
    gk = np.asarray(gather_pages(k_pages, page_table))
    for b in range(B):
        np.testing.assert_allclose(gk[b, :lengths[b]], k[b, :lengths[b]])
    # Padding of seq1 (t=5) must not have been written anywhere.
    assert np.all(np.asarray(k_pages)[0] == 0)  # NULL page untouched

    # Decode one token for each sequence at position lengths[b].
    newk = rng.standard_normal((B, Hkv, D)).astype(np.float32)
    newv = rng.standard_normal((B, Hkv, D)).astype(np.float32)
    positions = jnp.asarray(lengths, jnp.int32)
    k_pages, v_pages = write_decode_kv(
        k_pages, v_pages, jnp.asarray(newk), jnp.asarray(newv), page_table,
        positions, jnp.asarray([True, True]))
    q = rng.standard_normal((B, Hq, D)).astype(np.float32)
    ctx = np.asarray(positions) + 1
    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), k_pages, v_pages, page_table, jnp.asarray(ctx)))
    for b in range(B):
        fullk = np.concatenate([k[b, :lengths[b]], newk[b][None]], 0)
        fullv = np.concatenate([v[b, :lengths[b]], newv[b][None]], 0)
        ref = _naive_attention(q[b][None], fullk, fullv,
                               kv_len=ctx[b], q_start=ctx[b] - 1)[0]
        np.testing.assert_allclose(got[b], ref, rtol=1e-4, atol=1e-5)


def test_invalid_kv_writes_do_not_touch_last_page():
    """Regression: invalid (padding/inactive/NULL-page) writes must be
    dropped, not wrapped to the last pool slot (a -1 scatter index is
    normalized by JAX to num_slots-1 before the bounds check)."""
    P, ps, Hkv, D = 4, 2, 1, 4
    k_pages = jnp.zeros((P, ps, Hkv, D), jnp.float32)
    v_pages = jnp.zeros((P, ps, Hkv, D), jnp.float32)
    ones = jnp.ones((1, 2, Hkv, D), jnp.float32)
    # Sequence owns page 1 but declares length 1: token t=1 is padding.
    k2, v2 = write_prefill_kv(k_pages, v_pages, ones, ones,
                              jnp.asarray([[1]], jnp.int32),
                              jnp.zeros(1, jnp.int32),
                              jnp.asarray([1], jnp.int32))
    assert np.all(np.asarray(k2)[2:] == 0)          # pages 2,3 untouched
    assert np.all(np.asarray(k2)[0] == 0)           # NULL page untouched
    # Inactive decode write must be dropped too.
    k3, v3 = write_decode_kv(k_pages, v_pages, ones[:, 0], ones[:, 0],
                             jnp.asarray([[1]], jnp.int32),
                             jnp.asarray([0], jnp.int32),
                             jnp.asarray([False]))
    assert np.all(np.asarray(k3) == 0)


def test_sampling_greedy_and_filters():
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.standard_normal((3, 50)).astype(np.float32))
    g = np.asarray(greedy(logits))
    assert g.tolist() == np.argmax(np.asarray(logits), -1).tolist()

    key = jax.random.PRNGKey(0)
    # temperature 0 → greedy regardless of key.
    st = SamplingTensors(temperature=jnp.zeros(3), top_p=jnp.ones(3),
                         top_k=jnp.zeros(3, jnp.int32))
    assert np.asarray(sample_tokens(logits, st, key)).tolist() == g.tolist()
    # top_k=1 → greedy even at high temperature.
    st = SamplingTensors(temperature=jnp.full((3,), 5.0), top_p=jnp.ones(3),
                         top_k=jnp.ones(3, jnp.int32))
    assert np.asarray(sample_tokens(logits, st, key)).tolist() == g.tolist()
    # tiny top_p → greedy.
    st = SamplingTensors(temperature=jnp.full((3,), 5.0),
                         top_p=jnp.full((3,), 1e-6),
                         top_k=jnp.zeros(3, jnp.int32))
    assert np.asarray(sample_tokens(logits, st, key)).tolist() == g.tolist()
    # high temperature + full top_p samples valid ids.
    st = SamplingTensors(temperature=jnp.full((3,), 1.0), top_p=jnp.ones(3),
                         top_k=jnp.zeros(3, jnp.int32))
    toks = np.asarray(sample_tokens(logits, st, key))
    assert toks.shape == (3,) and (toks >= 0).all() and (toks < 50).all()


def test_compute_logprobs():
    logits = jnp.asarray([[0.0, 1.0, 2.0]], jnp.float32)
    lp = np.asarray(compute_logprobs(logits, jnp.asarray([2])))
    ref = 2.0 - np.log(np.exp([0.0, 1.0, 2.0]).sum())
    assert lp[0] == pytest.approx(ref, rel=1e-5)


class TestPallasPagedAttention:
    """Fused kernel vs XLA reference, via the Pallas interpreter on CPU."""

    def test_matches_reference(self):
        import numpy as np
        import jax.numpy as jnp

        from xllm_service_tpu.ops.attention import paged_decode_attention
        from xllm_service_tpu.ops.pallas.paged_attention import (
            paged_decode_attention_pallas)

        rng = np.random.default_rng(0)
        B, Hq, Hkv, D, P, ps, MP = 3, 8, 2, 32, 16, 8, 6
        q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)), jnp.float32)
        pt = jnp.asarray(rng.integers(1, P, size=(B, MP)), jnp.int32)
        # Mixed contexts incl. a 1-token row and a full-table row.
        ctx = jnp.asarray([13, 1, MP * ps], jnp.int32)
        ref = paged_decode_attention(q, k, v, pt, ctx)
        out = paged_decode_attention_pallas(q, k, v, pt, ctx,
                                            interpret=True)
        assert jnp.allclose(ref, out, atol=1e-5), \
            float(jnp.max(jnp.abs(ref - out)))

    def test_null_pages_masked(self):
        import numpy as np
        import jax.numpy as jnp

        from xllm_service_tpu.ops.attention import paged_decode_attention
        from xllm_service_tpu.ops.pallas.paged_attention import (
            paged_decode_attention_pallas)

        rng = np.random.default_rng(1)
        B, Hq, Hkv, D, P, ps, MP = 2, 4, 2, 16, 8, 8, 4
        q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)), jnp.float32)
        # Tables padded with NULL page 0 beyond the first entries.
        pt = jnp.asarray([[3, 0, 0, 0], [5, 2, 0, 0]], jnp.int32)
        ctx = jnp.asarray([5, 12], jnp.int32)
        ref = paged_decode_attention(q, k, v, pt, ctx)
        out = paged_decode_attention_pallas(q, k, v, pt, ctx,
                                            interpret=True)
        assert jnp.allclose(ref, out, atol=1e-5)

    def test_model_deltas_match_reference(self):
        """Sliding window (static and traced), Gemma soft-cap + scale
        override, and GPT-OSS sinks in the V1 kernel vs the XLA
        reference paths — the SWA-families-on-the-kernel-path surface
        (round-4 verdict item 3)."""
        import numpy as np
        import jax.numpy as jnp

        from xllm_service_tpu.ops.attention import (
            paged_decode_attention, paged_decode_attention_current)
        from xllm_service_tpu.ops.pallas.paged_attention import (
            paged_decode_attention_pallas)

        rng = np.random.default_rng(21)
        B, Hq, Hkv, D, P, ps, MP = 3, 8, 2, 32, 16, 8, 6
        q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)), jnp.float32)
        pt = jnp.asarray(rng.integers(1, P, size=(B, MP)), jnp.int32)
        ctx = jnp.asarray([13, 1, MP * ps], jnp.int32)
        kc = jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.float32)
        sinks = jnp.asarray(rng.normal(size=(Hq,)), jnp.float32)

        cases = [
            dict(sliding_window=5),
            dict(sliding_window=jnp.int32(5)),      # traced per-layer form
            dict(sliding_window=1),                 # degenerate W=1
            dict(logits_soft_cap=20.0),
            dict(scale=0.17),
            dict(sinks=sinks),
            dict(sliding_window=7, logits_soft_cap=30.0, scale=0.2),
            dict(sliding_window=4, sinks=sinks),    # GPT-OSS shape
        ]
        for extras in cases:
            ref = paged_decode_attention_current(
                q, k, v, pt, ctx, kc, vc,
                extras.get("logits_soft_cap", 0.0),
                extras.get("sliding_window", 0),
                extras.get("scale"), extras.get("sinks"))
            out = paged_decode_attention_pallas(
                q, k, v, pt, ctx, kc, vc, interpret=True, **extras)
            assert jnp.allclose(ref, out, atol=1e-5), (
                extras, float(jnp.max(jnp.abs(ref - out))))
            if "sinks" not in extras:
                ref2 = paged_decode_attention(
                    q, k, v, pt, ctx,
                    extras.get("logits_soft_cap", 0.0),
                    extras.get("sliding_window", 0),
                    extras.get("scale"))
                out2 = paged_decode_attention_pallas(
                    q, k, v, pt, ctx, interpret=True, **extras)
                assert jnp.allclose(ref2, out2, atol=1e-5), (
                    extras, float(jnp.max(jnp.abs(ref2 - out2))))

    def test_window_with_trimmed_null_pages(self):
        """O(W) page trimming leaves leading NULL entries in the table;
        the windowed kernel must never read their (stale page-0) bytes
        into live lanes."""
        import numpy as np
        import jax.numpy as jnp

        from xllm_service_tpu.ops.attention import (
            paged_decode_attention_current)
        from xllm_service_tpu.ops.pallas.paged_attention import (
            paged_decode_attention_pallas)

        rng = np.random.default_rng(22)
        B, Hq, Hkv, D, P, ps, MP = 2, 4, 2, 16, 8, 4, 5
        q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
        # Page 0 holds garbage that must stay masked.
        k = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)) * 50, jnp.float32)
        v = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)) * 50, jnp.float32)
        W = 6
        # ctx=17: positions < 17-6=11 are trimmable → pages 0,1 freed
        # (positions 0..7), entries NULLed. Window spans pages 2..4.
        pt = jnp.asarray([[0, 0, 3, 4, 5], [0, 0, 6, 7, 1]], jnp.int32)
        ctx = jnp.asarray([17, 18], jnp.int32)
        kc = jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.float32)
        ref = paged_decode_attention_current(
            q, k, v, pt, ctx, kc, vc, sliding_window=W)
        out = paged_decode_attention_pallas(
            q, k, v, pt, ctx, kc, vc, sliding_window=W, interpret=True)
        assert jnp.allclose(ref, out, atol=1e-5), \
            float(jnp.max(jnp.abs(ref - out)))

    # -- the window's page walk (PR 34) ---------------------------------
    # Under a STATIC window the grid has ceil(W / ps) + 1 columns a row
    # and starts at the row's first live page. One call holds the whole
    # sweep as its rows.
    WALK_PS, WALK_MP = 8, 12

    @staticmethod
    def _walk_rows(W, ps, MP, current):
        """Contexts through every alignment over three page boundaries
        past the window (``first`` moves), rows shorter than the window,
        an inactive row and a full table."""
        ctxs = list(range(W, W + 3 * ps + 2))
        ctxs += [0, 1, max(W // 2, 1), W - 1, MP * ps - 1, MP * ps]
        return [c for c in ctxs if current or c <= MP * ps]

    @staticmethod
    def _walk_tables(ctxs, W, ps, MP, current, n_garbage):
        """Real pages only where the window needs them; leading columns
        NULL (as ``_swa_trim`` leaves them), every other column a page of
        garbage, so a column wrongly folded cannot pass."""
        import numpy as np
        pt = np.zeros((len(ctxs), MP), np.int32)
        nxt = n_garbage
        for b, c in enumerate(ctxs):
            q_pos = c if current else c - 1
            lo = max(q_pos - W + 1, 0) // ps
            hi = (c - 1) // ps if c > 0 else -1
            for j in range(MP):
                if lo <= j <= hi:
                    pt[b, j] = nxt
                    nxt += 1
                elif j > hi:
                    pt[b, j] = 1 + (b + j) % (n_garbage - 1)
        return pt, nxt

    @pytest.mark.parametrize("layered", [False, True],
                             ids=["pool4d", "layered"])
    @pytest.mark.parametrize("current", [True, False],
                             ids=["in_register", "written"])
    @pytest.mark.parametrize("W", [8, 16, 32, 13],
                             ids=["W=ps", "W=2ps", "W=4ps", "W=13"])
    def test_static_window_walks_the_windows_pages(self, W, current,
                                                   layered):
        import numpy as np

        from xllm_service_tpu.ops.attention import (
            paged_decode_attention, paged_decode_attention_current)
        from xllm_service_tpu.ops.pallas.paged_attention import (
            _paged_decode_attention_impl, paged_decode_attention_pallas)
        from xllm_service_tpu.ops.plan import decode_walk_columns

        ps, MP, G = self.WALK_PS, self.WALK_MP, 4
        assert decode_walk_columns(MP, ps, W) < MP
        rng = np.random.default_rng(34 + W)
        ctxs = self._walk_rows(W, ps, MP, current)
        pt, P = self._walk_tables(ctxs, W, ps, MP, current, G)
        B, Hq, Hkv, D, L = len(ctxs), 4, 2, 16, 2
        pools = rng.normal(size=(2, L, P, ps, Hkv, D))
        pools[:, :, :G] *= 50       # NULL page 0 and the garbage pages
        k5, v5 = (jnp.asarray(x, jnp.float32) for x in pools)
        q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
        pt, ctx = jnp.asarray(pt), jnp.asarray(ctxs, jnp.int32)
        cur = ([jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.float32)
                for _ in range(2)] if current else [None, None])
        if current:
            ref = paged_decode_attention_current(
                q, k5[1], v5[1], pt, ctx, *cur, sliding_window=W)
        else:
            ref = paged_decode_attention(q, k5[1], v5[1], pt, ctx,
                                         sliding_window=W)
        pool = ((k5, v5) if layered else (k5[1], v5[1]))
        kw = dict(interpret=True, layer=jnp.int32(1) if layered else None)
        out = paged_decode_attention_pallas(
            q, *pool, pt, ctx, *cur, sliding_window=W, **kw)
        # (a written-token row of context 0 attends to nothing)
        live = np.asarray(ctxs) >= (0 if current else 1)
        err = np.abs(np.asarray(ref) - np.asarray(out)).max(axis=(1, 2))
        assert (err[live] < 1e-5).all(), [
            (c, float(e)) for c, e in zip(ctxs, err) if e >= 1e-5]
        # The full walk (the window as a traced scalar) folds the same
        # pages: blocks of the plan's K pages that start at column 0 and
        # not at the row's first live one, so the last bits may differ
        # ...
        full = paged_decode_attention_pallas(
            q, *pool, pt, ctx, *cur, sliding_window=jnp.int32(W), **kw)
        assert np.abs(np.asarray(full) - np.asarray(out)
                      )[live].max() < 1e-5
        # ... and at a page a grid step in the same order: the same bits,
        # inactive rows too.
        win = jnp.full((1,), W, jnp.int32)
        one = [_paged_decode_attention_impl(
            q, *pool, pt, ctx, *cur, win, None, interpret=True,
            layer=kw["layer"], walk=walk, fold=1)
            for walk in (decode_walk_columns(MP, ps, W), MP)]
        assert jnp.array_equal(*one)

    # -- a block of K pages a grid step (PR 46) ------------------------
    # ONE online-softmax update a block, the table folded once outside
    # the kernel. K is a jit static of the implementation, as ``walk``
    # is; the public wrapper leaves it to ``ops/plan.py``
    # ``paged_fold_pages``.
    FOLD_PS, FOLD_MP, FOLD_W = 8, 13, 16        # 13: no multiple of a K

    # (key-value heads, head width, pool type) whose page the kernel reads
    # FLAT (PR 51; ops/plan.py ``paged_flat_positions`` > 1), with the
    # group sizes folded over each: the hybrid cell's 4 heads at a group
    # of 8 under a packed query (the odd half of every row zeros), the
    # fifth cell's group of 5, a TP slice's 2 and 1 heads; 4 positions a
    # tile of bfloat16 at 4 heads, 2 of float32.
    FLAT_PAGES = [(4, 128, "bfloat16", 8), (4, 128, "bfloat16", 5),
                  (4, 128, "float32", 8), (4, 128, "float32", 5),
                  (2, 128, "bfloat16", 4), (2, 128, "float32", 4),
                  (1, 128, "bfloat16", 8), (1, 128, "float32", 8)]

    @staticmethod
    def _fold_cases(flat_pages):
        import itertools
        cases = [pytest.param(*c, None, id="-".join(
            (f"K{c[0]}", f"g{c[1]}", "layered" if c[2] else "pool4d",
             c[3], "in_register" if c[4] else "written")))
            for c in itertools.product(
                (1, 2, 4, 8), (1, 4, 5), (False, True),
                ("static", "traced", "none"), (True, False))]
        cases.append(pytest.param(4, 4, True, "none", False, "soft_cap",
                                  id="K4-soft_cap"))
        cases.append(pytest.param(4, 4, True, "static", True, "sinks",
                                  id="K4-sinks"))
        # the flat page: every shape under every window and both places
        # of the current token at the cells' K; every K, the soft cap and
        # the sinks at the hybrid cell's page
        flat = [(8, page, n % 2 == 0, window, current, None)
                for n, (page, window, current) in enumerate(
                    itertools.product(
                        flat_pages, ("static", "traced", "none"),
                        (True, False)))]
        packed = flat_pages[0]
        flat += [(K, packed, layered, "static", current, None)
                 for K, (layered, current) in itertools.product(
                     (1, 2, 4), ((True, False), (False, True)))]
        flat += [(4, packed, True, "none", False, "soft_cap"),
                 (4, packed, True, "static", True, "sinks")]
        cases += [pytest.param(K, page, layered, window, current, extra,
                               id="-".join(
            (f"K{K}", "flat%dx%d%s-g%d" % page,
             "layered" if layered else "pool4d", window,
             "in_register" if current else "written")
            + ((extra,) if extra else ())))
            for K, page, layered, window, current, extra in flat]
        return cases

    @pytest.mark.parametrize("K,group,layered,window,current,extra",
                             _fold_cases(FLAT_PAGES))
    def test_block_fold_matches_reference(self, K, group, layered, window,
                                          current, extra):
        """Rows whose live pages are 0, 1, K - 1, K, K + 1 and the whole
        table, whole and part pages, a walk that is no multiple of K,
        NULL leading columns under a window, and every dead column a
        page of garbage x50 (a column wrongly folded cannot pass)."""
        import numpy as np

        from xllm_service_tpu.ops.attention import (
            paged_decode_attention, paged_decode_attention_current)
        from xllm_service_tpu.ops.pallas.paged_attention import (
            _paged_decode_attention_impl)
        from xllm_service_tpu.ops.plan import decode_walk_columns

        from xllm_service_tpu.ops.plan import paged_flat_positions

        ps, MP, G = self.FOLD_PS, self.FOLD_MP, 4
        W = self.FOLD_W if window != "none" else 0
        walk = decode_walk_columns(MP, ps, W if window == "static" else 0)
        assert walk % K or K == 1
        pages = sorted({0, 1, K - 1, K, K + 1, MP})
        ctxs = sorted({c for n in pages for c in (n * ps, n * ps - 3)
                       if 0 <= c <= MP * ps})
        if W:
            ctxs += self._walk_rows(W, ps, MP, current)
        ctxs = [c for c in ctxs if current or c <= MP * ps]
        pt, P = self._walk_tables(ctxs, W or MP * ps + 1, ps, MP, current,
                                  G)
        rng = np.random.default_rng(46 + K)
        # ``group``: a group size over the small page the kernel reads by
        # heads, or a page read flat with its group
        Hkv, D, dtype, group = (group if isinstance(group, tuple)
                                else (2, 16, "float32", group))
        dtype, L = jnp.dtype(dtype), 2
        flat = paged_flat_positions(Hkv, D, dtype.itemsize)
        assert (flat > 1) == (D == 128)
        if flat > 1:
            # a tile of the flat page holds several positions (a context
            # ends inside one: ctx % flat != 0 among the rows)
            assert flat == (16 if dtype.itemsize == 2 else 8) // Hkv
            assert any(c % flat for c in ctxs)
        B, Hq = len(ctxs), Hkv * group
        pools = rng.normal(size=(2, L, P, ps, Hkv, D))
        pools[:, :, :G] *= 50       # NULL page 0 and the garbage pages
        k5, v5 = (jnp.asarray(x, dtype) for x in pools)
        q = rng.normal(size=(B, Hq, D))
        if flat > 1 and group == 8:
            # the packed query (models/transformer.py ``_kv_pack``): two
            # heads of 64 to a row, so half of every query row is zeros
            q *= (np.arange(Hq)[:, None] % 2 == 0) == (np.arange(D) < 64)
        q = jnp.asarray(q, dtype)
        pt, ctx = jnp.asarray(pt), jnp.asarray(ctxs, jnp.int32)
        cur = ([jnp.asarray(rng.normal(size=(B, Hkv, D)), dtype)
                for _ in range(2)] if current else [None, None])
        cap = 20.0 if extra == "soft_cap" else 0.0
        sinks = (jnp.asarray(rng.normal(size=(Hq,)), jnp.float32)
                 if extra == "sinks" else None)
        if current:
            ref = paged_decode_attention_current(
                q, k5[1], v5[1], pt, ctx, *cur, cap, W, None, sinks)
        else:
            ref = paged_decode_attention(q, k5[1], v5[1], pt, ctx, cap, W,
                                         None, sinks)
        out = _paged_decode_attention_impl(
            q, *((k5, v5) if layered else (k5[1], v5[1])), pt, ctx, *cur,
            jnp.full((1,), W, jnp.int32), sinks, interpret=True,
            logits_soft_cap=cap, layer=jnp.int32(1) if layered else None,
            walk=walk, fold=K)
        # (a written-token row of context 0 attends to nothing)
        live = np.asarray(ctxs) >= (0 if current else 1)
        err = np.abs(np.asarray(ref, np.float32)
                     - np.asarray(out, np.float32)).max(axis=(1, 2))
        # bfloat16: the probabilities go to the MXU in the pool's type
        # and the output is rounded once more (values of a few units)
        tol = 1e-5 if dtype == jnp.float32 else 4e-2
        assert (err[live] < tol).all(), [
            (c, float(e)) for c, e in zip(ctxs, err) if e >= tol]

    @pytest.mark.parametrize("window,MP,ps,want", [
        (0, 12, 8, 12),                 # full attention
        ("traced", 12, 8, 12),          # per-layer window vectors
        (96, 12, 8, 12),                # W = MP * ps
        (200, 12, 8, 12),               # W > MP * ps
        (88, 12, 8, 12),                # span 12: not shorter
        (8, 12, 8, 2), (16, 12, 8, 3), (32, 12, 8, 5), (13, 12, 8, 3),
        (80, 12, 8, 11),
        (4096, 64, 128, 33),            # the docqa cell
        (4096, 32, 128, 32),
    ], ids=str)
    def test_grid_columns(self, window, MP, ps, want):
        """The grid the kernel is lowered with, read off the jaxpr:
        ``want`` columns a row, in blocks of the plan's K."""
        import jax

        from xllm_service_tpu.ops.pallas.paged_attention import (
            paged_decode_attention_pallas)
        from xllm_service_tpu.ops.plan import (
            decode_walk_columns, paged_fold_pages)

        B, Hq, Hkv, D, P = 2, 4, 2, 16, 4
        args = (jnp.zeros((B, Hq, D)), jnp.zeros((P, ps, Hkv, D)),
                jnp.zeros((P, ps, Hkv, D)), jnp.zeros((B, MP), jnp.int32),
                jnp.zeros((B,), jnp.int32))
        if window == "traced":
            jaxpr = jax.make_jaxpr(
                lambda w, *a: paged_decode_attention_pallas(
                    *a, sliding_window=w, interpret=True))(
                        jnp.int32(8), *args)
        else:
            jaxpr = jax.make_jaxpr(
                lambda *a: paged_decode_attention_pallas(
                    *a, sliding_window=window, interpret=True))(*args)
            assert decode_walk_columns(MP, ps, window) == want

        def grids(jp):
            for eqn in jp.eqns:
                if eqn.primitive.name == "pallas_call":
                    yield tuple(eqn.params["grid_mapping"].grid)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from grids(sub)
        # a grid step folds a block of K pages (PR 46), K from shapes
        K = paged_fold_pages(ps, Hkv, D, 4, want)
        assert 1 < K <= want
        assert list(grids(jaxpr.jaxpr)) == [(B, -(-want // K))]


class TestPagedKvUpdateKernel:
    """The Pallas in-place decode KV write (ops/pallas/kv_update.py) —
    the round-5 fix for XLA copying BOTH pools around the scatter every
    burst step (~8.6 GB/step at bench shape, found by the offline v5e
    AOT harness). Must match the XLA scatter bit-for-bit, including the
    drop cases."""

    def test_matches_xla_scatter_including_drops(self, monkeypatch):
        import numpy as np
        from xllm_service_tpu.ops import attention as att
        from xllm_service_tpu.ops.plan import KernelPlan
        from xllm_service_tpu.ops.pallas.kv_update import paged_kv_update
        # The REFERENCE is the dispatcher under the reference plan (the
        # XLA scatter), whatever the environment says: the plan decides,
        # and the comparison can never be kernel-vs-itself.
        monkeypatch.setenv("XLLM_PALLAS_KV", "1")
        rng = np.random.default_rng(0)
        L, P, ps, Hkv, D, B, MP = 8, 32, 8, 2, 64, 5, 4
        kp = jnp.asarray(rng.normal(size=(L, P, ps, Hkv, D)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(L, P, ps, Hkv, D)), jnp.float32)
        kn = jnp.asarray(rng.normal(size=(L, B, Hkv, D)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(L, B, Hkv, D)), jnp.float32)
        # DISJOINT per-row page tables (the allocator's exclusive-
        # ownership invariant, like TestPagedPrefillKvUpdateKernel):
        # random tables collide rows on shared pages, and two scatters
        # to one page make the bit-for-bit assertion seed-dependent.
        pt = jnp.asarray(np.arange(1, B * MP + 1).reshape(B, MP),
                         jnp.int32)
        pt = pt.at[1, :].set(0)                  # NULL pages → dropped
        pos = jnp.asarray([0, 5, 7, 13, 100], jnp.int32)  # 100: off-table
        act = jnp.asarray([1, 1, 0, 1, 1], bool)          # row 2 inactive
        ref_k, ref_v = att.write_decode_kv_all_layers(
            kp, vp, kn, vn, pt, pos, act, KernelPlan())
        new_k, new_v = paged_kv_update(kp, vp, kn, vn, pt, pos, act,
                                       interpret=True)
        assert jnp.array_equal(ref_k, new_k)
        assert jnp.array_equal(ref_v, new_v)
        via_k, via_v = att.write_decode_kv_all_layers(
            kp, vp, kn, vn, pt, pos, act,
            KernelPlan(kv_writers=True, interpret=True))
        assert jnp.array_equal(ref_k, via_k)
        assert jnp.array_equal(ref_v, via_v)

    def test_layered_decode_kernel_matches_sliced(self):
        """layer= + full 5D pools (no per-layer slice for XLA to
        materialize) must equal the per-layer-sliced kernel call."""
        import numpy as np
        from xllm_service_tpu.ops.pallas.paged_attention import (
            _paged_decode_attention_impl)
        rng = np.random.default_rng(1)
        L, P, ps, Hkv, D, B, MP, Hq = 3, 8, 8, 2, 64, 4, 4, 8
        kp5 = jnp.asarray(rng.normal(size=(L, P, ps, Hkv, D)), jnp.float32)
        vp5 = jnp.asarray(rng.normal(size=(L, P, ps, Hkv, D)), jnp.float32)
        q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.float32)
        pt = jnp.asarray(1 + rng.integers(0, P - 1, size=(B, MP)),
                         jnp.int32)
        ctx = jnp.asarray([5, 17, 25, 31], jnp.int32)
        for l in range(L):
            ref = _paged_decode_attention_impl(
                q, kp5[l], vp5[l], pt, ctx, kc, vc, interpret=True)
            got = _paged_decode_attention_impl(
                q, kp5, vp5, pt, ctx, kc, vc, interpret=True,
                layer=jnp.int32(l))
            assert jnp.allclose(ref, got, atol=1e-6), f"layer {l}"


class TestPagedPrefillKvUpdateKernel:
    """The in-place prefill KV write (page-granular RMW) must match the
    XLA scatter on aligned windows, including ragged lengths, NULL
    pages, and prefix-cache (nonzero page-aligned start) rows."""

    def test_matches_xla_scatter(self, monkeypatch):
        import numpy as np
        from xllm_service_tpu.ops import attention as att
        from xllm_service_tpu.ops.pallas.kv_update import (
            paged_prefill_kv_update)
        from xllm_service_tpu.ops.plan import KernelPlan
        monkeypatch.setenv("XLLM_PALLAS_KV", "1")   # the plan decides
        rng = np.random.default_rng(5)
        L, P, ps, Hkv, D, B, T, MP = 3, 32, 8, 2, 16, 4, 16, 6
        kp = jnp.asarray(rng.normal(size=(L, P, ps, Hkv, D)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(L, P, ps, Hkv, D)), jnp.float32)
        kn = jnp.asarray(rng.normal(size=(L, B, T, Hkv, D)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(L, B, T, Hkv, D)), jnp.float32)
        # DISJOINT pages per row — the allocator's exclusive-ownership
        # invariant (the RMW page write requires it; a shared page's
        # identity-written tail would clobber the other owner's rows).
        pt = jnp.asarray(np.arange(1, B * MP + 1).reshape(B, MP),
                         jnp.int32)
        pt = pt.at[2, :].set(0)                      # NULL row
        start = jnp.asarray([0, 8, 0, 16], jnp.int32)  # page-aligned
        lens = jnp.asarray([16, 11, 16, 5], jnp.int32)  # ragged tails
        ref_k, ref_v = att.write_prefill_kv_all_layers(
            kp, vp, kn, vn, pt, start, lens, KernelPlan())
        new_k, new_v = paged_prefill_kv_update(
            kp, vp, kn, vn, pt, start, lens, interpret=True)
        assert jnp.array_equal(ref_k, new_k)
        assert jnp.array_equal(ref_v, new_v)
        via_k, via_v = att.write_prefill_kv_all_layers(
            kp, vp, kn, vn, pt, start, lens,
            KernelPlan(kv_writers=True, interpret=True))
        assert jnp.array_equal(ref_k, via_k)
        assert jnp.array_equal(ref_v, via_v)


def test_kv_update_kernels_match_scatter_at_mla_latent_shape():
    """DeepSeek-style latent pools (Hkv=1, minor dim NOT 128-aligned)
    ride the in-place writers too. This pins interpret-mode PARITY at a
    small unaligned-minor geometry (D=72) against the raw _xla scatters
    called directly; Mosaic compilability at the real (Hkv=1, D=576)
    shape is evidenced separately by the offline AOT probe matrix
    (docs/AOT_VERDICTS_r5.txt)."""
    import numpy as np
    from xllm_service_tpu.ops import attention as att
    from xllm_service_tpu.ops.pallas.kv_update import (
        paged_kv_update, paged_prefill_kv_update)
    rng = np.random.default_rng(7)
    L, P, ps, Hkv, D, B, MP = 2, 24, 8, 1, 72, 3, 4
    kp = jnp.asarray(rng.normal(size=(L, P, ps, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(L, P, ps, Hkv, D)), jnp.float32)
    pt = jnp.asarray(np.arange(1, B * MP + 1).reshape(B, MP), jnp.int32)
    # decode write
    kn = jnp.asarray(rng.normal(size=(L, B, Hkv, D)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(L, B, Hkv, D)), jnp.float32)
    pos = jnp.asarray([0, 9, 23], jnp.int32)
    act = jnp.asarray([1, 1, 0], bool)
    ref = att.write_decode_kv_all_layers_xla(kp, vp, kn, vn, pt, pos, act)
    got = paged_kv_update(kp, vp, kn, vn, pt, pos, act, interpret=True)
    assert jnp.array_equal(ref[0], got[0]) and jnp.array_equal(ref[1],
                                                               got[1])
    # prefill write
    T = 16
    knp = jnp.asarray(rng.normal(size=(L, B, T, Hkv, D)), jnp.float32)
    vnp = jnp.asarray(rng.normal(size=(L, B, T, Hkv, D)), jnp.float32)
    start = jnp.asarray([0, 8, 16], jnp.int32)
    lens = jnp.asarray([16, 10, 3], jnp.int32)
    ref = att.write_prefill_kv_all_layers_xla(kp, vp, knp, vnp, pt,
                                              start, lens)
    got = paged_prefill_kv_update(kp, vp, knp, vnp, pt, start, lens,
                                  interpret=True)
    assert jnp.array_equal(ref[0], got[0]) and jnp.array_equal(ref[1],
                                                               got[1])
