"""Percentile rule, token gaps, lateness and the window's reduction."""

import pytest

from chipbench import stats


def test_percentile_is_loadgens_nearest_rank():
    vals = list(range(1, 102))            # 1..101
    assert stats.percentile(vals, 50) == 51
    assert stats.percentile(vals, 90) == 91
    assert stats.percentile([5.0], 95) == 5.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


@pytest.mark.parametrize("n,p,ok", [
    (100, 90, True), (99, 90, False), (200, 95, True), (199, 95, False),
    (20, 50, True), (19, 50, False), (1000, 99, True), (999, 99, False)])
def test_ten_samples_beyond_it(n, p, ok):
    assert stats.supported(n, p) is ok
    got = stats.tail_percentile(list(range(n)), p)
    assert (got is not None) is ok


def test_token_gaps_spread_a_frame_over_its_tokens():
    rec = {"frames": [[10.0, 1], [10.1, 1], [10.4, 3]]}
    gaps = stats.token_gaps(rec)
    assert [round(g, 6) for _, g in gaps] == [0.1, 0.1, 0.1, 0.1]
    assert [t for t, _ in gaps] == [10.1, 10.4, 10.4, 10.4]


def _rec(due, first, n, gap, ok=True):
    return {"ok": ok, "due": due, "sent": due + 0.002,
            "frames": [[first + i * gap, 1] for i in range(n)]}


def test_end_to_end_counts_by_due_time_and_arrival_time():
    recs = [_rec(100.0 + i, 100.2 + i, 11, 0.05) for i in range(30)]
    recs.append(_rec(99.5, 99.7, 11, 0.05))         # due before the window
    recs.append(_rec(131.0, 131.2, 11, 0.05))       # due after it
    out = stats.end_to_end(recs, 100.0, 130.0)
    assert out["_n_ttft"] == 30
    assert out["ttft_p50_ms"] == pytest.approx(200.0)
    assert out["ttft_p90_ms"] is None               # 30 samples: no p90
    assert out["itl_p95_ms"] == pytest.approx(50.0)
    # the early request's tokens after 100.0 count, the late one's do not
    assert out["_tokens"] == 30 * 11 + 5
    assert out["out_tok_s"] == pytest.approx((30 * 11 + 5) / 30.0)


def test_lateness_is_send_minus_due():
    recs = [{"due": 1.0, "sent": 1.001}, {"due": 2.0, "sent": 2.003},
            {"due": 3.0, "sent": 3.020}, {"due": None, "sent": None}]
    late = stats.lateness(recs)
    assert late["median_ms"] == pytest.approx(3.0)
    assert late["worst_ms"] == pytest.approx(20.0)


def test_iqr_share_follows_statistics_quantiles():
    vals = [100, 101, 102, 103, 104, 105]
    assert stats.iqr_share(vals) == pytest.approx((104.25 - 100.75) / 102.5)



def test_tail_reader_reads_the_window_and_nothing_from_too_few():
    """`itl_tail_ms`: the percentile its metric file names, over the
    token gaps of the window alone; with fewer than ten samples beyond it
    the reader returns nothing and the metric is left out."""
    from chipbench.readers import itl_tail_ms
    recs = [_rec(100.0 + 0.1 * i, 100.0 + 0.1 * i + 0.01, 3, 0.05)
            for i in range(200)]
    recs.append(_rec(90.0, 90.1, 3, 7.0))           # before the window
    ctx = {"records": recs, "open_t": 100.0, "close_t": 130.0}
    assert itl_tail_ms.read(ctx, {"percentile": 95.0}) \
        == pytest.approx(50.0)
    few = dict(ctx, records=recs[:50])              # 100 gaps: 5 beyond
    assert itl_tail_ms.read(few, {"percentile": 95.0}) is None
