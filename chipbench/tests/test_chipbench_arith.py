"""The benchmark's arithmetic against hand counts: visibility and
percentiles, the metric readers, kernel bytes and the plain reference."""

from __future__ import annotations

import pathlib
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from chipbench import bytes as kbytes  # noqa: E402
from chipbench import gen, reference, run, window  # noqa: E402


def test_visible_at_first_poll_reaching_seq():
    times = np.array([10.0, 10.1, 10.2, 10.3])
    counts = np.array([[0, 0], [512, 0], [512, 1024], [1536, 1024]])
    got = window.visible_times(times, counts, [0, 1, 0, 1, 0],
                               [512, 512, 1024, 1024, 2048])
    np.testing.assert_array_equal(got[:4], [10.1, 10.2, 10.3, 10.2])
    assert np.isnan(got[4])  # never reached


def _rec():
    # batches: tenant, due, sent, replied, status, seq, rows, row_lo
    b = np.array([[0, 0.5, 0.5, 0.51, 200, 512, 512, 0],    # before open
                  [0, 1.0, 1.0, 1.02, 200, 1024, 512, 512],
                  [1, 1.5, 1.5, 1.51, 200, 512, 512, 0],
                  [0, 2.0, 2.0, 2.04, 200, 1536, 512, 1024],
                  [1, 2.5, 2.5, 2.6, 503, -1, 512, 512],
                  [1, 3.5, 3.5, 3.51, 200, 1024, 512, 512]])  # after close
    vis = np.array([0.6, 1.3, 1.6, 2.4, np.nan, 3.6])
    q = np.array([[0, 1.2, 1.2, 1.45, 200], [1, 2.2, 2.2, 2.3, 200],
                  [0, 2.8, 2.8, 2.95, 200]])
    return {"batches": b, "visible": vis, "queries": q, "t_open": 1.0,
            "t_close": 3.0, "seconds": 2.0, "chunk": 4096, "setup_s": 42.0,
            "stats_open": {"chunks": 10, "drained_rows": 40960},
            "stats_close": {"chunks": 14, "drained_rows": 40960 + 12288}}


def _read(name, rec):
    spec = [{"name": name, "unit": "u"}]
    got = run.read_metrics(REPO, spec, rec)
    return got[name]["value"] if got else None


def test_end_to_end_readers_by_hand():
    rec = _rec()
    # queryable inside (1, 3]: the 512-row batches visible at 1.3, 1.6, 2.4
    assert _read("events_per_s", rec) == pytest.approx(3 * 512 / 2.0)
    # due in [1, 3) and acknowledged: 300, 100, 400 ms
    assert _read("visible_p50_ms", rec) == pytest.approx(300.0)
    assert _read("visible_p95_ms", rec) == pytest.approx(
        np.percentile([300.0, 100.0, 400.0], 95))
    assert _read("query_p50_ms", rec) == pytest.approx(150.0)
    assert _read("setup_s", rec) == 42.0


def test_layer_readers_by_hand():
    rec = _rec()
    assert _read("post_p95_ms", rec) == pytest.approx(
        np.percentile([20.0, 10.0, 40.0], 95))
    assert _read("chunk_fill", rec) == pytest.approx(100 * 12288 / (4 * 4096))
    assert _read("drain_ms", rec) == pytest.approx(2000.0 / 4)
    rec["stats_close"] = rec["stats_open"]
    assert _read("drain_ms", rec) is None  # nothing drained: no reading


def test_seg_scan_bytes_by_hand():
    # (1, 4096) int32: read 4096 values + 4096 int32 flags, write 4096
    assert kbytes.seg_scan(1, 4096) == {"bytes": 3 * 4 * 4096, "ops": 4095}
    assert kbytes.seg_scan(8, 256, itemsize=2)["bytes"] == 8 * 256 * 8
    peak = kbytes.peaks("TPU v5 lite")
    t, bound = kbytes.least_seconds(kbytes.seg_scan(1, 4096), peak)
    assert bound == "memory" and t == pytest.approx(49152 / 819e9)
    assert kbytes.roofline_percent(kbytes.seg_scan(1, 4096), peak,
                                   2 * t) == pytest.approx(50.0)
    with pytest.raises(KeyError):
        kbytes.peaks("TPU v9 imaginary")


def test_reference_window_horizon_and_unseen_by_hand():
    keys = np.array([7, 7, 3, 7, 7, 7])
    ts = np.array([1.0, 2.0, 3.0, 70.0, 71.0, 72.0], np.float32)
    xs = np.array([5, -2, 4, 1, 9, 3])
    # key 7: last 3 rows (window 3) are ts 70, 71, 72, all inside
    # 72 - 64; key 3: one row; key 9 unseen
    got = reference.key_folds("sum_i32", keys, ts, xs, [7, 3, 9], window=3,
                              horizon=64.0)
    assert got == {7: (True, 13), 3: (True, 4), 9: (False, 0)}
    # window 5 reaches ts 2.0, which is not later than 72 - 64 = 8
    got = reference.key_folds("max_i32", keys, ts, xs, [7, 9], window=5,
                              horizon=64.0)
    assert got == {7: (True, 9), 9: (False, -2**31)}
    got = reference.key_folds("sum_i32", [1, 1], [0.0, 0.0], [100, 100],
                              [1], window=4, horizon=64.0, bits=8)
    assert got == {1: (True, -56)}  # 200 wraps at 8 bits


def test_stream_blocks_agree_and_seeds_share_one_arrival_pattern():
    def stream():
        return gen.TenantStream(2**31 + 7, 1, first_event_rate=10000,
                                hot_auction_ratio=2, in_flight_auctions=100)
    whole = stream().rows(0, gen.BLOCK + 100)
    part = stream().rows(gen.BLOCK - 50, gen.BLOCK + 100)
    for a, b in zip(whole, part):
        np.testing.assert_array_equal(a[gen.BLOCK - 50:], b)
    assert np.all(np.diff(whole[1]) > 0)  # per-tenant event time
    gaps = gen.exponential_gaps(1000, 0.02, 11)
    assert gaps.mean() == pytest.approx(0.02, rel=0.01)
    shifts = {gen.rotation(1000, seed, 11) for seed in (1, 2, 2**31 + 5)}
    assert len(shifts) == 3  # seeds start the one pattern at other points
    assert np.bincount(gen.balanced_order(1000, 4, 12)).tolist() == [250] * 4


def test_nexmark_bids_by_hand():
    # bids are events 4..49 of each 50; event e is at e / 10000 s
    j = np.array([0, 45, 46, 92])
    np.testing.assert_array_equal(gen.bid_events(j), [4, 49, 54, 104])
    np.testing.assert_array_equal(gen.last_auction(j), [2, 2, 5, 8])
    k, t, x = gen.TenantStream(2**31 + 9, 0, first_event_rate=10000,
                               hot_auction_ratio=2,
                               in_flight_auctions=100).rows(0, 200000)
    np.testing.assert_allclose(t[:3], [4e-4, 5e-4, 6e-4], rtol=1e-6)
    assert 100 <= x.min() and x.max() <= 10**8  # cents, PriceGenerator
    assert np.mean(x > 2**15 - 1) > 0.5  # most prices leave int16
    last = gen.last_auction(np.arange(200000)) + 1000
    hot = k == last // 100 * 100
    assert hot.mean() == pytest.approx(0.5, abs=0.01)  # hotAuctionRatio 2
    assert np.all(k >= np.maximum(last - 100, 1000)) and np.all(k <= last + 10)
