"""The benchmark's arithmetic against hand counts: visibility and
percentiles, the metric readers, kernel bytes, the data generators, the
reference folds and the comparison."""

from __future__ import annotations

import hashlib
import json
import pathlib
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from chipbench import bytes as kbytes  # noqa: E402
from chipbench import byname, control, gen, reference, run, window  # noqa: E402
from chipbench.generators import nexmark_bids as bids  # noqa: E402
from chipbench.generators import tsbs_cpu  # noqa: E402


def fold(name):
    return byname.fold(REPO, name)


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
    got = reference.key_folds(fold("sum_i32"), keys, ts, xs, [7, 3, 9],
                              window=3, horizon=64.0)
    assert got == {7: (True, 13), 3: (True, 4), 9: (False, 0)}
    # window 5 reaches ts 2.0, which is not later than 72 - 64 = 8
    got = reference.key_folds(fold("max_i32"), keys, ts, xs, [7, 9],
                              window=5, horizon=64.0)
    assert got == {7: (True, 9), 9: (False, -2**31)}
    got = reference.key_folds(fold("sum_i32"), [1, 1], [0.0, 0.0],
                              [2**31 - 1, 2], [1], window=4, horizon=64.0)
    assert got == {1: (True, -2**31 + 1)}  # wraps at the stated int32
    got = reference.key_folds(fold("sum_i32"), [1, 1], [0.0, 0.0],
                              [20000, 20000], [1], window=4, horizon=64.0,
                              narrow=True)
    assert got == {1: (True, -25536)}  # 40000 wraps at int16, the narrower
    got = reference.key_folds(fold("max_i32"), [1, 1], [0.0, 0.0],
                              [5, 40000], [1], window=4, horizon=64.0,
                              narrow=True)
    assert got == {1: (True, 40000 - 2**16)}


def test_stream_blocks_agree_and_seeds_share_one_arrival_pattern():
    def stream():
        return bids.TenantStream(2**31 + 7, 1, first_event_rate=10000,
                                 hot_auction_ratio=2, in_flight_auctions=100)
    whole = stream().rows(0, bids.BLOCK + 100)
    part = stream().rows(bids.BLOCK - 50, bids.BLOCK + 100)
    for a, b in zip(whole, part):
        np.testing.assert_array_equal(a[bids.BLOCK - 50:], b)
    assert np.all(np.diff(whole[1]) > 0)  # per-tenant event time
    gaps = gen.exponential_gaps(1000, 0.02, 11)
    assert gaps.mean() == pytest.approx(0.02, rel=0.01)
    shifts = {gen.rotation(1000, seed, 11) for seed in (1, 2, 2**31 + 5)}
    assert len(shifts) == 3  # seeds start the one pattern at other points
    assert np.bincount(gen.balanced_order(1000, 4, 12)).tolist() == [250] * 4


def test_nexmark_bids_by_hand():
    # bids are events 4..49 of each 50; event e is at e / 10000 s
    j = np.array([0, 45, 46, 92])
    np.testing.assert_array_equal(bids.bid_events(j), [4, 49, 54, 104])
    np.testing.assert_array_equal(bids.last_auction(j), [2, 2, 5, 8])
    k, t, x = bids.TenantStream(2**31 + 9, 0, first_event_rate=10000,
                                hot_auction_ratio=2,
                                in_flight_auctions=100).rows(0, 200000)
    np.testing.assert_allclose(t[:3], [4e-4, 5e-4, 6e-4], rtol=1e-6)
    assert 100 <= x.min() and x.max() <= 10**8  # cents, PriceGenerator
    assert np.mean(x > 2**15 - 1) > 0.5  # most prices leave int16
    last = bids.last_auction(np.arange(200000)) + 1000
    hot = k == last // 100 * 100
    assert hot.mean() == pytest.approx(0.5, abs=0.01)  # hotAuctionRatio 2
    assert np.all(k >= np.maximum(last - 100, 1000)) and np.all(k <= last + 10)


# sha256 of keys, ts and values bytes of rows(0, 3 * BLOCK + 17), as the
# NEXmark generator gave them before it was found by name
BIDS_ROWS_SHA256 = {
    (7, 0): "4a88d21d3502eab503913060d2ad6fef32ee504be68fa2e4b9821232dacd891f",
    (7, 3): "e123a5475b783b0d855c0bcf84c0a7a19126a2017ee77254e14ecbfeaf1d2a23",
    (2**31 + 101, 0):
        "f614ad8499540647a49e68bf01156704496db50a2d859a9e41cf67c0b2ee5646",
    (2**31 + 101, 3):
        "f1f11979f1bd85ee2aabdc685fc588e902d04c3c58bf48e8d33c9d6374c4ea06",
}


@pytest.mark.parametrize("seed,tenant", sorted(BIDS_ROWS_SHA256))
def test_nexmark_rows_are_byte_identical_when_found_by_name(seed, tenant):
    cfg = json.loads((REPO / "chipbench" / "configs"
                      / "bids-max.json").read_text())["generator"]
    streams = byname.tenant_streams(REPO, seed, cfg)
    k, t, x = streams[tenant].rows(0, 3 * bids.BLOCK + 17)
    assert (k.dtype, t.dtype, x.dtype) == (np.int32, np.float32, np.int32)
    digest = hashlib.sha256(k.tobytes() + t.tobytes() + x.tobytes())
    assert digest.hexdigest() == BIDS_ROWS_SHA256[(seed, tenant)]


def test_tsbs_cpu_rows_by_hand():
    hosts = 3000  # 21 intervals to a block: 63,000 rows
    s = tsbs_cpu.TenantStream(2**31 + 11, 2, hosts=hosts, log_interval_s=10)
    n = 3 * s.block_rows + 5
    k, t, x = s.rows(0, n)
    assert s.block_rows == 21 * hosts
    assert (k.dtype, t.dtype, x.dtype) == (np.int32, np.float32, np.float32)
    assert x.shape == (n, 10) and len(tsbs_cpu.FIELDS) == 10
    assert tsbs_cpu.FIELDS[0] == "usage_user"
    assert tsbs_cpu.FIELDS[-1] == "usage_guest_nice"
    r = np.arange(n)
    np.testing.assert_array_equal(k, r % hosts)  # interval-major
    np.testing.assert_array_equal(t, (r // hosts) * 10.0)
    assert x.min() >= 0.0 and x.max() <= 100.0
    assert (x == 0.0).any() and (x == 100.0).any()  # the clamp binds
    first = x[:hosts]
    # the first interval is uniform in [0, 100)
    assert first.min() < 0.1 and 99.9 < first.max() < 100.0
    np.testing.assert_allclose(np.percentile(first, [25, 50, 75]),
                               [25.0, 50.0, 75.0], atol=1.5)
    # one interval later each field moved by one N(0, 1) step, clamped
    step = (x[hosts:2 * hosts] - first).ravel()
    free = (x[hosts:2 * hosts].ravel() > 0) & (x[hosts:2 * hosts].ravel() < 100)
    assert np.std(step[free]) == pytest.approx(1.0, abs=0.05)
    assert abs(np.mean(step[free])) < 0.05
    # blocks read out of order, and rows across a block edge, agree
    late = tsbs_cpu.TenantStream(2**31 + 11, 2, hosts=hosts, log_interval_s=10)
    edge = 2 * s.block_rows
    part = late.rows(edge - 7, n)
    head = late.rows(0, 100)
    for a, b in zip((k, t, x), part):
        np.testing.assert_array_equal(a[edge - 7:], b)
    for a, b in zip((k, t, x), head):
        np.testing.assert_array_equal(a[:100], b)
    other = tsbs_cpu.TenantStream(2**31 + 11, 1, hosts=hosts,
                                  log_interval_s=10).rows(0, hosts)[2]
    assert not np.array_equal(other, first)  # tenants are fleets of their own
    assert late.rows(5, 5)[2].shape == (0, 10)


def test_max_fold_on_rows_window_horizon_and_unseen_by_hand():
    f = fold("max")
    assert f.IDENTITY == np.float32(np.finfo(np.float32).min)
    keys = [4, 4, 2, 4, 4]
    ts = np.array([0.0, 10.0, 20.0, 3000.0, 3610.0], np.float32)
    xs = np.zeros((5, 10), np.float32)
    xs[0, 0] = 90.0  # outside the window of 3 (rows 1, 3, 4)
    xs[1, 1] = 80.0  # in the window, outside the horizon: 10 <= 3610 - 3600
    xs[3, 2] = 70.5
    xs[4, 3] = 0.25
    xs[2] = np.arange(10)
    got = reference.key_folds(f, keys, ts, xs, [4, 2, 7], window=3,
                              horizon=3600.0)
    want4 = np.zeros(10, np.float32)
    want4[2], want4[3] = 70.5, 0.25
    assert got[4][0] and got[2][0] and not got[7][0]
    np.testing.assert_array_equal(got[4][1], want4)
    np.testing.assert_array_equal(got[2][1], np.arange(10))
    np.testing.assert_array_equal(got[7][1], np.full(10, f.IDENTITY))
    got = reference.key_folds(f, keys, ts, xs, [4], window=4, horizon=4000.0)
    assert run.answer(*got[4])[1][:4] == (90.0, 80.0, 70.5, 0.25)
    got = reference.key_folds(f, [1, 1], [0.0, 1.0], [1.5, -2.0], [1, 2],
                              window=8, horizon=10.0)
    assert run.answer(*got[1]) == (True, (1.5,))  # a scalar is a row of one
    assert run.answer(*got[2]) == (False, (float(f.IDENTITY),))


def _rec_for_compare(streams, acked, queried, got, service):
    per = {f"tenant-{i}": {"queryable_rows": a, "dropped_rows": 0,
                           "shed_rows": 0, "throttled_rows": 0}
           for i, a in enumerate(acked)}
    return {"config": {"service": service}, "streams": streams,
            "acked_rows": acked, "queried": queried, "got": got,
            "names": list(per), "stats_end": {"per_tenant": per},
            "flushed": True, "batches": np.zeros((0, 8)),
            "queries": np.zeros((0, 5)), "visible": np.zeros(0)}


class _Rows:
    def __init__(self, k, t, x):
        self.k, self.t, self.x = k, t, x

    def rows(self, lo, hi):
        return self.k[lo:hi], self.t[lo:hi], self.x[lo:hi]


def test_compare_takes_list_answers_exactly():
    x = np.array([[1.5, 2.0], [0.25, 3.0], [7.0, 7.0]], np.float32)
    stream = _Rows(np.array([1, 1, 2]), np.array([0.0, 1.0, 2.0]), x)
    service = {"window": 4, "horizon": 100.0}
    rec = _rec_for_compare([stream], [3], [[1, 2, 9]], [{
        1: (True, [1.5, 3.0]), 2: (True, [7.0, 7.0]),
        9: (False, [float(np.finfo(np.float32).min)] * 2)}], service)
    rec["fold"] = fold("max")
    assert run.compare(rec)["mismatched_folds"] == 0
    for key, bad in ((1, (True, [1.5, 3.0000002])),  # one float32 ulp off
                     (2, (False, [7.0, 7.0])),
                     (9, (False, [float(np.finfo(np.float32).min)]))):
        wrong = {**rec["got"][0], key: bad}
        assert run.compare(dict(rec, got=[wrong]))["mismatched_folds"] == 1
    missing = {1: rec["got"][0][1]}
    assert run.compare(dict(rec, got=[missing]))["mismatched_folds"] == 2
    # scalar folds: a JSON number and a list of one compare alike
    ints = _Rows(np.array([5, 5]), np.array([0.0, 1.0]), np.array([3, 4]))
    rec = _rec_for_compare([ints], [2], [[5]], [{5: (True, [7])}], service)
    rec["fold"] = fold("sum_i32")
    assert run.compare(rec)["mismatched_folds"] == 0
    assert run.compare(dict(rec, got=[{5: (True, 7)}]))["mismatched_folds"] == 0
    assert run.compare(dict(rec, got=[{5: (True, 8)}]))["mismatched_folds"] == 1


def test_float16_narrow_control_fails_the_reference_answers():
    hosts, per_tenant = 200, 200 * 400
    streams = byname.tenant_streams(REPO, 2**31 + 13, {
        "name": "tsbs_cpu", "tenants": 2, "hosts": hosts,
        "log_interval_s": 10})
    service = {"window": 360, "horizon": 3600.0}
    queried = [list(range(0, hosts, 5)) + [hosts + 1]] * 2
    rec = _rec_for_compare(streams, [per_tenant] * 2, queried, None, service)
    rec["fold"] = fold("max")
    sound = [reference.key_folds(rec["fold"], *s.rows(0, per_tenant), q,
                                 window=360, horizon=3600.0)
             for s, q in zip(streams, queried)]
    assert run.compare(dict(rec, got=sound))["mismatched_folds"] == 0
    narrowed = run.compare(dict(rec, got=control.narrow(rec)))
    # every found host's ten maxima but the clamped ones leave float16
    assert narrowed["mismatched_folds"] == sum(len(q) - 1 for q in queried)
