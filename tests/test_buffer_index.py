"""The open buffer's index by series (`BlockBucket.group`,
`ShardBuffer.read_many`): a read returns what the scan it replaced
returned, bit for bit, over any interleaving of appends and reads; the
index is built by reads alone, once a bucket while nothing is written,
and the counters say which way a read went."""

import sys
import threading
import time

import numpy as np
import pytest

from m3_tpu.storage import buffer as buffer_mod
from m3_tpu.storage.buffer import ShardBuffer, dedup_sorted

BLOCK = 1_000
PAST = FUTURE = 10 * BLOCK
N_SERIES = 9


def scan_read(buf, series_idx, start_ns, end_ns):
    """`ShardBuffer.read` as it was before the index: the plain
    reference, a scan of every overlapping bucket's whole column."""
    all_ts, all_vals = [], []
    for bs in sorted(buf.buckets):
        if bs + buf.block_size_ns <= start_ns or bs >= end_ns:
            continue
        sidx, ts, vals = buf.buckets[bs].cols.view()
        m = sidx == series_idx
        if not m.any():
            continue
        s, t, v = dedup_sorted(sidx[m], ts[m], vals[m])
        keep = (t >= start_ns) & (t < end_ns)
        all_ts.append(t[keep])
        all_vals.append(v[keep])
    if not all_ts:
        return np.zeros(0, np.int64), np.zeros(0, np.float64)
    return np.concatenate(all_ts), np.concatenate(all_vals)


def same(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()
    return True


def check_reads(buf, start_ns, end_ns, idxs=range(-1, N_SERIES + 2)):
    idxs = list(idxs)
    many = buf.read_many(idxs, start_ns, end_ns)
    assert len(many) == len(idxs)
    for idx, got in zip(idxs, many):
        want = scan_read(buf, idx, start_ns, end_ns)
        assert same(got, want), (idx, start_ns, end_ns)
        assert same(buf.read(idx, start_ns, end_ns), want)


def new_buffer():
    return ShardBuffer(BLOCK, PAST, FUTURE)


def counters():
    return {name: getattr(buffer_mod, attr).value() for name, attr in (
        ("indexed", "_READ_INDEXED"), ("tail_scans", "_READ_TAIL_SCANS"),
        ("builds", "_INDEX_BUILDS"), ("rows", "_INDEX_ROWS"))}


def moved(before):
    return {k: v - before[k] for k, v in counters().items()}


def scrape(buf, k, series=range(N_SERIES), value=None, every=10):
    """Every series once at step k, as a remote-write batch lands."""
    series = np.fromiter(series, np.int32)
    buf.write_batch(series, np.full(len(series), k * every, np.int64),
                    np.full(len(series), float(k if value is None else value)))


RANGES = [(0, 3 * BLOCK), (0, BLOCK), (BLOCK, 2 * BLOCK), (35, 2 * BLOCK - 15),
          (40, 41), (BLOCK - 10, BLOCK + 10), (5 * BLOCK, 6 * BLOCK),
          (-(1 << 62), 1 << 62), (-(1 << 70), 1 << 70), (40, 1 << 70)]


@pytest.mark.parametrize("rng_ns", RANGES)
def test_in_order_scrapes_read_as_the_scan(rng_ns):
    buf = new_buffer()
    for k in range(150):                 # straddles the boundary at step 100
        scrape(buf, k)
    check_reads(buf, *rng_ns)


def test_out_of_order_rows():
    buf = new_buffer()
    for k in (5, 3, 9, 1, 7):
        scrape(buf, k)
    buf.write(2, 20, 2.5)                # a late point of one series alone
    check_reads(buf, 0, BLOCK)
    check_reads(buf, 25, 75)


@pytest.mark.parametrize("where", ["prefix", "tail", "either-side"])
def test_duplicate_timestamp_last_arrival_wins(where):
    buf = new_buffer()
    for k in range(8):
        scrape(buf, k)
    if where == "prefix":
        scrape(buf, 4, value=44.0)
        check_reads(buf, 0, BLOCK)       # groups the duplicate in
    elif where == "tail":
        check_reads(buf, 0, BLOCK)
        scrape(buf, 9, value=1.0)
        scrape(buf, 9, value=99.0)
    else:
        check_reads(buf, 0, BLOCK)       # step 4's first arrival is indexed
        scrape(buf, 4, value=44.0)
    bucket = buf.buckets[0]
    before = bucket.indexed_n
    check_reads(buf, 0, BLOCK)
    if where != "prefix":
        assert bucket.indexed_n == before < bucket.cols.n   # the tail path
    t, v = buf.read(3, 0, BLOCK)
    step = 9 if where == "tail" else 4
    assert v[t.tolist().index(step * 10)] == (99.0 if where == "tail" else 44.0)
    assert len(set(t.tolist())) == len(t)


def test_a_series_absent_from_a_bucket():
    buf = new_buffer()
    for k in range(6):
        scrape(buf, k, series=(0, 2, 5))
    check_reads(buf, 0, BLOCK)
    t, v = buf.read(1, 0, BLOCK)
    assert t.dtype == np.int64 and v.dtype == np.float64 and not len(t)
    scrape(buf, 6, series=(7,))          # known only to the tail
    check_reads(buf, 0, BLOCK)
    assert buf.read(7, 0, BLOCK)[0].tolist() == [60]


def test_rows_straddling_a_block_boundary():
    buf = new_buffer()
    sidx = np.arange(N_SERIES, dtype=np.int32).repeat(4)
    ts = np.tile(np.array([BLOCK - 20, BLOCK - 10, BLOCK, BLOCK + 10]), N_SERIES)
    assert buf.write_batch(sidx, ts, ts.astype(np.float64)) is False
    assert sorted(buf.buckets) == [0, BLOCK]
    for rng_ns in RANGES:
        check_reads(buf, *rng_ns)


def test_a_grow_between_the_grouping_and_the_read():
    buf = new_buffer()
    for k in range(100):                 # 900 of the columns' first 1,024
        scrape(buf, k, every=1)
    check_reads(buf, 0, BLOCK)
    bucket = buf.buckets[0]
    cap, order = len(bucket.cols.sidx), bucket.order
    for k in range(100, 120):
        scrape(buf, k, every=1)
    assert len(bucket.cols.sidx) > cap
    assert 0 < bucket.cols.n - bucket.indexed_n <= bucket.indexed_n
    check_reads(buf, 0, BLOCK)
    check_reads(buf, 50, 110)
    assert bucket.order is order         # positions outlive the copy


def test_reads_build_once_and_scan_nothing_while_nothing_is_written():
    buf = new_buffer()
    for k in range(150):
        scrape(buf, k)
    before = counters()
    for _ in range(5):
        buf.read_many(list(range(N_SERIES)), 0, 2 * BLOCK)
    buf.read(0, 0, BLOCK)                # one bucket overlaps
    buf.read(N_SERIES + 3, 0, 2 * BLOCK)   # absent: the index says so
    assert moved(before) == {"builds": 2, "rows": 150 * N_SERIES,
                             "tail_scans": 0,
                             "indexed": 5 * 2 * N_SERIES + 1 + 2}


def test_a_write_then_a_read_scans_the_tail_and_a_long_tail_regroups():
    buf = new_buffer()
    for k in range(10):
        scrape(buf, k)
    buf.read(0, 0, BLOCK)
    before = counters()
    scrape(buf, 10)
    check_reads(buf, 0, BLOCK, idxs=[0, 1])
    got = moved(before)                  # read_many and two reads a check
    assert got["builds"] == 0 and got["indexed"] == 0
    assert got["tail_scans"] == 2 + 2
    for k in range(11, 21):              # the tail passes the prefix
        scrape(buf, k)
    bucket = buf.buckets[0]
    assert bucket.cols.n - bucket.indexed_n > bucket.indexed_n
    before = counters()
    check_reads(buf, 0, BLOCK, idxs=[4])
    assert moved(before) == {"builds": 1, "rows": 21 * N_SERIES,
                             "tail_scans": 0, "indexed": 2}
    assert bucket.indexed_n == bucket.cols.n


def test_an_append_never_builds_or_touches_the_index():
    buf = new_buffer()
    before = counters()
    for k in range(30):
        scrape(buf, k)
    bucket = buf.buckets[0]
    assert bucket.order is None and bucket.indexed_n == 0
    assert moved(before) == {"builds": 0, "rows": 0, "tail_scans": 0,
                             "indexed": 0}
    buf.read(0, 0, BLOCK)
    order, bounds, n = bucket.order, bucket.bounds, bucket.indexed_n
    scrape(buf, 30)
    buf.write(1, 305, 1.0)
    assert (bucket.order is order and bucket.bounds is bounds
            and bucket.indexed_n == n)


@pytest.mark.parametrize("how", ["snapshot", "drain"])
def test_snapshot_and_drain_after_reads_equal_a_bucket_never_read(how):
    def load(read_between):
        buf = new_buffer()
        rng = np.random.default_rng(3)
        for k in rng.permutation(40).tolist() + [7, 7, 12]:
            scrape(buf, k, series=rng.permutation(N_SERIES)[:6].tolist(),
                   value=rng.integers(0, 100))
            if read_between and k % 3 == 0:
                buf.read_many([0, 4, 8], 0, BLOCK)
        return buf

    read, unread = load(True), load(False)
    assert read.buckets[0].order is not None and unread.buckets[0].order is None
    for got, want in zip(getattr(read, how)(0), getattr(unread, how)(0)):
        assert got.dtype == want.dtype and got.tobytes() == want.tobytes()
    if how == "drain":
        assert 0 not in read.buckets     # the index went with the bucket
        assert same(read.read(0, 0, BLOCK), scan_read(unread, 0, 0, BLOCK))


@pytest.mark.parametrize("seed", range(12))
def test_interleavings_of_appends_and_reads_equal_the_scan(seed):
    """Seeded property: batches in and out of order, duplicates on either
    side of the index's edge, series that come and go, rows across three
    blocks, and a read after every few appends."""
    rng = np.random.default_rng(seed)
    buf = new_buffer()
    span = 3 * BLOCK
    for step in range(rng.integers(20, 60)):
        kind = rng.integers(0, 5)
        if kind == 0:                    # an in-order scrape
            scrape(buf, step * 4, series=rng.permutation(N_SERIES)[
                :rng.integers(1, N_SERIES + 1)].tolist(), value=step)
        elif kind == 1:                  # a batch of anything, anywhere
            k = rng.integers(1, 40)
            buf.write_batch(rng.integers(0, N_SERIES, k).astype(np.int32),
                            rng.integers(0, span // 10, k) * 10,
                            rng.random(k))
        elif kind == 2:                  # one late point
            buf.write(int(rng.integers(0, N_SERIES)),
                      int(rng.integers(0, span)), float(step))
        elif kind == 3 and buf.buckets:  # a duplicate of a stored point
            bs = sorted(buf.buckets)[rng.integers(0, len(buf.buckets))]
            sidx, ts, _ = buf.buckets[bs].cols.view()
            at = rng.integers(0, len(ts))
            buf.write(int(sidx[at]), int(ts[at]), -float(step))
        else:
            lo = int(rng.integers(-50, span))
            check_reads(buf, lo, lo + int(rng.integers(1, span)))
        if rng.random() < 0.3:
            lo = int(rng.integers(0, span))
            check_reads(buf, lo, lo + int(rng.integers(1, span)),
                        idxs=rng.integers(0, N_SERIES, 3).tolist())
    check_reads(buf, 0, span)
    for bs in sorted(buf.buckets):
        b = buf.buckets[bs]
        assert b.indexed_n <= b.cols.n
        assert sorted(b.order.tolist()) == list(range(b.indexed_n))


def test_a_reader_and_a_writer_on_one_shard():
    """A second of reads beside appends, each under the lock the shard's
    callers hold: every read equals the scan of the same instant, and a
    write that returned before a read began is in that read."""
    buf, lock = new_buffer(), threading.Lock()
    done, failures, wrote, reads = threading.Event(), [], [-1], [0]
    steps = 3 * BLOCK // 10

    def write():
        k = 0
        while not done.is_set():
            with lock:
                scrape(buf, k % steps, value=k)
            wrote[0] = k                 # acknowledged
            k += 1
            if k % 50 == 0:
                time.sleep(0.001)

    def read():
        rng = np.random.default_rng(1)
        while not done.is_set():
            acked = wrote[0]
            idxs = rng.integers(0, N_SERIES, 3).tolist()
            with lock:
                got = buf.read_many(idxs, 0, 3 * BLOCK)
                want = [scan_read(buf, idx, 0, 3 * BLOCK) for idx in idxs]
            for g, w in zip(got, want):
                if not (g[0].tobytes() == w[0].tobytes()
                        and g[1].tobytes() == w[1].tobytes()):
                    failures.append(("differs from the scan", idxs, acked))
                at = np.flatnonzero(g[0] == (acked % steps) * 10)
                if acked >= 0 and not (len(at) == 1 and g[1][at[0]] >= acked):
                    failures.append(("acknowledged write not read", acked))
            reads[0] += 1

    threads = [threading.Thread(target=write, name="test-writer"),
               threading.Thread(target=read, name="test-reader")]
    before = counters()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)          # hand the GIL over mid-read, often
    try:
        for th in threads:
            th.start()
        time.sleep(1.0)
    finally:
        done.set()
        for th in threads:
            th.join(timeout=30)
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    assert not failures, failures[:3]
    got = moved(before)
    assert reads[0] > 20 and got["tail_scans"] > 0 and got["builds"] >= 3
