"""The reduction from a profiler trace to numbers, against a small
trace recorded on a TPU v5e (benchmark/tests/small.xplane.pb: two rounds
of the pack kernel on [256 rows x 128 points], the decode kernel, and a
512x512 matmul, with the harness's bench.sync annotation)."""

import os

import pytest

import tiny  # noqa: F401
from harness import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
PACK = (r"^_encode_batch(\.\d+)?$", r'custom_call_target="tpu_custom_call"')


@pytest.fixture(scope="module")
def trace():
    return tr.Trace(os.path.join(HERE, "small.xplane.pb"))


def test_planes_ops_and_sync(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    assert len(trace.devices["/device:TPU:0"]) == 4330
    assert trace.sync_offset == 38555495314.0
    # perf_counter_ns maps onto the trace's clock and back
    assert trace.to_trace_ns(trace.sync_offset + 5.0) == 5.0


def test_busy_union_and_idle(trace):
    lo, hi = 46_000_000.0, 73_000_000.0
    busy = trace.busy(lo, hi)["/device:TPU:0"]
    assert all(b > a for a, b in busy)
    assert all(busy[i][1] < busy[i + 1][0] for i in range(len(busy) - 1))
    assert trace.busy_s(lo, hi) == pytest.approx(0.00067335, rel=1e-6)
    # clipping: half the window holds the first round only
    assert 0 < trace.busy_s(lo, 56_000_000.0) < trace.busy_s(lo, hi)
    assert trace.busy_s(0.0, 1000.0) == 0.0


def test_per_kernel_time_names_and_bytes(trace):
    calls, seconds, nbytes = trace.kernel(0, 1e12, *PACK)
    assert calls == 2
    assert seconds == pytest.approx(0.000319055, rel=1e-6)
    # by its own shapes: u32[480,256] out, four [256,256] 32-bit planes in
    assert nbytes == 2 * (480 * 256 * 4 + 4 * 256 * 256 * 4)
    ops = trace.op_seconds(0, 1e12)
    top = sorted(ops, key=ops.get, reverse=True)[:3]
    assert top == ["_encode_batch.1", "run.1", "while.10"]   # stable names
    assert tr.op_name("%fusion.1 = f32[4,2]{1,0} fusion(f32[4,2] %p)") == \
        "fusion.1"
    assert tr.hlo_bytes("%a = (s32[2,3], pred[8]) custom-call(bf16[4] %x), "
                        "custom_call_target=\"t\", x={u32[9]}") == 24 + 8 + 8


def test_roofline_share_and_unknown_device(trace):
    calls, seconds, nbytes = trace.kernel(0, 1e12, *PACK)
    r = tr.roofline_share(seconds, 0.0, nbytes, "TPU v5 lite")
    assert r["bound"] == "memory"
    assert r["share"] == pytest.approx(100 * (nbytes / 819e9) / seconds)
    assert 1.0 < r["share"] < 1.4          # far from 100: nothing hidden
    assert tr.roofline_share(1e-3, 197e12 * 1e-3, 1.0,
                             "TPU v5 lite")["bound"] == "compute"
    with pytest.raises(KeyError):
        tr.roofline_share(seconds, 0.0, nbytes, "TPU v9 imaginary")


def test_idle_gaps_go_to_the_first_active_host_label():
    busy = [(10.0, 20.0), (50.0, 60.0)]
    host = [(0.0, 40.0, "http"), (5.0, 30.0, "query.fetch"),
            (25.0, 28.0, "gc"), (70.0, 80.0, "http")]
    got = tr.attribute_gaps(busy, 0.0, 100.0, host,
                            ["gc", "query.fetch", "http"], "loadgen-wait")
    ns = {k: round(v * 1e9, 6) for k, v in got.items()}
    # 0-5 http; 5-10 fetch; 10-20 busy; 20-25 fetch; 25-28 gc; 28-30 fetch;
    # 30-40 http; 40-50 nobody; 50-60 busy; 60-70 nobody; 70-80 http; 80-100
    assert ns == {"http": 25.0, "query.fetch": 12.0, "gc": 3.0,
                  "loadgen-wait": 40.0}
    assert sum(ns.values()) == 100.0 - tr.total(busy)
    assert tr.union([(3, 5), (1, 2), (4, 9), (2, 2.5)]) == [(1, 2.5), (3, 9)]
