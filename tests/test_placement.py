"""QueryPlacement decision model: measured transfer + rate EWMAs drive the
device-vs-host routing (m3_tpu/query/placement.py). The decision math
runs on injected measurements; the final test drives the LIVE probe
against this process's default jax backend (compile + a 1MB transfer)."""

import numpy as np

from m3_tpu.query.placement import QueryPlacement, _ewma


class _FakeDev:
    platform = "cpu"
    id = 0


def _mk(mode="auto", bw=None, rtt=0.003, host_rate=None, accel_rate=None):
    p = QueryPlacement()
    p._mode = mode
    p._cpu_checked = True
    p._cpu_device = _FakeDev()
    p._probed_at = float("inf")  # suppress the live probe
    p._d2h_bw = bw
    p._rtt = rtt
    p._host_rate = host_rate
    p._accel_rate = accel_rate
    return p


CELLS = 10_000 * 447          # the bench grid
RESULT = 10_000 * 110 * 4     # one f32 result plane
RATES = dict(host_rate=150e6, accel_rate=5e9)  # both sides observed


class TestChoose:
    def test_slow_transfer_routes_host(self):
        p = _mk(bw=15e6, **RATES)  # 4.2MB result at 15MB/s = ~290ms
        assert p.choose(CELLS, RESULT) is p._cpu_device

    def test_fast_transfer_routes_device(self):
        p = _mk(bw=5e9, **RATES)  # transfer ~1ms
        assert p.choose(CELLS, RESULT) is None

    def test_tiny_result_routes_device_even_on_slow_transfer(self):
        # sum(rate(..)) shape: 110 floats. Host compute of 4.5M cells
        # (~30ms) loses to rtt + ~0 transfer.
        p = _mk(bw=15e6, **RATES)
        assert p.choose(CELLS, 110 * 4) is None

    def test_unmeasured_rate_stays_on_device(self):
        # No evaluation has run on the host yet: the model has no
        # host_rate, and an assumed one must not take work off the chip
        # (tiny evaluations would otherwise all go to the host).
        for missing in ("host_rate", "accel_rate"):
            rates = {**RATES, missing: None}
            assert _mk(bw=15e6, **rates).choose(CELLS, RESULT) is None
            assert _mk(bw=15e6, **rates).choose(100, 400) is None

    def test_mode_overrides(self):
        assert _mk(mode="device", bw=1e3).choose(CELLS, RESULT) is None
        p = _mk(mode="host", bw=1e12)
        assert p.choose(CELLS, RESULT) is p._cpu_device

    def test_no_probe_yet_prefers_device(self):
        p = _mk(bw=None, **RATES)
        assert p.choose(CELLS, RESULT) is None

    def test_no_cpu_backend_means_device(self):
        p = _mk(bw=1e3, **RATES)
        p._cpu_device = None
        assert p.choose(CELLS, RESULT) is None


class TestObserve:
    def test_host_observation_updates_host_rate(self):
        p = _mk()
        p.observe(_FakeDev(), cells=1_000_000, result_bytes=0, seconds=0.01)
        assert p._host_rate == 1e8
        # EWMA folds subsequent observations.
        p.observe(_FakeDev(), cells=1_000_000, result_bytes=0, seconds=0.02)
        assert 5e7 < p._host_rate < 1e8

    def test_accel_observation_nets_out_transfer(self):
        p = _mk(bw=100e6, rtt=0.0)
        # 0.05s total with 0.04s of modeled transfer -> 0.01s compute.
        p.observe(None, cells=1_000_000, result_bytes=4_000_000,
                  seconds=0.05)
        assert abs(p._accel_rate - 1e8) / 1e8 < 0.01

    def test_bad_observations_ignored(self):
        p = _mk()
        p.observe(None, cells=0, result_bytes=0, seconds=0.0)
        assert p._accel_rate is None

    def test_snapshot_shape(self):
        snap = _mk(bw=50e6, host_rate=1e8).snapshot()
        assert snap["mode"] == "auto"
        assert round(snap["d2h_bw_mb_s"], 1) == round(50e6 / 2**20, 1)
        assert snap["host_rate_cells_s"] == 1e8


def test_ewma():
    assert _ewma(None, 10.0) == 10.0
    assert np.isclose(_ewma(10.0, 20.0), 13.0)


def test_live_probe_rtt_excludes_compile():
    """The probe times the SECOND tiny dispatch: the first pays XLA
    compile + backend warmup and must not seed the RTT EWMA. Discriminating bound: measure this
    backend's actual compile+first-dispatch cost of an equivalent fresh
    jit in-test; the recorded rtt must undercut it (a compile-polluted
    rtt would be >= it by construction)."""
    import time

    import jax
    import jax.numpy as jnp

    # Process warm-up first: the first-ever jit call pays backend/global
    # init on top of the compile, which would inflate the reference
    # measurement ~7x and let a compile-polluted rtt slip under the bound.
    np.asarray(jax.jit(lambda x: x * 2)(jnp.arange(8)))
    # What a compile-polluted rtt would be on THIS backend, right now. A
    # fresh random constant embeds in the HLO, so neither the in-process
    # jit cache nor the persistent compilation cache (standard on TPU
    # VMs) can serve it — this is a REAL compile, every run.
    k = int(np.random.randint(1, 1 << 30))
    t0 = time.perf_counter()
    np.asarray(jax.jit(lambda x: x + k)(jnp.arange(8)))
    first_dispatch = time.perf_counter() - t0

    # Min of three probes: the timed warm dispatch is sub-ms, so one
    # scheduler preemption could push a single sample past the floor.
    # Each sample uses a FRESH instance (fresh _probe_fn, fresh compile):
    # re-arming one instance would let samples 2-3 ride the already-
    # compiled probe fn and stay warm even with the warm-up dispatch
    # regressed — min() would then hide exactly the pollution this test
    # exists to catch.
    rtts = []
    for _ in range(3):
        p = QueryPlacement()
        p._probe_link()
        assert p._rtt is not None and p._d2h_bw is not None
        rtts.append(p._rtt)
    rtt = min(rtts)
    # Regression check this exists for: remove the probe's warm-up
    # dispatch and rtt rises to ~first_dispatch, failing this bound on
    # every backend (compile dwarfs a warm round trip on CPU and TPU
    # alike).
    assert rtt < max(0.5 * first_dispatch, 0.005), (
        f"rtt {rtt * 1e3:.2f}ms vs compile+first-dispatch "
        f"{first_dispatch * 1e3:.2f}ms: compile-polluted")


def test_probe_guard_fresh_instance_even_early_in_uptime():
    """_probed_at starts as None, not 0.0: with a 0.0 sentinel the claim
    guard `now - 0.0 < PROBE_REFRESH_S` would skip every probe for the
    first PROBE_REFRESH_S of MONOTONIC time — i.e. the first minute
    after boot on Linux, where CLOCK_MONOTONIC is uptime. Hermetic: the
    guard method takes `now` explicitly, no backend or clock patching."""
    from m3_tpu.query.placement import PROBE_REFRESH_S

    p = QueryPlacement()
    assert p._claim_probe(1.0)  # "just booted": must probe
    assert p._probed_at == 1.0  # stamped
    # fresh: within the refresh window
    assert not p._claim_probe(1.0 + PROBE_REFRESH_S / 2)
    # stale: re-probes
    assert p._claim_probe(1.0 + PROBE_REFRESH_S + 1.0)
