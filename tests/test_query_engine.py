"""Query engine tests: PromQL parsing, executor semantics (selectors,
temporal functions, aggregation, binary ops, histogram_quantile) against
in-memory storage (reference behaviors from src/query/functions and the
promql engine the reference embeds)."""

import math

import numpy as np
import pytest

from m3_tpu.query import Engine, METRIC_NAME, Tags, parse
from m3_tpu.query import promql
from m3_tpu.query.executor import QueryError
from m3_tpu.query.model import MatchType

S = 1_000_000_000
MIN = 60 * S
STEP = 30 * S


class MemStorage:
    """Minimal fetch_raw storage: list of (tags-dict, times, values)."""

    def __init__(self):
        self.series = []

    def add(self, tags, t, v):
        self.series.append((
            {k.encode() if isinstance(k, str) else k:
             x.encode() if isinstance(x, str) else x for k, x in tags.items()},
            np.asarray(t, np.int64), np.asarray(v, np.float64)))
        return self

    def fetch_raw(self, matchers, start_ns, end_ns):
        out = {}
        for i, (tags, t, v) in enumerate(self.series):
            if all(m.matches(tags.get(m.name, b"")) for m in matchers):
                keep = (t >= start_ns) & (t < end_ns)
                sid = b",".join(k + b"=" + val for k, val in sorted(tags.items()))
                out[sid] = {"tags": tags, "t": t[keep], "v": v[keep]}
        return out


@pytest.fixture
def storage():
    st = MemStorage()
    t = np.arange(0, 40) * 15 * S  # 15s resolution, 10 minutes
    st.add({"__name__": "http_requests_total", "job": "api", "instance": "a"},
           t, np.arange(40) * 10.0)  # steady 10/15s counter
    st.add({"__name__": "http_requests_total", "job": "api", "instance": "b"},
           t, np.arange(40) * 5.0)
    st.add({"__name__": "http_requests_total", "job": "db", "instance": "c"},
           t, np.arange(40) * 2.0)
    st.add({"__name__": "memory_bytes", "job": "api", "instance": "a"},
           t, np.full(40, 100.0))
    st.add({"__name__": "memory_bytes", "job": "api", "instance": "b"},
           t, np.full(40, 300.0))
    return st


@pytest.fixture
def engine(storage):
    return Engine(storage)


def run(engine, q, start=5 * MIN, end=9 * MIN, step=STEP):
    return engine.execute_range(q, start, end, step)


class TestParser:
    def test_selector_with_matchers_range_offset(self):
        ast = parse('http_requests_total{job="api",instance=~"a|b"}[5m] offset 1m')
        assert ast.name == b"http_requests_total"
        assert ast.range_ns == 5 * MIN
        assert ast.offset_ns == MIN
        assert ast.matchers[0].name == b"job"
        assert ast.matchers[1].type == MatchType.REGEXP

    def test_precedence(self):
        ast = parse("a + b * c")
        assert ast.op == "+"
        assert ast.rhs.op == "*"
        ast = parse("a * b + c")
        assert ast.op == "+"
        assert ast.lhs.op == "*"
        ast = parse("2 ^ 3 ^ 2")  # right-assoc
        assert ast.rhs.op == "^"

    def test_aggregation_modifiers_both_positions(self):
        a1 = parse("sum by (job) (x)")
        a2 = parse("sum(x) by (job)")
        assert a1.grouping == a2.grouping == (b"job",)
        a3 = parse("topk(3, x)")
        assert a3.op == "topk" and isinstance(a3.param, promql.NumberLiteral)

    def test_bool_and_matching(self):
        ast = parse("a > bool b")
        assert ast.bool_mode
        ast = parse("a / on(job) group_left(env) b")
        assert ast.matching.on and ast.matching.labels == (b"job",)
        assert ast.matching.group_left
        assert ast.matching.include == (b"env",)

    def test_unary_minus_precedence(self):
        # Unary '-' binds between '^' and '*' (Go/prom spec).
        eng = Engine(MemStorage())
        out = run(eng, "-2^2")
        np.testing.assert_allclose(out.values[0], -4.0)
        out = run(eng, "-2*3")
        np.testing.assert_allclose(out.values[0], -6.0)

    def test_modulo_truncated(self):
        eng = Engine(MemStorage())
        out = run(eng, "-5 % 3")
        np.testing.assert_allclose(out.values[0], -2.0)  # Go math.Mod

    def test_string_escapes_preserve_utf8(self):
        ast = parse('{env="café", path="a\\nb"}')
        assert ast.matchers[0].value == "café".encode()
        assert ast.matchers[1].value == b"a\nb"

    def test_durations(self):
        assert promql.parse_duration_ns("1h30m") == 90 * 60 * S
        assert promql.parse_duration_ns("500ms") == 500_000_000

    def test_parse_errors(self):
        for bad in ["sum(", "a{job=}", "rate(x[5m)", "topk(x)"]:
            with pytest.raises(ValueError):
                parse(bad)


class TestSelectors:
    def test_instant_vector_lookback(self, engine):
        blk = run(engine, "memory_bytes")
        assert blk.n_series == 2
        assert np.all(blk.values[0] == 100.0) or np.all(blk.values[1] == 100.0)

    def test_matcher_filtering(self, engine):
        blk = run(engine, 'http_requests_total{job="api"}')
        assert blk.n_series == 2
        blk = run(engine, 'http_requests_total{job!="api"}')
        assert blk.n_series == 1

    def test_offset(self, engine):
        blk = run(engine, "http_requests_total offset 1m")
        base = run(engine, "http_requests_total")
        # offset shifts values back: at time t we see t-1m's value
        assert blk.values[0][4] == base.values[0][2]  # 2 steps of 30s = 1m


class TestTemporalFunctions:
    # rtol 1e-6 throughout: the rate family finishes on device in f32
    # (one packed transfer); exact-window cases land within ~3e-8.

    def test_rate_steady_counter(self, engine):
        blk = run(engine, "rate(http_requests_total[2m])")
        # instance a increments 10 per 15s -> 2/3 per second
        rates = {t.as_dict()[b"instance"]: v for t, v in
                 zip(blk.series_tags, blk.values)}
        np.testing.assert_allclose(rates[b"a"], 10 / 15, rtol=1e-6)
        np.testing.assert_allclose(rates[b"b"], 5 / 15, rtol=1e-6)
        # rate drops the metric name
        assert all(t.get(METRIC_NAME) is None for t in blk.series_tags)

    def test_increase(self, engine):
        blk = run(engine, "increase(http_requests_total[2m])")
        rates = {t.as_dict()[b"instance"]: v for t, v in
                 zip(blk.series_tags, blk.values)}
        np.testing.assert_allclose(rates[b"a"], 10 / 15 * 120, rtol=1e-6)

    def test_avg_over_time_gauge(self, engine):
        blk = run(engine, "avg_over_time(memory_bytes[2m])")
        vals = {t.as_dict()[b"instance"]: v for t, v in
                zip(blk.series_tags, blk.values)}
        np.testing.assert_allclose(vals[b"a"], 100.0)
        np.testing.assert_allclose(vals[b"b"], 300.0)


class TestAggregation:
    def test_sum_by(self, engine):
        blk = run(engine, "sum by (job) (rate(http_requests_total[2m]))")
        assert blk.n_series == 2
        vals = {t.as_dict()[b"job"]: v for t, v in zip(blk.series_tags, blk.values)}
        np.testing.assert_allclose(vals[b"api"], 15 / 15, rtol=1e-6)
        np.testing.assert_allclose(vals[b"db"], 2 / 15, rtol=1e-6)

    def test_sum_without(self, engine):
        blk = run(engine, "sum without (instance) (memory_bytes)")
        assert blk.n_series == 1
        np.testing.assert_allclose(blk.values[0], 400.0)
        assert blk.series_tags[0].as_dict() == {b"job": b"api"}

    def test_global_aggregations(self, engine):
        for q, exp in [("sum(memory_bytes)", 400.0), ("min(memory_bytes)", 100.0),
                       ("max(memory_bytes)", 300.0), ("avg(memory_bytes)", 200.0),
                       ("count(memory_bytes)", 2.0)]:
            blk = run(engine, q)
            assert blk.n_series == 1, q
            np.testing.assert_allclose(blk.values[0], exp, err_msg=q)

    def test_stddev(self, engine):
        blk = run(engine, "stddev(memory_bytes)")
        np.testing.assert_allclose(blk.values[0], 100.0)  # population stddev

    def test_quantile(self, engine):
        blk = run(engine, "quantile(0.5, memory_bytes)")
        np.testing.assert_allclose(blk.values[0], 200.0)

    def test_topk(self, engine):
        blk = run(engine, "topk(1, memory_bytes)")
        assert blk.n_series == 1
        assert blk.series_tags[0].as_dict()[b"instance"] == b"b"

    def test_count_values(self, engine):
        blk = run(engine, 'count_values("val", memory_bytes)')
        got = {t.as_dict()[b"val"]: v[0] for t, v in
               zip(blk.series_tags, blk.values)}
        assert got == {b"100": 1.0, b"300": 1.0}


class TestBinaryOps:
    def test_vector_scalar(self, engine):
        blk = run(engine, "memory_bytes / 100")
        assert sorted(v[0] for v in blk.values) == [1.0, 3.0]

    def test_vector_vector_one_to_one(self, engine):
        blk = run(engine, 'memory_bytes / on(instance) '
                          'http_requests_total{job="api"}')
        assert blk.n_series == 2

    def test_comparison_filters(self, engine):
        blk = run(engine, "memory_bytes > 200")
        finite = [np.isfinite(v).all() for v in blk.values]
        # only instance b (300) survives; filter keeps original values
        surviving = [v for v, f in zip(blk.values, finite) if f]
        assert len(surviving) == 1
        np.testing.assert_allclose(surviving[0], 300.0)

    def test_comparison_bool(self, engine):
        blk = run(engine, "memory_bytes > bool 200")
        got = sorted(v[0] for v in blk.values)
        assert got == [0.0, 1.0]

    def test_scalar_arithmetic(self, engine):
        out = run(engine, "2 + 3 * 4")
        np.testing.assert_allclose(out.values[0], 14.0)

    def test_set_ops(self, engine):
        blk = run(engine, 'memory_bytes and http_requests_total{instance="a"}')
        assert blk.n_series == 1
        blk = run(engine, 'memory_bytes unless http_requests_total{instance="a"}')
        assert [t.as_dict()[b"instance"] for t in blk.series_tags] == [b"b"]

    def test_many_to_many_rejected(self, engine):
        with pytest.raises(QueryError):
            run(engine, "memory_bytes / on(job) http_requests_total")


class TestFunctions:
    def test_math(self, engine):
        blk = run(engine, "sqrt(memory_bytes)")
        assert sorted(v[0] for v in blk.values) == [10.0, pytest.approx(math.sqrt(300))]

    def test_clamp(self, engine):
        blk = run(engine, "clamp(memory_bytes, 150, 250)")
        assert sorted(v[0] for v in blk.values) == [150.0, 250.0]

    def test_absent(self, engine):
        blk = run(engine, 'absent(nonexistent_metric{foo="bar"})')
        assert blk.n_series == 1
        np.testing.assert_allclose(blk.values[0], 1.0)
        blk = run(engine, "absent(memory_bytes)")
        assert np.all(np.isnan(blk.values[0]))

    def test_scalar_vector_roundtrip(self, engine):
        blk = run(engine, "vector(42)")
        np.testing.assert_allclose(blk.values[0], 42.0)
        blk = run(engine, "scalar(vector(7)) + 1")
        np.testing.assert_allclose(blk.values[0], 8.0)

    def test_label_replace(self, engine):
        blk = run(engine, 'label_replace(memory_bytes, "env", "prod-$1", '
                          '"instance", "(.*)")')
        envs = sorted(t.as_dict()[b"env"] for t in blk.series_tags)
        assert envs == [b"prod-a", b"prod-b"]

    def test_time(self, engine):
        blk = run(engine, "time()")
        np.testing.assert_allclose(blk.values[0][0], 5 * 60.0)


class TestAgainstRealStorage:
    def test_promql_over_database(self):
        """End-to-end: tagged writes into the real storage engine, PromQL
        range query through LocalStorage (the §3.3 read path minus RPC)."""
        from m3_tpu.index.namespace_index import NamespaceIndex
        from m3_tpu.parallel.sharding import ShardSet
        from m3_tpu.query import LocalStorage
        from m3_tpu.storage.database import Database
        from m3_tpu.storage.namespace import NamespaceOptions

        T0 = 1_600_000_000 * S
        now = {"t": T0}
        db = Database(ShardSet(8), clock=lambda: now["t"])
        db.create_namespace(b"metrics", NamespaceOptions(index_enabled=True),
                            index=NamespaceIndex(clock=lambda: now["t"]))
        for i in range(40):
            now["t"] = T0 + i * 15 * S  # stay inside the acceptance window
            for inst, slope in [(b"a", 10.0), (b"b", 5.0)]:
                tags = {b"__name__": b"requests_total", b"instance": inst}
                sid = b"requests_total|instance=" + inst
                db.write(b"metrics", sid, T0 + i * 15 * S, slope * i, tags=tags)
        eng = Engine(LocalStorage(db, b"metrics"))
        blk = eng.execute_range("sum(rate(requests_total[2m]))",
                                T0 + 5 * MIN, T0 + 9 * MIN, STEP)
        assert blk.n_series == 1
        np.testing.assert_allclose(blk.values[0], 15 / 15, rtol=1e-6)


class TestCostEnforcement:
    def test_per_query_budget_released_between_queries(self, storage):
        from m3_tpu.utils.cost import CostLimitExceeded, Enforcer

        glob = Enforcer(limit=500, name="global")
        eng = Engine(storage, cost_enforcer=glob)
        # Each query fetches well under the limit; many in sequence must NOT
        # exhaust the global budget (charges are released per query).
        for _ in range(20):
            run(eng, "memory_bytes")
        assert glob.current() == 0

    def test_over_limit_query_rejected_and_rolled_back(self, storage):
        from m3_tpu.utils.cost import CostLimitExceeded, Enforcer

        glob = Enforcer(limit=10_000, name="global")
        eng = Engine(storage, cost_enforcer=glob, per_query_cost_limit=10)
        with pytest.raises(CostLimitExceeded):
            run(eng, "http_requests_total")  # 3 series x 40 points > 10
        assert glob.current() == 0  # failed query leaves no residue
        eng2 = Engine(storage, cost_enforcer=glob)
        run(eng2, "memory_bytes")  # global budget unaffected


class TestHistogramQuantile:
    def test_le_buckets(self):
        st = MemStorage()
        t = np.arange(0, 40) * 15 * S
        # Cumulative bucket counts: 60% <= 0.1, 90% <= 0.5, 100% <= +Inf
        for le, frac in [("0.1", 0.6), ("0.5", 0.9), ("+Inf", 1.0)]:
            st.add({"__name__": "req_duration_bucket", "le": le, "job": "api"},
                   t, np.full(40, 100.0 * frac))
        eng = Engine(st)
        blk = run(eng, "histogram_quantile(0.5, req_duration_bucket)")
        assert blk.n_series == 1
        # rank 50 falls in the first bucket: 0 + 0.1 * (50/60)
        np.testing.assert_allclose(blk.values[0], 0.1 * 50 / 60, rtol=1e-6)
        blk = run(eng, "histogram_quantile(0.99, req_duration_bucket)")
        # above 90% -> +Inf bucket -> returns lower bound 0.5
        np.testing.assert_allclose(blk.values[0], 0.5)


class TestMathDateFunctions:
    """Round-4 function-table completion: trig, date, pi, absent_over_time
    (promql functions.go parity)."""

    def test_trig_family(self, engine):
        base = run(engine, "http_requests_total")
        for name, fn in [("sin", np.sin), ("cos", np.cos), ("tan", np.tan),
                         ("atan", np.arctan), ("sinh", np.sinh),
                         ("tanh", np.tanh), ("asinh", np.arcsinh)]:
            blk = run(engine, f"{name}(http_requests_total)")
            np.testing.assert_allclose(blk.values, fn(base.values),
                                       rtol=1e-9, equal_nan=True)
        blk = run(engine, "deg(rad(http_requests_total))")
        np.testing.assert_allclose(blk.values, base.values, rtol=1e-9)
        # domain errors yield NaN, not exceptions
        blk = run(engine, "acos(http_requests_total)")
        assert np.isnan(blk.values[base.values > 1]).all()

    def test_pi_scalar(self, engine):
        blk = run(engine, "vector(pi())")
        np.testing.assert_allclose(blk.values, np.pi)

    def test_date_functions_on_known_timestamp(self, engine):
        # 2021-02-15T12:34:56Z
        ts = 1613392496.0
        for name, want in [("year", 2021), ("month", 2), ("day_of_month", 15),
                           ("day_of_week", 1), ("hour", 12), ("minute", 34),
                           ("day_of_year", 46), ("days_in_month", 28)]:
            blk = run(engine, f"{name}(vector({ts}))")
            assert (blk.values == float(want)).all(), (name, blk.values)

    def test_date_no_arg_uses_eval_time(self, engine):
        blk = run(engine, "year()")
        t = run(engine, "vector(time())")
        import datetime as dt
        want = [dt.datetime.fromtimestamp(v, dt.timezone.utc).year
                for v in t.values[0]]
        np.testing.assert_array_equal(blk.values[0], want)

    def test_absent_over_time(self, engine):
        blk = run(engine, "absent_over_time(http_requests_total[2m])")
        assert blk.n_series == 1
        assert np.isnan(blk.values).all()  # data exists everywhere
        blk = run(engine, 'absent_over_time(no_such_metric{job="x"}[2m])')
        assert blk.n_series == 1
        assert (blk.values == 1.0).all()
        assert blk.series_tags[0].as_dict().get(b"job") == b"x"

    def test_date_no_arg_is_vector(self, engine):
        """dateWrapper emits a one-series vector with empty labels, so
        `x and on() (hour() < 24)` vector-matches (the alerting idiom)."""
        blk = run(engine, "memory_bytes and on() (hour() < 24)")
        assert blk.n_series == 2
        blk = run(engine, "memory_bytes and on() (hour() > 24)")
        finite = np.isfinite(blk.values)
        assert not finite.any()


def test_group_aggregation(engine):
    blk = run(engine, "group by (job) (http_requests_total)")
    assert blk.n_series == 2
    assert (blk.values == 1.0).all()
    blk = run(engine, "group(memory_bytes)")
    assert blk.n_series == 1 and (blk.values == 1.0).all()


class TestSubqueries:
    """`expr[range:res]` — prometheus promql/engine.go evalSubquery: the
    inner expression evaluates at res-aligned absolute timestamps, each
    outer window sees the inner values in (T-range, T]."""

    def test_parse_shapes(self):
        ast = parse("max_over_time(rate(m[5m])[30m:1m])")
        sub = ast.args[0]
        assert isinstance(sub, promql.Subquery)
        assert sub.range_ns == 30 * MIN and sub.step_ns == MIN
        assert parse("avg_over_time(x[1h:])").args[0].step_ns == 0
        off = parse("sum_over_time((a + b)[10m:30s] offset 5m)").args[0]
        assert off.offset_ns == 5 * MIN and off.step_ns == 30 * S
        with pytest.raises(promql.ParseError):
            parse("x[5m:bogus]")
        with pytest.raises(QueryError):
            # bare subquery outside a range function
            Engine(MemStorage()).execute_range("x[5m:1m]", 0, MIN, STEP)

    def test_max_over_time_of_rate_subquery(self, engine):
        """Brute-force reference: evaluate rate() per res-aligned timestamp
        with instant queries, take the max of each trailing window."""
        q = "max_over_time(rate(http_requests_total[2m])[6m:1m])"
        got = run(engine, q)
        res, rng = MIN, 6 * MIN
        for si in range(got.n_series):
            tags = got.series_tags[si]
            sel = "rate(http_requests_total{instance=\"%s\"}[2m])" % (
                tags.get(b"instance").decode())
            for i, T in enumerate(got.meta.times()):
                ks = [k * res for k in range(int(T - rng) // res + 1,
                                             int(T) // res + 1)]
                vals = []
                for t_ev in ks:
                    b = engine.execute_range(sel, t_ev, t_ev, res)
                    if b.n_series:
                        v = float(b.values[0][0])
                        if math.isfinite(v):
                            vals.append(v)
                want = max(vals) if vals else float("nan")
                have = float(got.values[si][i])
                if math.isnan(want):
                    assert math.isnan(have)
                else:
                    assert have == pytest.approx(want, rel=1e-9), (si, i)

    def test_default_resolution_is_query_step(self, engine):
        a = run(engine, "avg_over_time(memory_bytes[3m:])")
        b = run(engine, "avg_over_time(memory_bytes[3m:30s])")
        assert np.allclose(a.values, b.values, equal_nan=True)

    def test_subquery_over_binary_expr(self, engine):
        got = run(engine, "sum_over_time((memory_bytes * 2)[2m:1m])")
        # memory series are constant 100/300 -> each 2m window holds 2
        # res-aligned evals of the doubled value.
        by_inst = {t.get(b"instance"): v for t, v in
                   zip(got.series_tags, got.values)}
        assert np.allclose(by_inst[b"a"], 400.0)
        assert np.allclose(by_inst[b"b"], 1200.0)

    def test_subquery_offset(self, engine):
        plain = run(engine, "avg_over_time(memory_bytes[2m:30s])")
        off = run(engine, "avg_over_time(memory_bytes[2m:30s] offset 2m)",
                  start=7 * MIN)
        # constant series: offset shifts the window but values are equal
        assert np.allclose(off.values, plain.values[:, : off.values.shape[1]])

    def test_non_dividing_resolution_counts_exact_samples(self, engine):
        # 45s does not divide the 30s query step -> the packed-gather path;
        # windows must hold exactly the 45s-aligned timestamps in
        # (T-3m, T], i.e. 4 per window.
        got = run(engine, "count_over_time(memory_bytes[3m:45s])")
        assert np.allclose(got.values, 4.0)
        got = run(engine, "min_over_time(memory_bytes[3m:45s])")
        by_inst = {t.get(b"instance"): v for t, v in
                   zip(got.series_tags, got.values)}
        assert np.allclose(by_inst[b"a"], 100.0)


    def test_end_not_on_step_grid(self, engine):
        # end - start not a multiple of step: the last output step is
        # BELOW end, and the fine grid must size to it (regression: the
        # HTTP drive passes arbitrary epoch-second ranges).
        got = engine.execute_range("avg_over_time(memory_bytes[2m:30s])",
                                   5 * MIN, 9 * MIN + 15 * S, STEP)
        ref = engine.execute_range("avg_over_time(memory_bytes[2m:30s])",
                                   5 * MIN, 9 * MIN, STEP)
        assert got.values.shape == ref.values.shape
        assert np.allclose(got.values, ref.values, equal_nan=True)

    def test_duplicate_offset_rejected(self):
        with pytest.raises(promql.ParseError):
            parse("rate(x[5m] offset 1h offset 0s)")

    def test_range_shorter_than_resolution(self, engine):
        # prom-legal: each window holds 0 or 1 res-aligned evals.
        got = run(engine, "last_over_time(memory_bytes[30s:1m])")
        finite = np.isfinite(got.values)
        assert finite.any() and not finite.all()
        assert np.all(np.isin(got.values[finite], (100.0, 300.0)))

    def test_increase_subquery_matches_plain_range(self, engine):
        # res | range with a continuously-sampled counter: the subquery
        # form must agree with the plain matrix selector to within the
        # extrapolation of one sample step (here the grids coincide).
        a = run(engine, "increase(http_requests_total[3m:15s])")
        b = run(engine, "increase(http_requests_total[3m])")
        av = {t.get(b"instance"): v for t, v in zip(a.series_tags, a.values)}
        bv = {t.get(b"instance"): v for t, v in zip(b.series_tags, b.values)}
        for inst in (b"a", b"b", b"c"):
            np.testing.assert_allclose(av[inst], bv[inst], rtol=1e-6)


class TestAtModifier:
    """`@ <ts>` / `@ start()` / `@ end()` pin a selector's evaluation time;
    the result is constant across the output grid (prom promql/engine.go)."""

    def test_parse(self):
        ast = parse("metric @ 1609746000")
        assert ast.at_ns == 1_609_746_000 * S
        assert parse("metric @ start()").at_ns == "start"
        assert parse("rate(m[5m] @ end())").args[0].at_ns == "end"
        assert parse("metric @ -5").at_ns == -5 * S
        sub = parse("avg_over_time(m[10m:1m] @ 1609746000)").args[0]
        assert isinstance(sub, promql.Subquery)
        assert sub.at_ns == 1_609_746_000 * S
        with pytest.raises(promql.ParseError):
            parse("metric @ start() @ end()")
        with pytest.raises(promql.ParseError):
            parse("(a + b) @ 5")
        with pytest.raises(promql.ParseError):
            parse("metric @ bogus()")

    def test_instant_at_is_constant(self, engine):
        got = run(engine, "http_requests_total{instance=\"a\"} @ 360")
        # pinned at t=360s -> the 360/15=24th sample (value 240) everywhere
        assert got.values.shape[1] == 9
        assert np.allclose(got.values, 240.0)

    def test_at_start_and_end(self, engine):
        base = run(engine, "http_requests_total{instance=\"a\"}")
        s_pin = run(engine, "http_requests_total{instance=\"a\"} @ start()")
        e_pin = run(engine, "http_requests_total{instance=\"a\"} @ end()")
        assert np.allclose(s_pin.values, base.values[0][0])
        assert np.allclose(e_pin.values, base.values[0][-1])

    def test_range_func_at(self, engine):
        pinned = run(engine, "increase(http_requests_total{instance=\"a\"}[2m] @ 480)")
        plain = run(engine, "increase(http_requests_total{instance=\"a\"}[2m])",
                    start=8 * MIN, end=8 * MIN, step=STEP)
        assert np.allclose(pinned.values, plain.values[0][0], rtol=1e-6)

    def test_at_with_offset(self, engine):
        # offset applies relative to the pinned time
        a = run(engine, "http_requests_total{instance=\"a\"} @ 480 offset 1m")
        b = run(engine, "http_requests_total{instance=\"a\"} @ 420")
        assert np.allclose(a.values, b.values)

    def test_subquery_at(self, engine):
        got = run(engine, "avg_over_time(memory_bytes[2m:30s] @ 480)")
        assert np.allclose(got.values[got.series_tags.index(
            next(t for t in got.series_tags if t.get(b"instance") == b"a"))],
            100.0)

    def test_sharded_fast_path_skips_at(self, engine):
        # @ on the inner selector must not take the mesh fast path blindly;
        # single-device engine: just assert correctness of the value.
        got = run(engine, "sum(increase(http_requests_total[2m] @ 480))")
        # all three counters: (10+5+2)/15s * 120s = 136
        assert np.allclose(got.values, 17 / 15 * 120, rtol=1e-6)

    def test_zero_range_and_resolution_rejected(self):
        with pytest.raises(promql.ParseError):
            parse("avg_over_time(x[5m:0s])")
        with pytest.raises(promql.ParseError):
            parse("avg_over_time(x[0s:1m])")
        with pytest.raises(promql.ParseError):
            parse("rate(x[0s])")
        with pytest.raises(promql.ParseError):
            parse("rate(m[5m] offset 0s offset 5m)")

    def test_single_step_empty_window_is_nan_not_crash(self, engine):
        # window (60s, 90s] holds no 1m-aligned timestamp: prometheus
        # returns an empty matrix; here the series row is all-NaN.
        blk = engine.execute_range("last_over_time(memory_bytes[30s:1m])",
                                   90 * S, 90 * S, S)
        assert blk.values.shape[1] == 1
        assert np.all(np.isnan(blk.values))

    def test_offset_before_range_rejected(self):
        # prom requires the range selector before any offset modifier
        with pytest.raises(promql.ParseError):
            parse("rate(c offset 5m [5m])")
        # ...but a subquery OF an offset selector stays legal
        parse("avg_over_time(x offset 5m [1h:])")


class TestUpstreamSemanticEdges:
    """Targeted upstream-conformance cases beyond the main suites."""

    def test_rate_with_counter_reset_through_engine(self):
        st = MemStorage()
        t = np.arange(0, 20) * 15 * S
        # counter climbs to 150, resets to 5, climbs again
        v = np.concatenate([np.arange(10) * 15.0 + 10,
                            np.arange(10) * 15.0 + 5])
        st.add({"__name__": "c"}, t, v)
        eng = Engine(st)
        blk = eng.execute_range("increase(c[2m])", 3 * MIN, 4 * MIN, STEP)
        vals = blk.values[0]
        finite = vals[np.isfinite(vals)]
        # every window spanning the reset must still be positive (the
        # pre-reset value is added back, promql extrapolation applies)
        assert (finite > 0).all(), vals

    def test_histogram_quantile_missing_inf_bucket_is_nan(self):
        # upstream: no le="+Inf" bucket -> NaN (total count unknowable)
        st = MemStorage()
        t = np.arange(0, 10) * 15 * S
        for le, frac in ((b"0.1", 10.0), (b"1", 40.0), (b"10", 100.0)):
            st.add({"__name__": "h_bucket", "le": le}, t, np.full(10, frac))
        eng = Engine(st)
        blk = eng.execute_range("histogram_quantile(0.5, h_bucket)",
                                MIN, 2 * MIN, STEP)
        assert np.all(np.isnan(blk.values)), blk.values

    def test_histogram_quantile_with_inf_bucket(self):
        st = MemStorage()
        t = np.arange(0, 10) * 15 * S
        for le, frac in ((b"0.1", 10.0), (b"1", 40.0), (b"10", 100.0),
                         (b"+Inf", 100.0)):
            st.add({"__name__": "h_bucket", "le": le}, t, np.full(10, frac))
        eng = Engine(st)
        blk = eng.execute_range("histogram_quantile(0.5, h_bucket)",
                                MIN, 2 * MIN, STEP)
        vals = blk.values[0][np.isfinite(blk.values[0])]
        # rank 50 of 100 -> (1, 10] bucket, interpolated to 2.5
        np.testing.assert_allclose(vals, 2.5)

    def test_only_inf_bucket_is_nan(self):
        # len(buckets) < 2: a lone +Inf bucket must be NaN, not 0.0
        st = MemStorage()
        t = np.arange(0, 10) * 15 * S
        st.add({"__name__": "h_bucket", "le": "+Inf"}, t, np.full(10, 100.0))
        eng = Engine(st)
        blk = eng.execute_range("histogram_quantile(0.5, h_bucket)",
                                MIN, 2 * MIN, STEP)
        assert np.all(np.isnan(blk.values)), blk.values

    def test_subquery_inside_aggregation(self, engine):
        # sum over per-series subquery averages — composes through the
        # aggregation path without touching the mesh fast path
        blk = run(engine, "sum(avg_over_time(memory_bytes[2m:30s]))")
        np.testing.assert_allclose(
            blk.values[0][np.isfinite(blk.values[0])], 400.0)



class TestRemainingFunctionConformance:
    """Exact-value coverage for the functions no other test touches
    (upstream promql/functions.go semantics)."""

    def test_hyperbolic_and_log2_sgn(self, engine):
        base = run(engine, "http_requests_total")
        for name, fn in [("cosh", np.cosh), ("acosh", np.arccosh),
                         ("atanh", np.arctanh), ("log2", np.log2),
                         ("sgn", np.sign)]:
            with np.errstate(invalid="ignore", divide="ignore"):
                want = fn(base.values)
            blk = run(engine, f"{name}(http_requests_total)")
            np.testing.assert_allclose(blk.values, want, rtol=1e-9,
                                       equal_nan=True, err_msg=name)

    def test_clamp_min_max(self, engine):
        blk = run(engine, "clamp_min(memory_bytes, 150)")
        assert sorted(v[0] for v in blk.values) == [150.0, 300.0]
        blk = run(engine, "clamp_max(memory_bytes, 150)")
        assert sorted(v[0] for v in blk.values) == [100.0, 150.0]

    def test_sort_desc(self, engine):
        # instant-query ordering by value, descending (functions.go sortDesc)
        blk = run(engine, "sort_desc(memory_bytes)")
        vals = [v[0] for v in blk.values]
        assert vals == sorted(vals, reverse=True) == [300.0, 100.0]

    def test_present_and_stdvar_over_time(self, engine):
        blk = run(engine, "present_over_time(memory_bytes[2m])")
        np.testing.assert_allclose(blk.values, 1.0)
        # constant series: population variance over any window is 0
        blk = run(engine, "stdvar_over_time(memory_bytes[2m])")
        np.testing.assert_allclose(blk.values, 0.0, atol=1e-9)
        # Linear counter 10/15s. A window holds every raw sample of
        # (T-1m, T] whatever the step (query/window.py; before PR 42 a
        # 30s step saw 2 of the 4): k=4 points with gap g=10, and stdvar
        # of k evenly spaced points is g^2*(k^2-1)/12 = 125.
        blk = run(engine, "stdvar_over_time(http_requests_total[1m])")
        k, g = 4, 10.0
        want = g * g * (k * k - 1) / 12.0
        filled = blk.values[0][np.isfinite(blk.values[0])]
        np.testing.assert_allclose(filled[2:], want, rtol=1e-6)
        # The same at a step that divides the cadence: 15s step, [1m].
        fine = engine.execute_range(
            "stdvar_over_time(http_requests_total[1m])",
            5 * MIN, 8 * MIN, 15 * S)
        want4 = 10.0 * 10.0 * (4 * 4 - 1) / 12.0
        vals = fine.values[0][np.isfinite(fine.values[0])]
        np.testing.assert_allclose(vals[3:], want4, rtol=1e-6)


class TestInterpreterAccountsForWhatItMoves:
    """An interpreter evaluation runs on the default backend and counts
    its result transfer once per materialised result: a `LazyBlock`
    family when `.values` is first read, an eager family when the block
    is built. The ≥4,096-row grouped reduce takes the f32 segment kernel."""

    N_BIG = 4100

    @staticmethod
    def _storage(n):
        st = MemStorage()
        t = np.arange(0, 40) * 15 * S
        for i in range(n):
            st.add({"__name__": "reqs", "job": f"j{i % 4}",
                    "instance": f"i{i}"}, t, np.arange(40) * float(i % 7 + 1))
        return st

    @pytest.mark.parametrize("query,n,lazy", [
        ("rate(reqs[2m])", 6, True),
        ("irate(reqs[2m])", 6, False),
        ("sum by (job) (reqs)", N_BIG, None),
    ])
    def test_answer_equals_reference_and_d2h_counted_once(
            self, query, n, lazy, monkeypatch):
        from m3_tpu.ops import series_agg
        from m3_tpu.query.block import LazyBlock
        from m3_tpu.utils.instrument import ROOT

        reduced = []
        kernel = series_agg.grouped_reduce

        def spy(vals, *args):
            reduced.append(np.shape(vals))
            return kernel(vals, *args)

        monkeypatch.setattr(series_agg, "grouped_reduce", spy)

        def d2h():
            snap = ROOT.snapshot()
            return (snap.get("telemetry.transfer.d2h_bytes", 0),
                    snap.get("telemetry.transfer.d2h_transfers", 0))

        eng = Engine(self._storage(n))
        start, end = 5 * MIN, 9 * MIN
        before = d2h()
        ref = eng.execute_range_ref(query, start, end, STEP)
        if lazy is not None:
            assert isinstance(ref, LazyBlock) == lazy
            if lazy:
                assert d2h() == before  # nothing materialised yet
            vals = ref.values
            assert ref.values is vals
            want = n * ref.meta.steps * 4
            assert d2h() == (before[0] + want, before[1] + 1)
        else:
            assert reduced == [(n, ref.meta.steps)]
        got = eng.execute_range(query, start, end, STEP)
        by_tags = {t.id(): row for t, row in zip(ref.series_tags, ref.values)}
        assert len(by_tags) == got.n_series == (4 if lazy is None else n)
        for t, row in zip(got.series_tags, got.values):
            np.testing.assert_allclose(row, by_tags[t.id()], rtol=1e-5)
        # and the plain arithmetic of steady counters: slope i%7+1 per 15 s
        slopes = np.array([i % 7 + 1 for i in range(n)], float)
        if lazy is None:
            steps = ref.meta.times() // (15 * S)
            for t, row in zip(ref.series_tags, ref.values):
                j = int(t.get(b"job")[1:])
                np.testing.assert_allclose(
                    row, slopes[j::4].sum() * steps, rtol=1e-5)
        else:
            inst = [int(t.get(b"instance")[1:]) for t in ref.series_tags]
            np.testing.assert_allclose(
                ref.values, np.repeat((slopes[inst] / 15.0)[:, None],
                                      ref.meta.steps, axis=1), rtol=1e-5)


def test_readme_lists_every_environment_option():
    """The README's "Environment options" table is the whole `M3_TPU_*`
    surface: the names read anywhere under m3_tpu/ and the table's rows
    are the same set."""
    import pathlib
    import re

    root = pathlib.Path(__file__).resolve().parent.parent
    in_code = set()
    for path in (root / "m3_tpu").rglob("*"):
        if path.suffix in (".py", ".md", ".txt"):
            in_code |= set(re.findall(r"M3_TPU_[A-Z0-9_]+", path.read_text()))
    readme = (root / "README.md").read_text()
    section = readme.split("## Environment options", 1)[1].split("\n## ", 1)[0]
    rows = re.findall(r"^\| `(M3_TPU_[A-Z0-9_]+)` \| [^|]+ \| [^|]+ \|$",
                      section, re.M)
    assert len(rows) == len(set(rows))
    assert set(rows) == in_code
