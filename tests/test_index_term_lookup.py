"""How a frozen segment resolves a matcher's terms (PR 44).

A term is found by bisecting the dictionary's sorted list; a regexp whose
bytes name its terms — no metacharacter, or an alternation of such
literals — is that many lookups; anything else walks the term range its
literal prefix leaves. Whatever the route, the postings are those of a
plain scan of the whole dictionary with `fullmatch`, which is the oracle
here (it shares no code with the segment), and `index.terms.*` says which
route ran.

test_fuzz style: every case derives from a seed, failures print it."""

import re

import numpy as np
import pytest

from m3_tpu.index import query as iq
from m3_tpu.index import segment as seg_mod
from m3_tpu.index.namespace_index import NamespaceIndex
from m3_tpu.index.query import literal_alternatives, literal_terms
from m3_tpu.index.segment import (
    Document,
    ImmutableSegment,
    MutableSegment,
    execute,
    execute_ref,
)
from m3_tpu.utils import tracing, xtime
from test_index_property import PATTERNS

BLOCK = 4 * xtime.HOUR
T0 = 1_600_000_000 * xtime.SECOND // BLOCK * BLOCK  # a block's first instant


@pytest.mark.parametrize("pattern,want", [
    (b"a|b", (b"a", b"b")),
    (b"a|a", (b"a",)),
    (b"a|", (b"a", b"")),
    (b"|", (b"",)),
    (b"host_1|host_22", (b"host_1", b"host_22")),
    (b"b|a|b|c", (b"b", b"a", b"c")),
    (b"a\x00|\xff", (b"a\x00", b"\xff")),
    (b"a|b.", None),
    (b"(a|b)", None),
    (b"a\\|b", None),
    (b"^a|b$", None),
    (b"a|b*", None),
    (b"(?i)a|b", None),
    (b"a|[bc]", None),
    (b"a|b{2}", None),
    (b"a|b+", None),
    (b"a", None),
    (b"", None),
])
def test_literal_alternatives(pattern, want):
    assert literal_alternatives(pattern) == want
    # a lone literal is its own one term; nothing else differs
    assert literal_terms(pattern) == (
        (pattern,) if pattern in (b"a", b"") else want)
    if want is not None:
        # the claim the lookups rest on: the pattern matches exactly
        # its branches, whole
        cre = re.compile(pattern)
        for lit in want:
            assert cre.fullmatch(lit)
        for other in (b"x", b"ab", b"a|b", pattern):
            assert bool(cre.fullmatch(other)) == (other in want)


# ---------------------------------------------------------------------------
# regexp_postings against a scan of the whole dictionary
# ---------------------------------------------------------------------------

PARTS = [b"", b"a", b"ab", b"abc", b"abd", b"b", b"ba", b"\x00", b"a\x00",
         b"a\x00b", b"\xff", b"\xff\xff", b"z\xff", b"zz", b"host_1",
         b"host_12", b"host_123", b"P" * 64, b"P" * 64 + b"a",
         b"P" * 64 + b"\x00", b"Q" * 150]


def _rand_terms(rng):
    n = int(rng.integers(1, 40))
    out = set()
    for _ in range(n):
        k = int(rng.integers(1, 3))
        out.add(b"".join(PARTS[int(rng.integers(len(PARTS)))]
                         for _ in range(k)))
    if rng.random() < 0.5:
        out.add(b"")
    return sorted(out)


def _segment_over(rng, terms):
    """Each term held by one to three documents, a document holding one
    to three terms of field f (so spans overlap across terms)."""
    n_docs = max(len(terms), 4)
    held = [[] for _ in range(n_docs)]
    for t in terms:
        for d in rng.choice(n_docs, size=int(rng.integers(1, 4)),
                            replace=False):
            held[int(d)].append(t)
    mut = MutableSegment()
    mut.insert_batch([
        Document(b"doc-%04d" % i,
                 tuple((b"f", t) for t in ts) + ((b"g", b"x"),))
        for i, ts in enumerate(held)])
    return ImmutableSegment.from_mutable(mut)


def _scan(seg, field, pattern):
    """The plain reference: fullmatch over every term, spans unioned."""
    terms, offs, cat = seg.field_raw(field)
    cre = re.compile(pattern)
    keep = [i for i in range(len(terms)) if cre.fullmatch(terms[i])]
    if not keep:
        return np.zeros(0, np.int32)
    return np.unique(np.concatenate(
        [cat[offs[i]:offs[i + 1]] for i in keep]))


def _rand_alternation(rng, terms):
    k = int(rng.integers(2, 9))
    branches = []
    for _ in range(k):
        r = rng.random()
        if r < 0.6:
            branches.append(terms[int(rng.integers(len(terms)))])
        elif r < 0.9:
            branches.append(PARTS[int(rng.integers(len(PARTS)))] + b"x")
        else:
            branches.append(b"")
    return b"|".join(branches)


@pytest.mark.parametrize("seed", range(8))
def test_regexp_postings_equal_a_plain_scan(seed):
    rng = np.random.default_rng(4400 + seed)
    for round_ in range(12):
        terms = _rand_terms(rng)
        seg = _segment_over(rng, terms)
        assert seg.terms(b"f") == terms
        patterns = list(PATTERNS)
        patterns += [_rand_alternation(rng, terms) for _ in range(6)]
        patterns += [terms[int(rng.integers(len(terms)))] for _ in range(3)]
        patterns += [b"host_1", b"host_12|host_1", b"P" * 64 + b"|" + b"Q" * 150]
        for p in patterns:
            want = _scan(seg, b"f", p)
            ctx = f"seed={4400 + seed} round={round_} pattern={p!r} terms={terms}"
            got = seg.regexp_postings(b"f", re.compile(p))
            assert got.dtype == np.int32, ctx
            assert np.array_equal(got, want), ctx
            # a query stands in for its compiled pattern, and both
            # searchers agree with the scan
            q = iq.new_regexp(b"f", p)
            assert np.array_equal(seg.regexp_postings(b"f", q), want), ctx
            assert np.array_equal(execute(seg, q), want), ctx
            assert np.array_equal(execute_ref(seg, q), want), ctx


def test_term_postings_find_every_term_and_nothing_else():
    rng = np.random.default_rng(4444)
    for _ in range(20):
        terms = _rand_terms(rng)
        seg = _segment_over(rng, terms)
        _terms, offs, cat = seg.field_raw(b"f")
        for i, t in enumerate(terms):
            assert np.array_equal(seg.term_postings(b"f", t),
                                  cat[offs[i]:offs[i + 1]]), (terms, t)
        for t in (b"nope", b"a\x00\x00\x00", b"P" * 63, b"Q" * 151,
                  b"\xff\xff\xff"):
            if t not in terms:
                assert len(seg.term_postings(b"f", t)) == 0, (terms, t)
        assert len(seg.term_postings(b"absent", b"a")) == 0


# ---------------------------------------------------------------------------
# counters and costs: which route ran
# ---------------------------------------------------------------------------


def _hosts_segment(n=200):
    mut = MutableSegment()
    mut.insert_batch([
        Document(b"cpu|host_%d|%s" % (h, f),
                 ((b"__name__", b"cpu"), (b"hostname", b"host_%d" % h),
                  (b"field", f)))
        for h in range(n) for f in (b"usage_user", b"usage_idle")])
    return ImmutableSegment.from_mutable(mut)


class _Moved:
    """index.terms.* by difference: the counters are the process's."""

    NAMES = {"lookups": seg_mod._LOOKUPS, "literal_sets": seg_mod._LITERAL_SETS,
             "scans": seg_mod._SCANS, "terms_scanned": seg_mod._TERMS_SCANNED}

    def __init__(self):
        self.was = {k: c.value() for k, c in self.NAMES.items()}

    def __call__(self):
        return {k: c.value() - self.was[k] for k, c in self.NAMES.items()}


def test_an_alternation_of_hosts_is_a_set_of_lookups():
    seg = _hosts_segment()
    hosts = [b"host_%d" % h for h in (3, 17, 30, 31, 99, 150, 151, 199)]
    moved = _Moved()
    got = seg.regexp_postings(b"hostname", re.compile(b"|".join(hosts)))
    assert moved() == {"lookups": 8, "literal_sets": 1, "scans": 0,
                       "terms_scanned": 0}
    assert sorted(seg.ids_for(got)) == sorted(
        b"cpu|%s|%s" % (h, f) for h in hosts
        for f in (b"usage_user", b"usage_idle"))


def test_a_literal_pattern_is_one_lookup_whatever_its_prefix_range_holds():
    seg = _hosts_segment()
    moved = _Moved()
    # host_1 prefixes host_10..host_19 and host_100..host_199
    got = seg.regexp_postings(b"hostname", re.compile(b"host_1"))
    assert moved() == {"lookups": 1, "literal_sets": 1, "scans": 0,
                       "terms_scanned": 0}
    assert sorted(seg.ids_for(got)) == [b"cpu|host_1|usage_idle",
                                        b"cpu|host_1|usage_user"]
    assert len(seg.regexp_postings(b"hostname", re.compile(b"host_"))) == 0


def test_a_prefix_pattern_scans_its_range_only():
    seg = _hosts_segment()
    moved = _Moved()
    got = seg.regexp_postings(b"hostname", re.compile(b"host_1.*"))
    assert moved() == {"lookups": 0, "literal_sets": 0, "scans": 1,
                       "terms_scanned": 111}
    assert len(got) == 2 * 111
    moved = _Moved()
    seg.term_postings(b"hostname", b"host_7")
    seg.regexp_postings(b"absent", re.compile(b"a|b"))  # no such field
    assert moved() == {"lookups": 1, "literal_sets": 0, "scans": 0,
                       "terms_scanned": 0}


def test_counters_are_served_and_costs_land_on_a_detailed_index_query(
        monkeypatch):
    from m3_tpu.utils import instrument

    nsi = NamespaceIndex(block_size_ns=BLOCK)
    for h in range(50):
        nsi.insert(b"s%d" % h, {b"__name__": b"cpu",
                                b"hostname": b"host_%d" % h}, T0)
    snap = instrument.ROOT.snapshot()
    for name in ("lookups", "literal_sets", "scans", "terms_scanned"):
        assert "index.terms." + name in snap
    tracer = tracing.Tracer(sample_rate=1.0)
    monkeypatch.setattr(tracing, "TRACER", tracer)
    with tracer.span_from(tracing.SpanContext(7, 1), "asked") as root:
        ids = nsi.query(iq.new_conjunction(
            iq.new_term(b"__name__", b"cpu"),
            iq.new_regexp(b"hostname", b"host_1|host_2|host_33"),
            iq.new_negation(iq.new_regexp(b"hostname", b"host_3.*"))))
    with tracer.span("head-sampled") as plain:
        nsi.query(iq.new_regexp(b"hostname", b"host_4|host_5.*"))
    assert ids == [b"s1", b"s2"]
    (child,) = root.children
    assert child.name == "index.query"
    assert child.costs == {"lookups_n": 4, "terms_scanned_n": 11}
    # a root nobody asked for is not detailed: the span, no costs
    (child,) = plain.children
    assert child.name == "index.query" and child.costs == {}


# ---------------------------------------------------------------------------
# the query's own contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pattern", [b"a(", b"a|b(", b"[a", b"a{2,1}", b"*a"])
def test_an_invalid_pattern_still_fails_at_construction(pattern):
    with pytest.raises(re.error):
        iq.RegexpQuery(b"f", pattern)
    with pytest.raises(re.error):
        iq.new_regexp(b"f", pattern)


@pytest.mark.parametrize("pattern,waits", [
    (b"host_1", True), (b"host_1|host_2", True), (b"", True), (b"|", True),
    (b"host_1.*", False), (b"(a|b)", False), (b"a$", False),
])
def test_the_automaton_is_built_only_where_a_scan_can_use_it(pattern, waits):
    q = iq.RegexpQuery(b"f", pattern)
    assert (q._compiled is None) == waits
    cre = q.compiled()
    assert cre.pattern == pattern and q.compiled() is cre
    assert bool(q.fullmatch(b"host_1")) == bool(cre.fullmatch(b"host_1"))
    assert q == iq.RegexpQuery(b"f", pattern)
    assert hash(q) == hash(iq.RegexpQuery(b"f", pattern))
    # the dict walk of a mutable segment asks for it and gets it
    mut = MutableSegment()
    mut.insert(Document(b"d", ((b"f", b"host_1"),)))
    assert len(execute(mut, q)) == len(execute_ref(mut, q))


# ---------------------------------------------------------------------------
# NamespaceIndex.query over two blocks, thin's three matcher shapes
# ---------------------------------------------------------------------------

FIELDS = [b"usage_user", b"usage_system", b"usage_idle", b"usage_nice"]


def _two_block_index(n_hosts=60):
    nsi = NamespaceIndex(block_size_ns=BLOCK)
    for blk, hosts in ((0, range(0, 45)), (1, range(15, n_hosts))):
        for h in hosts:
            for f in FIELDS:
                nsi.insert(b"cpu|%03d|%s" % (h, f),
                           {b"__name__": b"cpu", b"hostname": b"host_%d" % h,
                            b"field": f, b"region": b"r%d" % (h % 3)},
                           T0 + blk * BLOCK)
        if blk == 0:
            nsi.tick(T0 + BLOCK + 1, retention_ns=30 * xtime.DAY)  # seal it
    return nsi


def _thin_shapes(rng, n_hosts):
    one = b"host_%d" % int(rng.integers(n_hosts))
    eight = b"|".join(b"host_%d" % int(h)
                      for h in rng.integers(0, n_hosts + 5, size=8))
    field = FIELDS[int(rng.integers(len(FIELDS)))]
    name = iq.new_term(b"__name__", b"cpu")
    return [
        iq.new_conjunction(name, iq.new_regexp(b"hostname", one),
                           iq.new_regexp(b"field", field)),
        iq.new_conjunction(name, iq.new_regexp(b"hostname", eight),
                           iq.new_regexp(b"field", field)),
        iq.new_conjunction(name, iq.new_regexp(b"hostname", eight)),
    ]


@pytest.mark.parametrize("seed", range(4))
def test_namespace_query_over_two_blocks_equals_the_oracle(seed):
    rng = np.random.default_rng(4460 + seed)
    nsi = _two_block_index()
    segs = nsi._snapshot_segments(0, 2**63 - 1)
    assert len(segs) == 2
    for _ in range(10):
        for q in _thin_shapes(rng, 60):
            want = sorted({i for s in segs
                           for i in s.ids_for(execute_ref(s, q))})
            assert nsi.query(q) == want, (4460 + seed, q)
            # one block's range reaches one segment
            want0 = sorted(segs[0].ids_for(execute_ref(segs[0], q)))
            assert nsi.query(q, T0, T0 + BLOCK) == want0, (4460 + seed, q)
