"""Index query DSL (reference: src/m3ninx/idx/query.go — term / regexp /
conjunction / disjunction / negation builders compiled into searchers)."""

from __future__ import annotations

import dataclasses
import re
from typing import Optional, Tuple

# Bytes that start regex syntax; a literal prefix scan stops at the first
# one (mirrors regexp/syntax LiteralPrefix consumed by fst/regexp's
# prefix-range prune).
_META = frozenset(b".^$*+?{}[]\\|()")
_QUANT = frozenset(b"*?{")
_META_BUT_BAR = bytes(sorted(_META - {0x7C}))


def literal_prefix(pattern: bytes) -> bytes:
    """Longest guaranteed literal prefix of a regexp over bytes.

    Conservative by construction: a too-SHORT prefix only widens the term
    range that gets automaton-matched afterwards, never the results.
    Rules: an alternation ANYWHERE voids the prefix (a top-level `|`
    lets a match start down the other branch, and telling top-level from
    grouped needs a full parse — give up the prune instead); otherwise
    stop at the first metacharacter, and `*`/`?`/`{` quantify the
    previous literal, so it is dropped from the prefix."""
    if 0x7C in pattern:  # "|"
        return b""
    out = bytearray()
    for c in pattern:
        if c in _META:
            if c in _QUANT and out:
                out.pop()
            break
        out.append(c)
    return bytes(out)


def literal_terms(pattern: bytes) -> Optional[Tuple[bytes, ...]]:
    """Every term a pattern can match, where its own bytes say so, else
    None (a scan decides).

    The test: no member of _META but `|`, so no escape, group, anchor,
    class, quantifier or flag can be present; the pattern is then a
    literal, or an alternation of literals of which each matches itself
    alone (Prometheus' FastRegexMatcher turns the same shape into set
    membership). An empty branch is the literal b"". Branches come back
    de-duplicated in the pattern's order. Conservative like
    literal_prefix: None only costs the scan."""
    if len(pattern.translate(None, _META_BUT_BAR)) != len(pattern):
        return None
    return tuple(dict.fromkeys(pattern.split(b"|")))


def literal_alternatives(pattern: bytes) -> Optional[Tuple[bytes, ...]]:
    """literal_terms of a pattern that IS an alternation (`host_1|host_22`);
    None for everything else, a lone literal included."""
    return literal_terms(pattern) if 0x7C in pattern else None


class Query:
    pass


@dataclasses.dataclass(frozen=True)
class AllQuery(Query):
    """Matches every document (m3ninx all searcher)."""


@dataclasses.dataclass(frozen=True)
class TermQuery(Query):
    field: bytes
    value: bytes


@dataclasses.dataclass(frozen=True)
class RegexpQuery(Query):
    field: bytes
    pattern: bytes

    def __post_init__(self):
        # Compile ONCE at construction (idx.NewRegexpQuery compiles the
        # automaton up front, and an invalid pattern fails here); every
        # per-segment scan reuses it. A pattern without metacharacters,
        # or an alternation of such, is always valid and resolves by
        # lookups in a frozen segment: its automaton waits for a caller
        # that asks (a mutable segment's dict walk, the oracle).
        no_scan = literal_terms(self.pattern) is not None
        object.__setattr__(self, "_compiled",
                           None if no_scan else re.compile(self.pattern))

    def compiled(self):
        c = self._compiled
        if c is None:
            c = re.compile(self.pattern)
            object.__setattr__(self, "_compiled", c)
        return c

    @property
    def fullmatch(self):
        """With `pattern`, what a segment's regexp_postings reads of a
        compiled pattern: a query stands in for one, and compiles only
        if a scan asks for the matcher."""
        return self.compiled().fullmatch


@dataclasses.dataclass(frozen=True)
class ConjunctionQuery(Query):
    queries: Tuple[Query, ...]


@dataclasses.dataclass(frozen=True)
class DisjunctionQuery(Query):
    queries: Tuple[Query, ...]


@dataclasses.dataclass(frozen=True)
class NegationQuery(Query):
    query: Query


def new_term(field: bytes, value: bytes) -> TermQuery:
    return TermQuery(field, value)


def new_regexp(field: bytes, pattern: bytes) -> RegexpQuery:
    return RegexpQuery(field, pattern)  # constructor validates eagerly


def new_conjunction(*queries: Query) -> Query:
    flat = []
    for q in queries:
        if isinstance(q, ConjunctionQuery):
            flat.extend(q.queries)
        else:
            flat.append(q)
    return flat[0] if len(flat) == 1 else ConjunctionQuery(tuple(flat))


def new_disjunction(*queries: Query) -> Query:
    flat = []
    for q in queries:
        if isinstance(q, DisjunctionQuery):
            flat.extend(q.queries)
        else:
            flat.append(q)
    return flat[0] if len(flat) == 1 else DisjunctionQuery(tuple(flat))


def new_negation(q: Query) -> NegationQuery:
    return NegationQuery(q)
