"""The thin mix's tail design: one schedule for every run, exact class
counts, and a seed that changes hosts and data only."""

import collections

import numpy as np

import tiny  # noqa: F401  (puts benchmark/ on sys.path)
from harness import datagen, schedule, spec


def _cell():
    return tiny.cell("cpu4k-query-thin").to_wire()


def test_schedule_is_the_same_for_any_seed():
    cell = _cell()
    a = schedule.arrivals(cell["traffic"], 20.0)
    assert np.array_equal(a, schedule.arrivals(cell["traffic"], 20.0))
    # a longer window extends the same stream
    assert np.array_equal(a, schedule.arrivals(cell["traffic"], 40.0)[:len(a)])
    r1 = schedule.requests_for(cell, 7, len(a))
    r2 = schedule.requests_for(cell, 3_000_000_019, len(a))
    assert [r["cls"] for r in r1] == [r["cls"] for r in r2]
    assert [r["cls"] for r in r1] == list(
        schedule.class_sequence(cell["traffic"], len(a)))


def test_whole_decks_hold_every_class_in_exactly_its_share():
    traffic = _cell()["traffic"]
    deck = schedule.deck(traffic["mix"])
    want = collections.Counter(deck)
    for decks in (1, 3, 17):
        seq = schedule.class_sequence(traffic, decks * len(deck))
        got = collections.Counter(int(c) for c in seq)
        assert got == {c: n * decks for c, n in want.items()}
        # and each deck by itself, not only the total
        for d in range(decks):
            part = seq[d * len(deck):(d + 1) * len(deck)]
            assert collections.Counter(int(c) for c in part) == want
    # the heaviest class is a fifth of the mix, so the 95th percentile
    # lies inside its mode and not between two classes
    heaviest = [m["class"] for m in traffic["mix"]].index("cpu-max-all-8")
    assert want[heaviest] / len(deck) == 0.2


def test_a_different_seed_changes_hosts_fields_end_and_data_only():
    cell = _cell()
    r1 = schedule.requests_for(cell, 7, 40)
    r2 = schedule.requests_for(cell, 8, 40)
    assert [r["cls"] for r in r1] == [r["cls"] for r in r2]
    assert [r["hosts"] for r in r1] != [r["hosts"] for r in r2]
    assert [r["path"] for r in r1] != [r["path"] for r in r2]
    assert r1 == schedule.requests_for(cell, 7, 40)
    # warm-up draws differ from the window's
    assert r1 != schedule.requests_for(cell, 7, 40, salt=1)
    cfg = cell["config"]
    v1, v2 = datagen.walk(cfg, 7, 30), datagen.walk(cfg, 8, 30)
    assert v1.shape == (cfg["scale"] * 10, 30) and not np.array_equal(v1, v2)
    assert np.array_equal(v1, datagen.walk(cfg, 7, 30))
    assert v1.max() <= 100
    big = 2**31 + 12345          # the driver's seeds pass 32 signed bits
    assert np.array_equal(datagen.walk(cfg, big, 5), datagen.walk(cfg, big, 5))
    assert datagen.series_labels(cfg, 7)[0]["hostname"] == "host_0"


def test_every_cell_and_reader_is_found_by_name():
    bench = spec.load_benchmark()
    decl = spec.layer_metric_declarations()
    for m in bench["per_layer"]:
        assert decl[m["name"]] == m, m["name"]
        assert callable(spec.load_reader("layer_metrics", m["name"]))
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        for m in cell.end_to_end:
            assert callable(spec.load_reader("end_to_end", m["name"]))
        assert {c["name"] for c in cell.classes} == {
            m["class"] for m in cell.traffic.get("mix", [])}
