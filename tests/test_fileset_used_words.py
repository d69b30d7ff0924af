"""data.bin holds each row's used words, not the tile's padded width
(persist/fs.py): what is written, what a reader hands back, what a
padded fileset of an older tree still reads as, and what a rotten
count does. Every consumer downstream keeps the padded [S, MW] tile
and the row checksum stays adler32 of the padded row."""

import json
import os
import zlib

import numpy as np
import pytest

from m3_tpu.persist import fs as pfs
from m3_tpu.persist.diskio import CorruptionError
from m3_tpu.storage.block import SealedBlock, encode_block
from m3_tpu.storage.series import SeriesRegistry
from m3_tpu.utils import xtime

NS = b"default"
BLOCK = 2 * xtime.HOUR
T0 = 1_600_000_000 * xtime.SECOND - (1_600_000_000 * xtime.SECOND) % BLOCK
MW = 12

# row -> (words by column, nbits): the shapes a count has to survive
CRAFTED = {
    "empty": ({}, 0),
    "full": ({c: 0x80000001 + c for c in range(MW)}, MW * 32),
    # nbits reaches into word 4, which is all zeros: the count is 4
    "last_used_word_zero": ({0: 7, 3: 0xFFFF0000}, 4 * 32 + 9),
    # a set bit past ceil(nbits / 32): the count follows the bit
    "bit_past_nbits": ({0: 1, 1: 2, 9: 0x00000100}, 2 * 32 - 3),
    "one_word": ({0: 0xDEADBEEF}, 32),
    "hole_then_word": ({5: 1}, 6 * 32),
}
ROW = {name: r for r, name in enumerate(CRAFTED)}
WANT_COUNTS = {"empty": 0, "full": MW, "last_used_word_zero": 4,
               "bit_past_nbits": 10, "one_word": 1, "hole_then_word": 6}


def crafted_block(names=tuple(CRAFTED)):
    words = np.zeros((len(names), MW), np.uint32)
    for r, name in enumerate(names):
        for c, w in CRAFTED[name][0].items():
            words[r, c] = w
    n = len(names)
    return SealedBlock(
        block_start=T0, window=8, series_indices=np.arange(n, dtype=np.int32),
        words=words, nbits=np.array([CRAFTED[k][1] for k in names], np.int32),
        npoints=np.arange(n, dtype=np.int32))


def encoded_block(rng, n=9, w=7):
    ts = (T0 + np.arange(w, dtype=np.int64)[None, :] * 10 * xtime.SECOND
          + np.zeros((n, 1), np.int64))
    vals = rng.integers(0, 50, size=(n, w)).astype(np.float64)
    npoints = np.full(n, w, np.int32)
    npoints[2] = 0  # a series that missed the block: an empty row
    return encode_block(T0, np.arange(n, dtype=np.int32), ts, vals, npoints)


def encoded_by(kind, rng, monkeypatch):
    """A tile as each producer of sealed words leaves it."""
    if kind == "pallas_pack":
        monkeypatch.setenv("M3_TPU_PALLAS", "1")  # interpret mode off the chip
        return encoded_block(rng)
    assert kind == "merged"
    from m3_tpu.storage.block import merge_sealed_blocks

    n, w = 6, 5
    halves = []
    for h in range(2):
        start = T0 + h * BLOCK
        ts = (start + np.arange(w, dtype=np.int64)[None, :] * 10 * xtime.SECOND
              + np.zeros((n, 1), np.int64))
        vals = rng.integers(0, 50, size=(n, w)).astype(np.float64)
        halves.append(encode_block(start, np.arange(n, dtype=np.int32), ts,
                                   vals, np.full(n, w, np.int32)))
    return merge_sealed_blocks(*halves)


def registry_for(n):
    reg = SeriesRegistry()
    ids = [b"uw.%03d" % i for i in range(n)]
    for sid in ids:
        reg.get_or_create(sid)
    return reg, ids


def write_by(route, root, blk):
    """One fileset through each of the four ways into `_write`."""
    reg, ids = registry_for(blk.num_series)
    w = pfs.FilesetWriter(root)
    pm = pfs.PersistManager(root)
    if route == "write":
        return w.write(NS, 1, blk, reg), ids
    if route == "write_rows":
        return w.write_rows(NS, 1, blk, ids), ids
    if route == "flush_volume":
        return pm.write_block(NS, 1, blk, reg), ids
    assert route == "snapshot_volume"
    return pm.write_snapshot(NS, 1, blk, reg, version=3,
                             wal_position=(2, 40)), ids


ROUTES = ("write", "write_rows", "flush_volume", "snapshot_volume")


def padded_adlers(tile):
    return np.array([zlib.adler32(r.tobytes()) for r in tile], np.int64)


def reseal(path):
    """Recompute digest.json and the checkpoint over the files as they lie."""
    digests = {name: pfs._adler(os.path.join(path, name)) for name in (
        pfs.INFO_FILE, pfs.DATA_FILE, pfs.INDEX_FILE, pfs.SUMMARIES_FILE,
        pfs.BLOOM_FILE)}
    with open(os.path.join(path, pfs.DIGEST_FILE), "w") as f:
        json.dump(digests, f)
    with open(os.path.join(path, pfs.CHECKPOINT_FILE), "w") as f:
        json.dump({"digest": pfs._adler(os.path.join(path, pfs.DIGEST_FILE))},
                  f)


def write_padded(root, blk):
    """A fileset as the writer before the layout key laid it out: the
    whole [S, MW] tile in data.bin, no key in info.json."""
    path, ids = write_by("write_rows", root, blk)
    with open(os.path.join(path, pfs.DATA_FILE), "wb") as f:
        f.write(np.ascontiguousarray(blk.words, np.uint32).tobytes())
    with open(os.path.join(path, pfs.INFO_FILE)) as f:
        info = json.load(f)
    del info[pfs.LAYOUT_KEY]
    with open(os.path.join(path, pfs.INFO_FILE), "w") as f:
        json.dump(info, f)
    reseal(path)
    return path, ids


def stored_counts(path):
    with open(os.path.join(path, pfs.INFO_FILE)) as f:
        s = json.load(f)["num_series"]
    raw = np.fromfile(os.path.join(path, pfs.DATA_FILE), np.uint32)
    return raw[len(raw) - s:], raw


def set_counts(path, counts):
    _, raw = stored_counts(path)
    raw[len(raw) - len(counts):] = counts
    raw.tofile(os.path.join(path, pfs.DATA_FILE))


# ------------------------------------------------------------- the count


@pytest.mark.parametrize("name", sorted(CRAFTED))
def test_count_is_one_past_the_last_nonzero_word(name):
    blk = crafted_block((name,))
    assert pfs.used_word_counts(blk.words).tolist() == [WANT_COUNTS[name]]


def test_counts_of_a_tile_with_no_rows_or_no_width():
    assert pfs.used_word_counts(np.zeros((0, MW), np.uint32)).shape == (0,)
    assert pfs.used_word_counts(np.zeros((3, 0), np.uint32)).tolist() == [0] * 3


def test_an_encoded_row_ends_where_its_bits_end(rng):
    """On what the codec writes the count is ceil(nbits / 32) or a word
    less (a last word of zero bits), never more; a row of no points
    still holds its header."""
    blk = encoded_block(rng)
    counts = pfs.used_word_counts(np.asarray(blk.words))
    by_bits = -(-np.asarray(blk.nbits) // 32)
    assert (counts <= by_bits).all() and (counts >= by_bits - 1).all()
    assert 0 < counts.max() < np.asarray(blk.words).shape[1]


# ------------------------------------------------------------ round trips


@pytest.mark.parametrize("route", ROUTES)
@pytest.mark.parametrize("tile", ("crafted", "encoded"))
def test_tile_round_trips_bit_for_bit(tmp_path, rng, route, tile):
    blk = crafted_block() if tile == "crafted" else encoded_block(rng)
    path, ids = write_by(route, str(tmp_path), blk)
    reader = pfs.FilesetReader(path, verify=True)
    reader.verify_rows()
    got, got_ids = reader.to_block()
    want = np.asarray(blk.words)
    assert got.words.dtype == np.uint32 and got.words.shape == want.shape
    np.testing.assert_array_equal(got.words, want)
    np.testing.assert_array_equal(got.nbits, blk.nbits)
    np.testing.assert_array_equal(got.npoints, blk.npoints)
    assert got_ids == ids
    assert got.checksum == blk.checksum
    if tile == "encoded":
        for a, b in zip(got.read_all(), blk.read_all()):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("kind", ("pallas_pack", "merged"))
def test_tiles_of_other_producers_round_trip(tmp_path, rng, monkeypatch, kind):
    blk = encoded_by(kind, rng, monkeypatch)
    path, _ = write_by("flush_volume", str(tmp_path), blk)
    reader = pfs.FilesetReader(path)
    reader.verify_rows()
    got, _ = reader.to_block()
    np.testing.assert_array_equal(got.words, np.asarray(blk.words))
    assert got.checksum == blk.checksum
    for a, b in zip(got.read_all(), blk.read_all()):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("route", ROUTES)
def test_data_file_holds_used_words_then_counts(tmp_path, route):
    blk = crafted_block()
    path, _ = write_by(route, str(tmp_path), blk)
    counts, raw = stored_counts(path)
    assert counts.tolist() == [WANT_COUNTS[k] for k in CRAFTED]
    s = blk.num_series
    assert os.path.getsize(os.path.join(path, pfs.DATA_FILE)) == \
        4 * (int(counts.sum()) + s)
    # row order, back to back, row 0's codewords first
    want = np.concatenate([blk.words[r, :c] for r, c in enumerate(counts)])
    np.testing.assert_array_equal(raw[:len(raw) - s], want)
    with open(os.path.join(path, pfs.INFO_FILE)) as f:
        info = json.load(f)
    assert info[pfs.LAYOUT_KEY] == pfs.LAYOUT_USED_WORDS
    assert info["max_words"] == MW and info["num_series"] == s


def test_a_fileset_of_no_rows_round_trips(tmp_path):
    blk = crafted_block(())
    path, _ = write_by("write_rows", str(tmp_path), blk)
    assert os.path.getsize(os.path.join(path, pfs.DATA_FILE)) == 0
    reader = pfs.FilesetReader(path)
    reader.verify_rows()
    got, ids = reader.to_block()
    assert got.words.shape == (0, MW) and ids == []


@pytest.mark.parametrize("shape", ((1, 1), (5, 3), (64, 40), (3, 475)))
def test_random_tiles_round_trip(tmp_path, rng, shape):
    s, mw = shape
    words = rng.integers(0, 2 ** 32, size=shape, dtype=np.uint64).astype(
        np.uint32)
    # zero tails of every length, and zeros inside a row
    cut = rng.integers(0, mw + 1, size=s)
    words[np.arange(mw)[None, :] >= cut[:, None]] = 0
    words[rng.random(shape) < 0.2] = 0
    blk = SealedBlock(
        block_start=T0, window=8, series_indices=np.arange(s, dtype=np.int32),
        words=words, nbits=(cut * 32).astype(np.int32),
        npoints=np.ones(s, np.int32))
    path, _ = write_by("write_rows", str(tmp_path), blk)
    got, _ = pfs.FilesetReader(path).to_block()
    np.testing.assert_array_equal(got.words, words)


# -------------------------------------------------------------- the seeker


@pytest.mark.parametrize("route", ("flush_volume", "snapshot_volume"))
def test_seeker_returns_the_padded_row(tmp_path, route):
    blk = crafted_block()
    path, ids = write_by(route, str(tmp_path), blk)
    sk = pfs.Seeker(path)
    for r, sid in enumerate(ids):
        row, nbits, npoints = sk.seek(sid)
        assert row.shape == (MW,) and row.dtype == np.uint32
        np.testing.assert_array_equal(row, blk.words[r])
        assert (nbits, npoints) == (int(blk.nbits[r]), int(blk.npoints[r]))
    assert sk.seek(b"uw.absent") is None


def test_seeker_does_not_expand_the_fileset(tmp_path):
    path, ids = write_by("write_rows", str(tmp_path), crafted_block())
    sk = pfs.Seeker(path)
    for sid in ids:
        sk.seek(sid)
    assert "_words" not in vars(sk._reader)


# ---------------------------------------------------------- the checksums


def test_checksums_stay_those_of_the_padded_tile(tmp_path):
    blk = crafted_block()
    path, _ = write_by("write_rows", str(tmp_path), blk)
    want = padded_adlers(blk.words)
    reader = pfs.FilesetReader(path)
    np.testing.assert_array_equal(reader.row_checksums(), want)
    got, _ = reader.to_block()
    np.testing.assert_array_equal(got.expected_row_sums, want)
    np.testing.assert_array_equal(got.row_checksums(), want)
    by_row = {e.row: e.checksum for e in reader.entries}
    assert [by_row[r] for r in range(blk.num_series)] == want.tolist()
    assert reader.info["block_checksum"] == zlib.adler32(blk.words.tobytes())
    assert got.checksum == zlib.adler32(blk.words.tobytes())


def test_only_data_and_info_differ_from_a_padded_fileset(tmp_path):
    """index.bin, summaries.bin and bloom.bin do not know the layout."""
    blk = crafted_block()
    new, _ = write_by("write_rows", str(tmp_path / "new"), blk)
    old, _ = write_padded(str(tmp_path / "old"), blk)
    for name in (pfs.INDEX_FILE, pfs.SUMMARIES_FILE, pfs.BLOOM_FILE):
        with open(os.path.join(new, name), "rb") as a, \
                open(os.path.join(old, name), "rb") as b:
            assert a.read() == b.read(), name
    assert os.path.getsize(os.path.join(old, pfs.DATA_FILE)) == \
        4 * blk.num_series * MW


# ------------------------------------------------- a padded fileset boots


@pytest.mark.parametrize("tile", ("crafted", "encoded"))
def test_padded_fileset_reads_as_before(tmp_path, rng, tile):
    blk = crafted_block() if tile == "crafted" else encoded_block(rng)
    path, ids = write_padded(str(tmp_path), blk)
    reader = pfs.FilesetReader(path, verify=True)
    assert pfs.LAYOUT_KEY not in reader.info
    reader.verify_rows()
    got, got_ids = reader.to_block()
    np.testing.assert_array_equal(got.words, np.asarray(blk.words))
    assert got_ids == ids
    np.testing.assert_array_equal(
        reader.row_checksums(), padded_adlers(np.asarray(blk.words)))
    sk = pfs.Seeker(path)
    for r, sid in enumerate(ids):
        np.testing.assert_array_equal(sk.seek(sid)[0],
                                      np.asarray(blk.words)[r])


def test_padded_fileset_boots_a_shard(tmp_path, rng):
    """The filesystem bootstrap source over a padded fileset."""
    from m3_tpu.storage.retriever import BlockRetriever

    blk = encoded_block(rng)
    path, ids = write_padded(str(tmp_path), blk)
    r = BlockRetriever(pfs.PersistManager(str(tmp_path)))
    want = blk.read_all()
    for row in (0, 5):
        ts, vals = r.retrieve(NS, 1, T0, ids[row])
        n = int(blk.npoints[row])
        np.testing.assert_array_equal(ts, want[0][row, :n])
        np.testing.assert_array_equal(vals, want[1][row, :n])


def test_an_unknown_layout_is_refused(tmp_path):
    path, _ = write_by("write_rows", str(tmp_path), crafted_block())
    with open(os.path.join(path, pfs.INFO_FILE)) as f:
        info = json.load(f)
    info[pfs.LAYOUT_KEY] = "words_of_a_later_tree"
    with open(os.path.join(path, pfs.INFO_FILE), "w") as f:
        json.dump(info, f)
    reseal(path)
    with pytest.raises(ValueError, match="unknown data layout"):
        pfs.FilesetReader(path)


# ------------------------------------------------------------ rotten counts


def _rot_too_large(counts):
    counts[0] = 2 ** 31


def _rot_over_width_sum_kept(counts):
    counts[ROW["full"]] += 1
    counts[ROW["one_word"]] -= 1


def _rot_sum_one_over(counts):
    counts[ROW["one_word"]] += 1


def _rot_sum_one_under(counts):
    counts[ROW["hole_then_word"]] -= 1


@pytest.mark.parametrize("rot", (_rot_too_large, _rot_over_width_sum_kept,
                                 _rot_sum_one_over, _rot_sum_one_under),
                         ids=lambda f: f.__name__[5:])
@pytest.mark.parametrize("opener", ("reader", "seeker"))
def test_rotten_count_is_a_corruption_error(tmp_path, rot, opener):
    path, _ = write_by("write_rows", str(tmp_path), crafted_block())
    counts, _ = stored_counts(path)
    rot(counts)
    set_counts(path, counts)
    with pytest.raises(CorruptionError) as ei:
        if opener == "reader":
            pfs.FilesetReader(path, verify=False)
        else:
            pfs.Seeker(path)
    assert ei.value.path == path


def test_counts_that_shift_rows_inside_their_sum_fail_the_row_adlers(tmp_path):
    """Two counts off by one each way pass the sum: the rows they move
    are named by the row checksums, as any rotten word is."""
    path, ids = write_by("write_rows", str(tmp_path), crafted_block())
    counts, _ = stored_counts(path)
    # the row before takes the next row's one word
    a, b = ROW["bit_past_nbits"], ROW["one_word"]
    assert b == a + 1
    counts[a] += 1
    counts[b] -= 1
    set_counts(path, counts)
    reader = pfs.FilesetReader(path, verify=False)
    with pytest.raises(CorruptionError) as ei:
        reader.verify_rows()
    assert set(ei.value.rows) == {a, b} and ei.value.path == path
    blk, _ = reader.to_block()
    with pytest.raises(CorruptionError):
        blk.read_all()
    with pytest.raises(CorruptionError):
        pfs.Seeker(path).seek(ids[a])


@pytest.mark.parametrize("cut", (1, 2, 3))
def test_a_data_file_torn_inside_a_word_is_a_corruption_error(tmp_path, cut):
    path, _ = write_by("write_rows", str(tmp_path), crafted_block())
    dpath = os.path.join(path, pfs.DATA_FILE)
    with open(dpath, "rb+") as f:
        f.truncate(os.path.getsize(dpath) - cut)
    with pytest.raises(CorruptionError) as ei:
        pfs.FilesetReader(path, verify=False)
    assert ei.value.path == path


def test_a_data_file_shorter_than_its_counts_is_a_corruption_error(tmp_path):
    path, _ = write_by("write_rows", str(tmp_path), crafted_block())
    with open(os.path.join(path, pfs.DATA_FILE), "rb+") as f:
        f.truncate(4 * (len(CRAFTED) - 1))
    with pytest.raises(CorruptionError) as ei:
        pfs.FilesetReader(path, verify=False)
    assert ei.value.path == path


def test_a_flipped_word_still_names_its_row(tmp_path):
    path, ids = write_by("write_rows", str(tmp_path), crafted_block())
    row = ROW["bit_past_nbits"]
    counts, raw = stored_counts(path)
    raw[int(counts[:row].sum()) + 1] ^= 0x10
    raw.tofile(os.path.join(path, pfs.DATA_FILE))
    with pytest.raises(CorruptionError) as ei:
        pfs.FilesetReader(path, verify=False).verify_rows()
    assert ei.value.rows == [row] and ei.value.ids == [ids[row]]


# -------------------------------------------------------------- the counters


@pytest.mark.parametrize("route", ROUTES)
def test_counters_move_once_a_fileset(tmp_path, route):
    blk = crafted_block()
    data0, tile0 = pfs._DATA_WORDS.value(), pfs._TILE_WORDS.value()
    write_by(route, str(tmp_path), blk)
    s = blk.num_series
    assert pfs._DATA_WORDS.value() - data0 == sum(WANT_COUNTS.values()) + s
    assert pfs._TILE_WORDS.value() - tile0 == s * MW


def test_counters_are_the_operators(tmp_path):
    from m3_tpu.utils.instrument import ROOT

    write_by("write_rows", str(tmp_path), crafted_block())
    snap = ROOT.snapshot()
    assert snap["persist.fs.data_words"] > 0
    assert snap["persist.fs.tile_words"] >= snap["persist.fs.data_words"]
