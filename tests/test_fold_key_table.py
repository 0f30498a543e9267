"""The fold's resident key table and the gather in front of the fold
(ISSUE 38).

A slice of the key-aggregation fold ships the rows of its key lanes in
``bls_backend._FoldKeyTable``, not the lanes: ``bls_backend._blinded_lanes``
gathers them on the device and puts the blinding half beside them.  The
oracle is the per-key host layout it replaced, copied below.  The lane cap
is monkeypatched to 64 lanes (segments of 8 keys + 8 blinding lanes, four
to a slice), as ``test_electra_fold`` does, so one small program serves
every case.
"""

import random
import sys
import threading

import numpy as np
import pytest

from benchmarks import counters
from lighthouse_tpu.common import tracing
from lighthouse_tpu.common.metrics import REGISTRY
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.ops import bigint as bi
from lighthouse_tpu.ops import bls_backend as bb
from lighthouse_tpu.ops import ec

CAP = 64          # lanes a slice
SEG_KEYS = 8      # key lanes a segment at that cap
SLICE = 4         # segments a slice


@pytest.fixture(scope="module")
def pks():
    return [bls.SecretKey.from_bytes(int(3800 + i).to_bytes(32, "big"))
            .public_key() for i in range(24)]


@pytest.fixture
def small_cap(monkeypatch):
    monkeypatch.setattr(bb, "_AGG_MAX_LANES", CAP)


@pytest.fixture
def lanes_seen(monkeypatch):
    """The (X, Y, Z) lanes handed to ``blinded_fold_device``, on the host."""
    seen = []
    real = bb._msm.blinded_fold_device

    def spy(X, Y, Z, ux, uy, n_segments):
        seen.append(tuple(np.asarray(a) for a in (X, Y, Z)))
        return real(X, Y, Z, ux, uy, n_segments)

    monkeypatch.setattr(bb._msm, "blinded_fold_device", spy)
    return seen


def _fresh(pks):
    """New ``PublicKey`` objects of the same keys: no cached row."""
    return [bls.PublicKey(pk.to_bytes(), pk.point) for pk in pks]


def _sets(members):
    sig = bls.Signature(b"\xc0" + b"\x00" * 95)   # never read by the fold
    return [bls.SignatureSet(sig, list(m), b"\x38" * 32) for m in members]


def _per_key_layout(sets, max_k, n_pad):
    """The host layout before ISSUE 38, the oracle: the blinding template
    copied a slice and three row writes a key from ``mont_limbs()``."""
    half = max_k * n_pad
    template = np.zeros((3, 2 * half, bi.L), np.uint32)
    for j, (bx, by) in enumerate(bb._BLIND_POINTS[:max_k]):
        rows = slice((max_k + j) * n_pad, (max_k + j + 1) * n_pad)
        template[0][rows] = bx
        template[1][rows] = by
        template[2][rows] = bi.ONE_M
    segments = [(i, lo) for i, s in enumerate(sets) if len(s.pubkeys) > 1
                for lo in range(0, len(s.pubkeys), max_k)]
    slices = []
    for first in range(0, len(segments), n_pad):
        X, Y, Z = template.copy()
        for i, (set_idx, lo) in enumerate(segments[first:first + n_pad]):
            for j, pk in enumerate(sets[set_idx].pubkeys[lo:lo + max_k]):
                xl, yl = pk.mont_limbs()
                lane = j * n_pad + i   # s-major layout for g1_segment_sum
                X[lane] = xl
                Y[lane] = yl
                Z[lane] = bi.ONE_M
        slices.append((X, Y, Z))
    return slices


def _layout_cases(pks):
    rng = random.Random(38)
    return {
        # one slice of four segments of 5, 8, 3 and 7 keys, two single keys
        "ragged": [pks[:1], pks[:5], pks[3:11], pks[2:5], pks[9:10],
                   pks[10:17]],
        # 21 keys at 8 a segment: three sub-segments, the last of 5 keys
        "wider_than_a_segment": [pks[:21], pks[4:11]],
        # a sync set drawn with replacement and one key twenty times
        "duplicate_keys": [[rng.choice(pks) for _ in range(8)],
                           [pks[3]] * 20, pks[:6]],
        # six segments: the second slice holds two of its four
        "partly_filled_last_slice": [pks[i:i + 5] for i in range(0, 18, 3)],
    }


@pytest.mark.parametrize("case", ["ragged", "wider_than_a_segment",
                                  "duplicate_keys",
                                  "partly_filled_last_slice"])
def test_gathered_lanes_equal_the_per_key_layout(pks, small_cap, lanes_seen,
                                                 case):
    sets = _sets(_layout_cases(_fresh(pks))[case])
    xa, ya, inf = bb.aggregate_pubkeys_device(sets)
    max_k, n_pad = bb._fold_shape([len(s.pubkeys) for s in sets])
    want = _per_key_layout(sets, max_k, n_pad)
    assert len(lanes_seen) == len(want) >= 1
    for got, oracle in zip(lanes_seen, want):
        for g, w in zip(got, oracle):
            assert g.dtype == w.dtype and g.shape == w.shape
            assert g.tobytes() == w.tobytes(), case
    for i, s in enumerate(sets):
        assert not inf[i]
        got = (int(bi.from_mont(xa[i])), int(bi.from_mont(ya[i])))
        assert got == s.aggregate_pubkey(), (case, i)


def test_two_public_key_objects_of_one_key_share_a_row(pks):
    a, b = _fresh(pks[:1]) + _fresh(pks[:1])
    assert a is not b and a._fold_row == b._fold_row == -1
    bb._FOLD_KEYS.add([a])
    bb._FOLD_KEYS.add([b])
    assert a._fold_row == b._fold_row >= 0
    assert bb._FOLD_KEYS._rows[a.to_bytes()] == a._fold_row


def _rows_grown(before, after):
    return {source: counters.delta(
        before, after, "bls_fold_key_rows_total",
        lambda labels, s=source: labels["source"] == s)
        for source in ("resident", "uploaded")}


def test_a_key_first_seen_is_uploaded_then_resident(small_cap):
    """A key the table has not seen, in the middle of a batch of resident
    keys, is converted and uploaded, its lanes counted ``uploaded``; the
    next request finds it resident.  The counter's two sources sum to the
    key lanes ``bls_fold_lanes_total`` counts."""
    old = [bls.SecretKey.from_bytes(int(3900 + i).to_bytes(32, "big"))
           .public_key() for i in range(12)]
    new = bls.SecretKey.from_bytes(int(3990).to_bytes(32, "big")).public_key()
    bb._FOLD_KEYS.add(old)
    sets = _sets([old[:7], old[3:9] + [new] + old[9:], [new, new], old[:1]])

    def request():
        before = counters.samples()
        with tracing.span("bls.aggregate"):
            xa, ya, inf = bb.aggregate_pubkeys_device(sets)
        after = counters.samples()
        for i, s in enumerate(sets):
            got = (int(bi.from_mont(xa[i])), int(bi.from_mont(ya[i])))
            assert not inf[i] and got == s.aggregate_pubkey(), i
        keys = counters.delta(before, after, "bls_fold_lanes_total",
                              lambda labels: labels["kind"] == "key")
        return _rows_grown(before, after), keys

    rows, keys = request()
    assert rows == {"resident": 16.0, "uploaded": 3.0}
    assert sum(rows.values()) == keys == 19.0
    assert new._fold_row >= 0
    rows, keys = request()
    assert rows == {"resident": 19.0, "uploaded": 0.0} and keys == 19.0
    assert "# TYPE bls_fold_key_rows_total counter" in REGISTRY.render()


def test_growing_past_a_capacity_keeps_every_row(pks, small_cap, monkeypatch):
    """A table of capacity 4 grows to 8, 16 and 32 across requests; every
    row keeps its place and its limbs, on the host and on the device, and
    the folds stay exact."""
    table = bb._FoldKeyTable(floor=4)
    monkeypatch.setattr(bb, "_FOLD_KEYS", table)
    keys = _fresh(pks)
    capacities = []
    for hi in (3, 6, 13, 24):
        sets = _sets([keys[:hi], keys[max(hi - 5, 0):hi]])
        xa, ya, inf = bb.aggregate_pubkeys_device(sets)
        for i, s in enumerate(sets):
            got = (int(bi.from_mont(xa[i])), int(bi.from_mont(ya[i])))
            assert not inf[i] and got == s.aggregate_pubkey()
        capacities.append(table.device.shape[0])
        rows = np.asarray(table.device)
        assert rows.shape == (len(table._host), bb._KEY_ROW_WORDS)
        assert not rows[:, 2 * bi.L:].any()
        assert rows[:, :2 * bi.L].tobytes() == table._host.tobytes()
        for j, pk in enumerate(keys[:hi]):
            assert pk._fold_row == j          # rows never move
            xl, yl = pk.mont_limbs()
            assert rows[j, :2 * bi.L].tolist() == xl.tolist() + yl.tolist()
        assert not rows[hi:].any()
    assert capacities == [4, 8, 16, 32]


def test_two_threads_assign_each_key_exactly_one_row(pks):
    """Eight threads add overlapping lists of their own ``PublicKey``
    objects of 24 keys, under a shortened switch interval: each key gets
    one row, the rows are 0..23, and every object caches its key's."""
    table = bb._FoldKeyTable(floor=4)
    rng = random.Random(3838)
    lists = [[bls.PublicKey(pk.to_bytes(), pk.point)
              for pk in rng.sample(pks, 16)] for _ in range(8)]
    barrier = threading.Barrier(len(lists))
    errors = []

    def worker(objs):
        try:
            barrier.wait(timeout=30)
            for lo in range(0, len(objs), 3):
                table.add(objs[lo:lo + 3])
        except Exception as e:          # surfaced by the assertion below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(objs,))
                   for objs in lists]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    seen = {pk.to_bytes() for objs in lists for pk in objs}
    assert sorted(table._rows.values()) == list(range(len(seen)))
    assert set(table._rows) == seen
    rows = np.asarray(table.device)
    for objs in lists:
        for pk in objs:
            assert pk._fold_row == table._rows[pk.to_bytes()]
            xl, yl = ec.ints_to_mont_limbs([pk.point[0], pk.point[1]])
            assert rows[pk._fold_row, :2 * bi.L].tolist() == (
                xl.tolist() + yl.tolist())
