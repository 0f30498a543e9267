"""SHA-256 kernel and merkleization correctness vs hashlib."""

import hashlib

import numpy as np
import pytest

from lighthouse_tpu.ops import sha256 as s


def _ref_hash_pairs(pairs: np.ndarray) -> np.ndarray:
    data = pairs.astype(">u4").tobytes()
    return np.stack(
        [
            np.frombuffer(hashlib.sha256(data[64 * i: 64 * (i + 1)]).digest(), dtype=">u4")
            for i in range(pairs.shape[0])
        ]
    ).astype(np.uint32)


@pytest.mark.parametrize("n", [1, 2, 7, 64, 333])
def test_hash_pairs_device_matches_hashlib(n):
    rng = np.random.default_rng(n)
    pairs = rng.integers(0, 2**32, size=(n, 16), dtype=np.uint32)
    got = np.asarray(s.hash_pairs_device(pairs))
    np.testing.assert_array_equal(got, _ref_hash_pairs(pairs))


def test_hash_pairs_np_matches_device():
    rng = np.random.default_rng(0)
    pairs = rng.integers(0, 2**32, size=(17, 16), dtype=np.uint32)
    np.testing.assert_array_equal(s.hash_pairs_np(pairs), np.asarray(s.hash_pairs_device(pairs)))


def _naive_merkleize(chunks: list[bytes], limit=None) -> bytes:
    n = len(chunks)
    size = max(limit if limit is not None else n, 1)
    depth = max(size - 1, 0).bit_length()
    padded = 1 << depth
    nodes = chunks + [b"\x00" * 32] * (padded - n)
    while len(nodes) > 1:
        nodes = [hashlib.sha256(nodes[i] + nodes[i + 1]).digest() for i in range(0, len(nodes), 2)]
    return nodes[0]


@pytest.mark.parametrize("n,limit", [(0, None), (1, None), (2, None), (3, None), (5, 8),
                                     (1, 16), (100, 128), (0, 4), (8, 8), (33, None)])
def test_merkleize_matches_naive(n, limit):
    rng = np.random.default_rng(n + (limit or 0))
    chunks = [rng.bytes(32) for _ in range(n)]
    got = s.merkleize(b"".join(chunks), limit)
    assert got == _naive_merkleize(chunks, limit)


def test_merkleize_device_path_matches_naive():
    rng = np.random.default_rng(7)
    chunks = [rng.bytes(32) for _ in range(1000)]
    got = s.merkleize(b"".join(chunks), device=True)
    assert got == _naive_merkleize(chunks)


def test_zero_hashes():
    assert s.ZERO_HASHES[1] == hashlib.sha256(b"\x00" * 64).digest()
    assert s.ZERO_HASHES[2] == hashlib.sha256(s.ZERO_HASHES[1] * 2).digest()


def test_mix_in_length():
    root = b"\x11" * 32
    assert s.mix_in_length(root, 5) == hashlib.sha256(root + (5).to_bytes(32, "little")).digest()


class TestDeviceThresholdCalibration:
    """Startup micro-calibration of the device-vs-host merkle routing."""

    def test_env_override_pins_threshold(self, monkeypatch):
        saved = (s._DEVICE_MIN_PAIRS, s._DEVICE_FOLD_MIN_LEAVES,
                 s._CALIBRATED)
        try:
            monkeypatch.setenv("LHTPU_SHA_DEVICE_MIN", "4096")
            out = s.calibrate_device_thresholds(force=True)
            assert out["source"] == "env"
            assert s._DEVICE_MIN_PAIRS == 4096
            assert s._DEVICE_FOLD_MIN_LEAVES == 8192
            from lighthouse_tpu.common.metrics import REGISTRY

            assert REGISTRY.gauge(
                "sha256_device_threshold_pairs").value == 4096
        finally:
            (s._DEVICE_MIN_PAIRS, s._DEVICE_FOLD_MIN_LEAVES,
             s._CALIBRATED) = saved

    def test_measured_calibration_sets_pow2_threshold(self, monkeypatch):
        saved = (s._DEVICE_MIN_PAIRS, s._DEVICE_FOLD_MIN_LEAVES,
                 s._CALIBRATED)
        try:
            monkeypatch.delenv("LHTPU_SHA_DEVICE_MIN", raising=False)
            out = s.calibrate_device_thresholds(sample_pairs=256,
                                                force=True)
            assert out["source"] == "measured"
            t = out["threshold_pairs"]
            assert t & (t - 1) == 0                 # power of two
            assert s._DEVICE_MIN_PAIRS == t
            assert s._DEVICE_FOLD_MIN_LEAVES <= 2 * t
            # one-shot: a second call without force is a cached no-op
            again = s.calibrate_device_thresholds()
            assert again.get("cached")
        finally:
            (s._DEVICE_MIN_PAIRS, s._DEVICE_FOLD_MIN_LEAVES,
             s._CALIBRATED) = saved

    def test_routing_decision_uses_calibrated_threshold(self):
        saved = (s._DEVICE_MIN_PAIRS, s._CALIBRATED)
        try:
            s._DEVICE_MIN_PAIRS = 1 << 30            # force host path
            rng = np.random.default_rng(3)
            pairs = rng.integers(0, 2**32, size=(64, 16), dtype=np.uint32)
            np.testing.assert_array_equal(
                s.batch_hash_pairs(pairs), _ref_hash_pairs(pairs))
        finally:
            s._DEVICE_MIN_PAIRS, s._CALIBRATED = saved


# -- validator element roots: one device program from the registry's columns ---

def _registry(n, seed):
    """Random columns: far-future epochs mixed in, slashed both ways, the
    first pubkey all zeros and the last all ones."""
    from lighthouse_tpu.types.registry import Validators
    from lighthouse_tpu.types.spec import FAR_FUTURE_EPOCH

    rng = np.random.default_rng(seed)
    v = Validators(n)
    v.pubkeys[...] = rng.integers(0, 256, (n, 48), dtype=np.uint8)
    v.pubkeys[0] = 0
    v.pubkeys[-1] = 0xFF
    v.withdrawal_credentials[...] = rng.integers(0, 256, (n, 32), np.uint8)
    v.effective_balance[...] = rng.integers(0, 2**63, n).astype(np.uint64)
    v.slashed[...] = rng.random(n) < 0.5
    v.slashed[0], v.slashed[-1] = n % 2 == 0, n % 2 == 1
    for c in ("activation_eligibility_epoch", "activation_epoch",
              "exit_epoch", "withdrawable_epoch"):
        getattr(v, c)[...] = np.where(
            rng.random(n) < 0.3, np.uint64(FAR_FUTURE_EPOCH),
            rng.integers(0, 2**40, n).astype(np.uint64))
    return v


def _record_chunks(v, i):
    def u64(x):
        return int(x).to_bytes(32, "little")

    pk = bytes(v.pubkeys[i])
    return [hashlib.sha256(pk.ljust(64, b"\x00")).digest(),
            bytes(v.withdrawal_credentials[i]), u64(v.effective_balance[i]),
            u64(v.slashed[i]), u64(v.activation_eligibility_epoch[i]),
            u64(v.activation_epoch[i]), u64(v.exit_epoch[i]),
            u64(v.withdrawable_epoch[i])]


def _counter(name, **labels):
    from lighthouse_tpu.common.metrics import REGISTRY

    want = ",".join(f'{k}="{v}"' for k, v in labels.items())
    for line in REGISTRY.render().splitlines():
        if line.startswith(f"{name}{{{want}}}"):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


@pytest.mark.parametrize("n", [1, 2047, 2048, 2049, 5000])
def test_validator_roots_fused_matches_host_path_and_hashlib(monkeypatch, n):
    """Both sides of the routing threshold, a bucket's last row (2048 of
    2048) and first (2049 of 4096): the fused program, whatever
    ``batch_roots`` routes to, the host's chunk words through
    ``_batch_merkleize_subtrees`` and hashlib per record all agree."""
    from lighthouse_tpu.ssz.core import _batch_merkleize_subtrees
    from lighthouse_tpu.types.registry import ValidatorRegistryType

    assert s._DEVICE_MIN_PAIRS == 2048
    v = _registry(n, seed=n)
    fused = s.validator_roots(v.columns())
    assert fused.shape == (n, 8) and fused.dtype == np.uint32
    want = b"".join(_naive_merkleize(_record_chunks(v, i)) for i in range(n))
    assert s.words_to_bytes(fused) == want
    leaves = np.stack([s.chunks_to_words(b"".join(_record_chunks(v, i)))
                       for i in range(n)])
    with monkeypatch.context() as host_only:
        host_only.setattr(s, "_DEVICE_MIN_PAIRS", 1 << 30)
        np.testing.assert_array_equal(
            fused, _batch_merkleize_subtrees(leaves))
    before = {p: _counter("validator_roots_total", path=p)
              for p in ("fused", "host")}
    routed = ValidatorRegistryType(2**40).batch_roots(v)
    np.testing.assert_array_equal(routed, fused)
    path = "fused" if n >= 2048 else "host"
    other = "host" if path == "fused" else "fused"
    assert _counter("validator_roots_total", path=path) - before[path] == n
    assert _counter("validator_roots_total", path=other) == before[other]


def test_validator_roots_fused_counts_chunks_lanes_and_rows():
    """16 chunks and 8 lanes a record, as the four per-level calls count
    them; the padding is the bucket's."""
    from lighthouse_tpu.types.registry import ValidatorRegistryType

    n, bucket = 2049, 4096
    v = _registry(n, seed=3)
    names = [("sha256_merkle_chunks_total", {"path": "levels_device"}),
             ("sha256_merkle_chunks_total", {"path": "levels_host"}),
             ("sha256_device_lanes_total", {"kind": "live"}),
             ("sha256_device_lanes_total", {"kind": "padding"}),
             ("validator_roots_total", {"path": "fused"}),
             ("validator_roots_total", {"path": "host"})]
    before = [_counter(name, **labels) for name, labels in names]
    ValidatorRegistryType(2**40).batch_roots(v)
    moved = [_counter(name, **labels) - b
             for (name, labels), b in zip(names, before)]
    assert moved == [16 * n, 0, 8 * n, 8 * (bucket - n), n, 0]


def test_validators_cache_root_after_mass_balance_change():
    """Every second effective balance moves (the epoch's own write): the
    cache re-roots those rows through the fused program and lands on a cold
    ``hash_tree_root``."""
    from lighthouse_tpu.ssz.tree_cache import ValidatorsCache
    from lighthouse_tpu.types.registry import ValidatorRegistryType

    typ = ValidatorRegistryType(2**40)
    v = _registry(6000, seed=11)
    cache = ValidatorsCache(typ, v)
    assert cache.root(typ, v) == typ.hash_tree_root(v)
    before = _counter("validator_roots_total", path="fused")
    v.effective_balance[::2] += np.uint64(10**9)
    v.exit_epoch[5] = np.uint64(77)
    assert cache.root(typ, v) == typ.hash_tree_root(v)
    # the cache's 3,001 dirty rows and the cold root's 6,000
    assert _counter("validator_roots_total", path="fused") - before == 9001
