"""BLS signature API + pluggable backend registry.

This is the rebuild of the reference's generic BLS facade
(/root/reference/crypto/bls/src/lib.rs:86-141): one stable API
(`verify_signature_sets`, `SignatureSet`, key/signature types) over
swappable backends:

- "reference": the pure-Python pairing in this package (correctness oracle)
- "fake":      structure checks only, signatures always verify (the
               reference's fake_crypto backend, used by spec tests)
- "tpu":       batched JAX/Pallas backend (lighthouse_tpu.ops.bls), the
               device data plane

Batch semantics mirror blst's verify_multiple_aggregate_signatures
(/root/reference/crypto/bls/src/impls/blst.rs:37-119): per-set nonzero
64-bit random scalars r_i, one combined multi-pairing check

    e(-g1, Σ r_i·sig_i) · ∏ e(r_i·agg_pk_i, H(m_i)) == 1
"""

from __future__ import annotations

import random
import secrets
import threading
import time
from dataclasses import dataclass
from typing import Callable, Sequence

from lighthouse_tpu.crypto.bls import curve as cv
from lighthouse_tpu.crypto.bls.fields import R
from lighthouse_tpu.crypto.bls.hash_to_curve import DST_G2, hash_to_g2

RAND_BITS = 64


class BlsError(ValueError):
    pass


from lighthouse_tpu.common.utils import LruCache  # noqa: E402

# bounded so a hostile stream of unique keys cannot exhaust memory;
# ~1M validators fit (mainnet registry scale)
_PK_INTERN = LruCache(capacity=1 << 20)

# hash-to-curve memo: a slot's firehose re-verifies the same <=64
# distinct attestation messages every admission sweep, and H(m) is a
# pure ~8 ms map on the host — amortize it across sweeps.  Bounded so a
# hostile stream of unique messages stays O(1) memory (default-DST
# messages only; `sign` keeps its explicit-dst path uncached).
_H2G_MEMO = LruCache(capacity=512)

# wire-signature interning (Signature.interned): bounded so a hostile
# stream of unique signatures stays O(1) memory — a slot's honest
# firehose carries far fewer distinct signatures than this
_SIG_INTERN = LruCache(capacity=1 << 16)


def _hash_to_g2_memo(message: bytes):
    pt = _H2G_MEMO.get(message)
    record_cache("hash_g2", hit=pt is not None)
    if pt is None:
        pt = hash_to_g2(message)
        _H2G_MEMO.put(message, pt)
    return pt


class PublicKey:
    """Compressed G1 public key with lazy decompression + caching."""

    __slots__ = ("_bytes", "_point", "_limbs", "_fold_row")

    def __init__(self, data: bytes, point=None):
        if len(data) != 48:
            raise BlsError("public key must be 48 bytes")
        self._bytes = bytes(data)
        self._point = point
        self._limbs = None
        # the key's row in the fold's resident key table
        # (ops/bls_backend._FoldKeyTable), -1 until the table first sees it
        self._fold_row = -1

    @property
    def point(self):
        if self._point is None:
            pt = cv.g1_from_bytes(self._bytes)
            if pt is cv.INF:
                raise BlsError("infinity public key rejected (eth2 KeyValidate)")
            self._point = pt
        return self._point

    def mont_limbs(self):
        """(x, y) Montgomery limb rows, cached — validator pubkeys recur
        across every slot, so the int->limb conversion amortizes to zero
        on the batch-aggregation device path."""
        if self._limbs is None:
            from lighthouse_tpu.ops import ec as _ec

            x, y = self.point
            self._limbs = (_ec.ints_to_mont_limbs([x])[0],
                           _ec.ints_to_mont_limbs([y])[0])
        return self._limbs

    def to_bytes(self) -> bytes:
        return self._bytes

    def __eq__(self, o):
        return isinstance(o, PublicKey) and self._bytes == o._bytes

    def __hash__(self):
        return hash(self._bytes)

    def __repr__(self):
        return f"PublicKey({self._bytes.hex()[:16]}…)"

    @staticmethod
    def interned(data: bytes) -> "PublicKey":
        """Process-wide interning: one PublicKey object per key, so the
        decompression/subgroup/limb caches riding on it are paid once
        per VALIDATOR (the reference's validator_pubkey_cache effect),
        no matter which state or batch the key appears in."""
        pk = _PK_INTERN.get(data)
        if pk is None:
            record_cache("pk_intern", hit=False)
            pk = PublicKey(data)
            _PK_INTERN.put(bytes(data), pk)
        else:
            record_cache("pk_intern", hit=True)
        return pk

    @staticmethod
    def aggregate(pubkeys: Sequence["PublicKey"]) -> "PublicKey":
        if not pubkeys:
            raise BlsError("cannot aggregate zero pubkeys")
        pt = cv.INF
        for pk in pubkeys:
            pt = cv.g1_add(pt, pk.point)
        return PublicKey(cv.g1_to_bytes(pt), pt)


class Signature:
    """Compressed G2 signature with lazy decompression.

    Subgroup checking is split from decompression so batch verifiers can
    run the ψ membership test for MANY fresh signatures in one device
    program (ops/ec.g2_subgroup_check_batch) instead of a per-signature
    host scalar mul; `point_unchecked` + `mark_subgroup_checked` is that
    seam.  The `point` property remains the safe single-signature path."""

    __slots__ = ("_bytes", "_point", "_subgroup_ok")

    def __init__(self, data: bytes, point=None):
        if len(data) != 96:
            raise BlsError("signature must be 96 bytes")
        self._bytes = bytes(data)
        self._point = point
        self._subgroup_ok = point is not None

    @property
    def point(self):
        if self._point is None:
            self._point = cv.g2_from_bytes(self._bytes)
            self._subgroup_ok = True
        elif not self._subgroup_ok:
            if not cv.g2_in_subgroup_fast(self._point):
                raise BlsError("signature not in G2 subgroup")
            self._subgroup_ok = True
        return self._point

    def point_unchecked(self):
        """Decompressed point WITHOUT the subgroup check (on-curve only).
        Callers must complete the membership test (device batch) before
        treating the signature as valid."""
        if self._point is None:
            self._point = cv.g2_from_bytes(self._bytes, subgroup_check=False)
        return self._point

    def subgroup_checked(self) -> bool:
        return self._subgroup_ok

    def mark_subgroup_checked(self):
        self._subgroup_ok = True

    def to_bytes(self) -> bytes:
        return self._bytes

    def is_infinity(self) -> bool:
        return self._bytes[0] & 0x40 != 0

    def __eq__(self, o):
        return isinstance(o, Signature) and self._bytes == o._bytes

    def __repr__(self):
        return f"Signature({self._bytes.hex()[:16]}…)"

    @staticmethod
    def interned(data: bytes) -> "Signature":
        """Process-wide interning for byte-identical wire signatures:
        the decompressed point (and subgroup verdict — a property of
        the bytes) is paid once per distinct signature, no matter how
        many admission sweeps or duplicate gossip copies carry it.  The
        wire ingest lane's counterpart to the scalar path's long-lived
        Attestation objects caching their own `_point`."""
        sig = _SIG_INTERN.get(data)
        if sig is None:
            record_cache("sig_intern", hit=False)
            sig = Signature(data)
            _SIG_INTERN.put(bytes(data), sig)
        else:
            record_cache("sig_intern", hit=True)
        return sig

    @staticmethod
    def decompress_batch(sigs: Sequence["Signature"]) -> bool:
        """Fill `_point` for every not-yet-decompressed signature in ONE
        native batch call (ops/native_bls.g2_decompress_batch) — one
        ctypes crossing instead of one per signature, and the C++ layer
        amortizes its field-constant setup.  Subgroup checks are NOT
        performed (the batch verifier's device ψ test covers them).
        Returns False if any signature fails decompression (not on
        curve / malformed); a valid INFINITY encoding decompresses to
        cv.INF and returns True — callers that must reject infinity
        signatures (all verifiers) check the cached point, as
        verify_sets_pipeline does.  Every decompressable signature
        keeps its point cached even when another in the batch fails."""
        pending = [s for s in sigs if s._point is None]
        if not pending:
            return True
        try:
            from lighthouse_tpu.ops import native_bls

            native = native_bls if native_bls.available() else None
        except Exception as e:
            from lighthouse_tpu.common.metrics import record_swallowed

            record_swallowed("bls.decompress_batch.native", e)
            native = None
        if native is None:
            ok = True
            for s in pending:
                try:
                    s.point_unchecked()
                except (BlsError, ValueError):
                    ok = False
            return ok
        res = native.g2_decompress_batch([s._bytes for s in pending])
        ok = True
        for s, r in zip(pending, res):
            if r is None:
                ok = False      # keep caching the rest: one malformed
                continue        # signature must not cost the batch its
            if r == native.G2_INF:   # amortized decompressions
                s._point = cv.INF
            else:
                (xa, xb), (ya, yb) = r
                s._point = (cv.Fq2(xa, xb), cv.Fq2(ya, yb))
        return ok

    @staticmethod
    def subgroup_check_batch(sigs: Sequence["Signature"]) -> bool:
        """Complete the G2 membership test for every decompressed,
        not-yet-checked signature in ONE native crossing
        (ops/native_bls.g2_in_subgroup_batch, ~70 µs/point vs ~1.6 ms
        for the per-signature host ψ check).  Passing signatures are
        marked checked (a property of the bytes — interned signatures
        pay this once ever); failing or infinity signatures stay
        UNMARKED so per-signature paths re-check and attribute.
        Returns True when every pending signature passed.  Falls back
        to the host ψ loop when the native layer is unavailable."""
        pending = []
        pts = []
        all_finite = True
        for s in sigs:
            if s._subgroup_ok:
                continue
            try:
                pt = s.point_unchecked()
            except (BlsError, ValueError):
                all_finite = False   # undecompressable: can't verify
                continue
            if pt is cv.INF:
                all_finite = False   # verifiers reject infinity anyway
                continue
            pending.append(s)
            pts.append(pt)
        if not pending:
            return all_finite
        native = None
        try:
            from lighthouse_tpu.ops import native_bls

            if native_bls.available():
                native = native_bls
        except Exception as e:
            from lighthouse_tpu.common.metrics import record_swallowed

            record_swallowed("bls.subgroup_batch.native", e)
        verdicts = (native.g2_in_subgroup_batch(pts)
                    if native is not None else None)
        if verdicts is None:
            verdicts = [1 if cv.g2_in_subgroup_fast(pt) else 0
                        for pt in pts]
        ok = all_finite
        for s, v in zip(pending, verdicts):
            if v == 1:
                s.mark_subgroup_checked()
            else:
                ok = False
        return ok

    @staticmethod
    def aggregate(sigs: Sequence["Signature"]) -> "Signature":
        if not sigs:
            raise BlsError("cannot aggregate zero signatures")
        pt = cv.INF
        for s in sigs:
            pt = cv.g2_add(pt, s.point)
        return Signature(cv.g2_to_bytes(pt), pt)


class SecretKey:
    __slots__ = ("k",)

    def __init__(self, k: int):
        if not 0 < k < R:
            raise BlsError("secret key out of range")
        self.k = k

    @staticmethod
    def from_bytes(data: bytes) -> "SecretKey":
        return SecretKey(int.from_bytes(data, "big"))

    @staticmethod
    def generate() -> "SecretKey":
        return SecretKey(secrets.randbelow(R - 1) + 1)

    def to_bytes(self) -> bytes:
        return self.k.to_bytes(32, "big")

    def public_key(self) -> PublicKey:
        pt = cv.g1_mul(cv.g1_generator(), self.k)
        return PublicKey(cv.g1_to_bytes(pt), pt)

    def sign(self, message: bytes, dst: bytes = DST_G2) -> Signature:
        h = hash_to_g2(message, dst)
        pt = cv.g2_mul(h, self.k)
        return Signature(cv.g2_to_bytes(pt), pt)


@dataclass
class SignatureSet:
    """One verification unit: signature over `message` by the aggregate of
    `pubkeys` (reference GenericSignatureSet,
    crypto/bls/src/generic_signature_set.rs:61-121)."""

    signature: Signature
    pubkeys: list[PublicKey]
    message: bytes

    def aggregate_pubkey(self):
        pt = cv.INF
        for pk in self.pubkeys:
            pt = cv.g1_add(pt, pk.point)
        return pt


# --- single verification ----------------------------------------------------

def verify(pubkey: PublicKey, message: bytes, signature: Signature) -> bool:
    try:
        sig_pt = signature.point
        pk_pt = pubkey.point
    except (BlsError, ValueError):
        return False
    if sig_pt is cv.INF:
        return False
    h = _hash_to_g2_memo(message)
    res = cv.multi_pairing([
        (cv.g1_neg(cv.g1_generator()), sig_pt),
        (pk_pt, h),
    ])
    return res.is_one()


def fast_aggregate_verify(
    pubkeys: Sequence[PublicKey], message: bytes, signature: Signature
) -> bool:
    if not pubkeys:
        return False
    return verify_signature_sets([SignatureSet(signature, list(pubkeys), message)])


def aggregate_verify(
    pubkeys: Sequence[PublicKey], messages: Sequence[bytes], signature: Signature
) -> bool:
    """Distinct-message aggregate verification."""
    if not pubkeys or len(pubkeys) != len(messages):
        return False
    try:
        sig_pt = signature.point
        pairs = [(cv.g1_neg(cv.g1_generator()), sig_pt)]
        for pk, msg in zip(pubkeys, messages):
            pairs.append((pk.point, _hash_to_g2_memo(msg)))
    except (BlsError, ValueError):
        return False
    if sig_pt is cv.INF:
        return False
    return cv.multi_pairing(pairs).is_one()


# --- batch verification backends -------------------------------------------

# buckets sized for the spread between a 1-set host batch (ms) and a cold
# device compile (minutes) — the default 10 s ceiling would flatten it
_STAGE_BUCKETS = (0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0,
                  10.0, 60.0, 300.0)


def record_batch(backend: str, n_sets: int) -> None:
    """Count one verification batch against a backend (single owner of
    the bls_verify_batches/sets series — the lint in tools/check_metrics
    rejects the same name registered from two modules)."""
    try:
        from lighthouse_tpu.common.metrics import REGISTRY

        REGISTRY.counter(
            "bls_verify_batches_total",
            "batches handed to a BLS backend").labels(backend=backend).inc()
        REGISTRY.counter(
            "bls_verify_sets_total",
            "signature sets handed to a BLS backend",
        ).labels(backend=backend).inc(n_sets)
        REGISTRY.histogram(
            "bls_verify_sets_per_batch",
            "signature sets per verification batch",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048,
                     4096),
        ).labels(backend=backend).observe(n_sets)
    except Exception as e:
        # metrics must never take down a verifier — but a broken
        # registry should not be invisible either
        from lighthouse_tpu.common.metrics import record_swallowed

        record_swallowed("bls.record_batch", e)


# labeled children memoized here: interned() runs per gossip signature
# at flood scale, so the per-call cost must stay one counter.inc()
_CACHE_COUNTERS: dict = {}


def record_cache(cache: str, hit: bool) -> None:
    """Hit/miss accounting for the verify-path caches (pubkey interning,
    hash-to-curve): amortization is the whole argument for the steady-
    state batch numbers, so the ratio must be observable."""
    key = (cache, hit)
    child = _CACHE_COUNTERS.get(key)
    if child is None:
        try:
            from lighthouse_tpu.common.metrics import REGISTRY

            child = REGISTRY.counter(
                "bls_cache_requests_total",
                "verify-path cache lookups by cache and outcome",
            ).labels(cache=cache, outcome="hit" if hit else "miss")
        except Exception as e:
            from lighthouse_tpu.common.metrics import record_swallowed

            record_swallowed("bls.record_cache", e)
            return  # metrics must never take down a verifier
        _CACHE_COUNTERS[key] = child
    child.inc()


def record_stage(backend: str, stage: str, seconds: float) -> None:
    """File one verify-pipeline stage wall time under the shared labeled
    histogram — every BLS backend (reference, tpu, sharded) reports its
    decompress/h2d/kernel/d2h-style breakdown through this one seam."""
    try:
        from lighthouse_tpu.common.metrics import REGISTRY

        REGISTRY.histogram(
            "bls_verify_stage_seconds",
            "per-stage wall time inside BLS batch verification "
            "(device stages time dispatch unless the caller syncs)",
            buckets=_STAGE_BUCKETS,
        ).labels(backend=backend, stage=stage).observe(seconds)
    except Exception as e:
        from lighthouse_tpu.common.metrics import record_swallowed

        record_swallowed("bls.record_stage", e)


def count_fold_lanes(key: int, blinding: int, padding: int) -> None:
    """Lanes of one dispatched slice of the key-aggregation fold
    (ops/bls_backend.aggregate_pubkeys_device): key lanes that carry a
    member, blinding lanes that carry a pool point, and the rest."""
    try:
        from lighthouse_tpu.common.metrics import REGISTRY

        lanes = REGISTRY.counter(
            "bls_fold_lanes_total",
            "lanes of the key-aggregation fold slices dispatched, by kind")
        lanes.labels(kind="key").inc(key)
        lanes.labels(kind="blinding").inc(blinding)
        lanes.labels(kind="padding").inc(padding)
    except Exception as e:
        from lighthouse_tpu.common.metrics import record_swallowed

        record_swallowed("bls.count_fold_lanes", e)


def count_fold_key_rows(resident: int, uploaded: int) -> None:
    """Key lanes of one dispatched slice of the key-aggregation fold by
    where their row came from: ``resident`` rows were already in the
    device's key table (ops/bls_backend._FoldKeyTable), ``uploaded`` rows
    this request converted and uploaded."""
    try:
        from lighthouse_tpu.common.metrics import REGISTRY

        rows = REGISTRY.counter(
            "bls_fold_key_rows_total",
            "key lanes of the key-aggregation fold slices dispatched, by "
            "where their limb row came from")
        rows.labels(source="resident").inc(resident)
        rows.labels(source="uploaded").inc(uploaded)
    except Exception as e:
        from lighthouse_tpu.common.metrics import record_swallowed

        record_swallowed("bls.count_fold_key_rows", e)


def count_fold_products(resident: int, materialized: int) -> None:
    """Fp lane-products of one dispatched slice of the key-aggregation
    fold (ops/msm.blinded_fold_products), by the multiply that runs
    them: ``resident`` the segment sum's (`MontField.mont_mul_lm`),
    ``materialized`` those of the rows behind it (`mont_mul`: the
    unblinding addition, the inversion ladder, the zero test)."""
    try:
        from lighthouse_tpu.common.metrics import REGISTRY

        products = REGISTRY.counter(
            "bls_fold_products_total",
            "Fp lane-products of the key-aggregation fold slices "
            "dispatched, by multiply")
        products.labels(multiply="resident").inc(resident)
        products.labels(multiply="materialized").inc(materialized)
    except Exception as e:
        from lighthouse_tpu.common.metrics import record_swallowed

        record_swallowed("bls.count_fold_products", e)


def count_g2_subgroup_products(resident: int, materialized: int) -> None:
    """Fp lane-products of one dispatched ψ membership program
    (ops/bls_backend._g2_subgroup_products), by the multiply that runs
    them: ``resident`` `MontField.mont_mul_lm`, ``materialized``
    `mont_mul` (its schoolbook products are arrays of the program)."""
    try:
        from lighthouse_tpu.common.metrics import REGISTRY

        products = REGISTRY.counter(
            "g2_subgroup_products_total",
            "Fp lane-products of the G2 membership programs dispatched, "
            "by multiply")
        products.labels(multiply="resident").inc(resident)
        products.labels(multiply="materialized").inc(materialized)
    except Exception as e:
        from lighthouse_tpu.common.metrics import record_swallowed

        record_swallowed("bls.count_g2_subgroup_products", e)


def count_merged(path: str, sets: int) -> None:
    """Signature sets folded into a blinded same-message merge, by where
    the fold ran: ``host`` (the reference rung's `_merge_on_host`, pure
    Python) or ``device`` (the "tpu" backend's merge program,
    ops/msm._blinded_merge)."""
    try:
        from lighthouse_tpu.common.metrics import REGISTRY

        REGISTRY.counter(
            "pre_bls_merged_total",
            "signature sets folded into blinded same-message merges, by "
            "where the fold ran").labels(path=path).inc(sets)
    except Exception as e:
        from lighthouse_tpu.common.metrics import record_swallowed

        record_swallowed("bls.count_merged", e)


def count_merge_lanes(live: int, padding: int) -> None:
    """Lanes of the same-message merge program dispatched: those that
    carry a set, and the padding of the slices."""
    try:
        from lighthouse_tpu.common.metrics import REGISTRY

        lanes = REGISTRY.counter(
            "bls_merge_lanes_total",
            "lanes of the same-message merge dispatches, by kind")
        lanes.labels(kind="live").inc(live)
        lanes.labels(kind="padding").inc(padding)
    except Exception as e:
        from lighthouse_tpu.common.metrics import record_swallowed

        record_swallowed("bls.count_merge_lanes", e)


def count_merge_products(products: int) -> None:
    """Fp lane-products of one dispatched merge (ops/msm
    .blinded_merge_products), all through `MontField.mont_mul_lm`."""
    try:
        from lighthouse_tpu.common.metrics import REGISTRY

        REGISTRY.counter(
            "bls_merge_products_total",
            "Fp lane-products of the same-message merge dispatches").inc(
                products)
    except Exception as e:
        from lighthouse_tpu.common.metrics import record_swallowed

        record_swallowed("bls.count_merge_products", e)


def _merge_on_host(sets: Sequence[SignatureSet]) -> Sequence[SignatureSet]:
    """``sets`` with those that share a message folded into one blinded
    set each, in Python (pool/pre_aggregation.merge_same_message): the
    host rung's own merge, as the "tpu" backend merges such sets on the
    device.  Unchanged when no message repeats or with LHTPU_PRE_BLS=0."""
    if len({s.message for s in sets}) == len(sets):
        return sets
    from lighthouse_tpu.pool import pre_aggregation

    if not pre_aggregation.enabled():
        return sets
    merged, stats = pre_aggregation.merge_same_message(list(sets))
    if stats.merged:
        count_merged("host", stats.merged)
    return merged


def _verify_signature_sets_reference(sets: Sequence[SignatureSet],
                                     chunk_size: int | None = None) -> bool:
    """Randomized batch verification (one multi-pairing for the batch),
    sets that share a message first merged on the host (`_merge_on_host`)
    so that a flood batch pays one pairing lane a message.
    ``chunk_size`` is accepted for seam compatibility and ignored: the
    host path has no device to overlap with."""
    if not sets:
        return False
    sets = _merge_on_host(sets)
    t0 = time.perf_counter()
    prepared = []
    for s in sets:
        if not s.pubkeys:
            return False
        try:
            sig_pt = s.signature.point
            agg_pk = s.aggregate_pubkey()
        except (BlsError, ValueError):
            return False
        if sig_pt is cv.INF:
            return False
        prepared.append((sig_pt, agg_pk, s.message))
    now = time.perf_counter()
    record_stage("reference", "decompress", now - t0)
    t0 = now
    pairs = []
    sig_acc = cv.INF
    for sig_pt, agg_pk, message in prepared:
        rand = 0
        while rand == 0:
            rand = secrets.randbits(RAND_BITS)
        sig_acc = cv.g2_add(sig_acc, cv.g2_mul(sig_pt, rand))
        pairs.append((cv.g1_mul(agg_pk, rand), _hash_to_g2_memo(message)))
    pairs.append((cv.g1_neg(cv.g1_generator()), sig_acc))
    now = time.perf_counter()
    record_stage("reference", "accumulate", now - t0)
    t0 = now
    ok = cv.multi_pairing(pairs).is_one()
    record_stage("reference", "pairing", time.perf_counter() - t0)
    return ok


def _verify_signature_sets_fake(sets: Sequence[SignatureSet],
                                chunk_size: int | None = None) -> bool:
    """Structure checks only; all well-formed signatures verify (reference
    fake_crypto backend, crypto/bls/src/impls/fake_crypto.rs)."""
    if not sets:
        return False
    for s in sets:
        if not s.pubkeys:
            return False
        if len(s.signature.to_bytes()) != 96:
            return False
    return True


_BACKENDS: dict[str, Callable[[Sequence[SignatureSet]], bool]] = {
    "reference": _verify_signature_sets_reference,
    "fake": _verify_signature_sets_fake,
}

_active_backend = "reference"


def register_backend(name: str, fn: Callable[[Sequence[SignatureSet]], bool]):
    _BACKENDS[name] = fn


def _resolve_backend(name: str) -> Callable[[Sequence[SignatureSet]], bool]:
    if name in ("tpu", "sharded") and name not in _BACKENDS:
        # lazy registration: importing a device backend pulls in jax
        # (explicit re-register in case the module was already imported)
        import importlib

        if name == "tpu":
            mod = importlib.import_module("lighthouse_tpu.ops.bls_backend")
            _BACKENDS.setdefault("tpu", mod.verify_signature_sets_device)
        else:
            mod = importlib.import_module(
                "lighthouse_tpu.parallel.bls_sharded")
            _BACKENDS.setdefault("sharded", mod.verify_signature_sets_sharded)
    try:
        return _BACKENDS[name]
    except KeyError:
        raise KeyError(
            f"unknown BLS backend {name!r}; have {sorted(_BACKENDS)}"
        ) from None


def set_backend(name: str):
    global _active_backend
    if name != "auto":
        _resolve_backend(name)  # validate eagerly ("auto" resolves per call)
    _active_backend = name


def get_backend() -> str:
    return _active_backend


def resolve_auto_backend() -> str:
    """'auto' policy: the device pipeline when a TPU is attached, the
    pure-Python reference otherwise (XLA-CPU runs the limb programs slower
    than host Python at node batch sizes).  LHTPU_BLS_BACKEND overrides."""
    import os

    env = os.environ.get("LHTPU_BLS_BACKEND")
    if env:
        _resolve_backend(env)  # fail fast on a typo'd override
        return env
    # a device probe that RAISES propagates: the node fails to start with
    # that error instead of pinning itself to pure Python for its life
    import jax

    platform = jax.devices()[0].platform
    return "tpu" if platform == "tpu" else "reference"


# --- offload supervisor: backend health ladder + crash-safe recovery ---------
#
# A single device fault (XLA compile error, wedged kernel, lost device,
# corrupt readback) must never surface to a verification caller as an
# exception or a wrong verdict: consensus work bounds LIVENESS on
# verification availability, not just throughput.  The supervisor wraps
# the device backends ("tpu", "sharded") behind:
#
# - a per-backend CIRCUIT BREAKER: closed -> open (exponential backoff)
#   -> half-open probe -> closed, so a faulting backend is benched and
#   automatically re-promoted after a successful probe;
# - a WATCHDOG: each supervised batch runs on a daemon thread with an
#   LHTPU_WATCHDOG_S deadline — a hang becomes a recoverable
#   WatchdogTimeout instead of a stuck verifier (the wedged thread is
#   abandoned; its late result is discarded);
# - CRASH-SAFE RECOVERY: on any fault the batch is re-verified on the
#   pure-Python reference backend, the ladder's terminal rung, which is
#   authoritative and never circuit-broken — callers always get a
#   correct verdict, never a torn partial;
# - an optional AUDIT (LHTPU_SUPERVISOR_AUDIT probability): a device
#   verdict is cross-checked against the reference; a mismatch counts
#   as a corrupt-verdict fault, opens the circuit, and the reference
#   verdict is returned.
#
# Health is observable as bls_backend_health{backend,state} gauges and
# zero-duration "bls.backend_health" slot-timeline trace events (the
# PR 1 tracing ring), faults as bls_supervisor_faults_total{backend,kind}.

from lighthouse_tpu.ops import faults as _faults  # stdlib-only module

_DEVICE_BACKENDS = ("tpu", "sharded")
_HEALTH_STATES = ("closed", "open", "half_open")

_FAULT_LOGGED: set[tuple[str, str]] = set()


def _set_health_gauge(backend: str, state: str) -> None:
    try:
        from lighthouse_tpu.common.metrics import REGISTRY

        g = REGISTRY.gauge(
            "bls_backend_health",
            "backend circuit-breaker state (1 = current): "
            "closed|open|half_open")
        for st in _HEALTH_STATES:
            g.labels(backend=backend, state=st).set(
                1.0 if st == state else 0.0)
    except (AttributeError, KeyError, TypeError, ValueError) as e:
        from lighthouse_tpu.common.metrics import record_swallowed

        record_swallowed("bls.supervisor.health_gauge", e)


def _note_transition(backend: str, old: str, new: str) -> None:
    _set_health_gauge(backend, new)
    from lighthouse_tpu.common import flight_recorder as flight
    from lighthouse_tpu.common import tracing

    # zero-duration event in the slot timeline: health flips show up in
    # the same per-slot breakdown as the batches they affected
    with tracing.span("bls.backend_health", backend=backend,
                      transition=f"{old}->{new}"):
        pass
    # the black box: every breaker transition is a flight event, and a
    # breaker OPENING is a trip condition — the ring that led up to it
    # (faults, recoveries, ladder state) dumps to disk
    flight.emit("breaker", plane="bls", backend=backend, old=old, new=new)
    if new == "open":
        flight.trip("bls_breaker_open", backend=backend, old=old)


def _record_fault(backend: str, kind: str, exc: BaseException | None) -> None:
    try:
        from lighthouse_tpu.common.metrics import REGISTRY

        REGISTRY.counter(
            "bls_supervisor_faults_total",
            "device-backend faults absorbed by the offload supervisor, "
            "by backend and kind",
        ).labels(backend=backend, kind=kind).inc()
    except (AttributeError, KeyError, TypeError, ValueError) as e:
        from lighthouse_tpu.common.metrics import record_swallowed

        record_swallowed("bls.supervisor.fault_counter", e)
    from lighthouse_tpu.common import flight_recorder as flight

    flight.emit("supervisor_fault", plane="bls", backend=backend,
                fault=kind, exc=repr(exc) if exc is not None else None)
    if (backend, kind) not in _FAULT_LOGGED:
        _FAULT_LOGGED.add((backend, kind))
        import sys

        print(f"lighthouse_tpu: BLS backend {backend!r} fault ({kind}): "
              f"{exc!r} — degrading; further occurrences counted in "
              f"bls_supervisor_faults_total", file=sys.stderr)


def _record_recovery(entry_backend: str) -> None:
    try:
        from lighthouse_tpu.common.metrics import REGISTRY

        REGISTRY.counter(
            "bls_supervisor_recoveries_total",
            "supervised batches served by the reference backend after "
            "device faults or degradation, by requested backend",
        ).labels(backend=entry_backend).inc()
    except (AttributeError, KeyError, TypeError, ValueError) as e:
        from lighthouse_tpu.common.metrics import record_swallowed

        record_swallowed("bls.supervisor.recovery_counter", e)


class _CircuitBreaker:
    """Per-backend health state machine.

    closed (healthy) -> open on LHTPU_SUPERVISOR_FAILS consecutive
    faults; open -> half_open when the backoff expires (exactly ONE
    probe batch rides through); half_open -> closed on probe success,
    or back to open with DOUBLED backoff (capped) on probe failure."""

    def __init__(self, backend: str, fail_threshold: int,
                 backoff_s: float, backoff_max_s: float):
        self.backend = backend
        self.fail_threshold = fail_threshold
        self.base_backoff_s = backoff_s
        self.backoff_max_s = backoff_max_s
        self._lock = threading.Lock()
        self.state = "closed"
        self.failures = 0
        self.backoff_s = backoff_s
        self.open_until = 0.0
        _set_health_gauge(backend, "closed")

    def allow(self) -> bool:
        """May a batch be attempted on this backend right now?"""
        transition = None
        with self._lock:
            if self.state == "closed":
                ok = True
            elif self.state == "open":
                if time.monotonic() >= self.open_until:
                    transition = (self.state, "half_open")
                    self.state = "half_open"
                    ok = True  # the probe
                else:
                    ok = False
            else:  # half_open: a probe is already in flight elsewhere
                ok = False
        if transition is not None:
            _note_transition(self.backend, *transition)
        return ok

    def record_success(self) -> None:
        with self._lock:
            old = self.state
            self.state = "closed"
            self.failures = 0
            self.backoff_s = self.base_backoff_s
        if old != "closed":
            _note_transition(self.backend, old, "closed")

    def record_failure(self, kind: str) -> None:
        now = time.monotonic()
        opened = None
        with self._lock:
            old = self.state
            self.failures += 1
            if old == "half_open" or self.failures >= self.fail_threshold:
                self.state = "open"
                self.open_until = now + self.backoff_s
                if old == "half_open":  # failed probe: back off harder
                    self.backoff_s = min(self.backoff_s * 2,
                                         self.backoff_max_s)
                if old != "open":
                    opened = (old, "open")
        if opened is not None:
            _note_transition(self.backend, *opened)


class _Supervisor:
    """Config snapshot + breakers; rebuilt by :func:`reset_supervisor`."""

    def __init__(self):
        from lighthouse_tpu.common import env as envreg

        self.enabled = envreg.get_bool("LHTPU_SUPERVISOR", True)
        self.watchdog_s = envreg.get_float("LHTPU_WATCHDOG_S", 900.0)
        self.audit = min(max(
            envreg.get_float("LHTPU_SUPERVISOR_AUDIT", 0.0), 0.0), 1.0)
        raw = envreg.get("LHTPU_SUPERVISOR_LADDER") or ""
        ladder = [r.strip() for r in raw.split(",") if r.strip()]
        self.ladder = ladder or ["tpu", "sharded", "reference"]
        if "reference" not in self.ladder:
            self.ladder.append("reference")
        threshold = max(1, envreg.get_int("LHTPU_SUPERVISOR_FAILS", 1))
        backoff = max(0.0, envreg.get_float(
            "LHTPU_SUPERVISOR_BACKOFF_S", 1.0))
        backoff_max = max(backoff, envreg.get_float(
            "LHTPU_SUPERVISOR_BACKOFF_MAX_S", 60.0))
        self.breakers = {
            b: _CircuitBreaker(b, threshold, backoff, backoff_max)
            for b in _DEVICE_BACKENDS}

    def ladder_from(self, entry: str) -> list[str]:
        if entry in self.ladder:
            return self.ladder[self.ladder.index(entry):]
        return [entry, "reference"]

    def _should_audit(self) -> bool:
        if self.audit >= 1.0:
            return True
        if self.audit <= 0.0:
            return False
        return random.random() < self.audit

    def _call_with_watchdog(self, rung: str, fn, sets, kwargs):
        timeout = self.watchdog_s
        if not timeout or timeout <= 0:
            return fn(sets, **kwargs)
        return _faults.run_with_deadline(
            lambda: fn(sets, **kwargs), timeout,
            f"lhtpu-bls-{rung}", f"{rung} batch")

    def verify(self, entry: str, sets, chunk_size) -> bool:
        """Walk the health ladder from ``entry``; the reference rung is
        the unconditional, never-raising terminal."""
        kwargs = {} if chunk_size is None else {"chunk_size": chunk_size}
        for rung in self.ladder_from(entry):
            if rung == "reference":
                break
            breaker = self.breakers.get(rung)
            if breaker is None or not breaker.allow():
                continue  # benched (or unknown): next rung
            try:
                fn = _resolve_backend(rung)
                ok = self._call_with_watchdog(rung, fn, sets, kwargs)
            except _faults.PROGRAM_FAULTS:
                # the device module failed to import or trace: loud, not
                # a breaker fault (and no half-open probe left wedged)
                breaker.record_failure("raise")
                raise
            except Exception as e:
                kind = _faults.classify(e)
                # fault first, then the breaker transition: the flight
                # ring reads causally (fault -> open) and a breaker-open
                # trip dump carries the fault that caused it
                _record_fault(rung, kind, e)
                breaker.record_failure(kind)
                continue
            except BaseException:
                # KeyboardInterrupt/SystemExit surfacing from the
                # watchdog thread must propagate — but not leave a
                # half-open probe wedged forever (allow() would return
                # False with no backoff expiry to clear it)
                breaker.record_failure("raise")
                raise
            from lighthouse_tpu.common import device_telemetry, tracing

            if self._should_audit():
                ref = _verify_signature_sets_reference(sets)
                if ref != ok:
                    _record_fault(rung, "corrupt", None)
                    breaker.record_failure("corrupt")
                    _record_recovery(entry)
                    tracing.add_attrs(served="reference")
                    device_telemetry.record_first_verify("reference")
                    return ref
            breaker.record_success()
            tracing.add_attrs(served=rung)
            device_telemetry.record_first_verify(rung)
            return ok
        # every device rung faulted or is benched: the in-flight sets are
        # re-verified whole on the authoritative CPU path — the caller
        # gets a correct verdict, never an exception or a torn partial
        from lighthouse_tpu.common import device_telemetry, tracing

        _record_recovery(entry)
        tracing.add_attrs(served="reference")
        ok = _verify_signature_sets_reference(sets)
        device_telemetry.record_first_verify("reference")
        return ok


_SUPERVISOR: _Supervisor | None = None
_SUPERVISOR_LOCK = threading.Lock()


def _get_supervisor() -> _Supervisor:
    global _SUPERVISOR
    s = _SUPERVISOR
    if s is None:
        with _SUPERVISOR_LOCK:
            if _SUPERVISOR is None:
                _SUPERVISOR = _Supervisor()
            s = _SUPERVISOR
    return s


def reset_supervisor() -> None:
    """Drop the supervisor singleton so the next verify re-reads the
    LHTPU_SUPERVISOR_* / LHTPU_WATCHDOG_S knobs (tests; SIGHUP-style
    reconfiguration)."""
    global _SUPERVISOR
    with _SUPERVISOR_LOCK:
        _SUPERVISOR = None


def backend_health() -> dict[str, str]:
    """Current circuit-breaker state per device backend."""
    sup = _get_supervisor()
    return {b: br.state for b, br in sup.breakers.items()}


def verify_signature_sets(
    sets: Sequence[SignatureSet], *, backend: str | None = None,
    chunk_size: int | None = None
) -> bool:
    """THE seam: batch-verify many signature sets on the active backend.

    Callers (block signature verifier, attestation batches) accumulate sets
    and call this once — mirroring the reference call site
    state_processing/src/per_block_processing/block_signature_verifier.rs:396.

    ``chunk_size`` tunes the overlapped dispatch pipeline (see
    ops/dispatch_pipeline): batches above it split into fixed
    power-of-two chunks whose host prep overlaps device execution.  None
    defers to LHTPU_BLS_CHUNK / the pipeline default; 0 forces the
    monolithic single-dispatch path.  It is only forwarded when set, so
    custom-registered backends with a bare ``fn(sets)`` signature keep
    working.

    Device backends ("tpu", "sharded") run SUPERVISED: watchdogged, on
    the backend health ladder, and recovered onto the reference backend
    on any fault — this call returns a correct verdict and never raises
    for device-side reasons (see the supervisor block above; opt out
    with LHTPU_SUPERVISOR=0).  Custom-registered backends and the
    reference/fake backends are invoked directly, unchanged.
    """
    name = backend or _active_backend
    if name == "auto":
        name = resolve_auto_backend()
    sup = _get_supervisor()
    supervised = sup.enabled and name in _DEVICE_BACKENDS
    if not supervised:
        fn = _resolve_backend(name)
    kwargs = {} if chunk_size is None else {"chunk_size": chunk_size}
    record_batch(name, len(sets))
    try:
        from lighthouse_tpu.common.metrics import REGISTRY

        timer = REGISTRY.histogram(
            "bls_verify_seconds",
            "wall time of one batch verification call",
            buckets=_STAGE_BUCKETS).labels(backend=name).time()
    except Exception as e:
        from contextlib import nullcontext

        from lighthouse_tpu.common.metrics import record_swallowed

        record_swallowed("bls.verify_timer", e)
        timer = nullcontext()
    from lighthouse_tpu.common import tracing

    with tracing.span("bls.verify", backend=name, sets=len(sets),
                      supervised=supervised):
        with timer:
            if supervised:
                return sup.verify(name, sets, chunk_size)
            ok = fn(sets, **kwargs)
            from lighthouse_tpu.common import device_telemetry

            # cold-start headline: first completed verification per
            # backend (the AOT program store's acceptance metric)
            device_telemetry.record_first_verify(name)
            return ok
