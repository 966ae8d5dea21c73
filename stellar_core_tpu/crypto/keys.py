"""Key management and the synchronous signature boundary.

Role parity: reference `src/crypto/SecretKey.{h,cpp}`:
- SecretKey::sign (SecretKey.cpp:123), random/from-seed/pseudo keys
- PubKeyUtils::verifySig (SecretKey.cpp:310) with the global verify-result
  cache (SecretKey.cpp:27-51,320-337)
- KeyUtils strkey round-trips

CPU crypto is OpenSSL via the `cryptography` package (the libsodium stand-in:
RFC 8032 semantics — cofactorless verify, rejects non-canonical S and
non-canonical point encodings). The TPU batch path (crypto/batch_verifier.py)
implements the SAME accept/reject semantics so backends are interchangeable.
"""

from __future__ import annotations

import hashlib
from typing import Optional

try:
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric import ed25519 as _ed
except ImportError:  # hermetic container: self-contained fallback
    # (native C ed25519c.c when a compiler exists, pure-Python RFC 8032
    # otherwise — identical accept/reject semantics, see crypto/fallback)
    InvalidSignature = serialization = _ed = None

from ..util.cache import RandomEvictionCache
from ..xdr import PublicKey, SignatureHint
from . import strkey
from .hashing import sha256

VERIFY_CACHE_SIZE = 0xFFFF

# tracked: the verify cache is the one structure every thread touches
# (main loop, threaded dispatch worker, HTTP metrics reads) — the
# lock-order checker (util/threads.py) watches it under tests
from ..util.threads import TrackedLock  # noqa: E402

_cache_lock = TrackedLock("crypto.verify-cache")
_verify_cache: RandomEvictionCache = RandomEvictionCache(VERIFY_CACHE_SIZE)


class VerdictCache:
    """A cache of verify verdicts and its lock. `PROCESS_CACHE` is the
    reference's process-wide gVerifySigCache: every node of a one-process
    simulation shares it, so a signature is verified once by whichever
    node sees it first. A node with `VERIFY_CACHE_SCOPE = "node"` gives
    its verifier stack one of its own, as a node in a process of its own
    has: it trusts no verdict another node computed."""

    __slots__ = ("lock", "store")

    def __init__(self, lock=None, store=None) -> None:
        self.lock = TrackedLock("crypto.verify-cache") \
            if lock is None else lock
        self.store = RandomEvictionCache(VERIFY_CACHE_SIZE) \
            if store is None else store


PROCESS_CACHE = VerdictCache(_cache_lock, _verify_cache)


def _cache_key(key32: bytes, sig: bytes, msg: bytes) -> bytes:
    h = hashlib.sha256()
    h.update(key32)
    h.update(sig)
    h.update(msg)
    return h.digest()


def verify_cache_stats(cache: Optional[VerdictCache] = None) -> dict:
    cache = cache or PROCESS_CACHE
    with cache.lock:
        return {"hits": cache.store.hits, "misses": cache.store.misses,
                "size": len(cache.store)}


def count_uncached(cache: VerdictCache, triples) -> int:
    """Of (key32, sig, msg) triples, those `cache` holds no verdict for.
    A look, not a probe: it counts no hit and no miss."""
    cks = [_cache_key(k, s, m) for (k, s, m) in triples]
    with cache.lock:
        return sum(1 for ck in cks if ck not in cache.store)


def flush_verify_cache() -> None:
    with _cache_lock:
        _verify_cache.clear()
        _verify_cache.hits = 0
        _verify_cache.misses = 0


def raw_verify(key32: bytes, sig: bytes, msg: bytes) -> bool:
    """Uncached single ed25519 verify (OpenSSL, or the self-contained
    fallback when `cryptography` is absent)."""
    if len(sig) != 64:
        return False
    if _ed is None:
        from . import fallback as _fb
        return _fb.ed25519_verify(key32, sig, msg)
    try:
        pk = _ed.Ed25519PublicKey.from_public_bytes(key32)
        pk.verify(sig, msg)
        return True
    except (InvalidSignature, ValueError):
        return False


_CPU_VERIFY_THREADS = None


def _cpu_verify_threads() -> int:
    """Shard width for large CPU verify batches (ISSUE 13: the replay
    pipeline is verify-bound on the sync CPU backend; sharding the
    native batch call over threads — it drops the GIL — is the only CPU
    lever left). SCT_VERIFY_CPU_THREADS=1 disables."""
    global _CPU_VERIFY_THREADS
    if _CPU_VERIFY_THREADS is None:
        import os
        try:
            n = int(os.environ.get("SCT_VERIFY_CPU_THREADS", "0"))
        except ValueError:
            n = 0
        if n <= 0:
            n = min(8, os.cpu_count() or 1)
        _CPU_VERIFY_THREADS = max(1, n)
    return _CPU_VERIFY_THREADS


def _verify_batch_sharded(lib, triples, nthreads: int) -> list:
    """Split one big batch across ephemeral worker threads, each running
    the native verify_batch ctypes call (GIL released inside). Pure
    function of the inputs — shard boundaries cannot change results."""
    from ..util.threads import spawn_worker
    n = len(triples)
    chunk = (n + nthreads - 1) // nthreads
    bounds = [(i, min(i + chunk, n)) for i in range(0, n, chunk)]
    results: list = [None] * len(bounds)
    errors: list = [None] * len(bounds)

    def run(idx, lo, hi):
        try:
            results[idx] = lib.verify_batch(triples[lo:hi])
        except BaseException as e:  # re-raised on the caller below
            errors[idx] = e

    threads = []
    for idx, (lo, hi) in enumerate(bounds[1:], start=1):
        threads.append(spawn_worker(
            "crypto.cpu-verify-shard",
            (lambda idx=idx, lo=lo, hi=hi: run(idx, lo, hi))))
    run(0, bounds[0][0], bounds[0][1])
    for t in threads:
        t.join()
    for e in errors:
        if e is not None:
            raise e
    out: list = []
    for r in results:
        out.extend(r)
    return out


def raw_verify_batch(triples) -> list:
    """[(key32, sig, msg)] → [bool], one native call when the C library
    is available (CpuSigVerifier's whole-batch drain path); batches of
    256+ shard over worker threads."""
    if _ed is None:
        from ..native import ed25519_native
        lib = ed25519_native()
        if lib is not None:
            out = [False] * len(triples)
            good = [i for i, (k, s, _m) in enumerate(triples)
                    if len(k) == 32 and len(s) == 64]
            good_triples = [triples[i] for i in good]
            nthreads = _cpu_verify_threads()
            if len(good) >= 256 and nthreads > 1:
                oks = _verify_batch_sharded(lib, good_triples, nthreads)
            else:
                oks = lib.verify_batch(good_triples)
            for i, ok in zip(good, oks):
                out[i] = ok
            return out
    return [raw_verify(k, s, m) for (k, s, m) in triples]


def verify_cached(cache: VerdictCache, key: PublicKey, sig: bytes,
                  msg: bytes) -> bool:
    ck = _cache_key(key.key_bytes, sig, msg)
    with cache.lock:
        got = cache.store.maybe_get(ck)
    if got is not None:
        return got
    ok = raw_verify(key.key_bytes, sig, msg)
    with cache.lock:
        cache.store.put(ck, ok)
    return ok


class PubKeyUtils:
    @staticmethod
    def verify_sig(key: PublicKey, sig: bytes, msg: bytes) -> bool:
        """Cached verify — the L0 in front of any batch backend
        (reference SecretKey.cpp:310-337)."""
        return verify_cached(PROCESS_CACHE, key, sig, msg)

    @staticmethod
    def get_hint(key: PublicKey) -> bytes:
        """Last 4 bytes of the key (reference getHint)."""
        return key.key_bytes[-4:]


class SecretKey:
    """Ed25519 secret key (seed form)."""

    def __init__(self, seed32: bytes) -> None:
        assert len(seed32) == 32
        self._seed = seed32
        if _ed is not None:
            self._sk = _ed.Ed25519PrivateKey.from_private_bytes(seed32)
            pub = self._sk.public_key().public_bytes(
                serialization.Encoding.Raw, serialization.PublicFormat.Raw)
        else:
            from . import fallback as _fb
            self._sk = None
            pub = _fb.ed25519_public(seed32)
        self._pub = PublicKey.ed25519(pub)

    # -- constructors -------------------------------------------------------
    @classmethod
    def random(cls) -> "SecretKey":
        import os
        return cls(os.urandom(32))

    @classmethod
    def from_seed(cls, seed32: bytes) -> "SecretKey":
        return cls(seed32)

    @classmethod
    def pseudo_random_for_testing(cls, rng=None) -> "SecretKey":
        from ..util import rnd
        r = rng or rnd.g_random
        return cls(bytes(r.getrandbits(8) for _ in range(32)))

    @classmethod
    def from_strkey_seed(cls, s: str) -> "SecretKey":
        return cls(strkey.decode_seed(s))

    # -- accessors ----------------------------------------------------------
    @property
    def public_key(self) -> PublicKey:
        return self._pub

    @property
    def seed(self) -> bytes:
        return self._seed

    def strkey_seed(self) -> str:
        return strkey.encode_seed(self._seed)

    def strkey_public(self) -> str:
        return strkey.encode_public_key(self._pub.key_bytes)

    # -- signing ------------------------------------------------------------
    def sign(self, msg: bytes) -> bytes:
        if self._sk is not None:
            return self._sk.sign(msg)
        from . import fallback as _fb
        return _fb.ed25519_sign(self._seed, msg)

    def sign_decorated(self, msg: bytes):
        from ..xdr import DecoratedSignature
        return DecoratedSignature(hint=PubKeyUtils.get_hint(self._pub),
                                  signature=self.sign(msg))

    def __repr__(self) -> str:
        return "SecretKey(%s)" % self.strkey_public()


class KeyUtils:
    @staticmethod
    def to_strkey(key: PublicKey) -> str:
        return strkey.encode_public_key(key.key_bytes)

    @staticmethod
    def from_strkey(s: str) -> PublicKey:
        return PublicKey.ed25519(strkey.decode_public_key(s))

    @staticmethod
    def short_name(key: PublicKey) -> str:
        return KeyUtils.to_strkey(key)[:5]
