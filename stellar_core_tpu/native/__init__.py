"""Native (C) batched host-prep for the ed25519 verifier.

Builds `prep.c` into a shared library on first use (cc -O2, cached under
build/) and exposes it through ctypes. The numpy/hashlib path in
ops/ed25519.py remains the fallback — the native path must produce
bit-identical arrays (tests/test_native_prep.py asserts parity).

Why C here: the per-item SHA-512 + mod-L loop is the one host-side cost
that can't be numpy-vectorized, and at the 100K sigs/s north star the
Python loop overhead alone would eat ~15% of a core (VERDICT r2 weak #7).
One C call per batch removes Python from the loop entirely.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
# SCT_SANITIZE reroutes every native build into a sanitizer-specific
# build dir: "1" (or "address") -> build/sanitized/ with
# -fsanitize=address,undefined, "thread" -> build/tsan/ with
# -fsanitize=thread. tools/build_native_sanitized.sh compiles all the
# extensions there, and the `sanitize`-marked differential tests run
# under them with libasan/libtsan preloaded (docs/static-analysis.md
# "Sanitized native builds"). Read at import so one process is wholly
# sanitized or wholly not — mixing sanitized and plain libs in-process
# is UB, and ASan and TSan are mutually exclusive per process.
_SAN_RAW = os.environ.get("SCT_SANITIZE", "")
_SAN_MODES = {"": "", "0": "", "1": "address", "address": "address",
              "thread": "thread"}
if _SAN_RAW not in _SAN_MODES:
    # fail LOUDLY: a typo ('tsan', 'asan') silently producing a plain
    # build would make the sanitizer run vacuously clean
    raise RuntimeError(
        "SCT_SANITIZE=%r is not a sanitize mode (use 1/address for "
        "ASan+UBSan, thread for TSan, 0/unset for none)" % _SAN_RAW)
SANITIZE_MODE = _SAN_MODES[_SAN_RAW]
SANITIZE = SANITIZE_MODE != ""   # truthy back-compat alias
if SANITIZE_MODE == "thread":
    _BUILD = os.path.join(_DIR, "build", "tsan")
    _SANITIZE_FLAGS = ["-fsanitize=thread",
                       "-fno-omit-frame-pointer", "-g"]
elif SANITIZE_MODE == "address":
    _BUILD = os.path.join(_DIR, "build", "sanitized")
    _SANITIZE_FLAGS = ["-fsanitize=address,undefined",
                       "-fno-omit-frame-pointer", "-g"]
else:
    _BUILD = os.path.join(_DIR, "build")
    _SANITIZE_FLAGS = []
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False
# engine name -> why it is missing here (engine_status): every engine
# below degrades to a slower Python path, which must not also be silent
_WHY_MISSING: dict = {}


def _cc_build(src_path: str, so_path: str, include_dir: str) -> bool:
    """Try cc/gcc/g++ -O2 -shared -fPIC; atomic-rename into so_path.
    Shared by the prep library and the XDR extension builds."""
    import tempfile
    extra = list(_SANITIZE_FLAGS)
    why = "no C compiler (cc/gcc/g++) on PATH"
    # the compiler must NOT inherit a sanitizer-runtime LD_PRELOAD: the
    # preload is for loading the built .so into THIS process, and a
    # TSan-preloaded python forking gcc can deadlock in the runtime's
    # fork interceptor (observed: 5-minute wedge under SCT_SANITIZE=thread)
    cc_env = {k: v for k, v in os.environ.items() if k != "LD_PRELOAD"}
    for cc in ("cc", "gcc", "g++"):
        tmp = tempfile.NamedTemporaryFile(
            dir=_BUILD, suffix=".so", delete=False)
        tmp.close()
        try:
            # -pthread: applyc.c's parallel close spawns worker threads;
            # harmless for the single-threaded extensions
            r = subprocess.run(
                [cc, "-O2", "-shared", "-fPIC", "-pthread"] + extra +
                ["-I", include_dir, "-o", tmp.name, src_path],
                capture_output=True, text=True, timeout=300, env=cc_env)
        except (OSError, subprocess.TimeoutExpired):
            os.unlink(tmp.name)
            continue
        if r.returncode == 0:
            os.rename(tmp.name, so_path)  # atomic: concurrent builders ok
            return True
        os.unlink(tmp.name)
        why = "%s failed: %s" % (cc, r.stderr.strip()[-400:])
    _WHY_MISSING[os.path.basename(src_path)] = why
    return False


def _compile() -> Optional[str]:
    import hashlib

    os.makedirs(_BUILD, exist_ok=True)
    src = os.path.join(_DIR, "prep.c")
    gen = os.path.join(_DIR, "gen_constants.py")
    from .gen_constants import header_text
    header = header_text()
    # hash ALL inputs into the artifact name: a constants or source change
    # can never silently reuse a stale library
    with open(src, "rb") as fh:
        digest = hashlib.sha256(
            fh.read() + header.encode() +
            open(gen, "rb").read()).hexdigest()[:16]
    so = os.path.join(_BUILD, "libsctprep-%s.so" % digest)
    if os.path.exists(so):
        return so
    hdr = os.path.join(_BUILD, "prep_constants.h")
    with open(hdr, "w") as fh:
        fh.write(header)
    return so if _cc_build(src, so, _BUILD) else None


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        try:
            so = _compile()
            if so is None:
                return None
            lib = ctypes.CDLL(so)
            lib.sct_prepare_packed.restype = ctypes.c_int
            lib.sct_prepare_packed.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_void_p]
            lib.sct_cache_keys.restype = ctypes.c_int
            lib.sct_cache_keys.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p]
            _LIB = lib
        except Exception as e:
            _WHY_MISSING["prep.c"] = repr(e)
            _LIB = None
        return _LIB


def available() -> bool:
    return _load() is not None


def engine_status() -> dict:
    """{engine: None | why it is missing} for the three native engines
    the served path silently runs slower without — "prep" (verify-kernel
    host prep; numpy/hashlib otherwise), "apply" (the close engine; the
    Python apply loop otherwise), "xdr" (the serializer; fastcodec
    otherwise). Builds whatever is not built yet."""
    _compile_xdr_ext()
    have = {"prep": (_load(), "prep.c"),
            "apply": (apply_engine(), "applyc.c"),
            "xdr": (_XDR_MOD, "xdrc.c")}
    return {name: None if engine is not None else _WHY_MISSING.get(
                src, "switched off by its SCT_NATIVE_* variable")
            for name, (engine, src) in have.items()}


def prepare_packed_native(pub_arr: np.ndarray, sig_arr: np.ndarray,
                          msgs: list, good: np.ndarray,
                          out: np.ndarray) -> Optional[np.ndarray]:
    """(n,32)/(n,64) uint8 + message list → the first n lanes of `out`
    (a zeroed (size, 128) uint8 buffer, size >= n) written in place, and
    the (n,) bool precheck mask; None when the native library is
    unavailable. Rows with wrong-length keys or sigs are zero rows with
    `good` False (same contract as _pack32)."""
    lib = _load()
    if lib is None:
        return None
    n = pub_arr.shape[0]
    blob = b"".join(msgs)
    off = np.zeros(n + 1, np.uint64)
    np.cumsum([len(m) for m in msgs], out=off[1:])
    pre_ok = np.empty(n, np.uint8)
    pub_c = np.ascontiguousarray(pub_arr)
    sig_c = np.ascontiguousarray(sig_arr)
    good_c = np.ascontiguousarray(good, np.uint8)
    msg_c = np.frombuffer(blob, np.uint8) if blob else \
        np.zeros(1, np.uint8)
    if out.dtype != np.uint8 or not out.flags.c_contiguous or \
            out.shape[0] < n or out.shape[1:] != (128,):
        raise ValueError("packed buffer must be C-contiguous uint8 "
                         "(>= %d, 128), got %s %r" % (n, out.dtype,
                                                      out.shape))
    lib.sct_prepare_packed(
        pub_c.ctypes.data, sig_c.ctypes.data, msg_c.ctypes.data,
        off.ctypes.data, good_c.ctypes.data, n,
        out.ctypes.data, pre_ok.ctypes.data)
    return pre_ok.astype(bool)


def cache_keys_native(triples) -> Optional[list]:
    """[(key32, sig64, msg)] → [sha256(key‖sig‖msg)] in one C call, or
    None (malformed lengths / library unavailable — callers fall back to
    the per-triple hashlib path). One drain's worth of verify-cache keys
    is ~1/3 of the host-side prewarm cost when hashed in Python."""
    lib = _load()
    n = len(triples)
    if lib is None or n == 0:
        return None
    pubs = b"".join(t[0] for t in triples)
    sigs = b"".join(t[1] for t in triples)
    if len(pubs) != 32 * n or len(sigs) != 64 * n:
        return None
    msgs = b"".join(t[2] for t in triples)
    off = np.zeros(n + 1, np.uint64)
    np.cumsum([len(t[2]) for t in triples], out=off[1:])
    msg_c = np.frombuffer(msgs, np.uint8) if msgs else np.zeros(1, np.uint8)
    out = np.empty(32 * n, np.uint8)
    lib.sct_cache_keys(pubs, sigs, msg_c.ctypes.data, off.ctypes.data, n,
                       out.ctypes.data)
    ob = out.tobytes()
    return [ob[32 * i:32 * i + 32] for i in range(n)]


# --------------------------------------------------------------------------
# Native ed25519/X25519 (ed25519c.c): the CPU crypto floor when the
# `cryptography` package is absent. Loaded via ctypes like prep.c; shares
# the generated prep_constants.h. crypto/fallback.py holds the pure-Python
# oracle used when no compiler is available.

_ED_LIB = None
_ED_TRIED = False


class _Ed25519Native:
    """Thin ctypes wrapper; one instance per process."""

    def __init__(self, lib) -> None:
        self._lib = lib

    def public(self, seed: bytes) -> bytes:
        out = ctypes.create_string_buffer(32)
        self._lib.sct_ed25519_public(seed, out)
        return out.raw

    def sign(self, seed: bytes, msg: bytes) -> bytes:
        out = ctypes.create_string_buffer(64)
        self._lib.sct_ed25519_sign(seed, msg, len(msg), out)
        return out.raw

    def verify(self, pub: bytes, sig: bytes, msg: bytes) -> bool:
        if len(pub) != 32 or len(sig) != 64:
            return False
        return bool(self._lib.sct_ed25519_verify(pub, sig, msg, len(msg)))

    def verify_batch(self, triples) -> list:
        """[(key32, sig64, msg)] → [bool] in one C call."""
        n = len(triples)
        if n == 0:
            return []
        pubs = b"".join(t[0] for t in triples)
        sigs = b"".join(t[1] for t in triples)
        if len(pubs) != 32 * n or len(sigs) != 64 * n:
            # odd-length keys/sigs: per-item path handles rejections
            return [self.verify(k, s, m) for (k, s, m) in triples]
        msgs = b"".join(t[2] for t in triples)
        off = np.zeros(n + 1, np.uint64)
        np.cumsum([len(t[2]) for t in triples], out=off[1:])
        out = np.empty(n, np.uint8)
        self._lib.sct_ed25519_verify_batch(
            pubs, sigs, msgs or b"\x00",
            off.ctypes.data_as(ctypes.c_void_p), n,
            out.ctypes.data_as(ctypes.c_void_p))
        return out.astype(bool).tolist()

    def x25519(self, scalar: bytes, u: bytes) -> bytes:
        out = ctypes.create_string_buffer(32)
        self._lib.sct_x25519(scalar, u, out)
        return out.raw


def ed25519_native() -> Optional[_Ed25519Native]:
    """Build + load the native ed25519 library, or None (callers fall
    back to the pure-Python path). Gated by SCT_NATIVE_ED25519."""
    global _ED_LIB, _ED_TRIED
    if _ED_TRIED:
        return _ED_LIB
    with _LOCK:
        if _ED_TRIED:
            return _ED_LIB
        try:
            if os.environ.get("SCT_NATIVE_ED25519", "1") == "0":
                return None
            import hashlib
            os.makedirs(_BUILD, exist_ok=True)
            src = os.path.join(_DIR, "ed25519c.c")
            from .gen_constants import header_text
            header = header_text()
            with open(src, "rb") as fh:
                digest = hashlib.sha256(
                    fh.read() + header.encode()).hexdigest()[:16]
            so = os.path.join(_BUILD, "libscted25519-%s.so" % digest)
            if not os.path.exists(so):
                hdr = os.path.join(_BUILD, "prep_constants.h")
                with open(hdr, "w") as fh:
                    fh.write(header)
                if not _cc_build(src, so, _BUILD):
                    return None
            lib = ctypes.CDLL(so)
            lib.sct_ed25519_public.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p]
            lib.sct_ed25519_sign.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64,
                ctypes.c_char_p]
            lib.sct_ed25519_verify.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
                ctypes.c_uint64]
            lib.sct_ed25519_verify.restype = ctypes.c_int
            lib.sct_ed25519_verify_batch.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p]
            lib.sct_x25519.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p]
            if lib.sct_ed25519_init() != 0:
                return None
            _ED_LIB = _Ed25519Native(lib)
        except Exception:
            _ED_LIB = None
        finally:
            _ED_TRIED = True
        return _ED_LIB


# --------------------------------------------------------------------------
# Native transaction-apply engine (_sctapply extension, applyc.c): the
# replay-loop fast path. ledger/native_apply.py is the only caller; the
# Python apply path stays the fallback and the differential oracle
# (tests/test_native_apply.py).

_APPLY_MOD = None
_APPLY_TRIED = False


def apply_engine():
    """The _sctapply module, or None (gated by SCT_NATIVE_APPLY, absent
    compiler, or build failure — callers fall back to Python apply)."""
    global _APPLY_MOD, _APPLY_TRIED
    if _APPLY_TRIED:
        return _APPLY_MOD
    with _LOCK:
        if _APPLY_TRIED:
            return _APPLY_MOD
        _APPLY_TRIED = True
        if os.environ.get("SCT_NATIVE_APPLY", "1") == "0":
            return None
        import hashlib
        import importlib.util
        import sysconfig

        try:
            os.makedirs(_BUILD, exist_ok=True)
            src = os.path.join(_DIR, "applyc.c")
            with open(src, "rb") as fh:
                digest = hashlib.sha256(fh.read()).hexdigest()[:16]
            tag = getattr(sys.implementation, "cache_tag", "py")
            so = os.path.join(_BUILD, "_sctapply-%s-%s.so" % (tag, digest))
            if not os.path.exists(so):
                inc = sysconfig.get_paths()["include"]
                if not _cc_build(src, so, inc):
                    return None
            spec = importlib.util.spec_from_file_location("_sctapply", so)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _APPLY_MOD = mod
        except Exception as e:
            _WHY_MISSING["applyc.c"] = repr(e)
            _APPLY_MOD = None
        return _APPLY_MOD


# --------------------------------------------------------------------------
# Native XDR serializer (_sctxdr extension): compiles codec type trees into
# flat programs interpreted in C. xdr_bytes() prefers this engine; the
# pure-Python fastcodec stays the fallback and the behavioral oracle.

_XDR_MOD = None
_XDR_TRIED = False


def _compile_xdr_ext() -> None:
    """Build native/xdrc.c into an importable CPython extension, cached
    under build/ keyed by (source hash, interpreter ABI tag) — extension
    modules are not ABI-stable across CPython versions, so a cached build
    must never be reused by a different interpreter."""
    global _XDR_MOD, _XDR_TRIED
    with _LOCK:
        if _XDR_TRIED:
            return
        if os.environ.get("SCT_NATIVE_XDR", "1") == "0":
            _XDR_TRIED = True
            return
        import hashlib
        import importlib.util
        import sysconfig

        os.makedirs(_BUILD, exist_ok=True)
        src = os.path.join(_DIR, "xdrc.c")
        with open(src, "rb") as fh:
            digest = hashlib.sha256(fh.read()).hexdigest()[:16]
        tag = getattr(sys.implementation, "cache_tag", "py")
        so = os.path.join(_BUILD, "_sctxdr-%s-%s.so" % (tag, digest))
        if not os.path.exists(so):
            inc = sysconfig.get_paths()["include"]
            if not _cc_build(src, so, inc):
                _XDR_TRIED = True
                return
        try:
            spec = importlib.util.spec_from_file_location("_sctxdr", so)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _XDR_MOD = mod
        except Exception as e:
            _WHY_MISSING["xdrc.c"] = repr(e)
            _XDR_MOD = None
        _XDR_TRIED = True


def _build_xdr_spec(t, nodes, memo):
    """Flatten a codec type combinator into the C program's node list;
    returns the node index. `memo` breaks recursion (SCPQuorumSet nests
    itself) by reserving an index before children compile."""
    from ..xdr import codec as C

    key = id(t)
    if key in memo:
        return memo[key]
    idx = len(nodes)
    memo[key] = idx
    nodes.append(None)  # reserve

    if isinstance(t, C._Int):
        size = t._s.size
        signed = 1 if t._lo < 0 else 0
        nodes[idx] = (0, size, signed)
    elif isinstance(t, C._Bool):
        nodes[idx] = (1, 0, 0)
    elif isinstance(t, C.Opaque):
        nodes[idx] = (2, t.n, 0)
    elif isinstance(t, C.VarOpaque):
        nodes[idx] = (3, t.maxn, 0)
    elif isinstance(t, C.XdrString):
        nodes[idx] = (4, t._o.maxn, 0)
    elif isinstance(t, C.FixedArray):
        c = _build_xdr_spec(t.elem, nodes, memo)
        nodes[idx] = (5, t.n, c)
    elif isinstance(t, C.VarArray):
        c = _build_xdr_spec(t.elem, nodes, memo)
        nodes[idx] = (6, t.maxn, c)
    elif isinstance(t, C.OptionalT):
        c = _build_xdr_spec(t.elem, nodes, memo)
        nodes[idx] = (7, 0, c)
    elif isinstance(t, C.EnumT):
        nodes[idx] = (8, 0, 0, tuple(sorted(t.values)))
    elif isinstance(t, type) and issubclass(t, C.XdrStruct):
        fields = tuple(
            (n, _build_xdr_spec(ft, nodes, memo)) for n, ft in t.xdr_fields)
        nodes[idx] = (9, 0, 0, fields, t)
    elif isinstance(t, type) and issubclass(t, C.XdrUnion):
        sw = _build_xdr_spec(t.xdr_switch_type, nodes, memo)
        arms = tuple(
            (d, -1 if at is None else _build_xdr_spec(at, nodes, memo))
            for d, (an, at) in t.xdr_arms.items())
        if t.xdr_default is None:
            default = -2
        elif t.xdr_default[1] is None:
            default = -1
        else:
            default = _build_xdr_spec(t.xdr_default[1], nodes, memo)
        nodes[idx] = (10, sw, 0, (arms, default), t)
    else:
        raise TypeError("no native program for %r" % (t,))
    return idx


def _xdr_program(t):
    """Compiled program for a type, memoized on the class (pack and
    unpack share one program)."""
    _compile_xdr_ext()
    if _XDR_MOD is None:
        return None
    cached = t.__dict__.get("_native_prog") if isinstance(t, type) \
        else getattr(t, "_native_prog", None)
    if cached is not None:
        return cached or None
    try:
        nodes = []
        _build_xdr_spec(t, nodes, {})
        prog = _XDR_MOD.compile(tuple(nodes))
    except TypeError:
        prog = None
    try:
        t._native_prog = prog if prog is not None else False
    except (AttributeError, TypeError):
        pass
    return prog


def xdr_pack_fn(t):
    """Native pack function for a codec type, or None when the extension
    is unavailable or the type has a combinator the program can't express
    (callers fall back to fastcodec)."""
    prog = _xdr_program(t)
    if prog is None:
        return None
    pack = _XDR_MOD.pack

    def f(v, prog=prog, pack=pack):
        return pack(prog, v)
    return f


def xdr_unpack_fn(t):
    """Native unpack: f(buf, pos=0) -> (value, end), or None (fallback)."""
    prog = _xdr_program(t)
    if prog is None:
        return None
    unpack = _XDR_MOD.unpack

    def f(buf, pos=0, prog=prog, unpack=unpack):
        return unpack(prog, buf, pos)
    return f
