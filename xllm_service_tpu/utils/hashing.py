"""Chained MurmurHash3 block hashing for the cluster-wide prefix KV-cache index.

The global prefix-cache index keys KV blocks by a 128-bit chained hash:
``digest(block_i) = murmur3_x64_128(digest(block_{i-1}) || le32(tokens_i))``.
This mirrors the reference's chained block hashing
(``common/hash_util.cpp:16-42``) used by ``GlobalKVCacheMgr``
(``scheduler/managers/global_kvcache_mgr.cpp:71-129``), with a proper 16-byte
equality (the reference's ``Murmur3Key::operator==`` via ``strncmp`` is buggy
on embedded NUL bytes — hash_util.h:31-35 — and is deliberately not
replicated).

The hot path lives in the native library ``csrc/xllm_native.cpp`` (built once
on demand with the system C++ toolchain and loaded via ctypes). A pure-Python
implementation is kept both as a fallback and as a cross-check in tests.
"""

from __future__ import annotations

import ctypes
import os
import struct
import sys
from array import array
from typing import List, Optional, Sequence, Tuple

from xllm_service_tpu.utils.locks import make_lock

_MASK64 = (1 << 64) - 1


def _rotl64(x: int, r: int) -> int:
    return ((x << r) | (x >> (64 - r))) & _MASK64


def _fmix64(k: int) -> int:
    k ^= k >> 33
    k = (k * 0xFF51AFD7ED558CCD) & _MASK64
    k ^= k >> 33
    k = (k * 0xC4CEB9FE1A85EC53) & _MASK64
    k ^= k >> 33
    return k


def murmur3_x64_128_py(data: bytes, seed: int = 0) -> bytes:
    """Pure-Python MurmurHash3_x64_128. Returns 16 bytes (h1 || h2, LE)."""
    length = len(data)
    nblocks = length // 16
    # The native path takes a uint32 seed; mask identically here so both
    # implementations stay bit-identical for any Python int seed.
    seed &= 0xFFFFFFFF
    h1 = seed
    h2 = seed
    c1 = 0x87C37B91114253D5
    c2 = 0x4CF5AD432745937F

    for i in range(nblocks):
        k1, k2 = struct.unpack_from("<QQ", data, i * 16)
        k1 = (k1 * c1) & _MASK64
        k1 = _rotl64(k1, 31)
        k1 = (k1 * c2) & _MASK64
        h1 ^= k1
        h1 = _rotl64(h1, 27)
        h1 = (h1 + h2) & _MASK64
        h1 = (h1 * 5 + 0x52DCE729) & _MASK64
        k2 = (k2 * c2) & _MASK64
        k2 = _rotl64(k2, 33)
        k2 = (k2 * c1) & _MASK64
        h2 ^= k2
        h2 = _rotl64(h2, 31)
        h2 = (h2 + h1) & _MASK64
        h2 = (h2 * 5 + 0x38495AB5) & _MASK64

    tail = data[nblocks * 16:]
    k1 = 0
    k2 = 0
    tl = len(tail)
    for i in range(min(tl, 16) - 1, 7, -1):
        k2 ^= tail[i] << ((i - 8) * 8)
    if tl > 8:
        k2 = (k2 * c2) & _MASK64
        k2 = _rotl64(k2, 33)
        k2 = (k2 * c1) & _MASK64
        h2 ^= k2
    for i in range(min(tl, 8) - 1, -1, -1):
        k1 ^= tail[i] << (i * 8)
    if tl > 0:
        k1 = (k1 * c1) & _MASK64
        k1 = _rotl64(k1, 31)
        k1 = (k1 * c2) & _MASK64
        h1 ^= k1

    h1 ^= length
    h2 ^= length
    h1 = (h1 + h2) & _MASK64
    h2 = (h2 + h1) & _MASK64
    h1 = _fmix64(h1)
    h2 = _fmix64(h2)
    h1 = (h1 + h2) & _MASK64
    h2 = (h2 + h1) & _MASK64
    return struct.pack("<QQ", h1, h2)


# ---------------------------------------------------------------------------
# Native library loading (built on demand from csrc/xllm_native.cpp).
# ---------------------------------------------------------------------------

_native_lock = make_lock("hashing.native", 95)
_native_lib: Optional[ctypes.CDLL] = None
_native_tried = False


def _build_native() -> Optional[str]:
    """The hash library for the current csrc/xllm_native.cpp; None (no
    toolchain) falls back to the bit-identical pure-python murmur."""
    from xllm_service_tpu.utils.native_build import build_artifact
    return build_artifact("xllm_native.cpp", "libxllm_native", ".so",
                          ("-O2", "-std=c++17", "-shared", "-fPIC"),
                          timeout_s=120)


def _load_native() -> Optional[ctypes.CDLL]:
    global _native_lib, _native_tried
    with _native_lock:
        if _native_tried:
            return _native_lib
        _native_tried = True
        if os.environ.get("XLLM_DISABLE_NATIVE"):
            return None
        so = _build_native()
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(so)
            lib.xllm_murmur3_x64_128.argtypes = [
                ctypes.c_void_p, ctypes.c_int32, ctypes.c_uint32, ctypes.c_void_p]
            lib.xllm_prefix_block_hashes.argtypes = [
                ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_uint32, ctypes.c_void_p]
            lib.xllm_prefix_block_hashes.restype = ctypes.c_int32
            lib.xllm_chained_block_hash.argtypes = [
                ctypes.c_char_p, ctypes.c_int32, ctypes.c_char_p,
                ctypes.c_uint32, ctypes.c_void_p]
            lib.xllm_chained_block_hash.restype = None
            _native_lib = lib
        except OSError:
            _native_lib = None
        return _native_lib


def native_available() -> bool:
    return _load_native() is not None


def murmur3_x64_128(data: bytes, seed: int = 0) -> bytes:
    lib = _load_native()
    if lib is None:
        return murmur3_x64_128_py(data, seed)
    out = ctypes.create_string_buffer(16)
    lib.xllm_murmur3_x64_128(data, len(data), seed & 0xFFFFFFFF, out)
    return out.raw


def _as_i32(t: int) -> int:
    # Token ids are hashed as little-endian int32. Out-of-range values wrap
    # deterministically so the native and Python paths stay bit-identical.
    return ((t & 0xFFFFFFFF) ^ 0x80000000) - 0x80000000


def pack_tokens(tokens: Sequence[int]) -> Tuple["array[int]", bool]:
    """``tokens`` as ONE packed int32 buffer (``array('i')``: a sequence
    of the same ids, so ``len`` and slices read as the list's), built
    by compiled code, and whether an id lay outside int32 and the
    interpreted wrap of ``_as_i32`` had to run. A buffer handed in comes
    back as it is: a caller that keeps a prompt's buffer (the master, a
    request) packs it once, and ``prefix_block_hashes`` and
    ``prompt_digest`` read it."""
    if isinstance(tokens, array) and tokens.typecode == "i":
        return tokens, False
    try:
        return array("i", tokens), False
    except OverflowError:       # an id outside int32: wrap as _as_i32 does
        return array("i", [_as_i32(t) for t in tokens]), True


def _le32(tokens: Sequence[int], n: int) -> bytes:
    """The first ``n`` of ``tokens`` as little-endian int32 bytes: what
    every digest of this module hashes, on either path."""
    buf = pack_tokens(tokens)[0]
    if sys.byteorder == "big":
        buf = array("i", buf)
        buf.byteswap()
    return memoryview(buf)[:n].tobytes()


def chained_block_hash_py(tokens: Sequence[int], prev: Optional[bytes],
                          seed: int = 0) -> bytes:
    buf = (prev or b"") + struct.pack(
        f"<{len(tokens)}i", *[_as_i32(t) for t in tokens])
    return murmur3_x64_128_py(buf, seed)


def chained_block_hash(tokens: Sequence[int], prev: Optional[bytes],
                       seed: int = 0) -> bytes:
    """Digest of ONE block given its predecessor's digest (None for block
    0): ``prefix_block_hashes(...)[i]`` without rehashing blocks 0..i-1.
    For a caller that keeps the digests of a token list that only grows
    (runtime/kv_cache.py ``PrefixCacheIndex.extend_digests``)."""
    lib = _load_native()
    if lib is None:
        return chained_block_hash_py(tokens, prev, seed)
    if prev is not None and len(prev) != 16:
        raise ValueError("prev is a 16-byte digest or None")
    n = len(tokens)
    out = ctypes.create_string_buffer(16)
    # The library copies the buffer byte for byte, so le32 in is le32
    # hashed, whatever the host's byte order.
    lib.xllm_chained_block_hash(_le32(tokens, n), n, prev,
                                seed & 0xFFFFFFFF, out)
    return out.raw


def prefix_block_hashes(tokens: Sequence[int], block_size: int,
                        seed: int = 0) -> List[bytes]:
    """Chained digests of every *complete* ``block_size`` window of ``tokens``
    (a list of ids, or the buffer ``pack_tokens`` made of one).

    The trailing partial block is excluded: the prefix-cache index only tracks
    full blocks, matching the KV-page granularity of the worker.
    """
    n_blocks = len(tokens) // block_size
    if n_blocks == 0:
        return []
    data = _le32(tokens, n_blocks * block_size)
    lib = _load_native()
    if lib is None:
        out: List[bytes] = []
        prev = b""
        step = 4 * block_size
        for b in range(n_blocks):
            prev = murmur3_x64_128_py(
                prev + data[b * step:(b + 1) * step], seed)
            out.append(prev)
        return out
    buf = ctypes.create_string_buffer(16 * n_blocks)
    # The library copies the buffer byte for byte (as for
    # chained_block_hash): le32 in is le32 hashed.
    lib.xllm_prefix_block_hashes(data, n_blocks * block_size, block_size,
                                 seed & 0xFFFFFFFF, buf)
    raw = buf.raw
    return [raw[i * 16:(i + 1) * 16] for i in range(n_blocks)]


def prompt_digest(tokens: Sequence[int], seed: int = 0) -> str:
    """Whole-prompt content digest (hex) for the poison ledger
    (docs/ROBUSTNESS.md): unlike ``prefix_block_hashes`` it covers the
    trailing partial block too — two prompts quarantine together iff
    they are token-identical. Same int32 packing as the block hashes
    (and the same two inputs: a list, or its packed buffer), so the
    digest is stable across the native and Python paths."""
    return murmur3_x64_128(_le32(tokens, len(tokens)), seed).hex()
