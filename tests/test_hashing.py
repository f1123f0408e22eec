"""MurmurHash3 + chained block hashing: native/python parity and known vectors."""

import struct

import pytest

from xllm_service_tpu.utils import hashing


# Known-good MurmurHash3_x64_128 vectors (computed with the canonical smhasher
# reference implementation).
KNOWN_VECTORS = [
    (b"", 0, "00000000000000000000000000000000"),
    (b"a", 0, "897859f6655555855a890e51483ab5e6"),
    (b"abc", 0, "6778ad3f3f3f96b4522dca264174a23b"),
    (b"hello world", 0, "0e617feb46603f53b163eb607d4697ab"),
    (b"The quick brown fox jumps over the lazy dog", 0,
     "6c1b07bc7bbc4be347939ac4a93c437a"),
    (b"abc", 123, "a2bdf7a7bdbfab14f3a348a6d6c27db4"),
]


@pytest.mark.parametrize("data,seed,hexdigest", KNOWN_VECTORS)
def test_murmur3_py_known_vectors(data, seed, hexdigest):
    assert hashing.murmur3_x64_128_py(data, seed).hex() == hexdigest


def test_native_matches_python():
    if not hashing.native_available():
        pytest.skip("native lib unavailable")
    for data, seed, _ in KNOWN_VECTORS:
        assert hashing.murmur3_x64_128(data, seed) == \
            hashing.murmur3_x64_128_py(data, seed)
    blob = bytes(range(256)) * 7 + b"tail"
    assert hashing.murmur3_x64_128(blob, 42) == \
        hashing.murmur3_x64_128_py(blob, 42)


def test_prefix_block_hashes_chaining():
    tokens = list(range(300))
    bs = 128
    digests = hashing.prefix_block_hashes(tokens, bs, seed=7)
    # 300 tokens → 2 complete blocks; trailing partial block excluded.
    assert len(digests) == 2

    # Manual chain: block0 = H(tokens[0:128]); block1 = H(d0 || tokens[128:256]).
    d0 = hashing.murmur3_x64_128_py(struct.pack("<128i", *tokens[:128]), 7)
    d1 = hashing.murmur3_x64_128_py(
        d0 + struct.pack("<128i", *tokens[128:256]), 7)
    assert digests[0] == d0
    assert digests[1] == d1


def test_prefix_block_hashes_prefix_property():
    """Shared prefixes share digests; divergence changes all later digests."""
    a = list(range(512))
    b = list(range(512))
    b[300] = 9999  # diverge inside block 2
    da = hashing.prefix_block_hashes(a, 128)
    db = hashing.prefix_block_hashes(b, 128)
    assert da[0] == db[0] and da[1] == db[1]
    assert da[2] != db[2]
    assert da[3] != db[3]  # chained: divergence propagates


def test_native_prefix_matches_python_fallback(monkeypatch):
    if not hashing.native_available():
        pytest.skip("native lib unavailable")
    tokens = [(i * 2654435761) % 50000 for i in range(1000)]
    native = hashing.prefix_block_hashes(tokens, 64, seed=3)
    monkeypatch.setattr(hashing, "_load_native", lambda: None)
    pure = hashing.prefix_block_hashes(tokens, 64, seed=3)
    assert native == pure


def test_out_of_range_token_ids_native_python_parity(monkeypatch):
    """Out-of-int32 ids must wrap identically on both paths (cluster-wide
    hash stability)."""
    tokens = [2**31, -5, 2**40 + 3, 1] * 32
    a = hashing.prefix_block_hashes(tokens, 128)
    monkeypatch.setattr(hashing, "_load_native", lambda: None)
    b = hashing.prefix_block_hashes(tokens, 128)
    assert a == b


def test_empty_and_short():
    assert hashing.prefix_block_hashes([], 128) == []
    assert hashing.prefix_block_hashes([1, 2, 3], 128) == []


# One block given its predecessor's digest: what a caller that keeps the
# digests of a growing token list hashes when a block fills
# (runtime/kv_cache.py extend_digests).
_CHAIN_TOKENS = {
    "in_range": [(i * 2654435761) % 50000 for i in range(5 * 64 + 9)],
    "out_of_range": [2**31, -5, 2**40 + 3, 1, -2**31 - 1, 7] * 55,
}


@pytest.mark.parametrize("seed", [0, 7, 12345])
@pytest.mark.parametrize("ids", sorted(_CHAIN_TOKENS))
def test_chained_block_hash_block_by_block(ids, seed, monkeypatch):
    """The single-block hash, native and pure Python, walks the same chain
    as ``prefix_block_hashes`` over the whole list, digest for digest."""
    if not hashing.native_available():
        pytest.skip("native lib unavailable")
    tokens, bs = _CHAIN_TOKENS[ids], 64
    whole = hashing.prefix_block_hashes(tokens, bs, seed)
    assert len(whole) == len(tokens) // bs >= 5
    prev_n = prev_p = None
    for b, want in enumerate(whole):
        block = tokens[b * bs:(b + 1) * bs]
        prev_n = hashing.chained_block_hash(block, prev_n, seed)
        prev_p = hashing.chained_block_hash_py(block, prev_p, seed)
        assert prev_n == prev_p == want, b
    # with no library the bound name falls back to the same digests
    monkeypatch.setattr(hashing, "_load_native", lambda: None)
    assert hashing.chained_block_hash(tokens[bs:2 * bs], whole[0], seed) \
        == whole[1]


# The master hashes a prompt twice a request (the quarantine gate's
# whole-prompt digest, the router's block hashes): both pack the list
# ONCE in compiled code and wrap id by id only where an id lies outside
# int32, and both read a buffer ``pack_tokens`` already made. Every
# digest stays what the token-by-token reference gives.
_I32_MAX, _I32_MIN = 2**31 - 1, -2**31
_PROMPTS = {
    "empty": [],
    "one_partial_block": list(range(1, 100)),
    "exact_blocks": [(i * 2654435761) % 120000 for i in range(3 * 128)],
    "long_16150": [(i * 40503 + 17) % 151936 for i in range(16150)],
    "int32_edges": [_I32_MAX, _I32_MIN, 0, -1, _I32_MAX - 1,
                    _I32_MIN + 1, 5] * 40,
    "outside_int32": [2**31, -5, 2**40 + 3, 1, -2**31 - 1, 7,
                      2**70] * 40,
}
_WRAPS = {"outside_int32"}


def _reference(tokens, bs, seed):
    blocks, prev = [], None
    for b in range(len(tokens) // bs):
        prev = hashing.chained_block_hash_py(
            tokens[b * bs:(b + 1) * bs], prev, seed)
        blocks.append(prev)
    data = struct.pack(f"<{len(tokens)}i",
                       *[hashing._as_i32(t) for t in tokens])
    return blocks, hashing.murmur3_x64_128_py(data, seed).hex()


@pytest.mark.parametrize("native", ["native", "no_native"])
@pytest.mark.parametrize("given", ["list", "packed"])
@pytest.mark.parametrize("ids", sorted(_PROMPTS))
def test_prompt_hashes_equal_reference(ids, given, native, monkeypatch):
    tokens, bs, seed = _PROMPTS[ids], 128, 1234567
    want_blocks, want_digest = _reference(tokens, bs, seed)
    if native == "native":
        if not hashing.native_available():
            pytest.skip("native lib unavailable")
    else:
        monkeypatch.setattr(hashing, "_load_native", lambda: None)
    calls = []
    real = hashing._as_i32
    monkeypatch.setattr(hashing, "_as_i32",
                        lambda t: calls.append(t) or real(t))
    arg = tokens
    if given == "packed":
        arg, wrapped = hashing.pack_tokens(tokens)
        assert wrapped == (ids in _WRAPS)
        assert len(arg) == len(tokens)
        assert hashing.pack_tokens(arg) == (arg, False)  # no second pack
        del calls[:]    # from here on nothing converts: the buffer is read
    assert hashing.prefix_block_hashes(arg, bs, seed) == want_blocks
    assert hashing.prompt_digest(arg, seed) == want_digest
    if ids in _WRAPS and given == "list":
        assert calls            # the wrap ran, id by id
    else:
        # the regression this guards: one interpreted call a token
        assert calls == []
