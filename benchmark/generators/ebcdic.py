"""EBCDIC encode helpers of the frozen input generators.

Copied from cobrix_tpu/testing/generators.py as of PR 21 and frozen here:
later PRs may change the program and may not change the yardstick. The
ASCII -> EBCDIC table is the inverse of the program's "common" code page,
written out so that nothing here imports the program
(tests/benchmark/test_benchmark_generators.py holds the copy byte-equal
to the original).
"""
import numpy as np

ENCODE_LUT = np.frombuffer(bytes.fromhex(
    "404040404040404040400d404025404040404040404040404040404040404040"
    "405a7f7b5b6c507d4d5d5c4e6b604b61f0f1f2f3f4f5f6f7f8f97a5e4c7e6e6f"
    "7cc1c2c3c4c5c6c7c8c9d1d2d3d4d5d6d7d8d9e2e3e4e5e6e7e8e9bae0bbb06d"
    "79818283848586878889919293949596979899a2a3a4a5a6a7a8a9c04fd0a140"),
    dtype=np.uint8)


def ebcdic_encode(text: str, length=None, pad: int = 0x00) -> bytes:
    """ASCII text to EBCDIC, padded to `length` with `pad` bytes."""
    raw = np.frombuffer(text.encode("ascii", "replace"), dtype=np.uint8)
    out = ENCODE_LUT[np.minimum(raw, 127)]
    if length is not None:
        padded = np.full(length, pad, dtype=np.uint8)
        padded[: min(len(out), length)] = out[:length]
        return padded.tobytes()
    return out.tobytes()


def encode_strings_column(values, width: int, pad: int = 0x00) -> np.ndarray:
    """[N] of str -> [N, width] EBCDIC uint8."""
    out = np.full((len(values), width), pad, dtype=np.uint8)
    for i, v in enumerate(values):
        enc = np.frombuffer(v.encode("ascii", "replace")[:width],
                            dtype=np.uint8)
        out[i, : len(enc)] = ENCODE_LUT[np.minimum(enc, 127)]
    return out


def encode_comp3_unsigned(values: np.ndarray, digits: int) -> np.ndarray:
    """[N] ints -> [N, digits//2+1] packed BCD with 0xF sign nibble."""
    width = digits // 2 + 1
    n = len(values)
    nibble_count = width * 2 - 1
    nibbles = np.zeros((n, nibble_count), dtype=np.uint8)
    v = values.astype(np.int64).copy()
    for pos in range(nibble_count - 1, -1, -1):
        nibbles[:, pos] = v % 10
        v //= 10
    out = np.zeros((n, width), dtype=np.uint8)
    for b in range(width):
        high = nibbles[:, b * 2]
        low = nibbles[:, b * 2 + 1] if b * 2 + 1 < nibble_count \
            else np.full(n, 0x0F, dtype=np.uint8)
        out[:, b] = (high << 4) | low
    out[:, -1] = (nibbles[:, -1] << 4) | 0x0F
    return out


def encode_comp_be(values: np.ndarray, width: int) -> np.ndarray:
    """[N] ints -> [N, width] big-endian binary."""
    out = np.zeros((len(values), width), dtype=np.uint8)
    v = values.astype(np.int64).copy()
    for b in range(width - 1, -1, -1):
        out[:, b] = v & 0xFF
        v >>= 8
    return out


def sample_indices(n: int, size: int, seed: int) -> np.ndarray:
    """A seeded, sorted sample of `size` record indices out of `n`."""
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(n, size=min(size, n), replace=False))
