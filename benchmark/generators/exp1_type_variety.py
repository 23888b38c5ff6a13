"""Upstream exp1 (TestDataGen6TypeVariety over data/test6_copybook.cob):
fixed-length records of every numeric codec and strings at irregular
offsets.

Frozen copy of cobrix_tpu.testing.generators.generate_exp1 as of PR 21
(1,493 B records of 195 fields in this port): the same draws in the same
order, so the same seed gives the same bytes. Beside the bytes it returns
what it drew, which the post-window check holds the decoded table to
without the program.
"""
import math

import numpy as np

from .ebcdic import encode_strings_column, sample_indices

# Each spec entry is one put call of the upstream generator IN ORDER:
# (name, pic, kind, params). The copybook text is emitted from this same
# table, so the generator layout and the parsed schema cannot drift apart.
#
# kinds:
#   id      - int32 big-endian record counter (putIntToArray)
#   str     - EBCDIC string, NUL-padded (putStringToArray)
#   disp    - DISPLAY digits (encodeUncompressed); params: digits, signed,
#             sep ('lead'/'trail'/None = overpunch), lead (overpunch/sign
#             position), dot (explicit decimal byte index), neg (uses the
#             per-record isNegative flag)
#   bin     - big-endian two's complement (encodeBinSigned/Unsigned
#             precision buckets: <=4 digits 2B, <=9 4B, <=18 8B, else
#             ceil((log2(10)*digits+1)/8) bytes)
#   bcd     - packed decimal (encodeBcd); params: digits, signed encoder
#             (sign nibble C/D) vs unsigned (F), neg
#   float/double - IEEE754 BE of digits[:5].digits[5:7] / digits[:10].digits[10:14]
_D = "disp"


def _exp1_spec():
    nums = [1, 2, 3, 4, 5, 8, 9, 10, 11, 17, 18, 19, 20, 37]
    decs = [("99V9", 3), ("99V99", 4), ("9(3)V99", 5), ("9(4)V9(4)", 8),
            ("9(5)V9(4)", 9), ("9(5)V9(5)", 10), ("9(15)V99", 17),
            ("9(16)V99", 18), ("9(17)V99", 19), ("9(18)V9(10)", 28)]
    spec = [("ID", "9(7)  BINARY", "id", {})]
    spec.append(("STRING-VAL", "X(10)", "str", {}))
    for i, d in enumerate(nums):
        spec.append((f"NUM-STR-INT{i + 1:02d}", f"9({d})", _D,
                     dict(digits=d)))
    for i, d in enumerate(nums[1:]):
        spec.append((f"NUM-STR-SINT{i + 2:02d}", f"S9({d})", _D,
                     dict(digits=d, signed=True, neg=True)))
    for i, (pic, d) in enumerate(decs):
        spec.append((f"NUM-STR-DEC{i + 1:02d}", pic, _D, dict(digits=d)))
    for i, (pic, d) in enumerate(decs):
        spec.append((f"NUM-STR-SDEC{i + 1:02d}", "S" + pic, _D,
                     dict(digits=d, signed=True, neg=True)))
    # explicit decimal point ('.' literally in the data)
    for i, (pic, d, dot) in enumerate([("S9(3).99", 5, 3), ("S9(4).9(4)", 8, 4),
                                       ("S9(5).9(4)", 9, 5),
                                       ("S9(5).9(5)", 10, 5)]):
        spec.append((f"NUM-STR-EDEC{i + 3:02d}", pic, _D,
                     dict(digits=d, signed=True, neg=True, dot=dot)))
    usages = ["COMP", "COMP", "COMP-0", "COMP-4", "COMP-5"] + ["BINARY"] * 9
    for i, (d, u) in enumerate(zip(nums, usages)):
        spec.append((f"NUM-BIN-INT{i + 1:02d}", f"9({d}) {u}", "bin",
                     dict(digits=d)))
    for i, d in enumerate(nums):
        u = "COMP" if i < 5 else "BINARY"
        spec.append((f"NUM-SBIN-SINT{i + 1:02d}", f"S9({d}) {u}", "bin",
                     dict(digits=d, neg=True)))
    for i, (pic, d) in enumerate(decs):
        spec.append((f"NUM-BIN-DEC{i + 1:02d}", f"{pic} COMP", "bin",
                     dict(digits=d)))
    for i, (pic, d) in enumerate(decs):
        spec.append((f"NUM-SBIN-DEC{i + 1:02d}", f"S{pic} COMP", "bin",
                     dict(digits=d, neg=True)))
    for i, d in enumerate(nums):
        spec.append((f"NUM-BCD-INT{i + 1:02d}", f"9({d}) COMP-3", "bcd",
                     dict(digits=d)))
    for i, d in enumerate(nums):
        spec.append((f"NUM-BCD-SINT{i + 1:02d}", f"S9({d}) COMP-3", "bcd",
                     dict(digits=d, signed=True, neg=True)))
    for i, (pic, d) in enumerate(decs):
        spec.append((f"NUM-BCD-DEC{i + 1:02d}", f"{pic} COMP-3", "bcd",
                     dict(digits=d)))
    for i, (pic, d) in enumerate(decs):
        spec.append((f"NUM-BCD-SDEC{i + 1:02d}", f"S{pic} COMP-3", "bcd",
                     dict(digits=d, signed=True, neg=True)))
    spec += [
        ("NUM-SL-STR-INT01", "S9(9) SIGN IS LEADING SEPARATE", _D,
         dict(digits=9, signed=True, neg=True, sep="lead")),
        ("NUM-SL-STR-DEC01", "99V99 SIGN IS LEADING SEPARATE CHARACTER", _D,
         dict(digits=4, signed=True, neg=True, sep="lead")),
        ("NUM-ST-STR-INT01", "S9(9) SIGN IS TRAILING SEPARATE", _D,
         dict(digits=9, signed=True, neg=True, sep="trail")),
        ("NUM-ST-STR-DEC01", "99V99 SIGN TRAILING SEPARATE", _D,
         dict(digits=4, signed=True, neg=True, sep="trail")),
        ("NUM-SLI-STR-DEC01", "SV9(7) SIGN LEADING", _D,
         dict(digits=7, signed=True, neg=True, lead=True)),
        ("NUM-STI-STR-DEC01", "SV9(7) SIGN TRAILING", _D,
         dict(digits=7, signed=True, neg=True)),
        ("NUM-SLI-DEBUG", "X(7)", _D,
         dict(digits=7, signed=True, neg=True, lead=True)),
        ("NUM-STI-DEBUG", "X(7)", _D, dict(digits=7, signed=True, neg=True)),
        ("FLOAT-01", "COMP-1", "float", {}),
        ("DOUBLE-01", "COMP-2", "double", {}),
        ("COMMON-8-BIN", "9(8) BINARY", "bin", dict(digits=8)),
        ("COMMON-S3-BIN", "S9(3) BINARY", "bin", dict(digits=3)),
        ("COMMON-S94COMP", "S9(04) COMP", "bin", dict(digits=4)),
        ("COMMON-S8-BIN", "S9(8) BINARY", "bin", dict(digits=8)),
        ("COMMON-DDC97-BIN", "S9V9(7) BINARY", "bin", dict(digits=8)),
        ("COMMON-97COMP3", "9(07) COMP-3", "bcd", dict(digits=7)),
        ("COMMON-915COMP3", "9(15) COMP-3", "bcd", dict(digits=15)),
        ("COMMON-S95COMP3", "S9(5) COMP-3", "bcd",
         dict(digits=5, signed=True, neg=True)),
        ("COMMON-S999DCCOMP3", "S9(09)V99 COMP-3", "bcd",
         dict(digits=11, signed=True, neg=True)),
        ("COMMON-S913COMP3", "S9(13) COMP-3", "bcd",
         dict(digits=13, signed=True, neg=True)),
        ("COMMON-S913DCCOMP3", "S9(13)V99 COMP-3", "bcd",
         dict(digits=15, signed=True, neg=True)),
        ("COMMON-S911DCC2", "S9(11)V99 COMP-3", "bcd",
         dict(digits=13, signed=True, neg=True)),
        ("COMMON-S910DCC3", "S9(10)V999 COMP-3", "bcd",
         dict(digits=13, signed=True, neg=True)),
        ("COMMON-S03DDC", "SV9(5) COMP-3", "bcd",
         dict(digits=5, signed=True, neg=True)),
        # U03DDC/UPC5DDC/UPI5DDC use the SIGNED encoder with a positive
        # value: sign nibble 0xC, never 0xF (generator lines 542-546)
        ("COMMON-U03DDC", "V9(5) COMP-3", "bcd", dict(digits=5, signed=True)),
        ("COMMON-UPC5DDC", "PPP9(5) COMP-3", "bcd",
         dict(digits=5, signed=True)),
        ("COMMON-SPC5DDC", "SPP99999 COMP-3", "bcd",
         dict(digits=5, signed=True, neg=True)),
        ("COMMON-UPI5DDC", "9(5)PPP COMP-3", "bcd",
         dict(digits=5, signed=True)),
        ("COMMON-SPI5DDC", "S99999PPP COMP-3", "bcd",
         dict(digits=5, signed=True, neg=True)),
        ("COMMON-UPC5DISP", "SPPP9(5)", _D,
         dict(digits=5, signed=True, neg=True)),
        ("COMMON-UPI5DISP", "S9(5)PPP", _D,
         dict(digits=5, signed=True, neg=True)),
        ("COMMON-UPC1BIN", "SPPP9 COMP", "bin", dict(digits=1)),
        ("COMMON-UPI1BIN", "S9PPP COMP", "bin", dict(digits=1)),
        ("COMMON-UPC3BIN", "SPPP9(3) COMP", "bin", dict(digits=3)),
        ("COMMON-UPI3BIN", "S9(3)PPP COMP", "bin", dict(digits=3)),
        ("COMMON-UPC5BIN", "SPPP9(5) COMP", "bin", dict(digits=5)),
        ("COMMON-UPI5BIN", "S9(5)PPP COMP", "bin", dict(digits=5)),
        ("COMMON-UPC10BIN", "SPPP9(10) COMP", "bin", dict(digits=10)),
        ("COMMON-UPI10BIN", "S9(10)PPP COMP", "bin", dict(digits=10)),
        ("EX-NUM-INT01", "+9(8)", _D,
         dict(digits=8, signed=True, neg=True, sep="lead")),
        ("EX-NUM-INT02", "9(8)+", _D,
         dict(digits=8, signed=True, neg=True, sep="trail")),
        ("EX-NUM-INT03", "-9(8)", _D,
         dict(digits=8, signed=True, neg=True, sep="lead")),
        ("EX-NUM-INT04", "Z(8)-", _D,
         dict(digits=8, signed=True, neg=True, sep="trail")),
        ("EX-NUM-DEC01", "+9(6)V99", _D,
         dict(digits=8, signed=True, neg=True, sep="lead")),
        ("EX-NUM-DEC02", "Z(6)VZZ-", _D,
         dict(digits=8, signed=True, neg=True, sep="trail")),
        ("EX-NUM-DEC03", "9(6).99-", _D,
         dict(digits=8, signed=True, neg=True, sep="trail", dot=6)),
    ]
    return spec


EXP1_SPEC = _exp1_spec()


def _bin_width(digits: int) -> int:
    """encodeBinSigned/Unsigned byte width: IBM precision buckets."""
    if digits <= 4:
        return 2
    if digits <= 9:
        return 4
    if digits <= 18:
        return 8
    return math.ceil((math.log2(10.0) * digits + 1) / 8)


def _exp1_width(kind: str, p: dict) -> int:
    if kind == "id":
        return 4
    if kind == "str":
        return 10
    if kind == "disp":
        return (p["digits"] + (1 if p.get("sep") else 0)
                + (1 if p.get("dot") is not None else 0))
    if kind == "bin":
        return _bin_width(p["digits"])
    if kind == "bcd":
        return p["digits"] // 2 + 1
    return {"float": 4, "double": 8}[kind]


def _exp1_copybook() -> str:
    lines = ["        01  RECORD."]
    for name, pic, _, _ in EXP1_SPEC:
        clause = "" if pic.startswith("COMP-") else "PIC "
        # clause on a continuation line: cols 72+ are comment area and the
        # longest SIGN clauses would spill past it on a single line
        lines.append(f"          10  {name}")
        lines.append(f"              {clause}{pic}.")
    return "\n".join(lines) + "\n"


COPYBOOK = _exp1_copybook()
RECORD_SIZE = sum(_exp1_width(k, p) for _, _, k, p in EXP1_SPEC)

_EXP1_NAMES = ["Jene", "Maya", "Starr", "Lynell", "Eliana", "Tyesha",
               "Beatrice", "Otelia", "Timika", "Wilbert", "Mindy", "Sunday",
               "Tyson", "Cliff", "Mabelle", "Verdie", "Sulema", "Alona",
               "Suk", "Deandra", "Doretha", "Cassey", "Janiece", "Deshawn",
               "Willis", "Carrie", "Gabriele", "Inge", "Edyth", "Estelle"]


def encode_bcd_digits(digits: np.ndarray, sign_nibbles: np.ndarray
                      ) -> np.ndarray:
    """[n, d] digit values + [n] sign nibbles -> [n, d//2+1] packed BCD
    laid out as encodeBcd (GeneratorTools.scala:410-437): nibble stream =
    [0-pad if d even] + digits + sign, packed high-first."""
    n, d = digits.shape
    width = d // 2 + 1
    stream = np.zeros((n, width * 2), dtype=np.uint8)
    pad = 1 if d % 2 == 0 else 0
    stream[:, pad:pad + d] = digits
    stream[:, pad + d] = sign_nibbles
    return (stream[:, 0::2] << 4) | stream[:, 1::2]


_POW10 = 10 ** np.arange(18, dtype=np.int64)[::-1]


def _digits_to_int64(digits: np.ndarray) -> np.ndarray:
    d = digits.shape[1]
    return digits.astype(np.int64) @ _POW10[-d:]


def encode_bin_digits(digits: np.ndarray, neg: np.ndarray) -> np.ndarray:
    """[n, d] digit values (+ neg mask) -> [n, w] big-endian two's
    complement, w per the encodeBinSigned/Unsigned precision buckets."""
    n, d = digits.shape
    w = _bin_width(d)
    out = np.zeros((n, w), dtype=np.uint8)
    if d <= 18:
        v = _digits_to_int64(digits)
        v = np.where(neg, -v, v)
        for b in range(w - 1, -1, -1):
            out[:, b] = (v & 0xFF).astype(np.uint8)
            v >>= 8
        return out
    # >18 digits: base-1e9 limbs, repeated divmod-256 to extract bytes
    # LSB-first (the vectorized equivalent of strToBigArray's BigInt path)
    n_limbs = -(-d // 9)
    limbs = np.zeros((n, n_limbs), dtype=np.int64)
    for j in range(n_limbs):
        hi = d - 9 * (n_limbs - j)
        chunk = digits[:, max(hi, 0):hi + 9]
        limbs[:, j] = _digits_to_int64(chunk)
    for b in range(w - 1, -1, -1):
        carry = np.zeros(n, dtype=np.int64)
        for j in range(n_limbs):
            cur = carry * 1_000_000_000 + limbs[:, j]
            limbs[:, j] = cur >> 8
            carry = cur & 0xFF
        out[:, b] = carry.astype(np.uint8)
    if neg.any():
        # two's complement of the magnitude: invert + ripple-add 1
        inv = 255 - out[neg]
        carry = np.ones(inv.shape[0], dtype=np.int64)
        for b in range(w - 1, -1, -1):
            s = inv[:, b].astype(np.int64) + carry
            inv[:, b] = (s & 0xFF).astype(np.uint8)
            carry = s >> 8
        out[neg] = inv
    return out


def _encode_exp1_disp(digits: np.ndarray, neg: np.ndarray, p: dict
                      ) -> np.ndarray:
    """DISPLAY plane of the exp1 generator (encodeUncompressed +
    putEncodedNumStrToArray placement, GeneratorTools.scala:245-332):
    overpunched sign unless sign-separate; optional literal '.' byte."""
    n, d = digits.shape
    body = 0xF0 + digits
    sep = p.get("sep")
    if p.get("signed") and not sep:
        pos = 0 if p.get("lead") else d - 1
        zone = np.where(neg, 0xD0, 0xC0).astype(np.uint8)
        body[:, pos] = zone + digits[:, pos]
    dot = p.get("dot")
    if dot is not None:
        body = np.concatenate(
            [body[:, :dot],
             np.full((n, 1), 0x4B, dtype=np.uint8),  # EBCDIC '.'
             body[:, dot:]], axis=1)
    if sep:
        sign_col = np.where(neg, 0x60, 0x4E).astype(  # EBCDIC '-' / '+'
            np.uint8)[:, None]
        order = [sign_col, body] if sep == "lead" else [body, sign_col]
        body = np.concatenate(order, axis=1)
    return body



def generate(num_records: int, seed: int):
    """(file bytes, facts): `num_records` records from `seed`. Each record
    draws one 56-digit number (7 draws of 8 digits), a name and a sign
    flag; every numeric field encodes a digit prefix of that number in its
    own representation."""
    rng = np.random.default_rng(seed)
    n = num_records
    nums = rng.integers(10_000_000, 100_000_000, size=(n, 7))
    digits56 = np.zeros((n, 56), dtype=np.uint8)
    for j in range(7):
        v = nums[:, j].copy()
        for pos in range(7, -1, -1):
            digits56[:, j * 8 + pos] = v % 10
            v //= 10
    neg = rng.integers(0, 2, size=n).astype(bool)
    neg[0] = True  # the upstream generator forces record 0 negative
    names = np.asarray(_EXP1_NAMES)[rng.integers(0, len(_EXP1_NAMES), n)]

    parts = []
    for _name, _pic, kind, p in EXP1_SPEC:
        if kind == "id":
            ids = np.arange(1, n + 1, dtype=">i4")
            parts.append(ids.view(np.uint8).reshape(n, 4))
            continue
        if kind == "str":
            parts.append(encode_strings_column(list(names), 10, pad=0x00))
            continue
        if kind == "float":
            v = (_digits_to_int64(digits56[:, :7]) / 100.0)
            v = np.where(neg, -v, v).astype(">f4")
            parts.append(v.view(np.uint8).reshape(n, 4))
            continue
        if kind == "double":
            v = _digits_to_int64(digits56[:, :14]) / 10_000.0
            v = np.where(neg, -v, v).astype(">f8")
            parts.append(v.view(np.uint8).reshape(n, 8))
            continue
        d = p["digits"]
        fneg = neg if p.get("neg") else np.zeros(n, dtype=bool)
        pref = digits56[:, :d]
        if kind == "disp":
            parts.append(_encode_exp1_disp(pref, fneg, p))
        elif kind == "bin":
            parts.append(encode_bin_digits(pref, fneg))
        elif kind == "bcd":
            if p.get("signed"):
                sn = np.where(fneg, 0x0D, 0x0C).astype(np.uint8)
            else:
                sn = np.full(n, 0x0F, dtype=np.uint8)
            parts.append(encode_bcd_digits(pref, sn))
    out = np.concatenate(parts, axis=1)
    if out.shape[1] != RECORD_SIZE:
        raise RuntimeError(f"exp1 record is {out.shape[1]} B, "
                           f"not {RECORD_SIZE}")
    data = out.tobytes()
    # NUM-STR-INT06 is PIC 9(8): the first draw of 8 digits, as it stands
    facts = {"records": n, "bytes": len(data), "chunk_records": [n],
             "first8_sum": int(nums[:, 0].sum()),
             "negative_records": int(neg.sum())}
    return data, facts


def records_for(target_bytes: int) -> int:
    """Whole records that fit `target_bytes`: a 64 MiB chunk is then one
    read chunk of the program exactly, with no ragged tail."""
    return max(1, target_bytes // RECORD_SIZE)


def merge_facts(parts: list) -> dict:
    """Facts of a file made of several generated chunks, in order."""
    merged = {key: sum(p[key] for p in parts)
              for key in ("records", "bytes", "first8_sum",
                          "negative_records")}
    merged["chunk_records"] = [n for p in parts for n in p["chunk_records"]]
    return merged


def sample(path: str, out_path: str, size: int, seed: int) -> np.ndarray:
    """Copy a seeded sample of whole records into `out_path`; returns
    their record indices."""
    raw = np.memmap(path, dtype=np.uint8, mode="r").reshape(-1, RECORD_SIZE)
    idx = sample_indices(raw.shape[0], size, seed)
    with open(out_path, "wb") as f:
        f.write(raw[idx].tobytes())
    return idx


def check_table(table, facts: dict) -> list:
    """What this generator knows of the decoded table without the program:
    the rows, the record counter as it was written chunk by chunk, the sum
    of the first eight drawn digits, and how many records drew a negative
    sign. Returns the list of what does not hold."""
    import pyarrow.compute as pc

    wrong = []
    root = table.column("RECORD")
    if table.num_rows != facts["records"]:
        wrong.append(f"rows {table.num_rows} != {facts['records']} written")
        return wrong
    ids = pc.struct_field(root, ["ID"]).to_numpy()
    want = np.concatenate([np.arange(1, n + 1) for n in facts["chunk_records"]])
    if not np.array_equal(ids, want):
        wrong.append("the ID column is not the record counter as written")
    first8 = pc.sum(pc.cast(pc.struct_field(root, ["NUM_STR_INT06"]),
                            "int64")).as_py()
    if first8 != facts["first8_sum"]:
        wrong.append(f"sum(NUM_STR_INT06) {first8} != "
                     f"{facts['first8_sum']} drawn")
    negative = pc.sum(pc.less(pc.struct_field(root, ["NUM_STR_SINT02"]),
                              0)).as_py()
    if negative != facts["negative_records"]:
        wrong.append(f"{negative} negative NUM_STR_SINT02 != "
                     f"{facts['negative_records']} drawn")
    return wrong
