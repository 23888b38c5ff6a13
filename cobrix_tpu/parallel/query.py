"""Device-resident query path: decode + aggregate in ONE XLA program.

A full decode returns more bytes to the host than it took in (values
widen to int32/int64 with a validity byte each), so a pipeline that
pulls every decoded column back pays the link twice. When the consumer
reduces, consume the columns ON the device — decode and reduce inside
one jitted program — and transfer only the reduced results. This is the
production shape of the reference's mainframe->Parquet->SQL-aggregate
pipelines (the Spark stage after the Cobrix scan), collapsed into the
scan itself.

Two programs share `DeviceAggregator`, its byte projection (`select`:
the device is sent and decodes only the fields the query touches) and
its `put` / `submit` / `fetch`:

**The query** (`bind_query`, what ``query.dataset(...).aggregate()``
runs on a device backend, chunk by chunk through `QueryRun`): specs of
stats/aggregate.parse_specs (count, min, max, sum, avg, sums of
products), a query/expr.py predicate with SQL/Kleene nulls, and
`group_by`. It is EXACT, in integer arithmetic. Operands are fixed-scale
COMP / COMP-3 / DISPLAY leaves of at most 18 digits outside OCCURS;
their unscaled mantissas multiply in int64, each row's product is split
into two 32-bit limbs that sum apart (no sum can wrap under 2^31 rows a
launch), and the host puts the limbs together in Python integers. That a
row's product itself fits 63 bits is PROVEN per chunk from what the
program observes, the largest magnitude of each factor among the
chunk's rows: a chunk it cannot prove is answered by the host kernels in
Python integers (`query.fallback`) and counted, never approximated.
Groups: rows are keyed by the RAW bytes of their key fields
(`KEY_BYTES_MAX` in all); the program finds the chunk's `GROUPS_MAX`
smallest keys that pass the predicate by repeated minimum (no scatter,
sort or scan) and reduces each under its mask; a chunk with more keys
goes to the host, counted. The host decodes each raw key with the
scalar oracle's decoder and merges the keys that decode alike. Whatever
does not bind (`NotOnDevice`: floats, wide or dynamic-scale numerics,
OCCURS, string predicates, wider keys) is the caller's to decode.

**The column summary** (`columns=`, `aggregate(matrix)`: sum, count, min
and max of every numeric field, OCCURS slots together; with a sharded
mesh GSPMD inserts the cross-chip psum): accumulates in float64, which
the TPU emulates and which rounds past 2^53, so it is a summary, not an
answer to hold digits to; no product entry point calls it.
"""
from __future__ import annotations

import decimal
import threading
from collections import OrderedDict
from dataclasses import dataclass, field as dc_field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..copybook.ast import Group
from ..copybook.copybook import Copybook
from ..plan.compiler import Codec
from ..profiling import Stage, annotate
from ..query.expr import And, Comparison, Expr, IsIn, Not, Or
from ..query.pushdown import _inside_array
from ..reader.columnar import (_FLOAT_CODECS, _NUMERIC_CODECS,
                               ColumnarDecoder, _dyn_scale,
                               fixed_point_exponent)
from ..stats.aggregate import (AggSpec, Factor, average, key_order,
                               scaled, shape_result)
from .mesh import batch_sharding, data_mesh, pad_batch_to_multiple
from .sharded import ShardedColumnarDecoder


class DeviceAggregator:
    """Decode + reduce on device; only scalars cross the host link.

    `columns`: field names to summarise (numeric fields only; OCCURS
    elements of a field aggregate together). None = every numeric field in
    the plan. `query`: a `BoundQuery` instead, whose fields are the
    columns: the program is then the exact query (module docstring) and
    `start()` gives the run that takes a file's chunks. The decode is
    automatically projected to the fields either reads.
    """

    def __init__(self, copybook: Copybook,
                 columns: Optional[Sequence[str]] = None,
                 active_segment: Optional[str] = None,
                 mesh=None, pack_bytes: bool = True,
                 backend: Optional[str] = None,
                 query: Optional["BoundQuery"] = None):
        self.query = query
        if query is not None:
            columns = query.select
            # one program on one device: the chunk loop's launches are a
            # read's (ColumnarDecoder._submit_block)
            mesh = mesh if mesh is not None else data_mesh(n_devices=1)
            if mesh.devices.size != 1:
                raise ValueError("a device query runs on one device")
        self.decoder = ShardedColumnarDecoder(
            copybook, mesh=mesh, active_segment=active_segment,
            select=columns, backend=backend)
        # byte width a [n, extent] record matrix must have BEFORE byte
        # projection (plan.max_extent shrinks when projection remaps)
        self.record_extent = self.decoder.plan.max_extent
        self.gather_index: Optional[np.ndarray] = None
        # the byte ranges of a record the projection keeps, in order
        self.spans: Optional[List[Tuple[int, int]]] = None
        if pack_bytes:
            self._build_byte_projection()
        self._agg_fn = None
        # field name -> [(group index, positions within the group)]; one
        # entry PER GROUP, not per column — the traced program reduces a
        # whole [batch, positions] plane at once, so an OCCURS 2000 field
        # adds a handful of HLO reductions instead of 2000 scalar chains
        per_field: Dict[str, Dict[int, List[int]]] = {}
        for gi, g in enumerate(self.decoder.kernel_groups):
            if g.codec not in _NUMERIC_CODECS and g.codec not in _FLOAT_CODECS:
                continue
            for pos, c in enumerate(g.columns):
                per_field.setdefault(c.name, {}).setdefault(gi, []).append(pos)
        self.fields = {name: [(gi, tuple(ps)) for gi, ps in by_group.items()]
                       for name, by_group in per_field.items()}

    def _build_byte_projection(self):
        """Host-side byte projection: rewrite the plan's column offsets
        into a compacted layout covering only the byte ranges the query
        reads, so `put` transfers just those bytes: H2D bytes shrink by
        the projection ratio — the physical payoff of `select`
        (plan/compiler.py) that the reference's prune-free scan cannot
        express (CobolScanners.scala:38-55)."""
        import bisect

        cols = self.decoder.plan.columns
        if not cols:
            return
        full_extent = self.record_extent
        ranges = sorted({(c.offset, c.width) for c in cols})
        merged: List[List[int]] = []
        for o, w in ranges:
            if merged and o <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], o + w)
            else:
                merged.append([o, o + w])
        total = sum(e - s for s, e in merged)
        if total >= full_extent * 0.9:
            return  # dense plan: the gather would cost more than it saves
        starts = [s for s, _ in merged]
        packed_start = {}
        pos = 0
        for s, e in merged:
            packed_start[s] = pos
            pos += e - s
        for c in cols:
            j = bisect.bisect_right(starts, c.offset) - 1
            s, _e = merged[j]
            c.offset = packed_start[s] + (c.offset - s)
        self.decoder.rebuild_groups()
        self.spans = [(s, e) for s, e in merged]
        self.gather_index = np.concatenate(
            [np.arange(s, e, dtype=np.int64) for s, e in merged])

    @property
    def mesh(self):
        return self.decoder.mesh

    def _build(self):
        import jax.numpy as jnp
        from jax import lax

        from ..ops.device import DeviceProgram

        if self.query is not None:
            return self._build_query()
        decode_all = self.decoder.build_jax_decode_fn(mesh=self.mesh)
        groups = self.decoder.kernel_groups
        fields = self.fields

        def agg(data, n):
            outs = decode_all(data)
            # padded rows are all-zero bytes, which decode as VALID zeros
            # for the binary/float codecs — mask them out of every reduction
            # (the normal decode path slices [:n] host-side; an aggregate
            # has no post-hoc slice, so the mask must live in the program)
            row_live = jnp.arange(data.shape[0], dtype=jnp.int32) < n
            res = {}
            for name, slots in fields.items():
                total = jnp.zeros((), dtype=jnp.float64)
                count = jnp.zeros((), dtype=jnp.int32)
                vmin = jnp.asarray(jnp.inf, dtype=jnp.float64)
                vmax = jnp.asarray(-jnp.inf, dtype=jnp.float64)
                for gi, poss in slots:
                    g = groups[gi]
                    planes = decode_all.group_planes(outs, gi)
                    if len(poss) == len(g.columns):
                        sel = slice(None)  # whole group: skip the gather
                    else:
                        sel = jnp.asarray(poss)
                    spec = g.columns[poss[0]]
                    is_display = g.codec in (Codec.DISPLAY_NUM,
                                             Codec.DISPLAY_NUM_ASCII)
                    if g.wide:
                        # uint128-limb plane: the f64 approximation
                        hi, lo = planes.hi[:, sel], planes.values[:, sel]
                        mag = (hi.astype(jnp.float64) * jnp.float64(2.0 ** 64)
                               + lo.astype(jnp.float64))
                        v64 = jnp.where(planes.negative[:, sel], -mag, mag)
                        valid = planes.valid[:, sel] & row_live[:, None]
                        if is_display and (spec.params.explicit_decimal
                                           or _dyn_scale(spec)):
                            dots = planes.dots[:, sel].astype(jnp.float64)
                            v64 = v64 * jnp.power(jnp.float64(10.0), -dots)
                        elif _dyn_scale(spec):
                            # wide binary PIC P: exact digit count from the
                            # integer limbs, not the rounded f64 value
                            v64 = v64 * _dyn_pow10_limbs(
                                hi, lo, spec.params.scale_factor, jnp)
                        else:
                            e = fixed_point_exponent(spec)
                            if e:
                                v64 = v64 * (10.0 ** e)
                    else:
                        values = planes.values[:, sel]
                        valid = planes.valid[:, sel] & row_live[:, None]
                        if g.codec in (Codec.DOUBLE_IBM, Codec.DOUBLE_IEEE):
                            # device carries IEEE754 bit patterns (uint64);
                            # on TPU a device-side bitcast + reduction runs
                            # through the f64 emulation and may drift a last
                            # ULP from the host-decoded values — acceptable
                            # for float aggregates, which round by
                            # construction; the DECODE path keeps
                            # bit-exactness by shipping patterns to the host
                            values = lax.bitcast_convert_type(values,
                                                              jnp.float64)
                        v64 = values.astype(jnp.float64)
                        # integer outputs are unscaled mantissas; apply the
                        # decimal scale so aggregates are in field units
                        # (the row path does this at materialization via
                        # Decimal). All slots of one field share one
                        # ColumnSpec dtype, so the exponent rule is uniform
                        # across the plane.
                        if is_display and (spec.params.explicit_decimal
                                           or _dyn_scale(spec)):
                            # per-value exponent plane ('.' position or the
                            # PIC P digit count)
                            dots = planes.dots[:, sel].astype(jnp.float64)
                            v64 = v64 * jnp.power(jnp.float64(10.0), -dots)
                        elif _dyn_scale(spec):
                            # narrow binary PIC P: exact digit count from
                            # the integer values, not the rounded f64
                            v64 = v64 * _dyn_pow10_int(
                                values, spec.params.scale_factor, jnp)
                        elif g.codec in (Codec.BINARY, Codec.BCD,
                                         Codec.DISPLAY_NUM,
                                         Codec.DISPLAY_NUM_ASCII):
                            e = fixed_point_exponent(spec)
                            if e:
                                v64 = v64 * (10.0 ** e)
                    total = total + jnp.where(valid, v64, 0.0).sum(
                        dtype=jnp.float64)
                    count = count + valid.sum(dtype=jnp.int32)
                    vmin = jnp.minimum(
                        vmin, jnp.where(valid, v64, jnp.inf).min())
                    vmax = jnp.maximum(
                        vmax, jnp.where(valid, v64, -jnp.inf).max())
                res[name] = {"sum": total, "count": count,
                             "min": vmin, "max": vmax}
            res["records"] = n
            return res

        sharding = batch_sharding(self.mesh)
        return DeviceProgram(agg, interpreted=decode_all.interpret,
                             device_groups=decode_all.device_groups,
                             in_shardings=(sharding, None))

    def _operand_slots(self) -> Dict[str, Tuple[int, int]]:
        """{operand name: (kernel group, position in it)} of the query's
        operands in this decoder's plan; NotOnDevice where the plan
        decodes one in a way the integer program does not read."""
        slots = {}
        for name, operand in self.query.operands.items():
            found = [(gi, pos)
                     for gi, g in enumerate(self.decoder.kernel_groups)
                     for pos, c in enumerate(g.columns)
                     if c.statement is operand.statement]
            if len(found) != 1:
                raise NotOnDevice(f"{name}: {len(found)} plan columns")
            g = self.decoder.kernel_groups[found[0][0]]
            c = g.columns[found[0][1]]
            if (g.codec not in _NUMERIC_CODECS or g.wide or _dyn_scale(c)
                    or c.params.explicit_decimal or c.gates
                    or fixed_point_exponent(c) != -operand.scale):
                raise NotOnDevice(f"{name}: not a fixed-scale numeric of "
                                  "at most 18 digits")
            slots[name] = found[0]
        return slots

    def _key_bytes(self) -> List[int]:
        """Where the key fields' raw bytes lie in the packed layout, in
        key order."""
        at = []
        for st in self.query.key_statements:
            (c,) = [c for c in self.decoder.plan.columns
                    if c.statement is st]
            at.extend(range(c.offset, c.offset + c.width))
        return at

    def _build_query(self):
        """The exact query as one program: decode, predicate, groups,
        integer reductions (module docstring). Arguments: the packed
        [bucket, extent] uint8 block and its real rows; result: the
        groups' partials, a few hundred bytes."""
        import jax
        import jax.numpy as jnp

        from ..ops.device import DeviceProgram

        query = self.query
        decode_all = self.decoder.build_jax_decode_fn(mesh=self.mesh)
        groups = self.decoder.kernel_groups
        slots = self._operand_slots()
        key_bytes = self._key_bytes()
        k_max = GROUPS_MAX if key_bytes else 1
        absent = jnp.int32(_ABSENT)

        def run(data, n):
            outs = decode_all(data)
            # padded rows are all-zero bytes, which decode as VALID zeros
            # for the binary codecs: no post-hoc slice here, so the mask
            # lives in the program
            live = jnp.arange(data.shape[0], dtype=jnp.int32) < n
            col = {}
            for name, (gi, pos) in slots.items():
                planes = decode_all.group_planes(outs, gi)
                col[name] = (planes.values[:, pos].astype(jnp.int64),
                             planes.valid[:, pos])
            with jax.named_scope("cobrix.filter"):
                keep = live
                if query.filter is not None:
                    keep = live & _eval_filter(jnp, query, query.filter,
                                               col)[0]
            with jax.named_scope("cobrix.reduce"):
                gid = jnp.zeros(data.shape[0], dtype=jnp.int32)
                for at in key_bytes:
                    gid = gid * 256 + data[:, at].astype(jnp.int32)
                gid = jnp.where(keep, gid, absent)
                # the k_max smallest keys present, ascending: each the
                # least key above the one before (`absent` once none is)
                found, last = [], jnp.int32(-1)
                for _ in range(k_max):
                    last = jnp.min(jnp.where(gid > last, gid, absent))
                    found.append(last)
                cand = jnp.stack(found)
                more = jnp.any((gid > last) & (gid < absent))
                # [groups, rows]: the batch axis stays in the lanes
                member = ((cand[:, None] == gid[None, :])
                          & (cand[:, None] < absent))
                result = {"cand": cand, "more": more,
                          "rows": member.sum(axis=1, dtype=jnp.int32),
                          "terms": [], "fmax": {}}
                for term in query.terms:
                    value, ok, factors = _term_rows(jnp, query, term, col)
                    for text, rows in factors.items():
                        result["fmax"][text] = jnp.max(jnp.where(
                            live & ok, jnp.abs(rows), 0))
                    m = member & ok[None, :]
                    part = {"n": m.sum(axis=1, dtype=jnp.int32)}
                    if term.fn == "sum":
                        # two 32-bit limbs that sum apart: no sum of
                        # fewer than 2^31 rows can wrap
                        part["lo"] = jnp.where(
                            m, (value & _LIMB_MASK)[None, :], 0).sum(axis=1)
                        part["hi"] = jnp.where(
                            m, (value >> 32)[None, :], 0).sum(axis=1)
                    elif term.fn == "min":
                        part["v"] = jnp.where(m, value[None, :],
                                              _INT64_MAX).min(axis=1)
                    else:
                        part["v"] = jnp.where(m, value[None, :],
                                              -_INT64_MAX).max(axis=1)
                    result["terms"].append(part)
                return result

        return DeviceProgram(run, interpreted=decode_all.interpret,
                             device_groups=decode_all.device_groups)

    def start(self, stats=None) -> "QueryRun":
        """A run of the query over one or more files' chunks; `stats` is
        the read's DeviceStats."""
        return QueryRun(self, stats)

    def device_program(self):
        """The decode+reduce as an ops.device.DeviceProgram, built once."""
        if self._agg_fn is None:
            self._agg_fn = self._build()
        return self._agg_fn

    def pack(self, matrix: np.ndarray) -> np.ndarray:
        """The launch buffer of a [n, record] matrix: the bytes the
        projected plan reads, each kept range one strided copy, in a
        bucket of rows (`_bucket_size`) zeroed past the last."""
        n = matrix.shape[0]
        with Stage("pack"):
            block = np.empty((self.decoder._bucket_size(n),
                              self.decoder.plan.max_extent), dtype=np.uint8)
            at = 0
            for s, e in self.spans or [(0, block.shape[1])]:
                block[:n, at:at + e - s] = matrix[:, s:e]
                at += e - s
            block[n:] = 0
        return block

    def put(self, arr: np.ndarray, block: Optional[int] = None):
        """Pad `arr` ([n, record_extent] uint8), byte-project it to the
        query's packed layout, and transfer it H2D with the mesh
        sharding. Returns (device_array, n). `block`: pad to this fixed
        batch so a streaming loop reuses one compiled program."""
        import jax

        if (self.gather_index is not None
                and arr.shape[1] > len(self.gather_index)):
            # ship only the bytes the projected plan reads
            arr = np.ascontiguousarray(arr[:, self.gather_index])
        n = arr.shape[0]
        nd = self.decoder.n_devices
        if block is not None:
            # round up so the padded batch stays shardable over the mesh
            multiple = -(-block // nd) * nd
        else:
            multiple = self.decoder._mesh_bucket(n)
        padded = pad_batch_to_multiple(arr, multiple)
        return jax.device_put(padded, batch_sharding(self.mesh)), n

    def submit(self, x, n):
        """Dispatch the aggregate program on a device-resident padded batch
        (from `put`) WITHOUT synchronizing — returns the device-side scalar
        tree. A streaming loop that submits every block before fetching
        lets the runtime overlap H2D transfers with compute. `n` may be a
        host int or a device scalar — on-HBM pipelines pass the framing
        program's live-record count without syncing it to the host."""
        count = np.int32(n) if isinstance(n, (int, np.integer)) else n
        with annotate("cobrix_device_aggregate"):
            return self.device_program()(x, count)

    def fetch(self, tree) -> Dict[str, dict]:
        """Transfer a submitted scalar tree to host and shape the result.
        This is the ONLY D2H transfer and the synchronization point."""
        import jax

        # ONE D2H transfer for the whole stat tree — per-scalar float()/
        # int() would wait on the device once for each
        out = jax.device_get(tree)
        result: Dict[str, dict] = {}
        for name, stats in out.items():
            if name == "records":
                continue
            count = int(stats["count"])
            result[name] = {
                "sum": float(stats["sum"]) if count else None,
                "count": count,
                "min": float(stats["min"]) if count else None,
                "max": float(stats["max"]) if count else None,
            }
        return result

    def aggregate_device(self, x, n: int) -> Dict[str, dict]:
        """Aggregate an already-device-resident padded batch (from `put`).
        Wall-clocking this call times dispatch + decode + reduce + scalar
        fetch."""
        return self.fetch(self.submit(x, n))

    def aggregate(self, arr: np.ndarray) -> Dict[str, dict]:
        """arr: [batch, extent] uint8. Returns per-field scalar aggregates;
        the only D2H traffic is these scalars. Fields with zero valid
        values report sum/min/max as None (never +-inf)."""
        x, n = self.put(arr)
        return self.aggregate_device(x, n)


def _dyn_pow10_int(values, sf: int, jnp):
    """10^-(|sf| + decimal digit count of |value|) for narrow binary PIC P
    aggregation — the exact integer digit count (a rounded f64 compare
    would miscount at 10^k boundaries), traced in-program through the same
    helper the row path uses (columnar._digit_count)."""
    from ..reader.columnar import _digit_count

    nd = _digit_count(values, xp=jnp)
    return jnp.power(jnp.float64(10.0),
                     -(nd.astype(jnp.float64) + jnp.float64(-sf)))


def _dyn_pow10_limbs(hi, lo, sf: int, jnp):
    """Same for wide binary PIC P: exact digit count from the uint128
    magnitude limbs (columnar._digit_count_limbs, traced)."""
    from ..reader.columnar import _digit_count_limbs

    nd = _digit_count_limbs(hi, lo, xp=jnp)
    return jnp.power(jnp.float64(10.0),
                     -(nd.astype(jnp.float64) + jnp.float64(-sf)))


def merge_aggregates(parts: Sequence[Dict[str, dict]]) -> Dict[str, dict]:
    """Combine per-block partial aggregates from a streaming loop (the
    host-side DCN-style reduction: scalars only, SURVEY.md §2.5)."""
    result: Dict[str, dict] = {}
    for part in parts:
        for name, s in part.items():
            if name not in result:
                result[name] = dict(s)
                continue
            r = result[name]
            r["count"] += s["count"]
            if s["sum"] is not None:
                r["sum"] = s["sum"] + (r["sum"] or 0.0)
            if s["min"] is not None:
                r["min"] = s["min"] if r["min"] is None \
                    else min(r["min"], s["min"])
            if s["max"] is not None:
                r["max"] = s["max"] if r["max"] is None \
                    else max(r["max"], s["max"])
    return result


def aggregate_file(copybook: Copybook, data, columns=None, mesh=None
                   ) -> Dict[str, dict]:
    """One-shot helper over a fixed-length byte image."""
    agg = DeviceAggregator(copybook, columns=columns, mesh=mesh)
    rs = agg.record_extent
    arr = np.frombuffer(data, dtype=np.uint8)
    n = arr.size // copybook.record_size
    arr = arr[:n * copybook.record_size].reshape(n, copybook.record_size)
    return agg.aggregate(np.ascontiguousarray(arr[:, :rs]))


# -- the exact query --------------------------------------------------------

# the raw bytes of all key fields together, and the distinct raw keys one
# chunk's program reduces: a chunk with more is the host's
KEY_BYTES_MAX = 2
GROUPS_MAX = 16
# rows a launch: a read chunk of narrower records goes in several (the
# [groups, rows] masks stay tens of MB, a limb's sum far inside 63 bits)
LAUNCH_ROWS_MAX = 2 ** 20
_ABSENT = 2 ** 31 - 1
_INT64_MAX = 2 ** 63 - 1
_LIMB_MASK = 0xFFFFFFFF
# a literal scaled to a field's units must compare in int64
_LITERAL_MAX = 2 ** 62


class NotOnDevice(Exception):
    """The query does not bind to the exact device program: the caller
    decodes (query/dataset._aggregate_by_decode)."""


@dataclass(frozen=True)
class Operand:
    """A numeric field the program reads: its statement, the decimal
    scale of its unscaled mantissa, and whether its Arrow column is an
    integer (then sums of it are Python ints, else Decimals)."""

    statement: object
    scale: int
    integer: bool


@dataclass(frozen=True)
class Term:
    """One reduction the program makes: `fn` sum, min or max over the
    product of `factors`. An avg shares its sum's."""

    fn: str
    factors: Tuple[Factor, ...]


@dataclass
class BoundQuery:
    """Specs, predicate and keys bound to a copybook for the device."""

    copybook: Copybook
    specs: List[AggSpec]
    filter: Optional[Expr]
    keys: List[str]
    key_statements: list
    key_types: list
    operands: Dict[str, Operand]
    terms: List[Term] = dc_field(default_factory=list)

    def __post_init__(self):
        for spec in self.specs:
            term = self.term_of(spec)
            if term is not None and term not in self.terms:
                self.terms.append(term)

    @staticmethod
    def term_of(spec: AggSpec) -> Optional[Term]:
        if spec.fn == "count":
            return None
        return Term("sum" if spec.fn == "avg" else spec.fn, spec.factors)

    @property
    def select(self) -> Tuple[str, ...]:
        """The statements the decode plan keeps: operands and keys."""
        names = [o.statement.name for o in self.operands.values()]
        names += [st.name for st in self.key_statements]
        return tuple(sorted(set(names)))

    def scale_of(self, factors) -> int:
        return sum(self.operands[f.field].scale for f in factors)

    def fingerprint(self) -> tuple:
        """What one compiled program answers: (copybook, specs, filter,
        keys)."""
        return (id(self.copybook), tuple(s.text for s in self.specs),
                self.filter.canonical() if self.filter is not None else "",
                tuple(self.keys))


def bind_query(copybook: Copybook, specs: Sequence[AggSpec],
               filter_: Optional[Expr], keys: Sequence[str],
               schema) -> BoundQuery:
    """Bind to `copybook` what `dataset(...).aggregate(specs, filter_,
    keys)` asks, or raise NotOnDevice. Every field is a primitive outside
    OCCURS and a top-level column of `schema` (the dataset's Arrow
    schema, whose names the caller uses); operands of aggregates and of
    the predicate are integers or decimals there; literals are numbers
    (or null with == / !=), compared exactly at the field's scale; the
    keys' raw bytes number at most KEY_BYTES_MAX."""
    import pyarrow as pa

    top_level = set(schema.names)

    def statement(name):
        if name not in top_level:
            raise NotOnDevice(f"{name}: not a top-level column")
        try:
            st = copybook.get_field_by_name(name)
        except ValueError as exc:
            raise NotOnDevice(str(exc)) from exc
        if isinstance(st, Group) or _inside_array(st):
            raise NotOnDevice(f"{name}: a group or inside OCCURS")
        return st

    operands: Dict[str, Operand] = {}

    def operand(name):
        if name not in operands:
            st = statement(name)
            kind = schema.field(name).type
            if pa.types.is_integer(kind):
                operands[name] = Operand(st, 0, True)
            elif pa.types.is_decimal(kind) and kind.scale >= 0:
                operands[name] = Operand(st, kind.scale, False)
            else:
                raise NotOnDevice(f"{name}: {kind} is no exact numeric")
        return operands[name]

    def literal(name, value):
        if value is None:
            return
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            raise NotOnDevice(f"{name}: literal {value!r} is no number")
        try:
            low, _exact = _scaled_literal(value, operand(name).scale)
        except (decimal.InvalidOperation, ValueError, OverflowError) as exc:
            raise NotOnDevice(f"{name}: literal {value!r}") from exc
        if abs(low) >= _LITERAL_MAX:
            raise NotOnDevice(f"{name}: literal {value!r} out of range")

    def walk(expr):
        if isinstance(expr, Comparison):
            operand(expr.field)
            literal(expr.field, expr.value)
        elif isinstance(expr, IsIn):
            operand(expr.field)
            for value in expr.values:
                literal(expr.field, value)
        elif isinstance(expr, (And, Or)):
            for arg in expr.args:
                walk(arg)
        elif isinstance(expr, Not):
            walk(expr.arg)
        else:
            raise NotOnDevice(f"filter node {expr!r}")

    for spec in specs:
        for name in spec.fields:
            operand(name)
    if filter_ is not None:
        walk(filter_)
    key_statements = [statement(name) for name in keys]
    if len(set(keys)) != len(keys):
        raise NotOnDevice("a key named twice")
    if sum(st.binary_properties.data_size
           for st in key_statements) > KEY_BYTES_MAX:
        raise NotOnDevice(f"keys wider than {KEY_BYTES_MAX} bytes")
    key_types = [schema.field(name).type for name in keys]
    if any(pa.types.is_floating(t) for t in key_types):
        raise NotOnDevice("a float key")
    return BoundQuery(copybook, list(specs), filter_, list(keys),
                      key_statements, key_types, operands)


def _scaled_literal(value, scale: int) -> Tuple[int, bool]:
    """A filter literal in a field's units of 10^-scale: (the greatest
    integer not above it, whether it is that integer). 0.05 against a
    V99 field is exactly 5: the literal is read as the decimal it was
    written as, never as the binary float beside it."""
    scaled = decimal.Decimal(str(value)).scaleb(scale)
    low = int(scaled.to_integral_value(rounding=decimal.ROUND_FLOOR))
    return low, scaled == low


def _compare(xp, op: str, values, low: int, exact: bool):
    if exact:
        return {"==": values == low, "!=": values != low,
                "<": values < low, "<=": values <= low,
                ">": values > low, ">=": values >= low}[op]
    # between two of the field's units: never equal, and above it from
    # the next unit on
    if op in ("==", "!="):
        return xp.full(values.shape, op == "!=")
    return values <= low if op in ("<", "<=") else values > low


def _eval_filter(xp, query: BoundQuery, expr: Expr, col):
    """(rows where `expr` is true, rows where it is false) over the
    decoded `col` {name: (int64 values, valid)}; a row in neither is
    null (query/expr.py: SQL/Kleene), and the caller drops it."""
    if isinstance(expr, Comparison):
        values, valid = col[expr.field]
        if expr.value is None:
            null = ~valid
            return (null, valid) if expr.op == "==" else (valid, null)
        hit = _compare(xp, expr.op, values, *_scaled_literal(
            expr.value, query.operands[expr.field].scale))
        return valid & hit, valid & ~hit
    if isinstance(expr, IsIn):
        # pyarrow's is_in: a null is in no set, and that is false
        values, valid = col[expr.field]
        hit = xp.zeros(values.shape, dtype=bool)
        for value in expr.values:
            hit = hit | _compare(xp, "==", values, *_scaled_literal(
                value, query.operands[expr.field].scale))
        hit = valid & hit
        return hit, ~hit
    if isinstance(expr, Not):
        true, false = _eval_filter(xp, query, expr.arg, col)
        return false, true
    parts = [_eval_filter(xp, query, arg, col) for arg in expr.args]
    true, false = parts[0]
    for t, f in parts[1:]:
        if isinstance(expr, And):
            true, false = true & t, false | f
        else:
            true, false = true | t, false & f
    return true, false


def _term_rows(xp, query: BoundQuery, term: Term, col):
    """(each row's product of the term's factors, where every factor is
    valid, {factor text: its rows}) in the unscaled integers of `col`."""
    product, ok, factors = None, None, {}
    for factor in term.factors:
        values, valid = col[factor.field]
        if factor.sign:
            one = 10 ** query.operands[factor.field].scale
            values = one + values if factor.sign > 0 else one - values
        factors[str(factor)] = values
        product = values if product is None else product * values
        ok = valid if ok is None else ok & valid
    return product, ok, factors


class _GroupPartial:
    """One group's accumulators, in Python integers: rows, and per term
    [sum or extreme (None over no values), values counted]."""

    __slots__ = ("rows", "terms")

    def __init__(self, n_terms: int):
        self.rows = 0
        self.terms = [[None, 0] for _ in range(n_terms)]

    def add(self, fn: str, i: int, value, n: int) -> None:
        if not n:
            return
        held = self.terms[i]
        if held[0] is None:
            held[0] = value
        elif fn == "sum":
            held[0] += value
        else:
            held[0] = min(held[0], value) if fn == "min" \
                else max(held[0], value)
        held[1] += n

    def merge(self, terms: Sequence[Term], other: "_GroupPartial") -> None:
        self.rows += other.rows
        for i, term in enumerate(terms):
            self.add(term.fn, i, *other.terms[i])


class QueryRun:
    """One run of a bound query over chunks of fixed-length records:
    `add` packs a chunk's record matrix to the query's bytes, sends and
    launches it, and brings home the chunk before (one launch in
    flight); `drain` brings home the last; `finish` merges the partials,
    decodes the keys and shapes the result. The stages and counts are a
    read's (`pack`, `h2d`, `launch`, `d2h_wait`, `DeviceStats.
    note_launch`) and the query's own (`query.merge`, `query.fallback`,
    `DeviceStats.note_query_chunk`)."""

    def __init__(self, aggregator: DeviceAggregator, stats=None):
        self.aggregator = aggregator
        self.query = aggregator.query
        self.stats = stats
        self.groups: Dict[int, _GroupPartial] = {}
        self._pending = None

    def add(self, matrix: np.ndarray) -> None:
        agg = self.aggregator
        # the span benchmark/trace_reduce.py knows the program's device
        # section by, as round a read's launches
        with annotate("cobrix_decode"):
            for start in range(0, matrix.shape[0], LAUNCH_ROWS_MAX):
                rows = matrix[start:start + LAUNCH_ROWS_MAX]
                launched = ColumnarDecoder._submit_block(
                    agg.device_program(), agg.pack(rows), rows.shape[0],
                    np.int32(rows.shape[0]))
                self.drain()
                self._pending = (launched, rows)

    def drain(self) -> None:
        if self._pending is None:
            return
        launched, matrix = self._pending
        self._pending = None
        out, rows = ColumnarDecoder._fetch_block(launched, self.stats)
        with Stage("query.merge"):
            partial = self._proven(out)
        fallback = partial is None
        if fallback:
            with Stage("query.fallback"):
                partial = host_partial(self.query, matrix)
        with Stage("query.merge"):
            for raw, group in partial.items():
                self.groups.setdefault(
                    raw, _GroupPartial(len(self.query.terms))).merge(
                        self.query.terms, group)
        if self.stats is not None:
            self.stats.note_query_chunk(
                rows, sum(g.rows for g in partial.values()), fallback)

    def _proven(self, out):
        """The chunk's partial from the program's output, or None where
        it does not prove itself exact: a row's product may have left 63
        bits (the factors' largest magnitudes multiplied do), or the
        chunk holds more keys than the program reduces."""
        terms = self.query.terms
        fits = all(
            _product(int(out["fmax"][str(f)]) for f in term.factors)
            < _INT64_MAX for term in terms)
        if bool(out["more"]) or not fits:
            return None
        partial = {}
        for k, raw in enumerate(out["cand"].tolist()):
            if raw == _ABSENT:
                continue
            group = partial[raw] = _GroupPartial(len(terms))
            group.rows = int(out["rows"][k])
            for i, (term, part) in enumerate(zip(terms, out["terms"])):
                value = ((int(part["hi"][k]) << 32) + int(part["lo"][k])
                         if term.fn == "sum" else int(part["v"][k]))
                group.add(term.fn, i, value, int(part["n"][k]))
        return partial

    def finish(self):
        """The result ``aggregate()`` returns (stats/aggregate.
        shape_result)."""
        self.drain()
        with Stage("query.merge"):
            result = finish_groups(self.query, self.groups)
        if self.stats is not None:
            self.stats.query_groups = (
                result.num_rows if self.query.keys else 1)
        return result


# aggregators by what their program answers; the key's copybook is held
# so that its id() cannot come back as another's
_AGGREGATORS: "OrderedDict[tuple, Tuple[Copybook, DeviceAggregator]]" = \
    OrderedDict()
_AGGREGATORS_CAP = 16
_aggregators_lock = threading.Lock()


def aggregator_for(query: BoundQuery, backend: str) -> DeviceAggregator:
    """The DeviceAggregator of (copybook, specs, filter, keys) on
    `backend`, built once: a query asked again finds its compiled
    program."""
    key = query.fingerprint() + (backend,)
    with _aggregators_lock:
        held = _AGGREGATORS.get(key)
        if held is not None and held[0] is query.copybook:
            _AGGREGATORS.move_to_end(key)
            return held[1]
    aggregator = DeviceAggregator(query.copybook, backend=backend,
                                  query=query)
    aggregator.device_program()   # NotOnDevice here, not mid-scan
    with _aggregators_lock:
        _AGGREGATORS[key] = (query.copybook, aggregator)
        while len(_AGGREGATORS) > _AGGREGATORS_CAP:
            _AGGREGATORS.popitem(last=False)
    return aggregator


def _product(values) -> int:
    total = 1
    for value in values:
        total *= value
    return total


def decode_raw_key(query: BoundQuery, options, raw: int) -> tuple:
    """The key values a raw key stands for, each decoded from its bytes
    by the scalar oracle's decoder (`options`, its DecodeOptions): what
    the decoded table shows."""
    widths = [st.binary_properties.data_size
              for st in query.key_statements]
    data = raw.to_bytes(sum(widths), "big") if widths else b""
    values, at = [], 0
    for st, width in zip(query.key_statements, widths):
        values.append(options.decode(st.dtype, data[at:at + width]))
        at += width
    return tuple(values)


def finish_groups(query: BoundQuery, by_raw: Dict[int, _GroupPartial]):
    """Partials by raw key -> the shaped result: raw keys that decode
    alike merge, sums take their scale, averages divide."""
    from ..reader.extractors import DecodeOptions

    terms = query.terms
    options = DecodeOptions.from_copybook(query.copybook)
    merged: Dict[tuple, _GroupPartial] = {}
    for raw, group in by_raw.items():
        merged.setdefault(decode_raw_key(query, options, raw),
                          _GroupPartial(len(terms))).merge(terms, group)
    if not query.keys and not merged:
        merged[()] = _GroupPartial(len(terms))   # one group, over no rows
    groups = []
    for key in sorted(merged, key=key_order):
        group, values = merged[key], {}
        for spec in query.specs:
            term = query.term_of(spec)
            if term is None:
                values[spec.text] = group.rows
                continue
            total, n = group.terms[terms.index(term)]
            if total is not None and not all(
                    query.operands[f.field].integer for f in term.factors):
                total = scaled(total, query.scale_of(term.factors))
            values[spec.text] = (average(total, n) if spec.fn == "avg"
                                 else total)
        groups.append((key, values))
    return shape_result(query.specs, query.keys, groups, query.key_types)


def host_partial(query: BoundQuery, matrix: np.ndarray
                 ) -> Dict[int, _GroupPartial]:
    """One chunk's partial by the host kernels, in Python integers: what
    the device's would be had it no width to keep to. The same
    predicate and term code as the program's, over numpy."""
    decoder = ColumnarDecoder(query.copybook, backend="numpy",
                              select=query.select)
    batch = decoder.decode(matrix)
    col = {}
    for name, operand in query.operands.items():
        (c,) = decoder.plan.columns_for(operand.statement)
        arrays = batch.column_arrays(c.index)
        col[name] = (np.asarray(arrays["values"]).astype(np.int64),
                     np.asarray(arrays["valid"]).astype(bool))
    n = matrix.shape[0]
    keep = np.ones(n, dtype=bool)
    if query.filter is not None:
        keep = _eval_filter(np, query, query.filter, col)[0]
    raw = np.zeros(n, dtype=np.int64)
    for st in query.key_statements:
        at = st.binary_properties.offset
        for j in range(st.binary_properties.data_size):
            raw = raw * 256 + matrix[:, at + j]
    wide = {name: (values.astype(object), valid)
            for name, (values, valid) in col.items()}
    rows = [_term_rows(np, query, term, wide)[:2] for term in query.terms]
    partial: Dict[int, _GroupPartial] = {}
    for key in np.unique(raw[keep]).tolist():
        member = keep & (raw == key)
        group = partial[key] = _GroupPartial(len(query.terms))
        group.rows = int(member.sum())
        for i, (term, (value, ok)) in enumerate(zip(query.terms, rows)):
            chosen = value[member & ok]
            if len(chosen):
                group.add(term.fn, i,
                          {"sum": sum, "min": min, "max": max}[term.fn](
                              chosen.tolist()), len(chosen))
    return partial
