"""Device-resident query path: decode + aggregate in ONE XLA program.

A full decode returns more bytes to the host than it took in (values
widen to int32/int64 with a validity byte each), so a pipeline that
pulls every decoded column back pays the link twice. When the consumer
reduces, consume the columns ON the device — decode and reduce inside
one jitted program — and transfer only the reduced results. This is the
production shape of the reference's mainframe->Parquet->SQL-aggregate
pipelines (the Spark stage after the Cobrix scan), collapsed into the
scan itself.

Combined with column projection (`select`), the device decodes only the
fields the query touches; with a sharded mesh, GSPMD inserts the psum
collectives for the cross-chip reduction over ICI (SURVEY.md §2.5).

Accumulator dtypes keep the Mosaic/TPU int32 discipline for counts and
float64 (XLA-emulated on TPU, exact to 2^53) for value sums — no int64
inside the hot program.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from ..copybook.copybook import Copybook
from ..plan.compiler import Codec
from ..profiling import annotate
from ..reader.columnar import (_FLOAT_CODECS, _NUMERIC_CODECS, _dyn_scale,
                               fixed_point_exponent)
from .mesh import batch_sharding, data_mesh, pad_batch_to_multiple
from .sharded import ShardedColumnarDecoder


class DeviceAggregator:
    """Decode + reduce on device; only scalars cross the host link.

    `columns`: field names to aggregate (numeric fields only; OCCURS
    elements of a field aggregate together). None = every numeric field in
    the plan. The decode is automatically projected to those fields.
    """

    def __init__(self, copybook: Copybook,
                 columns: Optional[Sequence[str]] = None,
                 active_segment: Optional[str] = None,
                 mesh=None, pack_bytes: bool = True,
                 backend: Optional[str] = None):
        self.decoder = ShardedColumnarDecoder(
            copybook, mesh=mesh, active_segment=active_segment,
            select=columns, backend=backend)
        # byte width a [n, extent] record matrix must have BEFORE byte
        # projection (plan.max_extent shrinks when projection remaps)
        self.record_extent = self.decoder.plan.max_extent
        self.gather_index: Optional[np.ndarray] = None
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
        self.gather_index = np.concatenate(
            [np.arange(s, e, dtype=np.int64) for s, e in merged])

    @property
    def mesh(self):
        return self.decoder.mesh

    def _build(self):
        import jax.numpy as jnp
        from jax import lax

        from ..ops.device import DeviceProgram

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
                    out = outs[gi]
                    if len(poss) == len(g.columns):
                        sel = slice(None)  # whole group: skip the gather
                    else:
                        sel = jnp.asarray(poss)
                    spec = g.columns[poss[0]]
                    is_display = g.codec in (Codec.DISPLAY_NUM,
                                             Codec.DISPLAY_NUM_ASCII)
                    if g.wide:
                        # uint128-limb plane: aggregate the f64 approximation
                        # (sums/min/max of >18-digit values round by nature)
                        hi, lo = out[0][:, sel], out[1][:, sel]
                        mag = (hi.astype(jnp.float64) * jnp.float64(2.0 ** 64)
                               + lo.astype(jnp.float64))
                        v64 = jnp.where(out[2][:, sel], -mag, mag)
                        valid = out[3][:, sel] & row_live[:, None]
                        if is_display and (spec.params.explicit_decimal
                                           or _dyn_scale(spec)):
                            dots = out[4][:, sel].astype(jnp.float64)
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
                        values = out[0][:, sel]
                        valid = out[1][:, sel] & row_live[:, None]
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
                            dots = out[2][:, sel].astype(jnp.float64)
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

    def device_program(self):
        """The decode+reduce as an ops.device.DeviceProgram, built once."""
        if self._agg_fn is None:
            self._agg_fn = self._build()
        return self._agg_fn

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
