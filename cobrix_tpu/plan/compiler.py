"""Columnar field-plan compiler: copybook AST -> flat decode plan.

This is the central TPU-first redesign. The reference binds a per-field JVM
closure at parse time and walks the AST per record
(RecordExtractors.scala:49, DecoderSelector.scala:54). Here the AST is
compiled ONCE into a flat list of column specs — (byte offset, width, codec,
params) per primitive leaf, with every OCCURS element expanded to its own
static slot — and specs are grouped by (codec, width) so one batched kernel
launch decodes the same-shaped columns of ALL records at once from a
`[batch, record_len]` uint8 matrix.

Variable layouts are handled statically where possible:
- OCCURS (fixed): expanded slots, all offsets static.
- OCCURS DEPENDING ON with the default fixed-size layout
  (`variable_size_occurs=false`): slots are static; per-record element
  visibility is a post-decode gate on the dependee column.
- REDEFINES: multiple columns over the same offsets (decode is read-only).
- Segment redefines: columns are tagged with their segment group; row
  materialization nulls inactive segments.
- variable_size_occurs=true: an OCCURS DEPENDING ON array takes
  `count x element` bytes and everything behind it moves. The plan stays the
  static max-size layout and names each such array as a *variable region*
  (`FieldPlan.regions`): the decoders read each region's count from the
  packed rows and move the bytes behind it to where the static layout has
  them (ops/expand.py), after which the one static program applies. Batched:
  any number of regions outside other arrays, each with an integral
  COMP / COMP-3 / DISPLAY dependee earlier in the record (in the same segment
  redefine, where there is one), plain REDEFINES before or behind them.
  Walked record by record on the host (reader.extractors), with the reason
  in `FieldPlan.row_path_reason`: a variable array inside another array or
  under a plain REDEFINES, a dependee that is out of reach (another segment
  redefine, inside an array, behind its array), of a string codec or with
  handlers.
- A variable array whose elements hold variable arrays of their own (two
  levels, `array_of_arrays`): the whole record has no static layout worth
  the name (40 slots of the largest element), so it is cut into two row
  kinds, each with a plan of its own (`rows_of`): the record without the
  array ("owner": what lies before it, then what lies behind it) and one
  row an element ("element": the element as a record, its variable arrays
  regions as above, behind the bytes of the record's prefix its arrays
  depend on, if any). reader/element_rows.py frames and packs them.
"""
from __future__ import annotations

import enum
from dataclasses import dataclass, field as dc_field
from typing import Dict, List, Optional, Sequence, Tuple

from ..copybook.ast import Group, Primitive, Statement
from ..copybook.copybook import Copybook
from ..copybook.datatypes import (
    AlphaNumeric,
    Decimal,
    Encoding,
    FloatingPointFormat,
    Integral,
    MAX_LONG_PRECISION,
    TrimPolicy,
    Usage,
)


class Codec(enum.Enum):
    """Kernel family a column decodes with (mirrors the ★ decoder components
    of SURVEY.md §2.1)."""

    EBCDIC_STRING = "ebcdic_string"      # LUT transcode
    ASCII_STRING = "ascii_string"        # mask controls/high bytes
    UTF16_STRING = "utf16_string"
    HEX_STRING = "hex_string"
    RAW_BYTES = "raw"
    DISPLAY_NUM = "display_num"          # zoned decimal (EBCDIC overpunch)
    DISPLAY_NUM_ASCII = "display_num_ascii"
    BCD = "bcd"                          # COMP-3 packed decimal
    BINARY = "binary"                    # COMP/COMP-4/5/9 two's complement
    FLOAT_IBM = "float_ibm"              # COMP-1 IBM hex float
    FLOAT_IEEE = "float_ieee"
    DOUBLE_IBM = "double_ibm"            # COMP-2
    DOUBLE_IEEE = "double_ieee"
    HOST_FALLBACK = "host"               # scalar-oracle per value


@dataclass(frozen=True)
class CodecParams:
    """Per-column decode parameters; hashable so identical (codec, width,
    params) columns batch into one kernel launch."""

    signed: bool = False
    big_endian: bool = True
    scale: int = 0
    scale_factor: int = 0
    explicit_decimal: bool = False
    precision: int = 0
    is_sign_separate: bool = False


@dataclass(frozen=True)
class Gate:
    """Visibility gate from OCCURS DEPENDING ON: the element at `elem_index`
    of the array exists iff elem_index < actual_count, where actual_count is
    the dependee column's value clamped to [min_size, max_size] (out-of-range
    values fall back to max_size — reference RecordExtractors.scala:64-80)."""

    depend_col: int
    min_size: int
    max_size: int
    elem_index: int


@dataclass(frozen=True)
class VariableRegion:
    """An OCCURS DEPENDING ON array under `variable_size_occurs` that lies
    in no other array: in the file it takes `count x element_size` bytes,
    in the plan's static layout `max_size x element_size` from `start`.
    The bytes from its compact end up to `scope_end` (the end of the
    enclosing segment redefine, which keeps its static size in the walk;
    None: the end of the record) move right by
    `(max_size - count) x element_size`. `depend_col` is the plan column
    of the dependee, static once the regions before this one are laid
    out, and `depend_*`, `signed`, `big_endian` what the expansion
    (ops/expand.py) needs to decode it: `depend_kind` is "binary",
    "bcd", "display_ebcdic" or "display_ascii". Regions are ordered by
    `start`."""

    name: str
    depend_col: int
    depend_offset: int
    depend_width: int
    depend_kind: str
    signed: bool
    big_endian: bool
    start: int
    element_size: int
    min_size: int
    max_size: int
    scope_end: Optional[int] = None

    @property
    def end(self) -> int:
        return self.start + self.max_size * self.element_size

    @property
    def max_shift(self) -> int:
        return (self.max_size - self.min_size) * self.element_size


@dataclass
class ColumnSpec:
    """One output column: a primitive leaf at one static OCCURS slot."""

    index: int                       # position in the plan's column list
    path: Tuple[str, ...]            # group names from root to the field
    name: str
    offset: int                      # byte offset within the record
    width: int                       # bytes of one instance
    codec: Codec
    params: CodecParams
    dtype: object                    # the CobolType (for host fallback/schema)
    slot_path: Tuple[int, ...] = ()  # occurrence indices of enclosing arrays
    gates: Tuple[Gate, ...] = ()     # ODO visibility gates (outermost first)
    statement: Optional[Primitive] = None
    segment: Optional[str] = None    # nearest enclosing segment redefine


@dataclass
class ColumnGroup:
    """Columns sharing (codec, width) — one batched kernel launch."""

    codec: Codec
    width: int
    columns: List[ColumnSpec] = dc_field(default_factory=list)


@dataclass
class FieldPlan:
    record_size: int
    columns: List[ColumnSpec]
    groups: List[ColumnGroup]
    trimming: TrimPolicy
    ebcdic_code_page: str
    ascii_charset: str
    is_utf16_big_endian: bool
    floating_point_format: FloatingPointFormat
    # variable_size_occurs: the arrays whose size moves what lies behind
    # them, by offset; and, where the layout cannot be laid to the static
    # program, why the records are walked instead
    regions: Tuple[VariableRegion, ...] = ()
    row_path_reason: Optional[str] = None

    def columns_for(self, st: Statement) -> List["ColumnSpec"]:
        return [c for c in self.columns if c.statement is st]

    @property
    def ambiguous_names(self) -> frozenset:
        """Leaf names used by more than one statement (name reuse across
        groups is idiomatic COBOL, qualified by OF/IN). Cost attribution
        must path-qualify these or same-named fields in different groups
        silently merge into one wrong row."""
        amb = getattr(self, "_ambiguous_names", None)
        if amb is None:
            owner: Dict[str, object] = {}
            dupes = set()
            for c in self.columns:
                prev = owner.setdefault(c.name, c.statement)
                if prev is not c.statement:
                    dupes.add(c.name)
            amb = frozenset(dupes)
            self._ambiguous_names = amb
        return amb

    def cost_name(self, c: "ColumnSpec") -> str:
        """The column's identity in the per-field cost table: the bare
        name when unique, the dotted path when the name is reused by
        another statement. OCCURS slots of one statement share both, so
        they still merge into one row."""
        if c.name in self.ambiguous_names:
            return ".".join(c.path + (c.name,))
        return c.name

    def describe(self) -> List[dict]:
        """One dict per FIELD (OCCURS slots of a statement collapse to
        one row carrying the slot count) — the structured form of the
        explain report's field-plan table: name, dotted path, first
        byte offset, per-instance width, kernel family, and the decode
        parameters that select the kernel variant."""
        rows: List[dict] = []
        by_field: Dict[int, dict] = {}
        for c in self.columns:
            key = id(c.statement) if c.statement is not None else id(c)
            row = by_field.get(key)
            if row is not None:
                row["occurs"] += 1
                continue
            p = c.params
            row = {
                "field": c.name,
                "path": ".".join(c.path + (c.name,)),
                "offset": c.offset,
                "width": c.width,
                "codec": c.codec.value,
                "occurs": 1,
                "signed": p.signed,
                "scale": p.scale,
                "precision": p.precision,
                "segment": c.segment,
            }
            by_field[key] = row
            rows.append(row)
        return rows

    def group_summary(self) -> List[dict]:
        """Kernel-group shape of the plan: one row per (codec, width)
        launch group with its column count — the launch count the batch
        decoder pays per chunk."""
        return [{"codec": g.codec.value, "width": g.width,
                 "columns": len(g.columns)}
                for g in self.groups]

    @property
    def max_extent(self) -> int:
        """Largest byte any column reads — the minimum row width a batch
        matrix needs for this plan. Much smaller than record_size when an
        active segment restricts the plan to a narrow redefine (exp2/exp3:
        64-byte contact records vs a 16 KB wide layout)."""
        return max((c.offset + c.width for c in self.columns), default=0)


def _classify(dtype, fp_format: FloatingPointFormat) -> Tuple[Codec, CodecParams]:
    """Map a CobolType to its kernel family (mirrors DecoderSelector dispatch)."""
    if isinstance(dtype, AlphaNumeric):
        enc = dtype.enc or Encoding.EBCDIC
        if enc is Encoding.EBCDIC:
            return Codec.EBCDIC_STRING, CodecParams()
        if enc is Encoding.ASCII:
            return Codec.ASCII_STRING, CodecParams()
        if enc is Encoding.UTF16:
            return Codec.UTF16_STRING, CodecParams()
        if enc is Encoding.HEX:
            return Codec.HEX_STRING, CodecParams()
        return Codec.RAW_BYTES, CodecParams()

    is_ebcdic = (dtype.enc or Encoding.EBCDIC) is Encoding.EBCDIC
    usage = dtype.usage
    if isinstance(dtype, Decimal):
        scale, sf, expl = dtype.scale, dtype.scale_factor, dtype.explicit_decimal
    else:
        scale, sf, expl = 0, 0, False
    params = CodecParams(
        signed=dtype.is_signed,
        big_endian=usage is not Usage.COMP9,
        scale=scale,
        scale_factor=sf,
        explicit_decimal=expl,
        precision=dtype.precision,
        is_sign_separate=dtype.is_sign_separate,
    )
    if usage is None:
        # Wide (19-38 digit) fields use the uint128-limb kernels, exact
        # while every byte of the field could be a digit (<= 38 slots).
        # PIC P (scale_factor<0) uses the per-value dot_scale plane: the
        # exponent depends on the decoded digit-char count
        # (BinaryUtils.addDecimalPoint, BinaryUtils.scala:194).
        display_width = (dtype.precision + (1 if expl else 0)
                         + (1 if dtype.is_sign_separate else 0))
        if display_width > 38:
            return Codec.HOST_FALLBACK, params
        return (Codec.DISPLAY_NUM if is_ebcdic else Codec.DISPLAY_NUM_ASCII), params
    if usage is Usage.COMP3:
        # digit slots = 2*bytes - 1; > 38 slots would overflow uint128
        if 2 * (dtype.precision // 2 + 1) - 1 > 38:
            return Codec.HOST_FALLBACK, params
        return Codec.BCD, params
    if usage in (Usage.COMP4, Usage.COMP5, Usage.COMP9):
        # 9-16 byte two's complement is exact in uint128 limbs
        if dtype.precision > 38:
            return Codec.HOST_FALLBACK, params
        return Codec.BINARY, params
    if usage is Usage.COMP1:
        if fp_format in (FloatingPointFormat.IBM, FloatingPointFormat.IBM_LE):
            return Codec.FLOAT_IBM, CodecParams(
                big_endian=fp_format is FloatingPointFormat.IBM)
        return Codec.FLOAT_IEEE, CodecParams(
            big_endian=fp_format is FloatingPointFormat.IEEE754)
    if usage is Usage.COMP2:
        if fp_format in (FloatingPointFormat.IBM, FloatingPointFormat.IBM_LE):
            return Codec.DOUBLE_IBM, CodecParams(
                big_endian=fp_format is FloatingPointFormat.IBM)
        return Codec.DOUBLE_IEEE, CodecParams(
            big_endian=fp_format is FloatingPointFormat.IEEE754)
    raise ValueError(f"Unknown usage {usage}")


_DEPENDEE_KINDS = {Codec.BINARY: "binary", Codec.BCD: "bcd",
                   Codec.DISPLAY_NUM: "display_ebcdic",
                   Codec.DISPLAY_NUM_ASCII: "display_ascii"}


def _region_dependee_fault(spec: "ColumnSpec", array_start: int,
                           segment: Optional[str]) -> Optional[str]:
    """Why the column `spec` cannot size a variable region that starts at
    `array_start` under `segment`, or None where it can: the count has to
    be an integer the batch kernels decode, at a static offset before the
    array, in every row that holds the array."""
    if spec.slot_path:
        return "is inside an array"
    if spec.statement.depending_on_handlers or not isinstance(
            spec.dtype, Integral):
        return "is not an integral number"
    if spec.codec not in _DEPENDEE_KINDS \
            or spec.params.scale_factor or spec.params.precision > 18:
        return "is not a COMP, COMP-3 or DISPLAY number of up to 18 digits"
    if spec.offset + spec.width > array_start:
        return "does not lie before its array"
    if spec.segment is not None and spec.segment != segment:
        return "lies in another segment redefine"
    return None


def _inside(st: Statement, group: Statement) -> bool:
    node = st.parent
    while node is not None:
        if node is group:
            return True
        node = node.parent
    return False


def _variable(st: Statement) -> bool:
    return st.is_array and st.depending_on is not None


def array_of_arrays(copybook: Copybook
                    ) -> Tuple[Optional[Group], Optional[str]]:
    """(the variable array whose elements hold variable arrays, why its
    records cannot be cut into element rows) under
    `variable_size_occurs`; (None, None) where no variable array holds
    another. Element rows take one such array, a field of the record's
    one root group (no REDEFINES over it), beside which no other array
    varies, and whose count the record holds before it."""
    variable = [st for st in copybook.ast.walk() if _variable(st)]
    outers = [st for st in variable if isinstance(st, Group)
              and any(_variable(c) for c in st.walk())]
    outers = [st for st in outers
              if not any(_inside(st, o) for o in outers)]
    if not outers:
        return None, None
    outer = outers[0]
    roots = [r for r in copybook.ast.children if isinstance(r, Group)]
    if len(outers) > 1:
        return outer, (f"{outer.name} and {outers[1].name} are variable "
                       "arrays of variable arrays")
    node = outer
    while node is not None:
        if node.redefines is not None or node.is_redefined:
            return outer, (f"{outer.name} is a variable array under a "
                           "REDEFINES")
        node = node.parent
    if outer.parent not in roots:
        return outer, (f"{outer.name} holds variable arrays and is not a "
                       "field of the record itself")
    if len(roots) > 1:
        return outer, (f"{outer.name} holds variable arrays in a copybook "
                       "of several records")
    beside = [st for st in variable
              if st is not outer and not _inside(st, outer)]
    if beside:
        return outer, (f"{beside[0].name} is a variable array beside "
                       f"{outer.name}, which holds variable arrays")
    return outer, None


def _statement_named(copybook: Copybook, name: str) -> Statement:
    return next(st for st in copybook.ast.walk() if st.name == name)


def compile_plan(copybook: Copybook,
                 active_segment: Optional[str] = None,
                 select: Optional[Sequence[str]] = None,
                 variable_size_occurs: bool = False,
                 rows_of: Optional[Tuple[str, str]] = None) -> FieldPlan:
    """Flatten the AST into columns. `active_segment`: compile only columns
    visible when that segment redefine is active (plus common columns);
    None compiles everything (single-segment / fixed-length files).

    `rows_of` ("owner" or "element", the name of an `array_of_arrays`
    array): the plan of one of the two row kinds its records are cut
    into. "owner": the record without the array, what lies behind it
    moved back by the array's static size. "element": one element from
    its first byte, behind the record's prefix up to the last dependee
    of the element's arrays that the element does not hold itself (none:
    the element alone), with only those dependees' columns from it.

    `select`: column projection — only primitives whose name (or an
    enclosing group's name) is listed are compiled; everything else decodes
    to null. This is the decode-only-what's-asked lever the reference
    cannot pull (its TableScan has no column pruning; every field decodes
    per record, CobolScanners.scala:38-55) and the main D2H-volume control
    for the device path. DEPENDING-ON dependees are always kept — array
    sizing needs them even when unselected."""
    from ..copybook.ast import transform_identifier

    columns: List[ColumnSpec] = []
    fp_format = copybook.floating_point_format
    sel = (None if select is None else
           {transform_identifier(str(s).strip()).upper() for s in select})
    # dependee statement name -> column index of its first compiled slot
    dependee_cols: Dict[str, int] = {}
    regions: List[VariableRegion] = []
    row_path_reasons: List[str] = []
    # an element plan's walk of the record's prefix: the dependees its
    # arrays need, and no other column
    prefix_only: Optional[set] = None

    def note_variable_array(st: Statement, offset: int, in_array: bool,
                            overlaid: bool, segment: Optional[str],
                            scope_end: Optional[int]) -> None:
        """A DEPENDING ON array under variable_size_occurs, met at
        `offset` of the static layout: a region, or a reason to walk."""
        if in_array:
            row_path_reasons.append(
                f"{st.name} is a variable array inside another array")
            return
        if overlaid:
            row_path_reasons.append(
                f"{st.name} is a variable array under a REDEFINES")
            return
        col = dependee_cols.get(st.depending_on)
        if col is None:
            row_path_reasons.append(
                f"{st.name} depends on {st.depending_on}, which the "
                "record does not hold before it")
            return
        dep = columns[col]
        fault = _region_dependee_fault(dep, offset, segment)
        if fault is not None:
            row_path_reasons.append(
                f"{st.name} depends on {st.depending_on}, which {fault}")
            return
        regions.append(VariableRegion(
            name=st.name, depend_col=col, depend_offset=dep.offset,
            depend_width=dep.width, depend_kind=_DEPENDEE_KINDS[dep.codec],
            signed=dep.params.signed, big_endian=dep.params.big_endian,
            start=offset,
            element_size=st.binary_properties.data_size,
            min_size=st.array_min_size, max_size=st.array_max_size,
            scope_end=scope_end))

    def resolve_gate(st: Statement, elem_index: int) -> Optional[Gate]:
        if st.depending_on is None:
            return None
        col = dependee_cols.get(st.depending_on)
        if col is None:
            return None
        return Gate(depend_col=col, min_size=st.array_min_size,
                    max_size=st.array_max_size, elem_index=elem_index)

    def add_column(st: Primitive, path: Tuple[str, ...], offset: int,
                   slot_path: Tuple[int, ...], gates: Tuple[Gate, ...],
                   segment: Optional[str]) -> None:
        if sel is not None and not st.is_dependee \
                and st.name.upper() not in sel \
                and not any(p.upper() in sel for p in path):
            return
        if prefix_only is not None and st.name not in prefix_only:
            return
        codec, params = _classify(st.dtype, fp_format)
        spec = ColumnSpec(
            index=len(columns),
            path=path,
            name=st.name,
            offset=offset,
            width=st.binary_properties.data_size,
            codec=codec,
            params=params,
            dtype=st.dtype,
            slot_path=slot_path,
            gates=gates,
            statement=st,
            segment=segment,
        )
        columns.append(spec)
        if st.is_dependee and st.name not in dependee_cols:
            dependee_cols[st.name] = spec.index

    def walk_children(group: Group, path: Tuple[str, ...], group_offset: int,
                      slot_path: Tuple[int, ...], gates: Tuple[Gate, ...],
                      segment: Optional[str], overlaid: bool = False,
                      scope_end: Optional[int] = None,
                      cut: Optional[Statement] = None,
                      stop: Optional[Statement] = None) -> None:
        # `cut`: a child left out, what lies behind it moved back by its
        # static size; `stop`: the child before which the walk ends
        moved = 0
        for st in group.children:
            if st is stop:
                return
            if st is cut:
                moved = st.binary_properties.data_size * st.array_max_size
                continue
            rel = (st.binary_properties.offset
                   - group.binary_properties.offset - moved)
            st_offset = group_offset + rel
            # the walk gives a member of a REDEFINES its static size,
            # whatever it holds: a variable array there moves nothing
            # behind the member, and moving bytes inside it would spoil
            # what the other members read (segment redefines apart: only
            # the active one is compiled)
            member = st.redefines is not None or st.is_redefined
            if variable_size_occurs and st.is_array \
                    and st.depending_on is not None:
                if slot_path == () or not row_path_reasons:
                    note_variable_array(
                        st, st_offset, bool(slot_path),
                        overlaid or (member and not (
                            isinstance(st, Group)
                            and st.is_segment_redefine)),
                        segment, scope_end)
            if isinstance(st, Group):
                seg, over, scope = segment, overlaid, scope_end
                if st.is_segment_redefine:
                    if (active_segment is not None
                            and st.name.upper() != active_segment.upper()):
                        continue
                    seg = st.name
                    scope = st_offset + st.binary_properties.data_size
                elif member:
                    over = True
                if st.is_array:
                    stride = st.binary_properties.data_size
                    for k in range(st.array_max_size):
                        gate = resolve_gate(st, k)
                        new_gates = gates + ((gate,) if gate else ())
                        walk_children(st, path + (st.name,),
                                      st_offset + k * stride,
                                      slot_path + (k,), new_gates, seg,
                                      over, scope)
                else:
                    walk_children(st, path + (st.name,), st_offset,
                                  slot_path, gates, seg, over, scope)
            else:
                if st.is_array:
                    stride = st.binary_properties.data_size
                    for k in range(st.array_max_size):
                        gate = resolve_gate(st, k)
                        new_gates = gates + ((gate,) if gate else ())
                        add_column(st, path, st_offset + k * stride,
                                   slot_path + (k,), new_gates, segment)
                else:
                    add_column(st, path, st_offset, slot_path, gates, segment)

    # 01-level roots lay out SEQUENTIALLY, even when one REDEFINES another:
    # the reference record walk advances the offset for every root
    # (RecordExtractors.scala:176-180, `nextOffset += size` unconditionally)
    # although the parsed offsets overlay — parity requires matching the
    # walk, not the parsed offsets.
    root_offset = 0
    record_size = copybook.record_size
    if rows_of is not None:
        # one root group holds the array (`array_of_arrays`)
        part, name = rows_of
        outer = _statement_named(copybook, name)
        root = outer.parent
        size = outer.binary_properties.data_size
        if part == "owner":
            walk_children(root, (root.name,), 0, (), (), None, cut=outer)
            record_size -= size * outer.array_max_size
        else:
            held = {st.name for st in outer.walk()}
            prefix_only = {st.depending_on for st in outer.walk()
                           if _variable(st)} - held
            prefix = 0
            if prefix_only:
                walk_children(root, (root.name,), 0, (), (), None,
                              stop=outer)
                prefix = max((c.offset + c.width for c in columns),
                             default=0)
            prefix_only = None
            walk_children(outer, (root.name, outer.name), prefix, (), (),
                          None)
            record_size = prefix + size
    for root in copybook.ast.children if rows_of is None else ():
        if isinstance(root, Group):
            walk_children(root, (root.name,), root_offset, (), (), None)
            # advance by the walked size (children sum x occurs), not
            # actual_size: a REDEFINES max-size adjustment does not move
            # the reference's walk
            root_offset += (root.binary_properties.data_size
                            * max(root.array_max_size, 1))

    group_map: Dict[Tuple[Codec, int], ColumnGroup] = {}
    for c in columns:
        key = (c.codec, c.width)
        if key not in group_map:
            group_map[key] = ColumnGroup(codec=c.codec, width=c.width)
        group_map[key].columns.append(c)

    if active_segment is None:
        # every segment redefine is compiled, over the same bytes, for
        # rows in which none is active (their columns come out null): a
        # region inside one moves nothing there. The readers decode a
        # multisegment file with regions segment by segment, each with
        # the plan of its own redefine
        regions = [r for r in regions if r.scope_end is None
                   and columns[r.depend_col].segment is None]
    return FieldPlan(
        record_size=record_size,
        columns=columns,
        groups=list(group_map.values()),
        trimming=copybook.string_trimming_policy,
        ebcdic_code_page=copybook.ebcdic_code_page,
        ascii_charset=copybook.ascii_charset,
        is_utf16_big_endian=copybook.is_utf16_big_endian,
        floating_point_format=copybook.floating_point_format,
        regions=(() if row_path_reasons
                 else tuple(sorted(regions, key=lambda r: r.start))),
        row_path_reason=(row_path_reasons[0] if row_path_reasons
                         else None),
    )


def merged_spans(intervals) -> List[Tuple[int, int]]:
    """Byte intervals [lo, hi) merged where they overlap or touch."""
    spans: List[Tuple[int, int]] = []
    for lo, hi in sorted(intervals):
        if spans and lo <= spans[-1][1]:
            spans[-1] = (spans[-1][0], max(spans[-1][1], hi))
        else:
            spans.append((lo, hi))
    return spans


def packed_position(spans: Sequence[Tuple[int, int]], offset: int) -> int:
    """Where byte `offset` of a record lies once `spans` (disjoint, each
    [lo, hi)) are laid side by side in their order."""
    at = 0
    for lo, hi in spans:
        if lo <= offset < hi:
            return at + offset - lo
        at += hi - lo
    raise ValueError(f"byte {offset} lies in none of {list(spans)}")
