"""Aggregates answered from statistics alone — when provably exact.

``dataset().aggregate()`` and ``count_rows()`` call in here first
(``use_stats=true``, no filter): if EVERY input file has a warm
profile under the read's exact configuration, ``count`` is the sum of
profiled record counts, ``min``/``max`` fold the per-chunk zone maps,
and ``sum`` folds the per-chunk exact sums (int/decimal kinds only —
float sums are order-dependent, never answered from stats). Anything
short of proof — a missing profile, a NaN-tainted chunk, an unknown
field, an inexact kind, a product, an average, a ``group_by`` — returns
None and the caller decodes, so a stats answer is always byte-identical
to the decoded one.

The spec grammar (`parse_specs`) and the shape of a result
(`shape_result`) are here too, one spelling for the stats, decode and
device paths.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from .profile import FileProfile

_UNPROVABLE = object()


@dataclass(frozen=True)
class Factor:
    """One factor of a product: the field itself, ``(1-FIELD)`` or
    ``(1+FIELD)`` (`sign` 0, -1, +1)."""

    field: str
    sign: int = 0

    def __str__(self) -> str:
        if not self.sign:
            return self.field
        return f"(1{'+' if self.sign > 0 else '-'}{self.field})"


@dataclass(frozen=True)
class AggSpec:
    """One aggregate: `fn` is count, min, max, sum or avg; `factors` is
    empty for count, one plain field for min, max and avg, and one or
    more factors for sum. `text` is the canonical spelling, the key of
    the result."""

    fn: str
    factors: Tuple[Factor, ...] = ()

    @property
    def text(self) -> str:
        if not self.factors:
            return self.fn
        return f"{self.fn}:{'*'.join(str(f) for f in self.factors)}"

    @property
    def field(self) -> Optional[str]:
        """The one plain field of a single-field spec, else None."""
        if len(self.factors) == 1 and not self.factors[0].sign:
            return self.factors[0].field
        return None

    @property
    def fields(self) -> List[str]:
        return [f.field for f in self.factors]

    def __iter__(self):
        # (fn, field): what a spec was before it could be a product
        return iter((self.fn, self.field))


_FIELD = r"[A-Za-z_][A-Za-z0-9_\-:.]*"
_FACTOR_RE = re.compile(
    rf"^(?:(?P<plain>{_FIELD})|\(1(?P<sign>[+-])(?P<inner>{_FIELD})\))$")

GRAMMAR = ("'count', 'min:FIELD', 'max:FIELD', 'sum:FIELD', 'avg:FIELD', or "
           "'sum:' of a '*'-product of factors FIELD, (1-FIELD), (1+FIELD)")


def _parse_factor(text: str, spec) -> Factor:
    m = _FACTOR_RE.match(text)
    if m is None:
        raise ValueError(
            f"unsupported factor {text!r} in aggregate spec {spec!r} "
            f"(use {GRAMMAR})")
    if m.group("plain"):
        return Factor(m.group("plain"))
    return Factor(m.group("inner"), 1 if m.group("sign") == "+" else -1)


def parse_specs(aggs: Sequence[str]) -> List[AggSpec]:
    """``["count", "min:FIELD", "sum:A*(1-B)", ...]`` -> ``[AggSpec]``
    (validated; the one spelling the stats, decode and device paths
    share). A spec still unpacks as ``(fn, field)``; `field` is None
    for count and for a product."""
    out: List[AggSpec] = []
    for spec in aggs:
        fn, sep, body = str(spec).partition(":")
        fn = fn.strip().lower()
        body = re.sub(r"\s+", "", body)
        if fn == "count" and not body:
            out.append(AggSpec("count"))
            continue
        if fn in ("min", "max", "sum", "avg") and sep and body:
            factors = tuple(_parse_factor(part, spec)
                            for part in body.split("*"))
            if fn == "sum" or (len(factors) == 1 and not factors[0].sign):
                out.append(AggSpec(fn, factors))
                continue
        raise ValueError(
            f"unsupported aggregate spec {spec!r} (use {GRAMMAR})")
    if not out:
        raise ValueError("aggregate() needs at least one spec")
    return out


def resolve_leaf(copybook, name: str) -> Optional[str]:
    """The profile key for an aggregate field reference (the same
    copybook resolution the filter binder uses), or None."""
    from ..copybook.ast import Group

    try:
        st = copybook.get_field_by_name(name)
    except (KeyError, ValueError):
        return None
    return None if isinstance(st, Group) else st.name


def load_all_profiles(files, copybook_contents,
                      params) -> Optional[List[FileProfile]]:
    """One profile per input file under this exact configuration — or
    None when ANY file lacks one (partial coverage cannot answer a
    whole-read aggregate)."""
    from ..plan.cache import parse_fingerprint
    from ..reader.stream import normalize_local
    from .collect import bump_overhead, profiling_eligibility
    from .store import StatsStore, local_fingerprint, \
        stats_config_fingerprint

    bump_overhead()
    if profiling_eligibility(files, params, "numpy") is not None:
        return None
    try:
        store = StatsStore(params.cache_dir)
    except OSError:
        return None
    config_fp = stats_config_fingerprint(
        parse_fingerprint(copybook_contents, params), params)
    profiles: List[FileProfile] = []
    for path in files:
        local = normalize_local(path)
        fingerprint = local_fingerprint(local)
        if fingerprint is None:
            return None
        profile = store.load(local, fingerprint, config_fp)
        if profile is None:
            return None
        profiles.append(profile)
    return profiles


def _fold_min_max(profiles: List[FileProfile], leaf: str, fn: str):
    best = None
    non_null = 0
    for profile in profiles:
        if leaf not in profile.field_kinds:
            return _UNPROVABLE
        for chunk in profile.chunks:
            fs = chunk.fields.get(leaf)
            if fs is None:
                return _UNPROVABLE
            present = chunk.records - fs.null_count
            if present <= 0:
                continue
            if fs.min is None:
                return _UNPROVABLE  # NaN taint / unknown zone map
            non_null += present
            value = fs.min if fn == "min" else fs.max
            if best is None:
                best = value
            else:
                best = min(best, value) if fn == "min" \
                    else max(best, value)
    return best if non_null else None  # SQL NULL over no values


def _fold_sum(profiles: List[FileProfile], leaf: str):
    total = None
    non_null = 0
    for profile in profiles:
        kind = profile.field_kinds.get(leaf)
        if kind not in ("int", "decimal"):
            return _UNPROVABLE  # float sums are not exactly foldable
        for chunk in profile.chunks:
            fs = chunk.fields.get(leaf)
            if fs is None or fs.sum is None:
                return _UNPROVABLE
            non_null += chunk.records - fs.null_count
            total = fs.sum if total is None else total + fs.sum
    return total if non_null else None


def aggregates_from_profiles(profiles: List[FileProfile], copybook,
                             specs: Sequence[Tuple[str, Optional[str]]]
                             ) -> Optional[Dict[str, object]]:
    """Every requested aggregate from statistics alone, keyed by its
    original spec spelling — or None when any one is unprovable (all
    or nothing: mixing stats and decode answers in one call would make
    the provenance unauditable)."""
    out: Dict[str, object] = {}
    for fn, field in specs:
        if fn == "count":
            out["count"] = sum(p.total_records for p in profiles)
            continue
        if field is None or fn == "avg":
            # a product's sum and an average are no chunk's statistic
            return None
        leaf = resolve_leaf(copybook, field)
        if leaf is None:
            return None
        value = (_fold_sum(profiles, leaf) if fn == "sum"
                 else _fold_min_max(profiles, leaf, fn))
        if value is _UNPROVABLE:
            return None
        out[f"{fn}:{field}"] = value
    return out


# -- the shape of a result --------------------------------------------------

def scaled(total: int, scale: int):
    """`total` units of 10^-`scale` as a Decimal of that exponent, every
    digit kept (``Decimal.scaleb`` would round to the context's 28)."""
    import decimal

    return decimal.Decimal((int(total < 0),
                            tuple(int(d) for d in str(abs(total))), -scale))


def average(total, count: int):
    """`avg`: the exact sum over the exact count, divided in `decimal`'s
    default context; None over no values."""
    import decimal

    if not count or total is None:
        return None
    return decimal.Decimal(total) / decimal.Decimal(count)


def _column(values: list):
    """A pyarrow array of one result column: int64 for ints that fit,
    one decimal type wide enough for every Decimal at their largest
    scale (no value is rounded), else what pyarrow infers."""
    import decimal

    import pyarrow as pa

    present = [v for v in values if v is not None]
    if present and all(isinstance(v, int) and not isinstance(v, bool)
                       for v in present):
        if all(-2 ** 63 <= v < 2 ** 63 for v in present):
            return pa.array(values, type=pa.int64())
        values = [None if v is None else decimal.Decimal(v) for v in values]
        present = [v for v in values if v is not None]
    if present and all(isinstance(v, decimal.Decimal) for v in present):
        scale = max(0, *(-v.as_tuple().exponent for v in present))
        digits = max(len(v.as_tuple().digits) + v.as_tuple().exponent
                     for v in present)
        wide = max(1, digits) + scale > 38
        return pa.array(values, type=(pa.decimal256(76, scale) if wide
                                      else pa.decimal128(38, scale)))
    return pa.array(values)


def shape_result(specs: Sequence[AggSpec], keys: Sequence[str],
                 groups: Sequence[Tuple[tuple, Dict[str, object]]],
                 key_types: Sequence = ()):
    """What ``aggregate()`` returns, from `groups`: ``[(key values,
    {spec text: value})]``, ascending by key. Without `keys` there is
    one group and the result is its ``{spec text: value}``; with them a
    ``pyarrow.Table``, key columns first (of `key_types`, the decoded
    columns' own), one row per group."""
    import pyarrow as pa

    if not keys:
        (_key, values), = groups
        return {spec.text: values[spec.text] for spec in specs}
    columns = {name: pa.array([key[i] for key, _ in groups],
                              type=key_types[i] if key_types else None)
               for i, name in enumerate(keys)}
    for spec in specs:
        if spec.text in columns:
            continue
        column = [values[spec.text] for _, values in groups]
        columns[spec.text] = (pa.array(column, type=pa.int64())
                              if spec.fn == "count" else _column(column))
    return pa.table(columns)


def key_order(key: tuple) -> tuple:
    """Sort key of a group's key values: ascending, nulls last."""
    return tuple((v is None, 0 if v is None else v) for v in key)
