"""A ``pyarrow.dataset``-shaped scan surface over mainframe files.

``dataset(path, copybook=...)`` returns a :class:`CobolDataset` that
duck-types the pyarrow Dataset API — ``schema``, ``scanner(columns=,
filter=)``, ``to_table``, ``to_batches``, ``head``, ``count_rows``,
``get_fragments`` — with one file per :class:`CobolFragment`. The
scanner accepts filters in any of three forms:

* a ``query.Expr`` (or its string grammar / wire JSON),
* a **pyarrow compute expression** (``pc.field("A") == "x"``) — lowered
  into the query AST through its canonical string form, so the same
  pushdown pipeline (plan pruning, pre-decode drops, late
  materialization) runs under engines that speak pyarrow expressions,
* nothing.

A pyarrow expression outside the supported subset falls back to a
post-hoc in-memory filter (correct, unpruned) rather than failing.

``aggregate(aggs, filter=, group_by=)`` reduces instead of materialising:
exact sums (products of fields, ``(1-F)``, ``(1+F)`` included), min, max,
avg and count, per group if asked. On a device backend the chip answers
what it can prove exact and only the groups' partials come back; every
other call decodes and reduces in ``pyarrow.compute``
(``_aggregate_by_decode``, the definition of every answer).

DuckDB / Polars worked example (README "Query pushdown")::

    dset = cobrix_tpu.query.dataset("companies.dat", copybook="c.cob",
                                    is_record_sequence=True)
    reader = dset.scanner(columns=["COMPANY_NAME"],
                          filter=pc.field("SEGMENT_ID") == "C"
                          ).to_reader()
    duckdb.sql("SELECT count(*) FROM reader")

This is the modern analogue of the reference's Spark DataSource L5/L6
layer (PAPER.md layer map): a standard query-engine surface whose
predicate/projection pruning the engine gets for free.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

from .expr import Expr, from_wire, normalize_filter, parse_filter


def _lower_filter(filter_):
    """(wire string | None, posthoc pyarrow expression | None)."""
    if filter_ is None:
        return None, None
    if isinstance(filter_, (Expr, str)):
        return normalize_filter(filter_), None
    # a pyarrow compute expression: its repr is a parseable spelling of
    # the supported subset; anything else falls back to post-hoc
    try:
        return normalize_filter(parse_filter(str(filter_))), None
    except (ValueError, TypeError):
        return None, filter_


class CobolScanner:
    """One materialization plan over a dataset (or one fragment)."""

    def __init__(self, ds: "CobolDataset", files: List[str],
                 columns: Optional[Sequence[str]],
                 filter_=None, batch_size: int = 131072):
        self.dataset = ds
        self.files = files
        self.columns = list(columns) if columns is not None else None
        if self.columns is not None:
            known = set(ds.schema.names)
            bad = [c for c in self.columns if c not in known]
            if bad:
                raise KeyError(
                    f"column(s) {bad} not in the dataset schema")
        self.batch_size = int(batch_size)
        self._wire, self._posthoc = _lower_filter(filter_)
        if self._wire is not None:
            from .expr import from_wire

            expr = from_wire(self._wire)
            if any(f in ds.generated_columns for f in expr.fields()):
                # predicates on generated columns (Record_Id, File_Id,
                # Seg_Id*) have no copybook field to push down against;
                # honor the documented contract and filter post-hoc
                self._wire = None
                self._posthoc = expr.to_pyarrow()

    @property
    def projected_schema(self):
        schema = self.dataset.schema
        if self.columns is None:
            return schema
        import pyarrow as pa

        return pa.schema([schema.field(c) for c in self.columns])

    def _read_table(self, files: List[str]):
        from ..api import read_cobol

        options = dict(self.dataset.options)
        if self.columns is not None:
            options["select"] = ",".join(
                c for c in self.columns
                if c not in self.dataset.generated_columns)
        if self._wire is not None:
            options["filter"] = self._wire
        data = read_cobol(files if len(files) > 1 else files[0],
                          copybook_contents=self.dataset.copybook_contents,
                          backend=self.dataset.backend, **options)
        table = data.to_arrow()
        if self._posthoc is not None:
            import pyarrow.dataset as pads

            table = pads.dataset(table).to_table(filter=self._posthoc)
        if self.columns is not None:
            table = table.select(self.columns)
        return table

    def to_table(self):
        return self._read_table(self.files)

    def to_batches(self):
        # ONE read over every file, like to_table: per-file reads would
        # restart File_Id/Record_Id bases at 0 for each file and the
        # two materialization paths would disagree on record identity
        table = self._read_table(self.files)
        yield from table.to_batches(max_chunksize=self.batch_size)

    def to_reader(self):
        import pyarrow as pa

        return pa.RecordBatchReader.from_batches(
            self.projected_schema, self.to_batches())

    def count_rows(self) -> int:
        return self.to_table().num_rows

    def head(self, num_rows: int):
        return self.to_table().slice(0, num_rows)


class CobolFragment:
    """One input file of the dataset (the pyarrow Fragment analogue);
    its scanner runs the same pushdown pipeline over just that file."""

    def __init__(self, ds: "CobolDataset", path: str):
        self.dataset = ds
        self.path = path

    @property
    def physical_schema(self):
        return self.dataset.schema

    def scanner(self, columns: Optional[Sequence[str]] = None,
                filter=None, batch_size: int = 131072,
                **_ignored) -> CobolScanner:
        return CobolScanner(self.dataset, [self.path], columns, filter,
                            batch_size)

    def to_table(self, columns: Optional[Sequence[str]] = None,
                 filter=None):
        return self.scanner(columns, filter).to_table()

    def count_rows(self, filter=None) -> int:
        return self.scanner(self.dataset._narrowest_columns(filter),
                            filter).count_rows()

    def __repr__(self) -> str:
        return f"<CobolFragment {self.path!r}>"


class CobolDataset:
    """Duck-typed ``pyarrow.dataset.Dataset`` over mainframe files."""

    def __init__(self, files: List[str], copybook_contents,
                 backend: str, options: dict, schema,
                 generated_columns: frozenset):
        self.files = files
        self.copybook_contents = copybook_contents
        self.backend = backend
        self.options = dict(options)
        self.schema = schema
        self.generated_columns = generated_columns
        # the ReadMetrics of the last aggregate() the device answered
        self.metrics = None

    def scanner(self, columns: Optional[Sequence[str]] = None,
                filter=None, batch_size: int = 131072,
                **_ignored) -> CobolScanner:
        """The pyarrow Scanner analogue. `columns` projects (and prunes
        the decode plan); `filter` pushes down (see module docs)."""
        return CobolScanner(self, self.files, columns, filter,
                            batch_size)

    def get_fragments(self, filter=None) -> List[CobolFragment]:
        return [CobolFragment(self, f) for f in self.files]

    def to_table(self, columns: Optional[Sequence[str]] = None,
                 filter=None):
        return self.scanner(columns, filter).to_table()

    def to_batches(self, columns: Optional[Sequence[str]] = None,
                   filter=None, batch_size: int = 131072):
        return self.scanner(columns, filter, batch_size).to_batches()

    def head(self, num_rows: int,
             columns: Optional[Sequence[str]] = None, filter=None):
        return self.scanner(columns, filter).head(num_rows)

    def _narrowest_columns(self, filter_) -> Optional[List[str]]:
        """A minimal projection for count_rows: the filter's own
        fields when there is a filter, else the first schema column —
        row counts never pay a full-width decode."""
        wire, posthoc = _lower_filter(filter_)
        if posthoc is not None:
            return None  # post-hoc filters need whatever they need
        if wire is not None:
            from .expr import from_wire

            names = [n for n in from_wire(wire).fields()
                     if n in set(self.schema.names)]
            if names:
                return names
        return [self.schema.names[0]] if self.schema.names else None

    def count_rows(self, filter=None) -> int:
        if filter is None:
            fast = self._aggregate_from_stats([("count", None)])
            if fast is not None:
                return fast["count"]
        return self.scanner(self._narrowest_columns(filter),
                            filter).count_rows()

    def aggregate(self, aggs: Sequence[str], filter=None,
                  group_by: Optional[Sequence[str]] = None):
        """Evaluate aggregates over the dataset, optionally per group.

        `aggs` is a list of specs (stats/aggregate.parse_specs):
        ``"count"``, ``"min:FIELD"``, ``"max:FIELD"``, ``"sum:FIELD"``,
        ``"avg:FIELD"``, and ``"sum:"`` of a ``*``-product of factors
        ``FIELD``, ``(1-FIELD)``, ``(1+FIELD)``. Without `group_by` the
        result is ``{spec: value}`` (``None`` = SQL NULL over no values;
        nulls are ignored by min/max/sum/avg, counted by count); with it
        a ``pyarrow.Table``, the key columns first, one row per group
        present in the filtered data, ascending by key (a null key
        last). Sums are exact: Python ints over integer fields, else
        ``decimal.Decimal`` at the sum of the factors' scales (a null
        factor makes the row's product null); ``avg`` is that sum over
        the count of non-null values, divided in ``decimal``'s default
        context. Float fields sum in float64.

        Three routes, one answer. With ``use_stats=true``, no filter,
        no `group_by` and a warm profile for EVERY input file, plain
        count/min/max/sum come from persisted statistics without
        decoding a byte (stats/aggregate.py), exact by construction:
        anything short of proof falls back, never approximates. On a
        device backend (``jax``, ``pallas``) a query the chip can answer
        exactly goes to it, chunk by chunk, and only the groups'
        partials come back (api.aggregate_on_device has the rule,
        parallel/query.py the program and what binds). Everything else is
        `_aggregate_by_decode`, which DEFINES what the other two must
        reproduce digit for digit.

        `self.metrics` is afterwards the call's ``ReadMetrics`` where
        the device answered (``metrics.as_dict()["device"]`` as a read's
        has it, with the ``query_*`` counts), else None.
        """
        from ..stats.aggregate import parse_specs

        specs = parse_specs(aggs)
        keys = [str(k) for k in (group_by or ())]
        self.metrics = None
        if filter is None and not keys:
            fast = self._aggregate_from_stats(specs)
            if fast is not None:
                return fast
        from ..reader.columnar import DEVICE_BACKENDS

        wire, posthoc = _lower_filter(filter)
        if self.backend in DEVICE_BACKENDS and posthoc is None:
            from ..api import aggregate_on_device
            from ..parallel.query import NotOnDevice

            try:
                result, self.metrics = aggregate_on_device(
                    self.files, self.copybook_contents, self.options,
                    self.backend, specs, from_wire(wire) if wire else None,
                    keys, self.schema)
                return result
            except NotOnDevice:
                pass  # raised before a byte is read: decode instead
        return self._aggregate_by_decode(specs, filter, keys)

    def _aggregate_from_stats(self, specs) -> Optional[dict]:
        """Stats-only answer, or None (then the caller decodes)."""
        from ..api import parse_options

        params, _opts = parse_options(dict(self.options))
        if not params.use_stats:
            return None
        from ..plan.cache import copybook_for_params
        from ..stats.aggregate import (aggregates_from_profiles,
                                       load_all_profiles)

        profiles = load_all_profiles(self.files, self.copybook_contents,
                                     params)
        if profiles is None:
            return None
        copybook = copybook_for_params(self.copybook_contents, params)
        return aggregates_from_profiles(profiles, copybook, specs)

    def _decoded_table(self, columns, filter_):
        """The decoded table `_aggregate_by_decode` computes over. The
        scalar oracle (``backend="host"``) has no pushdown: its table is
        decoded whole and filtered by the same bound expression
        (query/pushdown.BoundFilter), so a literal meets a field the
        same way on every backend."""
        if filter_ is None or self.backend != "host":
            return self.to_table(columns=columns, filter=filter_)
        wire, posthoc = _lower_filter(filter_)
        table = self.to_table(columns=None)
        if posthoc is not None:
            import pyarrow.dataset as pads

            return pads.dataset(table).to_table(filter=posthoc)
        from ..api import parse_options
        from ..plan.cache import copybook_for_params
        from .pushdown import BoundFilter

        params, _opts = parse_options(dict(self.options))
        bound = BoundFilter(
            from_wire(wire),
            copybook_for_params(self.copybook_contents, params), params)
        return table.filter(bound.eval_table(table))

    def _aggregate_by_decode(self, specs, filter_, keys=()):
        """The ground-truth path: decode, then pyarrow compute. The
        semantics here DEFINE what the stats and device paths must
        reproduce. Products and sums are made in decimal256 (float64
        where a factor is a float), so nothing rounds or wraps."""
        import decimal

        import pyarrow as pa
        import pyarrow.compute as pc

        from ..stats.aggregate import average, key_order, shape_result
        from ..stats.collect import leaf_columns

        wanted = sorted({f for spec in specs for f in spec.fields}
                        | set(keys))
        known = set(self.schema.names)
        cols = (wanted if wanted and all(f in known for f in wanted)
                else None)  # nested leaves need the full-width decode
        table = self._decoded_table(cols, filter_)
        leaves = leaf_columns(table)

        def leaf(name):
            if name not in leaves:
                raise KeyError(
                    f"aggregate field {name!r} is not a primitive "
                    "column of the decoded output")
            return leaves[name][1]

        def exact(col, floats: bool):
            if floats:
                return pc.cast(col, pa.float64())
            if pa.types.is_integer(col.type):
                return pc.cast(col, pa.decimal256(20, 0))
            return pc.cast(col, pa.decimal256(col.type.precision,
                                              col.type.scale))

        def values_of(spec):
            """(the column a sum, avg, min or max of `spec` reduces,
            whether its sum is an integer)."""
            cols_ = [leaf(f.field) for f in spec.factors]
            if spec.fn in ("min", "max"):
                return cols_[0], False
            floats = any(pa.types.is_floating(c.type) for c in cols_)
            one = (pa.scalar(1.0) if floats else
                   pa.scalar(decimal.Decimal(1), pa.decimal256(1, 0)))
            product = None
            for factor, col in zip(spec.factors, cols_):
                term = exact(col, floats)
                if factor.sign:
                    term = (pc.add if factor.sign > 0
                            else pc.subtract)(one, term)
                product = term if product is None \
                    else pc.multiply(product, term)
            return product, all(pa.types.is_integer(c.type) for c in cols_)

        work = {f"k{i}": leaf(name) for i, name in enumerate(keys)}
        wants, integral = [([], "count_all")], {}
        for j, spec in enumerate(specs):
            if spec.fn == "count":
                continue
            work[f"v{j}"], integral[j] = values_of(spec)
            wants += ([(f"v{j}", "sum"), (f"v{j}", "count")]
                      if spec.fn in ("sum", "avg") else [(f"v{j}", spec.fn)])
        if keys:
            grouped = pa.table(work).group_by(
                list(work)[:len(keys)], use_threads=False).aggregate(wants)
            rows = grouped.to_pylist()
        else:
            # one group, there even over no rows at all
            row = {"count_all": table.num_rows}
            for name, fn in wants[1:]:
                value = {"sum": pc.sum, "count": pc.count, "min": pc.min,
                         "max": pc.max}[fn](work[name]).as_py()
                row[f"{name}_{fn}"] = value
            rows = [row]
        groups = []
        for row in rows:
            values = {}
            for j, spec in enumerate(specs):
                if spec.fn == "count":
                    values[spec.text] = row["count_all"]
                elif spec.fn in ("min", "max"):
                    values[spec.text] = row[f"v{j}_{spec.fn}"]
                else:
                    total = row[f"v{j}_sum"]
                    if total is not None and integral[j]:
                        total = int(total)
                    values[spec.text] = (
                        total if spec.fn == "sum"
                        else average(total, row[f"v{j}_count"]))
            groups.append((tuple(row[f"k{i}"] for i in range(len(keys))),
                           values))
        groups.sort(key=lambda g: key_order(g[0]))
        return shape_result(specs, keys, groups,
                            [leaf(k).type for k in keys])

    def __repr__(self) -> str:
        return (f"<CobolDataset files={len(self.files)} "
                f"columns={len(self.schema.names)}>")


def dataset(path, copybook: Optional[str] = None,
            copybook_contents=None, backend: str = "numpy",
            **options) -> CobolDataset:
    """Open mainframe file(s) as a pyarrow-dataset-shaped object.

    `path`/`copybook`/`options` follow ``read_cobol``; the returned
    dataset's schema is derived up front from the copybook + options
    (no data is read until a scanner materializes)."""
    from ..api import (list_input_files, load_copybook_contents,
                       parse_options)
    from ..plan.cache import copybook_for_params
    from ..reader.arrow_out import arrow_schema
    from ..reader.columnar import validate_backend
    from ..reader.schema import output_schema_for

    validate_backend(backend)
    contents = load_copybook_contents(copybook, copybook_contents)
    files = list_input_files(path)
    if not files:
        raise FileNotFoundError(f"No input files found for path {path}")
    # schema derivation must see the caller's options, but select/filter
    # belong to each SCANNER, not the dataset identity
    probe_options = {k: v for k, v in options.items()
                     if k not in ("select", "filter")}
    params, _opts = parse_options(dict(probe_options))
    copybook_obj = copybook_for_params(contents, params)
    output_schema = output_schema_for(copybook_obj, params,
                                      params.needs_var_len_reader)
    schema = arrow_schema(output_schema.schema)
    generated = frozenset(
        n for n in schema.names
        if n in ("File_Id", "Record_Id", "Record_Byte_Length")
        or n.startswith("Seg_Id")
        or (params.input_file_name_column
            and n == params.input_file_name_column)
        or (params.corrupt_record_column
            and n == params.corrupt_record_column))
    return CobolDataset(files, contents, backend, probe_options, schema,
                        generated)
