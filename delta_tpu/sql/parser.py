"""SQL front end for the Delta statements — token-based recursive descent.

Scope is a superset of the reference grammar
(`antlr4/io/delta/sql/parser/DeltaSqlBase.g4:74-81`): VACUUM,
DESCRIBE HISTORY | DETAIL, GENERATE, CONVERT TO DELTA — plus the DML and
DDL the reference delegates to Spark SQL but a standalone engine must parse
itself: DELETE, UPDATE, MERGE INTO, CREATE [OR REPLACE] TABLE (columns,
generated columns, PARTITIONED BY, TBLPROPERTIES) and ALTER TABLE
(properties, columns incl. FIRST/AFTER, constraints).

The statement structure parses from the token stream (`sql/lexer.py` — a
real tokenizer, so keywords inside string literals, comments, and newlines
cannot mis-parse); embedded *expressions* (WHERE / ON / SET bodies / CHECK)
are sliced out of the source verbatim via token offsets and handed to the
expression parser (`expr/parser.py`), mirroring how the reference's
delegating parser hands expression text to Spark.

Table references are ``delta.`/path``` / ``parquet.`/path``` or a bare
quoted path, like the reference's path-based identifiers
(`DeltaTableIdentifier.scala`).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from delta_tpu.log.deltalog import DeltaLog
from delta_tpu.schema.types import StructField, StructType
from delta_tpu.sql.lexer import Token, tokenize
from delta_tpu.utils.errors import DeltaAnalysisError, DeltaParseError
from delta_tpu.utils import errors, telemetry

__all__ = ["execute_sql", "parse_statement"]


_TYPES = {
    "int": "IntegerType", "integer": "IntegerType", "bigint": "LongType",
    "long": "LongType", "smallint": "ShortType", "short": "ShortType",
    "tinyint": "ByteType", "byte": "ByteType", "string": "StringType",
    "varchar": "StringType", "double": "DoubleType", "float": "FloatType",
    "real": "FloatType", "boolean": "BooleanType", "bool": "BooleanType",
    "date": "DateType", "timestamp": "TimestampType", "binary": "BinaryType",
}


def _make_type(name: str, args: List[str]):
    import delta_tpu.schema.types as T

    low = name.lower()
    if low == "decimal":
        try:
            p = int(args[0]) if args else 10
            s = int(args[1]) if len(args) > 1 else 0
        except ValueError:
            raise errors.sql_invalid_decimal(args)
        return T.DecimalType(p, s)
    if low in ("char", "varchar") and args:
        try:
            n = int(args[0])
        except ValueError:
            raise errors.sql_unsupported_type(f"{name}({args[0]})")
        return T.CharType(n) if low == "char" else T.VarcharType(n)
    cls = _TYPES.get(low)
    if cls is None:
        raise errors.sql_unsupported_type(name)
    return getattr(T, cls)()


class _Parser:
    def __init__(self, sql: str):
        self.sql = sql
        self.toks: List[Token] = tokenize(sql)
        self.i = 0

    # -- token plumbing ----------------------------------------------------

    def peek(self, ahead: int = 0) -> Token:
        j = min(self.i + ahead, len(self.toks) - 1)
        return self.toks[j]

    def next(self) -> Token:
        t = self.toks[self.i]
        if t.kind != "END":
            self.i += 1
        return t

    def at_end(self) -> bool:
        t = self.peek()
        return t.kind == "END" or (t.kind == "PUNCT" and t.value == ";")

    def accept_word(self, *words: str) -> Optional[Token]:
        if self.peek().is_word(*words):
            return self.next()
        return None

    def expect_word(self, *words: str) -> Token:
        t = self.next()
        if not t.is_word(*words):
            raise errors.sql_expected(' or '.join(words), t.start, t.value)
        return t

    def accept_punct(self, p: str) -> bool:
        t = self.peek()
        if t.kind == "PUNCT" and t.value == p:
            self.next()
            return True
        return False

    def expect_punct(self, p: str) -> None:
        t = self.next()
        if not (t.kind == "PUNCT" and t.value == p):
            raise errors.sql_expected(repr(p), t.start, t.value)

    def expect_end(self) -> None:
        if not self.at_end():
            t = self.peek()
            raise errors.sql_trailing_input(t.start, t.value)

    # -- shared pieces -----------------------------------------------------

    def table_path(self) -> Tuple[str, str]:
        """[delta|parquet] . `path` | `path` | 'path' | bare path | name.

        Returns ("path", p) for explicit paths and ("name", n) for bare
        identifiers (resolved through the catalog at run time)."""
        t = self.next()
        if t.kind == "WORD" and t.value.lower() in ("delta", "parquet") and (
            self.peek().kind == "PUNCT" and self.peek().value == "."
        ):
            self.next()  # '.'
            ident = self.next()
            if ident.kind not in ("QUOTED_IDENT", "WORD", "STRING"):
                raise errors.sql_expected_table_identifier(t.value, ident.start)
            # delta.`/p` is a path; delta.name is a catalog name
            if ident.kind == "WORD":
                return ("name", ident.value)
            return ("path", ident.value)
        if t.kind in ("QUOTED_IDENT", "STRING"):
            return ("path", t.value)
        path_start = (t.kind == "WORD") or (
            t.kind == "PUNCT" and t.value in "./"
        )
        if not path_start:
            raise errors.sql_expected('table reference', t.start)
        # greedy run of ADJACENT tokens (no whitespace) forming a bare path
        # (/tmp/x, ./rel/x) or a dotted catalog name
        text = t.value
        end = t.end
        while True:
            nxt = self.peek()
            if nxt.kind == "END" or nxt.start != end:
                break
            if nxt.kind in ("WORD", "NUMBER") or (
                nxt.kind == "PUNCT" and nxt.value in "./-"
            ):
                text += nxt.value
                end = nxt.end
                self.next()
            else:
                break
        return ("path", text) if "/" in text else ("name", text)

    def ident(self) -> str:
        t = self.next()
        if t.kind in ("WORD", "QUOTED_IDENT"):
            return t.value
        raise errors.sql_expected('identifier', t.start)

    def slice_expr(
        self, stop_words: Tuple[str, ...] = (), stop_comma: bool = False
    ) -> Optional[str]:
        """Source text from here to the next boundary: a depth-0 stop
        keyword, an unbalanced ')', a depth-0 comma (when ``stop_comma``),
        ';' or end of input. CASE...END bodies are opaque — their WHEN/THEN
        keywords never terminate the slice. Returns None when empty."""
        depth = 0
        case_depth = 0
        start_tok = self.peek()
        last_end = start_tok.start
        while True:
            t = self.peek()
            if t.kind == "END" or (
                t.kind == "PUNCT" and t.value == ";" and depth == 0
            ):
                break
            if t.kind == "PUNCT" and t.value == "(":
                depth += 1
            elif t.kind == "PUNCT" and t.value == ")":
                if depth == 0:
                    break
                depth -= 1
            elif t.kind == "WORD" and t.value.upper() == "CASE":
                case_depth += 1
            elif t.kind == "WORD" and t.value.upper() == "END" and case_depth > 0:
                case_depth -= 1
            elif depth == 0 and case_depth == 0:
                if stop_comma and t.kind == "PUNCT" and t.value == ",":
                    break
                if t.kind == "WORD" and t.value.upper() in stop_words:
                    break
            self.next()
            last_end = t.end
        text = self.sql[start_tok.start:last_end].strip()
        return text or None

    def number(self, as_int: bool = False):
        t = self.next()
        if t.kind != "NUMBER":
            raise errors.sql_expected('a number', t.start)
        try:
            return int(t.value) if as_int else float(t.value)
        except ValueError:
            raise errors.sql_invalid_number(t.value, 'integer' if as_int else 'number', t.start)

    def string_or_number(self) -> str:
        t = self.next()
        if t.kind in ("STRING", "NUMBER", "WORD"):
            return t.value
        raise errors.sql_expected('literal', t.start)

    def properties(self) -> Dict[str, str]:
        """( 'k' = 'v' [, ...] )"""
        self.expect_punct("(")
        out: Dict[str, str] = {}
        while True:
            key = self.string_or_number()
            # dotted bare keys: delta.appendOnly
            while self.accept_punct("."):
                key += "." + self.string_or_number()
            self.expect_punct("=")
            out[key] = self.string_or_number()
            if self.accept_punct(")"):
                return out
            self.expect_punct(",")

    def column_type(self):
        name = self.ident()
        args: List[str] = []
        if self.accept_punct("("):
            while not self.accept_punct(")"):
                t = self.next()
                if t.kind == "NUMBER":
                    args.append(t.value)
                elif not (t.kind == "PUNCT" and t.value == ","):
                    raise errors.sql_bad_type_argument(t.start, t.value)
        return _make_type(name, args)

    def column_def(self) -> StructField:
        """name TYPE [GENERATED ALWAYS AS (expr)] [NOT NULL] [COMMENT 's'].
        Dotted names (``s.x``) address nested structs (ALTER ADD COLUMNS)."""
        name = self.ident()
        while self.accept_punct("."):
            name += "." + self.ident()
        dtype = self.column_type()
        nullable = True
        metadata: Dict[str, Any] = {}
        while True:
            if self.accept_word("NOT"):
                self.expect_word("NULL")
                nullable = False
            elif self.accept_word("COMMENT"):
                t = self.next()
                if t.kind != "STRING":
                    raise errors.sql_expected('comment string', t.start)
                metadata["comment"] = t.value
            elif self.accept_word("GENERATED"):
                self.expect_word("ALWAYS")
                self.expect_word("AS")
                self.expect_punct("(")
                expr = self.slice_expr()
                if expr is None:
                    raise DeltaParseError("Empty generation expression")
                self.expect_punct(")")
                from delta_tpu.schema.generated import GENERATION_EXPRESSION_KEY

                metadata[GENERATION_EXPRESSION_KEY] = expr
            else:
                break
        return StructField(name, dtype, nullable, metadata)

    def column_name_list(self) -> List[str]:
        self.expect_punct("(")
        out = [self.ident()]
        while self.accept_punct(","):
            out.append(self.ident())
        self.expect_punct(")")
        return out


def _log_for(ref: Tuple[str, str]) -> DeltaLog:
    kind, value = ref
    if kind == "name":
        from delta_tpu.catalog.catalog import resolve_identifier

        return DeltaLog.for_table(resolve_identifier(value))
    return DeltaLog.for_table(value)


def parse_statement(sql: str):
    """Parse one statement into a zero-argument runner (late-bound command
    construction so parse errors surface before any table IO)."""
    p = _Parser(sql)
    t = p.peek()
    if t.kind != "WORD":
        raise errors.sql_expected_statement(t.value)
    head = t.value.upper()
    if head == "SELECT":
        return _select(p)
    if head == "INSERT":
        return _insert(p)
    if head == "VACUUM":
        return _vacuum(p)
    if head == "DESCRIBE" or head == "DESC":
        return _describe(p)
    if head == "GENERATE":
        return _generate(p)
    if head == "CONVERT":
        return _convert(p)
    if head == "DELETE":
        return _delete(p)
    if head == "UPDATE":
        return _update(p)
    if head == "MERGE":
        return _merge(p)
    if head == "CREATE":
        return _create(p)
    if head == "ALTER":
        return _alter(p)
    if head == "RESTORE":
        return _restore(p)
    raise errors.unsupported_sql_statement(sql)


def execute_sql(sql: str) -> Any:
    """Parse and run one Delta statement; returns the command's result."""
    return parse_statement(sql)()


# -- statement parsers -------------------------------------------------------


def _parse_aggregate(text: str):
    """(func, inner_sql|'*') when ``text`` is a top-level aggregate call
    (COUNT/SUM/AVG/MIN/MAX), else None."""
    import re as _re

    m = _re.match(r"(?is)^\s*(count|sum|avg|min|max)\s*\((.*)\)\s*$", text)
    if not m:
        return None
    inner = m.group(2).strip()
    # the closing paren must match the opening one (reject `min(a) + max(b)`)
    depth = 0
    for ch in m.group(2):
        depth += ch == "("
        depth -= ch == ")"
        if depth < 0:
            return None
    return m.group(1).lower(), inner


def _select(p: _Parser):
    """SELECT <*|expr|aggregate [AS alias], ...> FROM <table>
    [VERSION AS OF n | TIMESTAMP AS OF ts] [WHERE pred]
    [GROUP BY col, ...] [ORDER BY col [ASC|DESC], ...] [LIMIT n] — the read
    surface reference users get from Spark SQL (`DeltaTableV2` + relation),
    routed through the engine's scan planner (`exec/scan.scan_to_table`).
    Aggregates: COUNT(*)/COUNT/SUM/AVG/MIN/MAX, optionally grouped. Returns
    an Arrow table."""
    import re as _re

    p.expect_word("SELECT")
    star = False
    items: List[Tuple[str, Optional[str]]] = []  # (expr sql, alias)
    if p.accept_punct("*"):
        star = True
    else:
        while True:
            text = p.slice_expr(stop_words=("FROM",), stop_comma=True)
            if text is None:
                raise errors.sql_expected("projection expression",
                                          p.peek().start)
            m = _re.search(r"(?is)\s+as\s+([A-Za-z_][A-Za-z_0-9]*|`[^`]+`)\s*$",
                           text)
            alias = None
            if m:
                alias = m.group(1).strip("`")
                text = text[: m.start()]
            items.append((text.strip(), alias))
            if not p.accept_punct(","):
                break
    p.expect_word("FROM")
    path = p.table_path()
    version = timestamp = None
    if p.accept_word("VERSION"):
        p.expect_word("AS")
        p.expect_word("OF")
        version = int(p.number(as_int=True))
    elif p.accept_word("TIMESTAMP"):
        p.expect_word("AS")
        p.expect_word("OF")
        t = p.next()
        if t.kind not in ("STRING", "NUMBER"):
            raise errors.sql_expected("timestamp literal", t.start)
        timestamp = t.value
    cond = None
    if p.accept_word("WHERE"):
        cond = p.slice_expr(stop_words=("GROUP", "ORDER", "LIMIT"))
        if cond is None:
            raise DeltaParseError("Empty WHERE clause")
    group_by: List[str] = []
    if p.accept_word("GROUP"):
        p.expect_word("BY")
        while True:
            group_by.append(p.ident())
            if not p.accept_punct(","):
                break
    order: List[Tuple[str, str]] = []
    if p.accept_word("ORDER"):
        p.expect_word("BY")
        while True:
            col = p.ident()
            direction = "ascending"
            if p.accept_word("DESC"):
                direction = "descending"
            else:
                p.accept_word("ASC")
            order.append((col, direction))
            if not p.accept_punct(","):
                break
    limit = None
    if p.accept_word("LIMIT"):
        limit = int(p.number(as_int=True))
    p.expect_end()

    def run():
        # the query's root span: the log's update, then either the device
        # aggregate or the scan (`delta.scan`) and the host's aggregate
        with telemetry.record_operation("delta.sql.select") as ev:
            out = select()
            ev.data["rowsOut"] = out.num_rows
            return out

    def select():
        from delta_tpu.exec.scan import scan_to_table
        from delta_tpu.expr import ir as _ir
        from delta_tpu.expr.parser import parse_expression
        from delta_tpu.expr.vectorized import evaluate

        # one span for what comes before the plan: the handle, the snapshot
        # (`delta.log.update` inside it) and the select list and predicate
        # parsed against its schema
        with telemetry.record_operation("delta.sql.select.resolve"):
            log = _log_for(path)
            sel_version, sel_timestamp = version, timestamp
            if not log.table_exists and path[0] == "path":
                # `delta.\`/t@v3\`` embedded time travel (reads only)
                from delta_tpu.log.deltalog import extract_path_time_travel

                spec = extract_path_time_travel(path[1])
                if spec is not None:
                    base_log = DeltaLog.for_table(spec[0])
                    if base_log.table_exists:
                        log = base_log
                        if sel_version is None and sel_timestamp is None:
                            sel_version, sel_timestamp = spec[1], spec[2]
            snap = log.snapshot_for(sel_version, sel_timestamp)
            schema_cols = [f.name for f in snap.metadata.schema.fields]
            lower = {c.lower(): c for c in schema_cols}
            parsed_items = None
            read_cols = None
            has_agg = False
            if not star:
                # projection pushdown: decode only the referenced columns
                parsed_items = []
                needed = set()
                for text, alias in items:
                    key = text.strip("`").lower()
                    agg = _parse_aggregate(text)
                    if agg is not None:
                        func, inner = agg
                        if inner == "*":
                            if func != "count":
                                raise errors.sql_star_only_in_count(func)
                            inner_e = None
                        else:
                            inner_e = parse_expression(inner)
                            for r in _ir.references(inner_e):
                                if r.lower() in lower:
                                    needed.add(lower[r.lower()])
                        parsed_items.append(
                            ("agg", (func, inner_e), alias or text))
                        has_agg = True
                    elif key in lower:
                        parsed_items.append(("col", lower[key], alias))
                        needed.add(lower[key])
                    else:
                        e = parse_expression(text)
                        parsed_items.append(("expr", e, alias or text))
                        for r in _ir.references(e):
                            if r.lower() in lower:
                                needed.add(lower[r.lower()])
                for g in group_by:
                    if g.strip("`").lower() in lower:
                        needed.add(lower[g.strip("`").lower()])
                for col, _dir in order:
                    if col.strip("`").lower() in lower:
                        needed.add(lower[col.strip("`").lower()])
                if needed:
                    read_cols = [c for c in schema_cols if c in needed]
                elif has_agg and schema_cols:
                    # aggregate-only projection (e.g. COUNT(*)): one narrow
                    # column is enough to carry the row count
                    read_cols = [schema_cols[0]]
                else:
                    read_cols = None
            if (has_agg or group_by) and star:
                raise DeltaParseError("SELECT * cannot be combined with GROUP BY")
            agg_filters = [parse_expression(cond)] if has_agg and cond else []
        out = None
        hidden: List[str] = []
        order_keys = [c.strip("`").lower() for c, _d in order]
        if has_agg:
            # an aggregate over columns that have lanes, ungrouped or
            # grouped by a few values, is answered from them
            # (ops/column_aggregate), or declines
            from delta_tpu.ops.column_aggregate import device_aggregate

            out = device_aggregate(snap, agg_filters, parsed_items, group_by,
                                   order_keys)
            if out is not None:
                # group keys carried only for ORDER BY follow the select list
                hidden = out.column_names[len(parsed_items):]
        # answered from the lanes, or the scan decodes the columns it needs
        table = None if out is not None else scan_to_table(
            snap, filters=[cond] if cond else (), columns=read_cols)
        pre_sort = False
        if table is not None and (has_agg or group_by):
            out, hidden = _run_aggregate(table, parsed_items, group_by,
                                         order_keys, evaluate)
        elif table is not None:
            # ORDER BY resolves against source columns first (SQL allows
            # sorting by non-projected columns), then aliases
            src_lower = {c.lower(): c for c in table.column_names}
            pre_sort = bool(order) and all(
                c.strip("`").lower() in src_lower for c, _d in order)
            if pre_sort:
                table = table.sort_by([
                    (src_lower[c.strip("`").lower()], d) for c, d in order])
            if parsed_items is not None:
                import pyarrow as pa

                arrays, names = [], []
                for kind, payload, alias in parsed_items:
                    if kind == "col":
                        arrays.append(table.column(payload))
                        names.append(alias or payload)
                    else:
                        arrays.append(evaluate(payload, table))
                        names.append(alias)
                # from_arrays keeps duplicate output names (SELECT id, id)
                out = pa.Table.from_arrays(
                    [a.combine_chunks() if isinstance(a, pa.ChunkedArray) else a
                     for a in arrays], names=names)
            else:
                out = table
        if order and not pre_sort:
            # the sort of the answer: over a few rows, Arrow's fixed cost
            with telemetry.record_operation("delta.sql.select.order",
                                            {"rows": out.num_rows}):
                out_lower = {c.lower(): c for c in out.column_names}
                keys = []
                for col, direction in order:
                    real = out_lower.get(col.strip("`").lower())
                    if real is None:
                        raise errors.column_not_found_in_table(
                            col, out.column_names)
                    keys.append((real, direction))
                out = out.sort_by(keys)
        if hidden:
            # group keys carried only for ORDER BY drop out of the result
            out = out.drop_columns(hidden)
        if limit is not None:
            out = out.slice(0, limit)
        return out

    return run


def _run_aggregate(table, parsed_items, group_by, order_keys, evaluate):
    """Execute the aggregate leg of a SELECT: non-aggregate items must be
    GROUP BY keys; aggregates compute over Arrow's hash aggregation (or
    whole-table kernels when ungrouped). Returns (table, hidden) where
    ``hidden`` are group keys appended ONLY so ORDER BY can resolve them —
    the caller drops them after sorting."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.compute as pc

    tbl_lower = {c.lower(): c for c in table.column_names}
    group_keys = []
    for g in group_by:
        real = tbl_lower.get(g.strip("`").lower())
        if real is None:
            raise errors.column_not_found_in_table(g, table.column_names)
        group_keys.append(real)
    group_set = {g.lower() for g in group_keys}

    work_cols: dict = {g: table.column(g) for g in group_keys}
    aggs = []   # (workname, arrow_func, outname) in projection order
    layout = []  # ("key", real, outname) | ("agg", workname, outname)
    fn_map = {"count": "count", "sum": "sum", "avg": "mean",
              "min": "min", "max": "max"}
    for i, (kind, payload, alias) in enumerate(parsed_items):
        if kind == "col":
            if payload.lower() not in group_set:
                raise errors.sql_column_needs_group_by(payload)
            layout.append(("key", payload, alias or payload))
        elif kind == "expr":
            raise DeltaParseError(
                "Non-aggregate expressions in an aggregate SELECT must be "
                "GROUP BY columns"
            )
        else:
            func, inner_e = payload
            work = f"__agg{i}"
            if inner_e is None:  # COUNT(*): count a non-null constant
                work_cols[work] = pa.chunked_array(
                    [pa.array(np.ones(table.num_rows, np.int8))])
            else:
                work_cols[work] = evaluate(inner_e, table)
            aggs.append((work, fn_map[func], alias))
            layout.append(("agg", work, alias))

    work = pa.table(work_cols)
    if group_keys:
        res = work.group_by(group_keys).aggregate(
            [(w, f) for w, f, _ in aggs])
        agg_out = {w: f"{w}_{f}" for w, f, _ in aggs}
    else:
        cols = {}
        for w, f, _ in aggs:
            col = work.column(w)
            if f == "count":
                cols[f"{w}_{f}"] = pa.array([len(col) - col.null_count])
            else:
                kern = {"sum": pc.sum, "mean": pc.mean,
                        "min": pc.min, "max": pc.max}[f]
                # the kernel scalar carries the aggregate's natural type even
                # when its value is null (empty table) — keep it, or an
                # all-null untyped column breaks INSERT...SELECT casts
                s = kern(col)
                cols[f"{w}_{f}"] = pa.array([s.as_py()], type=s.type)
        res = pa.table(cols)
        agg_out = {w: f"{w}_{f}" for w, f, _ in aggs}

    # ORDER BY may reference a group key the projection dropped: carry it
    # through under its real name and let the caller drop it after sorting
    hidden = []
    projected = {outname.lower() for _k, _n, outname in layout}
    for g in group_keys:
        if g.lower() not in projected and g.lower() in order_keys:
            layout.append(("key", g, g))
            hidden.append(g)
    arrays, names = [], []
    for kind, name, outname in layout:
        src = name if kind == "key" else agg_out[name]
        col = res.column(src)
        arrays.append(col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col)
        names.append(outname)
    return pa.Table.from_arrays(arrays, names=names), hidden


def _insert(p: _Parser):
    """INSERT INTO|OVERWRITE <table> [(col, ...)] VALUES (...), ... |
    SELECT ... — the write companion of the SELECT surface (Spark handles
    this for the reference; here it routes through WriteIntoDelta)."""
    p.expect_word("INSERT")
    mode = "append"
    if p.accept_word("OVERWRITE"):
        mode = "overwrite"
        p.accept_word("INTO", "TABLE")
    else:
        p.expect_word("INTO")
    path = p.table_path()
    cols: Optional[List[str]] = None
    if p.accept_punct("("):
        cols = []
        while True:
            cols.append(p.ident())
            if p.accept_punct(")"):
                break
            p.expect_punct(",")
    if p.peek().is_word("SELECT"):
        select_run = _select(p)

        def run():
            from delta_tpu.commands.write import WriteIntoDelta

            log = _log_for(path)
            data = select_run()
            if cols is not None:
                if len(cols) != data.num_columns:
                    raise errors.sql_insert_arity_mismatch(
                        len(cols), data.num_columns)
                data = data.rename_columns(cols)
            else:
                # INSERT ... SELECT binds positionally: the projection must
                # cover the whole target schema (silent null-fill of missing
                # columns is a data bug, not a convenience)
                target = [f.name for f in log.update().metadata.schema.fields]
                if len(target) != data.num_columns:
                    raise errors.sql_insert_arity_mismatch(
                        len(target), data.num_columns)
                data = data.rename_columns(target)
            return WriteIntoDelta(log, mode, data).run()

        return run
    p.expect_word("VALUES")
    rows: List[List[str]] = []
    while True:
        p.expect_punct("(")
        vals: List[str] = []
        while True:
            v = p.slice_expr(stop_comma=True)
            if v is None:
                raise DeltaParseError("Empty VALUES expression")
            vals.append(v)
            if p.accept_punct(")"):
                break
            p.expect_punct(",")
        rows.append(vals)
        if not p.accept_punct(","):
            break
    p.expect_end()
    widths = {len(r) for r in rows}
    if len(widths) != 1:
        raise errors.sql_insert_arity_mismatch(min(widths), max(widths))
    if cols is not None and len(cols) != next(iter(widths)):
        raise errors.sql_insert_arity_mismatch(len(cols), next(iter(widths)))

    def run():
        import pyarrow as pa

        from delta_tpu.commands.write import WriteIntoDelta
        from delta_tpu.expr.parser import parse_expression
        from delta_tpu.expr.vectorized import arrow_type_for

        log = _log_for(path)
        schema = log.update().metadata.schema
        names = cols if cols is not None else [f.name for f in schema.fields]
        # parse time already checked the explicit-column-list arity; this
        # guards the schema-width binding when no column list was given
        if cols is None and len(names) != next(iter(widths)):
            raise errors.sql_insert_arity_mismatch(len(names), next(iter(widths)))
        types = {f.name.lower(): arrow_type_for(f.data_type) for f in schema.fields}
        arrays = {}
        for j, name in enumerate(names):
            vals = [parse_expression(r[j]).eval({}) for r in rows]
            at = types.get(name.lower())
            arrays[name] = pa.array(vals, type=at)
        data = pa.table(arrays)
        return WriteIntoDelta(log, mode, data).run()

    return run


def _vacuum(p: _Parser):
    p.expect_word("VACUUM")
    path = p.table_path()
    hours = None
    dry = False
    if p.accept_word("RETAIN"):
        hours = p.number()
        p.expect_word("HOURS", "HOUR")
    if p.accept_word("DRY"):
        p.expect_word("RUN")
        dry = True
    p.expect_end()

    def run():
        from delta_tpu.commands.vacuum import VacuumCommand

        return VacuumCommand(_log_for(path), hours, dry_run=dry).run()

    return run


def _restore(p: _Parser):
    """``RESTORE TABLE t TO VERSION AS OF n`` /
    ``RESTORE TABLE t TO TIMESTAMP AS OF 'ts'`` (beyond the reference
    grammar; modern Delta's restore statement)."""
    p.expect_word("RESTORE")
    p.accept_word("TABLE")
    path = p.table_path()
    p.expect_word("TO")
    which = p.expect_word("VERSION", "TIMESTAMP").value.upper()
    p.expect_word("AS")
    p.expect_word("OF")
    if which == "VERSION":
        version, timestamp = p.number(as_int=True), None
    else:
        version, timestamp = None, p.string_or_number()
    p.expect_end()

    def run():
        from delta_tpu.commands.restore import RestoreCommand

        cmd = RestoreCommand(_log_for(path), version=version, timestamp=timestamp)
        cmd.run()
        return cmd.metrics

    return run


def _describe(p: _Parser):
    p.expect_word("DESCRIBE", "DESC")
    which = p.expect_word("HISTORY", "DETAIL").value.upper()
    path = p.table_path()
    limit = None
    if which == "HISTORY" and p.accept_word("LIMIT"):
        limit = p.number(as_int=True)
    p.expect_end()

    def run():
        from delta_tpu.commands.describe import describe_detail, describe_history

        log = _log_for(path)
        if which == "HISTORY":
            return describe_history(log, limit)
        return describe_detail(log)

    return run


def _generate(p: _Parser):
    p.expect_word("GENERATE")
    t = p.next()
    mode = t.value if t.kind in ("WORD", "STRING") else None
    if mode is None or mode.lower() != "symlink_format_manifest":
        raise errors.unsupported_generate_mode(mode)
    p.expect_word("FOR")
    p.expect_word("TABLE")
    path = p.table_path()
    p.expect_end()

    def run():
        from delta_tpu.hooks.symlink_manifest import generate_full_manifest

        return generate_full_manifest(_log_for(path))

    return run


def _convert(p: _Parser):
    p.expect_word("CONVERT")
    p.expect_word("TO")
    p.expect_word("DELTA")
    path = p.table_path()
    part_schema = None
    if p.accept_word("PARTITIONED"):
        p.expect_word("BY")
        p.expect_punct("(")
        fields = [p.column_def()]
        while p.accept_punct(","):
            fields.append(p.column_def())
        p.expect_punct(")")
        part_schema = StructType(fields)
    p.expect_end()

    def run():
        from delta_tpu.commands.convert import ConvertToDeltaCommand

        return ConvertToDeltaCommand(
            _log_for(path), partition_schema=part_schema
        ).run()

    return run


def _delete(p: _Parser):
    p.expect_word("DELETE")
    p.expect_word("FROM")
    path = p.table_path()
    cond = None
    if p.accept_word("WHERE"):
        cond = p.slice_expr()
        if cond is None:
            raise DeltaParseError("Empty WHERE clause")
    p.expect_end()

    def run():
        from delta_tpu.commands.delete import DeleteCommand

        cmd = DeleteCommand(_log_for(path), cond)
        cmd.run()
        return cmd.metrics

    return run


def _set_assignments(p: _Parser, stop_words: Tuple[str, ...]) -> Dict[str, str]:
    """col = expr [, col = expr ...] with verbatim expression slices."""
    sets: Dict[str, str] = {}
    while True:
        col = p.ident()
        while p.accept_punct("."):
            col += "." + p.ident()
        p.expect_punct("=")
        expr = p.slice_expr(stop_words, stop_comma=True)
        if expr is None:
            raise errors.sql_empty_set_expression(col)
        sets[col] = expr
        if not p.accept_punct(","):
            return sets


def _update(p: _Parser):
    p.expect_word("UPDATE")
    path = p.table_path()
    p.expect_word("SET")
    sets = _set_assignments(p, ("WHERE",))
    cond = None
    if p.accept_word("WHERE"):
        cond = p.slice_expr()
        if cond is None:
            raise DeltaParseError("Empty WHERE clause")
    p.expect_end()

    def run():
        from delta_tpu.commands.update import UpdateCommand

        cmd = UpdateCommand(_log_for(path), sets, cond)
        cmd.run()
        return cmd.metrics

    return run


def _merge(p: _Parser):
    from delta_tpu.commands.merge import MergeClause

    p.expect_word("MERGE")
    p.expect_word("INTO")
    target_path = p.table_path()
    target_alias = None
    if p.accept_word("AS"):
        target_alias = p.ident()
    elif p.peek().kind == "WORD" and not p.peek().is_word("USING"):
        target_alias = p.ident()
    p.expect_word("USING")
    source_path = p.table_path()
    source_alias = None
    if p.accept_word("AS"):
        source_alias = p.ident()
    elif p.peek().kind == "WORD" and not p.peek().is_word("ON"):
        source_alias = p.ident()
    p.expect_word("ON")
    cond = p.slice_expr(("WHEN",))
    if cond is None:
        raise DeltaParseError("Empty MERGE condition")

    matched: List[MergeClause] = []
    not_matched: List[MergeClause] = []
    while p.accept_word("WHEN"):
        negated = False
        if p.accept_word("NOT"):
            negated = True
        p.expect_word("MATCHED")
        clause_cond = None
        if p.accept_word("AND"):
            clause_cond = p.slice_expr(("THEN",))
            if clause_cond is None:
                raise DeltaParseError("Empty clause condition")
        p.expect_word("THEN")
        if negated:
            p.expect_word("INSERT")
            if p.accept_punct("*"):
                not_matched.append(
                    MergeClause("insert", condition=clause_cond, assignments=None)
                )
            else:
                cols = p.column_name_list()
                p.expect_word("VALUES")
                p.expect_punct("(")
                vals: List[str] = []
                while True:
                    v = p.slice_expr(stop_comma=True)
                    if v is None:
                        raise DeltaParseError("Empty VALUES expression")
                    vals.append(v)
                    if p.accept_punct(")"):
                        break
                    p.expect_punct(",")
                if len(cols) != len(vals):
                    raise errors.sql_insert_arity_mismatch(len(cols), len(vals))
                not_matched.append(
                    MergeClause(
                        "insert", condition=clause_cond,
                        assignments=dict(zip(cols, vals)),
                    )
                )
        elif p.accept_word("DELETE"):
            matched.append(MergeClause("delete", condition=clause_cond))
        else:
            p.expect_word("UPDATE")
            p.expect_word("SET")
            if p.accept_punct("*"):
                matched.append(
                    MergeClause("update", condition=clause_cond, assignments=None)
                )
            else:
                sets = _set_assignments(p, ("WHEN",))
                matched.append(
                    MergeClause("update", condition=clause_cond, assignments=sets)
                )
    p.expect_end()

    def run():
        from delta_tpu.commands.merge import MergeIntoCommand
        from delta_tpu.exec.scan import scan_to_table

        source = scan_to_table(_log_for(source_path).update())
        cmd = MergeIntoCommand(
            _log_for(target_path), source, cond,
            matched, not_matched,
            source_alias=source_alias, target_alias=target_alias,
        )
        cmd.run()
        return cmd.metrics

    return run


def _create(p: _Parser):
    p.expect_word("CREATE")
    replace = False
    if p.accept_word("OR"):
        p.expect_word("REPLACE")
        replace = True
    p.expect_word("TABLE")
    if_not_exists = False
    if p.accept_word("IF"):
        p.expect_word("NOT")
        p.expect_word("EXISTS")
        if_not_exists = True
    path = p.table_path()
    if p.peek().is_word("SHALLOW"):
        # CREATE TABLE <dst> SHALLOW CLONE <src> [VERSION|TIMESTAMP AS OF]
        p.expect_word("SHALLOW")
        p.expect_word("CLONE")
        src = p.table_path()
        version = timestamp = None
        if p.accept_word("VERSION"):
            p.expect_word("AS")
            p.expect_word("OF")
            version = int(p.number(as_int=True))
        elif p.accept_word("TIMESTAMP"):
            p.expect_word("AS")
            p.expect_word("OF")
            t = p.next()
            if t.kind not in ("STRING", "NUMBER"):
                raise errors.sql_expected("timestamp literal", t.start)
            timestamp = t.value
        p.expect_end()

        def run_clone():
            from delta_tpu.commands.clone import CloneCommand

            kind, value = path
            if kind != "path":
                raise errors.create_table_needs_location(value)
            cmd = CloneCommand(
                _log_for(src), value, version=version, timestamp=timestamp,
            )
            cmd.run()
            return cmd.metrics

        return run_clone
    fields: List[StructField] = []
    if p.accept_punct("("):
        fields.append(p.column_def())
        while p.accept_punct(","):
            fields.append(p.column_def())
        p.expect_punct(")")
    if p.accept_word("USING"):
        fmt = p.ident()
        if fmt.lower() != "delta":
            raise errors.unsupported_table_format(fmt)
    part_cols: List[str] = []
    props: Dict[str, str] = {}
    comment = None
    location = None
    while not p.at_end():
        if p.accept_word("PARTITIONED"):
            p.expect_word("BY")
            part_cols = p.column_name_list()
        elif p.accept_word("TBLPROPERTIES"):
            props = p.properties()
        elif p.accept_word("COMMENT"):
            t = p.next()
            if t.kind != "STRING":
                raise errors.sql_expected('comment string', t.start)
            comment = t.value
        elif p.accept_word("LOCATION"):
            t = p.next()
            if t.kind != "STRING":
                raise errors.sql_expected('location string', t.start)
            location = t.value
        else:
            t = p.peek()
            raise errors.sql_unexpected_input(t.start, t.value)
    p.expect_end()
    if replace and if_not_exists:
        raise DeltaParseError("CREATE OR REPLACE cannot have IF NOT EXISTS")

    def run():
        from delta_tpu.commands.create import CreateDeltaTableCommand

        kind, value = path
        register_name = None
        if kind == "name":
            from delta_tpu.catalog.catalog import default_catalog

            cat = default_catalog()
            if location is not None:
                target = location
                register_name = value
            elif cat.table_exists(value):
                target = cat.table_path(value)
            else:
                raise errors.create_table_needs_location(value)
        else:
            target = location or value
        mode = "create_or_replace" if replace else (
            "create_if_not_exists" if if_not_exists else "create"
        )
        result = CreateDeltaTableCommand(
            DeltaLog.for_table(target),
            schema=StructType(fields) if fields else None,
            mode=mode,
            partition_columns=part_cols,
            configuration=props or None,
            name=register_name,
            description=comment,
        ).run()
        if register_name is not None:
            from delta_tpu.catalog.catalog import default_catalog

            cat = default_catalog()
            if not cat.table_exists(register_name):
                cat.register(register_name, target)
        return result

    return run


def _alter(p: _Parser):
    from delta_tpu.commands import alter as alter_mod

    p.expect_word("ALTER")
    p.expect_word("TABLE")
    path = p.table_path()

    if p.accept_word("SET"):
        p.expect_word("TBLPROPERTIES")
        props = p.properties()
        p.expect_end()
        return lambda: alter_mod.set_table_properties(
            _log_for(path), props
        )
    if p.accept_word("UNSET"):
        p.expect_word("TBLPROPERTIES")
        if_exists = False
        if p.accept_word("IF"):
            p.expect_word("EXISTS")
            if_exists = True
        p.expect_punct("(")
        keys = [p.string_or_number()]
        while p.accept_punct(","):
            keys.append(p.string_or_number())
        p.expect_punct(")")
        p.expect_end()
        return lambda: alter_mod.unset_table_properties(
            _log_for(path), keys, if_exists=if_exists
        )
    if p.accept_word("ADD"):
        if p.accept_word("COLUMNS", "COLUMN"):
            p.expect_punct("(")
            specs: List[Tuple[StructField, Any]] = []
            while True:
                f = p.column_def()
                pos = None
                if p.accept_word("FIRST"):
                    pos = "first"
                elif p.accept_word("AFTER"):
                    pos = ("after", p.ident())
                specs.append((f, pos))
                if p.accept_punct(")"):
                    break
                p.expect_punct(",")
            p.expect_end()

            def run_add():
                positions = {f.name: pos for f, pos in specs if pos is not None}
                return alter_mod.add_columns(
                    _log_for(path), [f for f, _ in specs],
                    positions=positions or None,
                )

            return run_add
        p.expect_word("CONSTRAINT")
        name = p.ident()
        p.expect_word("CHECK")
        p.expect_punct("(")
        expr = p.slice_expr()
        if expr is None:
            raise DeltaParseError("Empty CHECK expression")
        p.expect_punct(")")
        p.expect_end()
        return lambda: alter_mod.add_constraint(_log_for(path), name, expr)
    if p.accept_word("DROP"):
        p.expect_word("CONSTRAINT")
        if_exists = False
        if p.accept_word("IF"):
            p.expect_word("EXISTS")
            if_exists = True
        name = p.ident()
        p.expect_end()
        return lambda: alter_mod.drop_constraint(
            _log_for(path), name, if_exists=if_exists
        )
    if p.accept_word("ALTER", "CHANGE"):
        p.accept_word("COLUMN")
        name = p.ident()
        while p.accept_punct("."):
            name += "." + p.ident()
        new_type = None
        comment = None
        position = None
        nullable = None
        while not p.at_end():
            if p.accept_word("TYPE"):
                new_type = p.column_type()
            elif p.accept_word("COMMENT"):
                t = p.next()
                if t.kind != "STRING":
                    raise errors.sql_expected('comment string', t.start)
                comment = t.value
            elif p.accept_word("FIRST"):
                position = "first"
            elif p.accept_word("AFTER"):
                position = ("after", p.ident())
            elif p.accept_word("DROP"):
                p.expect_word("NOT")
                p.expect_word("NULL")
                nullable = True
            elif p.accept_word("SET"):
                p.expect_word("NOT")
                p.expect_word("NULL")
                nullable = False
            else:
                t = p.peek()
                raise errors.sql_unexpected_input(t.start, t.value)
        p.expect_end()
        return lambda: alter_mod.change_column(
            _log_for(path), name, new_type=new_type,
            nullable=nullable, comment=comment, position=position,
        )
    t = p.peek()
    raise errors.sql_unsupported_alter_action(t.start)
