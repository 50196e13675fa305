"""Error taxonomy, mirroring the reference's user-facing error factory
(``DeltaErrors.scala``) and the public concurrency exception hierarchy
(``io/delta/exceptions/DeltaConcurrentExceptions.scala``, also surfaced to
Python in the reference via ``python/delta/exceptions.py``)."""
from __future__ import annotations

from typing import Iterable, Optional

__all__ = [
    "DeltaError",
    "DeltaAnalysisError",
    "DeltaIllegalArgumentError",
    "DeltaIllegalStateError",
    "CommitAttemptsExhausted",
    "DeltaFileNotFoundError",
    "DeltaIOError",
    "DeltaUnsupportedOperationError",
    "DeltaParseError",
    "MetadataChangedException",
    "ProtocolChangedException",
    "ConcurrentWriteException",
    "ConcurrentAppendException",
    "ConcurrentDeleteReadException",
    "ConcurrentDeleteDeleteException",
    "ConcurrentTransactionException",
    "DeltaConcurrentModificationException",
    "InvariantViolationError",
    "SchemaMismatchError",
    "ProtocolError",
    "VersionNotFoundError",
    "TimestampEarlierThanCommitRetentionError",
    "TemporallyUnstableInputError",
]


class DeltaError(Exception):
    """Base for all delta-tpu errors."""


class DeltaAnalysisError(DeltaError):
    pass


class DeltaIllegalArgumentError(DeltaError, ValueError):
    pass


class DeltaIllegalStateError(DeltaError, RuntimeError):
    pass


class CommitAttemptsExhausted(DeltaIllegalStateError):
    """A commit gave up after its attempts bound (delta.tpu.maxCommitAttempts
    or a maintenance `txn.transaction.commit_attempts_cap`). A dedicated
    subclass so background maintenance can classify losing-to-foreground
    without message matching; still a DeltaIllegalStateError to callers."""


class DeltaFileNotFoundError(DeltaError, FileNotFoundError):
    pass


class DeltaIOError(DeltaError, IOError):
    pass


class DeltaUnsupportedOperationError(DeltaError, NotImplementedError):
    pass


class InvariantViolationError(DeltaError):
    """Row-level constraint / NOT NULL violation
    (``schema/InvariantViolationException.scala``)."""


class DeltaParseError(DeltaAnalysisError):
    """SQL statement failed to tokenize or parse (≈ Spark ParseException)."""


class SchemaMismatchError(DeltaAnalysisError):
    """Write schema incompatible with table schema
    (``DeltaErrors.failedToMergeFields`` etc.)."""


class ProtocolError(DeltaError):
    """Table requires a newer reader/writer than this client
    (``DeltaErrors.InvalidProtocolVersionException``)."""


class VersionNotFoundError(DeltaAnalysisError):
    def __init__(self, user_version: int, earliest: int, latest: int):
        super().__init__(
            f"Cannot time travel Delta table to version {user_version}. "
            f"Available versions: [{earliest}, {latest}]."
        )
        self.user_version = user_version
        self.earliest = earliest
        self.latest = latest


class TimestampEarlierThanCommitRetentionError(DeltaAnalysisError):
    pass


class TemporallyUnstableInputError(DeltaAnalysisError):
    """Requested timestamp is after the latest commit timestamp."""

    def __init__(self, user_ts, commit_ts, latest_version: int):
        super().__init__(
            f"The provided timestamp ({user_ts}) is after the latest version "
            f"available to this table ({commit_ts}, version {latest_version})."
        )
        self.commit_ts = commit_ts
        self.latest_version = latest_version


# ---------------------------------------------------------------------------
# Concurrency exceptions (conflict-checker verdicts) — names match
# io/delta/exceptions/DeltaConcurrentExceptions.scala so users can map 1:1.
# ---------------------------------------------------------------------------

class DeltaConcurrentModificationException(DeltaError):
    """Base of the OCC conflict hierarchy."""

    def __init__(self, message: str, conflicting_commit: Optional[dict] = None):
        super().__init__(message)
        self.conflicting_commit = conflicting_commit


class ConcurrentWriteException(DeltaConcurrentModificationException):
    """A concurrent transaction wrote new data the current transaction read
    (or the commit file appeared non-atomically)."""


class MetadataChangedException(DeltaConcurrentModificationException):
    """The table metadata changed since the transaction's snapshot."""


class ProtocolChangedException(DeltaConcurrentModificationException):
    """The protocol version changed since the transaction's snapshot."""


class ConcurrentAppendException(DeltaConcurrentModificationException):
    """Files were added by a concurrent commit in a region this txn read."""


class ConcurrentDeleteReadException(DeltaConcurrentModificationException):
    """A concurrent commit deleted a file this transaction read."""


class ConcurrentDeleteDeleteException(DeltaConcurrentModificationException):
    """A concurrent commit deleted a file this transaction also deletes."""


class ConcurrentTransactionException(DeltaConcurrentModificationException):
    """Overlapping SetTransaction appId with a concurrent commit."""


def versions_not_contiguous(versions: Iterable[int]) -> DeltaIllegalStateError:
    return DeltaIllegalStateError(
        f"Versions ({list(versions)}) are not contiguous. This can happen when "
        "files have been manually deleted from the transaction log."
    )


# ---------------------------------------------------------------------------
# Error factories — the user-facing message contract, mirroring the relevant
# subset of ``DeltaErrors.scala`` (message text and remediation advice kept
# 1:1 where the situation exists in this engine).
# ---------------------------------------------------------------------------

_CONCURRENCY_DOC = "https://docs.delta.io/latest/concurrency-control.html"


def _concurrent_msg(base: str, commit: Optional[dict]) -> str:
    """``DeltaErrors.concurrentModificationExceptionMsg`` composition: base
    message + conflicting-commit provenance + doc pointer."""
    import json

    msg = base
    if commit:
        msg += f"\nConflicting commit: {json.dumps(commit, default=str)}"
    return msg + f"\nRefer to {_CONCURRENCY_DOC} for more details."


def concurrent_write_exception(commit: Optional[dict] = None) -> ConcurrentWriteException:
    return ConcurrentWriteException(_concurrent_msg(
        "A concurrent transaction has written new data since the current "
        "transaction read the table. Please try the operation again.",
        commit), commit)


def metadata_changed_exception(commit: Optional[dict] = None) -> MetadataChangedException:
    return MetadataChangedException(_concurrent_msg(
        "The metadata of the Delta table has been changed by a concurrent "
        "update. Please try the operation again.", commit), commit)


def protocol_changed_exception(commit: Optional[dict] = None) -> ProtocolChangedException:
    additional = ""
    if commit and commit.get("version") == 0:
        # DeltaErrors.scala:1164-1171 — empty-directory race hint
        additional = (
            "This happens when multiple writers are writing to an empty "
            "directory. Creating the table ahead of time will avoid this "
            "conflict. "
        )
    return ProtocolChangedException(_concurrent_msg(
        "The protocol version of the Delta table has been changed by a "
        f"concurrent update. {additional}Please try the operation again.",
        commit), commit)


def concurrent_append_exception(
    partition: str, commit: Optional[dict] = None,
    custom_retry: Optional[str] = None,
) -> ConcurrentAppendException:
    return ConcurrentAppendException(_concurrent_msg(
        f"Files were added to {partition} by a concurrent update. "
        + (custom_retry or "Please try the operation again."), commit), commit)


def concurrent_delete_read_exception(
    file: str, commit: Optional[dict] = None
) -> ConcurrentDeleteReadException:
    return ConcurrentDeleteReadException(_concurrent_msg(
        "This transaction attempted to read one or more files that were "
        f"deleted (for example {file}) by a concurrent update. "
        "Please try the operation again.", commit), commit)


def concurrent_delete_delete_exception(
    file: str, commit: Optional[dict] = None
) -> ConcurrentDeleteDeleteException:
    return ConcurrentDeleteDeleteException(_concurrent_msg(
        "This transaction attempted to delete one or more files that were "
        f"deleted (for example {file}) by a concurrent update. "
        "Please try the operation again.", commit), commit)


def concurrent_transaction_exception(
    commit: Optional[dict] = None, app_id: Optional[str] = None,
) -> ConcurrentTransactionException:
    detail = f" (conflicting appId={app_id})" if app_id else ""
    return ConcurrentTransactionException(_concurrent_msg(
        "This error occurs when multiple streaming queries are using the "
        f"same checkpoint to write into this table{detail}. Did you run "
        "multiple instances of the same streaming query at the same time?",
        commit), commit)


def not_a_delta_table(identifier: str, operation: Optional[str] = None) -> DeltaAnalysisError:
    if operation:
        return DeltaAnalysisError(
            f"{identifier} is not a Delta table. {operation} is only "
            "supported for Delta tables."
        )
    return DeltaAnalysisError(f"{identifier} is not a Delta table.")


def modify_append_only_table() -> DeltaUnsupportedOperationError:
    return DeltaUnsupportedOperationError(
        "This table is configured to only allow appends. If you would like "
        "to permit updates or deletes, use 'ALTER TABLE <table_name> SET "
        "TBLPROPERTIES (delta.appendOnly=false)'."
    )


def invalid_protocol_version(
    client_reader: int, client_writer: int, table_reader: int, table_writer: int
) -> ProtocolError:
    return ProtocolError(
        "Delta protocol version "
        f"(reader={table_reader}, writer={table_writer}) is too new for this "
        f"client (supports reader={client_reader}, writer={client_writer}). "
        "Please upgrade to a newer release."
    )


def not_null_invariant_violated(
    column: str, null_rows: Optional[int] = None
) -> InvariantViolationError:
    detail = f" ({null_rows} null rows)" if null_rows else ""
    return InvariantViolationError(
        f"NOT NULL constraint violated for column: {column}{detail}."
    )


def check_constraint_violated(
    name: str, expr_sql: str, values: Optional[dict] = None
) -> InvariantViolationError:
    lines = "".join(f"\n - {c} : {v}" for c, v in (values or {}).items())
    return InvariantViolationError(
        f"CHECK constraint {name} ({expr_sql}) violated by row with values:"
        f"{lines}"
    )


def new_check_constraint_violated(num: int, table: str, expr: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"{num} rows in {table} violate the new CHECK constraint ({expr})"
    )


def merge_conflicting_set_columns(column: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"There is a conflict from these SET columns: duplicate assignment "
        f"to {column!r}."
    )


def char_varchar_length_exceeded(
    column: str, declared: str, limit: int, sample
) -> InvariantViolationError:
    return InvariantViolationError(
        f"Exceeds char/varchar type length limitation: column {column} is "
        f"declared {declared} but value {sample!r} is longer than {limit} "
        "characters."
    )


def replace_where_mismatch(replace_where: str, detail: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Data written out does not match replaceWhere '{replace_where}'.\n"
        f"Invalid data would be written to {detail}."
    )


def unset_nonexistent_property(key: str, table: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Attempted to unset non-existent property '{key}' in table {table}"
    )


def retention_period_too_short(retention_hours: float, configured_hours: float):
    return DeltaIllegalArgumentError(
        "Are you sure you would like to vacuum files with such a low "
        f"retention period ({retention_hours} hours)? If you have writers "
        "that are currently writing to this table, there is a risk that you "
        "may corrupt the state of your Delta table.\nIf you are certain "
        "there are no operations being performed on this table, such as "
        "insert/upsert/delete/optimize, then you may turn off this check by "
        "setting delta.tpu.retentionDurationCheck.enabled = false\nIf you "
        "are not sure, please use a value not less than "
        f"{configured_hours} hours."
    )


def missing_part_files(version: int, cause: Exception) -> DeltaIllegalStateError:
    return DeltaIllegalStateError(
        f"Couldn't find all part files of the checkpoint version: {version} "
        f"({cause})"
    )


# ---------------------------------------------------------------------------
# Named factories for every analysis-time error path — no call site raises a
# bare f-string DeltaAnalysisError (enforced by tests/test_errors.py); each
# message carries what went wrong plus how to fix it, the DeltaErrors.scala
# contract.
# ---------------------------------------------------------------------------


def invalid_table_identifier(name: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Invalid table identifier: {name!r}. Use 'table', 'db.table', or a "
        "path identifier delta.`/path/to/table`."
    )


def table_already_exists_in_catalog(name: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Table {name!r} already exists in catalog. Use CREATE OR REPLACE to "
        "overwrite it, or DROP TABLE first."
    )


def table_being_created_concurrently(name: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Table {name!r} is being created concurrently by another writer. "
        "Wait for that create to finish, or retry the operation."
    )


def table_not_found_in_catalog(name: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Table {name!r} not found in catalog. Check the identifier, or use "
        "a path identifier delta.`/path/to/table` for path-addressed tables."
    )


def table_already_exists(path: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Table already exists: {path}. Use mode='overwrite' / CREATE OR "
        "REPLACE to replace it, or pick a different location."
    )


def unsupported_sql_statement(sql: str) -> DeltaParseError:
    return DeltaParseError(
        f"Unsupported SQL statement: {sql.strip()[:80]!r}. Supported "
        "statements: SELECT, CREATE/REPLACE TABLE, ALTER TABLE, "
        "INSERT/UPDATE/DELETE/MERGE, OPTIMIZE, VACUUM, DESCRIBE, RESTORE, "
        "CONVERT TO DELTA, GENERATE, SHALLOW CLONE."
    )


def unsupported_generate_mode(mode: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Unsupported GENERATE mode: {mode!r}. The only supported mode is "
        "'symlink_format_manifest'."
    )


def unsupported_table_format(fmt: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Unsupported table format: {fmt!r}. CREATE TABLE ... USING must be "
        "'delta'; to import an existing parquet table, use CONVERT TO DELTA "
        "parquet.`/path`."
    )


def unsupported_arrow_type(t) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Unsupported Arrow type for a Delta schema: {t}. Cast the column "
        "to a supported primitive, struct, array, or map type before writing."
    )


def arrow_mapping_missing(type_name: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"No Arrow mapping for Delta type {type_name}. This type cannot be "
        "materialized by the vectorized reader."
    )


def add_column_anchor_not_found(column: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Couldn't resolve the position to add the column {column}: the "
        "AFTER anchor column does not exist at that nesting level."
    )


def column_already_exists(column: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(f"Column {column} already exists.")


def struct_not_found_at_position(position) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Struct not found at position {position}; the parent of a nested "
        "column operation must be a struct column."
    )


def column_not_in_schema(column: str, schema_cols=None) -> DeltaAnalysisError:
    detail = f" Available columns: {list(schema_cols)}." if schema_cols else ""
    return DeltaAnalysisError(f"Column {column} does not exist.{detail}")


def drop_column_index_below_zero(position: int) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Index {position} to drop column is lower than 0"
    )


def invalid_timestamp_format(ts, cause=None) -> DeltaAnalysisError:
    tail = f": {cause}" if cause is not None else "."
    return DeltaAnalysisError(
        f"Invalid timestamp {ts!r}. Provide epoch milliseconds or an "
        f"ISO-8601 string like '2024-05-01 12:00:00'{tail}"
    )


def column_not_found_in_table(column: str, available) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Column {column!r} not found among {list(available)}."
    )


def cannot_tokenize_predicate(fragment: str) -> DeltaParseError:
    return DeltaParseError(
        f"Cannot tokenize predicate at {fragment!r}. Check for unbalanced "
        "quotes or unsupported characters."
    )


def unexpected_end_of_expression(source: str) -> DeltaParseError:
    return DeltaParseError(
        f"Unexpected end of expression: {source!r}. The predicate ends "
        "mid-term — a operand or closing parenthesis is missing."
    )


def trailing_tokens(token, source: str) -> DeltaParseError:
    return DeltaParseError(
        f"Trailing tokens at {token} in {source!r}. Combine multiple "
        "conditions with AND/OR."
    )


def interval_without_date(source: str) -> DeltaParseError:
    return DeltaParseError(
        "INTERVAL is supported only added to or subtracted from a DATE "
        f"literal, in {source!r}."
    )


def bad_date_literal(text: str, source: str) -> DeltaParseError:
    return DeltaParseError(
        f"DATE literal {text!r} is not yyyy-mm-dd, in {source!r}."
    )


def bad_interval_literal(source: str) -> DeltaParseError:
    return DeltaParseError(
        "INTERVAL takes a whole number and YEAR, MONTH or DAY, in "
        f"{source!r}."
    )


def unexpected_keyword(text: str, source: str) -> DeltaParseError:
    return DeltaParseError(
        f"Unexpected keyword {text} in {source!r}."
    )


def bad_column_path(source: str) -> DeltaParseError:
    return DeltaParseError(
        f"Bad column path after '.' in {source!r}. Nested fields are "
        "addressed as parent.child (backquote names with special characters)."
    )


def unexpected_token(token, source: str) -> DeltaParseError:
    return DeltaParseError(f"Unexpected token {token} in {source!r}.")


def expected_type_name(token) -> DeltaParseError:
    return DeltaParseError(
        f"Expected type name, got {token}. Use a Delta type like INT, "
        "BIGINT, DOUBLE, STRING, DATE, TIMESTAMP, or DECIMAL(p, s)."
    )


def column_not_found_in_row(column: str, available) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Column not found: {column} in {list(available)}"
    )


def unsupported_function(name: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Unsupported function: {name}. See delta_tpu.expr.ir.FUNCTION_NAMES "
        "for the supported surface."
    )


def invalid_column_position_spec(spec: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Invalid column position spec {spec!r}. Use FIRST or AFTER "
        "<existing column>."
    )


def constraint_already_exists(name: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Constraint '{name}' already exists. DROP CONSTRAINT first to "
        "replace it."
    )


def constraint_does_not_exist(name: str, table: str = "") -> DeltaAnalysisError:
    where = f" in table {table}" if table else ""
    return DeltaAnalysisError(
        f"Constraint '{name}' does not exist{where}. Nothing to drop."
    )


def zorder_column_not_in_schema(column: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Z-order column {column!r} not in table schema."
    )


def zorder_on_partition_column(column: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Cannot Z-order by partition column {column!r}: partition values "
        "are constant within a file, so they add no clustering. Z-order by "
        "data columns instead."
    )


def invalid_merge_clause(kind: str, matched: bool) -> DeltaAnalysisError:
    allowed = "UPDATE or DELETE" if matched else "INSERT"
    block = "WHEN MATCHED" if matched else "WHEN NOT MATCHED"
    return DeltaAnalysisError(
        f"Invalid {block} clause: {kind}. Only {allowed} is allowed here."
    )


def update_column_not_found(column: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Column {column!r} not found in table schema. SET clauses may only "
        "assign existing columns."
    )


# -- SQL parse family (DeltaSqlBase.g4 / ParseException analogues) ----------


def sql_unexpected_character(c: str, offset: int) -> DeltaParseError:
    return DeltaParseError(f"Unexpected character {c!r} at offset {offset}")


def sql_expected(what: str, offset, got=None) -> DeltaParseError:
    tail = f", got {got!r}" if got is not None else ""
    return DeltaParseError(f"Expected {what} at offset {offset}{tail}")


def sql_unexpected_input(offset, got) -> DeltaParseError:
    return DeltaParseError(f"Unexpected token at offset {offset}: {got!r}")


def sql_trailing_input(offset, got) -> DeltaParseError:
    return DeltaParseError(
        f"Unexpected trailing input at offset {offset}: {got!r}"
    )


def sql_invalid_decimal(args) -> DeltaParseError:
    return DeltaParseError(
        f"Invalid DECIMAL precision/scale: {args}. Use DECIMAL(precision, "
        "scale) with 1 <= precision <= 38 and 0 <= scale <= precision."
    )


def sql_unsupported_type(name: str) -> DeltaParseError:
    return DeltaParseError(
        f"Unsupported SQL type: {name!r}. Use a Delta type like INT, BIGINT, "
        "DOUBLE, STRING, DATE, TIMESTAMP, BOOLEAN, BINARY, or DECIMAL(p, s)."
    )


def sql_invalid_number(value, kind: str, offset) -> DeltaParseError:
    return DeltaParseError(f"Invalid {kind} {value!r} at offset {offset}")


def sql_bad_type_argument(offset, value) -> DeltaParseError:
    return DeltaParseError(f"Bad type argument at offset {offset}: {value!r}")


def sql_empty_set_expression(column: str) -> DeltaParseError:
    return DeltaParseError(f"Empty SET expression for column {column!r}")


def sql_insert_arity_mismatch(n_cols: int, n_vals: int) -> DeltaParseError:
    return DeltaParseError(
        f"INSERT columns ({n_cols}) and VALUES ({n_vals}) differ"
    )


def sql_unsupported_alter_action(offset) -> DeltaParseError:
    return DeltaParseError(f"Unsupported ALTER TABLE action at offset {offset}")


def sql_expected_statement(got) -> DeltaParseError:
    return DeltaParseError(f"Expected a statement keyword, got {got!r}")


def sql_star_only_in_count(func: str) -> DeltaParseError:
    return DeltaParseError(
        f"{func}(*) is not valid; '*' is only allowed in COUNT(*)."
    )


def sql_column_needs_group_by(column: str) -> DeltaParseError:
    return DeltaParseError(
        f"Column {column} must appear in GROUP BY or inside an aggregate "
        "function"
    )


def sql_expected_table_identifier(after: str, offset) -> DeltaParseError:
    return DeltaParseError(
        f"Expected table identifier after {after}. at offset {offset}"
    )


def create_table_needs_location(name: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"CREATE TABLE {name}: unregistered name needs LOCATION "
        "(or use delta.`/path`)"
    )


def parse_expected(what, got, source: str) -> DeltaParseError:
    return DeltaParseError(f"Expected {what} at token {got} in {source!r}")


# -- expression typing ------------------------------------------------------


def cannot_compare_types(left: str, right: str, sql: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(f"Cannot compare {left} with {right} in {sql}")


def cannot_apply_operator(op: str, left: str, right: str, sql: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Cannot apply {op!r} to {left} and {right} in {sql}"
    )


def like_requires_strings(got: str, sql: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"LIKE requires string operands, got {got} in {sql}"
    )


# -- schema machinery (SchemaUtils / DeltaErrors schema family) -------------


def invalid_column_name(name: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f'Attribute name "{name}" contains invalid character(s) among '
        '" ,;{}()\\n\\t=". Please use alias to rename it.'
    )


def partition_column_not_found(column: str, schema_str: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Partition column `{column}` not found in schema {schema_str}"
    )


def duplicate_columns(context: str, first: str, second: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Found duplicate column(s) {context}: {first}, {second}"
    )


def generated_column_type_change(name: str, data_type: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Column {name} is a generated column or a column used by a "
        f"generated column; its data type {data_type} cannot be changed."
    )


def add_column_index_below_zero(position: int, name: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Index {position} to add column {name} is lower than 0"
    )


def add_column_index_too_large(position: int, name: str, length: int) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Index {position} to add column {name} is larger than struct "
        f"length: {length}"
    )


def parent_not_struct(name: str, found: Optional[str] = None) -> DeltaAnalysisError:
    tail = f" Found {found}" if found else ""
    return DeltaAnalysisError(
        f"Cannot add {name} because its parent is not a StructType.{tail}"
    )


def replace_column_index_oob(position: int) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Index {position} to replace column is out of bounds"
    )


def array_access_needs_element_step(verb: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Incorrectly accessing an ArrayType during {verb}: use the element "
        "step"
    )


def nested_op_only_in_struct(verb: str, found: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Can only {verb} nested columns inside StructType. Found: {found}"
    )


def drop_column_index_too_large(position: int, length: int) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Index {position} to drop column equals to or is larger than "
        f"struct length: {length}"
    )


def array_access_element_path_hint(corrected_path: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        "An ArrayType was found. In order to access elements of an "
        f"ArrayType, specify {corrected_path}"
    )


def map_access_needs_key_or_value(name: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Cannot access {name} in a MapType: use key or value"
    )


def column_path_not_nested(path: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Column path {path} descends into a non-nested type"
    )


def column_path_not_found(path: str, schema_str: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Couldn't find column {path} in schema {schema_str}"
    )


def parent_is_not_struct(parent: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(f"Parent {parent} is not a struct")


def position_after_column_not_found(column: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Couldn't find column {column} to position AFTER"
    )


def add_columns_must_be_nullable(name: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"ADD COLUMNS requires nullable columns, {name} is NOT NULL"
    )


def cannot_change_column_type(name: str, old: str, new: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Cannot change column {name} from {old} to {new}"
    )


def cannot_change_nullable_to_not_null(name: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Cannot change nullable column {name} to NOT NULL"
    )


# -- generated columns ------------------------------------------------------


def invalid_generation_expression(column: str, cause) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Invalid generation expression for column {column!r}: {cause}"
    )


def generation_expr_unknown_column(column: str, ref: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Generation expression for {column!r} references unknown column "
        f"{ref!r}"
    )


def generation_expr_references_generated(column: str, ref: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Generation expression for {column!r} references generated column "
        f"{ref!r}; generated columns cannot reference each other"
    )


def generation_expr_type_mismatch(column: str, got, want, cause) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Generation expression for {column!r} produces type {got}, which "
        f"cannot become declared type {want}: {cause}"
    )


# -- commands ---------------------------------------------------------------


def partition_path_segment_invalid(segment: str, rel_path: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Expecting partition column in path segment {segment!r} of {rel_path!r}"
    )


def partition_path_mismatch(rel_path: str, found, expected) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Partition columns in path {rel_path!r} ({sorted(found)}) don't "
        f"match the declared partition schema ({sorted(expected)}). "
        "CONVERT TO DELTA requires PARTITIONED BY matching the layout."
    )


def replace_requires_existing_table(path: str) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Table not found: {path} (REPLACE requires an existing table; use "
        "CREATE OR REPLACE)"
    )


def merge_unresolvable_qualifier(
    name: str, qualifier: str, target_alias, source_alias
) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Cannot resolve {name!r} in MERGE: qualifier {qualifier!r} matches "
        f"neither target alias {target_alias!r} nor source alias "
        f"{source_alias!r}"
    )


def merge_unresolvable_column(name: str, target_cols, source_cols) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Cannot resolve {name!r} in MERGE (target={list(target_cols)}, "
        f"source={list(source_cols)})"
    )


def merge_clause_unresolvable(column: str, clause: str, source_cols) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"cannot resolve {column} in {clause} clause given columns "
        f"{list(source_cols)} (enable delta.tpu.schema.autoMerge.enabled to "
        "evolve the target schema instead)"
    )


def update_expression_type_mismatch(name: str, new_type, old_type) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"UPDATE expression for {name} has incompatible type {new_type} "
        f"(column is {old_type})"
    )


def partition_columns_mismatch(given, current) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"Partition columns {list(given)} don't match the table's {current}"
    )


def replace_where_needs_partition_columns(pred_sql: str, partition_cols) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"replaceWhere {pred_sql!r} must reference only partition columns "
        f"{partition_cols}"
    )


def cdf_start_after_latest(start: int, latest: int) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"CDF start version {start} is after the latest table version {latest}"
    )


def cdf_start_after_end(start: int, end: int) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"CDF start version {start} is after end version {end}"
    )


def cdf_start_unavailable(start: int, earliest: int) -> DeltaAnalysisError:
    return DeltaAnalysisError(
        f"CDF start version {start} is no longer available (earliest "
        f"retained commit is {earliest}); the change feed for cleaned-up "
        "versions is lost"
    )
