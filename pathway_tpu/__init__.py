"""pathway_tpu — a TPU-native incremental stream-processing framework.

A from-scratch re-design of the capabilities of Pathway (declarative Table
API, incremental differential computation, connectors, persistence, vector
indexes, LLM/RAG toolkit) built TPU-first: dense compute lowers to JAX/XLA
(embedders, rerankers, KNN distance+top-k run on the MXU; corpora shard
across chips over ICI), while the host-side engine pumps columnar delta
batches through an epoch-synchronous operator graph.

Import convention mirrors the reference: ``import pathway_tpu as pw``.
"""

from __future__ import annotations

from pathway_tpu.internals import dtype as _dt
from pathway_tpu.internals import reducers
from pathway_tpu.internals import universe as _universe_mod
from pathway_tpu.internals.api import (
    ERROR,
    Pending,
    Pointer,
    PyObjectWrapper,
    unwrap_py_object,
    wrap_py_object,
)
from pathway_tpu.internals.custom_reducers import BaseCustomAccumulator
from pathway_tpu.internals.datetime_types import DateTimeNaive, DateTimeUtc, Duration
from pathway_tpu.internals.errors import global_error_log, local_error_log
from pathway_tpu.internals.expression import (
    ColumnExpression,
    ColumnReference,
    apply,
    apply_async,
    apply_async_with_type,
    apply_fully_async,
    apply_with_type,
    cast,
    coalesce,
    declare_type,
    fill_error,
    if_else,
    make_tuple,
    require,
    unwrap,
)
from pathway_tpu.internals.groupbys import GroupedJoinResult, GroupedTable
from pathway_tpu.internals.join_mode import JoinMode
from pathway_tpu.internals.joins import (
    JoinResult,
    OuterJoinResult,
    groupby,
    join,
    join_inner,
    join_left,
    join_outer,
    join_right,
)
from pathway_tpu.internals.json import Json
from pathway_tpu.internals.parse_graph import G, clear_graph
from pathway_tpu.internals.run import run, run_all
from pathway_tpu.internals.schema import (
    ColumnDefinition,
    Schema,
    SchemaProperties,
    column_definition,
    schema_builder,
    schema_from_csv,
    schema_from_dict,
    schema_from_pandas,
    schema_from_types,
)
from pathway_tpu.internals.table import Joinable, Table, TableLike
from pathway_tpu.internals.table_slice import TableSlice
from pathway_tpu.internals.thisclass import left, right, this
from pathway_tpu.internals import udfs
from pathway_tpu.internals.udfs import (
    UDF,
    UDFAsync,
    UDFSync,
    async_executor,
    auto_executor,
    fully_async_executor,
    sync_executor,
    udf,
    udf_async,
)
from pathway_tpu.internals.universe import Universe
from pathway_tpu.internals import config as _config
from pathway_tpu.internals.config import set_license_key, set_monitoring_config

# persistent XLA compilation cache for the whole package (engine runs,
# tests, benchmark): $JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
_config.enable_compile_cache()
# every compilation counted on /metrics (compiles_total)
from pathway_tpu.engine import probes as _probes  # noqa: E402

_probes.watch_compiles()

# submodule namespaces (populated lazily to avoid import cycles)
from pathway_tpu import asynchronous  # noqa: E402
from pathway_tpu import debug  # noqa: E402
from pathway_tpu import io  # noqa: E402
from pathway_tpu import persistence  # noqa: E402
from pathway_tpu.stdlib import graphs, indexing, ml, ordered, stateful, statistical, temporal, utils, viz  # noqa: E402
from pathway_tpu.internals.interactive import (  # noqa: E402
    LiveTable,
    enable_interactive_mode,
)
from pathway_tpu.stdlib.temporal import (  # noqa: E402
    AsofJoinResult,
    IntervalJoinResult,
    WindowJoinResult,
)
from pathway_tpu.internals.row_transformer import (  # noqa: E402
    ClassArg,
    attribute,
    input_attribute,
    input_method,
    method,
    output_attribute,
    transformer,
)
from pathway_tpu.stdlib.utils.async_transformer import AsyncTransformer  # noqa: E402
from pathway_tpu.stdlib.utils.pandas_transformer import pandas_transformer  # noqa: E402
from pathway_tpu.internals.sql import sql  # noqa: E402
from pathway_tpu.internals.yaml_loader import load_yaml  # noqa: E402
from pathway_tpu.internals.iterate import iterate, iterate_universe  # noqa: E402
from pathway_tpu.internals.exported import (  # noqa: E402
    ExportedTable,
    export_table,
    import_table,
)
from pathway_tpu.internals.monitoring import MonitoringLevel  # noqa: E402
from pathway_tpu import demo  # noqa: E402

# typing aliases (reference exposes these as pw.*)
from pathway_tpu.internals.api import (  # noqa: E402
    PathwayType as Type,
    PersistenceMode,
)

PointerType = Pointer
DATE_TIME_NAIVE = _dt.DATE_TIME_NAIVE
DATE_TIME_UTC = _dt.DATE_TIME_UTC
DURATION = _dt.DURATION

__version__ = "0.1.0"

universes = _universe_mod


def assert_table_has_schema(
    table: Table,
    schema,
    *,
    allow_superset: bool = True,
    ignore_primary_keys: bool = True,
) -> None:
    schema.assert_matches_schema(
        table.schema,
        allow_superset=allow_superset,
        ignore_primary_keys=ignore_primary_keys,
    )


def table_transformer(fn=None, **kwargs):
    """Decorator marking a function as a table→table transformer (parity
    shim; performs schema checks when annotated)."""

    def wrap(f):
        return f

    if fn is not None:
        return wrap(fn)
    return wrap


__all__ = [
    "Table",
    "TableLike",
    "TableSlice",
    "Joinable",
    "JoinMode",
    "JoinResult",
    "OuterJoinResult",
    "GroupedJoinResult",
    "AsofJoinResult",
    "IntervalJoinResult",
    "WindowJoinResult",
    "UDFAsync",
    "UDFSync",
    "Type",
    "PersistenceMode",
    "join",
    "join_inner",
    "join_left",
    "join_right",
    "join_outer",
    "groupby",
    "enable_interactive_mode",
    "Schema",
    "Json",
    "Pointer",
    "Duration",
    "DateTimeNaive",
    "DateTimeUtc",
    "UDF",
    "udf",
    "this",
    "left",
    "right",
    "reducers",
    "apply",
    "apply_with_type",
    "apply_async",
    "cast",
    "coalesce",
    "declare_type",
    "if_else",
    "make_tuple",
    "require",
    "unwrap",
    "fill_error",
    "run",
    "run_all",
    "debug",
    "io",
    "demo",
    "indexing",
    "ml",
    "temporal",
    "ExportedTable",
    "export_table",
    "import_table",
    "iterate",
    "sql",
    "AsyncTransformer",
    "pandas_transformer",
    "column_definition",
    "schema_from_types",
    "schema_from_dict",
    "schema_from_pandas",
    "schema_builder",
    "global_error_log",
    "ERROR",
    "Pending",
]
