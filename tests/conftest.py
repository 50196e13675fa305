"""Test harness.

Multi-device testing mirrors the reference's ``local[*]`` trick
(SURVEY §4 "Multi-node without a cluster"): a virtual 8-device CPU mesh runs
the same `shard_map`/`pjit` code paths as a real TPU slice, with task-level
parallelism real. Must set flags before the first jax import.
"""
import os

# Tests force the CPU (env for child processes, config for this one): an
# attached chip belongs to one process at a time and is never a test target.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

import pytest

from delta_tpu.log.deltalog import DeltaLog
from delta_tpu.protocol import filenames
from delta_tpu.protocol.actions import Action, Metadata, Protocol
from delta_tpu.schema.types import IntegerType, StringType, StructType


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: benchmark-scale tests excluded from the tier-1 run "
        "(-m 'not slow')",
    )


@pytest.fixture(autouse=True)
def _clear_deltalog_cache():
    DeltaLog.clear_cache()
    yield
    DeltaLog.clear_cache()


@pytest.fixture
def tmp_table(tmp_path):
    return str(tmp_path / "table")


TEST_SCHEMA = StructType().add("id", IntegerType()).add("value", StringType())


def commit_manually(log: DeltaLog, version: int, actions, overwrite: bool = False):
    """Write a commit file directly, bypassing the transaction layer —
    the analogue of the reference's ``DeltaTestUtils.commitManually``."""
    path = f"{log.log_path}/{filenames.delta_file(version)}"
    log.store.write(path, [a.json() for a in actions], overwrite=overwrite)


def init_metadata(partition_columns=None, configuration=None, schema=None) -> Metadata:
    return Metadata(
        schema_string=(schema or TEST_SCHEMA).to_json(),
        partition_columns=list(partition_columns or []),
        configuration=dict(configuration or {}),
    )
