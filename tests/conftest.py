"""Test config: run on CPU with 8 virtual devices so sharding tests work
without TPU hardware, and Pallas kernels run in interpret mode.

The suite is pinned to the CPU whatever the machine holds: a chip
belongs to one process at a time, and the driver runs several workers.
XLA_FLAGS asks for the 8-device virtual CPU mesh (honored at backend
init, which hasn't happened yet at conftest import time); JAX_PLATFORMS
is set for the child processes tests start, and the config update pins
this process even where the variable was read before this file ran.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Small kernel shapes for in-process cluster tests: the default knobs size
# the resolver for TPU throughput (T=1024, 4096-entry ring) — per-commit
# overkill that makes CPU unit tests crawl. Tests that exercise the commit
# pipeline pass these unless the test is about capacity itself.
TEST_KNOBS = dict(
    batch_txn_capacity=16,
    point_reads_per_txn=2,
    point_writes_per_txn=2,
    range_reads_per_txn=4,
    range_writes_per_txn=4,
    key_limbs=4,
    hash_table_bits=14,
    range_ring_capacity=64,
    coarse_buckets_bits=8,
    initial_backoff_s=0.0001,
)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_failure_monitor():
    """The failure monitor is process-global (one per real process, by
    design); in the one-process test suite that would leak one test's
    failed endpoints into the next test's health verdict."""
    from foundationdb_tpu.rpc import failuremon

    failuremon.monitor().reset()
    yield
