"""Test harness config: run JAX on a virtual 8-device CPU mesh.

Mirrors the reference's DistributedQueryRunner trick (SURVEY §4): multi-node
paths are exercised in one process.

The suite always runs on the CPU backend, whatever the host holds: the
platform is pinned below (and in the environment, so worker subprocesses the
tests spawn inherit it).  Programs are compiled for the chip without a chip
only in tests/test_tpu_compile.py, from a fixture of its own.
"""

import os
import tempfile

# History-based optimization makes planning stateful across *processes* by
# design (the journal is durable): a polluted host journal would make every
# plan-shape assertion depend on what ran before.  The suite gets a fresh
# journal per run and pins HBO off; test_hbo opts back in per-fixture.
os.environ["TRINO_TPU_JOURNAL_DIR"] = tempfile.mkdtemp(
    prefix="trino-tpu-test-journal-")
os.environ["TRINO_TPU_HBO"] = "0"

# The query-state WAL (retry_policy=TASK) is durable by design too, and its
# default directory is one per uid: a TrinoTpuServer that boots in one xdist
# worker adopts whatever in-flight query it finds there (boot recovery), so
# it re-ran another worker's running FTE query over the same spool root, and
# that query then read 0 rows (seen 3 times in 5 whole runs once PR 37 moved
# the files' timing).  One directory a test process; children inherit it.
os.environ["TRINO_TPU_QUERY_STATE_DIR"] = tempfile.mkdtemp(
    prefix="trino-tpu-test-query-state-")

# executable_cache.init_compile_cache() would otherwise have six xdist
# workers (and every spawned worker process) write each CPU executable into
# one <checkout>/.jax_cache; the suite keeps JAX's persistent cache off, as
# it was before that rule existed
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "0")
os.environ["JAX_PLATFORMS"] = "cpu"

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

assert len(jax.devices()) == 8, "expected 8 virtual CPU devices for tests"


def pytest_configure(config):
    # tier-1 runs -m 'not slow' inside an 870s budget; the >=1M-NDV hash
    # bake-off legs opt out via this marker
    config.addinivalue_line(
        "markers", "slow: long-running bench-scale tests, excluded by tier-1")
