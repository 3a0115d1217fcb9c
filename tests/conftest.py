"""Test harness: run everything on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding correctness is
validated on XLA's host platform with 8 forced devices (the same trick the
driver's dryrun uses). Must run before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent XLA compilation cache: pytest re-runs recompile hundreds of
# kernels otherwise; cache survives across processes and cuts suite time ~10x.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/jax_pytest_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

# Something (a plugin, a sitecustomize) may have imported jax before this
# conftest ran, with jax_platforms read from the then-current env: the env
# mutation above would be too late for THIS process, so force the config
# directly. Backends are not initialized at conftest time, so it takes
# effect.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running; excluded from the tier-1 run")
