"""Test environment: force CPU with 8 virtual XLA devices so every sharding
test runs an honest 8-way mesh without TPU hardware (SURVEY.md §4).

The env var is set for this process and the subprocesses tests start; the
config update covers a jax that was imported before this file ran.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)


def pytest_report_header():
    return f"jax backend: {jax.default_backend()} devices: {jax.device_count()}"
