"""Test env: JAX on the CPU with an 8-device virtual mesh (SURVEY.md §4.4).

Both settings are environment variables JAX reads when it is imported,
so they are set here, before the first ``import jax`` of the session.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402


def pytest_report_header(config):
    return f"jax devices: {jax.device_count()} ({jax.devices()[0].platform})"
