"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (the SURVEY.md §4 strategy:
`xla_force_host_platform_device_count` lets pjit shardings, collective merge
order, and per-shard numerics be validated on one host without a TPU slice).
Import order matters: the environment first, then jax.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402


def pytest_report_header(config):
    return f"jax devices: {jax.devices()}"


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / TF-subprocess integration tests"
    )
