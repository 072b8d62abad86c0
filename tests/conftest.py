"""Test configuration: force JAX onto a virtual 8-device CPU mesh BEFORE jax imports.

Mirrors the reference's testcontainers strategy (SURVEY §4.3) — multi-device behavior
is tested without fixed TPU infra by forcing XLA's host platform to expose 8 virtual
devices; sharding/collective code paths compile and execute for real.
"""

import os

# Both variables are read when JAX first initialises a backend, which no import
# above this line has done.
if not os.environ.get("RUN_TPU_TESTS"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    assert jax.devices()[0].platform == "cpu", "tests must run on the virtual CPU mesh"

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running gates excluded from the tier-1 `-m 'not slow'` run")


@pytest.fixture()
def client_hub():
    from cyberfabric_core_tpu.modkit import ClientHub

    return ClientHub()


@pytest.fixture()
def fresh_registry():
    """Isolate module registrations per test."""
    # ensure the full decorator inventory exists BEFORE saving — otherwise a
    # first-in-process user of this fixture snapshots an empty registry and
    # teardown wipes the registrations for every later test
    import cyberfabric_core_tpu.modules  # noqa: F401
    from cyberfabric_core_tpu.modkit import registry as reg

    saved = list(reg._REGISTRATIONS)
    reg._REGISTRATIONS.clear()
    yield reg
    reg._REGISTRATIONS[:] = saved
