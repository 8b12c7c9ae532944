"""Repo-level pytest config: the tests run on the CPU backend, with a
deterministic 8-device CPU mesh.

Sharding / halo-exchange logic is tested without TPU hardware via XLA's
host-platform device virtualization (SURVEY.md §4: "CPU tests with
xla_force_host_platform_device_count=8").  The chip is reached only
through ``python chip_smoke.py`` on a machine that has one; the
compile rehearsals in tests/test_tpu_compile.py are the pre-flight.
The persistent compilation cache is off for the suite: CPU compiles
gain nothing from it, and every worker would write into the checkout.

The ``cpu_mesh4`` fixture below is the CPU-mesh test rig (ISSUE 7):
a session-scoped 4-device channel-sharding mesh over the virtualized
host devices, so sharded == single-device byte-identity runs in
tier-1 on any CPU box.  Tests that need a different layout call
``tpudas.parallel.mesh.make_mesh`` themselves under the same 8
virtual devices.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("MPLBACKEND", "Agg")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="session")
def cpu_mesh4():
    """Session-scoped 4-device channel mesh (``{'time': 1, 'ch': 4}``)
    over the CPU-virtualized devices — what the realtime sharded ==
    single-device equivalence tests run on."""
    if len(jax.devices()) < 4:
        pytest.skip(
            "needs >= 4 devices (XLA_FLAGS "
            "--xla_force_host_platform_device_count)"
        )
    from tpudas.parallel.mesh import make_mesh

    return make_mesh(4)
