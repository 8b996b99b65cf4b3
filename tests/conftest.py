"""Test harness: single host stands in for a pod.

Mirrors the reference's test strategy (SURVEY.md §4): everything distributed
runs on one machine — there, `local[N]` Spark / local Ray; here, an 8-device
virtual CPU mesh via `--xla_force_host_platform_device_count=8`. Must be set
before jax initializes its backends, hence module-level in conftest.
"""

import os

# Force-override whatever platform the machine selects: every test outside
# tests/tpu runs on the virtual CPU mesh.
# tests/tpu re-runs itself in a child pytest that needs the REAL backend;
# the child sets ZOO_TPU_SUBPROC so this pin steps aside there.
if os.environ.get("ZOO_TPU_SUBPROC") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla_flags:
        os.environ["XLA_FLAGS"] = (
            xla_flags + " --xla_force_host_platform_device_count=8").strip()
    # init_zoo_context turns JAX's persistent compilation cache on. Its
    # placement is tested (test_compile_cache.py), but writing every tiny
    # CPU executable to a cold cache measured +50% wall time on test
    # files, which this suite cannot spare; children inherit the switch.
    os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
# Keep CPU tests deterministic and fast.
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402
import pytest  # noqa: E402

if os.environ.get("ZOO_TPU_SUBPROC") != "1":
    jax.config.update("jax_platforms", "cpu")


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def rng():
    return jax.random.PRNGKey(0)
