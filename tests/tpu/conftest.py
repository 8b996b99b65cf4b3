"""Real-chip test subset: runs whenever a TPU backend is reachable.

On the machine with the chip, run the directory directly:

    ZOO_TPU_SUBPROC=1 python -m pytest tests/tpu -q

`ZOO_TPU_SUBPROC=1` makes tests/conftest.py step aside (it pins every other
pytest process to the virtual CPU mesh before jax initializes). There a
missing TPU is a failure (exit code `NO_TPU_RC`), never a skip.

Inside a full `pytest tests/` run the process is CPU-pinned and has not
touched the chip, so this directory re-runs itself in ONE child pytest that
selects the `tpu` platform (a chip belongs to one process at a time; the
parent stays off it). The child's result gates the parent: failure fails the
suite, success skips the local copies with the child's summary, and a child
that found no TPU (this sandbox) skips them with that reason.
"""

import os
import subprocess
import sys

import jax
import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
NO_TPU_RC = 75


def _run_subprocess_suite() -> None:
    env = dict(os.environ)
    env["ZOO_TPU_SUBPROC"] = "1"
    env["JAX_PLATFORMS"] = "tpu"
    # the CPU harness turns the persistent compile cache off; the chip
    # child wants it
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    # the parent run's CPU pin may have polluted XLA_FLAGS; harmless on TPU
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", _HERE, "-q", "-rs",
         "--no-header"],
        env=env, cwd=os.path.dirname(os.path.dirname(_HERE)),
        capture_output=True, text=True, timeout=3600)
    tail = "\n".join((proc.stdout or "").splitlines()[-15:])
    if proc.returncode == NO_TPU_RC:
        pytest.skip("no TPU backend on this machine (child pytest exited "
                    f"{NO_TPU_RC})", allow_module_level=False)
    if proc.returncode == 0:
        pytest.skip("on-chip suite ran in a TPU-backend subprocess:\n"
                    + tail, allow_module_level=False)
    raise RuntimeError(
        f"on-chip subprocess suite FAILED (rc={proc.returncode}):\n"
        + tail + "\n" + "\n".join((proc.stderr or "").splitlines()[-15:]))


@pytest.fixture(scope="session", autouse=True)
def tpu_backend():
    try:
        platform = jax.devices()[0].platform
    except RuntimeError:            # "Unable to initialize backend 'tpu'"
        platform = "none"
    if platform == "tpu":
        # match the framework's TPU default (init_zoo_context): rbg PRNG
        jax.config.update("jax_default_prng_impl", "rbg")
        yield
        return
    if os.environ.get("ZOO_TPU_SUBPROC") == "1":
        # we ARE the on-chip run and there is no chip
        pytest.exit(f"no TPU backend reachable (found {platform!r})",
                    returncode=NO_TPU_RC)
    _run_subprocess_suite()
    yield
