"""Test harness configuration.

Mirrors the reference's "real small world, no mocks" strategy (SURVEY.md
section 4): instead of `mpiexec -n 8 pytest`, we run every communicator
against a *real* 8-device mesh — virtual CPU devices created via
``--xla_force_host_platform_device_count`` — so collectives execute real
XLA programs, not stubs.  Env vars must be set before jax initializes.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax  # noqa: E402
import pytest  # noqa: E402

# Force the CPU backend whatever JAX_PLATFORMS says: the tests never
# take the chip.
jax.config.update("jax_platforms", "cpu")


def cpu_devices(n=8):
    devs = jax.devices("cpu")
    if len(devs) < n:
        pytest.skip(f"need {n} cpu devices, have {len(devs)}")
    return devs[:n]


@pytest.fixture(scope="session")
def devices8():
    return cpu_devices(8)


@pytest.fixture(scope="session")
def mesh8(devices8):
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(devices8), ("mn",))


@pytest.fixture
def pinned_and_unpinned_texts(monkeypatch):
    """``texts(build)``: the compiled text of the step ``build() ->
    (step, args)`` makes, as ``build_train_step`` jits it and again with
    ``in_shardings`` taken off its ``jax.jit``: for arguments that lie as
    the specs say, the pinned program must be the one ``jit`` made when
    it read the layout off the arguments."""
    real_jit = jax.jit

    def texts(build):
        pinned, out = [], []

        def unpinned_jit(*args, **kwargs):
            pinned.append(kwargs.pop("in_shardings", None))
            return real_jit(*args, **kwargs)

        for jit in (real_jit, unpinned_jit):
            monkeypatch.setattr(jax, "jit", jit)
            # one call site for both: the program text records its stack
            step, args = build()
            pinned.clear()  # build() may jit helpers of its own
            out.append(step.get_jitted(*args[:2]).lower(
                *args).compile().as_text())
        monkeypatch.setattr(jax, "jit", real_jit)
        assert any(shardings is not None for shardings in pinned)
        return out

    return texts


def subprocess_env(devices: int = 8) -> dict:
    """Env for spawning a framework subprocess on a virtual CPU mesh —
    shared by the multi-process tier and the example smoke tests (one
    place to change the recipe)."""
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    return env
