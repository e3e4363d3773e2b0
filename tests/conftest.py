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


# ----------------------------------------------------------------------
# Ahead-of-time compiles for a described v5e (``tests/test_tpu_compile*``)
# ----------------------------------------------------------------------
#: what a v5e reports as ``memory_stats()["bytes_limit"]`` (15.75 GiB less
#: 2 MiB; read on the chip, PERF.md section 6, PR 40)
V5E_BYTES_LIMIT = 16_909_336_064


@pytest.fixture(scope="module")
def topo():
    """A described ``v5e:2x2``.  Only a worker that runs a file asking
    for it loads the TPU library, and it does so here: nothing is
    described at import time, and everything compiles in the test's own
    process."""
    # the compiler otherwise logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without the chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def aot(topo):
    """``benchmarks/collective_schedule_aot.py``, the builders of the
    cells' steps over described devices, inside its one-process
    patch."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks",
        "collective_schedule_aot.py")
    spec = importlib.util.spec_from_file_location(
        "collective_schedule_aot", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    with module.one_process():
        yield module


#: a cut-down LM: the weight gradients are 0.5-2 MiB in bf16
_SMALL_LM = dict(n_layers=2, d_model=512, n_heads=4, vocab=4096,
                 seq_len=1024, per_chip_batch=2)


@pytest.fixture(scope="module")
def lm_step_builder(topo, aot):
    """``build(chips, **sizes) -> (step, abstract args)`` over described
    devices: ``build_lm_step`` at small sizes."""
    return lambda chips, **sizes: aot.build_lm_step(
        topo.devices[:chips], **{**_SMALL_LM, **sizes})


@pytest.fixture(scope="module")
def moe_step_builder(topo, aot):
    """``build(**sizes) -> (step, abstract args)`` over one described
    chip: ``build_moe_lm_step``."""
    return lambda **sizes: aot.build_moe_lm_step(topo.devices[:1], **sizes)


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
