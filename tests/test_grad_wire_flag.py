"""``examples/lm/train_lm.py --grad-wire``: the data-parallel step built
without ``param_specs``, whose gradients ``create_multi_node_optimizer``'s
bucketed wire ships, against the ``param_specs`` step (autodiff's
all-reduce a leaf) on four virtual devices: the same loss and the same
update.  ``cgpt590m_dpwire4_s2048`` runs the flag on four chips."""

import importlib.util
import os
from unittest import mock

import numpy as np
import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LR = 1e-3
ARGV = ["--cpu-mesh", "--d-model", "64", "--n-layers", "2", "--n-heads",
        "4", "--vocab", "128", "--seq-len", "32", "--batchsize", "8",
        "--steps", "2", "--report-every", "1", "--generate", "0",
        "--lr", str(LR)]


@pytest.fixture(scope="module")
def example():
    spec = importlib.util.spec_from_file_location(
        "train_lm_example", os.path.join(ROOT, "examples", "lm",
                                         "train_lm.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_wire_step_gives_the_param_specs_steps_loss_and_update(example):
    four = jax.devices("cpu")[:4]
    with mock.patch.object(jax, "devices", lambda *a: four):
        specs = example.main(ARGV)
        wire = example.main(ARGV + ["--grad-wire"])
    assert specs["comm"].size == wire["comm"].size == 4
    # the first loss is of the same weights, the second of the updated
    np.testing.assert_allclose(wire["losses"], specs["losses"], rtol=2e-6)
    for a, b in zip(jax.tree_util.tree_leaves(specs["params"]),
                    jax.tree_util.tree_leaves(wire["params"])):
        gap = np.abs(np.asarray(a) - np.asarray(b))
        # Adam's first steps are sign-like: a gradient that sums to
        # nearly nothing in another order may turn, by 2 lr a step
        assert gap.max() <= 4.1 * LR and gap.mean() < 0.01 * LR
    # the wire's step ships buckets under grad_sync, not a leaf at a time
    text = wire["step"].get_jitted(
        wire["params"], wire["opt_state"]).lower(
        wire["params"], wire["opt_state"], wire["batch"]).as_text()
    reduces = text.count("stablehlo.all_reduce")
    leaves = len(jax.tree_util.tree_leaves(wire["params"]))
    assert 0 < reduces <= 8 < leaves, (reduces, leaves)


def test_the_flag_is_the_data_parallel_steps_alone(example):
    for extra in (["--tp", "2"], ["--sp", "2"]):
        with pytest.raises(SystemExit):
            example.main(ARGV + ["--grad-wire"] + extra)
