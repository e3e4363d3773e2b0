"""ImageNet model-zoo tests.

Parity: the reference's ``examples/imagenet/models/{alex,googlenet,
googlenetbn,nin,resnet50}.py`` archs — forward shapes, BN-state handling,
and the has_aux train-step path that carries batch statistics.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

import chainermn_tpu as cmn
from chainermn_tpu import models
from chainermn_tpu.optimizers import build_train_step

IMG = 96  # small enough to be fast, large enough for every stem/pool stack


def _init_and_forward(model, batch=2, img=IMG):
    x = jnp.zeros((batch, img, img, 3), jnp.float32)
    variables = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        x[:1],
    )
    out = model.apply(variables, x, rngs={"dropout": jax.random.PRNGKey(2)})
    return variables, out


def _abstract_init_and_forward(model, **apply_kwargs):
    """Shapes and dtypes of ``init``'s variables and of ``apply``'s
    result on a batch of two: traced by ``jax.eval_shape``, never run (a
    shape needs no arithmetic, and op by op an Inception stack at this
    size takes over a minute)."""
    x = jnp.zeros((2, IMG, IMG, 3), jnp.float32)
    variables = jax.eval_shape(
        model.init,
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        x[:1],
    )
    out = jax.eval_shape(
        lambda v: model.apply(
            v, x, rngs={"dropout": jax.random.PRNGKey(2)}, **apply_kwargs),
        variables,
    )
    return variables, out


@pytest.mark.parametrize("factory", [
    models.AlexNet, models.NIN, models.VGG16, models.GoogLeNet,
])
def test_stateless_arch_forward_shape(factory):
    model = factory(num_classes=11, train=False)
    variables, out = _abstract_init_and_forward(model)
    assert out.shape == (2, 11)
    assert out.dtype == jnp.float32
    assert "batch_stats" not in variables


@pytest.mark.parametrize("factory", [
    models.GoogLeNetBN, models.ResNet18,
])
def test_bn_arch_forward_shape(factory):
    model = factory(num_classes=7, train=True)
    variables, (out, mut) = _abstract_init_and_forward(
        model, mutable=["batch_stats"])
    assert "batch_stats" in variables
    assert out.shape == (2, 7)
    assert jax.tree_util.tree_structure(
        mut["batch_stats"]
    ) == jax.tree_util.tree_structure(variables["batch_stats"])


def test_bf16_bn_numerics_close_to_fp32_and_stats_stay_fp32():
    """The default norm normalizes in the model's compute dtype (the
    round-3 MFU lever: bf16 arithmetic, +29% ResNet-50 throughput) but
    batch STATISTICS must stay fp32-accumulated and fp32-stored — the
    bf16 model's logits and running stats must track an explicit
    fp32-norm twin within bf16 tolerance."""
    from flax import linen as nn

    from chainermn_tpu.models.resnet import ResNet18

    def fp32_norm(size, **kw):
        del size
        kw.pop("dtype", None)
        return nn.BatchNorm(
            use_running_average=kw.pop("use_running_average", None),
            momentum=0.9, epsilon=1e-5, dtype=jnp.float32, **kw,
        )

    x = jnp.asarray(
        np.random.RandomState(0).randn(4, 32, 32, 3), jnp.float32
    )
    bf16 = ResNet18(num_classes=5, train=True)  # default: bf16 BN
    fp32 = ResNet18(num_classes=5, train=True, norm=fp32_norm)
    # a program each, not op by op
    v_bf = jax.jit(bf16.init)(jax.random.PRNGKey(0), x[:1])
    v_fp = jax.jit(fp32.init)(jax.random.PRNGKey(0), x[:1])
    # identical param trees (dtype is arithmetic-only, not storage)
    chex_equal = jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(np.allclose(np.asarray(a), np.asarray(b))),
        v_bf["params"], v_fp["params"],
    ))
    assert chex_equal
    out_bf, mut_bf = jax.jit(
        lambda v: bf16.apply(v, x, mutable=["batch_stats"]))(v_bf)
    out_fp, mut_fp = jax.jit(
        lambda v: fp32.apply(v, x, mutable=["batch_stats"]))(v_fp)
    np.testing.assert_allclose(
        np.asarray(out_bf), np.asarray(out_fp), atol=0.15, rtol=0.1
    )
    # running stats: stored fp32, numerically matching the fp32 twin
    for leaf_bf, leaf_fp in zip(
        jax.tree_util.tree_leaves(mut_bf["batch_stats"]),
        jax.tree_util.tree_leaves(mut_fp["batch_stats"]),
    ):
        assert leaf_bf.dtype == jnp.float32
        np.testing.assert_allclose(
            np.asarray(leaf_bf), np.asarray(leaf_fp), atol=2e-2
        )


def test_dropout_is_train_gated():
    img = 64  # the stem and pools leave nothing of 48
    model = models.AlexNet(num_classes=5, train=True)
    variables, _ = _init_and_forward(model, img=img)
    x = jnp.ones((4, img, img, 3))
    a = model.apply(variables, x, rngs={"dropout": jax.random.PRNGKey(3)})
    b = model.apply(variables, x, rngs={"dropout": jax.random.PRNGKey(4)})
    assert not np.allclose(np.asarray(a), np.asarray(b))
    det = models.AlexNet(num_classes=5, train=False)
    c = det.apply(variables, x)
    d = det.apply(variables, x)
    np.testing.assert_allclose(np.asarray(c), np.asarray(d))


class TestHasAuxTrainStep:
    """build_train_step(has_aux=True): BN stats flow through the step and
    are mean-reduced across the mesh."""

    @pytest.fixture(scope="class")
    def comm(self, devices8):
        return cmn.create_communicator("tpu", devices=devices8)

    def test_batch_stats_updated_and_replicated(self, comm):
        model = models.ResNet18(num_classes=4, dtype=jnp.float32, train=True)
        x0 = jnp.zeros((1, 32, 32, 3))
        variables = model.init(jax.random.PRNGKey(0), x0)
        params = {"params": variables["params"],
                  "batch_stats": variables["batch_stats"]}
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), comm)

        def loss_fn(p, batch):
            x, y = batch
            out, mut = model.apply(
                {"params": p["params"], "batch_stats": p["batch_stats"]},
                x, mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(
                out, y
            ).mean()
            return loss, mut["batch_stats"]

        step = build_train_step(
            comm, loss_fn, opt, has_aux=True, donate=False,
            merge_aux=lambda p, aux: {**p, "batch_stats": aux},
        )
        params, opt_state = step.place(params, opt.init(params))
        old_stats = jax.tree_util.tree_map(
            np.asarray, jax.device_get(params["batch_stats"])
        )
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32, 3))
        y = jnp.arange(8, dtype=jnp.int32) % 4
        new_params, _, metrics = step(params, opt_state, (x, y))
        new_stats = jax.device_get(new_params["batch_stats"])
        # Stats moved (momentum update happened)
        changed = jax.tree_util.tree_map(
            lambda a, b: not np.allclose(a, b), old_stats, new_stats
        )
        assert any(jax.tree_util.tree_leaves(changed))
        assert np.isfinite(float(metrics["loss"]))
