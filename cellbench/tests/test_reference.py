"""Each plain reference against the system at rehearsal size on the
CPU: logits, loss and gradients, with the system's model in float32 so
that the two must agree closely, and the control (scaled float8) apart
from both."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _max_rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def test_gpt_reference_matches_transformer_lm():
    from chainermn_tpu.models.transformer import TransformerLM, lm_loss
    from cellbench.reference import cerebras_gpt as ref
    from cellbench.runners.train_lm import keyed_leaves, program_tree

    cfg = {"n_embd": 64, "n_layer": 2, "n_head": 2, "n_inner": 256,
           "n_positions": 128, "vocab_size": 257}
    w = ref.init_weights(ref.seed_key(3), cfg)
    tokens = np.random.default_rng(0).integers(0, 257, (2, 128),
                                               dtype=np.int32)
    model = TransformerLM(vocab_size=257, d_model=64, n_heads=2,
                          n_layers=2, max_len=128, dtype=jnp.float32)
    tree = program_tree(w, 2)
    logits = model.apply(tree, tokens)
    want = jnp.stack([ref.logits_fn(w, t, cfg) for t in tokens])
    assert _max_rel(logits, want) < 1e-4

    loss, grads = jax.value_and_grad(
        lambda p: lm_loss(model.apply(p, tokens), tokens))(tree)
    ref_loss, ref_grads = ref._loss_and_grad(w, tokens[None], cfg, False)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    got = keyed_leaves(grads, 2)
    for name in ref.TOP_LEAVES:
        assert _max_rel(got[name], ref_grads[name]) < 1e-3, name
    for name in ref.LAYER_LEAVES:
        for l in range(2):
            assert _max_rel(got[f"{name}.{l}"], ref_grads[name][l]) < 1e-3, \
                (name, l)
    # the control is the same mathematics in scaled float8: apart from both
    low = jnp.stack([ref.logits_fn(w, t, cfg, lowp=True) for t in tokens])
    assert _max_rel(low, want) > 30 * _max_rel(logits, want)


def test_resnet_reference_matches_resnet50():
    from chainermn_tpu import models
    from cellbench.reference import resnet50 as ref
    from cellbench.runners.train_image import _flat, _nest

    cfg = {"num_filters": 64, "num_classes": 10}
    w = ref.init_weights(ref.seed_key(7), cfg)
    x = np.random.default_rng(0).standard_normal((8, 32, 32, 3),
                                                 dtype=np.float32)
    y = np.random.default_rng(1).integers(0, 10, 8)
    model = models.ResNet50(num_classes=10, train=True, dtype=jnp.float32)
    stats = model.init({"params": jax.random.PRNGKey(0)},
                       jnp.zeros((1, 32, 32, 3)))["batch_stats"]

    def loss_of(params):
        import optax

        out, _ = model.apply({"params": params, "batch_stats": stats},
                             jnp.asarray(x), mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            out, jnp.asarray(y)).mean(), out

    (loss, logits), grads = jax.value_and_grad(loss_of, has_aux=True)(
        _nest(w))
    want = ref.logits_fn(w, jnp.asarray(x))
    assert _max_rel(logits, want) < 1e-4
    ref_loss, ref_grads = jax.value_and_grad(ref.loss_fn)(
        w, jnp.asarray(x), jnp.asarray(y))
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    got = _flat(grads)
    assert set(got) == set(ref_grads)
    for name in got:
        # BatchNorm over 8 samples (1x1 maps in the last stage) amplifies
        # float32 summation-order differences at this size
        assert _max_rel(got[name], ref_grads[name]) < 1e-2, name
    low = ref.logits_fn(w, jnp.asarray(x), lowp=True)
    assert _max_rel(low, want) > 30 * _max_rel(logits, want)
