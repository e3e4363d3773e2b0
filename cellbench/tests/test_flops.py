"""``flops.py`` against hand-worked values and XLA's own count."""

import pytest

from cellbench import flops


def test_lm_flops_per_token_hand_worked():
    # 6*12*1536^2*18 = 3.058e9; 6*1536*50257 = 4.63e8; 6*2048*1536*18 = 3.40e8
    got = flops.lm_model_flops_per_token(1536, 18, 50257, 2048)
    assert got == pytest.approx(3.86e9, rel=2e-3)
    assert got == flops.lm_model_flops_per_token(1536, 18, 50257, 2048,
                                                 n_inner=6144)


def test_flash_call_counts():
    shape = dict(b=4, h=12, s=2048, dh=128)
    full = 2.0 * 4 * 12 * 2048 * 2048 * 128
    assert flops.flash_call_flops("fwd", **shape) == 2 * full / 2
    assert flops.flash_call_flops("dq", **shape) == 3 * full / 2
    assert flops.flash_call_flops("dkv", causal=False, **shape) == 4 * full
    t, bound = flops.roofline_seconds(
        flops.flash_call_flops("fwd", **shape),
        flops.flash_call_bytes("fwd", **shape),
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "compute" and t == pytest.approx(2 * full / 2 / 197e12)


def test_resnet50_macs_against_cost_analysis():
    import jax
    import jax.numpy as jnp

    from chainermn_tpu import models

    model = models.ResNet50(num_classes=1000, train=False)
    x = jnp.zeros((1, 224, 224, 3), jnp.bfloat16)
    variables = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, x))
    compiled = jax.jit(model.apply).lower(variables, x).compile()
    xla_macs = compiled.cost_analysis()["flops"] / 2
    assert flops.resnet50_forward_macs() == pytest.approx(xla_macs, rel=0.05)
    assert flops.resnet50_forward_macs() == pytest.approx(4.09e9, rel=5e-3)
