"""Ahead-of-time compiles of the Pallas kernels for a described v5e.

The TPU compiler is installed on the CPU-only test machine and compiles
for a chip that is described, not attached.  These cases hold the
main-path kernels at their real widths to "Mosaic accepts this": what
interpret mode cannot see (a slice not aligned to the tiling, a kernel
over its VMEM budget, a kernel that cannot be partitioned) is refused
here.  Nothing executes and nothing is timed — a compile that passes is
not a chip run (``chip_smoke.py`` is).

The described chip is ``conftest.py``'s ``topo`` fixture.  The programs
around the kernels have files of their own, so that the driver's
``--dist loadfile`` can spread them (``tests/README.md``): the LM step's
in ``test_tpu_compile_lm_step.py``, and one file a configuration's
whole step (``test_tpu_compile_granite4hmicro.py``,
``test_tpu_compile_qwen3next80b.py``, ``test_tpu_compile_kimilinear48b.py``).
"""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from chainermn_tpu.ops import pallas_attention as pa


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _grad_of(attend):
    """d(sum of outputs)/d(q, k, v): the backward kernels join the
    program."""
    def fn(q, k, v):
        return jax.grad(
            lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    return fn


def _qkv(sharding, b, s, h, d):
    x = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=sharding)
    return x, x, x


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("shape,kw", [  # (b, s, h, d), keyword overrides
    pytest.param((8, 2048, 8, 128), {}, id="s2048_d128"),
    pytest.param((1, 8192, 8, 128), {}, id="s8192_d128_b1"),
    pytest.param((8, 2048, 4, 256), {}, id="s2048_d256"),
    pytest.param((8, 1000, 8, 128), {}, id="ragged_s1000"),
    pytest.param((8, 2048, 8, 128),
                 dict(block_q=1024, block_k=2048,
                      bwd_block_q=1024, bwd_block_k=1024),
                 id="bench_geometry"),
    # the LM cells' own launch (Cerebras-GPT-590M, 4 x 2048 a chip):
    # default blocks, so the diagonal blocks run in compute tiles
    pytest.param((4, 2048, 12, 128), {}, id="cells_s2048_h12"),
])
def test_flash_attention_compiles(one_chip, shape, kw, grad):
    flash = functools.partial(
        pa.flash_attention, causal=True, interpret=False, **kw
    )
    text = _compiled_text(
        _grad_of(flash) if grad else flash, *_qkv(one_chip, *shape))
    # forward only: 1 kernel; with the gradient: forward + dq + dk/dv
    assert text.count("tpu_custom_call") >= (3 if grad else 1)


@pytest.mark.parametrize("page_size", [8, 16, 128])
def test_flash_decode_compiles(one_chip, page_size):
    capacity, heads, d, pages_per_slot = 32, 8, 128, 2048 // page_size
    num_pages = capacity * pages_per_slot + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages = sds((num_pages, page_size, heads, d), jnp.bfloat16)
    text = _compiled_text(
        functools.partial(pa.flash_decode, interpret=False),
        sds((capacity, heads, d), jnp.bfloat16), pages, pages,
        sds((capacity, pages_per_slot), jnp.int32),
        sds((capacity,), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_fused_cast_scale_compiles_at_resnet50_largest_bucket(one_chip):
    from chainermn_tpu.comm_wire.planner import plan_of_tree
    from chainermn_tpu.models import ResNet50

    variables = jax.eval_shape(
        lambda: ResNet50(num_classes=1000, train=True).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3), jnp.bfloat16)
        )
    )
    plan = plan_of_tree(variables["params"])
    n = max(b.size for b in plan.buckets)
    assert n > 4 * 1024 * 1024  # a real bucket, not a toy
    text = _compiled_text(
        lambda x: pa.fused_cast_scale(x, 0.25, jnp.bfloat16,
                                      interpret=False),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_ring_flash_compiles_on_four_chip_mesh(topo, grad):
    from chainermn_tpu.parallel.ring_attention import ring_attention

    mesh = Mesh(np.array(topo.devices).reshape(4), ("mn_seq",))
    spec = P(None, "mn_seq")
    ring = jax.shard_map(
        lambda q, k, v: ring_attention(
            q, k, v, "mn_seq", causal=True, use_flash=True,
            interpret=False,
        ),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    text = _compiled_text(
        _grad_of(ring) if grad else ring,
        *_qkv(NamedSharding(mesh, spec), 1, 8192, 8, 128),
    )
    assert "tpu_custom_call" in text
    assert "collective-permute" in text  # the ring itself


def test_block_diffusion_attention_compiles_at_the_cell_shape(one_chip):
    """The block-causal grouped-query kernels of ``sdar30b_train_bd4_s8192``
    (one 8192-token sequence as clean + noised copy, 32 query and 4
    key/value heads of 128, block length 4): both launches of the
    forward, dq and dk/dv kernels."""
    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16,
                              sharding=one_chip)
    text = _compiled_text(
        _grad_of(functools.partial(
            pa.block_diffusion_attention, block=4, interpret=False)),
        q, kv, kv)
    for kernel in ("_bdflash_forward", "_bdflash_backward_dq",
                   "_bdflash_backward_dkdv"):
        assert text.count(f"{kernel}/pallas_call") >= 2, kernel


def test_grouped_matmul_compiles_at_the_cell_shape(one_chip):
    """The expert layer's grouped products at the cell's sizes: 144 row
    blocks of 256 through 16 experts of 2048 x 768, forward and both
    gradients, up and down projections."""
    from chainermn_tpu.ops import grouped_matmul as gm

    rows = jax.ShapeDtypeStruct((144 * 256, 2048), jnp.bfloat16,
                                sharding=one_chip)
    up = jax.ShapeDtypeStruct((16, 2048, 768), jnp.float32,
                              sharding=one_chip)
    down = jax.ShapeDtypeStruct((16, 768, 2048), jnp.float32,
                                sharding=one_chip)
    groups = jax.ShapeDtypeStruct((144,), jnp.int32, sharding=one_chip)

    def loss(x, up, down, groups):
        hidden = gm.grouped_matmul(x, up, groups, 256, False)
        return gm.grouped_matmul(hidden, down, groups, 256,
                                 False).astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, (0, 1, 2)), rows, up, down, groups)
    # the first product forward (a sum's gradient needs no value of the
    # second), two input gradients, two weight gradients
    assert text.count('custom_call_target="tpu_custom_call"') >= 5
    assert "_grouped_matmul_dw" in text


def test_block_causal_attention_compiles_at_head_width_64(one_chip):
    """The causal launch of ``granite4hmicro_train_s8192``: one
    8192-token sequence, 32 query and 8 key/value heads of **64** (the
    other cells' heads are 128 wide), ``scale = 1 / 64`` handed to the
    kernels: forward, dq and dk/dv."""
    q = jax.ShapeDtypeStruct((1, 8192, 32, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 64), jnp.bfloat16,
                              sharding=one_chip)

    def attend(q, k, v):
        return pa.block_causal_attention_with_lse(
            q, k, v, 1, scale=1.0 / 64, interpret=False)[0]

    text = _compiled_text(_grad_of(attend), q, kv, kv)
    for kernel in ("_bdflash_forward", "_bdflash_backward_dq",
                   "_bdflash_backward_dkdv"):
        assert text.count(f"{kernel}/pallas_call") >= 1, kernel


def test_block_causal_attention_compiles_at_head_width_256(one_chip):
    """The causal launch of ``qwen3next80b_train_s8192``: 8192-token
    sequences, 16 query and 2 key/value heads of **256**: forward, dq
    and dk/dv.  At this width the family's default block is 512 (inside
    the cell's step a 1024 block asks for 16.9 MB of the 16 MB of scoped
    VMEM and is refused; alone it compiles)."""
    q = jax.ShapeDtypeStruct((1, 8192, 16, 256), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 2, 256), jnp.bfloat16,
                              sharding=one_chip)

    def attend(q, k, v):
        return pa.block_causal_attention_with_lse(
            q, k, v, 1, interpret=False)[0]

    text = _compiled_text(_grad_of(attend), q, kv, kv)
    for kernel in ("_bdflash_forward", "_bdflash_backward_dq",
                   "_bdflash_backward_dkdv"):
        assert text.count(f"{kernel}/pallas_call") >= 1, kernel
    assert pa._bc_geometry(q, kv, 1, None, False, "fwd", None)[1] == 512
    narrow = jax.ShapeDtypeStruct((1, 8192, 16, 128), jnp.bfloat16)
    assert pa._bc_geometry(narrow, narrow, 1, None, False, "fwd",
                           None)[1] == 1024


def test_block_causal_attention_compiles_at_key_width_192_value_width_128(
        one_chip):
    """The causal launch of ``kimilinear48b_train_s8192``: two
    8192-token sequences, 32 heads, keys **192** wide against values
    **128**, each at its own width (Mosaic takes the 192: on the chip
    the same launch with the keys padded to 256 lanes was 5 % slower,
    ``PERF.md`` section 6, PR 43): forward, dq and dk/dv."""
    qk = jax.ShapeDtypeStruct((2, 8192, 32, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16,
                             sharding=one_chip)

    def attend(q, k, v):
        return pa.block_causal_attention_with_lse(
            q, k, v, 1, scale=192 ** -0.5, interpret=False)[0]

    compiled = jax.jit(_grad_of(attend)).lower(qk, qk, v).compile()
    text = compiled.as_text()
    for kernel in ("_bdflash_forward", "_bdflash_backward_dq",
                   "_bdflash_backward_dkdv"):
        assert text.count(f"{kernel}/pallas_call") >= 1, kernel
    assert [x.shape for x in compiled.out_info] == [
        qk.shape, qk.shape, v.shape]
    assert pa._bc_geometry(qk, qk, 1, None, False, "fwd", None)[1] == 512


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_channel_decay_scan_compiles_at_the_cell_shape(one_chip, grad):
    """The channel-wise delta rule of ``kimilinear48b_train_s8192`` (two
    8192-token sequences, 32 heads of 128, chunk 64, ``g`` a float32 a
    key channel) through its Pallas kernels (``ops/kda_kernels.py``,
    asked for with ``interpret=False`` as a TPU takes them by itself):
    the forward launch alone, and with the gradient the forward that
    keeps the entering states and each chunk's ``T`` and the backward
    launch, each traced under the ``gdn_scan`` scope with no loop beside
    them; the residuals (537 + 134 MB) and ``dg`` (268 MB) are the
    temporaries, with the operands' copies into the kernels' layout."""
    from chainermn_tpu.ops.gated_delta import gated_delta_scan

    head = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16,
                                sharding=one_chip)
    g = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.float32,
                             sharding=one_chip)
    beta = jax.ShapeDtypeStruct((2, 8192, 32), jnp.float32,
                                sharding=one_chip)
    scan = functools.partial(gated_delta_scan, interpret=False)
    fn = jax.grad(lambda *a: scan(*a).astype(jnp.float32).sum(),
                  argnums=range(5)) if grad else scan
    compiled = jax.jit(fn).lower(head, head, head, g, beta).compile()
    text = compiled.as_text()
    kernels = re.findall(r'op_name="([^"]*/(_kda_\w+)/pallas_call)"', text)
    assert {name for _, name in kernels} == (
        {"_kda_forward", "_kda_backward"} if grad else {"_kda_forward"})
    assert all("/gdn_scan/" in op_name for op_name, _ in kernels)
    assert not re.search(r'op_name="[^"]*gdn_scan[^"]*while', text)
    assert compiled.memory_analysis().temp_size_in_bytes < (
        1.7e9 if grad else 0.7e9)


@pytest.mark.parametrize("shape", [
    # (b, s, channels, first column, columns of x, bias): a layer of
    # kimilinear48b, of qwen3next80b (q | k | v of the in-projection's q
    # | k | v | z) and of granite4hmicro (xBC of z | xBC | dt)
    pytest.param((2, 8192, 12288, 0, 12288, False), id="kimilinear48b"),
    pytest.param((2, 8192, 8192, 0, 12288, False), id="qwen3next80b"),
    pytest.param((1, 8192, 4352, 4096, 8512, True), id="granite4hmicro"),
])
def test_convolution_backward_compiles_at_the_cells_shapes(one_chip, shape):
    """The gradient of ``SiLU(causal_conv1d(...))`` through its Pallas
    kernel (``ops/conv_kernels.py``, asked for with ``interpret=False``
    as a TPU takes it by itself): one ``tpu_custom_call`` traced under
    the caller's scope that reads ``x``'s column range where it lies,
    nothing of autodiff's form left beside it (its pads of the four
    taps' cotangents to ``s + 3`` rows and the forward's padded ``x``:
    the forward is not computed for a gradient), and no temporary the
    size of ``x`` but ``dx`` and the cotangent handed in."""
    from chainermn_tpu.ops.ssd_scan import causal_conv1d

    b, s, c, start, total, has_bias = shape
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)
    args = (sd((b, s, total), jnp.bfloat16), sd((4, c), jnp.float32)) \
        + ((sd((c,), jnp.float32),) if has_bias else ())
    conv = functools.partial(causal_conv1d, scope="a_mixers_conv",
                             silu=True, first_column=start, interpret=False)
    compiled = jax.jit(jax.grad(
        lambda *a: conv(*a).astype(jnp.float32).sum(),
        argnums=range(len(args)))).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    kernel, = set(re.findall(r'op_name="([^"]*/pallas_call)"', text))
    # (outermost, the scope is wrapped: "transpose(jvp(a_mixers_conv))")
    assert re.search(r"a_mixers_conv\)*/_conv_backward/pallas_call$", kernel)
    assert f"[{b},{s + 3}," not in text
    assert [x.shape for x in compiled.out_info] == [a.shape for a in args]
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 2.1 * b * s * c * 2


def _scan_shapes(one_chip):
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)
    return (sd((1, 8192, 64, 64), jnp.bfloat16),
            sd((1, 8192, 64), jnp.float32), sd((64,), jnp.float32),
            sd((1, 8192, 128), jnp.bfloat16),
            sd((1, 8192, 128), jnp.bfloat16), sd((64,), jnp.float32))


def test_chunked_scan_compiles_at_the_cell_shape(one_chip):
    """``ops.ssd_scan``'s XLA form at one Mamba-2 layer of the cell: 8192
    positions, 64 heads of 64, state 128, chunk 256, forward and all
    gradients; the step's temporaries stay a pass's worth (no
    ``(chunks, heads, 256, 256)`` float32 tensor is kept: 537 MB)."""
    from chainermn_tpu.ops.ssd_scan import ssd_scan

    compiled = jax.jit(jax.grad(
        lambda *a: ssd_scan(*a).astype(jnp.float32).sum(),
        argnums=range(6))).lower(*_scan_shapes(one_chip)).compile()
    assert "tpu_custom_call" not in compiled.as_text()  # traced off a TPU
    assert compiled.memory_analysis().temp_size_in_bytes < 537e6 * 2


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_chunked_scan_kernels_compile_at_the_cell_shape(one_chip, grad):
    """The same layer through the Pallas kernels (``ops/ssd_kernels.py``,
    asked for with ``interpret=False`` as a TPU takes them by itself):
    the forward launch alone, and with the gradient the forward that
    keeps the entering states and the backward launch, each traced
    under the ``ssm_scan`` scope; the residual states (67 MB) and the
    per-group ``dB`` / ``dC`` (2 x 34 MB) are the temporaries."""
    from chainermn_tpu.ops.ssd_scan import ssd_scan

    scan = functools.partial(ssd_scan, interpret=False)
    fn = jax.grad(lambda *a: scan(*a).astype(jnp.float32).sum(),
                  argnums=range(6)) if grad else scan
    compiled = jax.jit(fn).lower(*_scan_shapes(one_chip)).compile()
    kernels = re.findall(r'op_name="([^"]*/(_ssd_\w+)/pallas_call)"',
                         compiled.as_text())
    assert {name for _, name in kernels} == (
        {"_ssd_forward", "_ssd_backward"} if grad else {"_ssd_forward"})
    assert all("/ssm_scan/" in op_name for op_name, _ in kernels)
    assert compiled.memory_analysis().temp_size_in_bytes < 537e6


def test_chunked_scan_kernels_compile_at_the_other_sizes_they_tile(one_chip):
    """What ``ssd_kernels.tiles`` admits besides the cell's launch: a
    chunk of 128, one group of 8 heads, two sequences whose length is
    no multiple of the chunk; forward and gradient."""
    from chainermn_tpu.ops.ssd_scan import ssd_scan

    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)
    args = (sd((2, 1000, 8, 64), jnp.bfloat16),
            sd((2, 1000, 8), jnp.float32), sd((8,), jnp.float32),
            sd((2, 1000, 128), jnp.bfloat16),
            sd((2, 1000, 128), jnp.bfloat16), sd((8,), jnp.float32))
    text = _compiled_text(jax.grad(
        lambda *a: ssd_scan(*a, chunk=128, interpret=False).astype(
            jnp.float32).sum(), argnums=range(6)), *args)
    assert text.count('custom_call_target="tpu_custom_call"') == 2


def _delta_shapes(one_chip, b=2, s=8192, key_heads=16, heads=32):
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)
    return (sd((b, s, key_heads, 128), jnp.bfloat16),
            sd((b, s, key_heads, 128), jnp.bfloat16),
            sd((b, s, heads, 128), jnp.bfloat16),
            sd((b, s, heads), jnp.float32), sd((b, s, heads), jnp.float32))


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_gated_delta_kernels_compile_at_the_cell_shape(one_chip, grad):
    """A Gated DeltaNet layer of ``qwen3next80b_train_s8192`` through the
    Pallas kernels (``ops/gated_delta_kernels.py``, asked for with
    ``interpret=False`` as a TPU takes them by itself): two sequences of
    8192 positions, 16 key and 32 value heads of 128, chunk 64; the
    forward launch alone, and with the gradient the forward that keeps
    the entering states and each chunk's ``T`` and the backward launch,
    each traced under the ``gdn_scan`` scope with no loop beside them;
    the residuals (537 + 134 MB) are the temporaries."""
    from chainermn_tpu.ops.gated_delta import gated_delta_scan

    scan = functools.partial(gated_delta_scan, interpret=False)
    fn = jax.grad(lambda *a: scan(*a).astype(jnp.float32).sum(),
                  argnums=range(5)) if grad else scan
    compiled = jax.jit(fn).lower(*_delta_shapes(one_chip)).compile()
    text = compiled.as_text()
    kernels = re.findall(r'op_name="([^"]*/(_gdn_\w+)/pallas_call)"', text)
    assert {name for _, name in kernels} == (
        {"_gdn_forward", "_gdn_backward"} if grad else {"_gdn_forward"})
    assert all("/gdn_scan/" in op_name for op_name, _ in kernels)
    assert not re.search(r'op_name="[^"]*gdn_scan[^"]*while', text)
    assert compiled.memory_analysis().temp_size_in_bytes < (
        1.25e9 if grad else 0.25e9)


def test_gated_delta_kernels_compile_at_the_other_sizes_they_tile(one_chip):
    """What ``gated_delta_kernels.tiles`` admits besides the cell's
    launch: one value head a key head and four, a length that is no
    multiple of a grid point's four chunks; forward and gradient."""
    from chainermn_tpu.ops.gated_delta import gated_delta_scan

    for key_heads, heads in ((2, 2), (1, 4)):
        text = _compiled_text(jax.grad(
            lambda *a: gated_delta_scan(*a, interpret=False).astype(
                jnp.float32).sum(), argnums=range(5)),
            *_delta_shapes(one_chip, 2, 1000, key_heads, heads))
        assert text.count('custom_call_target="tpu_custom_call"') == 2
