"""Ahead-of-time compiles of the Pallas kernels for a described v5e.

The TPU compiler is installed on the CPU-only test machine and compiles
for a chip that is described, not attached.  These cases hold the
main-path kernels at their real widths to "Mosaic accepts this": what
interpret mode cannot see (a slice not aligned to the tiling, a kernel
over its VMEM budget, a kernel that cannot be partitioned) is refused
here.  Nothing executes and nothing is timed — a compile that passes is
not a chip run (``chip_smoke.py`` is).

Only the worker that runs THIS file loads the TPU library, and it does
so inside the ``topo`` fixture: nothing is described at import time, and
everything compiles in the test's own process.
"""

import dataclasses
import functools
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from chainermn_tpu.ops import pallas_attention as pa


@pytest.fixture(scope="module")
def topo():
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
    return SingleDeviceSharding(topo.devices[0])


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
    pytest.param((8, 2048, 8, 128), dict(taxonomy="legacy"), id="legacy"),
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


# ----------------------------------------------------------------------
# The data-parallel LM step (build_train_step(param_specs=...), flash
# kernels in): where the compiler puts the gradient all-reduces.
# ----------------------------------------------------------------------
#: a cut-down LM: the weight gradients are 0.5-2 MiB in bf16
_SMALL_LM = dict(n_layers=2, d_model=512, n_heads=4, vocab=4096,
                 seq_len=1024, per_chip_batch=2)
#: collectives under this size are gains, biases and the loss
_WEIGHT_BYTES = 1 << 19


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


@pytest.fixture(scope="module")
def lm_step_builder(topo, aot):
    """``build(chips, **sizes) -> (step, abstract args)`` over described
    devices: ``build_lm_step`` at small sizes."""
    return lambda chips, **sizes: aot.build_lm_step(
        topo.devices[:chips], **{**_SMALL_LM, **sizes})


def test_dp_step_reduces_weight_gradients_asynchronously(lm_step_builder):
    step, abstract = lm_step_builder(4)
    schedule = step.collective_schedule(*abstract)
    census = schedule.census(min_bytes=_WEIGHT_BYTES)
    # one all-reduce a leaf, none glued: 4 kernels a layer, 2 embeddings
    assert census["n_sync"] + census["n_async"] == 10, schedule.condensed
    # What the option set delivers: every weight gradient's all-reduce is
    # an asynchronous collective fusion with compute between start and
    # done, the float32 embedding's among them: those that find a
    # weight-gradient matmul ride it, the rest ride other leaves' AdamW
    # updates (all 74 in the cell's 18-layer step,
    # benchmarks/collective_schedule_aot.py; 44 and 0.361 of the bytes
    # before the k-loop fusions were let in).  None blocks.
    assert census["n_sync"] == 0, schedule.condensed
    assert census["n_overlapped"] == census["n_async"], schedule.condensed
    assert census["overlapped_bytes_share"] == 1.0, schedule.condensed
    # and where: every start sits behind the last backward kernel (the
    # compiler defers the weight-gradient matmuls to pair them), not
    # inside the backward
    assert schedule.condensed.index("S") > schedule.condensed.rindex("k")
    assert "k" in schedule.condensed  # the flash kernels are in


def test_one_chip_step_is_the_program_without_the_rule(
        lm_step_builder, monkeypatch):
    from chainermn_tpu import optimizers

    texts = []
    for rule in (optimizers._grad_reduce_compiler_options,
                 lambda mesh, axes: None):
        monkeypatch.setattr(
            optimizers, "_grad_reduce_compiler_options", rule)
        # one call site for both: the program text records its stack
        step, abstract = lm_step_builder(1, n_layers=1)
        texts.append(step.get_jitted(*abstract[:2]).lower(
            *abstract).compile().as_text())
    assert texts[0] == texts[1]
    assert "all-reduce" not in texts[0]


@pytest.mark.parametrize("chips", [1, 4])
def test_pinned_step_is_the_program_without_the_pin(
        lm_step_builder, pinned_and_unpinned_texts, chips):
    pinned, unpinned = pinned_and_unpinned_texts(
        lambda: lm_step_builder(chips, n_layers=1))
    assert pinned == unpinned


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


# -- the hybrid cell's step under the plan its example would choose ----------
#: what a v5e reports as ``memory_stats()["bytes_limit"]`` (15.75 GiB less
#: 2 MiB; read on the chip, PERF.md section 6, PR 40)
_V5E_BYTES_LIMIT = 16_909_336_064
#: a Mamba-2 layer of the cell: 76 182 976 float32 parameters with ``mu``
#: and ``nu`` beside them
_MAMBA_LAYER_STATE = 76_182_976 * 12
_CELL_LAYERS = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@pytest.mark.parametrize("rows,layer_types,kept", [
    # the cell: one 8192-token sequence, all ten layers, every result
    pytest.param(1, _CELL_LAYERS, "mlp_in x10, ssm_in x9", id="cell"),
    # two sequences a step, a shorter plan from the same code; five of
    # the layers, on a device that reports the other five's state less
    pytest.param(2, _CELL_LAYERS[3:8], "mlp_in x4", id="two_sequences"),
])
def test_hybrid_step_under_the_examples_plan_fits_the_chip(
        lm_step_builder, monkeypatch, rows, layer_types, kept):
    """``granite4hmicro_train_s8192``'s step (``cellbench/configs/
    granite-4.0-h-micro.json`` through ``examples/lm/train_lm.py``'s
    options) with what its blocks keep across their recomputation
    chosen as the example chooses it on a v5e (``remat_budget`` of the
    reported limit and the abstract state, ``remat_plan``): compiles,
    the scan's and the attention's kernels in it, and arguments and
    temporaries stay 0.8 GB under the limit."""
    import types

    from chainermn_tpu.models.transformer import (
        BlockOptions,
        remat_budget,
        remat_kept,
        remat_plan,
    )

    # the program asks the backend which form of the scan to trace
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    limit = _V5E_BYTES_LIMIT \
        - (len(_CELL_LAYERS) - len(layer_types)) * _MAMBA_LAYER_STATE
    options = BlockOptions(
        norm="rmsnorm", norm_eps=1e-5, n_kv_heads=8,
        attention_scale=1 / 64, layer_types=layer_types, ssm_heads=64,
        ssm_head_dim=64, ssm_state=128, ssm_conv=4, ssm_chunk=256,
        gated_mlp=True, no_positions=True, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=8.0, use_flash=True,
        remat_blocks=True)
    sizes = dict(n_layers=len(layer_types), d_model=2048, n_heads=32,
                 vocab=12544, seq_len=8192, per_chip_batch=rows, d_ff=8192,
                 chunked_ce=7, lr=1e-4)
    tokens, widths = rows * 8192, options.remat_widths(8192)
    _, state = lm_step_builder(1, options=options, **sizes)
    options = dataclasses.replace(options, remat_budget_bytes=remat_budget(
        types.SimpleNamespace(memory_stats=lambda: {"bytes_limit": limit}),
        state[:2], tokens, widths))
    step, abstract = lm_step_builder(1, options=options, **sizes)
    said, kept_bytes = remat_kept(
        remat_plan(layer_types, tokens, widths,
                   options.remat_budget_bytes), tokens, widths)
    assert said == kept
    compiled = step.get_jitted(*abstract[:2]).lower(*abstract).compile()
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held + 0.8e9 <= limit, (held, limit)
    # kept for real: the temporaries hold them
    assert memory.temp_size_in_bytes > kept_bytes
    text = compiled.as_text()
    for kernel in ("_ssd_forward", "_ssd_backward", "_bdflash_forward",
                   "ssm_conv/_conv_backward"):
        assert f"{kernel}/pallas_call" in text, kernel
    if rows == 1:  # one forward in_proj product a layer, each kept
        for width, layers in ((16384, 10), (8512, 9)):
            assert len(re.findall(rf"= bf16\[1,8192,{width}\]\S* fusion\(",
                                  text)) == layers


# ----------------------------------------------------------------------
# qwen3next80b_train_s8192's whole step, as the MoE example builds it
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def moe_step_builder(topo, aot):
    """``build(**sizes) -> (step, abstract args)`` over one described
    chip: ``build_moe_lm_step``."""
    return lambda **sizes: aot.build_moe_lm_step(topo.devices[:1], **sizes)


def test_qwen3next_step_under_the_examples_plan_compiles_for_the_chip(
        moe_step_builder, monkeypatch):
    """``qwen3next80b_train_s8192``'s step (``cellbench/configs/
    qwen3-next-80b-a3b.json`` and ``cellbench/traffic/
    train_moe_s8192.json`` through ``examples/moe_lm/train_moe_lm.py``'s
    options) with what its blocks keep chosen as the example chooses it
    on a v5e: compiles, the causal kernels at head width 256, the
    grouped products and the delta rule's kernels in it, the plan
    ``gdn_in x3``, the arguments the 7.51 GB of float32 state.
    ``memory_analysis()`` counted 11.16 GB of temporaries where the chip
    reserved 8.61 while the delta rule ran in XLA (``PERF.md`` section
    6, PR 41); with its kernels (PR 42) it counts 7.09 GB where the
    chip reserves 6.11.  Every ``pallas_call`` of the mixers lies under
    ``gdn_scan`` and no ``while`` is left there."""
    import json
    import types

    from chainermn_tpu.models.transformer import (
        BlockOptions,
        remat_budget,
        remat_kept,
        remat_plan,
    )

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "cellbench", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "cellbench", "traffic",
                           "train_moe_s8192.json")) as f:
        traffic = json.load(f)
    rows, seq = traffic["per_chip_batch"], traffic["seq_len"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kinds = ("linear_attention",) * 3 + ("attention",)
    options = BlockOptions(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), qk_norm=True,
        rotary_fraction=cfg["partial_rotary_factor"],
        attn_output_gate=True, zero_centered_norm=True, layer_types=kinds,
        gdn_key_heads=cfg["linear_num_key_heads"],
        gdn_value_heads=cfg["linear_num_value_heads"],
        gdn_key_dim=cfg["linear_key_head_dim"],
        gdn_value_dim=cfg["linear_value_head_dim"],
        gdn_conv=cfg["linear_conv_kernel_dim"],
        gdn_chunk=cfg["linear_chunk_size"], use_flash=True,
        remat_blocks=True)
    sizes = dict(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["moe_intermediate_size"], n_experts=cfg["router_experts"],
        top_k=cfg["num_experts_per_tok"],
        held=(cfg["first_expert"], cfg["num_experts"]),
        shared_d_ff=cfg["shared_expert_intermediate_size"], seq_len=seq,
        per_chip_batch=rows, chunked_ce=cfg["head_chunks"],
        lr=cfg["optimizer"]["lr"], aux_coef=cfg["aux_loss_coef"])
    tokens = rows * seq
    widths = options.remat_widths(cfg["moe_intermediate_size"])
    _, state = moe_step_builder(options=options, **sizes)
    budget = remat_budget(
        types.SimpleNamespace(
            memory_stats=lambda: {"bytes_limit": _V5E_BYTES_LIMIT}),
        state[:2], tokens, widths)
    options = dataclasses.replace(options, remat_budget_bytes=budget)
    assert remat_kept(remat_plan(kinds, tokens, widths, budget), tokens,
                      widths) == ("gdn_in x3", 3 * tokens * 12288 * 2)
    step, abstract = moe_step_builder(options=options, **sizes)
    compiled = step.get_jitted(*abstract[:2]).lower(*abstract).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == pytest.approx(
        625_667_136 * 12, rel=1e-3)
    # not above the parent's temporaries (11.16 GB with the XLA form;
    # the kernels keep no (chunk, chunk) tensor or (c, b, h, ...) copy)
    assert memory.temp_size_in_bytes <= 11_164_387_328
    assert memory.temp_size_in_bytes <= 7.3e9
    text = compiled.as_text()
    for kernel in ("_bdflash_forward", "_bdflash_backward_dq",
                   "_bdflash_backward_dkdv", "_grouped_matmul",
                   "_grouped_matmul_dw", "_gdn_forward", "_gdn_backward"):
        assert f"{kernel}/pallas_call" in text, kernel
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    kernels = [name for name in op_names if "gdn_mixer" in name
               and name.endswith("/pallas_call")]
    # three layers: the convolution's backward (PR 45) under its scope
    conv = [name for name in kernels if "/gdn_conv/" in name]
    assert len(conv) == 3 and all(
        name.endswith("/gdn_conv/_conv_backward/pallas_call")
        for name in conv), conv
    delta_rule = sorted(set(kernels) - set(conv))
    assert delta_rule and all("/gdn_scan/_gdn_" in name
                              for name in delta_rule), delta_rule
    assert not [name for name in op_names
                if "gdn_scan" in name and "while" in name]
    for scope in ("gdn_mixer", "gdn_conv", "gdn_scan", "moe_shared"):
        assert scope in text, scope


# ----------------------------------------------------------------------
# kimilinear48b_train_s8192's whole step, as the MoE example builds it
# ----------------------------------------------------------------------
def test_kimilinear_step_under_the_examples_plan_compiles_for_the_chip(
        moe_step_builder, monkeypatch):
    """``kimilinear48b_train_s8192``'s step (``cellbench/configs/
    kimi-linear-48b-a3b.json`` and ``cellbench/traffic/
    train_kda_s8192.json`` through ``examples/moe_lm/train_moe_lm.py``'s
    options) with what its blocks keep chosen as the example chooses it
    on a v5e: with the channel-wise rule's kernels ``remat_widths`` holds
    no ``KDA_WORK`` and the plan is ``mlp_in x1, kda_in x4, latent_in
    x1`` (2.42 GB; with the XLA form nothing could be kept: ``PERF.md``
    section 6, PR 43); the step compiles, arguments and temporaries
    (15.41 GB counted ahead of time) stay 1 GB under the limit the chip
    reports, the causal kernels at 192 / 128, the grouped products and
    the delta rule's kernels are in it, every ``pallas_call`` of the
    mixers lies under ``kda_scan`` and no ``while`` is left there."""
    import json
    import types

    from chainermn_tpu.models.moe_transformer import RouterOptions
    from chainermn_tpu.models.transformer import (
        KDA_WORK,
        BlockOptions,
        remat_budget,
        remat_kept,
        remat_plan,
    )

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "cellbench", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "cellbench", "traffic",
                           "train_kda_s8192.json")) as f:
        traffic = json.load(f)
    lin = cfg["linear_attn_config"]
    rows, seq = traffic["per_chip_batch"], traffic["seq_len"]
    kinds = ("kda", "kda", "kda", "latent_attention")
    options = BlockOptions(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], layer_types=kinds,
        gdn_value_heads=lin["num_heads"], gdn_key_dim=lin["head_dim"],
        gdn_value_dim=lin["head_dim"],
        gdn_conv=lin["short_conv_kernel_size"],
        gdn_chunk=cfg["linear_chunk_size"],
        latent_kv_rank=cfg["kv_lora_rank"],
        latent_nope_dim=cfg["qk_nope_head_dim"],
        latent_shared_dim=cfg["qk_rope_head_dim"],
        latent_value_dim=cfg["v_head_dim"], gated_mlp=True,
        no_positions=True, use_flash=True, remat_blocks=True)
    n_layers, dense_layers = (cfg["num_hidden_layers"],
                              cfg["first_k_dense_replace"])
    sizes = dict(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_layers=n_layers,
        d_ff=cfg["moe_intermediate_size"], n_experts=cfg["router_experts"],
        top_k=cfg["num_experts_per_token"],
        held=(cfg["first_expert"], cfg["num_experts"]),
        shared_d_ff=cfg["moe_intermediate_size"]
        * cfg["num_shared_experts"], seq_len=seq, per_chip_batch=rows,
        chunked_ce=cfg["head_chunks"], lr=cfg["optimizer"]["lr"],
        aux_coef=cfg["aux_loss_coef"],
        router_options=RouterOptions(
            score=cfg["moe_router_activation_func"], selection_bias=True,
            routed_scale=cfg["routed_scaling_factor"], shared_gated=False),
        first_dense=dense_layers, dense_d_ff=cfg["intermediate_size"])
    tokens = rows * seq
    widths_of = lambda: options.remat_widths(cfg["intermediate_size"],
                                             cfg["num_attention_heads"])
    # off the TPU the XLA form runs, and its reserve with it
    assert KDA_WORK in widths_of()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    widths = widths_of()
    assert widths == {"mlp_in": 18432, "kda_in": 12288, "latent_in": 6144}
    _, state = moe_step_builder(options=options, **sizes)
    budget = remat_budget(
        types.SimpleNamespace(
            memory_stats=lambda: {"bytes_limit": _V5E_BYTES_LIMIT}),
        state[:2], tokens, widths)
    options = dataclasses.replace(options, remat_budget_bytes=budget)
    plan = remat_plan(
        [options.layer_type(i) for i in range(n_layers)], tokens, widths,
        budget, dense=[i < dense_layers for i in range(n_layers)])
    said, kept_bytes = remat_kept(plan, tokens, widths)
    assert said == "mlp_in x1, kda_in x4, latent_in x1"
    assert kept_bytes == tokens * 2 * (18432 + 4 * 12288 + 6144)
    step, abstract = moe_step_builder(options=options, **sizes)
    compiled = step.get_jitted(*abstract[:2]).lower(*abstract).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == pytest.approx(
        602_450_816 * 12, rel=1e-3)
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held + 1.0e9 <= _V5E_BYTES_LIMIT, (held, _V5E_BYTES_LIMIT)
    assert memory.temp_size_in_bytes > kept_bytes  # kept for real
    text = compiled.as_text()
    for kernel in ("_bdflash_forward", "_bdflash_backward_dq",
                   "_bdflash_backward_dkdv", "_grouped_matmul",
                   "_grouped_matmul_dw", "_kda_forward", "_kda_backward"):
        assert f"{kernel}/pallas_call" in text, kernel
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    kernels = [name for name in op_names if "kda_mixer" in name
               and name.endswith("/pallas_call")]
    # four layers: the convolution's backward (PR 45) under its scope,
    conv = [name for name in kernels if "/kda_conv/" in name]
    assert len(conv) == 4 and all(
        name.endswith("/kda_conv/_conv_backward/pallas_call")
        for name in conv), conv
    # and a forward, its recomputation and a backward of the rule each
    delta_rule = sorted(set(kernels) - set(conv))
    assert len(delta_rule) == 12
    assert all("/kda_scan/" in name and "/gdn_scan/_kda_" in name
               for name in delta_rule), delta_rule
    assert not [name for name in op_names
                if "kda_scan" in name and "while" in name]
    for scope in ("kda_mixer", "kda_conv", "kda_scan", "latent_proj",
                  "moe_shared", "gated_mlp"):
        assert scope in text, scope
