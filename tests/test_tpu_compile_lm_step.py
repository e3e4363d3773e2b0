"""The data-parallel LM step (``build_train_step(param_specs=...)``,
flash kernels in), compiled ahead of time for a described v5e: where the
compiler puts the gradient all-reduces, and that neither the step
builder's option rule nor its ``in_shardings`` pin changes a program
they do not concern.  Nothing executes and nothing is timed.
"""

import pytest

#: collectives under this size are gains, biases and the loss
_WEIGHT_BYTES = 1 << 19


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
    """Two compiles of the one-layer step for the described chips (over
    40 s in the driver's run): the text of a TPU program is what the
    pin must leave alone, and no CPU-mesh case reads one."""
    pinned, unpinned = pinned_and_unpinned_texts(
        lambda: lm_step_builder(chips, n_layers=1))
    assert pinned == unpinned
