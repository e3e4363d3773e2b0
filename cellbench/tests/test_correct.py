"""``correct`` has to come out false when it should: for the control
(the reference in scaled float8 put in the program's place), and for a
run whose timed path is broken underneath.  Rehearsal sizes, CPU; the
same readings were taken on the chip at the cells' own sizes
(``chip_readings.py``; ``PERF.md`` has them)."""

import argparse
import importlib

import pytest

from cellbench import compare, run


def _readings(workload, seeds, sizes, traffic):
    spec, _ = run.load_spec(workload, seeds[0], True, False)
    spec.sizes.update(sizes)
    spec.traffic.update(traffic)
    cell = importlib.import_module(
        f"cellbench.runners.{spec.config['runner']}").build(spec)
    for seed in seeds:
        cell.reseed(seed)
        program, inputs = cell.first_steps(), cell.first_inputs()
        reference = cell.reference(inputs)
        control = cell.reference(inputs, lowp=True)
        yield (compare.readings(program, reference),
               compare.readings(control, reference))


# BatchNorm over a rehearsal's 8 images is too ill-conditioned to tell
# bf16 from float8; 32 images of 64 px are the least that does
@pytest.mark.parametrize("workload,number,sizes,traffic", [
    ("cgpt590m_train_s2048", "grad_small_diff", {}, {}),
    ("resnet50_train_fed_b128", "grad_small_diff", {"image_size": 64},
     {"batch": 32, "n_train": 128, "n_val": 32}),
])
def test_control_stands_apart_from_the_program(workload, number, sizes,
                                               traffic):
    """At a test's size the bf16 program and the float8 control both
    move away from the float32 reference; the control at least three
    times as far in the number that separates them, so a limit between
    the two fails the one and passes the other."""
    sound, control = [], []
    for program_gaps, control_gaps in _readings(workload, (11, 12, 13),
                                                sizes, traffic):
        sound.append(program_gaps[number][0])
        control.append(control_gaps[number][0])
    assert min(control) > 3 * max(sound), (sound, control)
    limit = (min(control) * max(sound)) ** 0.5
    assert all(s <= limit for s in sound)
    assert all(c > limit for c in control)


def _run(workload, build=None):
    args = argparse.Namespace(workload=workload, seed=21, seconds=1.0,
                              trace=0, rehearse=True)
    return run.run_cell(args, check_chip=False, build=build)


def test_sound_run_is_correct():
    assert _run("cgpt590m_train_s2048")["correct"] is True


def test_step_that_returns_its_state_unchanged_is_not_correct():
    from cellbench.runners import train_lm

    def broken(spec):
        cell = train_lm.build(spec)
        step = cell.step

        def no_update(params, opt_state, batch):
            _, _, metrics = step(jax_copy(params), jax_copy(opt_state),
                                 batch)
            return params, opt_state, metrics

        no_update.place_batch = step.place_batch
        cell.step = no_update
        return cell

    import jax

    def jax_copy(tree):  # the real step donates its arguments
        return jax.tree_util.tree_map(lambda x: x.copy(), tree)

    result = _run("cgpt590m_train_s2048", build=broken)
    assert result["correct"] is False
    assert result["attempted"] > 0


def test_part_of_the_batch_left_out_is_not_correct():
    from cellbench.runners import train_lm

    def broken(spec):
        cell = train_lm.build(spec)
        step = cell.step

        def half_batch(params, opt_state, batch):
            # every row the first row: what was fed is not what is used
            batch = batch.at[1:].set(batch[:1])
            return step(params, opt_state, batch)

        half_batch.place_batch = step.place_batch
        cell.step = half_batch
        return cell

    assert _run("cgpt590m_train_s2048", build=broken)["correct"] is False
