"""Example-script smoke tests — the user-facing CLI surface.

The reference's examples ARE its integration suite (`mpiexec -n N
python train_*.py`, SURVEY.md section 2 #33-35); these tests run each
shipped script end-to-end as a subprocess on a virtual CPU mesh with
tiny shapes, asserting it exits cleanly and reaches its final report.
Slower than unit tests (each subprocess compiles its programs) but they
are the only coverage of the argparse wiring, device selection, and
training-loop assembly the docs tell users to copy.
"""

import os
import subprocess
import sys

from conftest import subprocess_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")


def _run(script, *args, tmp_path, devices=8, timeout=420):
    env = subprocess_env(devices)
    proc = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, script), *args],
        cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=timeout,
    )
    assert proc.returncode == 0, (
        f"{script} exited {proc.returncode}\n--- stdout ---\n"
        f"{proc.stdout[-3000:]}\n--- stderr ---\n{proc.stderr[-3000:]}"
    )
    return proc.stdout


class TestExampleScripts:
    def test_mnist_data_parallel(self, tmp_path):
        out = _run(
            "mnist/train_mnist.py", "--cpu-mesh", "--epoch", "1",
            "--n-train", "1024", "--n-test", "256", "--unit", "64",
            tmp_path=tmp_path,
        )
        assert "final:" in out and "loss" in out

    def test_mnist_model_parallel(self, tmp_path):
        out = _run(
            "mnist/train_mnist_model_parallel.py", "--cpu-mesh",
            "--epoch", "1", "--n-train", "512", "--n-test", "128",
            "--unit", "64", "--batchsize", "64", tmp_path=tmp_path,
        )
        assert "loss" in out

    def test_mnist_hybrid_dp_tp(self, tmp_path):
        out = _run(
            "mnist/train_mnist_hybrid.py", "--cpu-mesh", "--epoch", "1",
            "--n-train", "512", "--n-test", "128", "--unit", "64",
            "--batchsize", "64", "--tp", "2", tmp_path=tmp_path,
        )
        assert "loss" in out

    def test_imagenet_synthetic(self, tmp_path):
        """Over 40 s in the driver's run (a process that compiles
        ResNet-18's step and evaluator on eight devices): the one hold
        on the example the ResNet cell runs, through ``Trainer``, the
        iterators and ``MultiNodeEvaluator``."""
        out = _run(
            "imagenet/train_imagenet.py", "--cpu-mesh", "--epoch", "1",
            "--arch", "resnet18", "--image-size", "32",
            "--num-classes", "8", "--n-train", "64", "--n-val", "32",
            "--batchsize", "16", tmp_path=tmp_path,
        )
        assert "final:" in out

    def test_imagenet_native_uint8_wire(self, tmp_path):
        """The end-to-end uint8-wire path (VERDICT r4 #2): C++ loader
        ships raw uint8 crops, device_normalize runs inside the jitted
        step; training must still converge to a printed final record."""
        from chainermn_tpu.utils.native_loader import native_available

        if not native_available():
            pytest.skip("no C++ toolchain for the native loader")
        out = _run(
            "imagenet/train_imagenet.py", "--cpu-mesh", "--epoch", "1",
            "--arch", "resnet18", "--image-size", "32",
            "--num-classes", "8", "--n-train", "64", "--n-val", "32",
            "--batchsize", "16", "--native-loader",
            "--native-wire", "uint8", tmp_path=tmp_path,
        )
        assert "final:" in out

    def test_seq2seq(self, tmp_path):
        out = _run(
            "seq2seq/seq2seq.py", "--cpu-mesh", "--epoch", "1",
            "--n-train", "256", "--n-test", "64", "--unit", "32",
            "--batchsize", "32", tmp_path=tmp_path,
        )
        assert "final:" in out

    def test_seq2seq_model_parallel(self, tmp_path):
        # tiny dataset: the chain tier dispatches eagerly per stage, so
        # iteration count dominates smoke-test wall time
        out = _run(
            "seq2seq/seq2seq_mp1.py", "--cpu-mesh", "--epoch", "1",
            "--batchsize", "32", "--n-train", "64", "--n-test", "32",
            "--unit", "32", tmp_path=tmp_path, devices=2,
        )
        assert "train/loss" in out

    def test_moe_lm_composed(self, tmp_path):
        out = _run(
            "moe_lm/train_moe_lm.py", "--cpu-mesh", "--sp", "2",
            "--tp", "2", "--steps", "6", "--report-every", "3",
            "--seq-len", "32", "--d-model", "32", "--n-layers", "2",
            "--vocab", "64", "--vocab-parallel", "--generate", "8",
            tmp_path=tmp_path,
        )
        assert "final:" in out
        # the vocab-parallel head samples natively (frontier-row gather)
        assert "sampled (vp+tp/ep-sharded MoE KV-cache decode)" in out

    def test_moe_lm_composed_sampling(self, tmp_path):
        # train sharded (SP x TP x EP), then sample through the
        # tp/ep-sharded KV-cache decode under the same mesh
        out = _run(
            "moe_lm/train_moe_lm.py", "--cpu-mesh", "--sp", "2",
            "--tp", "2", "--steps", "4", "--report-every", "2",
            "--seq-len", "32", "--d-model", "32", "--n-layers", "2",
            "--vocab", "64", "--generate", "8", tmp_path=tmp_path,
        )
        assert "sampled (tp/ep-sharded MoE KV-cache decode)" in out

    def test_lm_sp_tp_train_and_sample(self, tmp_path):
        out = _run(
            "lm/train_lm.py", "--cpu-mesh", "--sp", "2", "--tp", "2",
            "--steps", "6", "--report-every", "3", "--seq-len", "32",
            "--d-model", "32", "--n-layers", "2", "--vocab", "64",
            "--generate", "8", tmp_path=tmp_path,
        )
        assert "final:" in out
        assert "sampled (tp-sharded KV-cache decode)" in out

    def test_lm_flash_train(self, tmp_path):
        """The README's one-chip LM command, ``--flash``: the Pallas
        kernels (interpreted here) inside ``build_train_step``'s
        vma-checked shard_map."""
        out = _run(
            "lm/train_lm.py", "--cpu-mesh", "--flash", "--steps", "4",
            "--report-every", "2", "--seq-len", "64", "--d-model", "32",
            "--n-layers", "2", "--vocab", "64", "--generate", "0",
            tmp_path=tmp_path,
        )
        assert "final:" in out

    def test_lm_serve_mode(self, tmp_path):
        """ISSUE 13 satellite: the --serve mode wires the trained
        checkpoint to the continuous-batching engine (greedy decode
        over the paged KV cache) and reports throughput + token
        latency percentiles."""
        out = _run(
            "lm/train_lm.py", "--cpu-mesh", "--steps", "10",
            "--report-every", "5", "--seq-len", "64", "--d-model", "32",
            "--n-layers", "2", "--vocab", "64", "--generate", "0",
            "--serve", "4", "--serve-tokens", "6",
            "--serve-capacity", "2", tmp_path=tmp_path,
        )
        assert "final:" in out
        assert "served 4 requests" in out
        assert "failed 0" in out

    def test_lm_vocab_parallel_train_and_sample(self, tmp_path):
        """vp tier end-to-end: vp_lm_loss training + native vp decode
        (the embedding/tied head stay sharded through sampling)."""
        out = _run(
            "lm/train_lm.py", "--cpu-mesh", "--tp", "2",
            "--vocab-parallel", "--steps", "6", "--report-every", "3",
            "--seq-len", "32", "--d-model", "32", "--n-layers", "2",
            "--vocab", "64", "--generate", "8", tmp_path=tmp_path,
        )
        assert "final:" in out
        assert "sampled (vocab-parallel KV-cache decode)" in out

    def test_mnist_checkpoint_resume(self, tmp_path):
        args = (
            "mnist/train_mnist_checkpoint.py", "--cpu-mesh",
            "--n-train", "512", "--n-test", "128", "--unit", "64",
        )
        _run(*args, "--epoch", "1", tmp_path=tmp_path)
        out = _run(*args, "--epoch", "2", tmp_path=tmp_path)
        assert "resumed" in out.lower()
