"""Tests for the model zoo additions, sparse allreduce, and example
scripts (run as subprocess smoke jobs, the reference's examples-are-tests
discipline)."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

REPO = Path(__file__).resolve().parent.parent
EXAMPLES = REPO / "examples"


def _run_example(script, *args, timeout=600, extra_env=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env["HVD_TPU_FORCE_CPU"] = "1"  # hermetic 8-device CPU mesh
    if extra_env:
        env.update(extra_env)
    proc = subprocess.run(
        [sys.executable, str(EXAMPLES / script), *args],
        env=env, cwd=str(REPO), capture_output=True, text=True,
        timeout=timeout)
    assert proc.returncode == 0, (
        f"{script} rc={proc.returncode}\nstdout: {proc.stdout[-2000:]}\n"
        f"stderr: {proc.stderr[-2000:]}")
    return proc


def _run_via_launcher(script, *args, np_ranks=2, timeout=600):
    """Run an example under ``python -m horovod_tpu.run -np N``."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_PLATFORMS", None)
    env["HOROVOD_CYCLE_TIME"] = "1"
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", str(np_ranks),
         sys.executable, str(EXAMPLES / script), *args],
        env=env, cwd=str(REPO), capture_output=True, text=True,
        timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc


class TestModelZoo:
    @pytest.mark.parametrize("name,shape", [
        ("vgg11", (2, 32, 32, 3)),
        ("inception_v3", (1, 128, 128, 3)),
        ("vit_s16", (2, 32, 32, 3)),
    ])
    def test_forward_shapes(self, name, shape):
        from horovod_tpu import models

        m = models.build(name, num_classes=7, dtype=jnp.float32)
        v = m.init(jax.random.PRNGKey(0), jnp.zeros(shape), train=False)
        out = m.apply(v, jnp.zeros(shape), train=False)
        assert out.shape == (shape[0], 7)

    def test_vit_spmd_train_step(self, hvd):
        """ViT trains under the full SPMD DP path (it has no batch_stats
        — the train-state plumbing must tolerate that)."""
        import optax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu import models

        n = hvd.size()
        model = models.VisionTransformer(
            num_classes=5, patch_size=8, embed_dim=32, depth=2,
            num_heads=2, dtype=jnp.float32, dropout=0.1)
        rng = jax.random.PRNGKey(0)
        sample = jnp.zeros((1, 32, 32, 3), jnp.float32)
        state, optimizer = models.create_train_state(
            rng, model, optax.adamw(1e-3), sample)
        step = models.make_train_step(model, optimizer)
        batch = {
            "image": jax.random.normal(rng, (2 * n, 32, 32, 3)),
            "label": jax.random.randint(rng, (2 * n,), 0, 5),
        }
        fn = hvd.spmd_fn(step, in_specs=(P(), P("hvd")),
                         out_specs=(P(), P()))
        l0 = None
        for _ in range(4):
            state, metrics = fn(state, batch)
            l0 = float(metrics["loss"]) if l0 is None else l0
        assert float(metrics["loss"]) < l0

    def test_build_unknown(self):
        from horovod_tpu import models

        with pytest.raises(ValueError, match="Unknown model"):
            models.build("alexnet9000")

    def test_transformer_lm_forward_and_loss_step(self):
        import optax

        from horovod_tpu import models

        lm = models.TransformerLM(vocab_size=50, num_layers=2, num_heads=2,
                                  embed_dim=32, max_len=32,
                                  dtype=jnp.float32)
        tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 50)
        v = lm.init(jax.random.PRNGKey(1), tokens, train=False)
        logits = lm.apply(v, tokens, train=False)
        assert logits.shape == (2, 16, 50)

        # Causality: logits at position t must not depend on tokens > t.
        tokens2 = tokens.at[:, -1].set((tokens[:, -1] + 1) % 50)
        logits2 = lm.apply(v, tokens2, train=False)
        np.testing.assert_allclose(np.asarray(logits[:, :-1]),
                                   np.asarray(logits2[:, :-1]), atol=1e-5)

    def test_vgg16_train_step_runs(self, hvd):
        import optax

        from horovod_tpu import models

        model = models.VGG16(num_classes=10, dtype=jnp.float32, hidden=64)
        rng = jax.random.PRNGKey(0)
        sample = jnp.zeros((1, 32, 32, 3), jnp.float32)
        state, opt = models.create_train_state(rng, model,
                                               optax.sgd(0.01), sample)
        step = models.make_train_step(model, opt)
        batch = {"image": jnp.zeros((8, 32, 32, 3)),
                 "label": jnp.zeros((8,), jnp.int32)}
        import horovod_tpu.jax as hj

        state, metrics = hj.spmd_run(step, state, batch,
                                     in_specs=(P(), P("hvd")),
                                     out_specs=(P(), P()))
        assert int(state["step"]) == 1


class TestSparseAllreduce:
    def test_spmd_dense_accumulation(self, hvd):
        import horovod_tpu.jax as hj

        def fn():
            r = jax.lax.axis_index("hvd")
            # Every rank updates row r and row 0.
            indices = jnp.stack([r, jnp.zeros((), jnp.int32)])
            values = jnp.ones((2, 3)) * (r + 1)
            return hj.allreduce_sparse(indices, values, dense_rows=8,
                                       average=False)

        out = hj.spmd_run(fn, out_specs=P())
        out = np.asarray(out)
        # Row 0 accumulates every rank's ones-row plus rank 0's own r+1
        # contribution: sum(r+1) + 1.
        assert out[0, 0] == pytest.approx(sum(r + 1 for r in range(8)) + 1)
        # Row r>0 gets only rank r's contribution (r+1).
        for r in range(1, 8):
            assert out[r, 0] == pytest.approx(r + 1)

    def test_spmd_gather_form(self, hvd):
        import horovod_tpu.jax as hj

        def fn():
            r = jax.lax.axis_index("hvd")
            return hj.allreduce_sparse(r[None], jnp.ones((1, 2)) * r,
                                       average=True)

        idx, vals = hj.spmd_run(fn, out_specs=(P(), P()))
        assert idx.shape == (8,)
        assert vals.shape == (8, 2)
        np.testing.assert_allclose(np.asarray(vals[:, 0]),
                                   np.arange(8) / 8.0)

    def test_eager_size1(self, hvd):
        import horovod_tpu.jax as hj

        dense = hj.allreduce_sparse(jnp.asarray([2, 2]),
                                    jnp.ones((2, 4)), dense_rows=5,
                                    average=False)
        assert dense.shape == (5, 4)
        np.testing.assert_allclose(np.asarray(dense[2]), 2 * np.ones(4))


class TestExamples:
    def test_jax_mnist(self):
        _run_example("jax_mnist.py", "--epochs", "3", "--batch-size", "8",
                     "--train-size", "2048", "--test-size", "512")

    def test_flax_imagenet_resnet50_smoke(self, tmp_path):
        _run_example("flax_imagenet_resnet50.py", "--smoke", "--epochs", "2",
                     "--steps-per-epoch", "3",
                     "--checkpoint", str(tmp_path / "ck.msgpack"))

    def test_long_context_ring_attention_smoke(self):
        _run_example("long_context_ring_attention.py", "--smoke")

    def test_jax_gpt_parallel_smoke(self):
        """Composed dp x sp x tp LM example: trains on the synthetic
        bigram corpus to well below the uniform-entropy floor (the
        example itself asserts a 2x NLL drop)."""
        proc = _run_example("jax_gpt_parallel.py", "--smoke")
        assert float(proc.stdout.strip().splitlines()[-1]) < 1.0

    def test_jax_word2vec_smoke(self):
        """Sparse-gradient skip-gram (reference
        examples/tensorflow_word2vec.py): loss falls and embeddings
        cluster by topic; the example itself asserts both."""
        proc = _run_example("jax_word2vec.py", "--smoke")
        assert float(proc.stdout.strip().splitlines()[-1]) > 0

    def test_torch_mnist_via_launcher(self):
        _run_via_launcher("torch_mnist.py", "--epochs", "4",
                          "--batch-size", "32", "--train-size", "2048")

    def test_tf_keras_mnist_via_launcher(self):
        """The TF-binding headline example (reference keras_mnist.py):
        keras DistributedOptimizer + callbacks converge to >0.9 test
        accuracy on 2 ranks (the script exits 1 below that)."""
        _run_via_launcher("tf_keras_mnist.py", "--epochs", "3",
                          "--batch-size", "32", "--train-size", "2048")

    def test_torch_synthetic_benchmark_via_launcher(self):
        """The torch-lane yardstick (reference
        examples/pytorch_synthetic_benchmark.py protocol) runs under the
        launcher and reports a positive throughput."""
        proc = _run_via_launcher(
            "torch_synthetic_benchmark.py", "--num-iters", "2",
            "--num-batches-per-iter", "2", "--num-warmup-batches", "1")
        assert float(proc.stdout.strip().splitlines()[-1]) > 0

    def test_jax_synthetic_benchmark(self):
        """The jax-lane yardstick of the same protocol: warm-up, timed
        groups, a positive rate, on whatever device it names."""
        proc = _run_example(
            "jax_synthetic_benchmark.py", "--model", "resnet18",
            "--image-size", "32", "--batch-size", "2", "--num-iters", "2",
            "--num-batches-per-iter", "2", "--num-warmup-batches", "1")
        assert "8 x cpu" in proc.stdout
        assert float(proc.stdout.strip().splitlines()[-1]) > 0

    def test_jax_transformer_zero_smoke(self, tmp_path):
        """ZeRO + orbax checkpoint LM example trains (loss falls) and a
        second invocation resumes from the saved step."""
        _run_example("jax_transformer_zero.py", "--smoke",
                     "--ckpt-dir", str(tmp_path / "zck"))
        # Second run resumes at steps==latest and exits cleanly.
        _run_example("jax_transformer_zero.py", "--smoke",
                     "--ckpt-dir", str(tmp_path / "zck"))
