"""On-device multi-step training windows (horovod_tpu/jax/window.py).

Pins the window API's mechanical acceptance bar (ISSUE 1): a K-step
``lax.scan`` window is numerically equivalent to K sequential steps of
the same train step — params, optimizer state, the RNG stream (the
per-step dropout key folds the carried step counter, so trajectory
equality IS the RNG pin: dropout-perturbed losses match per window),
and metric means — plus donation safety across windows, the
``steps_per_dispatch=1`` identity path, and the double-buffered
K-batch device stager's ordering.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu.jax as hvd
from horovod_tpu import data, models
from horovod_tpu.jax.window import (
    stack_batches,
    stacked_specs,
    windowed,
)


def _fresh_state():
    """Deterministic (PRNGKey-seeded) model + state: two calls build
    bit-identical starting points, one per loop under comparison."""
    model = models.MNISTNet()
    rng = jax.random.PRNGKey(7)
    sample = jnp.zeros((1, 28, 28, 1), jnp.float32)
    state, optimizer = models.create_train_state(
        rng, model, optax.sgd(0.1, momentum=0.9), sample)
    step = models.make_train_step(model, optimizer)
    return state, step


def _batches(n, global_batch=16, seed=0):
    rng = np.random.RandomState(seed)
    return [
        {"image": rng.randn(global_batch, 28, 28, 1).astype(np.float32),
         "label": rng.randint(0, 10, size=global_batch)}
        for _ in range(n)
    ]


def _sequential(state, step, batches):
    run = hvd.spmd_fn(step, in_specs=(P(), P("hvd")),
                      out_specs=(P(), P()))
    metrics = []
    for b in batches:
        state, m = run(state, b)
        metrics.append(m)
    return state, metrics


def _assert_trees_close(a, b, rtol=1e-5, atol=1e-6):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


class TestWindowEquivalence:
    def test_scan_window_matches_sequential_steps(self, hvd):
        """(a) K-step scan ≡ K sequential steps, f32 allclose: params,
        opt state (momentum), the step counter that drives the RNG
        stream, and per-window metric means — with an uneven tail (7
        batches, K=3 -> windows of 3/3/1) so the shorter-tail scan path
        is pinned too."""
        batches = _batches(7)
        K = 3

        state_seq, step = _fresh_state()
        state_seq, seq_metrics = _sequential(state_seq, step, batches)

        state_win, step_w = _fresh_state()
        state_win, win_metrics = hvd.run_steps(
            step_w, state_win, batches, steps_per_dispatch=K,
            donate=False)

        assert len(win_metrics) == 3
        assert int(state_win["step"]) == int(state_seq["step"]) == 7
        _assert_trees_close(state_win, state_seq)
        # Metric means per window == mean of the sequential per-step
        # metrics over the same K batches (dropout-perturbed losses, so
        # equality also pins the per-step RNG folding inside the scan).
        for w, lo in zip(range(3), (0, 3, 6)):
            group = seq_metrics[lo:lo + K]
            seq_mean = jax.tree_util.tree_map(
                lambda *ms: jnp.mean(jnp.stack(ms), axis=0), *group)
            _assert_trees_close(win_metrics[w], seq_mean)

    def test_donation_safe_across_windows(self, hvd):
        """(b) The donated-state path (donate=True, the training
        default: XLA reuses the state buffers in place across windows)
        must produce the same trajectory as the undonated one — and the
        handle must stay callable across consecutive windows feeding
        its own donated output back in."""
        batches = _batches(6, seed=3)

        state_a, step_a = _fresh_state()
        state_a, metrics_a = hvd.run_steps(
            step_a, state_a, batches, steps_per_dispatch=2, donate=True)

        state_b, step_b = _fresh_state()
        state_b, metrics_b = hvd.run_steps(
            step_b, state_b, batches, steps_per_dispatch=2, donate=False)

        assert len(metrics_a) == len(metrics_b) == 3
        _assert_trees_close(state_a, state_b)
        for ma, mb in zip(metrics_a, metrics_b):
            _assert_trees_close(ma, mb)

    def test_steps_per_dispatch_one_is_identity(self, hvd):
        """(c) K=1 is the identity path: windowed() returns the step fn
        unchanged, and run_steps degrades to the plain per-step loop
        with raw (un-averaged) per-step metrics."""
        def step(state, batch):
            return state, batch

        assert windowed(step, 1) is step

        batches = _batches(4, seed=5)
        state_seq, step_fn = _fresh_state()
        state_seq, seq_metrics = _sequential(state_seq, step_fn, batches)

        state_one, step_one = _fresh_state()
        state_one, one_metrics = hvd.run_steps(
            step_one, state_one, batches, steps_per_dispatch=1,
            donate=False)

        assert len(one_metrics) == 4  # one PER STEP, not per window
        _assert_trees_close(state_one, state_seq)
        for ma, mb in zip(one_metrics, seq_metrics):
            _assert_trees_close(ma, mb)

    def test_bad_steps_per_dispatch_rejected(self, hvd):
        state, step = _fresh_state()
        with pytest.raises(ValueError, match=">= 1"):
            hvd.run_steps(step, state, _batches(1), steps_per_dispatch=0)
        with pytest.raises(ValueError, match=">= 1"):
            windowed(step, 0)

    def test_empty_batches_is_a_noop(self, hvd):
        state, step = _fresh_state()
        out_state, metrics = hvd.run_steps(step, state, [],
                                           steps_per_dispatch=4)
        assert metrics == []
        _assert_trees_close(out_state, state)


class TestWindowStager:
    def test_prefetch_windows_order_and_tail(self, hvd):
        """(d) The double-buffered stager yields stacked windows in
        iteration order — window i holds batches [i*K, (i+1)*K) — with
        a shorter tail rather than dropped batches."""
        items = [{"x": np.full((4,), i, np.float32)} for i in range(7)]
        wins = list(data.prefetch_windows(items, 3, size=2))
        assert [w["x"].shape for w in wins] == [(3, 4), (3, 4), (1, 4)]
        for w, lo in zip(wins, (0, 3, 6)):
            np.testing.assert_array_equal(
                np.asarray(w["x"])[:, 0], np.arange(lo, min(lo + 3, 7)))

    def test_prefetch_windows_k1_adds_no_axis(self, hvd):
        items = [{"x": np.arange(4.0)} for _ in range(3)]
        out = list(data.prefetch_windows(items, 1, size=2))
        assert len(out) == 3
        assert np.asarray(out[0]["x"]).shape == (4,)

    def test_stager_lands_stacked_layout_on_mesh(self, hvd):
        """The stacked sharding P(None, "hvd"): window axis replicated,
        batch axis scattered over the 8-device mesh."""
        mesh = hvd.mesh()
        sharding = NamedSharding(mesh, P(None, "hvd"))
        items = [{"x": np.arange(16.0)} for _ in range(4)]
        wins = list(data.prefetch_windows(items, 2, sharding=sharding))
        assert len(wins) == 2
        leaf = wins[0]["x"]
        assert leaf.shape == (2, 16)
        assert {s.data.shape for s in leaf.addressable_shards} == {(2, 2)}

    def test_bad_window_size_rejected(self, hvd):
        with pytest.raises(ValueError, match=">= 1"):
            next(data.prefetch_windows([], 0))


class TestWindowHelpers:
    def test_stacked_specs_shifts_under_window_axis(self, hvd):
        assert stacked_specs(P("hvd")) == P(None, "hvd")
        assert stacked_specs(P()) == P(None)
        tree = {"a": P("hvd"), "b": P()}
        out = stacked_specs(tree)
        assert out == {"a": P(None, "hvd"), "b": P(None)}

    def test_stack_batches(self, hvd):
        batches = [{"x": jnp.full((2,), float(i))} for i in range(3)]
        stacked = stack_batches(batches)
        assert stacked["x"].shape == (3, 2)
        np.testing.assert_array_equal(np.asarray(stacked["x"])[:, 0],
                                      [0.0, 1.0, 2.0])
        with pytest.raises(ValueError, match="at least one"):
            stack_batches([])

    def test_windowed_train_step_builder(self, hvd):
        """models.make_windowed_train_step is the windowed() form of
        make_train_step — same trajectory as sequential stepping."""
        batches = _batches(2, seed=9)

        state_seq, step = _fresh_state()
        state_seq, seq_metrics = _sequential(state_seq, step, batches)

        model = models.MNISTNet()
        rng = jax.random.PRNGKey(7)
        sample = jnp.zeros((1, 28, 28, 1), jnp.float32)
        state_w, optimizer = models.create_train_state(
            rng, model, optax.sgd(0.1, momentum=0.9), sample)
        wstep = models.make_windowed_train_step(model, optimizer, 2)
        run = hvd.spmd_fn(wstep, in_specs=(P(), stacked_specs(P("hvd"))),
                          out_specs=(P(), P()))
        state_w, metrics = run(state_w, stack_batches(batches))

        _assert_trees_close(state_w, state_seq)
        seq_mean = jax.tree_util.tree_map(
            lambda *ms: jnp.mean(jnp.stack(ms), axis=0), *seq_metrics)
        _assert_trees_close(metrics, seq_mean)


class TestWindowTimeline:
    def test_window_marks_and_sync_span(self, hvd, tmp_path):
        """Window boundaries stay attributable: mark_window emits the
        WINDOW_START instant and devsync.window_sync wraps the boundary
        block in a WINDOW_SYNC span."""
        import json

        from horovod_tpu.utils.devsync import window_sync
        from horovod_tpu.utils.timeline import Timeline

        path = tmp_path / "trace.json"
        tl = Timeline(str(path))
        tl.mark_window(0, 30)
        checksum = window_sync(jnp.ones((4,)), timeline=tl, steps=30)
        assert checksum == 4.0
        tl.close()
        events = [json.loads(line.rstrip(",\n"))
                  for line in path.read_text().splitlines()[1:]
                  if line.strip().rstrip(",")]
        names = [e.get("name") for e in events]
        assert "WINDOW_START" in names
        assert "WINDOW_SYNC" in names
        start = next(e for e in events if e["name"] == "WINDOW_START")
        assert start["args"] == {"window": 0, "steps": 30}

    def test_window_sync_without_timeline(self, hvd):
        from horovod_tpu.utils.devsync import window_sync

        assert window_sync({"a": jnp.full((2,), 3.0)}) == 6.0


def test_pick_block_floors_at_sublane_tile(hvd):
    """ADVICE r5 #1: the default block ladder stops at the native
    8-sublane tile — lengths without a multiple-of-8 factor get the
    explicit pad-upstream error instead of a sub-tile kernel that only
    fails on real Mosaic."""
    from horovod_tpu.ops.attention import _pick_block

    assert _pick_block(256, 2048) == 256
    assert _pick_block(512, 768) == 256
    assert _pick_block(1024, 4096) == 1024
    assert _pick_block(1024, 1536) == 512
    assert _pick_block(256, 24) == 8
    assert _pick_block(256, 8) == 8
    for bad in (100, 33, 4):
        with pytest.raises(ValueError, match="[Pp]ad the sequence length"):
            _pick_block(256, bad)
