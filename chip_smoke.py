#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

One process, no children. Drives the trainer and the server once through
the entry points a user calls — ``bench.py``'s lane builders over
``hvd.init`` / ``models.create_train_state`` / ``hvd.spmd_fn``, and
``tools/serve_bench.py``'s geometry over ``ServeEngine`` / ``ServeFleet``
— at the width the repo supports (ResNet-50 at 224², the 12-layer 768-wide
LM), on seeded random weights, for a few steps and a few requests. It
checks what comes out by the repo's own means, fails on the first phase
that fails, and prints as its last line of standard output

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

It exits non-zero, with no result line, when JAX finds no TPU. With four
chips or more the same process goes on to data-parallel training over
all of them, a tp=4 serving engine and four one-chip replicas.

``--rehearsal`` is the only argument: the same phases at toy sizes on a
four-device virtual CPU platform with interpreted kernels, to debug the
script itself. It says "rehearsal" wherever the real run says "pass" and
its result line carries ``"ok": false``. The seconds each phase prints
are set-up facts (compile, first step, one step), not benchmark metrics.
"""

import argparse
import dataclasses
import functools
import json
import os
import re
import sys
import time

MOSAIC_CALL = "tpu_custom_call"   # how a compiled Pallas kernel lowers


@dataclasses.dataclass(frozen=True)
class Sizes:
    resnet: tuple          # bench.py arguments of the ResNet-50 lane
    resnet_batch: int      # images on one chip (= global batch on four)
    lm: tuple              # bench.py arguments of the LM
    lm_batch: int          # sequences on one chip (= global batch on four)
    seq_dense: int
    seq_flash: int
    serve: tuple           # tools/serve_bench.py arguments
    steps: int
    dp_tol_resnet: float   # |four-chip - one-chip| first-step loss, relative


#: An LM lane's first-step loss against lm_reference_loss, relative: every
#: lane reports the reference's value to four decimals on the chip
#: (10.8904 dense and flash at seq 2048; PERF.md "Bring-up").
REF_TOL = 0.002


# bench.py's and serve_bench's own defaults are the measured width; only
# what differs from them is spelled here.
REAL = Sizes(
    resnet=("--model", "resnet50"), resnet_batch=64,
    lm=("--model", "transformer_lm"), lm_batch=8,
    seq_dense=2048, seq_flash=4096,
    serve=("--requests", "8", "--rate", "50", "--new-min", "32",
           "--new-max", "32"),
    steps=5,
    # The step returns rank 0's loss: on four chips that is the mean over
    # its quarter of the same global batch, with batch statistics taken
    # over 16 images instead of 64.
    dp_tol_resnet=0.05)

TOY = Sizes(
    resnet=("--model", "resnet50", "--image-size", "32"), resnet_batch=8,
    lm=("--model", "transformer_lm", "--vocab", "512", "--lm-layers", "2",
        "--lm-dim", "64", "--lm-heads", "4"), lm_batch=4,
    seq_dense=128, seq_flash=256,
    serve=("--layers", "2", "--d-model", "64", "--heads", "4", "--vocab",
           "128", "--requests", "8", "--rate", "200", "--prompt-min", "4",
           "--prompt-max", "12", "--new-min", "4", "--new-max", "4",
           "--page-size", "8", "--decode-slots", "2", "--prefill-chunk",
           "4"),
    steps=3, dp_tol_resnet=0.5)


def say(tag, text):
    print(f"[chip_smoke] {tag}: {text}", flush=True)


def log(*args, **kwargs):
    """bench.py's lane builders narrate on stderr."""
    kwargs["file"] = sys.stderr
    print(*args, **kwargs)


def close(a, b, tol):
    return abs(a - b) <= tol * max(abs(a), abs(b))


# ------------------------------------------------------------------ train


def lm_reference_loss(lane, per_chip):
    """What rank 0's first step should report, computed another way: the
    lane's model with dense attention under the plain log-softmax head,
    forward only, in a plain ``jax.jit`` — on the lane's own parameters
    and rank 0's shard of its batch."""
    import functools

    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.attention import attend

    model = lane.model.clone(
        attn_fn=functools.partial(attend, impl="dense"), remat=False)

    @jax.jit
    def loss(params, tokens):
        logits = model.apply({"params": params}, tokens, train=False)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
        return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()

    return float(loss(lane.state["params"],
                      lane.batch["tokens"][:per_chip]))


def assert_mosaic(name, lowered_text, want):
    """Compiled, not interpreted: the Mosaic custom call is in the lowered
    program (and, in the rehearsal, is not)."""
    assert (MOSAIC_CALL in lowered_text) == want, (
        f"{name}: Mosaic custom call "
        f"{'missing from' if want else 'present in'} the lowered program")


def train_phase(name, argv, steps, *, want_mosaic=None,
                want_collective=False, ref_tol=None):
    """Build a bench.py lane and take ``steps`` steps on its one reused
    batch, each ended by block_until_ready. Finite loss, lower at the end
    than at the start, and (``ref_tol``) a first-step loss that agrees
    with lm_reference_loss. Returns the losses."""
    import jax
    import numpy as np

    import bench

    args = bench.build_parser().parse_args(list(argv))
    t0 = time.perf_counter()
    lane = bench.build_lane(args, log)
    build_s = time.perf_counter() - t0
    ref = (None if ref_tol is None
           else lm_reference_loss(lane, args.batch_size))
    if want_mosaic is not None or want_collective:
        text = lane.run_step._compiled.lower(lane.state, lane.batch).as_text()
        if want_mosaic is not None:
            assert_mosaic(name, text, want_mosaic)
        if want_collective:
            # a fused bucket reduces as all_reduce or, when large, as
            # reduce_scatter + all_gather (jax/fusion.py)
            assert re.search(r"stablehlo\.(all_reduce|reduce_scatter)",
                             text), (
                f"{name}: no gradient collective in the lowered train step")
            from tools.hvdverify import abstractify, audit_collectives

            audit = audit_collectives(
                lambda s, b: lane.run_step(s, b), abstractify(lane.state),
                abstractify(lane.batch))
            assert audit["count"] > 0, audit
    state, losses, secs = lane.state, [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, out = lane.run_step(state, lane.batch)
        jax.block_until_ready((state, out))
        secs.append(time.perf_counter() - t0)
        # the image step returns its metrics, the LM step its loss
        losses.append(float(out["loss"] if isinstance(out, dict) else out))
    assert np.isfinite(losses).all(), f"{name}: loss not finite: {losses}"
    assert steps < 2 or losses[-1] < losses[0], (
        f"{name}: loss did not fall over {steps} steps: {losses}")
    assert ref is None or close(losses[0], ref, ref_tol), (
        f"{name}: first-step loss {losses[0]} vs the reference {ref} on "
        f"the same parameters: beyond {ref_tol:.1%}")
    say(name, f"set-up: build {build_s:.1f}s, first step incl. compile "
              f"{secs[0]:.1f}s, one step {min(secs[1:] or secs):.3f}s; loss "
              + " -> ".join(f"{x:.4f}" for x in losses)
              + ("" if ref is None else
                 f"; reference first-step loss {ref:.4f} (tolerance "
                 f"{ref_tol:.1%})"))
    return losses


def check_flash_kernels():
    """The packed-grid flash forward and both backwards (the one kernel the
    policy answers, and the dQ / dK+dV split it answers past its VMEM
    budget) against the dense reference on seeded bf16 inputs. The
    reference runs in f32 at highest matmul precision; the bounds are
    bf16's (tools/tpu_flash_check.py's)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.attention import (dot_product_attention,
                                           flash_attention)

    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                 (2, 1024, 4, 64), jnp.bfloat16)
               for i in range(3))

    def run(attend, *qkv):
        return jax.value_and_grad(
            lambda *a: attend(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(*qkv)

    (_, got), (_, split) = (
        run(lambda *a: flash_attention(*a, causal=True, bwd_impl=bwd),
            q, k, v) for bwd in ("fused", "pallas"))
    out = flash_attention(q, k, v, causal=True)
    with jax.default_matmul_precision("highest"):
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        _, want = run(lambda *a: dot_product_attention(*a, causal=True),
                      *f32)
        ref = dot_product_attention(*f32, causal=True)
    errs = [max(float(jnp.max(jnp.abs(x.astype(jnp.float32) - b)))
                for x in a)
            for a, b in zip(((out,), *zip(got, split)), (ref, *want))]
    assert errs[0] < 2e-2 and max(errs[1:]) < 5e-2, (
        f"flash vs dense reference: max |err| out/dq/dk/dv = {errs}")
    say("train flash kernels", "max |err| vs the f32 dense reference: out "
        f"{errs[0]:.1e}, dq {errs[1]:.1e}, dk {errs[2]:.1e}, dv "
        f"{errs[3]:.1e} (bounds 2e-2 / 5e-2)")


def train_one_chip(sz, kernels_compiled):
    check_flash_kernels()
    resnet = train_phase(
        "train resnet50", (*sz.resnet, "--batch-size", str(sz.resnet_batch)),
        sz.steps)
    lm = (*sz.lm, "--batch-size", str(sz.lm_batch))
    # Both sides pinned: unset, the lane asks the policy, which picks the
    # kernels at these lengths on the chip.
    train_phase(f"train lm dense seq {sz.seq_dense}",
                (*lm, "--seq-len", str(sz.seq_dense), "--attention",
                 "dense"), sz.steps, ref_tol=REF_TOL)
    flash = ("--attention", "flash", "--remat", "--fused-ce")
    train_phase(f"train lm flash+fused-ce seq {sz.seq_flash}",
                (*lm, "--seq-len", str(sz.seq_flash), *flash), sz.steps,
                want_mosaic=kernels_compiled)
    # The one shape both lanes run: each against the same reference.
    train_phase(f"train lm flash+fused-ce seq {sz.seq_dense} (one step)",
                (*lm, "--seq-len", str(sz.seq_dense), *flash), 1,
                ref_tol=REF_TOL)
    return resnet[0]


def train_all_chips(sz, one_chip_resnet, n):
    """Data-parallel over every chip at the one-chip run's global batch.
    The step returns rank 0's loss: the LM's is held to the reference on
    rank 0's shard, ResNet-50's to the one-chip run."""
    resnet = train_phase(
        f"train resnet50 dp={n}",
        (*sz.resnet, "--batch-size", str(sz.resnet_batch // n)), sz.steps,
        want_collective=True)
    assert close(resnet[0], one_chip_resnet, sz.dp_tol_resnet), (
        f"resnet50: first-step loss on {n} chips {resnet[0]} vs one chip "
        f"{one_chip_resnet}: beyond {sz.dp_tol_resnet:.0%}")
    say(f"train resnet50 dp={n} vs one chip",
        f"first-step loss {resnet[0]:.4f} vs {one_chip_resnet:.4f} "
        f"(tolerance {sz.dp_tol_resnet:.0%})")
    # --attention unset: the policy's choice, on the chip the kernels under
    # the data-parallel shard_map.
    train_phase(
        f"train lm seq {sz.seq_dense} dp={n}",
        (*sz.lm, "--batch-size", str(sz.lm_batch // n), "--seq-len",
         str(sz.seq_dense)), sz.steps, want_collective=True,
        ref_tol=REF_TOL)


# ------------------------------------------------------------------ serve


#: How far below the best reference logit a greedy token may sit. On the
#: chip the f32 matmuls take bf16 passes and the paged kernel reduces in
#: f32 in another order, while along these streams a random 32,000-way
#: head has its runner-up within 0.05 of the winner at one position in
#: eight: two correct implementations part at such near-ties and their
#: streams differ from there on (PERF.md "Bring-up"). The widest gap seen
#: on the chip over every token of every engine is 0.010 and the bound is
#: five of those; a random wrong token sits 2.6 or more down.
TIE_TOL = 0.05


@functools.cache
def reference_gaps():
    """One jitted padded forward for every stream check: ``gap[i]`` is how
    far ``tokens[i + 1]`` sits below the best logit at position ``i``. The
    weights are an argument, so every engine's check shares one compile."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import parallel_lm as plm

    def gaps(params, tokens):
        logits = plm.lm_apply(params, tokens[None])[0, :-1]
        chosen = jnp.take_along_axis(logits, tokens[1:, None], -1)[:, 0]
        return logits.max(-1) - chosen

    return jax.jit(gaps)


def near_greedy(name, params, workload, streams):
    """Every token of every stream is the reference's greedy choice GIVEN
    THE STREAM'S OWN CONTEXT, up to a near-tie: one teacher-forced padded
    forward per stream (f32, highest matmul precision). Unlike comparing
    two streams token for token, this says something about every token
    after a parting too."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    lmax = int(params["pos"].shape[0])
    worst = 0.0
    for rid, ((_, prompt, _), out) in enumerate(zip(workload, streams)):
        n, m = len(prompt), len(out)
        tokens = np.zeros((lmax,), np.int32)       # causal: the pad is unseen
        tokens[:n + m] = np.concatenate([prompt, out])
        with jax.default_matmul_precision("highest"):
            gap = np.asarray(reference_gaps()(
                params, jnp.asarray(tokens)))[n - 1:n + m - 1]
        at = int(gap.argmax())
        assert gap[at] <= TIE_TOL, (
            f"{name}: request {rid} token {at} ({out[at]}) sits "
            f"{gap[at]:.3f} below the best reference logit (a near-tie is "
            f"within {TIE_TOL})")
        worst = max(worst, float(gap[at]))
    say("serve streams", f"{name}: every token of {len(streams)} streams "
        f"within {worst:.3f} of the best reference logit (bound {TIE_TOL})")


def identical(a, b):
    return f"{sum(x == y for x, y in zip(a, b))} of {len(a)}"


def check_paged_kernel(sargs, cfg):
    """The paged kernel against the gather reference, at the serving
    shape, on seeded random pages: half the slots mid-page, one on a page
    boundary, one idle. The reference matmuls run at highest precision —
    the kernel's arithmetic is f32 on the VPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops.attention import dot_product_attention
    from horovod_tpu.ops.paged_attention import paged_attention_decode
    from horovod_tpu.serve.engine import _gather_cache

    S, ps = cfg.decode_slots, cfg.page_size
    H, D = sargs.heads, sargs.d_model // sargs.heads
    pps = 4
    rng = np.random.default_rng(0)
    lengths = np.asarray(
        [0 if s == S - 1 else 2 * ps if s == 0 else int(rng.integers(
            1, pps * ps + 1)) for s in range(S)], np.int32)
    pages = 1 + S * pps
    k, v = (jnp.asarray(rng.normal(size=(pages, ps, H, D)), jnp.float32)
            for _ in range(2))
    q = jnp.asarray(rng.normal(size=(S, H, D)), jnp.float32)
    tables = np.arange(1, 1 + S * pps, dtype=np.int32).reshape(S, pps)
    out = np.asarray(paged_attention_decode(
        q, k, v, jnp.asarray(tables), jnp.asarray(lengths)))
    with jax.default_matmul_precision("highest"):
        for s, ln in enumerate(lengths):
            if ln == 0:
                assert not out[s].any(), "idle slot wrote output"
                continue
            ref = dot_product_attention(
                q[s][None], _gather_cache(k, tables[s])[:ln],
                _gather_cache(v, tables[s])[:ln], causal=True,
                scale=1.0 / np.sqrt(D), q_offset=int(ln) - 1)
            np.testing.assert_allclose(out[s], np.asarray(ref)[0],
                                       rtol=1e-4, atol=1e-4)
    say("serve paged kernel", f"{S} slots x {H} heads x {D}, page {ps}: "
        "equals the gather reference to 1e-4")


def serve_phase(name, params, cfg, workload, *, want_mosaic=None):
    """One ServeEngine answers the workload to completion; returns the
    token streams in arrival order."""
    from horovod_tpu.serve import ServeEngine
    from tools import serve_bench

    t0 = time.perf_counter()
    eng = ServeEngine(params, cfg, chips=cfg.tp_degree)
    if want_mosaic is not None:
        assert_mosaic(name, eng._step_decode.lower(
            eng.params, eng.cache.pages, eng._build_dec()).as_text(),
            want_mosaic)
    serve_bench._warm(eng, workload)
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    serve_bench.drive_continuous(eng, workload)
    run_s = time.perf_counter() - t0
    streams = finished_streams(name, eng.finished, workload)
    say(name, f"set-up: build + warm incl. compile {warm_s:.1f}s, "
              f"{len(streams)} requests / {sum(map(len, streams))} tokens "
              f"in {run_s:.2f}s; every request finished")
    return eng, streams


def finished_streams(name, reqs, workload):
    """Every request finished with every token asked for; the streams in
    arrival order."""
    reqs = sorted(reqs, key=lambda r: r.rid)
    assert len(reqs) == len(workload), (
        f"{name}: {len(reqs)} of {len(workload)} requests finished")
    for req, (_, _, n) in zip(reqs, workload):
        assert req.state == "finished" and len(req.output) == n, (
            f"{name}: request {req.rid} ended {req.state} with "
            f"{len(req.output)} of {n} tokens")
    return [list(r.output) for r in reqs]


def serve_one_chip(sz, kernels_compiled, tag):
    """serve_bench's default geometry and traffic over the LM's weights
    from seed 0, with a position table as long as the worst request.
    Returns what the all-chip phases serve again."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models import parallel_lm as plm
    from tools import lm_common, serve_bench

    sargs = serve_bench.build_parser().parse_args(list(sz.serve))
    cfg, lmax = serve_bench.build_config(sargs)
    params = lm_common.build_params(sargs, lmax)
    workload = serve_bench.make_workload(sargs)
    check_paged_kernel(sargs, cfg)
    _, gather = serve_phase(
        "serve gather", params,
        dataclasses.replace(cfg, attention="gather"), workload)
    cfg = dataclasses.replace(cfg, attention="paged")
    _, paged = serve_phase("serve paged", params, cfg, workload,
                           want_mosaic=kernels_compiled)
    t0 = time.perf_counter()
    decode = jax.jit(plm.lm_decode, static_argnames=("steps",))
    ref = [list(np.asarray(decode(
        params, jnp.asarray(prompt, jnp.int32)[None], steps=n))[0])
        for _, prompt, n in workload]
    say("serve lm_decode", f"set-up: {len(ref)} streams incl. one compile "
        f"a prompt length {time.perf_counter() - t0:.1f}s")
    for name, streams in (("lm_decode", ref), ("gather", gather),
                          ("paged", paged)):
        near_greedy(name, params, workload, streams)
    say("serve streams", f"identical: gather = lm_decode on "
        f"{identical(gather, ref)}, paged = gather on "
        f"{identical(paged, gather)}")
    if not kernels_compiled:
        # The CPU pins of the test suite, restated: bit-identical.
        assert gather == ref and paged == gather, "CPU streams differ"
    say("serve", tag)
    return sargs.heads, cfg, params, workload, paged


def serve_all_chips(one_chip, n, tag):
    """tp=n over one engine, then n one-chip replicas in this process."""
    import jax

    from horovod_tpu.serve import FleetConfig
    from tools import serve_bench

    heads, cfg, params, workload, one_chip_streams = one_chip
    eng, tp = serve_phase(
        f"serve paged tp={n}", params,
        dataclasses.replace(cfg, mesh=f"dp=1,tp={n}"), workload)
    shard = eng.cache.pages[0]["k"].addressable_shards[0].data.shape
    assert shard[2] == heads // n, (
        f"tp={n}: a shard holds {shard[2]} of {heads} heads")
    del eng
    near_greedy(f"tp={n} ({shard[2]} heads a shard)", params, workload, tp)

    t0 = time.perf_counter()
    fleet, reqs = serve_bench.run_fleet(
        params, cfg, FleetConfig(replicas=n, transport="inproc"), workload)
    try:
        homes = [next(iter(rep.engine.cache.pages[0]["k"].devices()))
                 for rep in fleet.replicas]
        assert len(set(homes)) == n, (
            f"{n} replicas hold their pages on {len(set(homes))} "
            f"device(s): {homes}")
        assert all(next(iter(jax.tree_util.tree_leaves(
            rep.engine.params)[0].devices())) == home
            for rep, home in zip(fleet.replicas, homes)), "params elsewhere"
        fl = finished_streams(f"serve fleet {n} x one chip", reqs, workload)
        busy = sum(1 for rep in fleet.replicas if rep.engine.steps)
        say(f"serve fleet {n} x one chip",
            f"set-up: build + warm + run incl. {n} replicas' compiles "
            f"{time.perf_counter() - t0:.1f}s")
    finally:
        fleet.close()
    near_greedy(f"fleet (pages on {n} distinct devices, {busy} replicas "
                "stepped)", params, workload, fl)
    say("serve streams", f"identical to the one-chip paged engine: tp={n} "
        f"on {identical(tp, one_chip_streams)}, fleet on "
        f"{identical(fl, one_chip_streams)}")
    say("serve all chips", tag)


# ------------------------------------------------------------------- main


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy sizes on a 4-device virtual CPU platform, "
                         "interpreted kernels: debugs this script, proves "
                         "nothing about the chip")
    args = ap.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()

    import jax

    import horovod_tpu.jax as hvd
    from horovod_tpu.utils import compile_cache

    devices = jax.devices()
    platform, kind, count = (devices[0].platform, devices[0].device_kind,
                             len(devices))
    say("device", f"platform={platform} device_kind={kind} count={count}")
    if platform != ("cpu" if args.rehearsal else "tpu"):
        sys.exit(f"chip_smoke: JAX found platform {platform!r} "
                 f"({kind} x {count}), not a TPU: nothing was run")
    say("compile cache", compile_cache.enable())
    sz, tag = (TOY, "rehearsal") if args.rehearsal else (REAL, "pass")
    t_start = time.perf_counter()

    hvd.init(devices=devices[:1])
    resnet_loss = train_one_chip(sz, kernels_compiled=not args.rehearsal)
    say("train", tag)
    served = serve_one_chip(sz, not args.rehearsal, tag)
    hvd.shutdown()

    if count >= 4:
        n = 4
        hvd.init(devices=devices[:n])
        train_all_chips(sz, resnet_loss, n)
        say(f"train dp={n}", tag)
        hvd.shutdown()
        serve_all_chips(served, n, tag)

    say("total", f"{time.perf_counter() - t_start:.0f}s")
    print(json.dumps({"ok": not args.rehearsal,
                      "device": {"platform": platform, "kind": kind,
                                 "count": count}}), flush=True)


if __name__ == "__main__":
    main()
