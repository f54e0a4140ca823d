#!/usr/bin/env python
"""Hardware measurement sweep: run every bench lane in PRIORITY order, so
a short chip budget captures the most valuable results first.

Each lane is a bounded subprocess — one ``bench.py`` process at a time
holds the chip, and this parent never imports JAX; results append to
PERF_RUNS.tsv as
    <utc-iso>\t<lane>\t<json-or-error>
and a summary table prints at the end. Safe to re-run: lanes already
recorded today can be skipped with --resume.

Priority:
  1. resnet50 baseline        (reference-parity tracked metric)
  2. resnet50 --fused-bn      (round-3 A/B: Pallas conv+BN statistics)
  3. transformer_lm           (long-context tokens/sec lane)
  4. resnet101 / vgg16 / inception_v3  (headline table cells)
  5. flash_check              (tools/tpu_flash_check.py artifact)
  6. resnet50 bs=128 / bs=256 (batch-size scaling lane)
"""

import argparse
import datetime
import os
import signal
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOG = os.path.join(REPO, "PERF_RUNS.tsv")

LANES = [
    ("resnet50", ["bench.py"]),
    # Window lane (round-6 tentpole, horovod_tpu/jax/window.py): 30
    # steps per dispatch via lax.scan — prices the host-gap fix right
    # next to the protocol headline (ResNet-50 device-only ceiling
    # ~2,580 img/s; the measured --num-batches-per-iter 30 proxy gave
    # 2,320). Record carries metric ..._win30, vs_baseline null.
    ("resnet50_win30", ["bench.py", "--steps-per-dispatch", "30"]),
    ("resnet50_fused_bn", ["bench.py", "--fused-bn"]),
    # Overlap A/B (round-7 tentpole, horovod_tpu/jax/fusion.py):
    # backward-overlapped bucketed collectives (reverse-order issue)
    # vs the legacy post-backward block —
    # adjacent so the pair shares chip condition. A 1 MiB fusion
    # threshold gives ResNet-50's 98 MB of fp32 gradients a ~100-bucket
    # plan, the regime where issue order and async scheduling can
    # matter; the record's "overlap"/"buckets" stamps carry the
    # dispatch-shape evidence. (Single chip prices dispatch overhead
    # only; the scaling win is the tools/scaling_model.py prediction
    # until a multi-chip slice exists.)
    ("resnet50_overlap_on", ["bench.py", "--overlap", "on"],
     {"HOROVOD_FUSION_THRESHOLD": "1048576"}),
    ("resnet50_overlap_off", ["bench.py", "--overlap", "off"],
     {"HOROVOD_FUSION_THRESHOLD": "1048576"}),
    # Hierarchical-ladder A/B (round-10 tentpole, horovod_tpu/jax/
    # fusion.py HOROVOD_HIERARCHICAL): each bucket as intra-slice rs ->
    # inter-slice exchange -> intra-slice ag, vs the adjacent flat
    # baselines (resnet50 / vgg16 above share chip condition). On a
    # single chip the ladder degrades to flat (the record's
    # "hierarchical" stamp says so — inner 0); on a multi-chip slice
    # the pinned inner=4 prices the ladder's extra collective launches
    # against the flat psum, and on a real multi-slice job the "wire"
    # stamp carries the ICI/DCN byte split the scaling model predicts
    # from. vgg16_dcn_int8_ab adds the int8 DCN wire (error-feedback
    # residuals ride the optimizer state): VGG's 528 MB gradient is the
    # DCN-bound regime where docs/benchmarks.md predicts 90.2% -> 96.4%
    # at 8x8.
    ("resnet50_hier_ab", ["bench.py", "--hierarchical", "on"],
     {"HOROVOD_HIERARCHICAL_INNER_SIZE": "4"}),
    ("vgg16_dcn_int8_ab", ["bench.py", "--model", "vgg16",
                           "--hierarchical", "on",
                           "--compression", "int8"],
     {"HOROVOD_HIERARCHICAL_INNER_SIZE": "4"}),
    # Honest re-adjudication lanes (round 5): both options were priced
    # under dispatch timing ("within noise" / never measured) — the
    # fixed protocol decides them on device time.
    ("resnet50_bf16_momentum", ["bench.py", "--bf16-momentum"]),
    ("resnet50_zero", ["bench.py", "--zero"]),
    # bf16-momentum's honest regime: VGG's 138M params make the
    # optimizer update ~23% of device time (PERF.md VGG profile), so
    # halving momentum traffic shows where ResNet's ~4% share could not.
    ("vgg16_bf16_momentum", ["bench.py", "--model", "vgg16",
                             "--bf16-momentum"]),
    # Inference lane (beyond the reference, docs/inference.md): greedy
    # KV-cache decode throughput of the packaged LM.
    ("transformer_lm_decode", ["tools/decode_bench.py"]),
    # Serving lanes (round-8 tentpole, horovod_tpu/serve/ +
    # docs/serving.md), adjacent to the decode lane so the single-batch
    # baseline and the engine share chip condition. serve_poisson:
    # the continuous-batching engine under open-loop Poisson load
    # (tokens/s/chip + p50/p99 TTFT + p50/p99 per-token latency +
    # page occupancy in one record). serve_static_ab: continuous vs
    # static batching on the IDENTICAL workload (same seed) — the
    # record's serve.ab.continuous_over_static carries the A/B verdict;
    # heterogeneous generation lengths (16..256) are the regime where
    # static batching's drain barrier holds slots hostage.
    ("serve_poisson", ["tools/serve_bench.py", "--requests", "64",
                       "--rate", "8", "--new-min", "16",
                       "--new-max", "256"]),
    ("serve_static_ab", ["tools/serve_bench.py", "--requests", "64",
                         "--rate", "8", "--new-min", "16",
                         "--new-max", "256", "--ab"]),
    # Gather-vs-paged decode attention A/B (round-9 tentpole,
    # horovod_tpu/ops/paged_attention.py): the SAME continuous engine
    # and workload, decode attention flipped between the dense
    # [S, Lmax, H, D] gather (reference) and the fused page-streaming
    # Pallas kernel. Long generations against a large Lmax are the
    # win regime (per-step K/V bytes O(t) vs O(Lmax)); the record's
    # serve.ab_attention.paged_over_gather carries the throughput
    # verdict and serve.attention the static byte accounting for both
    # policies.
    ("serve_paged_ab", ["tools/serve_bench.py", "--requests", "64",
                        "--rate", "8", "--new-min", "16",
                        "--new-max", "256", "--ab-attention"]),
    # Fleet fault A/B (round-12 tentpole, horovod_tpu/serve/fleet.py):
    # the SAME Poisson workload through a 2-replica fleet twice —
    # clean, then with replica 1 killed at 40% of the arrival horizon —
    # so one record carries the whole reliability story: the killed
    # replica's in-flight requests drain to the survivor and finish
    # BIT-IDENTICAL to the clean run (the bench aborts otherwise), the
    # incident is classified (crashed, not a hang), and
    # serve.fleet/serve.fleet_ab stamp redispatched count, KV tokens
    # recomputed, and the faulted-over-clean p99 TTFT the relaunch +
    # recompute cost shows up as.
    ("serve_fleet_fault_ab", ["tools/serve_bench.py", "--requests", "64",
                              "--rate", "8", "--new-min", "16",
                              "--new-max", "256", "--fleet", "2",
                              "--fault-plan", "kill:replica=1,at=40%",
                              "--require-finished"]),
    # Process-transport fleet A/B (round-13 tentpole, horovod_tpu/
    # serve/{transport,worker}.py): the SAME workload and fault plan,
    # but each replica is its own worker OS process behind the framed
    # RPC transport — the kill is a genuine SIGKILL of a real process,
    # classified through its reaped exit code, and serve.fleet stamps
    # transport="process" + per-RPC overhead p50/p99 + transport
    # incident counts beside the inproc lane above, so the record pair
    # prices exactly what crash isolation costs.
    ("serve_fleet_proc_ab", ["tools/serve_bench.py", "--requests", "64",
                             "--rate", "8", "--new-min", "16",
                             "--new-max", "256", "--fleet", "2",
                             "--fleet-transport", "process",
                             "--fault-plan", "kill:replica=1,at=40%",
                             "--require-finished"]),
    # Loopback-TCP fleet A/B (round-14 tentpole, serve/transport.py tcp
    # + serve/netfault.py): the SAME workload through a 2-replica fleet
    # on the TCP transport, with the whole HOST network-partitioned for
    # 2 s mid-run — the deterministic injector darkens every connection
    # to the host at the transport seam, detection rides the typed
    # taxonomy (deadline expiry or the half-open reset when the window
    # ends), BOTH replicas drain + redispatch as ONE classified
    # host_down incident, and every greedy stream still finishes
    # bit-identical to the clean run. serve.fleet stamps
    # transport="tcp" + hosts + host_incidents + rpc overhead on both
    # sides, so the record pair prices what the extra transport hop
    # and a whole-host loss cost.
    ("serve_fleet_tcp_ab", ["tools/serve_bench.py", "--requests", "64",
                            "--rate", "8", "--new-min", "16",
                            "--new-max", "256", "--fleet", "2",
                            "--fleet-transport", "tcp",
                            "--fleet-max-restarts", "4",
                            "--fault-plan",
                            "partition:host=0,at=50%,secs=2",
                            "--require-finished"]),
    # Rolling-update A/B (round-15 tentpole, serve/params_wire.py +
    # fleet.update_params): the SAME workload through a 2-replica TCP
    # fleet twice — clean, then with a mid-run ZERO-DOWNTIME rolling
    # weight update whose FIRST push attempt is torn mid-transfer by
    # the transfer: fault. The push must classify the tear, back off,
    # reconnect, and resume from the worker's verified offset (exactly
    # one transfer retry), both replicas must digest-verify the new
    # version's sha256, no request may drop or reject, and every
    # greedy stream stays bit-identical to the clean run (same params
    # content re-pushed as v2, so the version pin is exercised while
    # streams stay comparable). serve.fleet stamps params_push
    # (bytes/chunks/ms/retries/version) on the faulted side — the
    # record prices what a weight roll costs under live traffic.
    ("serve_fleet_update_ab", ["tools/serve_bench.py", "--requests",
                               "64", "--rate", "8", "--new-min", "16",
                               "--new-max", "256", "--fleet", "2",
                               "--fleet-transport", "tcp",
                               "--fleet-max-restarts", "4",
                               "--rolling-update-at", "50%",
                               "--fault-plan",
                               "transfer:replica=0,at=50%",
                               "--require-finished"]),
    # Prefix-caching A/B (round-16 tentpole, horovod_tpu/serve/
    # prefix.py): the many-users-one-system-prompt workload — every
    # prompt opens with the SAME 256-token system prompt — through a
    # 2-replica fleet twice, cold then cached. The cached side maps the
    # shared prompt's full pages read-only out of the radix index
    # (refcount++, copy-on-write on any overlap), rendezvous routing
    # keeps prefix-mates on one home, and the bench ABORTS unless every
    # greedy stream is bit-identical off vs on AND each (prefix,
    # replica) paid exactly ONE cold prefill. serve.prefix /
    # serve.fleet.prefix stamp hit_rate + prefill_tokens_saved +
    # pages_shared; serve.ab_prefix.cached_over_cold carries the
    # throughput verdict.
    ("serve_prefix_ab", ["tools/serve_bench.py", "--requests", "64",
                         "--rate", "8", "--new-min", "16",
                         "--new-max", "256", "--fleet", "2",
                         "--system-prompt-len", "256", "--ab-prefix",
                         "--require-finished"]),
    # TP-sharded decode A/B (round-18 tentpole, ServeConfig.mesh +
    # the SPMD step): the IDENTICAL workload through one engine twice
    # — unsharded, then head-sharded over dp=1,tp=4 (KV pages
    # [pages, page_size, H/tp, D] per chip, Megatron params,
    # vocab-parallel logits all-gathered so the host sampler sees the
    # full row). The bench ABORTS unless every greedy stream is
    # bit-identical across the sides and the sharded side's
    # kv_bytes_per_chip is at most 1/tp of the single-chip bytes;
    # serve.tp stamps degree/per-chip-bytes/wall-clock ratio. Default
    # geometry (12 heads, 32000 vocab, 4x mlp) divides tp=4 exactly —
    # the engine fail-fasts otherwise.
    ("serve_tp_ab", ["tools/serve_bench.py", "--requests", "64",
                     "--rate", "8", "--new-min", "16",
                     "--new-max", "256", "--mesh", "dp=1,tp=4",
                     "--ab-tp", "--require-finished"]),
    # Speculative-decoding A/B (round-19 tentpole, serve_step_spec +
    # serve/sampling.py): the IDENTICAL workload through one engine
    # twice — plain decode, then with the layer-skip draft (half the
    # stack, sharing embed/head and the target's own KV pages)
    # proposing 4 tokens per slot per tick, verified in ONE
    # rectangular-causal pass (q_offset=t, k_offset=0 — the chunked-
    # prefill shape). The bench ABORTS unless every greedy stream is
    # bit-identical across the sides; serve.ab_spec stamps k /
    # accept_rate / tokens_per_step / spec_over_base. On real
    # accelerators tokens_per_step > 1 converts directly to decode
    # throughput; the CPU ratio is honest, not flattering.
    ("serve_spec_ab", ["tools/serve_bench.py", "--requests", "64",
                       "--rate", "8", "--new-min", "16",
                       "--new-max", "256", "--speculate", "4",
                       "--ab-spec", "--require-finished"]),
    # Disaggregated prefill/decode A/B (round-20 tentpole,
    # serve/disagg.py + serve/kv_wire.py): the IDENTICAL mixed
    # long-prefill/short-decode Poisson workload through a colocated
    # 2-replica fleet, then split 1 prefill + 1 decode — every request
    # prefills in one pool, ships its finished KV pages over the
    # chunk-stream wire (per-page [page_size, H, D] tiles, per-chunk
    # CRC + whole-manifest sha256, resume-from-offset) and decodes in
    # the other. The bench ABORTS unless every greedy stream is
    # bit-identical colocated vs disaggregated (and vs lm_decode);
    # serve.disagg stamps transfers / kv_bytes_shipped / transfer
    # p50/p99 / TTFT+TBT both sides / disagg_over_colocated p99 TTFT.
    # Long prefills + short decodes is disaggregation's home turf —
    # the interference the split removes is prefill chunks stealing
    # decode ticks.
    ("serve_disagg_ab", ["tools/serve_bench.py", "--requests", "64",
                         "--rate", "8", "--prompt-min", "64",
                         "--prompt-max", "192", "--new-min", "4",
                         "--new-max", "32", "--pools", "1,1",
                         "--ab-disagg", "--require-finished"]),
    ("transformer_lm", ["bench.py", "--model", "transformer_lm"]),
    # Adjacent to the dense lane so the A/B shares chip condition: the
    # chunked fused loss removes the step's largest HBM tensor.
    ("transformer_lm_fused_ce", ["bench.py", "--model", "transformer_lm",
                                 "--fused-ce"]),
    ("transformer_lm_flash", ["bench.py", "--model", "transformer_lm",
                              "--flash-attention"]),
    # Truncated-vs-full causal grid A/B (adjacent so the pair shares
    # chip condition): same kernel, --flash-full-grid pins the full
    # (q-block, k-block) grid whose dead half the packed default skips.
    # BOTH sides pin --flash-bwd pallas (the policy's own backward
    # since PR 29; the scan is diagonal-truncated by construction, so
    # only the kernel split makes the A/B span all three grids). The
    # JSON's flash_grid field carries the step/byte/bwd accounting.
    ("transformer_lm_flash_trunc_pallasbwd",
     ["bench.py", "--model", "transformer_lm", "--attention", "flash",
      "--flash-bwd", "pallas"]),
    ("transformer_lm_flash_fullgrid",
     ["bench.py", "--model", "transformer_lm", "--attention", "flash",
      "--flash-full-grid", "--flash-bwd", "pallas"]),
    ("flash_check", ["tools/tpu_flash_check.py"]),
    # Dense against the kernels over blocks and both backwards at the
    # shapes ops.attention.attention_plan's constants come from.
    ("flash_block_sweep", ["tools/tpu_flash_check.py", "--block-sweep"]),
    # Flash-vs-dense ladder at constant 16k tokens/chip: flash's win
    # grows with the [L, L] score tensor, so the A/B runs at 4096 and
    # 8192 too (dense@8192's [2, 12, 8192, 8192] fp32 scores are
    # ~6.4 GB, ~12.9 GB with the softmax output — if that lane OOMs,
    # the record IS the flash argument; --remat bounds the rest).
    ("transformer_lm_seq4096", ["bench.py", "--model", "transformer_lm",
                                "--seq-len", "4096", "--batch-size", "4",
                                "--remat"]),
    ("transformer_lm_seq4096_flash", ["bench.py", "--model",
                                      "transformer_lm", "--seq-len", "4096",
                                      "--batch-size", "4", "--remat",
                                      "--flash-attention"]),
    # Grid-truncation A/B at the first flash-only length (16 k-blocks:
    # the packed grid runs ~53% of the full grid's steps here); both
    # sides pin the pallas backward (see the seq-2048 pair's note).
    ("transformer_lm_seq4096_flash_trunc_pallasbwd",
     ["bench.py", "--model", "transformer_lm", "--seq-len", "4096",
      "--batch-size", "4", "--remat", "--attention", "flash",
      "--flash-bwd", "pallas"]),
    ("transformer_lm_seq4096_flash_fullgrid",
     ["bench.py", "--model", "transformer_lm", "--seq-len", "4096",
      "--batch-size", "4", "--remat", "--attention", "flash",
      "--flash-full-grid", "--flash-bwd", "pallas"]),
    ("transformer_lm_seq8192", ["bench.py", "--model", "transformer_lm",
                                "--seq-len", "8192", "--batch-size", "2",
                                "--remat"]),
    ("transformer_lm_seq8192_flash", ["bench.py", "--model",
                                      "transformer_lm", "--seq-len", "8192",
                                      "--batch-size", "2", "--remat",
                                      "--flash-attention"]),
    # Fused-CE regime test (round-4): at vocab 32k/16k tokens the fused
    # loss showed no win (PERF.md) — its claimed regime is a bigger
    # head, where the dense [tokens, vocab] fp32 logits round-trips
    # dominate. A/B at vocab 64k prices that claim.
    ("transformer_lm_v64k", ["bench.py", "--model", "transformer_lm",
                             "--vocab", "64000"]),
    ("transformer_lm_v64k_fused_ce", ["bench.py", "--model",
                                      "transformer_lm", "--vocab", "64000",
                                      "--fused-ce"]),
    # Kitchen-sink long-context lane: flash + fused-CE + remat at seq
    # 8192 — the framework's best-recipe tokens/sec claim.
    ("transformer_lm_seq8192_flash_fused", ["bench.py", "--model",
                                            "transformer_lm", "--seq-len",
                                            "8192", "--batch-size", "2",
                                            "--remat", "--flash-attention",
                                            "--fused-ce"]),
    # Longest single-chip context rung: seq 16k, batch 1 (16k tok/chip
    # like every LM lane). Dense would need a [1,12,16384,16384] fp32
    # score tensor (12.9 GB) — structurally flash-only territory.
    ("transformer_lm_seq16384_flash_fused", ["bench.py", "--model",
                                             "transformer_lm", "--seq-len",
                                             "16384", "--batch-size", "1",
                                             "--remat", "--flash-attention",
                                             "--fused-ce"]),
    # Longest-rung grid A/B: at 64 k-blocks the dead half is ~49% of
    # the full grid's steps AND K/V DMA bytes — the lane family where
    # PERF.md's MFU table says the chip is least saturated (12-18%).
    # No bwd pin needed: auto already resolves to pallas at Lk 16384.
    ("transformer_lm_seq16384_flash_fused_fullgrid",
     ["bench.py", "--model", "transformer_lm", "--seq-len", "16384",
      "--batch-size", "1", "--remat", "--attention", "flash",
      "--fused-ce", "--flash-full-grid"]),
    # ViT: the compute-bound (MXU-friendly) image lane — unlike the
    # memory-bound ResNet family it should approach the chip's matmul
    # rate, quantifying how much of the ResNet gap is the model, not
    # the framework (PERF.md "memory-bound by design").
    ("vit_b16", ["bench.py", "--model", "vit_b16"]),
    ("resnet101", ["bench.py", "--model", "resnet101"]),
    ("resnet50_bs128", ["bench.py", "--batch-size", "128"]),
    ("resnet50_bs256", ["bench.py", "--batch-size", "256"]),
    # Big-compile lanes LAST, so a short budget spends its first minutes
    # on the fast lanes above. Each big model runs a *_warm compile-only
    # lane first: it pays the XLA compile into the persistent cache (the
    # cache column in PERF_RUNS.tsv records whether it did), so the
    # measured lane that follows starts warm.
    # GPT-2-medium MFU lane (VERDICT r5 ask #4): 24L x d-model 1024 x 16
    # heads (~355M params) prices the "26% MFU is device-bound at this
    # size" claim — if MFU rises with width, the 12L/768d number was
    # model-bound, not framework-bound. batch 4 seqs/chip (8k tok) +
    # --remat bound the dense lane's activation memory; the fused-CE and
    # flash variants A/B the same recipe questions as the base LM lanes.
    # Big first compile -> one warm compile-only pass first.
    ("transformer_lm_medium_warm",
     ["bench.py", "--model", "transformer_lm", "--d-model", "1024",
      "--lm-layers", "24", "--lm-heads", "16", "--batch-size", "4",
      "--remat", "--compile-only"]),
    ("transformer_lm_medium",
     ["bench.py", "--model", "transformer_lm", "--d-model", "1024",
      "--lm-layers", "24", "--lm-heads", "16", "--batch-size", "4",
      "--remat"]),
    ("transformer_lm_medium_fused_ce",
     ["bench.py", "--model", "transformer_lm", "--d-model", "1024",
      "--lm-layers", "24", "--lm-heads", "16", "--batch-size", "4",
      "--remat", "--fused-ce"]),
    ("transformer_lm_medium_flash",
     ["bench.py", "--model", "transformer_lm", "--d-model", "1024",
      "--lm-layers", "24", "--lm-heads", "16", "--batch-size", "4",
      "--remat", "--attention", "flash"]),
    ("vgg16_warm", ["bench.py", "--model", "vgg16", "--compile-only"]),
    ("vgg16", ["bench.py", "--model", "vgg16"]),
    ("inception_v3_warm", ["bench.py", "--model", "inception_v3",
                           "--compile-only"]),
    ("inception_v3", ["bench.py", "--model", "inception_v3"]),
    ("inception_v3_fused_bn", ["bench.py", "--model", "inception_v3",
                               "--fused-bn"]),
    # Inception window lane: the model with the LARGEST measured host
    # gap (32% at 29 ms steps; device-only ceiling ~3,250 img/s) —
    # after the plain inception lane so the A/B shares chip condition.
    ("inception_v3_win30", ["bench.py", "--model", "inception_v3",
                            "--steps-per-dispatch", "30"]),
]


def record(lane: str, payload: str, cache: str = "") -> None:
    stamp = datetime.datetime.now(datetime.timezone.utc).isoformat(
        timespec="seconds")
    # One record per physical line: stderr tails carry newlines/tabs.
    payload = payload.replace("\n", " ").replace("\t", " ")
    with open(LOG, "a") as f:
        f.write(f"{stamp}\t{lane}\t{payload}" +
                (f"\t{cache}" if cache else "") + "\n")


def cache_stat(cache_dir: str):
    """(entry count, total bytes) of the persistent compilation cache —
    the delta across a lane is the direct evidence of whether the
    backend serializes executables (round-3 verdict: 'was the warning
    logged? unrecorded')."""
    try:
        files = os.listdir(cache_dir)
    except OSError:
        return 0, 0
    total = 0
    for f in files:
        try:
            total += os.path.getsize(os.path.join(cache_dir, f))
        except OSError:
            pass
    return len(files), total


def run_lane(cmd, env, timeout: float):
    """Run one lane in its own process GROUP and kill the whole group on
    timeout: a lane (serve_bench's process fleet, say) may have
    children, and an orphan that still holds the chip would fail every
    subsequent lane."""
    proc = subprocess.Popen(
        [sys.executable, *cmd], cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout)
        return proc.returncode, out, err
    except subprocess.TimeoutExpired:
        try:
            os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        proc.wait(10)
        raise


def already_done_today(lane: str, after: str = "") -> bool:
    """A lane is settled by a record from today — or, when ``after`` is
    given (ISO UTC), a record stamped at or past that cutoff, so a
    re-price queue can re-run lanes that already recorded earlier the
    same day (ISO timestamps compare lexicographically)."""
    if not os.path.exists(LOG):
        return False
    today = datetime.datetime.now(datetime.timezone.utc).date().isoformat()
    for line in open(LOG):
        parts = line.rstrip("\n").split("\t")
        if (len(parts) >= 3 and parts[1] == lane
                and (parts[0] >= after if after
                     else parts[0].startswith(today))
                # Bench lanes record JSON (a failed lane records its
                # exit code and stderr tail instead, and reruns); the flash_check /
                # flash_block_sweep lanes record a "flash OK: ..."
                # stderr verdict — both count as done.
                and (parts[2].startswith("{")
                     or parts[2].startswith("flash OK:"))):
            return True
    return False


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--timeout", type=float, default=1500.0,
                    help="wall-clock bound per lane (seconds)")
    ap.add_argument("--resume", action="store_true",
                    help="skip lanes already recorded successfully today")
    ap.add_argument("--after", default="",
                    help="with --resume: only records at/past this ISO "
                         "UTC timestamp count as already done")
    ap.add_argument("--lanes", default="",
                    help="comma list to restrict (names from the table)")
    args = ap.parse_args()
    pick = set(args.lanes.split(",")) if args.lanes else None
    if pick is not None:
        known = {entry[0] for entry in LANES}
        unknown = pick - known
        if unknown:
            ap.error(f"unknown lane(s) {sorted(unknown)}; "
                     f"have {sorted(known)}")

    env = dict(os.environ)
    # `python tools/x.py` puts tools/ on sys.path, not the repo root —
    # every lane must import horovod_tpu regardless of entry location.
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # Where the lanes keep their persistent compile cache: each child's
    # horovod_tpu/utils/compile_cache.py decides, and the per-lane cache
    # column asks the same function. Loaded by path — importing the
    # package imports jax, and this parent stays off it.
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "compile_cache",
        os.path.join(REPO, "horovod_tpu", "utils", "compile_cache.py"))
    compile_cache = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(compile_cache)
    cache_dir = (compile_cache.cache_dir(env)
                 or env["JAX_COMPILATION_CACHE_DIR"])

    results = {}
    for lane, cmd, *tags in LANES:
        if pick is not None and lane not in pick:
            continue
        if args.resume and already_done_today(lane, args.after):
            print(f"[sweep] {lane}: already recorded today, skipping",
                  file=sys.stderr)
            continue
        # Tags: a dict of extra env for the lane (e.g. the overlap A/B
        # pair pins HOROVOD_FUSION_THRESHOLD so both sides run the same
        # plan).
        lane_env = {**env, **{k: v for t in tags for k, v in t.items()}}
        print(f"[sweep] running {lane}: {' '.join(cmd)}", file=sys.stderr,
              flush=True)
        n0, b0 = cache_stat(cache_dir)
        try:
            rc, out, err = run_lane(cmd, lane_env, args.timeout)
            if lane in ("flash_check", "flash_block_sweep"):
                # These print human-readable evidence, not bench JSON;
                # the record is the final stderr line (the ladder
                # verdict / best-config summary).
                payload = ("flash OK: " +
                           (err.strip().splitlines() or ["<no stderr>"])[-1]
                           if rc == 0 else f"rc={rc}: {err[-300:]}")
            else:
                lines = [l for l in out.strip().splitlines()
                         if l.startswith("{")]
                payload = lines[-1] if lines else (
                    f"rc={rc}, no JSON: {err[-300:]}")
        except subprocess.TimeoutExpired:
            payload = f"sweep-level timeout after {args.timeout:.0f}s"
        n1, b1 = cache_stat(cache_dir)
        cache = (f"cache={n1 - n0:+d}entries/{b1 - b0:+d}B "
                 f"(total {n1}/{b1}B)")
        record(lane, payload, cache)
        results[lane] = payload
        print(f"[sweep] {lane}: {cache}", file=sys.stderr, flush=True)
        print(f"[sweep] {lane}: {payload[:160]}", file=sys.stderr, flush=True)

    print("\n== sweep summary ==")
    for lane, payload in results.items():
        print(f"{lane:20s} {payload[:140]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
