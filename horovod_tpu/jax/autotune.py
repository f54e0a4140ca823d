"""Step-time autotuning for the XLA/SPMD lane (HOROVOD_AUTOTUNE).

The reference autotuner tuned {fusion threshold, cycle time} NUMERICALLY
and the hierarchical-allreduce/allgather modes CATEGORICALLY against
bytes/sec scored over sampling windows (horovod/common/parameter_manager.
h:35-43,149-217 — CategoricalParameterChain wrapping the numeric
Bayesian chain). On the compiled SPMD lane there is no cycle time — the
knobs with a data-plane meaning are the gradient-bucket fusion threshold
used by :mod:`horovod_tpu.jax.fusion` and the hierarchical-allreduce
routing (two-level ICI/DCN ladder vs flat psum) — and the honest
objective is measured step wall-time.

Mechanism: :func:`horovod_tpu.parallel.spmd.spmd_fn` dispatch handles
consult this tuner. Every ``window`` steps the tuner blocks on the step
output (the only way to observe real device time under async dispatch),
scores the current candidate in steps/sec, advances to the next, and
bumps ``generation`` — which makes every dispatch handle re-jit so the
new (threshold, hierarchical) pair re-traces into a new bucket/collective
plan. Per candidate the first window is discarded as warmup (it pays the
recompile), mirroring the reference's warmup-discard
(parameter_manager.h:38-43). Candidate order comes from the native GP +
expected-improvement machinery when available (``hvdtpu_ei_next`` — the
same csrc/autotune/ code that tunes the eager lane, reference
bayesian_optimization.h:31-44) run per hierarchical category, else a
sequential sweep; scores are synced from process 0 so every process
probes and converges identically. When probing ends the best
(threshold, hierarchical) pair wins, ``converged`` flips, and the hot
path never blocks again. Scores append to HOROVOD_AUTOTUNE_LOG in the
native tuner's TSV layout plus a hierarchical column.
"""

from __future__ import annotations

import time
from typing import List, Optional, Sequence, Tuple


# Sweep space: "no fusion" plus power-of-two thresholds spanning the
# reference's 0..64 MB range (parameter_manager.cc search space) one octave
# past it, since TPU gradient sets can exceed 64 MB.
DEFAULT_CANDIDATES = [0] + [1 << s for s in range(20, 28)]  # 1 MB .. 128 MB

Candidate = Tuple[int, bool]  # (fusion_threshold bytes, hierarchical)


def _hier_available(st) -> bool:
    """Whether the two-level ladder can tile the "hvd" axis — delegated
    to fusion.py's own resolution (the SAME resolve_hierarchical the
    traced collective runs, slice detection included) so the tuner's
    candidate space and the executing path can never drift apart."""
    from horovod_tpu.jax.fusion import resolve_hierarchical

    return resolve_hierarchical("on", st.global_device_count) > 0


class StepAutotuner:
    """Tunes ``config.fusion_threshold`` and
    ``config.hierarchical_allreduce`` against measured step rate.

    ``candidates`` accepts plain thresholds (tuned flat-only, the
    original surface) or ``(threshold, hierarchical)`` pairs. By default
    the space is every threshold in flat mode plus — when the mesh can
    actually ladder — every threshold in hierarchical mode, mirroring
    the reference's categorical x numeric joint space
    (parameter_manager.h:149-205).

    ``strategy``: ``"sweep"`` probes every candidate in order; ``"ei"``
    probes 3 seeds and then lets the native GP + expected-improvement
    machinery pick each next probe WITHIN a hierarchical category,
    alternating between categories that still have unprobed candidates,
    stopping at ``max_probes``. ``"auto"`` (default) uses EI when the
    native library is available and the candidate space is big enough to
    be worth a surrogate, else sweeps. Multi-host, process 0 alone picks
    candidates and broadcasts each decision, so probe sequences cannot
    diverge across hosts.
    """

    def __init__(
        self,
        config,
        log_path: str = "",
        candidates: Optional[Sequence] = None,
        window: int = 10,
        strategy: str = "auto",
        max_probes: Optional[int] = None,
    ) -> None:
        self.config = config
        if candidates is not None:
            cand = [c if isinstance(c, tuple) else (int(c), False)
                    for c in candidates]
        else:
            cand = [(t, False) for t in DEFAULT_CANDIDATES]
            from horovod_tpu.common.state import global_state

            if _hier_available(global_state()):
                cand += [(t, True) for t in DEFAULT_CANDIDATES]
        # Probe the CURRENT (default) setting first: if tuning ever
        # stalls (e.g. no handle keeps dispatching), the job is left at
        # the untuned default rather than at an arbitrary candidate.
        current: Candidate = (config.fusion_threshold,
                              bool(config.hierarchical_allreduce))
        self.candidates: List[Candidate] = [current] + [
            c for c in cand if c != current
        ]
        self.window = max(1, int(window))
        self.strategy = strategy
        self.max_probes = max_probes or (
            3 + (len(self.candidates) - 3 + 1) // 2
        )
        self.generation = 1
        self.converged = False
        self.best_threshold = current[0]
        self.best_hierarchical = current[1]
        self.best_score = -1.0
        self.probed: dict = {}  # (threshold, hier) -> synced score
        # Resolve the strategy NOW (setup time, where a cold native build
        # is acceptable) rather than mid-training. Only process 0's
        # strategy matters: it alone picks candidates; everyone else
        # follows its broadcast decisions, so per-host differences in
        # native availability cannot diverge the probe sequence.
        if strategy == "auto":
            if len(self.candidates) >= 5:
                try:
                    from horovod_tpu import native

                    native.load_library()
                    strategy = "ei"
                except Exception:
                    strategy = "sweep"
            else:
                strategy = "sweep"
        self._strategy_resolved = strategy
        self._ei_category = False  # alternates when both have unprobed
        self._warming = True
        self._steps_in_window = 0
        self._t0: Optional[float] = None
        self._samples = 0
        self._owner = None
        self._owner_idle = 0
        self._log = open(log_path, "w") if log_path else None
        self._apply(self.candidates[0])

    def _apply(self, cand: Candidate) -> None:
        self.config.fusion_threshold = cand[0]
        self.config.hierarchical_allreduce = cand[1]
        # Pin the tri-state knob too: without this, a FLAT candidate on
        # a DCN-present mesh would still ladder through the default
        # "auto" (fusion.resolve_hierarchical) and the categorical A/B
        # would silently probe ladder-vs-ladder.
        self.config.hierarchical = "on" if cand[1] else "off"

    def _current(self) -> Candidate:
        return (self.config.fusion_threshold,
                bool(self.config.hierarchical_allreduce))

    # -- dispatch-side hooks ------------------------------------------------

    def claim(self, handle) -> bool:
        """Bind the tuner to ONE dispatch handle at a time. Only the
        owner's steps are counted/scored; a second SPMD handle in the loop
        (eval step, metric reduction) would otherwise pollute the
        steps/sec score with a different program. If the owner stops
        dispatching (a warmup/eval handle that claimed first, a rebuilt
        step), ownership hands off to the active handle after 3 windows
        of owner inactivity and the partial window restarts — the sweep
        can slow down but never stalls. Both claim and handoff follow
        dispatch order, which is program order, so every process makes
        identical decisions."""
        if self._owner is None or handle is self._owner:
            self._owner = handle
            self._owner_idle = 0
            return True
        self._owner_idle += 1
        if self._owner_idle > 3 * self.window:
            self._owner = handle
            self._owner_idle = 0
            self._steps_in_window = 0
            self._warming = True
            self._t0 = None
            return True
        return False

    def step_done(self) -> bool:
        """Count one dispatched step; True when the caller must block on the
        step output and call :meth:`end_window`."""
        if self.converged:
            return False
        self._steps_in_window += 1
        return self._steps_in_window >= self.window

    def end_window(self, out=None) -> None:
        """Score the window that just completed.

        ``out`` is the window's step output: when given, the tuner itself
        waits for it (:func:`horovod_tpu.utils.devsync.window_sync`)
        BEFORE reading the clock — dispatch is asynchronous, so a probe
        that did not wait would score dispatch rate, not step rate, and
        converge to a meaningless winner. ``out=None``: the caller has
        already waited.
        """
        if out is not None:
            from horovod_tpu.utils.devsync import window_sync

            window_sync(out)
        now = time.perf_counter()
        self._steps_in_window = 0
        if self._warming or self._t0 is None:
            # Warmup window: paid the recompile for this candidate.
            self._log_line("warmup", self._current(), 0.0)
            self._warming = False
            self._t0 = now
            return
        score = self.window / (now - self._t0)  # steps/sec
        # Multi-host: every process adopts process 0's measurement, so
        # probed/best — and therefore every EI decision and the final
        # winner — are identical everywhere. Divergent bucket plans
        # would lower different collective sequences into the "same"
        # SPMD program (reference SyncParams rationale,
        # parameter_manager.h:95-96,232).
        score = self._sync_value(score)
        cur = self._current()
        self.probed[cur] = score
        self._log_line("sample", cur, score)
        if score > self.best_score:
            self.best_score = score
            self.best_threshold, self.best_hierarchical = cur
        nxt = self._decide_next()
        if nxt is None:
            self._apply((self.best_threshold, self.best_hierarchical))
            self.converged = True
            self.generation += 1
            self._log_line(
                "converged",
                (self.best_threshold, self.best_hierarchical),
                self.best_score)
            if self._log is not None:
                self._log.close()
                self._log = None
        else:
            self._apply(nxt)
            self.generation += 1
            self._warming = True
            self._t0 = now

    # -- candidate selection ------------------------------------------------

    @staticmethod
    def _xform(threshold: int) -> float:
        """Thresholds live on a log scale (0, 1 MB .. 128 MB); the GP
        surrogate sees log2(1 + MB) so candidates are evenly spaced."""
        import math

        return math.log2(1.0 + threshold / float(1 << 20))

    def _decide_next(self) -> Optional[Candidate]:
        """Process 0 picks the next probe; everyone adopts its choice.
        One broadcast decision per window makes divergence structurally
        impossible — no local EI result, native-build failure, or FP
        difference can fork the probe sequence across hosts."""
        from horovod_tpu.common.state import global_state

        st = global_state()
        if st.process_count <= 1:
            return self._next_candidate()
        import jax.numpy as jnp

        from horovod_tpu.jax import eager

        local = [-1, 0]
        if st.process_index == 0:
            nxt = self._next_candidate()
            if nxt is not None:
                local = [int(nxt[0]), int(nxt[1])]
        # int32 is enough: thresholds cap at 128 MB << 2^31.
        got = eager.process_broadcast(jnp.asarray(local, jnp.int32), 0)
        t = int(got[0])
        return None if t < 0 else (t, bool(int(got[1])))

    def _next_candidate(self) -> Optional[Candidate]:
        unprobed = [c for c in self.candidates if c not in self.probed]
        if not unprobed:
            return None
        if self._strategy_resolved == "sweep":
            return unprobed[0]
        if len(self.probed) >= self.max_probes:
            return None
        # Seeds: default (already probed first), largest flat, then —
        # when the space has a hierarchical category — the mid
        # hierarchical candidate, else the mid flat one.
        flats = [c for c in self.candidates if not c[1]]
        hiers = [c for c in self.candidates if c[1]]
        seeds = []
        if flats:
            seeds.append(flats[-1])
        if hiers:
            seeds.append(hiers[len(hiers) // 2])
        elif flats:
            seeds.append(flats[len(flats) // 2])
        for seed in seeds:
            if seed not in self.probed and seed in unprobed:
                return seed
        # EI within a category; alternate between categories that still
        # have unprobed candidates so both hierarchy modes keep getting
        # explored (the reference swept its categorical chain similarly).
        for _ in range(2):
            self._ei_category = not self._ei_category
            pool = [c for c in unprobed if c[1] == self._ei_category]
            if pool:
                break
        else:
            pool = unprobed
        if not pool:
            return unprobed[0]
        known = [(k, v) for k, v in self.probed.items()
                 if k[1] == pool[0][1]]
        if len(known) >= 2:
            try:
                from horovod_tpu import native

                i = native.ei_next(
                    [self._xform(k[0]) for k, _ in known],
                    [v for _, v in known],
                    [self._xform(c[0]) for c in pool],
                )
                if i >= 0:
                    return pool[i]
            except Exception:
                pass
        return pool[0]

    def _sync_value(self, value: float) -> float:
        """Adopt process 0's measurement (identity on one process)."""
        from horovod_tpu.common.state import global_state

        st = global_state()
        if st.process_count <= 1:
            return value
        import jax.numpy as jnp

        from horovod_tpu.jax import eager

        return float(
            eager.process_broadcast(
                jnp.asarray([value], jnp.float32), 0
            )[0]
        )

    def close(self) -> None:
        if self._log is not None:
            self._log.close()
            self._log = None

    # -- logging ------------------------------------------------------------

    def _log_line(self, kind: str, cand: Candidate, score: float) -> None:
        self._samples += 1
        if self._log is not None:
            # The native tuner's TSV columns (csrc/autotune/
            # parameter_manager.cc) — sample index, kind, threshold
            # bytes, cycle ms (n/a on this lane), score — plus a sixth
            # hierarchical column (0/1).
            self._log.write(
                f"{self._samples}\t{kind}\t{cand[0]}\t0.0\t{score}"
                f"\t{int(cand[1])}\n"
            )
            self._log.flush()
