"""Copy-on-write prefix caching (horovod_tpu/serve/prefix.py + the
PR-16 wiring through kvcache/scheduler/engine/router/fleet).

The acceptance pin: a cache-HIT decode is bit-identical to the cold
path and to ``lm_decode`` — shared pages serve the same K/V values, a
match never covers the whole prompt (first-token logits always come
off the prefill path), and any write to a shared page copies first.
The fleet half: the router rendezvous-hashes the normalized prefix so
prefix-mates co-locate, and a killed replica's redispatched requests
reuse the survivor's pages (``tokens_recomputed`` shrinks, stream
unchanged) — the redispatch-meets-prefix lane.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import parallel_lm as plm
from horovod_tpu.serve import (FleetConfig, PageAllocator, PrefixIndex,
                               ServeConfig, ServeEngine, ServeFleet,
                               aligned_prefix_len, prefix_route_key,
                               rendezvous_rank)
from horovod_tpu.serve.router import pick_replica

V, LMAX, LAYERS, H, DH, FFN = 64, 64, 2, 2, 8, 32


@pytest.fixture(scope="module")
def params():
    return plm.init_lm_params(jax.random.PRNGKey(0), V, LMAX, LAYERS, H,
                              DH, FFN)


def _prompt(i, lp):
    key = jax.random.fold_in(jax.random.PRNGKey(200), i)
    return np.asarray(jax.random.randint(key, (lp,), 0, V), np.int32)


def _ref(params, prompt, steps):
    return list(np.asarray(
        plm.lm_decode(params, jnp.asarray(prompt)[None], steps))[0])


def _cfg(**kw):
    base = dict(page_size=8, num_pages=40, decode_slots=2,
                prefill_chunk=4, prefix_caching=True)
    base.update(kw)
    return ServeConfig(**base)


# ------------------------------------------------------- pure helpers


class TestAlignedPrefixLen:
    def test_whole_pages_only(self):
        assert aligned_prefix_len(17, 8) == 16
        assert aligned_prefix_len(15, 8) == 8
        assert aligned_prefix_len(9, 8) == 8

    def test_never_the_entire_prompt(self):
        """The last token always prefills, so an exact-multiple prompt
        loses its final page from the matchable range — the hit path
        computes first-token logits exactly like a cold request."""
        assert aligned_prefix_len(16, 8) == 8
        assert aligned_prefix_len(8, 8) == 0

    def test_degenerate_prompts(self):
        assert aligned_prefix_len(1, 8) == 0
        assert aligned_prefix_len(0, 8) == 0


class TestRouteKey:
    def test_prefix_mates_share_the_key(self):
        """First-chunk hashing: "system prompt + user A" and "system
        prompt + user B" get the SAME key — the whole point of
        prefix-aware routing."""
        sys_p = list(range(20))
        a = prefix_route_key(sys_p + [91, 92], 8)
        b = prefix_route_key(sys_p + [77], 8)
        assert a is not None and a == b

    def test_different_first_chunk_different_key(self):
        assert prefix_route_key(list(range(16)), 8) != \
            prefix_route_key(list(range(1, 17)), 8)

    def test_unmatchable_prompt_has_no_key(self):
        # no full page clear of the last token -> no affinity
        assert prefix_route_key(list(range(8)), 8) is None
        assert prefix_route_key([1, 2, 3], 8) is None

    def test_stable_across_rebase(self):
        """rebase_for_recompute only APPENDS tokens: a redispatched
        request keeps its key, so the drained requests of a dead
        replica all rendezvous onto the same survivor."""
        p = list(range(20))
        assert prefix_route_key(p, 8) == \
            prefix_route_key(p + [5, 6, 7, 8, 9], 8)


class TestRendezvous:
    def test_deterministic_and_replica_dependent(self):
        assert rendezvous_rank("k", 0) == rendezvous_rank("k", 0)
        assert rendezvous_rank("k", 0) != rendezvous_rank("k", 1)

    def test_spreads_distinct_prefixes(self):
        """Different prefixes must not all pick the same home."""
        homes = {max(range(4), key=lambda r: rendezvous_rank(f"key{i}", r))
                 for i in range(32)}
        assert len(homes) > 1


# ------------------------------------------------------- radix index


class TestPrefixIndex:
    def _index(self, num_pages=32, ps=4):
        return PageAllocator(num_pages), PrefixIndex(
            PageAllocator(num_pages), ps)

    def test_insert_then_match_longest_chain(self):
        alloc = PageAllocator(32)
        idx = PrefixIndex(alloc, 4)
        prompt = list(range(11))            # 2 full pages of 4
        grant = alloc.alloc(3)
        table = list(grant) + [0]
        assert idx.insert(prompt, table) == 2
        # the index holds its own +1 on each indexed page
        assert alloc.refcount(grant[0]) == 2
        assert alloc.refcount(grant[1]) == 2
        assert alloc.refcount(grant[2]) == 1     # partial page: not indexed
        pages, matched = idx.match(prompt)
        assert pages == list(grant[:2]) and matched == 8
        # a shorter shared prompt matches its own aligned range only
        pages, matched = idx.match(list(range(7)))
        assert pages == [grant[0]] and matched == 4
        # divergent second chunk: only the first page matches
        pages, matched = idx.match([0, 1, 2, 3, 9, 9, 9, 9, 9])
        assert pages == [grant[0]] and matched == 4

    def test_match_never_covers_whole_prompt(self):
        alloc = PageAllocator(32)
        idx = PrefixIndex(alloc, 4)
        grant = alloc.alloc(2)
        idx.insert(list(range(8)), list(grant))
        # the exact-multiple prompt re-presented: only page 0 matches
        pages, matched = idx.match(list(range(8)))
        assert matched == 4 < 8

    def test_first_prefill_wins(self):
        alloc = PageAllocator(32)
        idx = PrefixIndex(alloc, 4)
        g1 = alloc.alloc(2)
        idx.insert(list(range(9)), list(g1))
        g2 = alloc.alloc(2)
        created = idx.insert(list(range(9)), list(g2))
        assert created == 0                  # chunks already present
        assert alloc.refcount(g2[0]) == 1    # second copy not retained
        pages, _ = idx.match(list(range(9)))
        assert pages == list(g1)

    def test_counters_commit_per_admission_not_per_probe(self):
        alloc = PageAllocator(32)
        idx = PrefixIndex(alloc, 4)
        idx.insert(list(range(9)), list(alloc.alloc(2)))
        for _ in range(5):                   # reserve-mode re-probes
            idx.match(list(range(9)))
        assert idx.lookups == 0 and idx.hits == 0
        idx.note_admission(2, 8)
        assert idx.lookups == 1 and idx.hits == 1
        assert idx.tokens_hit == 8 and idx.pages_shared == 2

    def test_reclaim_lru_leaf_only_and_refcount_gated(self):
        alloc = PageAllocator(32)
        idx = PrefixIndex(alloc, 4)
        grant = alloc.alloc(2)
        idx.insert(list(range(9)), list(grant))
        alloc.release([grant[0]])   # prefiller dropped the root page...
        # ...but still maps the LEAF: it is never a victim, and the
        # root is not a leaf — nothing is reclaimable
        assert idx.reclaim(2) == 0
        assert idx.entries == 2
        alloc.release([grant[1]])   # prefiller fully done
        # now the LEAF (page 1) goes first; the chain stays reachable
        assert idx.reclaim(1) == 1
        pages, matched = idx.match(list(range(9)))
        assert pages == [grant[0]] and matched == 4
        assert idx.reclaim(1) == 1
        assert idx.entries == 0
        assert alloc.available == alloc.capacity

    def test_flush_releases_everything(self):
        alloc = PageAllocator(32)
        idx = PrefixIndex(alloc, 4)
        held = alloc.alloc(2)
        idx.insert(list(range(9)), list(held))
        assert idx.flush() == 2
        assert idx.entries == 0
        # the requests' own holds survive the flush
        assert alloc.refcount(held[0]) == 1
        assert idx.match(list(range(9))) == ([], 0)


# ------------------------------------------------- COW on the cache


class TestCopyOnWrite:
    def test_cow_page_copies_content_and_swaps_holds(self, params):
        from horovod_tpu.serve import PagedKVCache

        cache = PagedKVCache(params, ServeConfig(page_size=8,
                                                 num_pages=9))
        (page,) = cache.allocator.alloc(1)
        cache.allocator.retain([page])      # a second holder appears
        k0 = np.asarray(cache.pages[0]["k"][page])
        new = cache.cow_page(page)
        assert new != page
        # bit-identical copy, old page still held by the other holder
        np.testing.assert_array_equal(
            np.asarray(cache.pages[0]["k"][new]), k0)
        assert cache.allocator.refcount(page) == 1
        assert cache.allocator.refcount(new) == 1
        cache.allocator.release([page])
        cache.allocator.release([new])

    def test_engine_cow_guard_unshares_a_sabotaged_page(self, params):
        """Force the backstop: retain a page the decode WILL write.
        The guard must copy it (cow_copies counts the slip) and the
        stream must stay bit-exact — a wrong token is the failure mode
        the guard exists to prevent."""
        prompt = _prompt(0, 11)
        eng = ServeEngine(params, _cfg())
        req = eng.submit(prompt, 6)
        eng.run(max_steps=4)                # prefill done, decoding
        assert req.generated
        ps = eng.config.page_size
        hot = int(req.page_table[req.next_pos // ps])
        eng.cache.allocator.retain([hot])   # simulate a stray share
        eng.run()
        assert req.state == "finished"
        assert eng.cow_copies >= 1
        assert req.output == _ref(params, prompt, 6)
        eng.cache.allocator.release([hot])  # our sabotage hold


# ------------------------------------------- engine hit exactness


class TestEngineHits:
    @pytest.mark.parametrize("admission", ["reserve", "lazy"])
    def test_hit_stream_bit_identical_to_cold_and_lm_decode(
            self, params, admission):
        sys_p = list(_prompt(1, 18))
        tails = [[3, 5, 9], [11, 2], [44, 1, 2, 3]]
        prompts = [np.asarray(sys_p + t, np.int32) for t in tails]
        cold_outs = []
        for cfg in (_cfg(admission=admission, prefix_caching=False),
                    _cfg(admission=admission)):
            eng = ServeEngine(params, cfg)
            outs = []
            for p in prompts:
                r = eng.submit(p, 6)
                eng.run()
                outs.append((r.output, r.prefix_hit_tokens))
            if not cfg.prefix_caching:
                cold_outs = outs
                continue
            stats = eng.prefix_stats()
            assert stats["hits"] == 2 and stats["lookups"] == 3
            assert stats["prefill_tokens_saved"] == 32   # 16 x 2
            assert stats["cow_copies"] == 0              # backstop idle
            assert outs[0][1] == 0                       # first is cold
            assert outs[1][1] == 16 and outs[2][1] == 16
            for (out, _), (cold, _), p in zip(outs, cold_outs, prompts):
                assert out == cold == _ref(params, p, 6)

    def test_admission_counts_only_missed_pages(self, params):
        """Reserve admission must charge need - hit pages: a request
        that fits ONLY thanks to its prefix hit is admitted."""
        sys_p = list(_prompt(2, 16))
        p1 = np.asarray(sys_p + [1, 2, 3], np.int32)
        # capacity 4: after r1 finishes, the index holds its 2 prefix
        # pages, leaving 2 free — a cold same-shape request needs 3
        # pages and would NOT fit, but the 2 hit pages make it fit.
        eng = ServeEngine(params, _cfg(num_pages=5))
        r1 = eng.submit(p1, 6)
        eng.run()
        assert r1.state == "finished"
        assert eng.prefix.entries == 2
        p2 = np.asarray(sys_p + [9, 8, 7], np.int32)
        need = eng.cache.pages_needed(len(p2), 6)
        free = eng.cache.allocator.available
        assert need > free                   # would NOT fit cold...
        r2 = eng.submit(p2, 6)
        eng.run()
        assert r2.state == "finished"        # ...but fits via the hit
        assert r2.prefix_hit_pages == 2
        assert r2.output == _ref(params, p2, 6)

    def test_update_params_flushes_the_index(self, params):
        eng = ServeEngine(params, _cfg())
        r = eng.submit(_prompt(3, 20), 4)
        eng.run()
        assert eng.prefix.entries > 0
        params2 = plm.init_lm_params(jax.random.PRNGKey(5), V, LMAX,
                                     LAYERS, H, DH, FFN)
        eng.update_params(params2)
        assert eng.prefix.entries == 0
        r2 = eng.submit(_prompt(3, 20), 4)   # same prompt, new weights
        eng.run()
        assert r2.prefix_hit_tokens == 0     # stale K/V never served
        assert r2.output == _ref(params2, _prompt(3, 20), 4)

    def test_prefix_survives_its_prefiller(self, params):
        """The index's own +1 keeps a prefix alive after the request
        that filled it released everything."""
        eng = ServeEngine(params, _cfg())
        p = _prompt(4, 20)
        r1 = eng.submit(p, 3)
        eng.run()
        assert r1.state == "finished" and r1.pages == []
        r2 = eng.submit(np.asarray(list(p) + [7], np.int32), 3)
        eng.run()
        assert r2.prefix_hit_tokens == 16

    def test_off_by_default_no_index_no_stats(self, params):
        eng = ServeEngine(params, ServeConfig(page_size=8, num_pages=40,
                                              decode_slots=2,
                                              prefill_chunk=4))
        assert eng.prefix is None
        assert eng.prefix_stats() is None
        assert "prefix" not in eng.stats()


# ------------------------------------------------- prefix routing


class _StubEngine:
    def __init__(self, free, occ, slots=2):
        self.config = ServeConfig(decode_slots=slots, page_size=8,
                                  num_pages=32)

        class _Cache:
            def occupancy(self_c):
                return occ

            def fits(self_c, lp, mn):
                return lp + mn <= 64

        self.cache = _Cache()
        self._free = free

    def _free_slots(self):
        return self._free


class _StubReplica:
    def __init__(self, rid, free=2, occ=0.0, state="healthy",
                 assigned=0):
        self.id = rid
        self.state = state
        self.engine = _StubEngine(free, occ)
        self.assigned = [object()] * assigned

    @property
    def healthy(self):
        return self.state == "healthy"


class TestPrefixRouting:
    def _req(self):
        from horovod_tpu.serve import Request

        return Request(prompt=np.arange(20, dtype=np.int32),
                       max_new_tokens=4)

    def test_route_key_beats_load(self):
        """Rendezvous rank is ordered FIRST: the prefix home wins even
        when another replica is less loaded."""
        reps = [_StubReplica(i) for i in range(4)]
        key = prefix_route_key(list(range(20)), 8)
        home = max(reps, key=lambda r: rendezvous_rank(key, r.id))
        for r in reps:                      # make every OTHER replica
            if r.id != home.id:             # look emptier
                r.engine._free = 2
        home.engine._free = 1
        assert pick_replica(reps, self._req(), key).id == home.id

    def test_no_key_routes_least_loaded(self):
        reps = [_StubReplica(0, free=0), _StubReplica(1, free=2)]
        assert pick_replica(reps, self._req(), None).id == 1

    def test_saturated_home_spills_to_next_ranked(self):
        """An ineligible home drops out and the next-ranked survivor
        takes the prefix — stateless failover, no table to migrate."""
        reps = [_StubReplica(i) for i in range(3)]
        key = prefix_route_key(list(range(20)), 8)
        order = sorted(reps, key=lambda r: -rendezvous_rank(key, r.id))
        order[0].state = "dead"
        assert pick_replica(reps, self._req(), key).id == order[1].id


# ------------------------------------- fleet-wide (inproc fast lane)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


def _fleet(params, clk, cfg, **kw):
    kw.setdefault("replicas", 2)
    kw.setdefault("backoff_base", 0.01)
    return ServeFleet(params, cfg, FleetConfig(**kw),
                      clock=clk, sleep=clk.sleep)


class TestFleetPrefix:
    def _drive(self, fl, clk):
        while not fl.idle:
            fl.step()
            clk.t += 0.001

    def test_prefix_mates_co_locate_one_cold_prefill(self, params):
        # 4 requests under the in-flight limit (decode_slots + 1 = 5):
        # nothing spills, every prefix-mate rendezvouses to ONE home
        clk = FakeClock()
        fl = _fleet(params, clk, _cfg(num_pages=64, decode_slots=4))
        sys_p = list(_prompt(5, 18))
        reqs = [fl.submit(np.asarray(sys_p + [50 + i], np.int32), 4)
                for i in range(4)]
        self._drive(fl, clk)
        homes = {r.replica for r in reqs}
        assert len(homes) == 1               # rendezvous co-location
        cold = [r for r in reqs if r.prefix_hit_tokens == 0]
        assert len(cold) == 1                # one cold prefill total
        pb = fl.stats()["fleet"]["prefix"]
        assert pb["hits"] == 3 and pb["requests"] == 4
        assert pb["prefill_tokens_saved"] == 3 * 16
        for i, r in enumerate(reqs):
            assert r.output == _ref(
                params, np.asarray(sys_p + [50 + i], np.int32), 4)

    def test_redispatch_lands_on_prefix_and_saves_recompute(
            self, params):
        """Satellite 3 (fast lane): kill the prefix home mid-decode —
        the drained requests rendezvous onto the survivor, whose index
        already holds their prefix (warmed by a same-prefix request
        that spilled there earlier), so the pessimistic drain-time
        ``tokens_recomputed`` is netted DOWN by the survivor's hits and
        every stream stays bit-identical to the fault-free run."""
        sys_p = list(_prompt(6, 18))
        prompts = [np.asarray(sys_p + [60 + i], np.int32)
                   for i in range(6)]
        refs = [_ref(params, p, 6) for p in prompts]

        def run(kill):
            clk = FakeClock()
            # decode_slots=2 -> in_flight_limit 3: the 4th+ submit
            # spills off the home, warming the survivor's index
            fl = _fleet(params, clk, _cfg(), max_restarts=2)
            reqs = [fl.submit(p, 6) for p in prompts]
            if kill:
                for _ in range(8):
                    fl.step()
                    clk.t += 0.001
                home = reqs[0].replica
                assert home is not None
                victims = [r for r in fl.replicas[home].assigned
                           if r.generated or r.prefill_pos]
                assert victims, "kill must catch in-flight work"
                fl.arm_fault_plan(f"kill:replica={home},at=0s")
            self._drive(fl, clk)
            return reqs, fl

        clean_reqs, _ = run(kill=False)
        reqs, fl = run(kill=True)
        f = fl.stats()["fleet"]
        assert f["incidents_by_class"] == {"crashed": 1}
        assert f["redispatched"] >= 1
        redispatched = [r for r in reqs if r.redispatches]
        # the pin: a redispatched request re-matched on the survivor
        assert any(r.prefix_hits_at_drain is not None
                   and r.prefix_hit_tokens > r.prefix_hits_at_drain
                   for r in redispatched), \
            "no redispatched request hit the survivor's prefix"
        pb = f["prefix"]
        assert pb["redispatch_tokens_saved"] > 0
        # tokens_recomputed is NET of the survivor's prefix hits:
        # strictly below the pessimistic drain-time total
        assert f["tokens_recomputed"] < f["tokens_recomputed_raw"]
        for r, ref, rc in zip(reqs, refs, clean_reqs):
            assert r.state == "finished"
            assert r.output == ref == rc.output

    def test_fleet_prefix_stats_absent_when_off(self, params):
        clk = FakeClock()
        fl = _fleet(params, clk, _cfg(prefix_caching=False))
        fl.submit(_prompt(7, 12), 3)
        self._drive(fl, clk)
        assert fl.stats()["fleet"]["prefix"] is None


# ------------------------------------------ over the wire (process)


class TestWireStubPrefix:
    def test_router_tolerates_prefix_keyless_workers(self):
        """A prefix-caching fleet over REAL worker processes that never
        stamp prefix keys (the protocol stub predates the prefix RPCs,
        exactly like a pre-PR-16 worker): routing still rendezvouses on
        the prefix key, the proxy mirror folds nothing (``_apply_prefix``
        absence tolerance), the fleet's router-side prefix block reports
        zero hits instead of crashing, and every stream is exact."""
        from tests.serve_stub_worker import expected_stream
        from tests.test_serve_worker import (SALT, STUB_PARAMS,
                                             _assert_reaped, _run_until,
                                             _stub_cmd)

        fl = ServeFleet(
            STUB_PARAMS,
            ServeConfig(page_size=8, num_pages=32, decode_slots=2,
                        prefill_chunk=4, prefix_caching=True),
            FleetConfig(replicas=2, transport="process",
                        backoff_base=0.01, rpc_deadline=10.0),
            worker_cmd=_stub_cmd())
        try:
            sys_p = list(range(3, 21))          # 18-token shared prefix
            prompts = [sys_p + [40 + i] for i in range(3)]
            reqs = [fl.submit(np.asarray(p, np.int32), 4)
                    for p in prompts]
            _run_until(fl, reqs)
            for p, r in zip(prompts, reqs):
                assert r.state == "finished"
                assert r.output == expected_stream(p, 4, SALT)
            # prefix-mates co-located by the route key (3 requests fit
            # under in_flight_limit = decode_slots + 1, so no spill) ...
            assert len({r.replica for r in reqs}) == 1
            # ... but the stub stamped nothing: router-side accounting
            # is present and honestly zero
            pb = fl.stats()["fleet"]["prefix"]
            assert pb is not None
            assert pb["requests"] == 3 and pb["hits"] == 0
            assert all(r.prefix_hit_tokens == 0 for r in reqs)
        finally:
            fl.close()
        _assert_reaped(fl)


@pytest.mark.slow
class TestRealWorkerPrefixE2E:
    """python -m horovod_tpu.serve.worker end to end (slow: each worker
    spawn pays the jax import + first-step compile)."""

    def test_kill_lands_on_prefix_warmed_survivor_bit_exact(
            self, params):
        """Satellite 3, real-worker edition: 6 prompts sharing an
        18-token prefix on a 2-replica process fleet; spill warms the
        survivor's index, then the rendezvous home is SIGKILLed
        mid-run. The redispatched requests re-match on the survivor
        over the wire (worker stamps counters per incarnation, proxy
        folds deltas), ``tokens_recomputed`` nets below the pessimistic
        drain-time count, and every greedy stream is bit-identical to
        ``lm_decode``."""
        import signal

        from tests.test_serve_worker import _assert_reaped

        sys_p = list(_prompt(8, 18))
        prompts = [np.asarray(sys_p + [60 + i], np.int32)
                   for i in range(6)]
        refs = [_ref(params, p, 10) for p in prompts]
        fl = ServeFleet(params, _cfg(num_pages=32),
                        FleetConfig(replicas=2, transport="process",
                                    backoff_base=0.01),
                        worker_env={"JAX_PLATFORMS": "cpu"})
        try:
            # pay compile on both replicas before the timed part; len-2
            # warm prompts have no aligned prefix, so no index pollution
            for _ in range(len(fl.replicas)):
                fl.submit(np.asarray([1, 2], np.int32), 2)
            fl.run()
            fl.reset_metrics()
            reqs = [fl.submit(p, 10) for p in prompts]
            for _ in range(4):
                fl.step()
            home = reqs[0].replica
            assert home is not None
            fl.arm_fault_plan(f"kill:replica={home},at=0s")
            fl.run()
            f = fl.stats()["fleet"]
            assert f["incidents_by_class"] == {"crashed": 1}
            assert f["incidents"][0]["code"] == -signal.SIGKILL
            assert f["redispatched"] >= 1
            redispatched = [r for r in reqs if r.redispatches]
            assert any(r.prefix_hits_at_drain is not None
                       and r.prefix_hit_tokens > r.prefix_hits_at_drain
                       for r in redispatched), \
                "no redispatched request hit the survivor's prefix"
            pb = f["prefix"]
            assert pb["redispatch_tokens_saved"] > 0
            assert f["tokens_recomputed"] < f["tokens_recomputed_raw"]
            for r, ref in zip(reqs, refs):
                assert r.state == "finished"
                assert r.output == ref
        finally:
            fl.close()
        _assert_reaped(fl)
