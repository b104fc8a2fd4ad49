"""Paged KV-cache subsystem: paged-kernel vs dense-ragged parity across
(pos, active, page_size) grids, allocator invariants (no double-free,
refcount balance, CoW isolation, full alloc/free round-trip), prefix-cache
semantics, and engine pool-exhaustion + drain.  Engine construction
helpers live in tests/conftest.py."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import make_engine, tiny_lm

from repro.configs import get_config
from repro.kernels.paged_attention import paged_decode_attention_tpu
from repro.kernels.ref import (decode_attention_ref, paged_decode_attention_ref,
                               paged_decode_attention_quant_ref)
from repro.models import LM, RuntimeKnobs
from repro.models.attention import (paged_cache_update,
                                    paged_decode_attention_xla)
from repro.runtime.kv_pool import (KV_PAGE_POLICIES, KVCacheManager,
                                   PagePool, PoolExhausted, PrefixCache,
                                   get_page_policy)
from repro.runtime.serve import Request, ServeConfig, ServeEngine
from repro.runtime.steps import pick_decode_splits

RNG = np.random.default_rng(11)


def arr(*s):
    return jnp.asarray(RNG.normal(size=s), jnp.float32)


# ----------------------------------------------------------- kernel parity
def _paged_case(b, kv, h, d, page_size, max_pages, *, extra_pages=3):
    """Random pools + a random page table with distinct live pages per
    slot (page 0 reserved as the null page)."""
    n_pages = 1 + b * max_pages + extra_pages
    kp = arr(n_pages, kv, page_size, d)
    vp = arr(n_pages, kv, page_size, d)
    perm = RNG.permutation(np.arange(1, n_pages))[:b * max_pages]
    pt = perm.reshape(b, max_pages).astype(np.int32)
    return kp, vp, pt


POS_CASES = [  # zero, page boundaries +-1, max-1, inactive slot at -1
    np.array([0, 15, 16, 63], np.int32),
    np.array([17, 31, 32, 62], np.int32),
    np.array([-1, 0, 47, 63], np.int32),
]


@pytest.mark.slow  # 108-case kernel-parity sweep: full-suite lane
@pytest.mark.parametrize("g", [1, 2, 4])
@pytest.mark.parametrize("window", [0, 8])
@pytest.mark.parametrize("page_size", [8, 16, 32])
@pytest.mark.parametrize("pos", POS_CASES)
def test_paged_kernel_matches_dense_ragged_ref(g, window, page_size, pos):
    """The paged kernel equals the DENSE ragged oracle on the gathered
    view — physical indirection must not change logical attention."""
    b, kv, d, s = 4, 2, 16, 64
    h = kv * g
    max_pages = s // page_size
    q = arr(b, h, 1, d)
    kp, vp, pt = _paged_case(b, kv, h, d, page_size, max_pages)
    # dense gather: slot b's logical cache is its pages back to back
    kd = jnp.asarray(kp)[pt].transpose(0, 2, 1, 3, 4).reshape(b, kv, s, d)
    vd = jnp.asarray(vp)[pt].transpose(0, 2, 1, 3, 4).reshape(b, kv, s, d)
    ref = decode_attention_ref(q, kd, vd, pos, window=window)
    out = paged_decode_attention_tpu(q, kp, vp, jnp.asarray(pt), pos,
                                     window=window, interpret=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-3


@pytest.mark.parametrize("page_size", [8, 32])
@pytest.mark.parametrize("pos", POS_CASES)
def test_paged_ref_and_kernel_agree(page_size, pos):
    b, kv, g, d, s = 4, 2, 2, 16, 64
    h = kv * g
    max_pages = s // page_size
    q = arr(b, h, 1, d)
    kp, vp, pt = _paged_case(b, kv, h, d, page_size, max_pages)
    ref = paged_decode_attention_ref(q, kp, vp, pt, pos)
    out = paged_decode_attention_tpu(q, kp, vp, jnp.asarray(pt), pos,
                                     interpret=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-3


def test_paged_kernel_scalar_pos_and_shared_pages():
    """Scalar pos broadcasts; two slots mapping the SAME physical page
    (prefix sharing) read identical K/V."""
    b, kv, g, d, ps, mp = 2, 2, 2, 16, 16, 2
    h = kv * g
    kp = arr(1 + 2 * mp, kv, ps, d)
    vp = arr(1 + 2 * mp, kv, ps, d)
    pt = np.array([[1, 2], [1, 3]], np.int32)  # page 1 shared
    q1 = arr(1, h, 1, d)
    q = jnp.concatenate([q1, q1], axis=0)
    out = paged_decode_attention_tpu(q, kp, vp, jnp.asarray(pt),
                                     jnp.int32(ps - 1), interpret=True)
    # positions < ps only touch the shared page: slots must agree exactly
    assert float(jnp.max(jnp.abs(out[0] - out[1]))) == 0.0


def test_paged_xla_matches_ref():
    b, kv, g, d, ps, s = 4, 2, 2, 16, 16, 64
    h = kv * g
    mp = s // ps
    q = arr(b, h, 1, d)
    kp, vp, pt = _paged_case(b, kv, h, d, ps, mp)
    pos = np.array([-1, 0, 31, 63], np.int32)
    ref = paged_decode_attention_ref(q, kp, vp, pt, pos, window=4)
    out = paged_decode_attention_xla(
        q.swapaxes(1, 2), kp.swapaxes(1, 2), vp.swapaxes(1, 2), pt, pos,
        window=4)
    assert float(jnp.max(jnp.abs(out.swapaxes(1, 2) - ref))) < 1e-5
    assert float(jnp.max(jnp.abs(out[0]))) == 0.0  # inactive slot zeroed


def _poison_unread_pages(pools, pt, pos, tq, page_size, window):
    """NaN in every page the kernel must not read: a slot's pages before
    its window or past its last query row, every page of an inactive
    slot, and the pages no slot maps."""
    read = set()
    for row, p in zip(pt, pos):
        if p < 0:
            continue
        first = max(0, p - window + 1) // page_size if window else 0
        read |= set(row[first:(p + tq - 1) // page_size + 1].tolist())
    unread = np.array(sorted(set(range(pools[0].shape[0])) - read))
    return [x.at[unread].set(jnp.nan) for x in pools]


@pytest.mark.parametrize("g", [1, 2])
@pytest.mark.parametrize("tq", [1, 4])
@pytest.mark.parametrize("window", [0, 8])
@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_paged_decode_reads_only_the_pages_a_slot_attends(g, tq, window,
                                                          dtype):
    """The per-slot kernel matches the oracle with NaN in every page past
    a slot's ``pos`` (or before its window) and in every page of an
    inactive slot, so nothing it must not read reaches the result.  Page
    tables are shuffled; slots sit at -1, 0, on and just past a page
    boundary, and at seven pages, which leave a last block of three under
    the kernel's four-page (128-key) blocks at ``page_size`` 32."""
    from repro.models.attention import quantize_kv

    b, kv, d, ps, mp = 6, 2, 16, 32, 8
    pos = np.array([-1, 0, 31, 32, 33, 204 - tq], np.int32)
    kp, vp, pt = _paged_case(b, kv, kv * g, d, ps, mp)
    q = arr(b, kv * g, tq, d)
    if dtype == "int8":
        (kq, ks), (vq, vs) = quantize_kv(kp, jnp.int8), quantize_kv(vp,
                                                                    jnp.int8)
        ref = paged_decode_attention_quant_ref(q, kq, vq, ks, vs, pt, pos,
                                               window=window)
        ks, vs = _poison_unread_pages([ks, vs], pt, pos, tq, ps, window)
        kw = dict(k_scale=ks, v_scale=vs)
        tol = 1e-3
    else:
        q, kq, vq = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
        ref = paged_decode_attention_ref(q.astype(jnp.float32), kq, vq, pt,
                                         pos, window=window)
        kq, vq = _poison_unread_pages([kq, vq], pt, pos, tq, ps, window)
        kw = {}
        tol = 3e-2  # bf16 output, p rounded to bf16 for the PV matmul
    out = paged_decode_attention_tpu(q, kq, vq, jnp.asarray(pt), pos,
                                     window=window, interpret=True, **kw)
    out = np.asarray(out.astype(jnp.float32))
    assert np.isfinite(out).all()
    assert np.abs(out - np.asarray(ref)).max() < tol
    assert (out[0] == 0).all()  # inactive slot writes zeros


def test_paged_cache_update_writes_mapped_page_and_null_for_inactive():
    kv, d, ps, n_pages = 2, 4, 8, 6
    kp = jnp.zeros((n_pages, ps, kv, d))
    vp = jnp.zeros((n_pages, ps, kv, d))
    k_new = arr(3, 1, kv, d)
    v_new = arr(3, 1, kv, d)
    pt = np.array([[1, 2], [3, 4], [0, 0]], np.int32)
    pos = np.array([3, 11, -1], np.int32)  # slot 2 inactive
    kp2, vp2 = paged_cache_update(kp, vp, k_new, v_new, pos, pt, ps)
    assert float(jnp.max(jnp.abs(kp2[1, 3] - k_new[0, 0]))) == 0.0
    assert float(jnp.max(jnp.abs(kp2[4, 3] - k_new[1, 0]))) == 0.0
    assert float(jnp.max(jnp.abs(vp2[4, 3] - v_new[1, 0]))) == 0.0
    # inactive write landed in the null page only; pages 1-5 untouched
    # elsewhere
    assert float(jnp.sum(jnp.abs(kp2[5]))) == 0.0
    assert float(jnp.sum(jnp.abs(kp2[2]))) == 0.0


# ----------------------------------------------------- allocator invariants
def test_pool_alloc_free_round_trip():
    pool = PagePool(17, 8, policy="pack", num_banks=4)
    cap = pool.capacity
    pages = pool.alloc(cap)  # drain completely
    assert sorted(pages) == list(range(1, 17))
    assert pool.available == 0
    with pytest.raises(PoolExhausted):
        pool.alloc(1)
    for p in pages:
        pool.decref(p)
    assert pool.available == cap
    # round-trip again: the free list regenerated cleanly
    again = pool.alloc(cap)
    assert sorted(again) == sorted(pages)


def test_pool_no_double_free_and_no_incref_of_free():
    pool = PagePool(9, 8)
    (p,) = pool.alloc(1)
    pool.incref(p)
    pool.decref(p)
    pool.decref(p)  # now free
    with pytest.raises(AssertionError):
        pool.decref(p)
    with pytest.raises(AssertionError):
        pool.incref(p)


def test_pool_null_page_is_never_allocated():
    pool = PagePool(5, 4)
    pages = pool.alloc(pool.capacity)
    assert 0 not in pages


def test_policy_pack_vs_spread_bank_placement():
    for name in ("pack", "spread"):
        assert KV_PAGE_POLICIES[name]().name == name
    pack = PagePool(33, 8, policy="pack", num_banks=4)
    spread = PagePool(33, 8, policy="spread", num_banks=4)
    n = 4
    assert pack.banks_touched(pack.alloc(n)) == 1
    assert spread.banks_touched(spread.alloc(n)) == 4
    with pytest.raises(KeyError):
        get_page_policy("nope")


def test_policy_pack_prefers_partially_used_banks():
    pool = PagePool(33, 8, policy="pack", num_banks=4)
    first = pool.alloc(3)
    second = pool.alloc(2)  # should stay in the same bank (still has room)
    assert pool.banks_touched(first + second) == 1


def _random_pool_workload(policy, seed):
    """Randomized alloc/incref/decref storm; refcounts must balance and
    the free list must exactly complement live pages at every step."""
    rng = np.random.default_rng(seed)
    pool = PagePool(41, 8, policy=policy, num_banks=5)
    live = {}  # page -> refcount we believe it has
    for _ in range(300):
        op = rng.integers(0, 3)
        if op == 0 and pool.available:
            n = int(rng.integers(1, pool.available + 1))
            for p in pool.alloc(n):
                assert p not in live
                live[p] = 1
        elif op == 1 and live:
            p = int(rng.choice(list(live)))
            pool.incref(p)
            live[p] += 1
        elif live:
            p = int(rng.choice(list(live)))
            pool.decref(p)
            live[p] -= 1
            if not live[p]:
                del live[p]
        assert pool.in_use == len(live)
        for p, r in live.items():
            assert pool.ref[p] == r
    for p in sorted(live):
        for _ in range(live[p]):
            pool.decref(p)
    assert pool.available == pool.capacity


@pytest.mark.parametrize("policy", ["pack", "spread"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pool_refcounts_balance_random_workload(policy, seed):
    _random_pool_workload(policy, seed)


# ------------------------------------------------- prefix cache + manager
def test_prefix_cache_lookup_insert_evict():
    pool = PagePool(9, 4)
    cache = PrefixCache(pool)
    prompt = np.arange(10, dtype=np.int32)  # 2 full pages + 2 tokens
    pages = pool.alloc(3)
    cache.insert(prompt, pages)  # only the 2 full pages are registered
    assert len(cache) == 2
    hit, matched = cache.lookup(prompt)
    assert hit == pages[:2] and matched == 8
    for p in hit:
        pool.decref(p)
    # different second page -> only the first page hits
    other = prompt.copy()
    other[5] += 1
    hit2, matched2 = cache.lookup(other)
    assert hit2 == pages[:1] and matched2 == 4
    pool.decref(hit2[0])
    # release the owner's refs: pages become cache-only and evictable
    for p in pages:
        pool.decref(p)
    freed = cache.evict(2)
    assert freed == 2 and len(cache) == 0


def test_cow_isolation():
    """CoW: writes through one slot's table must not reach the sharing
    slot's page — the allocator gives the writer a private copy."""
    m = KVCacheManager(slots=2, max_len=32, page_size=8, num_pages=12)
    prompt = np.arange(16, dtype=np.int32)  # exactly 2 pages -> full hit
    r0 = m.admit(0, prompt, max_new=4)
    assert r0.matched == 0 and r0.start == 0 and not r0.cow
    m.register_prefix(0, prompt)
    r1 = m.admit(1, prompt, max_new=4)
    assert r1.matched == 16  # full-prompt hit
    assert r1.start == 8  # re-runs the last page to recover logits
    assert len(r1.cow) == 1
    src, dst = r1.cow[0]
    # the shared page stays mapped in slot 0, the copy in slot 1
    assert m.page_table[0, 1] == src
    assert m.page_table[1, 1] == dst
    assert src != dst
    # slot 0's first page is genuinely shared (owner + slot1 + cache)
    shared = m.page_table[0, 0]
    assert m.page_table[1, 0] == shared
    assert m.pool.ref[shared] == 3
    m.free_slot(1)
    assert m.pool.ref[shared] == 2  # slot 0 + prefix cache


def test_manager_backpressure_and_rollback():
    m = KVCacheManager(slots=2, max_len=32, page_size=8, num_pages=5,
                       prefix_cache=False)
    r0 = m.admit(0, np.arange(9, dtype=np.int32), max_new=8)  # 3 pages
    assert r0 is not None
    assert m.admit(1, np.arange(9, dtype=np.int32), max_new=8) is None
    assert m.pool.in_use == 3  # failed admission rolled back cleanly
    m.free_slot(0)
    assert m.pool.in_use == 0
    assert m.admit(1, np.arange(9, dtype=np.int32), max_new=8) is not None


def test_manager_eviction_under_pressure():
    """Cache-only pages are evicted to satisfy a new admission."""
    m = KVCacheManager(slots=2, max_len=32, page_size=8, num_pages=6)
    prompt = np.arange(16, dtype=np.int32)
    m.admit(0, prompt, max_new=1)  # 3 pages (17 positions)
    m.register_prefix(0, prompt)
    m.free_slot(0)  # 2 pages survive, held by the prefix cache only
    assert m.pool.in_use == 2
    other = 100 + np.arange(17, dtype=np.int32)
    res = m.admit(1, other, max_new=16)  # needs 5 pages -> must evict
    assert res is not None
    assert m.pool.in_use == 5


def test_prefix_hits_count_once_per_admission_not_per_refusal():
    """An admission refused for lack of pages is looked up again on every
    retry; its prefix hits count once, when it is admitted."""
    m = KVCacheManager(slots=3, max_len=32, page_size=8, num_pages=8)
    prompt = np.arange(16, dtype=np.int32)  # 2 full pages
    m.admit(0, prompt, max_new=8)  # 3 pages
    m.register_prefix(0, prompt)
    m.admit(1, 100 + np.arange(20, dtype=np.int32), max_new=12)  # 4 pages
    before = m.stats()  # two cold admissions: 0 hits, 2 misses
    assert (before["prefix_hits"], before["prefix_misses"]) == (0, 2)
    longer = np.arange(24, dtype=np.int32)  # the 2 cached pages + 1 more
    for _ in range(3):  # 4 pages needed, 0 free: refused, pages held
        assert m.admit(2, longer, max_new=8) is None
    assert m.stats() == before
    m.free_slot(1)
    res = m.admit(2, longer, max_new=8)
    assert res is not None and res.matched == 16
    after = m.stats()
    assert after["prefix_hits"] == 2  # pages, as before
    assert after["prefix_misses"] == 3  # its third page was not cached


def _manager_admit_free_round_trip(seed, page_size, n_reqs):
    """Admissions and frees in random order: refcounts balance, the table
    maps exactly the held pages, and a drained manager leaves only
    prefix-cache refs behind."""
    rng = np.random.default_rng(seed)
    m = KVCacheManager(slots=4, max_len=32, page_size=page_size,
                       num_pages=4 * (32 // page_size) + 1)
    live = []
    for _ in range(n_reqs):
        free = [s for s in range(4) if s not in live]
        if free and (not live or rng.integers(0, 2)):
            s = int(rng.choice(free))
            plen = int(rng.integers(1, 16))
            res = m.admit(s, rng.integers(0, 8, size=plen).astype(np.int32),
                          max_new=int(rng.integers(1, 8)))
            if res is not None:
                live.append(s)
                assert all(m.page_table[s, i] > 0
                           for i in range(len(res.blocks)))
        elif live:
            m.free_slot(live.pop(int(rng.integers(0, len(live)))))
    for s in list(live):
        m.free_slot(s)
    # only prefix-cache refs remain
    assert m.pool.in_use == sum(1 for p in range(1, m.pool.num_pages)
                                if m.pool.ref[p] == 1)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_manager_admit_free_round_trip(seed):
    _manager_admit_free_round_trip(seed, page_size=8, n_reqs=8)


# Hypothesis variants of the allocator properties (skipped when the
# dependency is absent — the numpy-RNG versions above still run).
try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    pass
else:
    @pytest.mark.slow
    @settings(max_examples=30, deadline=None)
    @given(policy=st.sampled_from(["pack", "spread"]),
           seed=st.integers(0, 10_000))
    def test_pool_invariants_hypothesis(policy, seed):
        _random_pool_workload(policy, seed)

    @pytest.mark.slow
    @settings(max_examples=30, deadline=None)
    @given(seed=st.integers(0, 10_000), page_size=st.sampled_from([4, 8]),
           n_reqs=st.integers(1, 8))
    def test_manager_admit_free_round_trip_hypothesis(seed, page_size,
                                                      n_reqs):
        _manager_admit_free_round_trip(seed, page_size, n_reqs)


# ------------------------------------------------------------ engine level
def _shared_prefix_trace(n, shared_len, seed=5):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, 64, size=shared_len).astype(np.int32)
    reqs = []
    for i in range(n):
        tail = rng.integers(0, 64, size=int(rng.integers(1, 5))) \
            .astype(np.int32)
        prompt = np.concatenate([shared, tail]) if i % 2 else tail
        reqs.append(Request(i, prompt, max_new_tokens=4))
    return reqs


@pytest.mark.slow  # engine-equality suite: full-suite lane
def test_paged_engine_matches_dense_outputs():
    """Greedy outputs are layout-invariant: the paged engine (prefix
    cache on) reproduces the dense continuous engine token for token."""
    model, params = tiny_lm()
    outs = {}
    for cache in ("dense", "paged"):
        eng = ServeEngine(model, params,
                          ServeConfig(batch_slots=2, max_len=32,
                                      cache=cache, page_size=8))
        for r in _shared_prefix_trace(7, shared_len=9):
            eng.submit(Request(r.req_id, r.prompt.copy(),
                               max_new_tokens=r.max_new_tokens))
        done = eng.run()
        assert len(done) == 7
        outs[cache] = {r.req_id: r.output for r in done}
    assert outs["dense"] == outs["paged"]


def test_paged_engine_pool_exhaustion_backpressure_and_drain():
    """Regression: a pool far smaller than slots * max_len serves the
    whole queue — admission backpressures instead of step() raising, and
    freed pages admit the stragglers."""
    model, params = tiny_lm()
    # 8 usable pages of 8 = 64 positions, vs 2 slots * max_len 32 = 64
    # dense positions, but requests need 3 pages each -> at most 2 live;
    # queue depth forces multiple backpressure/drain cycles
    eng = ServeEngine(model, params,
                      ServeConfig(batch_slots=2, max_len=32, cache="paged",
                                  page_size=8, num_pages=9,
                                  prefix_cache=False))
    rng = np.random.default_rng(0)
    for i in range(6):
        eng.submit(Request(i, rng.integers(0, 64, size=12)
                           .astype(np.int32), max_new_tokens=6))
    done = eng.run()
    assert len(done) == 6
    assert all(len(r.output) == 6 for r in done)
    assert eng.kv.pool.in_use == 0  # every page returned on drain


def test_paged_engine_rejects_impossible_request_at_submit():
    model, params = tiny_lm()
    eng = ServeEngine(model, params,
                      ServeConfig(batch_slots=1, max_len=32, cache="paged",
                                  page_size=8, num_pages=3))
    with pytest.raises(ValueError):
        eng.submit(Request(0, np.zeros(20, np.int32), max_new_tokens=8))


def test_paged_engine_requires_continuous_attention():
    model, params = tiny_lm()
    with pytest.raises(ValueError):
        ServeEngine(model, params,
                    ServeConfig(batch_slots=1, max_len=32, mode="wave",
                                cache="paged"))
    ssm_cfg = dataclasses.replace(get_config("mamba2-1.3b", smoke=True),
                                  vocab_size=64)
    ssm = LM(ssm_cfg, RuntimeKnobs(cache_dtype=jnp.float32))
    with pytest.raises(ValueError):
        ServeEngine(ssm, ssm.init(jax.random.PRNGKey(0)),
                    ServeConfig(batch_slots=1, max_len=32, cache="paged"))


def test_prefix_cache_skips_prefill_work():
    """Requests repeating a cached prompt admit at the last chunk: the
    engine's prefix stats show hits and the matched length."""
    model, params = tiny_lm()
    eng = ServeEngine(model, params,
                      ServeConfig(batch_slots=1, max_len=32, cache="paged",
                                  page_size=8, prefill_chunk=8))
    prompt = np.arange(16, dtype=np.int32)
    eng.submit(Request(0, prompt, max_new_tokens=2))
    eng.run()
    assert eng.kv.stats()["prefix_entries"] == 2
    res = eng.kv.admit(0, prompt, max_new=2)
    assert res is not None and res.matched == 16 and res.start == 8
    eng.kv.free_slot(0)


def test_copy_cache_pages_duplicates_page_in_every_layer_pool():
    """LM.copy_cache_pages (the device half of CoW for callers without
    the full-rewrite invariant) copies src -> dst in each stacked pool."""
    model, _ = tiny_lm()
    caches = model.init_cache_paged(num_pages=5, page_size=8)
    leaf = caches["stack"]["k"]
    caches["stack"]["k"] = leaf.at[:, 2].set(7.0)
    out = jax.jit(model.copy_cache_pages)(caches, jnp.int32(2), jnp.int32(4))
    got = out["stack"]["k"]
    assert float(jnp.min(got[:, 4])) == 7.0  # every layer's page copied
    assert float(jnp.max(jnp.abs(got[:, 3]))) == 0.0  # others untouched


# ------------------------------------------------------- split-K autotune
def test_pick_decode_splits_heuristic():
    # short contexts stay single-stream
    assert pick_decode_splits(100, 1, max_len=1 << 15) == 1
    assert pick_decode_splits(2047, 1, max_len=1 << 15) == 1
    # long context, single slot: fan out
    assert pick_decode_splits(32_000, 1, max_len=1 << 15) == 8
    # wide batch already saturates the memory streams
    assert pick_decode_splits(32_000, 32, max_len=1 << 15) == 1
    assert pick_decode_splits(32_000, 8, max_len=1 << 15) == 4
    # splits must divide max_len
    assert (1 << 15) % pick_decode_splits(32_000, 1, max_len=1 << 15) == 0
    assert pick_decode_splits(32_000, 1, max_len=12_000) in (1, 2, 4, 8)
    # static knob overrides
    assert pick_decode_splits(32_000, 1, max_len=1 << 15, override=2) == 2
    assert pick_decode_splits(10, 64, max_len=1 << 15, override=4) == 4


def test_autotune_enabled_only_for_dense_pallas_auto():
    eng = make_engine(batch_slots=1, max_len=32)  # use_pallas=False
    assert not eng._autotune  # XLA path: nothing to tune
    # fan-out 1 resolves to the engine's base steps (no split-K rebuild)
    assert eng._step_for_splits(1, False) is eng._step
    assert eng._step_for_splits(1, True) is eng._step_sampled


@pytest.mark.parametrize("max_len,page_size", [
    (64, 8), (64, 16), (96, 16), (96, 32), (128, 16), (1 << 15, 32),
    (12_288, 16), (2048, 2048)])
def test_pick_decode_splits_divides_page_count(max_len, page_size):
    """Bugfix regression: the paged kernel tiles by whole pages, so the
    chosen fan-out must divide max_pages = max_len // page_size —
    dividing max_len alone is not enough (96/16 = 6 pages: 4 divides 96
    but not 6)."""
    max_pages = max_len // page_size
    for max_pos, batch in ((100, 1), (3000, 1), (32_000, 1), (32_000, 8),
                           (1 << 20, 2)):
        s = pick_decode_splits(max_pos, batch, max_len=max_len,
                               page_size=page_size)
        assert max_pages % s == 0, (max_pos, batch, s)
    for override in (2, 3, 4, 5, 8):
        s = pick_decode_splits(32_000, 1, max_len=max_len,
                               page_size=page_size, override=override)
        assert max_pages % s == 0 and 1 <= s <= override


def test_pick_decode_splits_paged_vs_dense_divisor():
    # the motivating misalignment: old logic picked 4 here (4 | 96)
    assert pick_decode_splits(32_000, 1, max_len=96, page_size=16) == 2
    # dense behaviour unchanged by the new keyword's default
    assert pick_decode_splits(32_000, 1, max_len=96) == \
        pick_decode_splits(32_000, 1, max_len=96, page_size=0)
    # a misaligned static override is clamped down to a divisor
    assert pick_decode_splits(10, 1, max_len=96, page_size=16,
                              override=4) == 3


# ----------------------------------------------- host-aligned pool sizing
def test_pool_rounds_up_num_pages_to_host_multiple():
    """Satellite regression: an unaligned num_pages is rounded UP (with
    a warning) instead of raising — capacity never silently shrinks and
    the host sub-pools stay equal."""
    with pytest.warns(RuntimeWarning, match="rounding up"):
        pool = PagePool(10, 8, num_hosts=4)
    assert pool.num_pages == 12
    assert pool.capacity == 11
    assert sum(pool.free_by_host()) == pool.available
    assert [pool.host_of(p) for p in (0, 2, 3, 11)] == [0, 0, 1, 3]
    # aligned pools stay warning-free
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        assert PagePool(12, 8, num_hosts=4).num_pages == 12
    with pytest.warns(RuntimeWarning):
        m = KVCacheManager(slots=2, max_len=32, page_size=8, num_pages=9,
                           num_hosts=2)
    assert m.pool.num_pages == 10
    # the manager still admits/frees cleanly over the rounded pool
    assert m.admit(0, np.arange(9, dtype=np.int32), max_new=4) is not None
    m.free_slot(0)
    assert m.pool.in_use == 0


# -------------------------------------------- buffered prefill / split-K
def _chunked_prefill(step, model, prompt, pt, c, *, buffered):
    """Drive a compiled paged chunked-prefill step over one slot's
    prompt; returns the per-chunk next-token arrays and final caches."""
    caches = model.init_cache_paged(num_pages=1 + pt.shape[1], page_size=8)
    buf = model.init_cache(1, 32)
    outs = []
    for ci in range(len(prompt) // c):
        chunk = jnp.asarray(prompt[None, ci * c:(ci + 1) * c])
        args = (model.init(jax.random.PRNGKey(0)), caches, chunk,
                jnp.int32(0), jnp.int32(ci * c), jnp.asarray(pt))
        if buffered:
            nxt, caches, buf = step(*args, buf)
        else:
            nxt, caches = step(*args)
        outs.append(np.asarray(nxt))
    return outs, caches


def test_buffered_prefill_matches_legacy_gather_step():
    """The buffered XLA chunked-prefill step (reusing the dense slot
    view across chunks) is bitwise-identical to the legacy per-chunk
    full-gather step — the retained parity oracle."""
    from repro.runtime.steps import compiled_step

    model, params = tiny_lm()
    rng = np.random.default_rng(4)
    prompt = rng.integers(1, 60, size=24).astype(np.int32)
    pt = np.array([[1, 2, 3, 4]], np.int32)
    legacy = compiled_step(model, "paged_prefill_chunk", page_size=8)
    buf_step = compiled_step(model, "paged_prefill_chunk_buf", page_size=8)
    ref_outs, ref_caches = _chunked_prefill(legacy, model, prompt, pt, 8,
                                            buffered=False)
    got_outs, got_caches = _chunked_prefill(buf_step, model, prompt, pt, 8,
                                            buffered=True)
    assert all((a == b).all() for a, b in zip(got_outs, ref_outs))
    for a, b in zip(jax.tree.leaves(got_caches),
                    jax.tree.leaves(ref_caches)):
        assert (np.asarray(a) == np.asarray(b)).all()


@pytest.mark.slow  # engine-equality suite: full-suite lane
def test_paged_pallas_engine_matches_xla_bitwise():
    """Fused Pallas paged prefill + decode vs the XLA buffered path:
    identical token streams (greedy and seeded-sampled), including
    prefix-cache hits (the gather-variant first chunk)."""
    from repro.runtime.sampling import SamplingParams

    model, params = tiny_lm()
    pallas = LM(model.cfg, model.knobs.with_(use_pallas=True))
    for sampled in (False, True):
        outs = {}
        for name, m in (("xla", model), ("pallas", pallas)):
            eng = ServeEngine(m, params,
                              ServeConfig(batch_slots=2, max_len=64,
                                          cache="paged", page_size=8,
                                          prefill_chunk=16))
            for r in _shared_prefix_trace(7, shared_len=17):
                sp = (SamplingParams(temperature=0.7, top_k=16, seed=3)
                      if sampled and r.req_id % 2 else SamplingParams())
                eng.submit(Request(r.req_id, r.prompt.copy(),
                                   max_new_tokens=6, sampling=sp))
            outs[name] = {r.req_id: r.output for r in eng.run()}
        assert outs["pallas"] == outs["xla"], f"sampled={sampled}"


@pytest.mark.parametrize("kind, scopes", [
    ("paged_serve", {"layer_scan", "attention", "kv_write", "kv_layout",
                     "mlp", "unembed", "sample"}),
    ("paged_prefill_chunk", {"layer_scan", "attention", "kv_write",
                             "kv_layout", "mlp", "unembed", "sample"})])
def test_paged_steps_name_their_parts(kind, scopes):
    """The compiled paged steps (Pallas path) carry the named scopes in
    their ops' ``op_name`` metadata."""
    import re

    from repro.runtime.steps import compiled_step

    model, params = tiny_lm()
    model = LM(model.cfg, model.knobs.with_(use_pallas=True))
    caches = model.init_cache_paged(9, 8)
    table = jnp.zeros((2, 8), jnp.int32)
    i32 = jnp.int32
    args = ((params, caches, jnp.zeros((2, 1), i32), jnp.zeros(2, i32),
             table) if kind == "paged_serve" else
            (params, caches, jnp.zeros((1, 16), i32), i32(0), i32(0), table))
    text = compiled_step(model, kind, page_size=8).lower(
        *args).compile().as_text()
    found = {part for path in re.findall(r'op_name="([^"]*)"', text)
             for part in path.split("/")}
    assert scopes <= found, scopes - found


def test_engine_gives_the_hlo_of_the_steps_it_ran():
    """``step_hlo_texts`` compiles again, from the shapes of its first
    call, each step the engine ran: the program a call with the engine's
    own arrays compiles."""
    import re

    model, params = tiny_lm()
    eng = ServeEngine(model, params, ServeConfig(
        batch_slots=2, max_len=32, cache="paged", page_size=8))
    assert eng.step_hlo_texts() == []
    eng.submit(Request(0, np.arange(1, 12, dtype=np.int32),
                       max_new_tokens=3))
    eng.run()
    texts = eng.step_hlo_texts()
    assert {re.search(r"^HloModule (\w+)", t, re.M).group(1)
            for t in texts} == {"jit_prefill_chunk_step", "jit_serve_step"}
    decode = eng._step.lower(
        eng.params, eng.caches, jnp.asarray(eng.tokens),
        jnp.asarray(eng.pos), jnp.asarray(eng.kv.page_table))
    assert decode.compile().as_text() in texts


@pytest.mark.slow
def test_paged_splitk_engine_matches_single_split():
    """Acceptance gate: the paged split-K decode variant emits the same
    tokens as the single-split kernel (max_len 64 / page 16 -> 4 pages,
    fan-out 4 = one page per split)."""
    model, params = tiny_lm()
    one = LM(model.cfg, model.knobs.with_(use_pallas=True))
    split = LM(model.cfg, model.knobs.with_(use_pallas=True,
                                            decode_splits=4))
    outs = {}
    for name, m in (("one", one), ("split", split)):
        eng = ServeEngine(m, params,
                          ServeConfig(batch_slots=2, max_len=64,
                                      cache="paged", page_size=16,
                                      prefill_chunk=16))
        for r in _shared_prefix_trace(5, shared_len=21, seed=8):
            eng.submit(Request(r.req_id, r.prompt.copy(),
                               max_new_tokens=8))
        outs[name] = {r.req_id: r.output for r in eng.run()}
    assert outs["split"] == outs["one"]
