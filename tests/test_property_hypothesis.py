"""Hypothesis property tests on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="install requirements-dev.txt for the property-test lane")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import block_table as BT
from repro.core.kv_page_manager import KVPageManager
from repro.kernels import ref
from repro.sim.simulator import lru_access

SET = settings(max_examples=25, deadline=None)


# ---------------------------------------------------------------------------
# LRU lookup: inclusion & capacity invariants
# ---------------------------------------------------------------------------
def _table(sets_, ways):
    return {"tags": jnp.zeros((sets_, ways), jnp.int32),
            "lru": jnp.zeros((sets_, ways), jnp.int32)}


@SET
@given(keys=st.lists(st.integers(0, 63), min_size=1, max_size=60),
       sets_=st.sampled_from([1, 2, 4]), ways=st.sampled_from([1, 2, 4]))
def test_cache_hit_implies_previously_inserted(keys, sets_, ways):
    state = _table(sets_, ways)
    seen = set()
    t = jnp.asarray(True)
    for i, k in enumerate(keys):
        state, hit = lru_access(state, sets_, jnp.asarray(k, jnp.int32), t,
                                jnp.asarray(i + 1, jnp.int32))
        if bool(hit):
            assert k in seen
        seen.add(k)


@SET
@given(keys=st.lists(st.integers(0, 1000), min_size=1, max_size=50))
def test_cache_never_exceeds_capacity(keys):
    sets_, ways = 2, 2
    state = _table(sets_, ways)
    t = jnp.asarray(True)
    for i, k in enumerate(keys):
        state, _ = lru_access(state, sets_, jnp.asarray(k, jnp.int32), t,
                              jnp.asarray(i + 1, jnp.int32))
    assert int((state["tags"] > 0).sum()) <= sets_ * ways


# ---------------------------------------------------------------------------
# block tables: flat <-> radix isomorphism for arbitrary mappings
# ---------------------------------------------------------------------------
@SET
@given(data=st.data(),
       b=st.integers(1, 4), maxp=st.sampled_from([4, 8, 16]),
       leaf=st.sampled_from([2, 4]))
def test_radix_flat_isomorphism(data, b, maxp, leaf):
    rng_seed = data.draw(st.integers(0, 2**16))
    rng = np.random.default_rng(rng_seed)
    flat = np.full((b, maxp), -1, np.int32)
    for i in range(b):
        n = rng.integers(0, maxp + 1)
        flat[i, :n] = rng.choice(10_000, n, replace=False)
    flat_j = jnp.asarray(flat)
    radix = BT.radix_from_flat(flat_j, leaf_size=leaf)
    merged = np.asarray(BT.flatten_radix(radix))
    assert (merged == flat).all()


# ---------------------------------------------------------------------------
# paged attention: physical placement invariance (THE NDPage invariant)
# ---------------------------------------------------------------------------
@SET
@given(seed=st.integers(0, 2**16), page=st.sampled_from([4, 8]),
       maxp=st.sampled_from([2, 4]))
def test_paged_attention_placement_invariance(seed, page, maxp):
    b, h, kh, d = 2, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(seed % 1000), 3)
    n = b * maxp + 1
    q = jax.random.normal(ks[0], (b, 1, h, d))
    kp = jax.random.normal(ks[1], (n, page, kh, d))
    vp = jax.random.normal(ks[2], (n, page, kh, d))
    rng = np.random.default_rng(seed)
    tab = np.full((b, maxp), -1, np.int32)
    lens = np.zeros((b,), np.int32)
    pool = list(rng.permutation(n))
    for i in range(b):
        lens[i] = rng.integers(1, maxp * page + 1)
        used = -(-int(lens[i]) // page)
        tab[i, :used] = [pool.pop() for _ in range(used)]
    out1 = ref.paged_attention_ref(q, kp, vp, jnp.asarray(tab),
                                   jnp.asarray(lens))
    perm = rng.permutation(n)
    inv = np.argsort(perm)
    tab2 = np.where(tab >= 0, inv[np.maximum(tab, 0)], -1).astype(np.int32)
    out2 = ref.paged_attention_ref(q, kp[perm], vp[perm],
                                   jnp.asarray(tab2), jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-5,
                               atol=1e-6)


# ---------------------------------------------------------------------------
# allocator: pages are never shared between live sequences
# ---------------------------------------------------------------------------
@SET
@given(ops=st.lists(st.tuples(st.sampled_from(["add", "append", "free"]),
                              st.integers(0, 3)), min_size=1, max_size=40))
def test_allocator_no_aliasing(ops):
    kvm = KVPageManager(num_pages=128, page_size=4, max_seqs=4, max_len=64)
    live = set()
    for op, sid in ops:
        try:
            if op == "add" and sid not in live:
                kvm.add_sequence(sid, prompt_len=3)
                live.add(sid)
            elif op == "append" and sid in live:
                kvm.append_token(sid)
            elif op == "free" and sid in live:
                kvm.free_sequence(sid)
                live.remove(sid)
        except MemoryError:
            pass
        allocated = [p for s in live for p in kvm.pages[s]]
        assert len(allocated) == len(set(allocated))


# ---------------------------------------------------------------------------
# online softmax (blockwise) == full softmax for arbitrary chunking
# ---------------------------------------------------------------------------
@SET
@given(seed=st.integers(0, 1000), chunks=st.sampled_from([16, 32, 64]))
def test_online_softmax_chunking_invariance(seed, chunks):
    from repro.models.attention import blockwise_attention
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 16))
    k = jax.random.normal(ks[1], (1, 64, 2, 16))
    v = jax.random.normal(ks[2], (1, 64, 2, 16))
    want = ref.flash_attention_ref(q, k, v, causal=True)
    got = blockwise_attention(q, k, v, causal=True, q_chunk=chunks,
                              kv_chunk=chunks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# design-space search: Pareto frontier law over arbitrary objectives
# ---------------------------------------------------------------------------
@SET
@given(vals=st.lists(st.tuples(st.floats(0, 1, allow_nan=False),
                               st.floats(0, 1, allow_nan=False),
                               st.floats(0, 1, allow_nan=False)),
                     min_size=1, max_size=25))
def test_pareto_frontier_membership_iff_nondominated(vals):
    """pareto_indices returns EXACTLY the non-dominated vectors: every
    member is undominated, every non-member has a dominator."""
    from repro.sim._search import OBJECTIVES, dominates, pareto_indices
    names = [n for n, _ in OBJECTIVES]
    vecs = [dict(zip(names, row)) for row in vals]
    front = set(pareto_indices(vecs))
    assert front
    for i, v in enumerate(vecs):
        dominated = any(dominates(w, v)
                        for j, w in enumerate(vecs) if j != i)
        assert (i in front) == (not dominated)


# ---------------------------------------------------------------------------
# zoo translation structures: range-table binary search & inverted hash
# ---------------------------------------------------------------------------
@SET
@given(spans=st.lists(st.tuples(st.integers(1, 50),      # range length
                                st.integers(0, 30)),     # gap after it
                      min_size=1, max_size=20),
       targets=st.lists(st.integers(0, 10**6), min_size=20,
                        max_size=20),
       probes=st.lists(st.integers(-10, 2000), min_size=1,
                       max_size=40),
       base=st.integers(0, 1000))
def test_range_table_binary_search_matches_linear_oracle(
        spans, targets, probes, base):
    """The searchsorted lookup (the production range-walk shape) and
    the O(ranges) linear scan agree on EVERY address — inside a range,
    in a gap, before the first, after the last."""
    from repro.core.page_table import (range_table_lookup,
                                       range_table_lookup_linear)
    starts, lengths = [], []
    pos = base
    for length, gap in spans:
        starts.append(pos)
        lengths.append(length)
        pos += length + gap + 1        # +1 keeps ranges non-overlapping
    starts = np.asarray(starts)
    lengths = np.asarray(lengths)
    tgt = np.asarray(targets[:len(starts)])
    addrs = np.asarray(probes) + base
    fast = range_table_lookup(starts, lengths, tgt, addrs)
    slow = range_table_lookup_linear(starts, lengths, tgt, addrs)
    np.testing.assert_array_equal(fast, slow)


@SET
@given(vpns=st.lists(st.integers(0, 2**31 - 1), min_size=1,
                     max_size=120, unique=True),
       log2_slots=st.sampled_from([7, 8, 10]))
def test_inverted_table_never_aliases_silently(vpns, log2_slots):
    """Open-addressed insert invariants: no two live vpns ever share a
    slot, and a vpn pays extra probes IFF its home slot was taken —
    collisions are never free and never silent."""
    from repro.core.page_table import _hash_np, inverted_table_insert
    vpns = np.asarray(vpns, np.int64)
    slots, probes = inverted_table_insert(vpns, log2_slots=log2_slots)
    assert len(np.unique(slots)) == len(slots)          # no aliasing
    homes = _hash_np(vpns) & np.uint32((1 << log2_slots) - 1)
    displaced = slots != homes.astype(np.int64)
    np.testing.assert_array_equal(probes > 0, displaced)
    # linear probing: the probe count is exactly the slot displacement
    # distance (mod table size)
    dist = (slots - homes.astype(np.int64)) % (1 << log2_slots)
    np.testing.assert_array_equal(probes, dist)
