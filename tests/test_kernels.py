"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import block_pages, paged_attention_pallas


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5)


def _paged_inputs(b, h, kh, d, page, maxp, dtype, seed=0, lens=None,
                  hole=False):
    """Random q and pools, and a table mapping each sequence's live pages
    to distinct physical pages (-1 past them).  ``lens`` fixes the
    lengths; ``hole`` unmaps the second page of every sequence that has
    three or more."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    n = b * maxp + 2
    q = jax.random.normal(ks[0], (b, 1, h, d), dtype)
    kp = jax.random.normal(ks[1], (n, page, kh, d), dtype)
    vp = jax.random.normal(ks[2], (n, page, kh, d), dtype)
    rng = np.random.default_rng(seed)
    tab = np.full((b, maxp), -1, np.int32)
    perm = rng.permutation(n)
    if lens is None:
        lens = [rng.integers(1, maxp * page + 1) for _ in range(b)]
    lens = np.asarray(lens, np.int32)
    k = 0
    for i in range(b):
        used = -(-int(lens[i]) // page)
        tab[i, :used] = perm[k:k + used]
        k += used
        if hole and used >= 3:
            tab[i, 1] = -1
    return q, kp, vp, jnp.asarray(tab), jnp.asarray(lens)


#: tokens a kernel block holds at 8-token pages, 4 KV heads, MAXP 64
BK = block_pages(8, 4, 64) * 8
assert BK < 512


class TestPagedAttentionKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("b,h,kh,d,page,maxp,lens,hole", [
        (2, 8, 2, 64, 16, 8, None, False),       # GQA
        (1, 4, 1, 128, 32, 4, None, False),      # MQA
        (3, 4, 4, 32, 8, 16, None, False),       # MHA
        # 1 token, one page, one block, one past a block, MAXP * page
        (5, 8, 4, 32, 8, 64, (1, 8, BK, BK + 1, 512), False),
        (3, 8, 2, 64, 16, 8, (40, 100, 128), True),   # -1 inside the range
        (3, 16, 8, 128, 16, 16, None, True),     # internlm2 heads, 2 blocks
    ])
    def test_matches_oracle(self, b, h, kh, d, page, maxp, lens, hole,
                            dtype):
        q, kp, vp, tab, lens = _paged_inputs(b, h, kh, d, page, maxp, dtype,
                                             lens=lens, hole=hole)
        want = ref.paged_attention_ref(q, kp, vp, tab, lens)
        got = paged_attention_pallas(q, kp, vp, tab, lens, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **_tol(dtype))

    @pytest.mark.parametrize("window,layer", [
        pytest.param(8, None, id="8"),
        pytest.param(40, None, id="40"),
        # pools stacked over 3 layers, read at the first, a middle and
        # the last, with and without a window
        *[pytest.param(w, i, id=f"{w}-stacked{i}")
          for w in (0, 40) for i in range(3)],
    ])
    def test_windowed(self, window, layer):
        q, kp, vp, tab, lens = _paged_inputs(2, 4, 2, 64, 16, 6,
                                             jnp.float32, seed=3)
        want = ref.paged_attention_ref(q, kp, vp, tab, lens, window=window)
        got = paged_attention_pallas(q, kp, vp, tab, lens, window=window,
                                     interpret=True)
        if layer is not None:
            # every other layer holds different data: reading the wrong
            # one fails the comparison; the layer's own output is the
            # unstacked call's, bit for bit
            ks = jax.random.split(jax.random.PRNGKey(11), 2)
            other = jax.random.normal(ks[0], (2,) + kp.shape, kp.dtype)
            kst = jnp.insert(other, layer, kp, axis=0)
            vst = jnp.insert(-other[::-1], layer, vp, axis=0)
            stacked = paged_attention_pallas(q, kst, vst, tab, lens, layer,
                                             window=window, interpret=True)
            np.testing.assert_array_equal(np.asarray(stacked),
                                          np.asarray(got))
            np.testing.assert_array_equal(np.asarray(ref.paged_attention_ref(
                q, kst, vst, tab, lens, layer, window=window)),
                np.asarray(want))
            got = stacked
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("window", [0, 24])
    def test_dead_pages_are_never_read(self, window):
        """Every pool page outside the live ranges is NaN: page 0, which
        an unmapped entry would clamp to, pages mapped past a sequence's
        length, and (with a window) pages wholly before it.  The kernel
        must fetch none of them."""
        b, h, kh, d, page, maxp = 3, 8, 2, 64, 8, 32
        q, kp, vp, tab, lens = _paged_inputs(
            b, h, kh, d, page, maxp, jnp.float32, seed=5,
            lens=(3, 70, 200), hole=True)
        tab = np.array(tab)
        n = kp.shape[0]
        # page 0 is never live; stale pages are mapped past the length
        # of the first two sequences
        free = [p for p in range(1, n) if p not in tab]
        tab[tab == 0] = free.pop()
        tab[0, 1:4] = free[:3]
        tab[1, 9:11] = free[3:5]
        pos = np.arange(maxp)[None, :] * page
        live = ((pos < np.asarray(lens)[:, None])
                & (pos + page > np.asarray(lens)[:, None] - window
                   if window else True) & (tab >= 0))
        dead = np.setdiff1d(np.arange(n), tab[live])
        assert 0 in dead and len(dead) > n // 2
        kp_nan = kp.at[dead].set(jnp.nan)
        vp_nan = vp.at[dead].set(jnp.nan)
        tab = jnp.asarray(tab)
        got = paged_attention_pallas(q, kp_nan, vp_nan, tab, lens,
                                     window=window, interpret=True)
        assert np.isfinite(np.asarray(got)).all()
        want = ref.paged_attention_ref(q, kp, vp, tab, lens, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_physical_placement_invariance(self):
        """NDPage core invariant: output independent of WHERE pages live."""
        q, kp, vp, tab, lens = _paged_inputs(2, 4, 2, 64, 8, 4, jnp.float32,
                                             seed=7)
        # permute physical pages and rewrite the table accordingly
        n = kp.shape[0]
        perm = np.random.default_rng(1).permutation(n)
        inv = np.argsort(perm)
        kp2 = kp[perm]
        vp2 = vp[perm]
        tab2 = jnp.where(tab >= 0, jnp.asarray(inv)[jnp.maximum(tab, 0)], -1)
        for attend in (ref.paged_attention_ref, functools.partial(
                paged_attention_pallas, interpret=True)):
            out1 = attend(q, kp, vp, tab, lens)
            out2 = attend(q, kp2, vp2, tab2, lens)
            np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                       rtol=1e-6)


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("b,s,h,kh,d,bq,bk", [
        (2, 128, 4, 2, 64, 64, 64),
        (1, 256, 8, 8, 32, 64, 128),
        (2, 128, 4, 1, 128, 32, 32),
    ])
    def test_matches_oracle(self, b, s, h, kh, d, bq, bk, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, s, h, d), dtype)
        k = jax.random.normal(ks[1], (b, s, kh, d), dtype)
        v = jax.random.normal(ks[2], (b, s, kh, d), dtype)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        got = flash_attention_pallas(q, k, v, causal=True, bq=bq, bk=bk,
                                     interpret=True)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **_tol(dtype))

    @pytest.mark.parametrize("causal,window", [(True, 16), (False, 0)])
    def test_masks(self, causal, window):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 128, 2, 64))
        k = jax.random.normal(ks[1], (1, 128, 2, 64))
        v = jax.random.normal(ks[2], (1, 128, 2, 64))
        want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
        got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                     bq=32, bk=32, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


class TestOpsDispatch:
    def test_cpu_defaults_to_ref(self):
        q, kp, vp, tab, lens = _paged_inputs(1, 2, 1, 32, 8, 2, jnp.float32)
        a = ops.paged_attention(q, kp, vp, tab, lens)
        b = ref.paged_attention_ref(q, kp, vp, tab, lens)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))

    def test_untiled_pages_take_the_reference(self):
        """Compiled, the kernel DMAs whole pages, which needs (KH, D) on
        whole (8, 128) tiles; other widths take the jnp reference."""
        q, kp, vp, tab, lens = _paged_inputs(2, 4, 2, 32, 8, 2, jnp.float32)
        a = ops.paged_attention(q, kp, vp, tab, lens, impl="pallas")
        b = ref.paged_attention_ref(q, kp, vp, tab, lens)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
        with pytest.raises(ValueError, match="tiles"):
            paged_attention_pallas(q, kp, vp, tab, lens)

    def test_blockwise_jnp_matches_flash_ref(self):
        """models.attention.blockwise_attention is itself oracle-consistent."""
        from repro.models.attention import blockwise_attention
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (2, 256, 4, 32))
        k = jax.random.normal(ks[1], (2, 256, 2, 32))
        v = jax.random.normal(ks[2], (2, 256, 2, 32))
        want = ref.flash_attention_ref(q, k, v, causal=True, window=50)
        got = blockwise_attention(q, k, v, causal=True, window=50,
                                  q_chunk=64, kv_chunk=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
