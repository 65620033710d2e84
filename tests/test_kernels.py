"""Per-kernel shape/dtype sweeps: interpret-mode Pallas kernels vs their
pure-jnp oracle, and the jnp-only ELL gathers vs plain numpy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis or skip-shim

rng = np.random.default_rng(0)


def _np_or_gather(ell, fr):
    """numpy OR-gather: next[v] = OR_d fr[ell[v, d]] (fr's row V is 0)."""
    return np.bitwise_or.reduce(np.asarray(fr)[np.asarray(ell)], axis=1)


class TestMsbfsExpand:
    @pytest.mark.parametrize("V,D,W", [(16, 2, 1), (64, 5, 2), (130, 8, 4),
                                       (257, 3, 7)])
    def test_sweep(self, V, D, W):
        from repro.kernels.msbfs_expand.ref import msbfs_expand_ref
        ell = jnp.asarray(rng.integers(0, V + 1, (V, D)).astype(np.int32))
        fr = jnp.asarray(
            rng.integers(0, 2**32, (V + 1, W), dtype=np.uint64).astype(np.uint32))
        fr = fr.at[-1].set(0)
        a = msbfs_expand_ref(ell, fr)
        assert np.array_equal(np.asarray(a), _np_or_gather(ell, fr))

    @given(st.integers(4, 80), st.integers(1, 6), st.integers(1, 3),
           st.integers(0, 5))
    @settings(max_examples=15, deadline=None)
    def test_property(self, V, D, W, seed):
        from repro.kernels.msbfs_expand import ops
        r = np.random.default_rng(seed)
        ell = jnp.asarray(r.integers(0, V + 1, (V, D)).astype(np.int32))
        fr = jnp.asarray(
            r.integers(0, 2**32, (V + 1, W), dtype=np.uint64).astype(np.uint32))
        got = np.asarray(ops.msbfs_hop_packed(ell, fr, backend="jnp"))
        fr0 = np.asarray(fr).copy()
        fr0[-1] = 0                              # the sentinel row is pinned
        assert np.array_equal(got[:-1], _np_or_gather(ell, fr0))
        assert not got[-1].any()

    def test_pack_unpack_roundtrip(self):
        from repro.kernels.msbfs_expand.ref import pack_bits, unpack_bits
        bits = jnp.asarray(rng.random((33, 70)) < 0.5)
        assert np.array_equal(np.asarray(unpack_bits(pack_bits(bits), 70)),
                              np.asarray(bits))


class TestPairwisePopcount:
    @pytest.mark.parametrize("Q,V", [(3, 40), (17, 333), (64, 1000),
                                     (5, 31), (9, 65)])
    def test_sweep(self, Q, V):
        from repro.kernels.pairwise_popcount import ops
        g = jnp.asarray(rng.random((Q, V)) < 0.4)
        ref = ops.pairwise_intersections(g, backend="jnp")
        itp = ops.pairwise_intersections(g, backend="interpret")
        assert np.array_equal(np.asarray(ref), np.asarray(itp))
        # ground truth on a couple of pairs
        gn = np.asarray(g)
        assert int(np.asarray(ref)[0, 1]) == int((gn[0] & gn[1]).sum())

    @given(st.integers(2, 20), st.integers(8, 120), st.integers(0, 4))
    @settings(max_examples=15, deadline=None)
    def test_property_symmetric_diag(self, Q, V, seed):
        from repro.kernels.pairwise_popcount import ops
        r = np.random.default_rng(seed)
        g = jnp.asarray(r.random((Q, V)) < 0.3)
        out = np.asarray(ops.pairwise_intersections(g, backend="interpret"))
        assert np.array_equal(out, out.T)
        assert np.array_equal(np.diag(out), np.asarray(g).sum(1))


class TestPathJoin:
    @pytest.mark.parametrize("NA,NB,LA,LB", [(8, 8, 3, 3), (37, 23, 5, 4),
                                             (100, 64, 9, 8), (1, 5, 2, 6)])
    def test_sweep(self, NA, NB, LA, LB):
        from repro.kernels.path_join import ops
        A = jnp.asarray(rng.integers(-1, 40, (NA, LA)).astype(np.int32))
        B = jnp.asarray(rng.integers(-1, 40, (NB, LB)).astype(np.int32))
        r1 = ops.path_overlap(A, B, backend="jnp")
        r2 = ops.path_overlap(A, B, backend="interpret")
        assert np.array_equal(np.asarray(r1), np.asarray(r2))

    def test_join_validity_semantics(self):
        from repro.kernels.path_join import ops
        A = jnp.asarray(np.array([[0, 1, 2], [3, 4, 5]], np.int32))
        B = jnp.asarray(np.array([[9, 2], [5, 2], [7, 5]], np.int32))
        valid = np.asarray(ops.keyed_join_valid(A, 2, B, 1,
                                                backend="interpret"))
        # A0 (ends 2) joins B0 (ends 2, no overlap beyond key) -> True
        # A0 with B1 (ends 2 but contains 5? no -> shares only key) -> True
        assert valid[0, 0]
        assert valid[0, 1]
        # A1 ends 5; B2 ends 5 but also fine; B1 contains 5 but ends 2
        assert valid[1, 2]
        assert not valid[1, 0]

    def test_splice_validity(self):
        from repro.kernels.path_join import ops
        P = jnp.asarray(np.array([[0, 1], [2, 3]], np.int32))
        C = jnp.asarray(np.array([[4, 5], [1, 9]], np.int32))
        v = np.asarray(ops.splice_join_valid(P, 1, C, 1, backend="interpret"))
        assert v[0, 0] and not v[0, 1]   # (0,1)x(1,9) shares vertex 1
        assert v[1, 0] and v[1, 1]


class TestFlashAttention:
    @pytest.mark.parametrize("B,Sq,Skv,Hq,Hkv,hd,causal", [
        (1, 16, 16, 2, 1, 8, True), (2, 64, 64, 4, 2, 32, True),
        (2, 64, 64, 4, 4, 32, False), (1, 1, 128, 8, 2, 16, True),
        (3, 33, 65, 6, 3, 24, True)])
    def test_sweep(self, B, Sq, Skv, Hq, Hkv, hd, causal):
        from repro.kernels.flash_attention import ops
        r = np.random.default_rng(1)
        q = jnp.asarray(r.standard_normal((B, Sq, Hq, hd)).astype(np.float32))
        k = jnp.asarray(r.standard_normal((B, Skv, Hkv, hd)).astype(np.float32))
        v = jnp.asarray(r.standard_normal((B, Skv, Hkv, hd)).astype(np.float32))
        a = ops.gqa_attention(q, k, v, causal=causal, backend="jnp")
        b = ops.gqa_attention(q, k, v, causal=causal, backend="interpret")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=1e-4)

    def test_bf16(self):
        from repro.kernels.flash_attention import ops
        r = np.random.default_rng(2)
        q = jnp.asarray(r.standard_normal((2, 32, 4, 16)), jnp.bfloat16)
        k = jnp.asarray(r.standard_normal((2, 32, 2, 16)), jnp.bfloat16)
        v = jnp.asarray(r.standard_normal((2, 32, 2, 16)), jnp.bfloat16)
        a = ops.gqa_attention(q, k, v, backend="jnp").astype(jnp.float32)
        b = ops.gqa_attention(q, k, v, backend="interpret").astype(jnp.float32)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-2, rtol=3e-2)

    def test_chunked_jnp_twin_matches_exact(self):
        """models.transformer.chunked_attention == exact softmax reference."""
        from repro.models.transformer import chunked_attention
        from repro.kernels.flash_attention.ref import attention_ref
        r = np.random.default_rng(3)
        B, Sq, Skv, Hq, Hkv, hd = 2, 24, 48, 4, 2, 16
        q = jnp.asarray(r.standard_normal((B, Sq, Hq, hd)).astype(np.float32))
        k = jnp.asarray(r.standard_normal((B, Skv, Hkv, hd)).astype(np.float32))
        v = jnp.asarray(r.standard_normal((B, Skv, Hkv, hd)).astype(np.float32))
        out = chunked_attention(q, k, v, causal=True, q_offset=Skv - Sq,
                                chunk=16)
        kk = jnp.repeat(k, Hq // Hkv, axis=2).transpose(0, 2, 1, 3).reshape(
            B * Hq, Skv, hd)
        vv = jnp.repeat(v, Hq // Hkv, axis=2).transpose(0, 2, 1, 3).reshape(
            B * Hq, Skv, hd)
        qq = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, hd)
        ref = attention_ref(qq, kk, vv, causal=True).reshape(
            B, Hq, Sq, hd).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)
