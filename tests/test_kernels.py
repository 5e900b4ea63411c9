"""Pallas kernel sweeps: shapes x dtypes, allclose vs the ref.py oracles
(interpret mode executes the kernel bodies on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _rand(shape, dtype=jnp.float32):
    x = RNG.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype=dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)


class TestSchurUpdate:
    @pytest.mark.parametrize("m,n,k", [(128, 128, 128), (256, 128, 64), (384, 256, 128)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, m, n, k, dtype):
        A, L, U = _rand((m, n), dtype), _rand((m, k), dtype), _rand((k, n), dtype)
        got = ops.schur_update(A, L, U, bm=128, bn=128, bk=64)
        want = ref.schur_update(A, L, U)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
        )

    def test_small_blocks(self):
        A, L, U = _rand((64, 64)), _rand((64, 32)), _rand((32, 64))
        got = ops.schur_update(A, L, U, bm=32, bn=32, bk=16)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref.schur_update(A, L, U)), rtol=2e-4, atol=2e-4
        )


class TestLuPanel:
    @pytest.mark.parametrize("R,v", [(64, 8), (256, 16), (128, 32)])
    def test_sweep(self, R, v):
        panel = _rand((R, v))
        w = jnp.asarray((RNG.random(R) > 0.2).astype(np.float32))
        gF, gO, gok = ops.lu_panel(panel, w)
        rF, rO, rok = ref.lu_panel(panel, w)
        np.testing.assert_array_equal(np.asarray(gO), np.asarray(rO))
        np.testing.assert_array_equal(np.asarray(gok), np.asarray(rok))
        np.testing.assert_allclose(np.asarray(gF), np.asarray(rF), rtol=1e-4, atol=1e-4)

    def test_masked_rows_untouched(self):
        panel = _rand((32, 8))
        w = jnp.ones(32).at[jnp.asarray([3, 5])].set(0.0)
        gF, _, _ = ops.lu_panel(panel, w)
        np.testing.assert_array_equal(np.asarray(gF)[[3, 5]], np.asarray(panel)[[3, 5]])


class TestCholPanel:
    @pytest.mark.parametrize("v", [8, 16, 32, 64])
    def test_sweep(self, v):
        B = _rand((v, v))
        A = B @ B.T / v + 2.0 * jnp.eye(v)
        got = ops.chol_panel(A)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref.chol_panel(A)), rtol=2e-4, atol=2e-4
        )
        # L is a genuine lower Cholesky factor, not merely oracle-equal
        np.testing.assert_array_equal(np.triu(np.asarray(got), 1), 0.0)
        np.testing.assert_allclose(np.asarray(got @ got.T), np.asarray(A), rtol=2e-4, atol=2e-4)

    def test_matches_numpy_float64_oracle(self):
        v = 32
        B = _rand((v, v))
        A = np.asarray(B @ B.T / v + 2.0 * jnp.eye(v))
        got = np.asarray(ops.chol_panel(jnp.asarray(A)))
        want = np.linalg.cholesky(A.astype(np.float64))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


class TestTrsm:
    @pytest.mark.parametrize("R,v", [(128, 16), (256, 32), (512, 64)])
    def test_right_upper(self, R, v):
        U = jnp.triu(_rand((v, v))) + 3.0 * jnp.eye(v)
        B = _rand((R, v))
        got = ops.trsm_right_upper(B, U, br=128)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref.trsm_right_upper(B, U)), rtol=2e-4, atol=2e-4
        )
        # residual check: X @ U == B
        np.testing.assert_allclose(np.asarray(got @ U), np.asarray(B), rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("v,C", [(16, 128), (32, 256)])
    @pytest.mark.parametrize("unit", [True, False])
    def test_left_lower(self, v, C, unit):
        L = jnp.tril(_rand((v, v)), -1) + (jnp.eye(v) if unit else 2.0 * jnp.eye(v))
        B = _rand((v, C))
        got = ops.trsm_left_lower(L, B, bc=128, unit=unit)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref.trsm_left_lower(L, B, unit=unit)),
            rtol=2e-4, atol=2e-4,
        )


# where the window starts, relative to the column tiles of width bc
C_STARTS = {
    "zero": lambda C, bc: 0,
    "in_first_tile": lambda C, bc: 5,
    "on_boundary": lambda C, bc: bc,
    "in_last_tile": lambda C, bc: C - 3,
    "end": lambda C, bc: C,
}


class TestFusedTrsmSchur:
    @pytest.mark.parametrize("M,C,v", [(128, 128, 16), (256, 128, 32), (64, 96, 8),
                                       (64, 384, 16)])
    @pytest.mark.parametrize("unit", [True, False])
    @pytest.mark.parametrize("where", sorted(C_STARTS))
    def test_sweep(self, M, C, v, unit, where):
        bm, bc = 64, 128  # several column tiles where C allows (bc fits to 96)
        c_start = C_STARTS[where](C, min(bc, C))
        # 0.3x off-diagonal keeps the forward substitution well-conditioned
        # (growth compounds through the Schur subtract at v=32 otherwise)
        L00 = 0.3 * jnp.tril(_rand((v, v)), -1) + (1.0 if unit else 2.0) * jnp.eye(v)
        A, R01, L10 = _rand((M, C)), _rand((v, C)), _rand((M, v))
        R01 = R01 * (jnp.arange(C) >= c_start)
        # the tiles left of the first visited one are never read: fill them
        # with values whose product with anything but 0 overflows
        left = min(c_start // bc, (C - 1) // bc) * bc
        A = A.at[:, :left].set(3e38)
        gA, gU = ops.fused_trsm_schur(A, L00, R01, L10, bm=bm, bc=bc, unit=unit,
                                      c_start=c_start)
        wA, wU = ref.fused_trsm_schur(A, L00, R01, L10, unit=unit)
        np.testing.assert_allclose(np.asarray(gU), np.asarray(wU), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(gA), np.asarray(wA), rtol=2e-4, atol=2e-4)
        gA, gU = np.asarray(gA), np.asarray(gU)
        np.testing.assert_array_equal(gA[:, :c_start], np.asarray(A)[:, :c_start])
        np.testing.assert_array_equal(gU[:, :c_start], 0.0)
        assert np.isfinite(gA).all() and np.isfinite(gU).all()

    def test_fused_equals_two_call_composition(self):
        v, M, C = 16, 64, 128
        L00 = jnp.tril(_rand((v, v)), -1) + jnp.eye(v)
        A, R01, L10 = _rand((M, C)), _rand((v, C)), _rand((M, v))
        gA, gU = ops.fused_trsm_schur(A, L00, R01, L10)
        U = ops.trsm_left_lower(L00, R01)
        np.testing.assert_array_equal(np.asarray(gU), np.asarray(U))
        np.testing.assert_array_equal(
            np.asarray(gA), np.asarray(ops.schur_update(A, L10, U))
        )


class TestSchurTiles:
    """The single-system fused kernel's tiles come from the shape."""

    def test_chip_size_fits_vmem_in_few_steps(self):
        M = C = 16384
        for dtype in (jnp.float32, jnp.bfloat16):
            bm, bc = ops.schur_tiles(M, C, 32, dtype)
            assert ops.schur_vmem_bytes(bm, bc, 32, dtype) <= ops.SCOPED_VMEM
            assert (M // bm) * (C // bc) <= 1024
            assert bm % 8 == 0 and bc % 128 == 0 and M % bm == 0 and C % bc == 0

    @pytest.mark.parametrize("M,C,v", [(24, 40, 8), (64, 96, 8), (256, 256, 32),
                                       (4104, 384, 32), (2048, 2048, 32), (1000, 1000, 8)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_small_and_odd_shapes_keep_exact_cover(self, M, C, v, dtype):
        bm, bc = ops.schur_tiles(M, C, v, dtype)
        sub = 8 * (4 // jnp.dtype(dtype).itemsize)
        assert (bm, bc) == (ops._fit(bm, M, sub), ops._fit(bc, C, ops.LANE))
        if ops.schur_vmem_bytes(M, C, v, dtype) <= ops.SCOPED_VMEM:
            assert (bm, bc) == (M, C)  # a matrix that fits is one tile

    @pytest.mark.parametrize("C", [32, 64, 96])
    def test_narrow_window_takes_a_whole_lane_tile(self, C):
        # the last windows of a conflux [2x2x1] plan at N=16384 are [8192, C]
        # with C < 128, which Mosaic pads to 128 lanes in VMEM
        assert ops.schur_vmem_bytes(4096, C, 32, jnp.float32) == \
            ops.schur_vmem_bytes(4096, 128, 32, jnp.float32)
        bm, bc = ops.schur_tiles(8192, C, 32, jnp.float32)
        assert bc == C and bm < 8192
        assert ops.schur_vmem_bytes(bm, bc, 32, jnp.float32) <= ops.SCOPED_VMEM

    def test_larger_tiles_do_not_fit(self):
        bm, bc = ops.schur_tiles(16384, 16384, 32, jnp.float32)
        assert ops.schur_vmem_bytes(2 * bm, bc, 32, jnp.float32) > ops.SCOPED_VMEM
        assert ops.schur_vmem_bytes(bm, 2 * bc, 32, jnp.float32) > ops.SCOPED_VMEM

    def test_tile_share(self):
        from repro.kernels.fused_schur import schur_tile_share

        assert schur_tile_share(16384, 32, 512) == pytest.approx(0.5137, abs=1e-4)
        assert schur_tile_share(256, 32, 256) == 1.0  # one tile: always visited
        shares = [schur_tile_share(4096, 32, bc) for bc in (128, 256, 512, 1024, 4096)]
        assert shares == sorted(shares) and 0.5 < shares[0] < shares[-1] == 1.0
        # counted tile by tile: tile j is visited while the window starts
        # before its end, and the last tile always
        N, v, bc = 2048, 128, 256
        nt = N // bc
        visited = sum(1 for t in range(N // v) for j in range(nt)
                      if (t + 1) * v < (j + 1) * bc or j == nt - 1)
        assert schur_tile_share(N, v, bc) == visited / (N // v * nt)


class TestFlashAttention:
    @pytest.mark.parametrize("B,S,H,KV,hd", [(2, 256, 4, 2, 32), (1, 128, 8, 8, 64),
                                             (2, 128, 4, 1, 16)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_sweep(self, B, S, H, KV, hd, dtype):
        q, k, v = (_rand((B, S, H, hd), dtype), _rand((B, S, KV, hd), dtype),
                   _rand((B, S, KV, hd), dtype))
        got = ops.flash_attention(q, k, v, bq=64, bkv=64)
        want = ref.flash_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
        )

    def test_sliding_window(self):
        q, k, v = _rand((1, 256, 2, 2, 16)[1:]), None, None  # placeholder reshaping below
        q = _rand((1, 256, 2, 16))
        k = _rand((1, 256, 2, 16))
        v = _rand((1, 256, 2, 16))
        got = ops.flash_attention(q, k, v, window=64, bq=64, bkv=64)
        want = ref.flash_attention(q, k, v, window=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)

    def test_softcap(self):
        q = _rand((1, 128, 2, 16))
        k = _rand((1, 128, 1, 16))
        v = _rand((1, 128, 1, 16))
        got = ops.flash_attention(q, k, v, softcap=30.0, bq=64, bkv=64)
        want = ref.flash_attention(q, k, v, softcap=30.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)

    def test_bidirectional(self):
        q = _rand((1, 128, 2, 16))
        k = _rand((1, 128, 2, 16))
        v = _rand((1, 128, 2, 16))
        got = ops.flash_attention(q, k, v, causal=False, bq=64, bkv=64)
        want = ref.flash_attention(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)

    @settings(max_examples=6, deadline=None)
    @given(st.sampled_from([64, 128]), st.sampled_from([1, 2]), st.sampled_from([16, 32]))
    def test_property_matches_ref(self, S, KV, hd):
        H = KV * 2
        q, k, v = _rand((1, S, H, hd)), _rand((1, S, KV, hd)), _rand((1, S, KV, hd))
        got = ops.flash_attention(q, k, v, bq=64, bkv=64)
        want = ref.flash_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-4, atol=3e-4)


class TestMambaScan:
    @pytest.mark.parametrize("B,S,di,N", [(2, 128, 64, 4), (1, 256, 128, 16), (2, 64, 32, 8)])
    def test_sweep(self, B, S, di, N):
        a = jnp.asarray(RNG.uniform(0.6, 0.999, (B, S, di, N)).astype(np.float32))
        b = _rand((B, S, di, N))
        C = _rand((B, S, N))
        got = ops.mamba_scan(a, b, C, bd=32, cs=32)
        want = ref.mamba_scan(a, b, C)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)

    def test_state_carries_across_chunks(self):
        """Chunked result must equal a single-chunk run (cs = S)."""
        B, S, di, N = 1, 64, 16, 4
        a = jnp.asarray(RNG.uniform(0.8, 0.99, (B, S, di, N)).astype(np.float32))
        b = _rand((B, S, di, N))
        C = _rand((B, S, N))
        chunked = ops.mamba_scan(a, b, C, bd=16, cs=8)
        whole = ops.mamba_scan(a, b, C, bd=16, cs=64)
        np.testing.assert_allclose(np.asarray(chunked), np.asarray(whole), rtol=1e-5, atol=1e-5)


class TestModelUsesKernelSemantics:
    """The model's blocked attention (jnp path) equals the Pallas kernel —
    proving the kernel can be swapped in on TPU without numeric drift."""

    def test_blocked_attention_matches_flash_kernel(self):
        from repro.models.layers.attention import blocked_attention

        B, S, H, KV, hd = 1, 128, 4, 2, 32
        q, k, v = _rand((B, S, H, hd)), _rand((B, S, KV, hd)), _rand((B, S, KV, hd))
        pos = jnp.arange(S)
        jnp_out = blocked_attention(q, k, v, pos, pos, causal=True, chunk=64)
        pl_out = ops.flash_attention(q, k, v, bq=64, bkv=64)
        np.testing.assert_allclose(np.asarray(jnp_out), np.asarray(pl_out), rtol=3e-4, atol=3e-4)
