"""Per-kernel validation: Pallas (interpret=True on CPU) vs ref.py oracles,
with hypothesis shape/dtype sweeps, plus algorithmic accuracy vs fp64 ground
truth and PRNG statistical sanity."""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax
import jax.numpy as jnp

from repro.kernels import expf as exp_mod
from repro.kernels import montecarlo as mc_mod
from repro.kernels import prng as prng_mod
from repro.kernels import ops, ref

INTERPRET = jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# exp
# ---------------------------------------------------------------------------

class TestExp:
    @pytest.mark.parametrize("shape", [(8,), (3, 777), (2, 5, 129), (1024,),
                                       (65, 1031)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_pallas_matches_ref(self, shape, dtype):
        rng = np.random.default_rng(hash((shape, str(dtype))) % 2**32)
        x = jnp.asarray(rng.uniform(-30, 30, shape), dtype)
        got = ops.exp(x, impl="pallas")
        want = ops.exp(x, impl="reference")
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-6, atol=1e-30)

    def test_accuracy_vs_fp64(self):
        x = jnp.linspace(-87, 88, 8191, dtype=jnp.float32)
        got = np.asarray(ops.exp(x, impl="pallas"), np.float64)
        want = np.exp(np.asarray(x, np.float64))
        np.testing.assert_allclose(got, want, rtol=2e-6)

    def test_extremes(self):
        x = jnp.asarray([-1e4, -87.5, 0.0, 88.9, 1e4], jnp.float32)
        y = np.asarray(ops.exp(x, impl="pallas"))
        assert y[0] == 0.0 and y[2] == pytest.approx(1.0) and np.isinf(y[-1])

    @settings(max_examples=30, deadline=None)
    @given(st.integers(1, 4096), st.integers(0, 2**31 - 1))
    def test_property_any_length(self, n, seed):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.uniform(-10, 10, (n,)), jnp.float32)
        got = ops.exp(x, impl="pallas")
        np.testing.assert_allclose(np.asarray(got),
                                   np.exp(np.asarray(x, np.float64)),
                                   rtol=2e-6)

    @pytest.mark.parametrize("block_rows", [8, 16, 64, 128])
    def test_block_shape_sweep(self, block_rows):
        """BlockSpec tiling must not change results (VMEM tiling sweep)."""
        x = jnp.asarray(np.random.default_rng(0).uniform(-5, 5, (block_rows * 2, 1024)),
                        jnp.float32)
        y = exp_mod.exp_2d(x, block_rows=block_rows, interpret=INTERPRET)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref.exp_ref(x)),
                                   rtol=1e-6)


# ---------------------------------------------------------------------------
# log
# ---------------------------------------------------------------------------

class TestLog:
    @pytest.mark.parametrize("shape", [(16,), (2, 555), (7, 7, 7)])
    def test_pallas_matches_ref(self, shape):
        rng = np.random.default_rng(42)
        x = jnp.asarray(rng.uniform(1e-3, 1e3, shape), jnp.float32)
        got = ops.log(x, impl="pallas")
        want = ops.log(x, impl="reference")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

    def test_accuracy_vs_fp64(self):
        x = jnp.asarray(np.logspace(-30, 30, 4097), jnp.float32)
        got = np.asarray(ops.log(x, impl="pallas"), np.float64)
        want = np.log(np.asarray(x, np.float64))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=6e-7)

    @settings(max_examples=20, deadline=None)
    @given(st.floats(1e-20, 1e20), st.integers(1, 500))
    def test_property_scale_invariance(self, scale, n):
        x = jnp.asarray(np.linspace(1.0, 2.0, n) * scale, jnp.float32)
        got = np.asarray(ops.log(x, impl="pallas"), np.float64)
        np.testing.assert_allclose(got, np.log(np.asarray(x, np.float64)),
                                   rtol=1e-5, atol=6e-7)

    def test_table_is_issr_sized(self):
        """The gather table must stay one-vreg-small (the ISSR argument)."""
        assert ref.LOGF_INVC.shape == (16,) and ref.LOGF_LOGC.shape == (16,)


# ---------------------------------------------------------------------------
# PRNG
# ---------------------------------------------------------------------------

class TestPrng:
    @pytest.mark.parametrize("kind", ["lcg", "xoshiro128p"])
    @pytest.mark.parametrize("shape", [(1000,), (10, 1000), (3, 5, 77)])
    def test_pallas_bitexact_vs_ref(self, kind, shape):
        got = ops.uniform(5, shape, kind=kind, impl="pallas")
        want = ops.uniform(5, shape, kind=kind, impl="reference")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("kind", ["lcg", "xoshiro128p"])
    def test_statistics(self, kind):
        u = np.asarray(ops.uniform(123, (1 << 18,), kind=kind))
        assert abs(u.mean() - 0.5) < 3e-3
        assert abs(u.std() - np.sqrt(1 / 12)) < 3e-3
        assert u.min() >= 0.0 and u.max() < 1.0
        # lag-1 autocorrelation ~ 0
        c = np.corrcoef(u[:-1], u[1:])[0, 1]
        assert abs(c) < 0.01

    def test_seeds_decorrelated(self):
        a = np.asarray(ops.uniform(1, (1 << 14,)))
        b = np.asarray(ops.uniform(2, (1 << 14,)))
        assert abs(np.corrcoef(a, b)[0, 1]) < 0.02

    def test_deterministic(self):
        a = ops.uniform(7, (4096,), impl="pallas")
        b = ops.uniform(7, (4096,), impl="pallas")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 5000))
    def test_property_bitexact(self, seed, n):
        got = ops.uniform(seed, (n,), impl="pallas")
        want = ops.uniform(seed, (n,), impl="reference")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Monte-Carlo
# ---------------------------------------------------------------------------

class TestMonteCarlo:
    @pytest.mark.parametrize("kind", ["lcg", "xoshiro128p"])
    @pytest.mark.parametrize("problem", ["pi", "poly"])
    def test_pallas_bitexact_vs_blocked_ref(self, kind, problem):
        iters, n_blocks = 16, 4
        sums = mc_mod.mc_partial_sums(jnp.uint32(9), kind=kind,
                                      problem=problem, iters=iters,
                                      n_blocks=n_blocks, interpret=INTERPRET)
        want = mc_mod.mc_blocked_ref(9, kind=kind, problem=problem,
                                     iters=iters, n_blocks=n_blocks)
        np.testing.assert_array_equal(np.asarray(sums), np.asarray(want))

    @pytest.mark.parametrize("kind", ["lcg", "xoshiro128p"])
    def test_pi_converges(self, kind):
        est = float(ops.mc_pi(11, 1 << 18, kind=kind))
        assert est == pytest.approx(np.pi, abs=0.02)

    @pytest.mark.parametrize("kind", ["lcg", "xoshiro128p"])
    def test_poly_converges(self, kind):
        est = float(ops.mc_poly(13, 1 << 18, kind=kind))
        assert est == pytest.approx(ref.MC_POLY_INTEGRAL, abs=0.01)

    def test_partial_sums_bounded(self):
        iters = 8
        sums = np.asarray(mc_mod.mc_partial_sums(
            jnp.uint32(1), kind="lcg", problem="pi", iters=iters, n_blocks=2,
            interpret=INTERPRET))
        assert (sums >= 0).all() and (sums <= iters).all()


# ---------------------------------------------------------------------------
# softmax
# ---------------------------------------------------------------------------

class TestSoftmax:
    @pytest.mark.parametrize("shape", [(4, 128), (2, 8, 256), (16, 1000),
                                       (1, 32768)])
    def test_pallas_matches_jax(self, shape):
        x = jnp.asarray(np.random.default_rng(3).normal(0, 4, shape),
                        jnp.float32)
        got = ops.softmax(x, impl="pallas")
        want = jax.nn.softmax(x, axis=-1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-5, atol=3e-7)

    def test_other_axis_runs_the_kernel(self):
        x = jnp.asarray(np.random.default_rng(8).normal(0, 3, (5, 40, 3)),
                        jnp.float32)
        got = ops.softmax(x, axis=1, impl="pallas")
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(jax.nn.softmax(x, axis=1)),
                                   rtol=3e-5, atol=3e-7)

    def test_rows_sum_to_one(self):
        x = jnp.asarray(np.random.default_rng(4).normal(0, 10, (32, 500)),
                        jnp.float32)
        s = np.asarray(ops.softmax(x, impl="pallas")).sum(-1)
        np.testing.assert_allclose(s, 1.0, rtol=1e-5)

    def test_translation_invariance(self):
        x = jnp.asarray(np.random.default_rng(5).normal(0, 2, (8, 64)),
                        jnp.float32)
        a = ops.softmax(x, impl="pallas")
        b = ops.softmax(x + 100.0, impl="pallas")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-6)

    def test_bf16_dtype_preserved(self):
        x = jnp.asarray(np.random.default_rng(6).normal(0, 1, (8, 128)),
                        jnp.bfloat16)
        y = ops.softmax(x, impl="pallas")
        assert y.dtype == jnp.bfloat16

    @pytest.mark.parametrize("shape", [(8, 128), (3, 5, 77), (2, 4, 1, 9, 64)])
    def test_grad_matches_reference(self, shape):
        """jax.grad through the kernel (its custom VJP) equals jax.grad
        through the jnp reference; ragged row counts are padded."""
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.normal(0, 3, shape), jnp.float32)
        w = jnp.asarray(rng.normal(0, 1, shape), jnp.float32)

        def grad(impl):
            return jax.grad(lambda a: jnp.sum(w * ops.softmax(a, impl=impl)))(x)

        np.testing.assert_allclose(np.asarray(grad("pallas")),
                                   np.asarray(grad("reference")),
                                   rtol=2e-5, atol=2e-7)

    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 64), st.integers(2, 512))
    def test_property_matches_reference(self, rows, cols):
        x = jnp.asarray(
            np.random.default_rng(rows * 1000 + cols).normal(0, 3, (rows, cols)),
            jnp.float32)
        got = ops.softmax(x, impl="pallas")
        want = ops.softmax(x, impl="reference")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-7)
