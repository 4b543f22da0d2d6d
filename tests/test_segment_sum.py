"""Pallas per-row segment-sum vs jnp oracle — shape/dtype sweeps,
padding edges, out-of-range ids, and the ops-layer dispatch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import segment_sum as segment_sum_op
from repro.kernels.segment_sum import segment_sum

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("T,R,B", [
    (8, 128, 128),     # exact tile multiples
    (3, 50, 20),       # everything ragged
    (16, 300, 60),     # multi-tile replica axis
    (1, 1, 1),         # degenerate
])
def test_segment_sum_sweep(dtype, T, R, B):
    with jax.enable_x64():
        vals = jax.random.normal(KEY, (T, R), jnp.float32).astype(dtype)
        ids = jax.random.randint(jax.random.fold_in(KEY, 1), (T, R), 0, B)
        out = segment_sum(vals, ids, B, interpret=True)
        want = ref.segment_sum_ref(vals, ids, B)
        assert out.shape == (T, B)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_segment_sum_integer_counts_exact():
    """The simulator feeds 0/1 occupancy masks: the kernel's sums must
    be integer-exact, not merely allclose."""
    with jax.enable_x64():
        vals = (jax.random.uniform(KEY, (5, 97)) < 0.5).astype(jnp.float64)
        ids = jax.random.randint(jax.random.fold_in(KEY, 1), (5, 97),
                                 0, 13)
        out = np.asarray(segment_sum(vals, ids, 13, interpret=True))
        want = np.asarray(ref.segment_sum_ref(vals, ids, 13))
        np.testing.assert_array_equal(out, want)


@pytest.mark.parametrize("p_busy,B", [
    (0.5, 1250),       # the recount: N * A = 250 * 5 (node, app) buckets
    (1.0, 3),          # every replica busy, up to R = 1000 per bucket
])
def test_segment_sum_f32_counts_exact_at_recount_shape(p_busy, B):
    """The simulator hands the kernel float32 0/1 masks at (T, R) =
    (256, 1000), under x64: counts up to R are exact in float32."""
    with jax.enable_x64():
        vals = (jax.random.uniform(KEY, (256, 1000)) < p_busy) \
            .astype(jnp.float32)
        ids = jax.random.randint(jax.random.fold_in(KEY, 1), (256, 1000),
                                 0, B)
        out = segment_sum(vals, ids, B, interpret=True)
        assert out.dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(ref.segment_sum_ref(vals, ids, B)))


def test_segment_sum_out_of_range_ids_dropped():
    with jax.enable_x64():
        vals = jnp.ones((2, 10), jnp.float64)
        ids = jnp.array([[0, 1, 2, 3, 4, 5, 6, 7, 8, 9]] * 2)
        out = np.asarray(segment_sum(vals, ids, 4, interpret=True))
        # ids >= 4 contribute nothing
        np.testing.assert_array_equal(out, np.ones((2, 4)))


def test_segment_sum_rejects_mismatched_shapes():
    with pytest.raises(ValueError, match="matching"):
        segment_sum(jnp.ones((2, 3)), jnp.zeros((3, 2), jnp.int32), 4,
                    interpret=True)


def test_ops_dispatch_matches_ref():
    with jax.enable_x64():
        vals = jax.random.normal(KEY, (4, 33), jnp.float64)
        ids = jax.random.randint(jax.random.fold_in(KEY, 1), (4, 33),
                                 0, 7)
        xla = segment_sum_op(vals, ids, 7, use_pallas=False)
        pal = segment_sum_op(vals, ids, 7, use_pallas=True,
                             interpret=True)
        np.testing.assert_allclose(np.asarray(xla), np.asarray(pal),
                                   rtol=1e-12, atol=1e-12)
