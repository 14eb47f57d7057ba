"""Property-based INDArray-vs-numpy oracle tests (hypothesis).

Reference test analog: nd4j-tests' randomized op checks. The example
counts are kept small — the deterministic oracle suite in
test_ndarray.py carries the broad coverage; these catch shape/dtype
edge cases humans don't enumerate (degenerate dims, negative axes,
broadcasting corners)."""

import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="hypothesis not installed in this container; the "
           "deterministic oracle suite in test_ndarray.py carries the "
           "coverage")

from hypothesis import given, settings, strategies as st
from hypothesis.extra import numpy as hnp

from deeplearning4j_tpu.ndarray import INDArray

SETTINGS = dict(max_examples=25, deadline=None, derandomize=True)

shapes = hnp.array_shapes(min_dims=1, max_dims=4, min_side=1, max_side=5)
floats = hnp.arrays(np.float32, shapes,
                    elements=st.floats(-100, 100, width=32))


@pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
@given(a=floats)
@settings(**SETTINGS)
def test_roundtrip(a):
    np.testing.assert_array_equal(INDArray(a).toNumpy(), a)


@pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
@given(a=floats, b=st.floats(-10, 10, width=32))
@settings(**SETTINGS)
def test_scalar_arithmetic(a, b):
    x = INDArray(a)
    np.testing.assert_allclose(x.add(b).toNumpy(), a + np.float32(b),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(x.mul(b).toNumpy(), a * np.float32(b),
                               rtol=1e-6, atol=1e-5)


@given(a=floats)
@settings(**SETTINGS)
def test_elementwise_pair(a):
    x = INDArray(a)
    y = INDArray(a * 0.5 + 1.0)
    np.testing.assert_allclose(x.sub(y).toNumpy(), a - (a * 0.5 + 1.0),
                               rtol=1e-5, atol=1e-5)


@given(a=floats, data=st.data())
@settings(**SETTINGS)
def test_reduction_over_random_axis(a, data):
    axis = data.draw(st.integers(-a.ndim, a.ndim - 1))
    x = INDArray(a)
    np.testing.assert_allclose(x.sum(axis).toNumpy(), a.sum(axis),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(x.max(axis).toNumpy(), a.max(axis),
                               rtol=1e-6, atol=1e-6)


@given(a=floats)
@settings(**SETTINGS)
def test_reshape_transpose_roundtrip(a):
    x = INDArray(a)
    flat = x.reshape(-1)
    assert flat.shape() == (a.size,)
    back = flat.reshape(*a.shape)
    np.testing.assert_array_equal(back.toNumpy(), a)
    if a.ndim == 2:
        np.testing.assert_array_equal(
            x.transpose().transpose().toNumpy(), a)


@given(n=st.integers(1, 5), k=st.integers(1, 5), m=st.integers(1, 5),
       data=st.data())
@settings(**SETTINGS)
def test_mmul_matches_numpy(n, k, m, data):
    el = st.floats(-10, 10, width=32)
    a = data.draw(hnp.arrays(np.float32, (n, k), elements=el))
    b = data.draw(hnp.arrays(np.float32, (k, m), elements=el))
    got = INDArray(a).mmul(INDArray(b)).toNumpy()
    np.testing.assert_allclose(got, a @ b, rtol=1e-4, atol=1e-4)


@given(a=floats, data=st.data())
@settings(**SETTINGS)
def test_scalar_get_put_roundtrip(a, data):
    idx = tuple(data.draw(st.integers(0, s - 1)) for s in a.shape)
    x = INDArray(a.copy())
    v = x.getDouble(*idx)
    assert v == pytest.approx(float(a[idx]), abs=1e-6)
    x.putScalar(*idx, 42.0)
    assert x.getDouble(*idx) == pytest.approx(42.0)


@given(a=floats, data=st.data())
@settings(**SETTINGS)
def test_out_of_bounds_always_raises(a, data):
    x = INDArray(a)
    idx = list(0 for _ in a.shape)
    ax = data.draw(st.integers(0, a.ndim - 1))
    idx[ax] = a.shape[ax]  # one past the end
    with pytest.raises(IndexError):
        x.getDouble(*idx)
    with pytest.raises(IndexError):
        x.putScalar(*idx, 1.0)
