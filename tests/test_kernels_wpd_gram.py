"""WPD and gram kernels in interpret mode against their references.

The WPD kernel accumulates taps in the same ascending order as
``ref.wpd_level``, so the two agree bit for bit; the gram kernel tiles
its reduction, so it agrees with ``ref.gram`` up to f32 summation order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gram import kernel as gram_kernel
from repro.kernels.gram import ops as gram_ops
from repro.kernels.wpd import kernel as wpd_kernel
from repro.kernels.wpd import ops as wpd_ops
from repro.signal import wavelet


@pytest.mark.parametrize("b,n,block_b", [(37, 2048, 16), (64, 256, 64), (5, 64, 8)])
@pytest.mark.parametrize("name", ["db4", "db1"])
def test_wpd_interpret_exactly_equals_ref(b, n, block_b, name):
    x = jax.random.normal(jax.random.PRNGKey(b), (b, n))
    h, g = wavelet.filters(name)
    a_k, d_k = wpd_kernel.wpd_level(
        x, h, g, taps=int(h.shape[0]), block_b=block_b, interpret=True
    )
    a_r, d_r = wpd_ops.wpd_level(x, wavelet=name, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_r))
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_r))


def test_wpd_rejects_odd_rows():
    h, g = wavelet.filters("db4")
    with pytest.raises(ValueError, match="even"):
        wpd_kernel.wpd_level(jnp.ones((4, 7)), h, g, taps=8, interpret=True)


@pytest.mark.parametrize("n,f", [(2048, 180), (300, 20)])
def test_gram_interpret_matches_ref(n, f):
    x = jax.random.normal(jax.random.PRNGKey(n), (n, f))
    g_k = gram_kernel.gram(x, interpret=True)
    g_r = gram_ops.gram(x, use_pallas=False)
    np.testing.assert_allclose(
        np.asarray(g_k), np.asarray(g_r), rtol=1e-5, atol=1e-3
    )
