"""The benchmark's own decode of the packed formats equals the program's
dequantize on seeded payloads, and the payloads have the weight
statistics they are drawn for."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import formats as F


@pytest.mark.parametrize("variant", ["q2_k", "q3_k"])
def test_decode_matches_program(variant):
    from repro.core.quantize import QTensor, dequantize

    K, N = 512, 256
    w = F.random_payload(variant, jax.random.PRNGKey(3), (), K, N, 0.05)
    mine = np.asarray(F.decode(w))
    theirs = np.asarray(dequantize(QTensor(variant, (K, N), w.arrays)))
    np.testing.assert_array_equal(mine, theirs)


@pytest.mark.parametrize("variant", ["q2_k", "q3_k"])
def test_payload_statistics(variant):
    K, N = 2048, 1024
    w = F.random_payload(variant, jax.random.PRNGKey(5), (2,), K, N,
                         1 / np.sqrt(K))
    x = np.asarray(F.decode(w))
    assert x.shape == (2, K, N)
    assert abs(x.mean()) < 0.02 / np.sqrt(K)
    assert x.std() == pytest.approx(1 / np.sqrt(K), rel=0.03)
    for name, (rows, dt) in F.LAYOUT[variant].items():
        assert w.arrays[name].shape == (2, K // rows, N)
        assert w.arrays[name].dtype == jnp.dtype(dt)


def test_slab_order():
    # one 2-bit byte per column: field j of packed row p is row j*64 + p
    qs = np.zeros((64, 1), np.uint8)
    qs[5, 0] = 0b11100100                 # fields 0..3 = 0, 1, 2, 3
    rows = np.asarray(F._unslab(jnp.asarray(qs), 2))[:, 0]
    assert [rows[j * 64 + 5] for j in range(4)] == [0, 1, 2, 3]
    assert rows.sum() == 6
