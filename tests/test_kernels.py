"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes and dtypes as mandated."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 env may lack hypothesis: skip only @given tests
    from conftest import given, settings, st

from repro.core.table import _as_u32
from repro.kernels.flash_attention import kernel as fk, ref as fr
from repro.kernels.hash_partition import kernel as hk, ref as hr
from repro.kernels.segment_reduce import kernel as sk, ref as sr

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
FLASH_CASES = [
    # b, hq, hkv, sq, sk, d, causal, window, q_offset
    (2, 4, 2, 128, 128, 64, True, None, 0),
    (1, 8, 8, 100, 100, 32, True, None, 0),      # ragged (non-multiple)
    (1, 4, 1, 64, 256, 64, False, None, 0),      # MQA, bidirectional
    (2, 2, 2, 1, 512, 64, True, None, 511),      # decode
    (1, 4, 2, 256, 256, 64, True, 64, 0),        # sliding window
    (1, 2, 2, 1, 384, 128, True, 128, 383),      # SWA decode
    (1, 1, 1, 16, 16, 128, True, None, 0),       # tiny
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(case, dtype):
    b, hq, hkv, sq, sk_, d, causal, window, qoff = case
    q = jnp.asarray(RNG.normal(size=(b, hq, sq, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, hkv, sk_, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, hkv, sk_, d)), dtype)
    got = fk.flash_attention_pallas(q, k, v, causal=causal, window=window,
                                    q_offset=qoff, interpret=True,
                                    block_q=64, block_k=64)
    exp = fr.flash_attention(q, k, v, causal=causal, window=window,
                             q_offset=qoff)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_kv_len_mask():
    q = jnp.asarray(RNG.normal(size=(1, 2, 8, 64)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 2, 128, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 2, 128, 64)), jnp.float32)
    got = fk.flash_attention_pallas(q, k, v, causal=False, kv_len=50,
                                    interpret=True, block_q=8, block_k=32)
    exp = fr.flash_attention(q, k, v, causal=False, kv_len=50)
    np.testing.assert_allclose(got, exp, rtol=2e-4, atol=2e-4)


def test_flash_matches_model_attend():
    """Kernel semantics == the XLA model path (layers.attend)."""
    from repro.models.layers import attend
    b, hq, hkv, s, d = 1, 4, 2, 96, 32
    q = jnp.asarray(RNG.normal(size=(b, hq, s, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)
    got_xla = attend(q, k, v, q_pos=pos, kv_pos=pos, causal=True, q_chunk=32)
    got_pl = fk.flash_attention_pallas(q, k, v, causal=True, interpret=True,
                                       block_q=32, block_k=32)
    np.testing.assert_allclose(got_xla, got_pl, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# segment reduce
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("n,s,bn,bs", [
    (1000, 37, 256, 128), (64, 8, 64, 64), (513, 100, 128, 64),
])
def test_segment_reduce_vs_ref(op, n, s, bn, bs):
    vals = jnp.asarray(RNG.normal(size=n), jnp.float32)
    segs = jnp.asarray(np.sort(RNG.integers(0, s, n)).astype(np.int32))
    got = sk.segment_reduce_pallas(vals, segs, s, op, interpret=True,
                                   block_n=bn, block_s=bs)
    exp = sr.segment_reduce(vals, segs, s, op)
    np.testing.assert_allclose(got, exp, rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(ids=st.lists(st.integers(0, 9), min_size=1, max_size=64))
def test_segment_reduce_property(ids):
    vals = jnp.ones((len(ids),), jnp.float32)
    segs = jnp.asarray(np.array(sorted(ids), np.int32))
    got = sk.segment_reduce_pallas(vals, segs, 10, "sum", interpret=True,
                                   block_n=32, block_s=16)
    counts = np.bincount(np.array(ids), minlength=10)
    np.testing.assert_allclose(got, counts)


def test_segment_reduce_out_of_range_dropped():
    vals = jnp.array([1., 2., 3.], jnp.float32)
    segs = jnp.array([0, 99, 1], jnp.int32)
    got = sk.segment_reduce_pallas(vals, segs, 2, "sum", interpret=True,
                                   block_n=8, block_s=8)
    np.testing.assert_allclose(got, [1., 3.])


# ---------------------------------------------------------------------------
# hash partition
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,k,p", [(500, 2, 16), (100, 1, 3), (1025, 3, 64)])
def test_hash_partition_vs_ref(n, k, p):
    cols = []
    for i in range(k):
        if i % 2:
            cols.append(jnp.asarray(RNG.normal(size=n), jnp.float32))
        else:
            cols.append(jnp.asarray(RNG.integers(0, 1000, n), jnp.int32))
    valid = jnp.asarray(RNG.random(n) < 0.8)
    keys = jnp.stack([_as_u32(c) for c in cols])
    dg, hg = hk.hash_partition_pallas(keys, valid, p, interpret=True,
                                      block_n=128)
    de, he = hr.hash_partition(cols, p, valid)
    np.testing.assert_array_equal(dg, de)
    np.testing.assert_array_equal(hg, he)
    # histogram counts exactly the valid rows
    assert int(hg.sum()) == int(valid.sum())


def test_hash_partition_determinism_and_balance():
    n, p = 4096, 16
    col = jnp.arange(n, dtype=jnp.int32)
    valid = jnp.ones((n,), bool)
    d1, h1 = hr.hash_partition([col], p, valid)
    d2, _ = hr.hash_partition([col], p, valid)
    np.testing.assert_array_equal(d1, d2)
    # murmur-style hash should balance sequential keys decently
    assert int(h1.max()) < 2 * n // p
