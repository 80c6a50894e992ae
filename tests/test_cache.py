"""Ring-buffer KV cache properties (hypothesis)."""
import jax.numpy as jnp
import numpy as np
from _mini_hypothesis import given, settings, strategies as st

from repro.runtime.cache import (batched_decode_mask, decode_mask, kv_write,
                                 prefill_mask)


@given(size=st.integers(2, 16), n_writes=st.integers(1, 40),
       window=st.sampled_from([0, 4, 8]))
@settings(max_examples=40, deadline=None)
def test_ring_buffer_semantics(size, n_writes, window):
    # B=2 with DIVERGED per-sequence positions (as after a batched
    # speculative commit): sequence b's write stream starts at offset[b]
    B, H, hd = 2, 1, 4
    offset = [0, 3]
    ck = jnp.zeros((B, size, H, hd))
    cv = jnp.zeros((B, size, H, hd))
    kp = jnp.full((B, size), -1, jnp.int32)
    for i in range(n_writes):
        vals = np.array([offset[b] + i for b in range(B)], np.float32)
        k = jnp.asarray(vals[:, None, None, None]
                        * np.ones((B, 1, H, hd), np.float32))
        ck, cv, kp = kv_write(ck, cv, kp, k, k,
                              jnp.asarray(vals, jnp.int32))
    kp_np = np.asarray(kp)
    # per sequence: slot s holds the latest written position congruent to s
    for b in range(B):
        positions = range(offset[b], offset[b] + n_writes)
        for s in range(size):
            expect = max((p for p in positions if p % size == s), default=-1)
            assert kp_np[b, s] == expect, (b, s)
            if expect >= 0:
                assert float(np.asarray(ck)[b, s, 0, 0]) == float(expect)
    # per-sequence decode masks at each sequence's own q_pos
    q = [offset[b] + n_writes for b in range(B)]
    ok = np.asarray(batched_decode_mask(
        kp, jnp.asarray([[qb] for qb in q], jnp.int32), window))  # (B, 1, S)
    for b in range(B):
        ref = np.asarray(decode_mask(kp[b], jnp.asarray(q[b]), window))
        np.testing.assert_array_equal(ok[b, 0], ref)
        for s in range(size):
            valid = kp_np[b, s] >= 0 and kp_np[b, s] <= q[b]
            if window:
                valid = valid and kp_np[b, s] > q[b] - window
            assert ok[b, 0, s] == valid


@given(S=st.integers(1, 24), window=st.sampled_from([0, 3, 7]))
@settings(max_examples=30, deadline=None)
def test_prefill_mask(S, window):
    m = np.asarray(prefill_mask(S, window))
    for q in range(S):
        for k in range(S):
            expect = k <= q and (window == 0 or k > q - window)
            assert m[q, k] == expect
