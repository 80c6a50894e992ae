"""Chunked-parallel mLSTM prefill must be EXACT vs the per-step recurrence
(EXPERIMENTS §Perf hillclimb B) — including state carry across chunks and
ragged tails."""
import jax
import jax.numpy as jnp
import pytest
from _mini_hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.models import xlstm as xl


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("xlstm-125m").reduced()
    p = xl.mlstm_init(cfg, jax.random.PRNGKey(0))
    return cfg, p


@given(S=st.integers(3, 40), chunk=st.sampled_from([4, 8, 16]),
       seed=st.integers(0, 50))
@settings(max_examples=15, deadline=None)
def test_chunked_matches_scan(setup, S, chunk, seed):
    cfg, p = setup
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, S, cfg.d_model),
                          jnp.float32)
    y_scan, st_scan = xl.mlstm_prefill_scan(cfg, p, x)
    y_chunk, st_chunk = xl.mlstm_prefill(cfg, p, x, chunk=chunk)
    assert float(jnp.max(jnp.abs(y_scan - y_chunk))) < 2e-3
    for k in ("C", "n", "m"):
        assert float(jnp.max(jnp.abs(st_scan[k] - st_chunk[k]))) < 2e-3


def test_state_continuation(setup):
    cfg, p = setup
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 30, cfg.d_model),
                          jnp.float32)
    y_full, _ = xl.mlstm_prefill(cfg, p, x, chunk=8)
    y1, st1 = xl.mlstm_prefill(cfg, p, x[:, :13], chunk=8)
    y2, _ = xl.mlstm_prefill(cfg, p, x[:, 13:], state=st1, chunk=8)
    err = float(jnp.max(jnp.abs(jnp.concatenate([y1, y2], 1) - y_full)))
    assert err < 2e-3
