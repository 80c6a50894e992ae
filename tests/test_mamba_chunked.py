"""Chunked SSD Mamba2 prefill must be EXACT vs the time-scan recurrence
(EXPERIMENTS §Perf iteration F), incl. state carry and ragged tails."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from _mini_hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.models import mamba2 as mb


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("zamba2-7b").reduced()
    p = mb.mamba_init(cfg, jax.random.PRNGKey(0))
    return cfg, dataclasses.replace(cfg, mamba_chunked=False), p


@given(S=st.integers(3, 40), chunk=st.sampled_from([4, 8, 16]),
       seed=st.integers(0, 50))
@settings(max_examples=12, deadline=None)
def test_chunked_matches_scan(setup, S, chunk, seed):
    cfg, cfg_scan, p = setup
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, S, cfg.d_model),
                          jnp.float32)
    y_scan, st_scan = mb.mamba_prefill(cfg_scan, p, x)
    y_chunk, st_chunk = mb.mamba_prefill(cfg, p, x, chunk=chunk)
    assert float(jnp.max(jnp.abs(y_scan - y_chunk))) < 2e-3
    assert float(jnp.max(jnp.abs(st_scan["ssm"] - st_chunk["ssm"]))) < 2e-3


def test_state_continuation(setup):
    cfg, cfg_scan, p = setup
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 30, cfg.d_model),
                          jnp.float32)
    y_full, _ = mb.mamba_prefill(cfg_scan, p, x)
    y1, st1 = mb.mamba_prefill(cfg, p, x[:, :13], chunk=8)
    y2, _ = mb.mamba_prefill(cfg, p, x[:, 13:], state=st1, chunk=8)
    err = float(jnp.max(jnp.abs(jnp.concatenate([y1, y2], 1) - y_full)))
    assert err < 2e-3
