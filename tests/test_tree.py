"""Verification-tree properties (hypothesis) — paper §III-C1 machinery."""
import numpy as np
import pytest
from _mini_hypothesis import given, settings, strategies as st

from repro.core.speculative import tree as T


def accs_strategy():
    return st.tuples(
        st.integers(2, 5),                        # heads
        st.integers(2, 6),                        # top-k
        st.floats(0.3, 0.9),                      # a1
        st.floats(0.5, 0.95),                     # head decay
        st.floats(0.2, 0.8),                      # rank decay
    ).map(lambda t: T.default_accs(t[0], t[1], t[2], t[3], t[4]))


@given(accs=accs_strategy(), width=st.sampled_from([2, 4, 8, 16]))
@settings(max_examples=25, deadline=None)
def test_tree_is_valid(accs, width):
    spec = T.build_tree_greedy(accs, width)
    assert spec.width <= width
    assert spec.parent[0] == -1
    for i in range(1, spec.width):
        p = spec.parent[i]
        assert 0 <= p < i                          # topo order
        assert spec.depth[i] == spec.depth[p] + 1
        assert spec.mask[i, p] and spec.mask[i, i]
    # every path's prefix is an ancestor chain
    for row in spec.paths:
        for d in range(1, spec.max_depth):
            if row[d] != row[d - 1]:
                assert spec.parent[row[d]] == row[d - 1]


@given(accs=accs_strategy())
@settings(max_examples=15, deadline=None)
def test_acceptance_monotone_in_width(accs):
    als = [T.expected_acceptance_length(T.build_tree_greedy(accs, w), accs)
           for w in (1, 2, 4, 8, 16, 32)]
    assert all(b >= a - 1e-9 for a, b in zip(als, als[1:]))


@given(accs=accs_strategy(), width=st.sampled_from([4, 8, 12]),
       seed=st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_greedy_beats_random_trees(accs, width, seed):
    """Greedy-by-path-product selects the top-W node set => it is optimal
    under the estimator; any random valid tree must not beat it."""
    H, K = accs.shape
    # clamp to the tree capacity (sum of K^d, d<=H) or random growth can
    # exhaust the candidate space and loop forever
    cap = sum(K ** d for d in range(H + 1))
    width = min(width, cap)
    spec = T.build_tree_greedy(accs, width)
    best = T.expected_acceptance_length(spec, accs)
    rng = np.random.default_rng(seed)
    nodes = [(-1, 0, 0)]
    used = set()
    attempts = 0
    while len(nodes) < width and attempts < 10_000:
        attempts += 1
        p = int(rng.integers(0, len(nodes)))
        d = nodes[p][1] + 1
        r = int(rng.integers(0, K))
        if d > H or (p, r) in used:
            continue
        used.add((p, r))
        nodes.append((p, d, r))
    rand_spec = T.spec_from_nodes(nodes)
    rand_al = T.expected_acceptance_length(rand_spec, accs)
    assert best >= rand_al - 1e-9


@given(accs=accs_strategy(), width=st.sampled_from([4, 8]))
@settings(max_examples=10, deadline=None)
def test_refine_never_decreases(accs, width):
    g = T.build_tree_greedy(accs, width)
    r = T.refine_tree(g, accs)
    assert (T.expected_acceptance_length(r, accs)
            >= T.expected_acceptance_length(g, accs) - 1e-12)


def test_width_one_is_sequential():
    spec = T.spec_from_nodes([(-1, 0, 0)])
    accs = T.default_accs()
    assert T.expected_acceptance_length(spec, accs) == pytest.approx(1.0)


def test_table1_regime():
    """Estimator in the paper's Table-I numeric regime (MT-bench row)."""
    accs = T.default_accs(4, 10)
    al2 = T.expected_acceptance_length(T.build_tree(accs, 2), accs)
    al64 = T.expected_acceptance_length(T.build_tree(accs, 64), accs)
    assert 1.5 < al2 < 2.0                        # paper: 1.72
    assert 3.0 < al64 < 5.0                       # paper: 3.34-3.74
