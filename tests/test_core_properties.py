"""Property tests for the paper's core claims (Lemmas 1-3, Theorem 1).

Each hypothesis property maps to a paper statement.
"""
import math

from hypothesis import given, settings, strategies as st

from repro.core import (
    CostParams,
    build_gather_tree,
    build_gather_tree_distributed,
    ceil_log2,
    construction_alpha_rounds,
    lemma2_penalty_bound,
    simulate_gather,
    simulate_scatter,
    theorem1_bound,
)
from repro.core.distributions import NAMES, block_sizes

sizes = st.lists(st.integers(min_value=0, max_value=10_000), min_size=1,
                 max_size=130)
params = CostParams(alpha=2.0, beta=0.01)


@st.composite
def sizes_and_root(draw):
    m = draw(sizes)
    r = draw(st.integers(min_value=0, max_value=len(m) - 1))
    return m, r


# ---------------------------------------------------------------- structure

@given(sizes_and_root())
@settings(max_examples=150, deadline=None)
def test_tree_is_valid_spanning_tree_fixed_root(mr):
    m, r = mr
    t = build_gather_tree(m, root=r)
    t.validate(m)  # spanning, acyclic, sizes=subtree data, contiguous ranges
    assert t.root == r


@given(sizes)
@settings(max_examples=150, deadline=None)
def test_tree_is_valid_spanning_tree_free_root(m):
    t = build_gather_tree(m)
    t.validate(m)


@given(sizes_and_root())
@settings(max_examples=100, deadline=None)
def test_binomial_structure_and_round_budget(mr):
    """Lemma 1/3: ceil(log2 p) data rounds; node degree bounded binomially."""
    m, r = mr
    t = build_gather_tree(m, root=r)
    assert t.rounds <= ceil_log2(len(m))
    for e in t.edges:
        assert 0 <= e.round < ceil_log2(len(m))


@given(sizes_and_root())
@settings(max_examples=100, deadline=None)
def test_rank_order_contiguity(mr):
    """Paper ordering invariant: every message is a consecutive block range
    m_k..m_{k+l} — checked inside validate(); here also per-round disjoint."""
    m, r = mr
    t = build_gather_tree(m, root=r)
    by_round = {}
    for e in t.edges:
        by_round.setdefault(e.round, []).append(e)
    for rnd, es in by_round.items():
        endpoints = [x for e in es for x in (e.child, e.parent)]
        assert len(endpoints) == len(set(endpoints)), (
            "rounds are permutations: disjoint sender/receiver pairs")


# ------------------------------------------------------- distributed == ref

@given(sizes_and_root())
@settings(max_examples=120, deadline=None)
def test_distributed_protocol_matches_centralized_fixed_root(mr):
    m, r = mr
    t = build_gather_tree(m, root=r)
    td, plans, stats = build_gather_tree_distributed(m, root=r)
    assert _edgeset(t) == _edgeset(td)
    assert td.root == t.root == r


@given(sizes)
@settings(max_examples=120, deadline=None)
def test_distributed_protocol_matches_centralized_free_root(m):
    t = build_gather_tree(m)
    td, plans, stats = build_gather_tree_distributed(m)
    assert _edgeset(t) == _edgeset(td)
    assert td.root == t.root


@given(sizes)
@settings(max_examples=100, deadline=None)
def test_lemma3_message_complexity(m):
    """<= 2*ceil(log2 p)-1 dependent phases, constant-size payloads,
    O(p log p) total messages."""
    p = len(m)
    _, plans, stats = build_gather_tree_distributed(m)
    d = ceil_log2(p)
    assert stats.dependent_phases <= construction_alpha_rounds(p) == max(0, 2 * d - 1)
    assert stats.max_payload_scalars <= 4
    assert stats.messages <= 2 * p * max(1, d)
    # paper §3: each plan is a sequence of receives followed by ONE send
    for pl in plans:
        assert pl.send is None or all(rv[4] < pl.send[4] for rv in pl.recvs)


def _edgeset(t):
    return {(e.child, e.parent, e.size, e.round, e.lo, e.hi) for e in t.edges}


# ------------------------------------------------------------- cost bounds

@given(sizes)
@settings(max_examples=150, deadline=None)
def test_theorem1_free_root(m):
    """Lemma 1: d*alpha + beta*sum_{i!=r} m_i exactly bounds the gather."""
    t = build_gather_tree(m)
    sim = simulate_gather(t, params)
    d = ceil_log2(len(m))
    bound = d * params.alpha + params.beta * (sum(m) - m[t.root])
    assert sim <= bound + 1e-9


@given(sizes_and_root())
@settings(max_examples=150, deadline=None)
def test_theorem1_fixed_root_with_lemma2_penalty(mr):
    m, r = mr
    t = build_gather_tree(m, root=r)
    sim = simulate_gather(t, params, include_construction=True)
    bound = (theorem1_bound(m, r, params.alpha, params.beta)
             + lemma2_penalty_bound(t, m, params.beta))
    assert sim <= bound + 1e-9


@given(sizes_and_root())
@settings(max_examples=150, deadline=None)
def test_lemma2_worst_case_penalty_loose_bound(mr):
    """Paper: the penalty is < beta * sum_{i != r} m_i."""
    m, r = mr
    t = build_gather_tree(m, root=r)
    pen = lemma2_penalty_bound(t, m, params.beta)
    assert pen <= params.beta * (sum(m) - m[r]) + 1e-9


@given(sizes)
@settings(max_examples=100, deadline=None)
def test_free_root_meets_lemma1_bound_without_penalty(m):
    """Lemma 1's bound holds with NO penalty term for the chosen root.
    (Note: a fixed root holding a huge block can still beat the free root on
    absolute time, since sum_{i != r} m_i depends on r — hypothesis found
    m=[1,1,0,3]; the paper makes no cross-root claim.)"""
    t = build_gather_tree(m)
    d = ceil_log2(len(m))
    assert simulate_gather(t, params) <= (
        d * params.alpha + params.beta * (sum(m) - m[t.root]) + 1e-9)


@given(sizes_and_root())
@settings(max_examples=100, deadline=None)
def test_scatter_gather_time_symmetry(mr):
    m, r = mr
    t = build_gather_tree(m, root=r)
    g = simulate_gather(t, params, policy="round")
    s = simulate_scatter(t, params)
    assert math.isclose(g, s, rel_tol=1e-9, abs_tol=1e-9)


@given(sizes_and_root())
@settings(max_examples=100, deadline=None)
def test_ready_policy_never_slower_than_round_policy(mr):
    """Non-blocking receives (paper §3) can only help."""
    m, r = mr
    t = build_gather_tree(m, root=r)
    assert (simulate_gather(t, params, policy="ready")
            <= simulate_gather(t, params, policy="round") + 1e-9)


# --------------------------------------------------- degradation (beyond)

@given(sizes_and_root(), st.integers(min_value=1, max_value=200_000))
@settings(max_examples=100, deadline=None)
def test_graceful_degradation_valid_and_never_moves_more_bytes(mr, thr):
    m, r = mr
    base = build_gather_tree(m, root=r)
    deg = build_gather_tree(m, root=r, degrade_threshold=thr)
    deg.validate(m)
    assert deg.root == r
    assert deg.total_bytes_moved() <= base.total_bytes_moved()


# ------------------------------------------------------ paper distributions

def test_paper_distributions_shapes():
    for name in NAMES:
        for p in (1, 2, 5, 37, 64, 113):
            m = block_sizes(name, p, 100, seed=7)
            assert len(m) == p
            t = build_gather_tree(m, root=p // 2)
            t.validate(m)
