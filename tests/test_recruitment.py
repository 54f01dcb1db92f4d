"""Unit + property tests for the paper's client recruitment (core contribution)."""

import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.histogram import LOS_BIN_EDGES, l1_divergence, normalize, target_histogram
from repro.core.recruitment import (
    BALANCED,
    ClientStats,
    RecruitmentConfig,
    StreamingRecruiter,
    StreamingRecruitmentConfig,
    recruit,
    recruit_streaming,
    recruitment_curve,
    representativeness,
)

NUM_BINS = len(LOS_BIN_EDGES) - 1


def make_stats(counts_list):
    return [
        ClientStats(client_id=i, counts=np.asarray(c, dtype=np.int64), n=int(np.sum(c)))
        for i, c in enumerate(counts_list)
    ]


# --------------------------------------------------------------------------
# histogram
# --------------------------------------------------------------------------

def test_los_bins_match_paper():
    # paper: [0,1), [1,2), ..., [7,8), [8,14), [14, inf) — ten bins
    assert NUM_BINS == 10
    y = np.array([0.5, 1.5, 7.9, 8.0, 13.99, 14.0, 99.0])
    h = target_histogram(y)
    assert h[0] == 1 and h[1] == 1 and h[7] == 1
    assert h[8] == 2          # [8, 14)
    assert h[9] == 2          # [14, inf)
    assert h.sum() == len(y)


def test_normalize_zero_safe():
    assert normalize(np.zeros(10)).sum() == 0.0


def test_l1_divergence_bounds():
    a = np.array([10, 0, 0]); b = np.array([0, 0, 10])
    assert l1_divergence(a, a) == 0.0
    assert l1_divergence(a, b) == pytest.approx(2.0)  # disjoint supports


# --------------------------------------------------------------------------
# representativeness (eq. 4)
# --------------------------------------------------------------------------

def test_identical_distributions_rank_by_size():
    # same shape, different n: nu differs only through gamma_sa * n^-1/2
    base = np.array([5, 3, 2, 0, 0, 0, 0, 0, 0, 0])
    stats = make_stats([base * 2, base * 8, base * 32])
    nu = representativeness(stats, RecruitmentConfig(gamma_dv=0.5, gamma_sa=0.5))
    assert nu[0] > nu[1] > nu[2]  # bigger client = more representative (lower nu)


def test_divergent_client_penalized():
    typical = np.array([50, 30, 10, 5, 2, 1, 1, 1, 0, 0])
    outlier = np.array([0, 0, 0, 0, 0, 0, 0, 0, 30, 70])  # long-stay-only hospital
    stats = make_stats([typical, typical, typical, outlier])
    nu = representativeness(stats, RecruitmentConfig(gamma_dv=1.0, gamma_sa=0.0))
    assert nu[3] > nu[:3].max()


def test_gamma_weights_move_nu():
    a = np.array([50, 30, 20, 0, 0, 0, 0, 0, 0, 0])
    b = np.array([1, 1, 1, 1, 1, 1, 1, 1, 1, 1])
    stats = make_stats([a, b])
    qg = representativeness(stats, RecruitmentConfig(gamma_dv=1.0, gamma_sa=0.01))
    dg = representativeness(stats, RecruitmentConfig(gamma_dv=0.01, gamma_sa=1.0))
    # quality-greedy cares about shape, data-greedy about size
    assert not np.allclose(qg, dg)


# --------------------------------------------------------------------------
# recruitment (threshold crossing)
# --------------------------------------------------------------------------

def test_gamma_th_one_recruits_everyone():
    rng = np.random.default_rng(0)
    stats = make_stats([rng.integers(1, 100, NUM_BINS) for _ in range(23)])
    res = recruit(stats, RecruitmentConfig(gamma_th=1.0))
    assert res.num_recruited == 23
    assert sorted(res.recruited_ids.tolist()) == list(range(23))


def test_recruited_are_lowest_nu():
    rng = np.random.default_rng(1)
    stats = make_stats([rng.integers(1, 100, NUM_BINS) for _ in range(40)])
    res = recruit(stats, BALANCED)
    nu = res.nu
    recruited_nu = nu[np.isin(res.client_ids, res.recruited_ids)]
    excluded_nu = nu[~np.isin(res.client_ids, res.recruited_ids)]
    assert res.num_recruited >= 1
    assert recruited_nu.max() <= excluded_nu.min() + 1e-12


# Shared strategies for the recruitment property tests.  A population is a
# list of (histogram, sample-size) pairs — sizes drawn independently of the
# histogram mass so the n^-1/2 term is exercised on its own.
HISTOGRAMS = st.lists(st.integers(0, 50), min_size=NUM_BINS, max_size=NUM_BINS).filter(
    lambda c: sum(c) > 0
)
POPULATIONS = st.lists(
    st.tuples(HISTOGRAMS, st.integers(1, 5000)), min_size=2, max_size=20
)
GAMMA_PAIRS = st.tuples(
    st.floats(0.01, 2.0, allow_nan=False),
    st.floats(0.0, 2.0, allow_nan=False),
)


def make_stats_sized(population):
    """ClientStats with independently drawn histogram and sample size.

    ``n`` is clamped up to the histogram mass (a client can have unlabeled
    stays — mass < n — but never more counts than stays), so the n^-1/2 term
    is still exercised independently of the histogram shape."""
    return [
        ClientStats(
            client_id=i,
            counts=np.asarray(c, dtype=np.int64),
            n=max(int(n), int(np.sum(c))),
        )
        for i, (c, n) in enumerate(population)
    ]


@settings(max_examples=30, deadline=None)
@given(
    data=st.lists(
        st.lists(st.integers(0, 50), min_size=NUM_BINS, max_size=NUM_BINS).filter(
            lambda c: sum(c) > 0
        ),
        min_size=2,
        max_size=25,
    ),
    gammas=st.tuples(
        st.floats(0.01, 2.0, allow_nan=False),
        st.floats(0.0, 2.0, allow_nan=False),
    ),
)
def test_property_recruitment_invariants(data, gammas):
    """For any population and weights: recruited set is a non-empty subset,
    nu is finite-positive, and num_recruited is monotone in gamma_th."""
    stats = make_stats(data)
    gdv, gsa = gammas
    counts = []
    for gth in (0.05, 0.25, 0.5, 0.75, 1.0):
        cfg = RecruitmentConfig(gamma_dv=gdv, gamma_sa=gsa, gamma_th=gth)
        res = recruit(stats, cfg)
        assert 1 <= res.num_recruited <= len(stats)
        assert np.all(np.isfinite(res.nu)) and np.all(res.nu >= 0)
        assert len(set(res.recruited_ids.tolist())) == res.num_recruited
        counts.append(res.num_recruited)
    assert counts == sorted(counts)          # monotone in gamma_th
    assert counts[-1] == len(stats)          # gamma_th = 1 -> everyone


@settings(max_examples=20, deadline=None)
@given(perm_seed=st.integers(0, 2**31 - 1))
def test_property_order_invariance(perm_seed):
    """Recruitment outcome is invariant to client presentation order."""
    rng = np.random.default_rng(7)
    data = [rng.integers(1, 60, NUM_BINS) for _ in range(17)]
    stats = make_stats(data)
    res_a = recruit(stats, BALANCED)
    perm = np.random.default_rng(perm_seed).permutation(len(stats))
    res_b = recruit([stats[i] for i in perm], BALANCED)
    assert sorted(res_a.recruited_ids.tolist()) == sorted(res_b.recruited_ids.tolist())


@settings(max_examples=25, deadline=None)
@given(population=POPULATIONS, gammas=GAMMA_PAIRS)
def test_property_greedy_threshold_crossing(population, gammas):
    """Eq. 5, exactly: recruitment is the shortest ascending-nu prefix whose
    cumulative representativeness reaches iota — plus the crossing client."""
    stats = make_stats_sized(population)
    gdv, gsa = gammas
    cfg = RecruitmentConfig(gamma_dv=gdv, gamma_sa=gsa, gamma_th=0.35)
    res = recruit(stats, cfg)
    order = np.argsort(res.nu, kind="stable")
    k = res.num_recruited
    # the recruited ids ARE the ascending-nu greedy prefix, in nu order
    np.testing.assert_array_equal(res.recruited_ids, res.client_ids[order][:k])
    cumulative = np.cumsum(res.nu[order])
    assert res.iota == pytest.approx(cfg.gamma_th * res.nu_g)
    if k < len(stats):
        # sum through the recruited prefix crossed the threshold ...
        assert cumulative[k - 1] >= res.iota - 1e-9
    if k >= 2:
        # ... and no shorter prefix did (the one before the crosser is below)
        assert cumulative[k - 2] < res.iota + 1e-9


@settings(max_examples=25, deadline=None)
@given(population=POPULATIONS, gammas=GAMMA_PAIRS)
def test_property_iota_monotone_and_nested(population, gammas):
    """gamma_th up => iota up and the recruited set only ever grows (the
    greedy order is fixed by nu, so recruitment sets are nested prefixes),
    reaching the full population at gamma_th = 1.0."""
    stats = make_stats_sized(population)
    gdv, gsa = gammas
    prev_iota, prev_ids = -np.inf, set()
    for gth in (0.05, 0.2, 0.5, 0.8, 1.0):
        res = recruit(stats, RecruitmentConfig(gamma_dv=gdv, gamma_sa=gsa, gamma_th=gth))
        assert res.iota >= prev_iota - 1e-12
        ids = set(res.recruited_ids.tolist())
        assert prev_ids <= ids
        prev_iota, prev_ids = res.iota, ids
    assert len(prev_ids) == len(stats)  # gamma_th = 1.0 recruits everyone


@settings(max_examples=20, deadline=None)
@given(population=POPULATIONS, perm_seed=st.integers(0, 2**31 - 1))
def test_property_permutation_invariance_random_populations(population, perm_seed):
    """For arbitrary drawn populations, recruitment does not depend on the
    order clients are presented in: nu values travel with their client and
    the recruited nu multiset is unchanged.  (Ties in nu may legitimately
    swap *which* tied client crosses the threshold, so id-set equality is
    only asserted when all nu are distinct.)"""
    stats = make_stats_sized(population)
    perm = np.random.default_rng(perm_seed).permutation(len(stats))
    res_a = recruit(stats, BALANCED)
    res_b = recruit([stats[int(i)] for i in perm], BALANCED)
    np.testing.assert_allclose(res_a.nu[perm], res_b.nu, rtol=0, atol=0)
    assert res_a.num_recruited == res_b.num_recruited
    assert res_a.nu_g == pytest.approx(res_b.nu_g)
    nu_by_id = {int(i): float(v) for i, v in zip(res_a.client_ids, res_a.nu)}
    recruited_nu_a = sorted(nu_by_id[int(i)] for i in res_a.recruited_ids)
    recruited_nu_b = sorted(nu_by_id[int(i)] for i in res_b.recruited_ids)
    np.testing.assert_allclose(recruited_nu_a, recruited_nu_b, rtol=0, atol=0)
    if len(set(res_a.nu.tolist())) == len(stats):
        assert sorted(res_a.recruited_ids.tolist()) == sorted(res_b.recruited_ids.tolist())


def test_recruitment_curve_matches_paper_shape():
    """Fig. 2: num recruited grows with gamma_th, hits all clients at 1.0."""
    rng = np.random.default_rng(3)
    stats = make_stats([rng.integers(1, 100, NUM_BINS) * rng.integers(1, 50) for _ in range(189)])
    curve = recruitment_curve(stats, BALANCED, [0.05, 0.1, 0.3, 0.6, 1.0])
    ns = [n for _, n in curve]
    assert ns == sorted(ns)
    assert ns[-1] == 189
    assert ns[0] < 189 // 2  # low threshold recruits a minority


def test_invalid_configs_raise():
    with pytest.raises(ValueError):
        RecruitmentConfig(gamma_th=0.0)
    with pytest.raises(ValueError):
        RecruitmentConfig(gamma_th=1.5)
    with pytest.raises(ValueError):
        RecruitmentConfig(gamma_dv=-1.0)
    with pytest.raises(ValueError):
        ClientStats(client_id=0, counts=np.ones(10), n=0)


# --------------------------------------------------------------------------
# disclosure validation + mass-normalized divergence (bugfix regressions)
# --------------------------------------------------------------------------

def test_counts_exceeding_n_rejected():
    # a histogram can never count more stays than the client reports having
    with pytest.raises(ValueError, match="exceeds reported n"):
        ClientStats(client_id=3, counts=np.full(10, 2), n=4)
    # fewer is fine: stays may lack an LoS label
    ClientStats(client_id=3, counts=np.full(10, 2), n=40)


def test_divergence_normalized_by_histogram_mass():
    """Two clients with the *same* LoS distribution must get the same
    divergence term even if one has unlabeled stays (mass < n).  The old
    code divided by n, under-scaling the partially-labeled client's p_local
    so it no longer summed to 1 and its divergence was biased upward."""
    shape = np.array([30, 10, 5, 3, 2, 0, 0, 0, 0, 0])
    fully = ClientStats(client_id=0, counts=shape, n=int(shape.sum()))
    partial = ClientStats(client_id=1, counts=shape, n=int(shape.sum()) * 2)
    nu = representativeness([fully, partial], RecruitmentConfig(gamma_dv=1.0, gamma_sa=0.0))
    assert nu[0] == pytest.approx(nu[1], abs=1e-12)


# --------------------------------------------------------------------------
# threshold-crossing edges (bugfix regressions)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n", [3, 4, 5, 7])
def test_exact_tie_recruits_through_crossing_only(n):
    """iota landing exactly on a cumulative boundary recruits up to and
    including the crossing client — never one past it.  With gamma_dv=0 and
    identical sizes every nu equals n^-1/2, so gamma_th=0.4 over 10 clients
    makes the 4th prefix an exact mathematical tie with iota; irrational
    nu values (n=3,5,7) exercise the float-rounding side of the tie."""
    shape = np.array([5, 3, 2, 0, 0, 0, 0, 0, 0, 0])
    stats = [ClientStats(client_id=i, counts=shape * n, n=int(shape.sum()) * n) for i in range(10)]
    cfg = RecruitmentConfig(gamma_dv=0.0, gamma_sa=1.0, gamma_th=0.4)
    res = recruit(stats, cfg)
    assert res.num_recruited == 4


def test_full_threshold_with_zero_nu_population():
    """All-identical distributions with gamma_sa=0 give nu == 0 everywhere;
    gamma_th=1.0 must still recruit the whole population (the old crossing
    logic found iota=0 at the first client and recruited exactly one)."""
    shape = np.array([4, 3, 2, 1, 0, 0, 0, 0, 0, 0])
    stats = [ClientStats(client_id=i, counts=shape, n=int(shape.sum())) for i in range(25)]
    res = recruit(stats, RecruitmentConfig(gamma_dv=1.0, gamma_sa=0.0, gamma_th=1.0))
    assert res.num_recruited == 25
    assert res.nu_g == 0.0


def test_is_recruited_matches_isin():
    rng = np.random.default_rng(11)
    stats = make_stats([rng.integers(1, 100, NUM_BINS) for _ in range(60)])
    res = recruit(stats, BALANCED)
    for cid in res.client_ids:
        assert res.is_recruited(int(cid)) == bool(np.isin(cid, res.recruited_ids))
    assert not res.is_recruited(10_000)


# --------------------------------------------------------------------------
# streaming recruitment (population scale)
# --------------------------------------------------------------------------

def random_population(num, seed=0, lo=1, hi=400):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(num):
        n = int(rng.integers(lo, hi))
        counts = rng.multinomial(n, rng.dirichlet(np.full(NUM_BINS, 0.7)))
        out.append(ClientStats(client_id=i, counts=counts, n=n))
    return out


def test_streaming_exact_parity_at_paper_scale():
    """Populations within the exact buffer (default 1024 >= 10^3) delegate
    to the exact oracle: identical participant sets, nu_g, and iota."""
    stats = random_population(1000, seed=5)
    exact = recruit(stats, BALANCED)
    streamed = recruit_streaming(iter(stats), BALANCED)
    assert streamed.mode == "exact"
    assert sorted(streamed.recruited_ids.tolist()) == sorted(exact.recruited_ids.tolist())
    assert streamed.nu_g == pytest.approx(exact.nu_g, rel=0, abs=0)
    assert streamed.iota == pytest.approx(exact.iota, rel=0, abs=0)
    assert streamed.clients_seen == 1000


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_streaming_sketch_tolerance(seed):
    """Above the exact buffer the sketch path carries a tolerance contract:
    num_recruited within a few percent of the exact oracle and the
    participant sets nearly identical (pool candidates are re-scored
    exactly; only the iota estimate moves the cutoff)."""
    stats = random_population(3000, seed=seed)
    exact = recruit(stats, BALANCED)
    streamed = recruit_streaming(
        iter(stats),
        BALANCED,
        stream=StreamingRecruitmentConfig(exact_buffer=200, pool_size=3000),
    )
    assert streamed.mode == "sketch"
    assert not streamed.pool_exhausted
    rel = abs(streamed.num_recruited - exact.num_recruited) / exact.num_recruited
    assert rel <= 0.05
    overlap = len(set(streamed.recruited_ids) & set(exact.recruited_ids))
    assert overlap / exact.num_recruited >= 0.9
    # the sketch's independent count estimate lands in the same ballpark
    assert abs(streamed.estimated_num_recruited - exact.num_recruited) <= 0.15 * exact.num_recruited


def test_streaming_order_robust():
    """The sketch decision may move the cutoff by a few clients across
    presentation orders, but stays within the tolerance contract."""
    stats = random_population(2500, seed=9)
    base = recruit_streaming(
        iter(stats), BALANCED,
        stream=StreamingRecruitmentConfig(exact_buffer=128, pool_size=2500),
    )
    perm = np.random.default_rng(0).permutation(len(stats))
    shuffled = recruit_streaming(
        (stats[int(i)] for i in perm), BALANCED,
        stream=StreamingRecruitmentConfig(exact_buffer=128, pool_size=2500),
    )
    rel = abs(base.num_recruited - shuffled.num_recruited) / base.num_recruited
    assert rel <= 0.05


def test_streaming_gamma_th_one_recruits_everyone():
    stats = random_population(600, seed=3)
    cfg = RecruitmentConfig(gamma_dv=0.5, gamma_sa=0.5, gamma_th=1.0)
    streamed = recruit_streaming(
        iter(stats), cfg, stream=StreamingRecruitmentConfig(exact_buffer=64, pool_size=32)
    )
    assert streamed.mode == "sketch"
    assert sorted(streamed.recruited_ids.tolist()) == list(range(600))


def test_streaming_pool_exhaustion_flagged():
    """A pool too small to hold the iota crossing truncates num_recruited —
    that must be flagged and warned about, never silent."""
    stats = random_population(800, seed=4)
    with pytest.warns(UserWarning, match="pool"):
        streamed = recruit_streaming(
            iter(stats), BALANCED,
            stream=StreamingRecruitmentConfig(exact_buffer=32, pool_size=24),
        )
    assert streamed.pool_exhausted
    assert streamed.num_recruited == 24


def test_streaming_recruiter_lifecycle():
    stats = random_population(50, seed=6)
    rec = StreamingRecruiter(BALANCED)
    rec.extend(stats)
    first = rec.finalize()
    assert rec.finalize() is first          # idempotent
    with pytest.raises(RuntimeError):
        rec.observe(stats[0])               # sealed after finalize
    with pytest.raises(ValueError):
        StreamingRecruiter(BALANCED).finalize()  # empty stream
    assert first.is_recruited(int(first.recruited_ids[0]))
    excluded = set(range(50)) - set(first.recruited_ids.tolist())
    if excluded:
        assert not first.is_recruited(next(iter(excluded)))
