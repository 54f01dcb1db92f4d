"""In-jit DP-SGD: config validation, engine parity, accounting, structure.

The structural tests pin the acceptance criterion that DP noise rides the
*jitted cohort step*: the traced round jaxpr must not grow with the number
of clients (vmap, not a Python loop), and Gaussian sampling (``erf_inv``)
must appear inside the round program itself.
"""

from __future__ import annotations

import functools
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.data import CohortConfig, build_client_datasets, generate_cohort
from repro.federated import Federation, FederationConfig
from repro.federated.cohort import CohortTrainer
from repro.models.gru import GRUConfig, init_gru, make_loss_fn
from repro.optim import AdamW
from repro.privacy.accountant import (
    RdpAccountant,
    epsilon_after,
    rdp_subsampled_gaussian,
)
from repro.privacy.dp import (
    DPConfig,
    add_gaussian_noise,
    per_example_clip_factors,
    resolve_dp,
)


@functools.lru_cache(maxsize=1)
def _fixture():
    cohort = generate_cohort(CohortConfig().scaled(0.02), seed=0)
    clients = build_client_datasets(cohort)[:8]
    mcfg = GRUConfig(dropout=0.0, hidden_dim=8, num_layers=1)
    loss_fn = make_loss_fn(mcfg)
    params0 = init_gru(jax.random.key(0), mcfg)
    return clients, loss_fn, params0


def _run(privacy, engine="vectorized", rounds=2, seed=0):
    clients, loss_fn, params0 = _fixture()
    config = FederationConfig(
        rounds=rounds, local_epochs=1, batch_size=16, seed=seed,
        engine=engine, privacy=privacy,
    )
    fed = Federation(config, clients, loss_fn, AdamW(learning_rate=1e-2))
    return fed.run(params0)


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def _max_abs_diff(a, b):
    return max(
        float(np.max(np.abs(x - y))) for x, y in zip(_leaves(a), _leaves(b))
    )


# ---------------------------------------------------------------------------
# DPConfig / resolve_dp validation


def test_dp_config_rejects_json_strings_and_bools():
    with pytest.raises(TypeError, match="number"):
        DPConfig(clip_norm="0.1")
    with pytest.raises(TypeError, match="number"):
        DPConfig(noise_multiplier="1.0")
    with pytest.raises(TypeError, match="number"):
        DPConfig(noise_multiplier=True)
    with pytest.raises(TypeError, match="number"):
        DPConfig(delta="1e-5")


def test_dp_config_rejects_bad_ranges():
    with pytest.raises(ValueError):
        DPConfig(clip_norm=-1.0)
    with pytest.raises(ValueError):
        DPConfig(clip_norm=0.0)
    with pytest.raises(ValueError):
        DPConfig(noise_multiplier=-0.5)
    with pytest.raises(ValueError):
        DPConfig(delta=0.0)
    with pytest.raises(ValueError):
        DPConfig(delta=1.0)
    # Noise without a finite clip norm has unbounded sensitivity.
    with pytest.raises(ValueError, match="clip_norm"):
        DPConfig(clip_norm=None, noise_multiplier=1.0)


def test_resolve_dp_forms():
    assert resolve_dp(None) is None
    cfg = DPConfig(clip_norm=2.0, noise_multiplier=0.5)
    assert resolve_dp(cfg) is cfg
    from_dict = resolve_dp({"clip_norm": 2.0, "noise_multiplier": 0.5})
    assert from_dict == cfg
    with pytest.raises(ValueError, match="unknown"):
        resolve_dp({"clipnorm": 2.0})
    with pytest.raises(TypeError):
        resolve_dp({"clip_norm": "2.0"})


def test_noise_sigma_and_effective_clip():
    assert DPConfig(clip_norm=2.0, noise_multiplier=1.5).noise_sigma == 3.0
    assert DPConfig(clip_norm=None, noise_multiplier=0.0).effective_clip == float("inf")
    assert DPConfig(clip_norm=None, noise_multiplier=0.0).noise_sigma == 0.0


# ---------------------------------------------------------------------------
# Clip / noise primitives


def test_per_example_clip_factors():
    grads = {"w": jnp.array([[3.0, 4.0], [0.3, 0.4]])}  # norms 5.0 and 0.5
    f = per_example_clip_factors(grads, 1.0)
    np.testing.assert_allclose(np.asarray(f), [0.2, 1.0], rtol=1e-5)


def test_add_gaussian_noise_zero_sigma_is_identity():
    tree = {"a": jnp.arange(4.0), "b": jnp.ones((2, 2))}
    out = add_gaussian_noise(tree, jax.random.key(3), 0.0)
    assert _max_abs_diff(tree, out) == 0.0
    noised = add_gaussian_noise(tree, jax.random.key(3), 1.0)
    assert _max_abs_diff(tree, noised) > 0.0


# ---------------------------------------------------------------------------
# Engine parity and determinism


def test_degenerate_dp_matches_unprotected():
    """noise_multiplier=0, clip_norm=None is the unprotected objective."""
    degenerate = DPConfig(clip_norm=None, noise_multiplier=0.0)
    for engine in ("vectorized", "sequential"):
        base = _run(None, engine=engine)
        dp = _run(degenerate, engine=engine)
        assert _max_abs_diff(base.params, dp.params) < 2e-5, engine


def test_dp_cross_engine_parity():
    dp = DPConfig(clip_norm=1.0, noise_multiplier=1.1)
    vec = _run(dp, engine="vectorized")
    seq = _run(dp, engine="sequential")
    assert _max_abs_diff(vec.params, seq.params) < 2e-5


def test_seeded_dp_run_replays_bitwise():
    dp = DPConfig(clip_norm=1.0, noise_multiplier=1.1)
    a = _run(dp, seed=3)
    b = _run(dp, seed=3)
    assert _max_abs_diff(a.params, b.params) == 0.0
    assert [r.epsilon for r in a.history] == [r.epsilon for r in b.history]


# ---------------------------------------------------------------------------
# Accounting on round records


def test_dp_run_reports_monotone_epsilon():
    dp = DPConfig(clip_norm=1.0, noise_multiplier=1.1)
    result = _run(dp, rounds=3)
    eps = [r.epsilon for r in result.history]
    assert all(e is not None and math.isfinite(e) and e > 0 for e in eps)
    assert eps == sorted(eps) and eps[0] < eps[-1]
    assert result.summary()["epsilon"] == eps[-1]


def test_unprotected_run_reports_no_epsilon():
    result = _run(None)
    assert all(r.epsilon is None for r in result.history)
    assert result.summary()["epsilon"] is None


def test_accountant_basics():
    acc = RdpAccountant(noise_multiplier=1.0, delta=1e-5)
    assert acc.epsilon() == 0.0
    acc.step(0.5)
    e1 = acc.epsilon()
    acc.step(0.5)
    e2 = acc.epsilon()
    assert 0 < e1 < e2
    # More noise, same schedule: strictly tighter epsilon.
    quiet = RdpAccountant(noise_multiplier=2.0, delta=1e-5)
    quiet.step(0.5)
    quiet.step(0.5)
    assert quiet.epsilon() < e2
    # sigma = 0 provides no privacy: honest infinity, not a small number.
    assert RdpAccountant(noise_multiplier=0.0).epsilon() == 0.0
    none = RdpAccountant(noise_multiplier=0.0)
    none.step(0.5)
    assert none.epsilon() == float("inf")


def test_rdp_full_batch_closed_form():
    # q = 1 (no subsampling): RDP of the Gaussian mechanism is alpha/(2 sigma^2).
    sigma, alpha = 1.3, 7
    assert rdp_subsampled_gaussian(1.0, sigma, alpha) == pytest.approx(
        alpha / (2 * sigma**2)
    )
    assert rdp_subsampled_gaussian(0.0, sigma, alpha) == 0.0


def test_epsilon_after_matches_stepped_accountant():
    acc = RdpAccountant(noise_multiplier=1.1, delta=1e-5)
    acc.step(0.25, steps=10)
    assert epsilon_after(
        rounds=10, sampling_rate=0.25, noise_multiplier=1.1, delta=1e-5
    ) == pytest.approx(acc.epsilon())


# ---------------------------------------------------------------------------
# Structural: noise rides the jitted cohort round


def _round_args(params, num_clients, steps=2, batch=4, seq=6, feat=38):
    """A one-chunk round's program: no running sum in, the round's weight
    in, the FedAvg mean out."""
    shape = (num_clients, steps)
    x = jnp.zeros(shape + (batch, seq, feat), jnp.float32)
    y = jnp.zeros(shape + (batch,), jnp.float32)
    m = jnp.ones(shape + (batch,), jnp.float32)
    valid = jnp.ones(shape, bool)
    kd = jnp.stack(
        [jax.random.key_data(jax.random.key(i)) for i in range(num_clients)]
    )
    w = jnp.ones((num_clients,), jnp.float32)
    return (params, None, x, y, m, valid, kd, w, jnp.float32(num_clients))


def _trainer(dp):
    _, loss_fn, _ = _fixture()
    return CohortTrainer(
        loss_fn=loss_fn, optimizer=AdamW(learning_rate=1e-2),
        batch_size=4, local_epochs=1, dp=dp, donate=False,
    )


def test_dp_round_jaxpr_does_not_grow_with_clients():
    """vmap over the stacked client axis — no per-client Python loop."""
    _, _, params0 = _fixture()
    trainer = _trainer(DPConfig(clip_norm=1.0, noise_multiplier=1.1))
    small = str(trainer._round.trace(*_round_args(params0, 4)).jaxpr)
    large = str(trainer._round.trace(*_round_args(params0, 8)).jaxpr)
    assert small.count(" = ") == large.count(" = ")


def test_gaussian_sampling_is_inside_the_round_program():
    _, _, params0 = _fixture()
    dp_jaxpr = str(
        _trainer(DPConfig(clip_norm=1.0, noise_multiplier=1.1))
        ._round.trace(*_round_args(params0, 4)).jaxpr
    )
    plain_jaxpr = str(_trainer(None)._round.trace(*_round_args(params0, 4)).jaxpr)
    # Gaussian sampling lowers through erf_inv; the unprotected round
    # never samples a normal.
    assert "erf_inv" in dp_jaxpr
    assert "erf_inv" not in plain_jaxpr
