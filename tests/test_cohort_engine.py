"""Vectorized cohort engine vs the sequential reference oracle.

The contract: for any federation, participant mix, and client-size skew,
one vectorized round produces aggregated params matching the sequential
engine within 1e-5 (identical batch shuffles, identical dropout keys,
identical FedAvg weighting — the dummy padding steps are exact no-ops).
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.recruitment import BALANCED
from repro.data.pipeline import (
    ArrayDataset,
    ClientDataset,
    build_client_datasets,
    build_cohort_schedule,
    cohort_steps_per_epoch,
)
from repro.data.synth_eicu import CohortConfig, generate_cohort
from repro.federated import Federation, FederationConfig
from repro.federated.cohort import CohortTrainer
from repro.federated.fedavg import aggregate, aggregate_stacked, tree_allclose
from repro.federated.server import FederatedConfig, FederatedServer
from repro.launch.mesh import make_host_mesh
from repro.models.gru import GRUConfig, init_gru, make_loss_fn
from repro.obs.trace import Tracer
from repro.optim.adamw import AdamW
from repro.privacy.dp import DPConfig

SEQ_LEN, FEAT = 6, 38  # short stays keep the GRU scan cheap


def make_client(client_id: int, n: int, rng: np.random.Generator) -> ClientDataset:
    x = rng.normal(size=(n, SEQ_LEN, FEAT)).astype(np.float32)
    y = rng.uniform(0.5, 20.0, size=n).astype(np.float32)
    ds = ArrayDataset(x, y)
    return ClientDataset(client_id=client_id, train=ds, val=ds)


@pytest.fixture(scope="module")
def model():
    cfg = GRUConfig(input_dim=FEAT, hidden_dim=8, num_layers=2)
    return cfg, make_loss_fn(cfg), init_gru(jax.random.key(1), cfg)


def run_engines(clients, params0, loss_fn, **cfg_kwargs):
    opt = AdamW(learning_rate=5e-3, weight_decay=5e-3)
    outs = {}
    for engine in ("sequential", "vectorized"):
        fed = FederatedConfig(engine=engine, **cfg_kwargs)
        outs[engine] = FederatedServer(fed, clients, loss_fn, opt).run(params0)
    return outs["sequential"], outs["vectorized"]


def assert_params_close(a, b, atol=1e-5):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=atol, rtol=0)


# --------------------------------------------------------------------------
# parity against the sequential oracle
# --------------------------------------------------------------------------

def test_round_parity_16_clients_uneven_sizes(model):
    """The acceptance bar: 16 clients with heavy size skew (so the padded
    schedule is full of masked dummy batches) agree within 1e-5."""
    _, loss_fn, params0 = model
    rng = np.random.default_rng(0)
    sizes = [3, 5, 8, 13, 16, 21, 30, 33, 40, 47, 55, 64, 65, 90, 120, 130]
    clients = [make_client(i, n, rng) for i, n in enumerate(sizes)]
    seq, vec = run_engines(
        clients, params0, loss_fn, rounds=1, local_epochs=2, batch_size=32, seed=0
    )
    assert_params_close(seq.params, vec.params)
    assert seq.total_local_steps == vec.total_local_steps
    np.testing.assert_allclose(
        [r.mean_local_loss for r in seq.history],
        [r.mean_local_loss for r in vec.history],
        atol=1e-5,
    )


def test_multiround_parity_with_participation(model):
    """Across rounds with random 50% participation the engines consume the
    numpy RNG identically, so they select the same participants and stay
    in lockstep."""
    _, loss_fn, params0 = model
    rng = np.random.default_rng(1)
    clients = [make_client(i, int(n), rng) for i, n in enumerate(rng.integers(4, 70, 12))]
    seq, vec = run_engines(
        clients, params0, loss_fn,
        rounds=3, local_epochs=1, batch_size=16, participation_fraction=0.5, seed=7,
    )
    for rs, rv in zip(seq.history, vec.history):
        assert rs.participant_ids == rv.participant_ids
    assert_params_close(seq.params, vec.params)


def test_recruitment_composition(model):
    """Recruitment runs before the engine choice: both engines build the same
    recruited federation and agree on the trained params."""
    _, loss_fn, params0 = model
    cohort = generate_cohort(CohortConfig().scaled(0.02), seed=0)
    clients = build_client_datasets(cohort)
    cfg = GRUConfig()  # the real cohort's 38-feature, 24h shape
    seq, vec = run_engines(
        clients, init_gru(jax.random.key(0), cfg), make_loss_fn(cfg),
        rounds=1, local_epochs=1, recruitment=BALANCED, seed=0,
    )
    assert vec.recruitment is not None
    assert seq.federation_ids.tolist() == vec.federation_ids.tolist()
    assert 0 < len(vec.federation_ids) < len(clients)
    assert_params_close(seq.params, vec.params)


def test_chunked_cohort_matches_unchunked(model):
    """cohort_chunk only bounds memory; the aggregate is unchanged."""
    _, loss_fn, params0 = model
    rng = np.random.default_rng(2)
    clients = [make_client(i, int(n), rng) for i, n in enumerate(rng.integers(4, 50, 10))]
    opt = AdamW(learning_rate=5e-3, weight_decay=5e-3)
    results = []
    for chunk in (None, 3):
        fed = FederatedConfig(
            rounds=1, local_epochs=2, batch_size=16, engine="vectorized",
            cohort_chunk=chunk, seed=0,
        )
        results.append(FederatedServer(fed, clients, loss_fn, opt).run(params0).params)
    assert_params_close(results[0], results[1], atol=1e-6)


def test_shard_map_path_on_host_mesh(model):
    """The shard_map multi-device path degenerates correctly on a 1-device
    data mesh and still matches the plain vmap result."""
    _, loss_fn, params0 = model
    rng = np.random.default_rng(3)
    clients = [make_client(i, 20, rng) for i in range(4)]
    opt = AdamW(learning_rate=5e-3, weight_decay=5e-3)
    outs = []
    for mesh in (None, make_host_mesh()):
        fed = FederatedConfig(
            rounds=1, local_epochs=1, batch_size=16, engine="vectorized", mesh=mesh, seed=0
        )
        outs.append(FederatedServer(fed, clients, loss_fn, opt).run(params0).params)
    assert_params_close(outs[0], outs[1], atol=1e-6)


# --------------------------------------------------------------------------
# schedule + aggregation building blocks
# --------------------------------------------------------------------------

def test_cohort_schedule_shapes_and_masking():
    rng = np.random.default_rng(0)
    data = [
        ArrayDataset(rng.normal(size=(n, 3, 4)).astype(np.float32), np.ones(n, np.float32))
        for n in (5, 16, 33)
    ]
    batch, epochs = 16, 2
    assert cohort_steps_per_epoch([5, 16, 33], batch) == 3
    sched = build_cohort_schedule(data, batch, epochs, rng)
    assert sched.x.shape == (3, 6, 16, 3, 4)
    assert sched.y.shape == (3, 6, 16) and sched.mask.shape == (3, 6, 16)
    # real steps per client = ceil(n/B) per epoch
    np.testing.assert_array_equal(sched.step_valid.sum(axis=1), [2, 2, 6])
    assert sched.real_steps == 10
    # dummy steps carry an all-zero example mask; real steps cover n examples
    np.testing.assert_allclose(sched.mask.sum(axis=(1, 2)), [2 * 5, 2 * 16, 2 * 33])
    assert sched.mask[~sched.step_valid].sum() == 0
    np.testing.assert_array_equal(sched.weights, [5, 16, 33])


def test_schedule_consumes_rng_like_sequential():
    """Client-major permutation order: a schedule built from the same seed
    yields the same batches the per-client iterator would."""
    n = 20
    x = np.arange(n * 2, dtype=np.float32).reshape(n, 2)
    y = np.arange(n, dtype=np.float32)
    ds = ArrayDataset(x, y)
    sched = build_cohort_schedule([ds, ds], 8, 1, np.random.default_rng(5))
    rng = np.random.default_rng(5)
    for c in range(2):
        for t, (xb, yb, mb) in enumerate(ds.padded_batches(8, rng)):
            np.testing.assert_array_equal(sched.x[c, t], xb)
            np.testing.assert_array_equal(sched.y[c, t], yb)
            np.testing.assert_array_equal(sched.mask[c, t], mb)


def test_aggregate_stacked_matches_listwise():
    rng = np.random.default_rng(4)
    trees = [
        {"w": rng.normal(size=(3, 2)).astype(np.float32), "b": rng.normal(size=4).astype(np.float32)}
        for _ in range(5)
    ]
    weights = rng.uniform(1, 100, 5)
    stacked = jax.tree.map(lambda *ls: np.stack(ls), *trees)
    assert tree_allclose(
        aggregate_stacked(stacked, weights), aggregate(trees, weights), atol=1e-6
    )


def test_unknown_engine_rejected():
    with pytest.raises(ValueError):
        FederatedConfig(engine="warp-drive")


def test_cohort_trainer_key_count_mismatch(model):
    _, loss_fn, params0 = model
    rng = np.random.default_rng(6)
    trainer = CohortTrainer(
        loss_fn, AdamW(), batch_size=16, local_epochs=1
    )
    clients = [make_client(0, 8, rng)]
    with pytest.raises(ValueError):
        trainer.train_cohort(params0, clients, rng, [])


def test_cohort_chunk_zero_rejected(model):
    """chunk=0 must raise, not silently disable chunking (falsy-0 trap)."""
    _, loss_fn, params0 = model
    rng = np.random.default_rng(6)
    trainer = CohortTrainer(loss_fn, AdamW(), batch_size=16, local_epochs=1, cohort_chunk=0)
    clients = [make_client(0, 8, rng)]
    with pytest.raises(ValueError, match="cohort_chunk"):
        trainer.train_cohort(params0, clients, rng, [jax.random.key(0)])


def test_single_compilation_across_rounds(model):
    """The server pins steps_per_epoch to the federation-wide max, so rounds
    with different (randomly sampled) participant mixes reuse one compiled
    round function instead of retracing on every new cohort shape."""
    _, loss_fn, params0 = model
    rng = np.random.default_rng(8)
    # heavy size skew: per-cohort max steps would differ round to round
    sizes = [4, 6, 9, 30, 60, 90, 110, 140]
    clients = [make_client(i, n, rng) for i, n in enumerate(sizes)]
    opt = AdamW(learning_rate=5e-3, weight_decay=5e-3)
    fed = FederatedConfig(
        rounds=4, local_epochs=1, batch_size=32, engine="vectorized",
        participation_fraction=0.5, seed=3,
    )
    server = FederatedServer(fed, clients, loss_fn, opt)
    out = server.run(params0)
    mixes = {tuple(sorted(r.participant_ids)) for r in out.history}
    assert len(mixes) > 1  # the rounds really did sample different cohorts
    assert server.cohort_trainer._round._cache_size() == 1


def test_engine_default_is_vectorized():
    assert FederatedConfig().engine == "vectorized"
    assert dataclasses.replace(FederatedConfig(), engine="sequential").engine == "sequential"


# --------------------------------------------------------------------------
# the FedAvg fold: a round's programs start the sum and divide it
# --------------------------------------------------------------------------

# Six clients, so chunks of 1 and of 3 split them evenly.
FOLD_SIZES = (9, 23, 4, 17, 30, 12)
STAGINGS = ("resident", "rebuild")
DPS = {"plain": None, "dp": DPConfig(clip_norm=1.0, noise_multiplier=1.1)}
CHUNKS = (None, 1, 3)


class RoundSpy:
    """Wraps a trainer's round program and records each call's outputs and,
    at a round's last program, the weighted sum that program divides: the
    same program on copies of its inputs, with no ``total``."""

    def __init__(self, trainer: CohortTrainer):
        self.inner, self.outputs, self.sums = trainer._round, [], []
        trainer._round = self

    def __call__(self, params, acc, *args, total=None, **kwargs):
        if total is not None:
            acc_copy, args_copy = jax.tree.map(jnp.copy, (acc, args))
            wsum, _ = self.inner(params, acc_copy, *args_copy, **kwargs)
            self.sums.append((wsum, total))
        out = self.inner(params, acc, *args, total=total, **kwargs)
        self.outputs.append(out)
        return out


@pytest.fixture(scope="module")
def small_model():
    cfg = GRUConfig(input_dim=FEAT, hidden_dim=4, num_layers=1)
    return make_loss_fn(cfg), init_gru(jax.random.key(1), cfg)


@pytest.fixture(scope="module")
def fold_round(small_model):
    """One round over the FOLD_SIZES clients by (staging, dp, chunk), each
    run once: the returned params, per-client losses, stats and spy."""
    loss_fn, params0 = small_model
    clients = [make_client(i, n, np.random.default_rng(i)) for i, n in enumerate(FOLD_SIZES)]
    runs = {}

    def run(staging, dp, chunk):
        if (staging, dp, chunk) not in runs:
            trainer = CohortTrainer(
                loss_fn, AdamW(learning_rate=5e-3, weight_decay=5e-3), batch_size=8,
                local_epochs=2, cohort_chunk=chunk, staging=staging, dp=DPS[dp],
            )
            spy = RoundSpy(trainer)
            keys = list(jax.random.split(jax.random.key(4), len(clients)))
            params, losses, _ = trainer.train_cohort(
                params0, clients, np.random.default_rng(5), keys
            )
            runs[staging, dp, chunk] = params, losses, trainer.last_round_stats, spy
        return runs[staging, dp, chunk]

    return run


fold_grid = pytest.mark.parametrize(
    "staging,dp,chunk",
    [(s, d, c) for s in STAGINGS for d in DPS for c in CHUNKS],
)


@fold_grid
def test_round_params_are_the_mean_of_the_rounds_sum(
    fold_round, small_model, staging, dp, chunk
):
    """Bit for bit, the round's params are its last program's weighted sum
    divided by the round's weight, in the params' dtype."""
    _, params0 = small_model
    params, _, _, spy = fold_round(staging, dp, chunk)
    ((wsum, total),) = spy.sums
    assert total == np.float32(sum(FOLD_SIZES))
    expected = jax.tree.map(lambda s, p: (s / total).astype(p.dtype), wsum, params0)
    for got, want in zip(jax.tree.leaves(params), jax.tree.leaves(expected)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@fold_grid
def test_round_dispatches_one_program_per_chunk(fold_round, staging, dp, chunk):
    """A one-chunk round is one program call, and every round returns its
    last program's own output leaves: no per-leaf op runs after it."""
    params, _, stats, spy = fold_round(staging, dp, chunk)
    chunks = math.ceil(len(FOLD_SIZES) / (chunk or len(FOLD_SIZES)))
    assert len(spy.outputs) == stats["programs"] == stats["chunks"] == chunks
    last_params, _ = spy.outputs[-1]
    assert all(a is b for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(last_params)))


@pytest.mark.parametrize(
    "staging,dp,chunk",
    [(s, d, c) for s in STAGINGS for d in DPS for c in CHUNKS if c is not None],
)
def test_chunked_round_matches_one_chunk(fold_round, staging, dp, chunk):
    one_params, one_losses, _, _ = fold_round(staging, dp, None)
    params, losses, _, _ = fold_round(staging, dp, chunk)
    assert_params_close(one_params, params, atol=1e-6)
    np.testing.assert_allclose(losses, one_losses, atol=1e-6, rtol=0)


@pytest.mark.parametrize("staging", STAGINGS)
@pytest.mark.parametrize("chunk", CHUNKS)
def test_chunked_federation_builds_a_program_per_chunk_role(small_model, staging, chunk):
    """First, middle and last chunk each have a program of their own; a
    federation builds no more than its rounds' chunk roles need, and the
    round span counts the programs a round dispatched."""
    loss_fn, params0 = small_model
    clients = [make_client(i, n, np.random.default_rng(i)) for i, n in enumerate(FOLD_SIZES)]
    tracer = Tracer()
    config = FederationConfig(
        rounds=2, local_epochs=1, batch_size=8, seed=0,
        cohort_chunk=chunk, staging=staging,
    )
    fed = Federation(config, clients, loss_fn, AdamW(learning_rate=5e-3), tracer=tracer)
    fed.run(params0)
    chunks = math.ceil(len(FOLD_SIZES) / (chunk or len(FOLD_SIZES)))
    assert fed.cohort_trainer._round._cache_size() == min(chunks, 3)
    spans = tracer.spans("round")
    assert len(spans) == 2
    assert all(span.args["programs"] == chunks for span in spans)
