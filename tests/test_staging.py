"""Device-resident staging vs the rebuild path vs the sequential oracle.

The contract of ``staging="resident"``: client train arrays are uploaded
once per federation, every round stages only an int32 index plan packed
into a few lanes (``LanePlan``) drawn from the *same* numpy RNG stream as
``build_cohort_schedule``,
and the on-device batch gather reproduces the rebuilt schedule's batches
**bitwise** — so aggregated params match the PR-2 rebuild path and the
sequential oracle within the same 1e-5 the engine parity suite uses,
across chunking, donation, and the shard_map mesh path.  Prefetch (the
double-buffered background staging thread) must be a pure overlap: params
bit-identical on and off.  And the point of it all: per-round
host->device ``bytes_staged`` collapses (>=10x; in practice ~100-900x) at
the paper's 189-client federation.
"""

import threading
import time

import jax
import numpy as np
import pytest

from repro.data.device_cohort import (
    assign_lanes,
    build_device_cohort,
    build_lane_plan,
)
from repro.data.pipeline import (
    ArrayDataset,
    ClientDataset,
    build_cohort_schedule,
)
from repro.federated.cohort import CohortTrainer, chain_split_keys
from repro.federated.server import FederatedConfig, FederatedServer
from repro.federated.staging import StagingPipeline
from repro.launch.mesh import make_data_mesh
from repro.models.gru import GRUConfig, init_gru, make_loss_fn
from repro.optim.adamw import AdamW

SEQ_LEN, FEAT = 4, 6


def make_clients(count: int, rng: np.random.Generator, lo: int = 2, hi: int = 9):
    clients = []
    for i, n in enumerate(rng.integers(lo, hi, count)):
        x = rng.normal(size=(int(n), SEQ_LEN, FEAT)).astype(np.float32)
        y = rng.uniform(0.5, 20.0, size=int(n)).astype(np.float32)
        ds = ArrayDataset(x, y)
        clients.append(ClientDataset(client_id=i, train=ds, val=ds))
    return clients


@pytest.fixture(scope="module")
def model():
    cfg = GRUConfig(input_dim=FEAT, hidden_dim=4, num_layers=1)
    return make_loss_fn(cfg), init_gru(jax.random.key(1), cfg)


def run_server(clients, params0, loss_fn, **cfg_kwargs):
    defaults = dict(rounds=2, local_epochs=2, batch_size=4, seed=0)
    defaults.update(cfg_kwargs)
    fed = FederatedConfig(**defaults)
    opt = AdamW(learning_rate=5e-3, weight_decay=5e-3)
    return FederatedServer(fed, clients, loss_fn, opt).run(params0)


def assert_params_close(a, b, atol=1e-5):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=atol, rtol=0)


# --------------------------------------------------------------------------
# the index plan is the schedule, bit for bit
# --------------------------------------------------------------------------

def packed_plan(sizes, batch, epochs, rng, **kwargs):
    """A lane plan with the cohort packed by first-fit decreasing."""
    steps = [-(-n // batch) * epochs for n in sizes]
    spe = max(-(-n // batch) for n in sizes)
    lanes, width = assign_lanes(steps, np.zeros(len(sizes), int), 1, spe * epochs)
    return build_lane_plan(sizes, batch, epochs, rng, lanes, width, **kwargs)


def test_plan_gathers_schedule_bitwise():
    """Gathering the resident arrays through the packed plan reproduces
    each client's real steps of the rebuilt schedule exactly — x, y and
    mask, in order — the parity foundation."""
    rng = np.random.default_rng(3)
    sizes = (5, 9, 12, 3)
    data = [
        ArrayDataset(
            rng.normal(size=(n, 2, 3)).astype(np.float32),
            rng.uniform(1, 9, size=n).astype(np.float32),
        )
        for n in sizes
    ]
    batch, epochs = 4, 2
    sched = build_cohort_schedule(data, batch, epochs, np.random.default_rng(7))
    plan = packed_plan(sizes, batch, epochs, np.random.default_rng(7))
    assert plan.pad_index == max(sizes)
    assert plan.num_lanes < len(sizes)  # the cohort really was packed
    np.testing.assert_array_equal(plan.weights, sched.weights)
    # emulate the on-device gather on host: pad each client to pad_index+1
    for c, d in enumerate(data):
        xp = np.zeros((plan.pad_index + 1, 2, 3), np.float32)
        yp = np.zeros(plan.pad_index + 1, np.float32)
        xp[: sizes[c]], yp[: sizes[c]] = d.x, d.y
        mine = plan.valid & (plan.client == c)
        idx = plan.sample_idx[mine]  # row-major: lane order, then slot order
        real = sched.step_valid[c]
        np.testing.assert_array_equal(xp[idx], sched.x[c][real])
        np.testing.assert_array_equal(yp[idx], sched.y[c][real])
        mask = (idx < plan.pad_index).astype(np.float32)
        np.testing.assert_array_equal(mask, sched.mask[c][real])


def test_plan_consumes_rng_like_schedule():
    """Both builders draw the identical RNG stream — after building either,
    the generator state is the same, so rebuild and resident federations
    stay in lockstep round after round (participation sampling included)."""
    rng = np.random.default_rng(11)
    sizes = [int(n) for n in rng.integers(2, 40, 10)]
    data = [
        ArrayDataset(
            np.zeros((n, 2, 2), np.float32), np.zeros(n, np.float32)
        )
        for n in sizes
    ]
    r_sched, r_plan = np.random.default_rng(5), np.random.default_rng(5)
    build_cohort_schedule(data, 8, 3, r_sched)
    packed_plan(sizes, 8, 3, r_plan)
    assert r_sched.bit_generator.state == r_plan.bit_generator.state


def test_plan_rejects_small_pad_index():
    with pytest.raises(ValueError, match="pad_index"):
        packed_plan([5, 9], 4, 1, np.random.default_rng(0), pad_index=7)


def test_device_cohort_layout():
    rng = np.random.default_rng(1)
    clients = make_clients(3, rng, lo=3, hi=8)
    dc = build_device_cohort(clients)
    max_n = max(c.n_train for c in clients)
    # each stay's SEQ_LEN x FEAT features are one row padded to 128 lanes
    assert dc.x.shape == (3, max_n + 1, 128)
    assert dc.feature_shape == (SEQ_LEN, FEAT)
    assert dc.y.shape == (3, max_n + 1)
    assert dc.pad_index == max_n
    assert dc.nbytes == dc.x.nbytes + dc.y.nbytes
    for c in clients:
        r = dc.row_of(c)
        assert dc.owns(c)
        x = np.asarray(dc.x)[r]
        np.testing.assert_array_equal(
            x[: c.n_train, : SEQ_LEN * FEAT].reshape(c.n_train, SEQ_LEN, FEAT), c.train.x
        )
        np.testing.assert_array_equal(np.asarray(dc.y)[r, : c.n_train], c.train.y)
        # rows past n_train (the pad row included) and the lane padding are zero
        assert x[c.n_train :].sum() == 0.0
        assert x[:, SEQ_LEN * FEAT :].sum() == 0.0
    stranger = make_clients(1, rng)[0]
    assert not dc.owns(stranger)
    with pytest.raises(KeyError):
        dc.row_of(ClientDataset(client_id=99, train=stranger.train, val=stranger.val))


# --------------------------------------------------------------------------
# engine parity: resident == rebuild == sequential oracle
# --------------------------------------------------------------------------

def test_resident_parity_with_rebuild_and_oracle(model):
    """The acceptance bar: across multiple rounds with uneven client sizes,
    resident staging matches both the rebuild path and the sequential
    per-client oracle within 1e-5 on params and reported losses."""
    loss_fn, params0 = model
    clients = make_clients(12, np.random.default_rng(0), lo=2, hi=30)
    seq = run_server(clients, params0, loss_fn, engine="sequential")
    reb = run_server(clients, params0, loss_fn, engine="vectorized", staging="rebuild")
    res = run_server(clients, params0, loss_fn, engine="vectorized", staging="resident")
    assert_params_close(seq.params, res.params)
    assert_params_close(reb.params, res.params)
    assert seq.total_local_steps == res.total_local_steps
    np.testing.assert_allclose(
        [r.mean_local_loss for r in seq.history],
        [r.mean_local_loss for r in res.history],
        atol=1e-5,
    )


def test_resident_parity_chunked_donated_shard_map(model):
    """Chunking, donation off, and the mesh path change nothing: every
    resident variant agrees with the unchunked resident round to 1e-5
    (and chunk/donation variants to 1e-6, same bars as the engine suite)."""
    loss_fn, params0 = model
    clients = make_clients(11, np.random.default_rng(2), lo=2, hi=20)
    base = run_server(clients, params0, loss_fn, engine="vectorized", staging="resident")
    chunked = run_server(
        clients, params0, loss_fn, engine="vectorized", staging="resident", cohort_chunk=4
    )
    undonated = run_server(
        clients, params0, loss_fn, engine="vectorized", staging="resident",
        donate_buffers=False,
    )
    sharded = run_server(
        clients, params0, loss_fn, engine="vectorized", staging="resident",
        mesh=make_data_mesh(),
    )
    assert_params_close(base.params, chunked.params, atol=1e-6)
    assert_params_close(base.params, undonated.params, atol=0.0)
    assert_params_close(base.params, sharded.params)


def test_resident_parity_with_participation_sampling(model):
    """Random 50% participation: the resident plan builder consumes the
    numpy RNG exactly like the schedule builder, so rebuild and resident
    federations sample identical cohorts and agree on the params."""
    loss_fn, params0 = model
    clients = make_clients(10, np.random.default_rng(4), lo=2, hi=25)
    reb = run_server(
        clients, params0, loss_fn, rounds=3, engine="vectorized", staging="rebuild",
        participation_fraction=0.5, seed=9,
    )
    res = run_server(
        clients, params0, loss_fn, rounds=3, engine="vectorized", staging="resident",
        participation_fraction=0.5, seed=9,
    )
    for rr, rv in zip(reb.history, res.history):
        assert rr.participant_ids == rv.participant_ids
    assert_params_close(reb.params, res.params)


def test_prefetch_on_off_bit_identical(model):
    """The background staging thread is pure overlap: params and losses are
    bit-identical with prefetch on and off, and the prefetching run really
    did stage chunks ahead of the consumer."""
    loss_fn, params0 = model
    clients = make_clients(12, np.random.default_rng(5), lo=2, hi=20)
    results = {}
    stats = {}
    for prefetch in (True, False):
        fed = FederatedConfig(
            rounds=2, local_epochs=1, batch_size=4, seed=0, engine="vectorized",
            staging="resident", cohort_chunk=4, prefetch=prefetch,
        )
        server = FederatedServer(
            fed, clients, loss_fn, AdamW(learning_rate=5e-3, weight_decay=5e-3)
        )
        results[prefetch] = server.run(params0)
        stats[prefetch] = server.cohort_trainer.last_round_stats
    for a, b in zip(
        jax.tree.leaves(results[True].params), jax.tree.leaves(results[False].params)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        [r.mean_local_loss for r in results[True].history],
        [r.mean_local_loss for r in results[False].history],
    )
    # The overlap counter itself is thread-timing-dependent (a loaded CI
    # box can schedule the producer late), so the deterministic >=1 check
    # lives in test_staging_pipeline_really_runs_ahead; here we assert the
    # mechanism engaged and the accounting stays consistent.
    assert stats[True]["prefetch"] and stats[True]["plans_prefetched"] >= 0
    assert not stats[False]["prefetch"] and stats[False]["plans_prefetched"] == 0


# --------------------------------------------------------------------------
# the point: per-round host->device traffic collapses at 189 clients
# --------------------------------------------------------------------------

def test_bytes_staged_collapse_at_189_clients(model):
    """Resident staging moves >=10x fewer host bytes per round than the
    rebuild path at the paper's full 189-client federation (~35x even at
    this smoke scale's tiny 4x6 stays; ~900x at the real 24x38 shape)."""
    loss_fn, params0 = model
    clients = make_clients(189, np.random.default_rng(6))
    staged = {}
    for staging in ("rebuild", "resident"):
        fed = FederatedConfig(
            rounds=1, local_epochs=1, batch_size=8, seed=0,
            engine="vectorized", staging=staging,
        )
        server = FederatedServer(
            fed, clients, loss_fn, AdamW(learning_rate=5e-3, weight_decay=5e-3)
        )
        server.run(params0)
        stats = server.cohort_trainer.last_round_stats
        assert stats["staging"] == staging
        staged[staging] = stats["bytes_staged"]
        if staging == "resident":
            assert stats["bytes_resident"] > 0  # the one-time upload
    assert staged["rebuild"] >= 10 * staged["resident"]


def test_staging_comparison_smoke():
    """The bench harness behind --mode pipeline, at smoke scale: both
    headline numbers are recorded, the byte collapse holds (>=10x), and
    the cross-variant parity guard stays inside the engine tolerance."""
    from repro.experiments.paper import run_staging_comparison

    report = run_staging_comparison(
        rounds=2,
        total_stays=189 * 8,
        batch_size=8,
        cohort_chunk=64,
        variants=("rebuild", "resident"),
        repeats=1,
        verbose=False,
    )
    assert report["num_clients"] == 189
    assert report["bytes_ratio"] >= 10.0
    assert report["speedup"] > 0.0  # recorded; the >=1.5x bar is the bench's
    assert report["max_param_diff"] <= 1e-4
    res = report["variants"]["resident"]
    assert res["bytes_staged_per_round"] < report["variants"]["rebuild"]["bytes_staged_per_round"]


# --------------------------------------------------------------------------
# plumbing: pipeline ordering/errors, resident reuse, device-side keys
# --------------------------------------------------------------------------

def test_staging_pipeline_orders_and_overlaps():
    produced = []

    def stage(k):
        produced.append(k)
        return k * k

    pipe = StagingPipeline(stage, range(6))
    out = list(pipe)
    assert out == [k * k for k in range(6)]
    assert produced == list(range(6))  # strict order: the RNG contract


def test_staging_pipeline_propagates_errors():
    def stage(k):
        if k == 2:
            raise RuntimeError("boom at chunk 2")
        return k

    pipe = StagingPipeline(stage, range(5))
    got = []
    with pytest.raises(RuntimeError, match="boom at chunk 2"):
        for item in pipe:
            got.append(item)
    assert got == [0, 1]


def test_staging_pipeline_close_unblocks_producer():
    release = threading.Event()

    def stage(k):
        if k > 0:
            release.wait(timeout=5.0)
        return k

    pipe = StagingPipeline(stage, range(4))
    it = iter(pipe)
    assert next(it) == 0
    release.set()
    pipe.close()  # must not hang even with items unconsumed
    assert not pipe._thread.is_alive()


def test_staging_pipeline_really_runs_ahead():
    """With a slow consumer, the producer finishes staging the next chunk
    before it is requested (the double-buffer overlap)."""
    times = {}

    def stage(k):
        times[k] = time.perf_counter()
        return k

    pipe = StagingPipeline(stage, range(3))
    it = iter(pipe)
    first = next(it)
    time.sleep(0.15)  # "train" on chunk 0 while chunk 1 stages
    t_request = time.perf_counter()
    second = next(it)
    assert (first, second) == (0, 1)
    assert times[1] < t_request
    assert pipe.prefetched >= 1
    pipe.close()


def test_device_cohort_reused_across_rounds(model):
    """The federation's resident arrays are uploaded once and reused: the
    server's rounds all hit the same DeviceCohort object."""
    loss_fn, params0 = model
    clients = make_clients(6, np.random.default_rng(8), lo=2, hi=12)
    fed = FederatedConfig(
        rounds=3, local_epochs=1, batch_size=4, seed=0,
        engine="vectorized", staging="resident",
    )
    server = FederatedServer(
        fed, clients, loss_fn, AdamW(learning_rate=5e-3, weight_decay=5e-3)
    )
    server.run(params0)
    dc = server.cohort_trainer._device_cohort
    assert dc is not None and all(dc.owns(c) for c in clients)
    # a later round over a subset reuses the attached arrays
    trainer = server.cohort_trainer
    keys = list(jax.random.split(jax.random.key(3), 3))
    trainer.train_cohort(params0, clients[:3], np.random.default_rng(1), keys)
    assert trainer._device_cohort is dc


def test_caller_key_array_survives_donation(model):
    """Regression: a full-range key slice is an identity in jax, so the
    round's eager delete of staged buffers must never reach the caller's
    array — reusing the same device key data across trainers is the
    documented parity workflow."""
    loss_fn, params0 = model
    clients = make_clients(4, np.random.default_rng(10), lo=2, hi=8)
    _, key_data = chain_split_keys(jax.random.key(0), len(clients))
    results = {}
    for staging in ("resident", "rebuild"):
        trainer = CohortTrainer(
            loss_fn, AdamW(learning_rate=5e-3, weight_decay=5e-3),
            batch_size=4, local_epochs=1, staging=staging,
        )
        new_params, _, _ = trainer.train_cohort(
            params0, clients, np.random.default_rng(0), key_data
        )
        jax.block_until_ready(new_params)
        results[staging] = new_params
        assert not key_data.is_deleted()
    assert_params_close(results["resident"], results["rebuild"])


def test_staging_pipeline_runs_at_most_depth_ahead():
    """Regression: the producer takes a slot before staging, so with
    depth=1 it never builds chunk k+2 while chunk k is still in hand."""
    staged = []

    def stage(k):
        staged.append(k)
        return k

    pipe = StagingPipeline(stage, range(4))
    it = iter(pipe)
    assert next(it) == 0  # chunk 0 in hand; producer may stage only chunk 1
    time.sleep(0.3)
    assert staged == [0, 1], f"producer ran ahead: {staged}"
    assert next(it) == 1
    pipe.close()


def test_chain_split_keys_stays_on_device():
    """The vectorized engine consumes the key chain on device; returning
    numpy here would cost a sync + re-upload per round."""
    new_key, key_data = chain_split_keys(jax.random.key(0), 7)
    assert isinstance(key_data, jax.Array)
    assert not isinstance(key_data, np.ndarray)
    assert key_data.shape[0] == 7


def test_unknown_staging_rejected(model):
    loss_fn, _ = model
    with pytest.raises(ValueError, match="staging"):
        FederatedConfig(staging="teleport")
    with pytest.raises(ValueError, match="staging"):
        CohortTrainer(loss_fn, AdamW(), batch_size=4, local_epochs=1, staging="teleport")


def test_round_stats_report_staging(model):
    loss_fn, params0 = model
    clients = make_clients(5, np.random.default_rng(9), lo=2, hi=10)
    trainer = CohortTrainer(
        loss_fn, AdamW(learning_rate=5e-3, weight_decay=5e-3),
        batch_size=4, local_epochs=1, staging="resident",
    )
    keys = list(jax.random.split(jax.random.key(0), len(clients)))
    new_params, losses, steps = trainer.train_cohort(
        params0, clients, np.random.default_rng(0), keys
    )
    jax.block_until_ready(new_params)
    stats = trainer.last_round_stats
    assert stats["staging"] == "resident"
    assert stats["bytes_staged"] > 0
    assert stats["bytes_resident"] == trainer._device_cohort.nbytes
    assert np.isfinite(losses).all()
