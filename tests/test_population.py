"""Population-scale staging: LRU resident pools and the staging-pipeline
error contract.

The contract of ``resident_budget_bytes``: a federation whose baked cohort
exceeds the budget trains out of a bounded LRU pool of resident rows —
rows upload lazily per round via ``ensure_resident`` (run once per round,
before any plan is staged, so prefetch never races an eviction) — and the
aggregated params match the fully resident path within the engine parity
suite's 1e-5.  And ``StagingPipeline.close`` must never swallow a producer
exception the consumer didn't collect, nor silently abandon a stuck
producer thread.
"""

import logging
import threading
import time

import jax
import numpy as np
import pytest

from repro.data.device_cohort import build_device_cohort
from repro.data.pipeline import ArrayDataset, ClientDataset
from repro.federated.cohort import CohortTrainer, chain_split_keys
from repro.federated.staging import StagingPipeline
from repro.launch.mesh import make_data_mesh
from repro.models.gru import GRUConfig, init_gru, make_loss_fn
from repro.optim.adamw import AdamW

SEQ_LEN, FEAT = 4, 6


def row_bytes_of(clients) -> int:
    """One padded client row in the device cohort these clients would bake:
    ``(max_n + 1)`` samples of x (each stay's SEQ_LEN * FEAT features one row
    padded to 128 lanes) plus y."""
    max_n = max(c.n_train for c in clients)
    return (max_n + 1) * 128 * 4 + (max_n + 1) * 4


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


def make_trainer(loss_fn, **kwargs):
    defaults = dict(batch_size=4, local_epochs=1, staging="resident")
    defaults.update(kwargs)
    return CohortTrainer(
        loss_fn, AdamW(learning_rate=5e-3, weight_decay=5e-3), **defaults
    )


def assert_params_close(a, b, atol=1e-5):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=atol, rtol=0)


def run_sampled_rounds(trainer, params, clients, rounds=4, cohort_size=8):
    """Identical sampled-subset rounds for any trainer: same plan RNG, same
    participation draws, same key chain — so two trainers differ only in
    how rows reach the device."""
    trainer.attach_device_cohort(clients)  # the full federation, not a round
    plan_rng = np.random.default_rng(0)
    pick_rng = np.random.default_rng(42)
    key = jax.random.key(7)
    for _ in range(rounds):
        ids = np.sort(pick_rng.choice(len(clients), size=cohort_size, replace=False))
        cohort = [clients[int(i)] for i in ids]
        key, subs = chain_split_keys(key, len(cohort))
        params, _, _ = trainer.train_cohort(
            params, cohort, plan_rng, subs, steps_per_epoch=2
        )
    return jax.block_until_ready(params)


# --------------------------------------------------------------------------
# the LRU pool is a pure memory bound: params match fully resident
# --------------------------------------------------------------------------

def test_pooled_rounds_match_fully_resident(model):
    """Four sampled-subset rounds through a 10-row pool (evicting between
    rounds) aggregate the same params as the same rounds against the fully
    resident cohort — residency is transport, not math."""
    loss_fn, params0 = model
    clients = make_clients(30, np.random.default_rng(5))
    rb = row_bytes_of(clients)
    full = run_sampled_rounds(make_trainer(loss_fn), params0, clients)
    pooled_trainer = make_trainer(loss_fn, resident_budget_bytes=10 * rb)
    pooled = run_sampled_rounds(pooled_trainer, params0, clients)
    dc = pooled_trainer._device_cohort
    assert dc.is_pooled and dc.pool_rows == 10
    assert dc.evictions > 0, "4 rounds of 8 from 30 clients must evict"
    assert_params_close(pooled, full)
    stats = pooled_trainer.last_round_stats
    assert stats["pool"] and stats["pool_rows"] == 10
    assert 0 <= stats["pool_uploads"] <= 8  # this round's delta, not the total
    assert dc.nbytes == 10 * rb


def test_lru_evicts_oldest_untouched_and_reuploads_correctly(model):
    _, _ = model
    clients = make_clients(6, np.random.default_rng(2), lo=3, hi=9)
    rb = row_bytes_of(clients)
    dc = build_device_cohort(clients, resident_budget_bytes=4 * rb)
    assert dc.pool_rows == 4
    assert dc.ensure_resident(clients[:4]) == 4
    assert dc.ensure_resident([clients[0], clients[1]]) == 0  # refresh recency
    assert dc.hits == 2
    assert dc.ensure_resident([clients[4]]) == 1  # c2 is now the LRU victim
    assert dc.evictions == 1
    assert 2 not in dc.rows and {0, 1, 3, 4} <= dc.rows.keys()
    # the evicted client's row was handed to c4 with its data re-staged
    c4 = clients[4]
    row = np.asarray(dc.x[dc.row_of(c4)])
    features = row[: c4.n_train, : SEQ_LEN * FEAT].reshape(c4.n_train, SEQ_LEN, FEAT)
    np.testing.assert_array_equal(features, c4.train.x)
    np.testing.assert_array_equal(row[c4.n_train :], 0.0)
    np.testing.assert_array_equal(row[:, SEQ_LEN * FEAT :], 0.0)
    np.testing.assert_array_equal(
        np.asarray(dc.y[dc.row_of(c4)])[: c4.n_train], c4.train.y
    )
    # bringing c2 back is an upload again, not a hit
    assert dc.ensure_resident([clients[2]]) == 1
    assert dc.uploads == 6
    assert dc.bytes_uploaded == 6 * rb


def test_round_cohort_larger_than_pool_rejected(model):
    clients = make_clients(8, np.random.default_rng(3))
    dc = build_device_cohort(clients, resident_budget_bytes=3 * row_bytes_of(clients))
    with pytest.raises(ValueError, match="exceeds the resident pool"):
        dc.ensure_resident(clients[:4])


def test_budget_below_one_row_rejected():
    clients = make_clients(4, np.random.default_rng(4))
    with pytest.raises(ValueError, match="cannot hold even one client row"):
        build_device_cohort(clients, resident_budget_bytes=row_bytes_of(clients) - 1)


def test_foreign_client_rejected_by_pool(model):
    clients = make_clients(4, np.random.default_rng(6), lo=8)  # uniform rows
    dc = build_device_cohort(
        clients[:3], resident_budget_bytes=2 * row_bytes_of(clients)
    )
    assert dc.is_pooled
    with pytest.raises(KeyError, match="not part of the federation"):
        dc.ensure_resident([clients[3]])
    with pytest.raises(KeyError, match="not resident in the pool"):
        dc.row_of(clients[0])  # never made resident


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >1 device")
def test_pool_refuses_mesh():
    clients = make_clients(8, np.random.default_rng(7))
    with pytest.raises(ValueError, match="single-host"):
        build_device_cohort(
            clients,
            mesh=make_data_mesh(),
            resident_budget_bytes=2 * row_bytes_of(clients),
        )


def run_full_round(trainer, params, clients):
    _, subs = chain_split_keys(jax.random.key(5), len(clients))
    params, _, _ = trainer.train_cohort(
        params, clients, np.random.default_rng(1), subs, steps_per_epoch=2
    )
    return jax.block_until_ready(params)


# --------------------------------------------------------------------------
# staging pipeline error contract
# --------------------------------------------------------------------------

def test_close_reraises_uncollected_stage_exception():
    """A stage_fn failure the consumer never iterated to must surface from
    close(), not vanish in the drain loop."""

    def stage(k):
        raise RuntimeError("staging blew up")

    pipe = StagingPipeline(stage, range(3))
    deadline = time.monotonic() + 5.0
    while pipe._queue.qsize() == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    with pytest.raises(RuntimeError, match="staging blew up"):
        pipe.close()
    pipe.close()  # idempotent; the pending exception is delivered once


def test_close_flags_and_logs_stuck_producer(caplog):
    """A producer stuck inside stage_fn cannot be joined: close() must warn
    and flag the leak instead of silently abandoning the daemon thread."""
    release = threading.Event()

    def stage(k):
        release.wait(10.0)
        return k

    pipe = StagingPipeline(stage, range(2), join_timeout=0.2)
    with caplog.at_level(logging.WARNING, logger="repro.federated.staging"):
        pipe.close()
    assert pipe.leaked
    assert any("failed to join" in r.message for r in caplog.records)
    release.set()
    pipe._thread.join(timeout=5.0)


def test_killed_pipeline_mid_round_surfaces_error(model):
    """End to end: a staging failure mid-round kills the round with the
    original exception (not a hang, not a swallowed error), and the trainer
    survives to run the next round cleanly."""
    loss_fn, params0 = model
    clients = make_clients(12, np.random.default_rng(12))
    trainer = make_trainer(loss_fn, cohort_chunk=4)
    run_full_round(trainer, params0, clients)  # healthy attach + round
    boom = {"armed": True}
    real_put = trainer._device_put_chunk

    def failing_put(arrays):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("device lost")
        return real_put(arrays)

    trainer._device_put_chunk = failing_put
    _, subs = chain_split_keys(jax.random.key(8), len(clients))
    with pytest.raises(RuntimeError, match="device lost"):
        trainer.train_cohort(
            params0, clients, np.random.default_rng(3), subs, steps_per_epoch=2
        )
    trainer._device_put_chunk = real_put
    run_full_round(trainer, params0, clients)  # recovered


# --------------------------------------------------------------------------
# the population experiment drives all of it end to end
# --------------------------------------------------------------------------

def test_run_population_scale_smoke():
    """Tiny two-point sweep through the real bench harness: exact-mode
    parity at the small point, pooled rounds at both, and the report's
    scaling summary (the sub-linear and O(1)-membership assertions run
    inside)."""
    from repro.experiments.population import run_population_scale

    report = run_population_scale(
        populations=(60, 180),
        rounds=2,
        round_clients=12,
        pool_rows=24,
        verbose=False,
    )
    small, large = report["entries"]
    assert small["streaming_mode"] == "exact" and small["participant_match"]
    for entry in (small, large):
        assert entry["pool_rows"] == 24
        assert entry["pool_uploads_total"] >= 12
        assert entry["round_time_s"] > 0
    assert report["population_ratio"] == 3.0
