"""Donated round buffers: invalidation semantics + real peak-memory wins.

The vectorized engine jits its round step with ``donate_argnums`` on the
cross-chunk accumulator (aliased in place by XLA) and eagerly releases each
chunk's device-resident schedule once the step consuming it returns.  Two
properties are load-bearing for the 189-client paper federation:

* donated buffers are genuinely *gone* — jax raises on any reuse (the
  accumulator from chunk k cannot silently alias stale memory in chunk k+1);
* the round's peak live-buffer footprint is strictly lower than the
  non-donated path's (which holds the previous chunk's schedule while
  staging the next one).
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import ArrayDataset, ClientDataset, build_cohort_schedule
from repro.federated.cohort import CohortTrainer
from repro.models.gru import GRUConfig, init_gru, make_loss_fn
from repro.optim.adamw import AdamW

SEQ_LEN, FEAT = 4, 6
# Reusing a donated buffer raises from whichever dispatch path runs the op:
# jax's Python path raises RuntimeError("Array has been deleted"), while an
# op whose C++ fast path is already warm in this process (any earlier test
# in the same worker that ran it) hands the buffer to the runtime, which
# refuses it with ValueError("... deleted or donated").
REUSE_ERRORS = (RuntimeError, ValueError)


def make_clients(count: int, n: int, rng: np.random.Generator) -> list[ClientDataset]:
    clients = []
    for i in range(count):
        x = rng.normal(size=(n, SEQ_LEN, FEAT)).astype(np.float32)
        y = rng.uniform(0.5, 20.0, size=n).astype(np.float32)
        ds = ArrayDataset(x, y)
        clients.append(ClientDataset(client_id=i, train=ds, val=ds))
    return clients


@pytest.fixture(scope="module")
def model():
    cfg = GRUConfig(input_dim=FEAT, hidden_dim=4, num_layers=1)
    return make_loss_fn(cfg), init_gru(jax.random.key(1), cfg)


def make_trainer(loss_fn, donate: bool, chunk: int | None = None) -> CohortTrainer:
    return CohortTrainer(
        loss_fn=loss_fn,
        optimizer=AdamW(learning_rate=5e-3, weight_decay=5e-3),
        batch_size=4,
        local_epochs=1,
        cohort_chunk=chunk,
        donate=donate,
    )


def run_round(trainer, params, clients, seed=0):
    keys = list(jax.random.split(jax.random.key(seed), len(clients)))
    new_params, losses, steps = trainer.train_cohort(
        params, clients, np.random.default_rng(seed), keys
    )
    jax.block_until_ready(new_params)
    return new_params


def test_donated_accumulator_is_invalidated(model):
    """After the round step runs, the donated accumulator input is deleted
    and any reuse raises — XLA really did alias it into the output."""
    loss_fn, params = model
    trainer = make_trainer(loss_fn, donate=True)
    clients = make_clients(4, 8, np.random.default_rng(0))
    sched = build_cohort_schedule([c.train for c in clients], 4, 1, np.random.default_rng(1))
    key_data = jnp.stack(
        [jax.random.key_data(k) for k in jax.random.split(jax.random.key(0), 4)]
    )
    acc = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    acc_leaves = jax.tree.leaves(acc)
    out_acc, _ = trainer._round(
        params,
        acc,
        jnp.asarray(sched.x),
        jnp.asarray(sched.y),
        jnp.asarray(sched.mask),
        jnp.asarray(sched.step_valid),
        key_data,
        jnp.asarray(sched.weights),
    )
    jax.block_until_ready(out_acc)
    assert all(leaf.is_deleted() for leaf in acc_leaves)
    with pytest.raises(REUSE_ERRORS, match="deleted"):
        _ = acc_leaves[0] + 1.0
    with pytest.raises(REUSE_ERRORS, match="deleted"):
        np.asarray(acc_leaves[-1])
    # the round's *output* accumulator is alive and well
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(out_acc))


def test_undonated_buffers_survive(model):
    loss_fn, params = model
    trainer = make_trainer(loss_fn, donate=False)
    clients = make_clients(3, 8, np.random.default_rng(2))
    sched = build_cohort_schedule([c.train for c in clients], 4, 1, np.random.default_rng(1))
    key_data = jnp.stack(
        [jax.random.key_data(k) for k in jax.random.split(jax.random.key(0), 3)]
    )
    acc = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    out_acc, _ = trainer._round(
        params,
        acc,
        jnp.asarray(sched.x),
        jnp.asarray(sched.y),
        jnp.asarray(sched.mask),
        jnp.asarray(sched.step_valid),
        key_data,
        jnp.asarray(sched.weights),
    )
    jax.block_until_ready(out_acc)
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(acc))


def test_peak_live_buffers_strictly_lower_with_donation(model):
    """Across a chunked round, the donated path's peak live-buffer count and
    bytes are strictly below the plain path's (which keeps each consumed
    chunk's schedule alive until the next one is already staged)."""
    loss_fn, params = model
    clients = make_clients(12, 12, np.random.default_rng(3))
    stats = {}
    results = {}
    for donate in (False, True):
        gc.collect()
        trainer = make_trainer(loss_fn, donate=donate, chunk=4)
        results[donate] = run_round(trainer, params, clients)
        stats[donate] = trainer.last_round_stats
    assert stats[False]["chunks"] == stats[True]["chunks"] == 3
    assert stats[True]["peak_live_buffers"] < stats[False]["peak_live_buffers"]
    assert stats[True]["peak_live_bytes"] < stats[False]["peak_live_bytes"]
    # donation is a memory optimization only: results are bit-identical
    for a, b in zip(jax.tree.leaves(results[False]), jax.tree.leaves(results[True])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_round_stats_populated(model):
    loss_fn, params = model
    trainer = make_trainer(loss_fn, donate=True)
    clients = make_clients(5, 8, np.random.default_rng(4))
    run_round(trainer, params, clients)
    stats = trainer.last_round_stats
    assert stats is not None
    assert stats["donated"] is True
    assert stats["chunks"] == 1 and stats["shards"] >= 1
    assert stats["peak_live_buffers"] > 0 and stats["peak_live_bytes"] > 0
