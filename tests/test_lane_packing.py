"""Lane packing: a round's real client-steps share a few vmap lanes.

Within a round every client trains independently from the same global
parameters, so the resident engine lays each client's real steps end to
end in a lane (``LanePlan``) and scans ``W`` lanes of ``L`` slots instead
of every client for the longest client's step count.  The plan must feed
every client exactly the batches of ``build_cohort_schedule``, from the
same RNG draws; ``W`` must depend on the participant count alone, so one
compiled round serves every draw; and the packed round must match the
rebuild staging path and the sequential engine, DP-SGD and the mesh path
included.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.data.device_cohort import (
    assign_lanes,
    build_lane_plan,
    first_fit_decreasing,
    pack_federation,
)
from repro.data.pipeline import ArrayDataset, ClientDataset, build_cohort_schedule
from repro.federated import Federation, FederationConfig
from repro.federated.client import LocalTrainer
from repro.federated.cohort import CohortTrainer, chain_split_keys
from repro.federated.fedavg import aggregate
from repro.models.gru import GRUConfig, init_gru, make_loss_fn
from repro.obs.trace import Tracer
from repro.optim.adamw import AdamW
from repro.privacy.dp import DPConfig

SEQ_LEN, FEAT = 4, 6
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_clients(sizes, rng: np.random.Generator) -> list[ClientDataset]:
    clients = []
    for i, n in enumerate(sizes):
        x = rng.normal(size=(int(n), SEQ_LEN, FEAT)).astype(np.float32)
        y = rng.uniform(0.5, 20.0, size=int(n)).astype(np.float32)
        ds = ArrayDataset(x, y)
        clients.append(ClientDataset(client_id=i, train=ds, val=ds))
    return clients


# One large client pins the lane length; the rest are small, so first-fit
# decreasing packs the 10 clients into 3 lanes (batch 4).
UNEVEN = (40, 3, 9, 5, 14, 2, 7, 11, 4, 6)


@pytest.fixture(scope="module")
def model():
    cfg = GRUConfig(input_dim=FEAT, hidden_dim=4, num_layers=1)
    return make_loss_fn(cfg), init_gru(jax.random.key(1), cfg)


def packed(sizes, batch, epochs, rng, shards=1):
    """A plan for one shard-striped cohort, packed by first-fit decreasing."""
    sizes = np.asarray(sizes)
    per_epoch = -(-sizes // batch)
    shard = np.arange(sizes.size) % shards
    lane, width = assign_lanes(
        per_epoch * epochs, shard, shards, int(per_epoch.max()) * epochs
    )
    plan = build_lane_plan(sizes, batch, epochs, rng, shard * width + lane, width * shards)
    return plan, width


def client_slots(plan, c):
    """Flat slot numbers of client ``c``'s real steps, in scan order."""
    return np.flatnonzero((plan.valid & (plan.client == c)).ravel())


# --------------------------------------------------------------------------
# the plan: pure numpy
# --------------------------------------------------------------------------

@pytest.mark.parametrize("batch,epochs", [(4, 1), (4, 3), (7, 2)])
def test_every_real_step_appears_once_in_order(batch, epochs):
    """Each client's (epoch, step) batches appear once, on consecutive slots
    of one lane, in order, and hold the schedule's sample indices."""
    rng = np.random.default_rng(batch * 10 + epochs)
    sizes = [int(n) for n in rng.integers(1, 30, 12)]
    # Features equal to the sample index: the schedule's x is then its
    # index plan, padding slots zero.
    data = [
        ArrayDataset(
            np.arange(n, dtype=np.float32).reshape(n, 1, 1), np.zeros(n, np.float32)
        )
        for n in sizes
    ]
    sched = build_cohort_schedule(data, batch, epochs, np.random.default_rng(9))
    plan, _ = packed(sizes, batch, epochs, np.random.default_rng(9))
    flat_idx = plan.sample_idx.reshape(-1, batch)
    L = plan.total_steps
    for c, n in enumerate(sizes):
        slots = client_slots(plan, c)
        assert slots.size == -(-n // batch) * epochs
        assert np.all(np.diff(slots) == 1) and len(set(slots // L)) == 1
        idx = flat_idx[slots]
        real = sched.step_valid[c]
        mask = sched.mask[c][real].astype(bool)
        np.testing.assert_array_equal(idx[mask], sched.x[c][real][mask, 0, 0])
        assert (idx[~mask] == plan.pad_index).all()
        for e in range(epochs):
            epoch = idx.reshape(epochs, -1)[e]
            assert sorted(epoch[epoch != plan.pad_index]) == list(range(n))


def test_no_lane_exceeds_its_length():
    rng = np.random.default_rng(2)
    for _ in range(20):
        sizes = rng.integers(1, 50, rng.integers(1, 30))
        plan, _ = packed(sizes, 4, 2, rng)
        used = plan.valid.sum(axis=1)
        assert (used <= plan.total_steps).all()
        # a lane's clients run from slot 0 with no gap
        for w, u in enumerate(used):
            assert plan.valid[w, :u].all() and not plan.valid[w, u:].any()
    with pytest.raises(ValueError, match="client-steps"):
        build_lane_plan([8, 8], 4, 1, rng, [0, 0], 1)  # 4 steps in a lane of 2


def test_first_last_and_last_epoch_flags():
    batch, epochs = 4, 3
    plan, _ = packed(UNEVEN, batch, epochs, np.random.default_rng(0))
    first, last, last_epoch = (a.ravel() for a in (plan.first, plan.last, plan.last_epoch))
    for c, n in enumerate(UNEVEN):
        slots = client_slots(plan, c)
        assert first[slots].tolist() == [True] + [False] * (slots.size - 1)
        assert last[slots].tolist() == [False] * (slots.size - 1) + [True]
        per_epoch = -(-n // batch)
        assert last_epoch[slots].tolist() == [False] * (slots.size - per_epoch) + [True] * per_epoch
    # flags mark real slots only
    assert not (plan.first | plan.last | plan.last_epoch)[~plan.valid].any()
    assert plan.first.sum() == plan.last.sum() == len(UNEVEN)


def test_first_fit_decreasing_places_largest_first():
    lane, count = first_fit_decreasing([5, 4, 3, 3, 2, 2, 1], 6)
    assert lane.tolist() == [0, 1, 2, 2, 1, 3, 0] and count == 4
    with pytest.raises(ValueError, match="capacity"):
        first_fit_decreasing([7], 6)


def test_width_is_min_of_participants_and_federation_lanes():
    """Each draw's lane count is ``min(C, W_fed)``: it depends on how many
    participate, never on which."""
    rng = np.random.default_rng(4)
    clients = make_clients(rng.integers(1, 60, 40), rng)
    fed = pack_federation(clients, np.zeros(40, int), 1, 4, 2)
    assert 1 < fed.width < 40
    steps = np.asarray([-(-c.n_train // 4) * 2 for c in clients])
    for count in (1, fed.width - 1, fed.width, fed.width + 1, 40):
        for _ in range(5):
            pick = np.sort(rng.choice(40, count, replace=False))
            lane, width = assign_lanes(
                steps[pick], np.zeros(count, int), 1, fed.steps_per_epoch * 2,
                [fed.lane[int(i)] for i in pick], fed.width,
            )
            assert width == min(count, fed.width)
            assert (lane < width).all()
            if count <= fed.width:
                assert sorted(lane) == list(range(count))  # a lane each


def test_any_subset_fits_the_federation_lanes():
    """A subset of a packed set fits the same lanes: every draw builds."""
    rng = np.random.default_rng(5)
    sizes = rng.integers(1, 80, 50)
    clients = make_clients(sizes, rng)
    fed = pack_federation(clients, np.zeros(50, int), 1, 8, 3)
    L = fed.steps_per_epoch * 3
    for _ in range(40):
        pick = np.sort(rng.choice(50, rng.integers(fed.width + 1, 51), replace=False))
        lane, width = assign_lanes(
            -(-sizes[pick] // 8) * 3, np.zeros(pick.size, int), 1, L,
            [fed.lane[int(i)] for i in pick], fed.width,
        )
        plan = build_lane_plan(
            sizes[pick], 8, 3, rng, lane, width, steps_per_epoch=fed.steps_per_epoch
        )
        assert plan.num_lanes == fed.width
        assert plan.valid.sum() == (-(-sizes[pick] // 8) * 3).sum()


@pytest.mark.parametrize("shards", [1, 4])
def test_plan_leaves_the_rng_where_the_schedule_does(shards):
    rng = np.random.default_rng(6)
    sizes = [int(n) for n in rng.integers(1, 40, 14)]
    data = [ArrayDataset(np.zeros((n, 1, 1), np.float32), np.zeros(n, np.float32)) for n in sizes]
    r_sched, r_plan = np.random.default_rng(3), np.random.default_rng(3)
    build_cohort_schedule(data, 4, 3, r_sched)
    packed(sizes, 4, 3, r_plan, shards=shards)
    assert r_sched.bit_generator.state == r_plan.bit_generator.state


def test_shards_train_their_own_clients_on_equal_widths():
    """Under a mesh a client's lane lies in its own shard's block, and every
    shard gets the width the fullest shard needs."""
    sizes = np.asarray(UNEVEN * 2)
    plan, width = packed(sizes, 4, 2, np.random.default_rng(1), shards=4)
    assert plan.num_lanes == 4 * width
    for c in range(sizes.size):
        (lane,) = set(client_slots(plan, c) // plan.total_steps)
        assert lane // width == c % 4


# --------------------------------------------------------------------------
# the packed round
# --------------------------------------------------------------------------

def make_trainer(loss_fn, staging="resident", **kwargs):
    return CohortTrainer(
        loss_fn, AdamW(learning_rate=5e-3, weight_decay=5e-3),
        batch_size=4, local_epochs=2, staging=staging, **kwargs,
    )


def assert_params_close(a, b, atol=1e-5):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=atol, rtol=0)


def test_packed_round_matches_rebuild_and_sequential(model):
    """W < C: params and per-client losses of a packed round match the
    rebuild staging path and the sequential engine."""
    loss_fn, params0 = model
    clients = make_clients(UNEVEN, np.random.default_rng(0))
    _, key_data = chain_split_keys(jax.random.key(3), len(clients))
    out = {}
    for staging in ("resident", "rebuild"):
        trainer = make_trainer(loss_fn, staging)
        out[staging] = trainer.train_cohort(params0, clients, np.random.default_rng(7), key_data)
        if staging == "resident":
            assert trainer.last_round_stats["lanes"] < len(clients)
    seq = LocalTrainer(loss_fn, AdamW(learning_rate=5e-3, weight_decay=5e-3), 4, 2)
    rng, key, returned, losses = np.random.default_rng(7), jax.random.key(3), [], []
    for c in clients:
        key, sub = jax.random.split(key)
        p, loss, _ = seq.train_client(params0, c, rng, sub)
        returned.append(p)
        losses.append(loss)
    seq_params = aggregate(returned, [c.n_train for c in clients])
    res_params, res_losses, res_steps = out["resident"]
    assert res_steps == out["rebuild"][2]
    assert_params_close(res_params, out["rebuild"][0])
    assert_params_close(res_params, seq_params)
    np.testing.assert_allclose(res_losses, out["rebuild"][1], atol=1e-5, rtol=0)
    np.testing.assert_allclose(res_losses, losses, atol=1e-5, rtol=0)


def test_packed_dp_round_matches_sequential_dp():
    clients = make_clients(UNEVEN, np.random.default_rng(1))
    cfg = GRUConfig(input_dim=FEAT, dropout=0.0, hidden_dim=8, num_layers=1)
    loss_fn, params0 = make_loss_fn(cfg), init_gru(jax.random.key(0), cfg)
    runs = {}
    for engine in ("vectorized", "sequential"):
        config = FederationConfig(
            rounds=2, local_epochs=2, batch_size=4, seed=0, engine=engine,
            privacy=DPConfig(clip_norm=1.0, noise_multiplier=1.1),
        )
        fed = Federation(config, clients, loss_fn, AdamW(learning_rate=1e-2))
        runs[engine] = fed.run(params0)
        if engine == "vectorized":
            assert fed.cohort_trainer.last_round_stats["lanes"] < len(clients)
    assert_params_close(runs["vectorized"].params, runs["sequential"].params, atol=2e-5)


MESH_SCRIPT = """
import json, sys
import jax, numpy as np
sys.path.insert(0, sys.argv[1])
from test_lane_packing import UNEVEN, make_clients, make_trainer
from repro.federated.cohort import chain_split_keys
from repro.launch.mesh import make_data_mesh
from repro.models.gru import GRUConfig, init_gru, make_loss_fn

cfg = GRUConfig(input_dim=6, hidden_dim=4, num_layers=1)
loss_fn, params0 = make_loss_fn(cfg), init_gru(jax.random.key(1), cfg)
clients = make_clients(UNEVEN * 2, np.random.default_rng(0))
_, keys = chain_split_keys(jax.random.key(3), len(clients))
out = {"devices": jax.device_count()}
for name, mesh in (("one", None), ("mesh", make_data_mesh())):
    trainer = make_trainer(loss_fn, mesh=mesh)
    params, losses, _ = trainer.train_cohort(params0, clients, np.random.default_rng(7), keys)
    out[name] = [np.asarray(l).ravel().tolist() for l in jax.tree.leaves(params)]
    out[name + "_losses"] = np.asarray(losses).tolist()
    out[name + "_lanes"] = trainer.last_round_stats["lanes"]
print(json.dumps(out))
"""


def test_packed_round_under_a_four_device_mesh_matches_one_device():
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=os.pathsep.join([os.path.join(REPO, "src"), os.environ.get("PYTHONPATH", "")]),
    )
    done = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT, os.path.join(REPO, "tests")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-4000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    if out["devices"] < 2:
        pytest.skip("the forced host device count did not take")
    assert out["mesh_lanes"] % 4 == 0
    for a, b in zip(out["mesh"], out["one"]):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)
    np.testing.assert_allclose(out["mesh_losses"], out["one_losses"], atol=1e-5, rtol=0)


def test_draws_at_one_participant_count_build_one_program(model):
    loss_fn, params0 = model
    clients = make_clients(UNEVEN * 2, np.random.default_rng(2))
    config = FederationConfig(
        rounds=3, local_epochs=2, batch_size=4, seed=5, selection="uniform:0.6"
    )
    fed = Federation(config, clients, loss_fn, AdamW(learning_rate=5e-3))
    out = fed.run(params0)
    assert len({tuple(r.participant_ids) for r in out.history}) == 3
    assert fed.cohort_trainer._round._cache_size() == 1
    assert fed.cohort_trainer.last_round_stats["lanes"] < 12


def test_scanned_steps_are_lanes_times_slots(model):
    loss_fn, params0 = model
    clients = make_clients(UNEVEN, np.random.default_rng(3))
    tracer = Tracer()
    config = FederationConfig(rounds=2, local_epochs=2, batch_size=4, seed=0)
    fed = Federation(config, clients, loss_fn, AdamW(learning_rate=5e-3), tracer=tracer)
    fed.run(params0)
    stats = fed.cohort_trainer.last_round_stats
    L = 2 * max(-(-n // 4) for n in UNEVEN)
    assert stats["scanned_steps"] == stats["lanes"] * L
    for span in tracer.spans("round"):
        assert span.args["scanned_steps"] == span.args["lanes"] * L
        assert span.args["lanes"] == stats["lanes"] < len(clients)
