"""Ahead-of-time compiles for a described TPU v5e, no chip attached.

The Pallas kernels and the cohort round are compiled by the TPU compiler
for one chip of a ``v5e:2x2`` topology at real widths: the GRU-eICU shape
(batch 128, 24 hours, hidden 32), alone and vmapped over clients as the
cohort engine calls it; the SSD scan at mamba2-130m widths (24 heads of 64,
state 128, chunk 256); and one paper-shape federated round with the Pallas
GRU.  Each must contain ``tpu_custom_call``: the kernel was lowered by
Mosaic, not interpreted.  Interpret-mode parity lives in ``test_kernels.py``
and ``test_kernel_backward.py``.

The topology is described inside a module fixture (never at import, so
test workers that do not run this file never load the TPU library), and the
persistent compilation cache is off around every compile: a compile for a
described chip cannot be read back without one.
"""

import contextlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import backend
from repro.kernels.gru_scan.kernel import gru_scan, gru_scan_bwd
from repro.kernels.ssd.kernel import ssd_chunk_scan, ssd_chunk_scan_bwd

GRU_B, GRU_T, GRU_N = 128, 24, 32
CLIENTS = 8
SSD = dict(b=1, nc=2, l=256, h=24, p=64, n=128)
# The paper's federation: 189 hospitals, the largest with 8,336 train stays
# (66 steps of 128 per epoch, 264 over 4 local epochs), 24 hourly steps of
# 38 features; first-fit decreasing packs the 189 into 9 lanes of 264 slots.
# The resident cohort keeps each stay's 912 features as one row padded to
# 1024 lanes.
ROUND = dict(clients=189, rows=8337, lanes=9, slots=264, hours=24, features=38, width=1024)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def cache_off():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.values["jax_enable_compilation_cache"]
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def compile_text(fn, *args) -> str:
    with cache_off():
        return jax.jit(fn).lower(*args).compile().as_text()


def shapes(sharding, *dims, dtype=jnp.float32):
    return [jax.ShapeDtypeStruct(d, dtype, sharding=sharding) for d in dims]


def gru_fwd_shapes(sharding, lead=()):
    b, t, n = GRU_B, GRU_T, GRU_N
    return shapes(sharding, (*lead, b, t, 3 * n), (*lead, n, 3 * n), (*lead, 3 * n))


def gru_bwd_shapes(sharding, lead=()):
    b, t, n = GRU_B, GRU_T, GRU_N
    return gru_fwd_shapes(sharding, lead) + shapes(sharding, (*lead, b, t, n), (*lead, b, t, n))


fwd = lambda x, w, b: gru_scan(x, w, b, interpret=False)
bwd = lambda x, w, b, h, dy: gru_scan_bwd(x, w, b, h, dy, interpret=False)
# name -> (function, its argument shapes on a sharding); "-vmap" is the
# cohort engine's call, one more leading client axis on every argument.
GRU_CASES = {
    "fwd": (fwd, gru_fwd_shapes),
    "bwd": (bwd, gru_bwd_shapes),
    "fwd-vmap": (jax.vmap(fwd), lambda s: gru_fwd_shapes(s, (CLIENTS,))),
    "bwd-vmap": (jax.vmap(bwd), lambda s: gru_bwd_shapes(s, (CLIENTS,))),
}


@pytest.mark.parametrize("case", list(GRU_CASES))
def test_gru_scan_compiles_for_v5e(one_chip, case):
    fn, make_args = GRU_CASES[case]
    assert "tpu_custom_call" in compile_text(fn, *make_args(one_chip))


def ssd_shapes(sharding):
    b, nc, l, h, p, n = (SSD[k] for k in ("b", "nc", "l", "h", "p", "n"))
    return shapes(
        sharding,
        (b, nc, l, h, p), (b, nc, l, h), (b, nc, l, h), (b, nc, l, n), (b, nc, l, n),
    )


def test_ssd_forward_with_states_compiles_for_v5e(one_chip):
    fn = lambda *a: ssd_chunk_scan(*a, interpret=False, return_states=True)
    assert "tpu_custom_call" in compile_text(fn, *ssd_shapes(one_chip))


def test_ssd_backward_compiles_for_v5e(one_chip):
    b, nc, l, h, p, n = (SSD[k] for k in ("b", "nc", "l", "h", "p", "n"))
    extra = shapes(one_chip, (b, nc, h, p, n), (b, nc, l, h, p))
    fn = lambda *a: ssd_chunk_scan_bwd(*a, interpret=False)
    assert "tpu_custom_call" in compile_text(fn, *ssd_shapes(one_chip), *extra)


def test_paper_round_with_pallas_compiles_for_v5e(one_chip, monkeypatch):
    """The resident round program (the one the engine runs every resident
    round: a one-chunk round's only program, which starts the FedAvg sum
    and divides it) at the all-clients round's packed shape, with
    ``use_pallas=True`` and the backend steered to TPU so the forward and
    backward kernels are compiled, not interpreted."""
    from repro.federated.cohort import CohortTrainer
    from repro.models.gru import GRUConfig, init_gru, make_loss_fn
    from repro.optim.adamw import AdamW

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    cfg = GRUConfig(input_dim=ROUND["features"], use_pallas=True)
    trainer = CohortTrainer(
        loss_fn=make_loss_fn(cfg),
        optimizer=AdamW(learning_rate=5e-3, weight_decay=5e-3),
        batch_size=GRU_B,
        local_epochs=1,
        staging="resident",
    )
    params = jax.eval_shape(lambda: init_gru(jax.random.key(0), cfg))
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), params)
    c, rows, lanes, slots = (ROUND[k] for k in ("clients", "rows", "lanes", "slots"))
    args = (
        shapes(one_chip, (c, rows, ROUND["width"]), (c, rows))
        + shapes(one_chip, (lanes, slots), dtype=jnp.int32)  # rows
        + shapes(one_chip, (lanes, slots, GRU_B), dtype=jnp.int32)  # sample_idx
        + shapes(one_chip, (lanes, slots), (lanes, slots), (lanes, slots), dtype=jnp.bool_)
        + shapes(one_chip, (lanes, slots), dtype=jnp.int32)  # client
        + shapes(one_chip, (lanes, slots), dtype=jnp.bool_)  # last_epoch
        + shapes(one_chip, (c, 2), dtype=jnp.uint32)
        + shapes(one_chip, (c,))
    )
    with cache_off():
        total = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
        compiled = trainer._round.lower(
            p, None, *args, total=total, feature_shape=(ROUND["hours"], ROUND["features"])
        ).compile()
    text = compiled.as_text()
    # forward and backward kernel of each of the 2 layers
    assert text.count("tpu_custom_call") >= 4
    mem = compiled.memory_analysis()
    in_use = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert in_use < 0.9 * 16e9, f"round needs {in_use / 1e9:.2f} GB of 16"
