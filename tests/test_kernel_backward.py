"""Backward parity for the training-grade kernel tier.

The residual backward (single reverse scan over stashed hidden / chunk
states) and the hand-written Pallas backward kernels must reproduce the
jnp-oracle gradients everywhere the federated hot path composes them:
plain calls, odd sequence lengths, bf16, under ``vmap`` over clients ×
``lax.scan`` over steps, with the ``REPRO_PALLAS_INTERPRET`` override
forcing the backward kernels, and through a full federated round on the
``mesh="auto"`` leg with buffer donation on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import ArrayDataset, ClientDataset
from repro.federated.server import FederatedConfig, FederatedServer
from repro.kernels import backend
from repro.kernels.analysis import recompute_elimination_report
from repro.kernels.gru_scan.kernel import gru_scan_bwd
from repro.kernels.gru_scan.ops import gru_scan_op, gru_scan_oracle
from repro.kernels.gru_scan.ref import gru_scan_bwd_ref, gru_scan_ref
from repro.kernels.ssd.kernel import ssd_chunk_scan_bwd
from repro.kernels.ssd.ops import ssd_full
from repro.kernels.ssd.ref import (
    ssd_chunk_scan_bwd_ref,
    ssd_chunk_scan_ref,
    ssd_chunk_states_ref,
    ssd_ref,
)
from repro.models.gru import GRUConfig, init_gru, make_loss_fn
from repro.optim.adamw import AdamW

RNG = np.random.default_rng(7)

F32_TOL = 1e-5
BF16_TOL = 1e-2


def assert_grads_close(got, ref, tol: float) -> None:
    for g, r in zip(got, ref):
        g32 = np.asarray(g, np.float32)
        r32 = np.asarray(r, np.float32)
        assert np.all(np.isfinite(g32))
        scale = max(1.0, float(np.max(np.abs(r32))))
        np.testing.assert_array_less(np.max(np.abs(g32 - r32)), tol * scale)


def gru_inputs(b, t, n, dtype=jnp.float32):
    xg = jnp.asarray(RNG.normal(size=(b, t, 3 * n)), dtype)
    whh = jnp.asarray(RNG.normal(size=(n, 3 * n)) * 0.3, dtype)
    bhh = jnp.asarray(RNG.normal(size=(3 * n,)) * 0.1, dtype)
    return xg, whh, bhh


# --------------------------------------------------------------------------
# direct backward parity: residual + Pallas kernels vs the jnp oracle
# --------------------------------------------------------------------------

GRU_ODD_SHAPES = [(3, 7, 16), (2, 13, 32), (5, 31, 8), (1, 1, 8)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,n", GRU_ODD_SHAPES)
def test_gru_residual_backward_matches_oracle(dtype, b, t, n):
    """The op's new backward (residual reverse scan) vs full oracle VJP."""
    xg, whh, bhh = gru_inputs(b, t, n, dtype)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    g = jax.grad(loss(gru_scan_op), argnums=(0, 1, 2))(xg, whh, bhh)
    g_ref = jax.grad(loss(gru_scan_ref), argnums=(0, 1, 2))(xg, whh, bhh)
    assert_grads_close(g, g_ref, tol=F32_TOL if dtype == jnp.float32 else BF16_TOL)


@pytest.mark.parametrize("b,t,n", GRU_ODD_SHAPES)
def test_gru_pallas_backward_kernel_matches_oracle(b, t, n):
    """The hand-written backward kernel (interpret mode) against the oracle
    VJP cotangents directly — not just through the custom_vjp plumbing."""
    xg, whh, bhh = gru_inputs(b, t, n)
    dy = jnp.asarray(RNG.normal(size=(b, t, n)), jnp.float32)
    h_seq = gru_scan_ref(xg, whh, bhh)
    _, vjp = jax.vjp(gru_scan_ref, xg, whh, bhh)
    got = gru_scan_bwd(xg, whh, bhh, h_seq, dy, interpret=True)
    assert_grads_close(got, vjp(dy), tol=F32_TOL)


def test_gru_pallas_backward_ragged_batch_tile():
    """Batch 130 rags against b_tile=128: the zero-padded rows must not
    leak into the shared dW/db accumulators."""
    xg, whh, bhh = gru_inputs(130, 24, 32)
    dy = jnp.asarray(RNG.normal(size=(130, 24, 32)), jnp.float32)
    h_seq = gru_scan_ref(xg, whh, bhh)
    _, vjp = jax.vjp(gru_scan_ref, xg, whh, bhh)
    got = gru_scan_bwd(xg, whh, bhh, h_seq, dy, interpret=True)
    assert_grads_close(got, vjp(dy), tol=F32_TOL)


SSD_ODD_CASES = [(23, 8), (37, 16), (7, 4)]


@pytest.mark.parametrize("s,chunk", SSD_ODD_CASES)
def test_ssd_residual_backward_matches_oracle(s, chunk):
    """Odd lengths rag against the chunking; the residual backward through
    the full unchunked wrapper must match the per-step oracle."""
    b, h, p, n = 1, 2, 8, 8
    x = jnp.asarray(RNG.normal(size=(b, s, h, p)), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(RNG.normal(size=(b, s, h)), jnp.float32))
    a = -jnp.exp(jnp.asarray(RNG.normal(size=(h,)) * 0.3, jnp.float32))
    bm = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    cm = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)

    def loss(fn):
        return lambda xx, dd, bb, cc: jnp.sum(fn(xx, dd, a, bb, cc) ** 2)

    kernel = lambda xx, dd, aa, bb, cc: ssd_full(xx, dd, aa, bb, cc, chunk=chunk)
    g = jax.grad(loss(kernel), argnums=(0, 1, 2, 3))(x, dt, bm, cm)
    g_ref = jax.grad(loss(ssd_ref), argnums=(0, 1, 2, 3))(x, dt, bm, cm)
    assert_grads_close(g, g_ref, tol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("nc,length", [(3, 8), (5, 7)])
def test_ssd_chunked_backward_matches_chunk_oracle(dtype, nc, length):
    """Against the chunk-layout oracle (the old backward's reference) the
    new residual backward must hold 1e-5 f32 / 1e-2 bf16 — same layout,
    so only the backward implementation differs."""
    from repro.kernels.ssd.ops import ssd_chunk_scan

    b, h, p, n = 2, 2, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    xc = jax.random.normal(ks[0], (b, nc, length, h, p)).astype(dtype)
    dtc = jax.nn.softplus(jax.random.normal(ks[1], (b, nc, length, h))).astype(dtype)
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    cum = jnp.cumsum(dtc.astype(jnp.float32) * a, axis=2).astype(dtype)
    bc = (jax.random.normal(ks[3], (b, nc, length, n)) * 0.5).astype(dtype)
    cc = (jax.random.normal(ks[4], (b, nc, length, n)) * 0.5).astype(dtype)

    def loss(fn):
        return lambda *args: jnp.sum(fn(*args).astype(jnp.float32) ** 2)

    g = jax.grad(loss(ssd_chunk_scan), argnums=(0, 1, 2, 3, 4))(xc, dtc, cum, bc, cc)
    g_ref = jax.grad(loss(ssd_chunk_scan_ref), argnums=(0, 1, 2, 3, 4))(
        xc, dtc, cum, bc, cc
    )
    assert_grads_close(g, g_ref, tol=F32_TOL if dtype == jnp.float32 else BF16_TOL)


def _check_ssd_pallas_backward(b, nc, length, h, p, n):
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    xc = jax.random.normal(ks[0], (b, nc, length, h, p), jnp.float32)
    dtc = jax.nn.softplus(jax.random.normal(ks[1], (b, nc, length, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    cum = jnp.cumsum(dtc * a[None, None, None, :], axis=2)
    bc = jax.random.normal(ks[3], (b, nc, length, n)) * 0.5
    cc = jax.random.normal(ks[4], (b, nc, length, n)) * 0.5
    dy = jax.random.normal(ks[5], (b, nc, length, h, p))
    states = ssd_chunk_states_ref(xc, dtc, cum, bc, cc)
    _, vjp = jax.vjp(ssd_chunk_scan_ref, xc, dtc, cum, bc, cc)
    got = ssd_chunk_scan_bwd(xc, dtc, cum, bc, cc, states, dy, interpret=True)
    assert_grads_close(got, vjp(dy), tol=F32_TOL)
    resid = ssd_chunk_scan_bwd_ref(xc, dtc, cum, bc, cc, states, dy)
    assert_grads_close(resid, vjp(dy), tol=F32_TOL)


def test_ssd_pallas_backward_kernel_matches_oracle():
    _check_ssd_pallas_backward(b=2, nc=3, length=8, h=4, p=8, n=16)


def test_ssd_pallas_backward_kernel_multi_head_tile():
    """H=16 is two 8-head tiles: the per-tile dB/dC partials the wrapper
    sums, and the dS carry reset for each (batch, tile) pair."""
    _check_ssd_pallas_backward(b=2, nc=3, length=8, h=16, p=8, n=16)


# --------------------------------------------------------------------------
# composition: vmap over clients × lax.scan over steps
# --------------------------------------------------------------------------


def test_gru_backward_under_vmap_and_scan():
    """The cohort engine's composition: grads under jit(vmap(...)) driven by
    a lax.scan over steps must match the oracle composed identically."""
    clients, b, t, n, steps = 4, 3, 13, 16, 3
    xg = jnp.asarray(RNG.normal(size=(clients, b, t, 3 * n)), jnp.float32)
    whh = jnp.asarray(RNG.normal(size=(clients, n, 3 * n)) * 0.3, jnp.float32)
    bhh = jnp.asarray(RNG.normal(size=(clients, 3 * n)) * 0.1, jnp.float32)

    def train(op):
        grad_one = jax.grad(lambda w, x, bb: jnp.sum(op(x, w, bb) ** 2))

        def step(w, _):
            g = jax.vmap(grad_one)(w, xg, bhh)
            return w - 1e-3 * g, jnp.sum(g ** 2)

        return jax.jit(lambda w: jax.lax.scan(step, w, None, length=steps))

    (w_op, gs_op) = train(gru_scan_op)(whh)
    (w_ref, gs_ref) = train(gru_scan_ref)(whh)
    assert_grads_close([w_op], [w_ref], tol=F32_TOL)
    np.testing.assert_allclose(np.asarray(gs_op), np.asarray(gs_ref), rtol=1e-5)


def test_ssd_backward_under_vmap_and_scan():
    clients, b, s, h, p, n = 3, 1, 16, 2, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (clients, b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (clients, b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bm = jax.random.normal(ks[3], (clients, b, s, n)) * 0.5
    cm = jax.random.normal(ks[4], (clients, b, s, n)) * 0.5

    def train(fn):
        grad_one = jax.grad(
            lambda xx, dd, bb, cc: jnp.sum(fn(xx, dd, a, bb, cc) ** 2)
        )

        def step(carry, _):
            g = jax.vmap(grad_one)(carry, dt, bm, cm)
            return carry - 1e-3 * g, jnp.sum(g ** 2)

        return jax.jit(lambda xx: jax.lax.scan(step, xx, None, length=2))

    kernel = lambda xx, dd, aa, bb, cc: ssd_full(xx, dd, aa, bb, cc, chunk=8)
    (x_op, gs_op) = train(kernel)(x)
    (x_ref, gs_ref) = train(ssd_ref)(x)
    assert_grads_close([x_op], [x_ref], tol=1e-4)
    np.testing.assert_allclose(np.asarray(gs_op), np.asarray(gs_ref), rtol=1e-4)


# --------------------------------------------------------------------------
# backend selection + env override
# --------------------------------------------------------------------------


def test_backend_interpret_env_override(monkeypatch):
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    on_tpu = backend.on_tpu()
    assert backend.interpret() == (not on_tpu)
    assert backend.pallas_backward() == on_tpu
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert backend.interpret() is True
    assert backend.pallas_backward() is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "off")
    assert backend.pallas_backward() == on_tpu


def test_env_override_never_interprets_on_tpu(monkeypatch):
    """On a TPU the kernels compile whatever REPRO_PALLAS_INTERPRET says;
    an explicit ``interpret`` argument still wins."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert backend.interpret() is False
    assert backend.resolve_interpret(None) is False
    assert backend.resolve_interpret(True) is True
    assert backend.pallas_backward() is True


def test_forced_interpret_routes_backward_through_pallas(monkeypatch):
    """With REPRO_PALLAS_INTERPRET=1 the custom_vjp backward runs the
    hand-written Pallas kernels (interpret mode) — and still matches."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert backend.pallas_backward()
    xg, whh, bhh = gru_inputs(3, 9, 16)
    loss = lambda fn: (lambda *a: jnp.sum(fn(*a) ** 2))
    g = jax.grad(loss(gru_scan_op), argnums=(0, 1, 2))(xg, whh, bhh)
    g_ref = jax.grad(loss(gru_scan_ref), argnums=(0, 1, 2))(xg, whh, bhh)
    assert_grads_close(g, g_ref, tol=F32_TOL)


def test_recompute_elimination_structural():
    """The jaxpr check the benchmark report asserts on: the residual
    backward has strictly fewer scan sites than the oracle pairing."""
    xg, whh, bhh = gru_inputs(4, 12, 16)
    rep = recompute_elimination_report(gru_scan_op, gru_scan_oracle, xg, whh, bhh)
    assert rep["recompute_eliminated"]
    assert rep["residual_bwd"]["scans"] == 1
    assert rep["oracle_bwd"]["scans"] >= 2


# --------------------------------------------------------------------------
# full federated round: use_pallas=True vs jnp path, engines × staging × mesh
# --------------------------------------------------------------------------

NUM_CLIENTS, SEQ_LEN, FEAT = 8, 6, 5


@pytest.fixture(scope="module")
def fed_clients():
    rng = np.random.default_rng(11)
    clients = []
    for i, stays in enumerate(rng.integers(4, 9, NUM_CLIENTS)):
        x = rng.normal(size=(int(stays), SEQ_LEN, FEAT)).astype(np.float32)
        y = rng.uniform(0.5, 20.0, size=int(stays)).astype(np.float32)
        ds = ArrayDataset(x, y)
        clients.append(ClientDataset(client_id=i, train=ds, val=ds))
    return clients


def run_round(clients, *, use_pallas: bool, **cfg_kwargs):
    cfg = GRUConfig(input_dim=FEAT, hidden_dim=4, num_layers=2, dropout=0.0,
                    use_pallas=use_pallas)
    params0 = init_gru(jax.random.key(2), cfg)
    fed = FederatedConfig(rounds=2, local_epochs=1, batch_size=4, seed=0,
                          donate_buffers=True, **cfg_kwargs)
    opt = AdamW(learning_rate=5e-3, weight_decay=5e-3)
    return FederatedServer(fed, clients, make_loss_fn(cfg), opt).run(params0)


def assert_params_close(a, b, atol=F32_TOL):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=atol, rtol=0)


@pytest.mark.parametrize("engine", ["vectorized", "sequential"])
@pytest.mark.parametrize("staging", ["rebuild", "resident"])
def test_federated_round_use_pallas_parity(fed_clients, engine, staging):
    """Acceptance bar: a full federated round with use_pallas=True matches
    the jnp path to 1e-5 under both engines × both staging modes."""
    ref = run_round(fed_clients, use_pallas=False, engine=engine, staging=staging)
    pal = run_round(fed_clients, use_pallas=True, engine=engine, staging=staging)
    assert_params_close(ref.params, pal.params)
    np.testing.assert_allclose(
        [r.mean_local_loss for r in ref.history],
        [r.mean_local_loss for r in pal.history],
        atol=F32_TOL,
    )


def test_federated_round_use_pallas_parity_mesh(fed_clients):
    """The mesh='auto' leg (shard_map over the data mesh on CI's 4-device
    matrix entry, plain vmap on 1 device) with donation on."""
    ref = run_round(fed_clients, use_pallas=False, engine="vectorized", mesh="auto")
    pal = run_round(fed_clients, use_pallas=True, engine="vectorized", mesh="auto")
    assert_params_close(ref.params, pal.params)
