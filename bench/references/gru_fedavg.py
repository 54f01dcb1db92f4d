"""Plain reference: the paper's federated GRU, written from its description.

arXiv:2304.14663 (Table 1, eqs. 1-6): a 2-layer GRU (hidden 32, dropout
0.05 between layers) reads 24 hourly steps of 38 features; a ReLU head on
the last hidden state predicts length of stay; the loss is the mean
squared logarithmic error.  Each participating hospital runs
``local_epochs`` of AdamW (fresh optimizer state every round) over
shuffled minibatches of its own stays, and the server takes the FedAvg
mean of the returned parameters weighted by each hospital's stay count.
Recruitment is the paper's nu-greedy rule (eqs. 4-5) and per-round
selection is a uniform draw without replacement.

Written straight from that description in ``jax.numpy``: one client at a
time, one minibatch at a time, only the real batches of each client, no
padding of clients or steps, no vmap, no kernels.  It imports nothing of
the program.  What it shares with the program is only what defines the
job: the seed, and the order in which the seed's random streams are read
(numpy ``default_rng(seed)`` for selection and shuffles, client-major;
``jax.random.key(seed)`` split once per participant, then once per real
local step, the step's second key feeding dropout).

``mode`` is the arithmetic.  ``"default"`` is what the configurations
state: float32 everywhere, matmuls (the FedAvg weighted sum among them) at
JAX's default precision, which on a TPU is one bfloat16 pass.  The control, ``"bfloat16"``, casts parameters and inputs to bfloat16 for
the forward and backward passes while AdamW and FedAvg stay as in
``"default"``: the mixed-precision step a later change might take.
``fault="half_batch"`` leaves the second half of every minibatch out of
the loss and takes the mean over the rest.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

@dataclasses.dataclass(frozen=True)
class Model:
    input_dim: int
    hidden_dim: int
    num_layers: int
    dropout: float


def model_of(config: dict) -> Model:
    m = config["model"]
    return Model(int(m["input_dim"]), int(m["hidden_dim"]), int(m["num_layers"]), float(m["dropout"]))


def init_params(seed: int, model: Model) -> dict[str, jax.Array]:
    """torch.nn.GRU-style uniform(-1/sqrt(N), 1/sqrt(N)) init from ``key(seed)``.

    Per layer the key splits five ways (carry, w_ih, w_hh, b_ih, b_hh);
    then once more for the head weight; the head bias starts at zero.
    """
    key = jax.random.key(seed)
    s = 1.0 / jnp.sqrt(model.hidden_dim)
    g = 3 * model.hidden_dim
    out: dict[str, jax.Array] = {}
    for i in range(model.num_layers):
        key, k1, k2, k3, k4 = jax.random.split(key, 5)
        fan_in = model.input_dim if i == 0 else model.hidden_dim
        out[f"layers/{i}/w_ih"] = jax.random.uniform(k1, (fan_in, g), minval=-s, maxval=s)
        out[f"layers/{i}/w_hh"] = jax.random.uniform(k2, (model.hidden_dim, g), minval=-s, maxval=s)
        out[f"layers/{i}/b_ih"] = jax.random.uniform(k3, (g,), minval=-s, maxval=s)
        out[f"layers/{i}/b_hh"] = jax.random.uniform(k4, (g,), minval=-s, maxval=s)
    key, k_head = jax.random.split(key)
    out["head/w"] = jax.random.uniform(k_head, (model.hidden_dim, 1), minval=-s, maxval=s)
    out["head/b"] = jnp.zeros((1,))
    return out


def _predict(p, x, key, model: Model, dtype, precision):
    """Eq. (1) over 24 steps per layer, dropout between layers, eq. (2) head."""
    n = model.hidden_dim
    h_seq = x.astype(dtype)
    for i in range(model.num_layers):
        w_ih, w_hh = p[f"layers/{i}/w_ih"].astype(dtype), p[f"layers/{i}/w_hh"].astype(dtype)
        b_ih, b_hh = p[f"layers/{i}/b_ih"].astype(dtype), p[f"layers/{i}/b_hh"].astype(dtype)

        def cell(h, x_t, w_ih=w_ih, w_hh=w_hh, b_ih=b_ih, b_hh=b_hh):
            gi = jnp.dot(x_t, w_ih, precision=precision) + b_ih
            gh = jnp.dot(h, w_hh, precision=precision) + b_hh
            r = jax.nn.sigmoid(gi[:, :n] + gh[:, :n])
            z = jax.nn.sigmoid(gi[:, n : 2 * n] + gh[:, n : 2 * n])
            cand = jnp.tanh(gi[:, 2 * n :] + r * gh[:, 2 * n :])
            h = (1.0 - z) * cand + z * h
            return h, h

        h0 = jnp.zeros((x.shape[0], n), dtype)
        _, hs = jax.lax.scan(cell, h0, jnp.swapaxes(h_seq, 0, 1))
        h_seq = jnp.swapaxes(hs, 0, 1)
        if model.dropout > 0.0 and i < model.num_layers - 1:
            key, sub = jax.random.split(key)
            keep = jax.random.bernoulli(sub, 1.0 - model.dropout, h_seq.shape)
            h_seq = jnp.where(keep, h_seq / (1.0 - model.dropout), 0.0).astype(dtype)
    w, b = p["head/w"].astype(dtype), p["head/b"].astype(dtype)
    y_hat = jax.nn.relu(jnp.dot(h_seq[:, -1, :], w, precision=precision) + b)
    return y_hat[:, 0].astype(jnp.float32)


def _msle(p, x, y, mask, key, model, dtype, precision):
    y_hat = _predict(p, x, key, model, dtype, precision)
    err = (jnp.log1p(y) - jnp.log1p(y_hat)) ** 2
    return jnp.sum(err * mask) / jnp.maximum(jnp.sum(mask), 1.0)


MODES = {  # mode -> (compute dtype, matmul precision)
    "default": (jnp.float32, None),
    "bfloat16": (jnp.bfloat16, None),
}


@functools.lru_cache(maxsize=None)
def _step_fn(model: Model, opt: tuple, mode: str):
    lr, wd, b1, b2, eps = opt
    dtype, precision = MODES[mode]

    @jax.jit
    def step(p, mu, nu, count, key, x_all, y_all, idx, mask):
        keys = jax.random.split(key)
        x, y = x_all[idx], y_all[idx]
        loss, g = jax.value_and_grad(_msle)(p, x, y, mask, keys[1], model, dtype, precision)
        count = count + 1
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)
        new_p, new_mu, new_nu = {}, {}, {}
        for k in p:
            gk = g[k].astype(jnp.float32)
            new_mu[k] = b1 * mu[k] + (1 - b1) * gk
            new_nu[k] = b2 * nu[k] + (1 - b2) * (gk * gk)
            adam = (new_mu[k] / c1) / (jnp.sqrt(new_nu[k] / c2) + eps)
            new_p[k] = p[k] - lr * (adam + wd * p[k])
        return new_p, new_mu, new_nu, count, keys[0], loss

    return step


# ---------------------------------------------------------------------------
# recruitment and selection (spec strings of the traffic file)
# ---------------------------------------------------------------------------


def recruit(spec: str, clients) -> np.ndarray:
    """Federation ids, ascending.  ``all`` or ``nu-greedy:gdv,gsa,gth``."""
    ids = np.array([c.client_id for c in clients], dtype=np.int64)
    if spec == "all":
        return np.sort(ids)
    name, _, args = spec.partition(":")
    if name != "nu-greedy" or not args:
        raise ValueError(f"reference knows 'all' and 'nu-greedy:gdv,gsa,gth', not {spec!r}")
    gdv, gsa, gth = (float(a) for a in args.split(","))
    counts = np.stack([c.histogram().astype(np.float64) for c in clients])
    n = np.array([c.n for c in clients], dtype=np.float64)
    p_global = counts.sum(axis=0) / counts.sum()
    p_local = counts / np.maximum(counts.sum(axis=1), 1.0)[:, None]
    nu = gdv * np.abs(p_global[None, :] - p_local).sum(axis=1) + gsa * n**-0.5  # eq. 4
    order = np.argsort(nu, kind="stable")
    cum = np.cumsum(nu[order])
    if gth >= 1.0:
        cut = len(cum)
    else:  # eq. 5: up to and including the client whose prefix sum crosses iota
        tol = 1e-12 * max(float(cum[-1]), 1.0)
        cut = min(int(np.searchsorted(cum, gth * float(cum[-1]) - tol, side="left")) + 1, len(cum))
    return np.sort(ids[order][:cut])


def select(spec: str, federation: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """``uniform`` (everyone), ``uniform:<fraction>`` or ``uniform:<count>``."""
    name, _, arg = spec.partition(":")
    if name != "uniform":
        raise ValueError(f"reference knows 'uniform[:fraction|count]', not {spec!r}")
    if not arg:
        return np.sort(federation)
    count = max(1, int(round(float(arg) * len(federation)))) if "." in arg else int(arg)
    return np.sort(rng.choice(federation, size=min(count, len(federation)), replace=False))


def draws(traffic: dict, federation: np.ndarray, sizes: dict[int, int], seed: int, rounds: int) -> list[list[int]]:
    """Participant ids of the first ``rounds`` rounds over a recruited federation.

    Replays the numpy stream only: each round's selection draw, then one
    permutation per participant per epoch.  ``sizes`` maps ids to stay counts.
    """
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(rounds):
        part = select(traffic["selection"], federation, rng)
        for cid in part:
            for _ in range(int(traffic["local_epochs"])):
                rng.permutation(sizes[int(cid)])
        out.append([int(c) for c in part])
    return out


def participants(traffic: dict, clients, seed: int, rounds: int) -> list[list[int]]:
    """Participant ids of the first ``rounds`` rounds, without training."""
    fed = recruit(traffic["recruitment"], clients)
    return draws(traffic, fed, {c.client_id: c.n for c in clients}, seed, rounds)


# ---------------------------------------------------------------------------
# training rounds
# ---------------------------------------------------------------------------


class _Rounds:
    """What every round shares: the step, FedAvg, and the stays on the device."""

    def __init__(self, config: dict, traffic: dict, clients, mode: str, fault: str | None, device):
        if mode not in MODES:
            raise ValueError(f"unknown mode {mode!r}")
        if fault not in (None, "half_batch"):
            raise ValueError(f"unknown fault {fault!r}")
        self.model = model_of(config)
        o = config["optimizer"]
        opt = (float(o["learning_rate"]), float(o["weight_decay"]), float(o["b1"]), float(o["b2"]), float(o["eps"]))
        self.batch = int(traffic["batch_size"])
        self.epochs = int(traffic["local_epochs"])
        self.fault = fault
        self.step = _step_fn(self.model, opt, mode)
        self.fedavg = _fedavg_fn(MODES[mode][1])
        self.device = device or jax.devices()[0]
        # Every client's stays in one array, plus one all-zero row for batch tails.
        self.offsets, total = {}, 0
        for c in clients:
            self.offsets[c.client_id] = total
            total += c.n
        self.pad_row = total
        x_all = np.zeros((total + 1, *clients[0].x.shape[1:]), np.float32)
        y_all = np.zeros((total + 1,), np.float32)
        for c in clients:
            x_all[self.offsets[c.client_id] : self.offsets[c.client_id] + c.n] = c.x
            y_all[self.offsets[c.client_id] : self.offsets[c.client_id] + c.n] = c.y
        self.x_all, self.y_all = jax.device_put((x_all, y_all), self.device)
        self.by_id = {c.client_id: c for c in clients}

    def init(self, seed: int) -> dict:
        with jax.default_device(self.device):
            return init_params(seed, self.model)

    def round(self, p: dict, part, rng: np.random.Generator, chain) -> tuple[dict, list[float], jax.Array]:
        """One FedAvg round over ``part``: (new params, per-client losses, chain).

        A client's loss is its mean minibatch loss over its last epoch.
        """
        returned, client_losses = [], []
        for cid in part:
            client = self.by_id[int(cid)]
            chain, key = jax.random.split(chain)
            cp = p
            mu = {k: jnp.zeros_like(v) for k, v in p.items()}
            nu = {k: jnp.zeros_like(v) for k, v in p.items()}
            count = jnp.zeros((), jnp.int32)
            last = []
            for epoch in range(self.epochs):
                perm = rng.permutation(client.n)
                for s in range(0, client.n, self.batch):
                    sel = perm[s : s + self.batch]
                    idx = np.full(self.batch, self.pad_row, np.int32)
                    idx[: sel.size] = self.offsets[client.client_id] + sel
                    mask = np.zeros(self.batch, np.float32)
                    mask[: sel.size] = 1.0
                    if self.fault == "half_batch":
                        mask[self.batch // 2 :] = 0.0
                    cp, mu, nu, count, key, loss = self.step(cp, mu, nu, count, key, self.x_all, self.y_all, idx, mask)
                    if epoch == self.epochs - 1:
                        last.append(loss)
            client_losses.append(float(np.mean(np.asarray(jnp.stack(last)))))
            returned.append(cp)
        sizes = [self.by_id[int(c)].n for c in part]
        weights = jax.device_put(np.asarray(sizes, np.float32), self.device)
        return self.fedavg(returned, weights, float(sum(sizes))), client_losses, chain


def train(
    config: dict,
    traffic: dict,
    clients,
    seed: int,
    rounds: int,
    *,
    mode: str = "default",
    fault: str | None = None,
    device=None,
    extra_round: list[int] | None = None,
) -> dict:
    """Run ``rounds`` FedAvg rounds; return losses and parameters.

    Returns ``{"p0", "params" (after each round), "losses" (per round: the
    mean over participants of each one's mean loss over its last epoch),
    "client_losses" (per round, per participant), "participants"}``.
    Parameters are flat ``{"layers/0/w_ih": array}`` dicts of numpy float32.

    ``extra_round``: participant ids of one more round from the initial
    weights, with its own streams (``default_rng(seed)`` for shuffles,
    ``key(seed)`` for the chain, no selection draw); its outputs are under
    ``"extra"`` as ``{"params", "client_losses"}``.
    """
    r = _Rounds(config, traffic, clients, mode, fault, device)
    fed = recruit(traffic["recruitment"], clients)
    rng = np.random.default_rng(seed)
    chain = jax.random.key(seed)
    p0 = r.init(seed)
    p = p0
    out = {"p0": _host(p0), "params": [], "losses": [], "client_losses": [], "participants": []}
    for _ in range(rounds):
        part = select(traffic["selection"], fed, rng)
        p, client_losses, chain = r.round(p, part, rng, chain)
        out["params"].append(_host(p))
        out["losses"].append(float(np.mean(client_losses)))
        out["client_losses"].append(client_losses)
        out["participants"].append([int(c) for c in part])
    if extra_round is not None:
        p, client_losses, _ = r.round(p0, extra_round, np.random.default_rng(seed), jax.random.key(seed))
        out["extra"] = {"params": _host(p), "client_losses": client_losses}
    return out


@functools.lru_cache(maxsize=None)
def _fedavg_fn(precision):
    """FedAvg: the stay-count-weighted sum of the returned parameters as one
    contraction over clients, at the mode's matmul precision, over the
    total stay count."""

    @jax.jit
    def fedavg(returned, weights, total):
        return {
            k: jnp.tensordot(weights, jnp.stack([r[k] for r in returned]), axes=((0,), (0,)), precision=precision)
            / total
            for k in returned[0]
        }

    return fedavg


def _host(tree: dict) -> dict[str, np.ndarray]:
    return {k: np.asarray(v, np.float32) for k, v in tree.items()}
