"""The benchmark's own copy of the synthetic eICU cohort (paper Table 2).

The program generates its cohort inside the job service from the spec's
``data`` section; the benchmark cannot hand it arrays.  The plain
reference needs the same stays, so this module regenerates them with a
copy of the generator's arithmetic (same numpy calls in the same order),
independent of the program's code.  If the program's cohort ever drifts
from this one, the reference comparison in ``compare.py`` fails.

Only the training split is built: federated rounds never read the rest.
"""

from __future__ import annotations

import dataclasses

import numpy as np

NUM_HOSPITALS = 189
TOTAL_STAYS = 89_127
TRAIN_FRACTION = 62_375 / TOTAL_STAYS
VAL_FRACTION = 13_376 / TOTAL_STAYS
NUM_TEMPORAL = 20
NUM_STATIC = 18
NUM_HOURS = 24
LOS_MU0 = float(np.log(2.27))
LOS_SIGMA0 = float(np.sqrt(2.0 * np.log(3.69 / 2.27)))
# The paper's ten LoS bins (days) for the recruitment histograms.
LOS_BIN_EDGES = (0.0, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0, 14.0, np.inf)
MIN_TRAIN = 2  # hospitals with fewer train stays are dropped (208 -> 189 cut)


@dataclasses.dataclass(frozen=True)
class Client:
    client_id: int
    x: np.ndarray  # (n, 24, 38) float32, temporal fused with tiled static
    y: np.ndarray  # (n,) float32 LoS in days

    @property
    def n(self) -> int:
        return int(self.y.size)

    def histogram(self) -> np.ndarray:
        counts, _ = np.histogram(np.asarray(self.y, np.float64), bins=np.asarray(LOS_BIN_EDGES))
        return counts.astype(np.int64)


def _sizes(rng, hospitals, total, min_size, power):
    raw = rng.pareto(power, size=hospitals) + 1.0
    budget = total - min_size * hospitals
    if budget < 0:
        raise ValueError("total_stays too small for min_hospital_size * num_hospitals")
    sizes = np.floor(raw / raw.sum() * budget).astype(np.int64) + min_size
    order = np.argsort(-sizes)
    sizes[order[: total - int(sizes.sum())]] += 1
    return sizes


def train_clients(data: dict) -> list[Client]:
    """Training split per hospital for a config's ``data`` section.

    Keys: ``scale`` (fraction of the paper's stays), ``seed`` (the cohort
    seed), ``split_mode`` (only ``"global"``, the paper's protocol) and
    ``num_hospitals`` (null = 189).
    """
    if data.get("split_mode", "global") != "global":
        raise ValueError("the benchmark's cohort copy implements split_mode 'global' only")
    hospitals = int(data.get("num_hospitals") or NUM_HOSPITALS)
    total, min_size = TOTAL_STAYS, 25
    scale = float(data.get("scale", 1.0))
    if scale != 1.0:
        total = max(int(TOTAL_STAYS * scale), hospitals * 4)
        min_size = max(2, int(25 * scale))
    rng = np.random.default_rng(int(data.get("seed", 0)))

    sizes = _sizes(rng, hospitals, total, min_size, 1.3)
    hospital_id = np.repeat(np.arange(hospitals, dtype=np.int32), sizes)
    n = total
    mu_h = LOS_MU0 + rng.normal(0.0, 0.35, size=hospitals)
    sigma_h = LOS_SIGMA0 * rng.uniform(0.75, 1.30, size=hospitals)
    y = np.exp(rng.normal(mu_h[hospital_id], sigma_h[hospital_id])).astype(np.float32)
    y = np.clip(y, 2.0 / 24.0, 120.0)
    severity = (np.log(y) - LOS_MU0) / LOS_SIGMA0
    severity = severity + rng.normal(0.0, 1.05, size=n)
    off_t = rng.normal(0.0, 0.3, size=(hospitals, NUM_TEMPORAL))
    off_s = rng.normal(0.0, 0.3, size=(hospitals, NUM_STATIC))
    noise_h = rng.uniform(1.0, 1.0, size=hospitals)
    load_t = rng.normal(0.0, 1.0, size=NUM_TEMPORAL)
    trend = rng.normal(0.0, 0.15, size=NUM_TEMPORAL)
    hours = np.arange(NUM_HOURS, dtype=np.float32)
    base = severity[:, None] * load_t[None, :]
    x_t = (
        base[:, None, :]
        + trend[None, None, :] * (hours[None, :, None] / NUM_HOURS) * severity[:, None, None]
        + 0.10 * np.sin(2 * np.pi * hours[None, :, None] / 24.0)
        + off_t[hospital_id][:, None, :]
        + noise_h[hospital_id][:, None, None]
        * rng.normal(0.0, 1.0, size=(n, NUM_HOURS, NUM_TEMPORAL))
    ).astype(np.float32)
    load_s = rng.normal(0.0, 0.8, size=NUM_STATIC)
    x_s = (
        severity[:, None] * load_s[None, :]
        + off_s[hospital_id]
        + noise_h[hospital_id][:, None] * rng.normal(0.0, 1.0, size=(n, NUM_STATIC))
    ).astype(np.float32)
    unit = rng.integers(0, 4, size=n)
    for k in range(4):
        x_s[:, k] = (unit == k).astype(np.float32)
    perm = rng.permutation(n)
    train = np.zeros(n, dtype=bool)
    train[perm[: int(round(TRAIN_FRACTION * n))]] = True

    fused = np.concatenate(
        [x_t, np.repeat(x_s[:, None, :], NUM_HOURS, axis=1)], axis=-1
    ).astype(np.float32)
    clients = []
    for h in range(hospitals):
        m = (hospital_id == h) & train
        if int(m.sum()) >= MIN_TRAIN:
            clients.append(Client(h, fused[m], y[m]))
    return clients
