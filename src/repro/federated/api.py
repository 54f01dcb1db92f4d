"""Composable federation API: pluggable recruitment / selection / aggregation.

The paper's contribution is a *policy* — recruit clients from their output
distribution and sample size before the federation forms — yet the healthcare
FL literature treats recruitment, per-round selection, and aggregation as
interchangeable pipeline stages.  This module makes those three stages the
extension points of the runtime:

* ``RecruitmentPolicy`` — who joins the federation, decided once before
  round one from the disclosure tuples ``(P_co, n_c)``.  Built-ins:
  ``"nu-greedy"`` (the paper's greedy threshold rule, wrapping
  ``repro.core.recruitment``), ``"random-k"``, ``"top-n-samples"``, and
  ``"all"``.
* ``SelectionPolicy`` — which federation members train in a given round.
  Built-ins: ``"uniform"`` (the paper's uniform fraction/count sampling),
  ``"round-robin"`` (deterministic rotation), and ``"loss-weighted"``
  (sample proportional to last observed local loss).
* ``Aggregator`` — how client updates become the new global params.
  Built-ins: ``"fedavg"`` (weighted average, the engines' streamed in-jit
  reduction), ``"trimmed-mean"`` (coordinate-wise robust mean), and
  ``"hierarchical"`` (two-level FedAvg: regional sub-federations reduce —
  a psum per region under a mesh — then regions are averaged; the seed of
  the ROADMAP's multi-pod aggregation tier).

Every policy is resolvable from a string spec ``name`` or ``name:arg,...``
(``recruitment="nu-greedy"``, ``selection="uniform:0.1"``,
``aggregator="hierarchical:4"``) so :class:`FederationConfig` stays fully
declarative, or an instance can be passed directly.  User-defined policies
subclass the base classes and either register themselves
(:func:`register_recruitment` and friends) or are handed to the config as
objects — see ``examples/custom_policy.py``.

The round program
-----------------
:class:`Federation` decomposes the old monolithic ``FederatedServer.run``
loop into a fixed round program both engines, both staging modes, donation,
and shard_map flow through unchanged::

    build_federation -> select -> train -> aggregate -> record

How the *train -> aggregate* pair executes depends on the aggregator's
``mode``:

* ``"reduced"`` (fedavg) — the engine's own weighted-sum reduction *is* the
  aggregation: the vectorized engine streams it inside the jitted round
  (chunk accumulator, cross-shard psum), the sequential engine stacks the
  per-client params once.  This is bit-for-bit the pre-API hot path.
* ``"grouped"`` (hierarchical) — participants are partitioned by
  ``Aggregator.groups``; each group runs one engine round (FedAvg within
  the group, a single psum under a mesh), then the group means are combined
  by ``Aggregator.aggregate``.  Contiguous groups consume the shared RNG
  stream in the same client-major order as a flat round, so two-level
  FedAvg matches flat FedAvg within float tolerance.
* ``"stacked"`` (trimmed-mean) — the aggregator needs every client's
  params, which the vectorized engine never materializes (it reduces
  in-jit); these rounds run the per-client trainer and hand the stacked
  pytree to ``Aggregator.aggregate``.
* ``"buffered"`` (fedbuff, hierarchical-async) — a fourth delivery mode
  that never runs here: buffered aggregators are driven by the event loop
  of :class:`repro.federated.runtime.AsyncFederation`, and this facade
  rejects them at construction with a pointer to the async runtime.

Seeded-replay determinism
-------------------------
Every run is a pure function of ``FederationConfig.seed``.  Three
independent streams derive from it: the *recruitment* generator
(``default_rng([seed, 1])``, consumed once before round one), the shared
*batch-plan* generator (``default_rng(seed)``, consumed in client-major
order by selection and the schedule builders), and the jax *key chain*
(``jax.random.key(seed)``, advanced one ``split`` per cohort chunk /
sequential client via ``chain_split_keys``).  Policies draw only from the
generators they are handed at well-defined points, so two runs with equal
seeds replay bit-identically — and a run resumed from a
:class:`FederationSnapshot` (params + round index + both stream states +
adaptive policy state) continues exactly where the interrupted one left
off.  This contract is what the control plane's kill-and-resume parity
tests (`tests/test_federation_service.py`) pin down.

Legacy ``FederatedServer`` / ``FederatedConfig`` remain as thin deprecation
shims in ``repro.federated.server`` that map onto these policies.
"""

from __future__ import annotations

import dataclasses
import difflib
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.recruitment import (
    BALANCED,
    ClientStats,
    RecruitmentConfig,
    RecruitmentResult,
    preset_recruitment,
    recruit,
)
from repro.data.pipeline import ClientDataset, cohort_steps_per_epoch
from repro.federated.client import LocalTrainer
from repro.federated.cohort import STAGING_MODES, CohortTrainer, chain_split_keys
from repro.federated.fedavg import (
    aggregate_stacked,
    params_nbytes,
    trimmed_mean_stacked,
)
from repro.federated.selection import round_robin_clients, select_clients
from repro.obs.metrics import MetricsRegistry
from repro.obs.profile import CompileWatcher
from repro.obs.trace import Tracer, resolve_tracer
from repro.optim.adamw import AdamW
from repro.privacy.accountant import RdpAccountant
from repro.privacy.dp import DPConfig, resolve_dp

PyTree = Any

ENGINES = ("sequential", "vectorized")
AGGREGATION_MODES = ("reduced", "grouped", "stacked")


# ---------------------------------------------------------------------------
# policy protocols
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RecruitmentDecision:
    """What a recruitment policy returns: the federation, plus optional detail."""

    federation_ids: np.ndarray            # sorted client ids admitted to the federation
    detail: RecruitmentResult | None = None  # nu/iota accounting when the policy has it


class RecruitmentPolicy:
    """Decides, once, which candidate clients form the federation.

    Policies see only the disclosure tuples ``(P_co, n_c)`` — never raw
    features or model parameters — so recruitment stays model-agnostic.
    ``rng`` is a dedicated generator (independent of the per-round stream)
    for stochastic policies; deterministic policies ignore it.
    """

    def recruit(
        self, stats: Sequence[ClientStats], rng: np.random.Generator
    ) -> RecruitmentDecision:
        raise NotImplementedError


class SelectionPolicy:
    """Decides which federation members train in one round.

    ``rng`` is the run's shared numpy generator — the same stream the batch
    scheduler consumes, so engines stay in lockstep.  Implementations must
    return participant ids in sorted order (the cohort stacking order).
    ``observe`` is called after every round with the participants and their
    mean local losses, for adaptive policies; the default ignores it.
    """

    def select(
        self, round_index: int, federation_ids: np.ndarray, rng: np.random.Generator
    ) -> np.ndarray:
        raise NotImplementedError

    def observe(self, participant_ids: np.ndarray, losses: np.ndarray) -> None:
        pass

    def state_dict(self) -> dict:
        """JSON-serializable adaptive state for checkpoint/resume.

        Stateless policies (the default) return ``{}``; adaptive ones
        (e.g. loss-weighted) must round-trip everything ``observe``
        accumulated, or a resumed run diverges from the uninterrupted one.
        """
        return {}

    def load_state_dict(self, state: dict) -> None:
        pass


class Aggregator:
    """Combines one round's client updates into the new global params.

    ``mode`` tells the round program how updates must be delivered:
    ``"reduced"`` — the engine's weighted FedAvg reduction is this
    aggregator's exact result (the streamed hot path); ``"grouped"`` — run
    one engine round per ``groups(...)`` partition, then ``aggregate`` the
    stacked group means; ``"stacked"`` — materialize every client's params
    (per-client trainer) and ``aggregate`` the stacked pytree.
    """

    mode: str = "stacked"

    def aggregate(self, stacked: PyTree, weights: np.ndarray) -> PyTree:
        """Reduce a client-stacked pytree (leading client axis) to params."""
        raise NotImplementedError

    def groups(self, participant_ids: np.ndarray) -> list[np.ndarray]:
        """Partition participants for ``mode == "grouped"`` aggregators."""
        raise NotImplementedError


# ---------------------------------------------------------------------------
# string registries
# ---------------------------------------------------------------------------

_RECRUITMENTS: dict[str, Callable[..., RecruitmentPolicy]] = {}
_SELECTIONS: dict[str, Callable[..., SelectionPolicy]] = {}
_AGGREGATORS: dict[str, Callable[..., Aggregator]] = {}


def register_recruitment(name: str):
    """Register a recruitment factory under ``name`` (``@register_recruitment("x")``)."""
    def deco(factory):
        _RECRUITMENTS[name] = factory
        return factory
    return deco


def register_selection(name: str):
    def deco(factory):
        _SELECTIONS[name] = factory
        return factory
    return deco


def register_aggregator(name: str):
    def deco(factory):
        _AGGREGATORS[name] = factory
        return factory
    return deco


def _parse_arg(token: str):
    token = token.strip()
    try:
        return int(token)
    except ValueError:
        pass
    try:
        return float(token)
    except ValueError:
        return token


def _resolve(registry: dict, spec, kind: str, base: type):
    if isinstance(spec, base):
        return spec
    if not isinstance(spec, str):
        raise TypeError(f"{kind} must be a {base.__name__} or a spec string, got {type(spec).__name__}")
    name, _, rest = spec.partition(":")
    if name not in registry:
        known = ", ".join(sorted(registry))
        close = difflib.get_close_matches(name, registry, n=1)
        hint = f"; did you mean {close[0]!r}?" if close else ""
        raise ValueError(
            f"unknown {kind} policy {name!r}{hint}; choose from: {known}"
        )
    args = [_parse_arg(t) for t in rest.split(",")] if rest else []
    return registry[name](*args)


def resolve_recruitment(spec) -> RecruitmentPolicy:
    """``"nu-greedy"`` / ``"nu-greedy:0.5,0.5,0.1"`` / instance -> policy."""
    return _resolve(_RECRUITMENTS, spec, "recruitment", RecruitmentPolicy)


def resolve_selection(spec) -> SelectionPolicy:
    """``"uniform"`` / ``"uniform:0.1"`` / ``"round-robin:4"`` / instance -> policy."""
    return _resolve(_SELECTIONS, spec, "selection", SelectionPolicy)


def resolve_aggregator(spec) -> Aggregator:
    """``"fedavg"`` / ``"trimmed-mean:0.1"`` / ``"hierarchical:4"`` / instance -> policy."""
    return _resolve(_AGGREGATORS, spec, "aggregator", Aggregator)


def available_policies() -> dict[str, tuple[str, ...]]:
    """Registered spec names per stage — the discoverable policy surface."""
    return {
        "recruitment": tuple(sorted(_RECRUITMENTS)),
        "selection": tuple(sorted(_SELECTIONS)),
        "aggregator": tuple(sorted(_AGGREGATORS)),
    }


# ---------------------------------------------------------------------------
# recruitment policies
# ---------------------------------------------------------------------------


@register_recruitment("all")
class AllRecruitment(RecruitmentPolicy):
    """Everyone joins — standard FL (the paper's ac/sc baselines)."""

    def recruit(self, stats, rng) -> RecruitmentDecision:
        ids = np.array(sorted(s.client_id for s in stats), dtype=np.int64)
        return RecruitmentDecision(federation_ids=ids)


class NuGreedyRecruitment(RecruitmentPolicy):
    """The paper's greedy threshold rule (section 4.2) over nu_c.

    Spec forms: ``"nu-greedy"`` (BALANCED), ``"nu-greedy:quality-greedy"``
    (a section 6.2 preset), or ``"nu-greedy:gamma_dv,gamma_sa,gamma_th"``.
    """

    def __init__(self, config: RecruitmentConfig = BALANCED) -> None:
        self.config = config

    def recruit(self, stats, rng) -> RecruitmentDecision:
        result = recruit(stats, self.config)
        return RecruitmentDecision(
            federation_ids=np.sort(result.recruited_ids), detail=result
        )


@register_recruitment("nu-greedy")
def _nu_greedy(*args) -> NuGreedyRecruitment:
    if not args:
        return NuGreedyRecruitment(BALANCED)
    if len(args) == 1 and isinstance(args[0], str):
        return NuGreedyRecruitment(preset_recruitment(args[0]))
    if len(args) == 3:
        return NuGreedyRecruitment(RecruitmentConfig(*[float(a) for a in args]))
    raise ValueError(
        "nu-greedy spec takes no args, one preset name, or gamma_dv,gamma_sa,gamma_th"
    )


@register_recruitment("random-k")
class RandomKRecruitment(RecruitmentPolicy):
    """Recruit ``k`` clients uniformly at random — the recruitment control."""

    def __init__(self, k: int) -> None:
        if int(k) < 1:
            raise ValueError(f"random-k needs k >= 1, got {k}")
        self.k = int(k)

    def recruit(self, stats, rng) -> RecruitmentDecision:
        ids = np.array(sorted(s.client_id for s in stats), dtype=np.int64)
        k = min(self.k, len(ids))
        return RecruitmentDecision(np.sort(rng.choice(ids, size=k, replace=False)))


@register_recruitment("top-n-samples")
class TopNSamplesRecruitment(RecruitmentPolicy):
    """Recruit the ``n`` clients with the most local samples (ties: lower id)."""

    def __init__(self, n: int) -> None:
        if int(n) < 1:
            raise ValueError(f"top-n-samples needs n >= 1, got {n}")
        self.n = int(n)

    def recruit(self, stats, rng) -> RecruitmentDecision:
        ids = np.array([s.client_id for s in stats], dtype=np.int64)
        sizes = np.array([s.n for s in stats], dtype=np.int64)
        order = np.lexsort((ids, -sizes))
        return RecruitmentDecision(np.sort(ids[order[: min(self.n, len(ids))]]))


# ---------------------------------------------------------------------------
# selection policies
# ---------------------------------------------------------------------------


def _frac_or_count(arg) -> dict[str, Any]:
    """Spec arg -> kwargs: a float is a participation fraction, an int a count.

    The distinction is textual: ``"uniform:0.1"`` samples 10%,
    ``"uniform:12"`` samples 12 clients — so full participation by fraction
    must be spelled ``"uniform:1.0"`` (``"uniform:1"`` is a count of one).
    """
    if arg is None:
        return {}
    if isinstance(arg, float):
        return {"fraction": arg}
    if isinstance(arg, int):
        return {"count": arg}
    raise ValueError(f"selection arg must be a fraction or a count, got {arg!r}")


def _check_frac_count(fraction: float | None, count: int | None) -> None:
    """Fail at policy construction, not mid-run, on a bad participation spec."""
    if fraction is not None and count is not None:
        raise ValueError("give fraction or count, not both")
    if fraction is not None and not (0.0 < fraction <= 1.0):
        raise ValueError(f"fraction must be in (0, 1], got {fraction}")
    if count is not None and int(count) < 1:
        raise ValueError(f"count must be >= 1, got {count}")


class UniformSelection(SelectionPolicy):
    """The paper's per-round sampling: uniform without replacement.

    ``fraction``/``count`` both ``None`` means every federation member
    participates every round (the ac/arc settings).
    """

    def __init__(self, fraction: float | None = None, count: int | None = None) -> None:
        _check_frac_count(fraction, count)
        self.fraction, self.count = fraction, count

    def select(self, round_index, federation_ids, rng) -> np.ndarray:
        return select_clients(rng, federation_ids, fraction=self.fraction, count=self.count)


@register_selection("uniform")
def _uniform(arg=None) -> UniformSelection:
    return UniformSelection(**_frac_or_count(arg))


class RoundRobinSelection(SelectionPolicy):
    """Deterministic rotation through the sorted federation — no RNG at all.

    Every client participates at least once per ``ceil(N / k)`` consecutive
    rounds (exactly once when ``k`` divides ``N``; otherwise the wrapping
    window re-visits a few early ids each cycle), and per-round cohorts are
    reproducible independent of the seed.
    """

    def __init__(self, fraction: float | None = None, count: int | None = None) -> None:
        _check_frac_count(fraction, count)
        self.fraction, self.count = fraction, count

    def select(self, round_index, federation_ids, rng) -> np.ndarray:
        n = len(federation_ids)
        if self.fraction is None and self.count is None:
            count = n
        elif self.count is not None:
            count = min(int(self.count), n)
        else:
            count = max(1, int(round(self.fraction * n)))
        return round_robin_clients(round_index, federation_ids, count)


@register_selection("round-robin")
def _round_robin(arg=None) -> RoundRobinSelection:
    return RoundRobinSelection(**_frac_or_count(arg))


class LossWeightedSelection(SelectionPolicy):
    """Sample proportionally to each client's last observed local loss.

    Clients not yet observed weigh in at the mean observed loss (or
    uniformly before any observation), so round one degenerates to uniform
    sampling and coverage self-corrects as losses arrive.
    """

    def __init__(self, fraction: float | None = None, count: int | None = None) -> None:
        _check_frac_count(fraction, count)
        self.fraction, self.count = fraction, count
        self._loss: dict[int, float] = {}

    def observe(self, participant_ids, losses) -> None:
        for cid, loss in zip(np.asarray(participant_ids), np.asarray(losses)):
            if np.isfinite(loss):
                self._loss[int(cid)] = float(loss)

    def state_dict(self) -> dict:
        return {"loss": {str(cid): loss for cid, loss in self._loss.items()}}

    def load_state_dict(self, state: dict) -> None:
        self._loss = {int(cid): float(v) for cid, v in state.get("loss", {}).items()}

    def select(self, round_index, federation_ids, rng) -> np.ndarray:
        ids = np.asarray(federation_ids)
        n = len(ids)
        if self.fraction is None and self.count is None:
            count = n
        elif self.count is not None:
            count = min(int(self.count), n)
        else:
            count = max(1, int(round(self.fraction * n)))
        default = float(np.mean(list(self._loss.values()))) if self._loss else 1.0
        w = np.array([self._loss.get(int(c), default) for c in ids], dtype=np.float64)
        w = np.maximum(w, 1e-12)
        chosen = rng.choice(ids, size=count, replace=False, p=w / w.sum())
        return np.sort(chosen)


@register_selection("loss-weighted")
def _loss_weighted(arg=None) -> LossWeightedSelection:
    return LossWeightedSelection(**_frac_or_count(arg))


# ---------------------------------------------------------------------------
# aggregators
# ---------------------------------------------------------------------------


@register_aggregator("fedavg")
class FedAvgAggregator(Aggregator):
    """Sample-size-weighted parameter averaging (McMahan et al. 2017).

    ``mode = "reduced"``: the engines implement this exact reduction on
    their hot path (streamed chunk accumulator + psum), so no per-client
    params ever materialize.
    """

    mode = "reduced"

    def aggregate(self, stacked, weights):
        return aggregate_stacked(stacked, weights)


@register_aggregator("trimmed-mean")
class TrimmedMeanAggregator(Aggregator):
    """Coordinate-wise trimmed mean (Yin et al. 2018) — outlier-robust.

    Drops the ``floor(trim * C)`` smallest and largest values of every
    coordinate across the client axis, then averages the rest (unweighted,
    as in the robust-aggregation literature).  ``trim = 0`` is the plain
    coordinate mean.
    """

    mode = "stacked"

    def __init__(self, trim: float = 0.1) -> None:
        if not (0.0 <= trim < 0.5):
            hint = (
                f" — did you mean trim={min(trim / 2, 0.45):g} "
                "(the fraction trimmed from *each* tail)?"
                if 0.5 <= trim < 1.0
                else (
                    f" — to trim {trim:g} clients per tail out of C, pass "
                    f"the fraction {trim:g}/C"
                    if trim >= 1.0
                    else ""
                )
            )
            raise ValueError(
                f"trim fraction must be in [0, 0.5), got {trim}: trimming "
                f"half or more from both tails leaves no clients{hint}"
            )
        self.trim = float(trim)

    def aggregate(self, stacked, weights):
        return trimmed_mean_stacked(stacked, self.trim)


@register_aggregator("hierarchical")
class HierarchicalFedAvg(Aggregator):
    """Two-level FedAvg: regional sub-federations reduce first.

    Participants are split into ``num_regions`` contiguous groups; each
    group runs one engine round (its weighted sum is a single psum under a
    mesh), then the group means are FedAvg-ed with the groups' total sample
    weights.  Numerically this telescopes to flat FedAvg — the parity test
    — while structurally it is the ROADMAP's multi-pod aggregation tier:
    on a ``("pod", "data")`` mesh each region maps to a pod whose psum
    stays on local ICI before the small cross-pod combine.
    """

    mode = "grouped"

    def __init__(self, num_regions: int = 2) -> None:
        if int(num_regions) < 1:
            raise ValueError(f"hierarchical needs >= 1 region, got {num_regions}")
        self.num_regions = int(num_regions)

    def groups(self, participant_ids) -> list[np.ndarray]:
        ids = np.asarray(participant_ids)
        parts = np.array_split(ids, min(self.num_regions, len(ids)))
        return [p for p in parts if len(p)]

    def aggregate(self, stacked, weights):
        return aggregate_stacked(stacked, weights)


# ---------------------------------------------------------------------------
# run records
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RoundRecord:
    round_index: int
    participant_ids: list[int]       # sorted — the cohort stacking order
    mean_local_loss: float
    local_steps: int
    params_down: int                 # parameter tensors broadcast server -> clients
    params_up: int                   # parameter tensors returned clients -> server
    bytes_transferred: int           # down + up, from the param pytree's real sizes
    wall_time_s: float
    # Async-runtime extras (None on synchronous rounds): the virtual-clock
    # time the flush happened at, and the mean staleness (in parameter
    # versions) of the updates folded into it.
    virtual_time: float | None = None
    staleness: float | None = None
    # DP runs only: the cumulative (epsilon, delta)-DP budget *through* this
    # round, from the run's Rényi accountant at the configured delta —
    # monotonically non-decreasing over a run.  None without a privacy
    # config.
    epsilon: float | None = None

    @property
    def round_time_s(self) -> float:
        """Host wall-clock this round took — the timing field's public name
        (``wall_time_s`` kept for compatibility with existing reports)."""
        return self.wall_time_s

    def to_state(self) -> dict:
        """JSON-serializable form — one JSONL line of the record stream.

        Serializes the canonical ``round_time_s`` name; ``from_state``
        still accepts the legacy ``wall_time_s`` key so run directories
        written before the rename keep resuming.
        """
        state = dataclasses.asdict(self)
        state["round_time_s"] = state.pop("wall_time_s")
        return state

    @classmethod
    def from_state(cls, state: dict) -> "RoundRecord":
        state = dict(state)
        if "round_time_s" in state:
            state["wall_time_s"] = state.pop("round_time_s")
        return cls(**state)


@dataclasses.dataclass
class FederatedRunResult:
    params: PyTree
    history: list[RoundRecord]
    recruitment: RecruitmentResult | None
    federation_ids: np.ndarray
    total_wall_time_s: float
    total_local_steps: int
    # Final observability snapshot (repro.obs.MetricsRegistry.snapshot()):
    # staging/pool counters, comms bytes, compile events, DP epsilon — the
    # run's whole metrics series folded to its last value.
    metrics: dict[str, Any] | None = None

    def summary(self) -> dict[str, Any]:
        # Async-runtime totals: the simulated clock at the last flush and
        # the mean update staleness — None on synchronous runs, where no
        # record carries a virtual time.
        async_records = [r for r in self.history if r.virtual_time is not None]
        return {
            "rounds": len(self.history),
            "federation_size": int(self.federation_ids.size),
            "recruited": None if self.recruitment is None else self.recruitment.num_recruited,
            "total_wall_time_s": self.total_wall_time_s,
            "total_round_time_s": sum(r.round_time_s for r in self.history),
            "total_local_steps": self.total_local_steps,
            "params_down": sum(r.params_down for r in self.history),
            "params_up": sum(r.params_up for r in self.history),
            "bytes_transferred": sum(r.bytes_transferred for r in self.history),
            "virtual_time": max(r.virtual_time for r in async_records)
            if async_records
            else None,
            # Weight each flush by its participant count so the figure
            # reads as mean staleness per *update*, not per flush — a
            # one-update forced flush must not count like a full buffer.
            "mean_staleness": float(
                np.average(
                    [r.staleness for r in async_records],
                    weights=[max(len(r.participant_ids), 1) for r in async_records],
                )
            )
            if async_records
            else None,
            # DP runs: the final cumulative privacy budget (the last
            # record's epsilon — the accountant only ever grows it).
            "epsilon": next(
                (
                    r.epsilon
                    for r in reversed(self.history)
                    if r.epsilon is not None
                ),
                None,
            ),
            # The final metrics snapshot — staged bytes, prefetch hits,
            # pool uploads/evictions, comms accounting — so summaries no
            # longer drop the staging/observability counters.
            "metrics": self.metrics,
        }


# ---------------------------------------------------------------------------
# checkpoint / resume
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FederationSnapshot:
    """Everything ``Federation.run`` needs to continue from a round boundary.

    Captured by the ``snapshot_hook`` after each round's record lands and
    fed back through ``Federation.run(..., resume=snapshot)``: the resumed
    run restores the parameter pytree exactly (npz round-trips are
    bit-exact), both PRNG streams (the numpy batch-plan generator's
    bit-generator state and the jax key chain's raw key data), the record
    history, and any adaptive selection-policy state — so it consumes the
    identical batches and keys the uninterrupted run would have, and the
    final params match to float tolerance.  Recruitment is *not*
    snapshotted: it derives deterministically from the seed and is re-run
    on resume.
    """

    round_index: int              # the next round to run
    params: PyTree
    np_rng_state: dict            # batch-plan generator bit_generator.state
    jax_key_data: np.ndarray      # raw key data of the per-chunk key chain
    history: list[RoundRecord]
    selection_state: dict

    def save(self, directory: str, extra_state: dict | None = None) -> None:
        """Persist atomically via ``repro.checkpoint.store`` (overwrites)."""
        from repro.checkpoint.store import save_federation_snapshot

        state = {
            "kind": "sync",
            "round_index": int(self.round_index),
            "np_rng_state": self.np_rng_state,
            "history": [r.to_state() for r in self.history],
            "selection_state": self.selection_state,
        }
        state.update(extra_state or {})
        save_federation_snapshot(
            directory,
            trees={"params": self.params},
            arrays={"jax_key_data": np.asarray(self.jax_key_data)},
            state=state,
        )

    @classmethod
    def load(cls, directory: str, like_params: PyTree) -> "FederationSnapshot":
        from repro.checkpoint.store import load_federation_snapshot

        trees, arrays, state = load_federation_snapshot(directory, like_params)
        if state.get("kind") != "sync":
            raise ValueError(
                f"snapshot in {directory} is {state.get('kind')!r}, not a "
                "synchronous federation snapshot"
            )
        return cls(
            round_index=int(state["round_index"]),
            params=trees["params"],
            np_rng_state=state["np_rng_state"],
            jax_key_data=arrays["jax_key_data"],
            history=[RoundRecord.from_state(r) for r in state["history"]],
            selection_state=state.get("selection_state", {}),
        )


# ---------------------------------------------------------------------------
# the facade
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FederationConfig:
    """Declarative federation: every stage is a policy spec or instance."""

    rounds: int = 15
    local_epochs: int = 4
    batch_size: int = 128
    # Pipeline stages — spec strings ("nu-greedy", "uniform:0.1",
    # "hierarchical:4") or policy instances.
    recruitment: str | RecruitmentPolicy = "all"
    selection: str | SelectionPolicy = "uniform"
    aggregator: str | Aggregator = "fedavg"
    seed: int = 0
    # Engine / staging knobs, unchanged from the PR 3 runtime.
    engine: str = "vectorized"
    cohort_chunk: int | None = None
    mesh: Any = None
    donate_buffers: bool = True
    staging: str = "resident"
    prefetch: bool = True
    # Population scale: bound the device-resident cohort to this many bytes
    # (LRU pool of client rows, uploads only the round's sampled clients —
    # see repro.data.device_cohort).  None = bake the whole federation.
    resident_budget_bytes: int | None = None
    # In-jit DP-SGD (repro.privacy): a DPConfig, a job-spec dict
    # ({"clip_norm": ..., "noise_multiplier": ..., "delta": ...}), or None.
    # When set, every local step clips per-example gradients and adds
    # calibrated Gaussian noise inside the jitted step, and each
    # RoundRecord carries the accountant's cumulative epsilon.
    privacy: DPConfig | dict | None = None

    def __post_init__(self) -> None:
        if self.engine not in ENGINES:
            raise ValueError(f"unknown engine {self.engine!r}; choose from {ENGINES}")
        if self.staging not in STAGING_MODES:
            raise ValueError(
                f"unknown staging {self.staging!r}; choose from {STAGING_MODES}"
            )


class Federation:
    """Runs the round program over in-process clients with pluggable policies.

    ``Federation(config, clients, loss_fn, optimizer)`` resolves the three
    policy stages up front (unknown spec strings fail here, not mid-run) and
    exposes the same engine surface the legacy server did
    (``cohort_trainer``, ``trainer``, ``build_federation``).
    """

    def __init__(
        self,
        config: FederationConfig,
        clients: Sequence[ClientDataset],
        loss_fn: Callable[..., Any],
        optimizer: AdamW,
        tracer: Tracer | None = None,
        metrics: MetricsRegistry | None = None,
        profiler: Any = None,
    ) -> None:
        self.config = config
        # Observability: the null tracer keeps the uninstrumented hot path
        # at a handful of no-op calls per round; the registry always exists
        # so run summaries carry the staging/comms counters either way.
        self.tracer = resolve_tracer(tracer)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.profiler = profiler
        self.recruitment_policy = resolve_recruitment(config.recruitment)
        self.selection_policy = resolve_selection(config.selection)
        self.aggregator = resolve_aggregator(config.aggregator)
        if self.aggregator.mode == "buffered":
            raise ValueError(
                f"aggregator {config.aggregator!r} is asynchronous "
                "(mode='buffered'); run it with "
                "repro.federated.runtime.AsyncFederation instead of the "
                "synchronous Federation"
            )
        if self.aggregator.mode not in AGGREGATION_MODES:
            raise ValueError(
                f"aggregator mode {self.aggregator.mode!r} not in {AGGREGATION_MODES}"
            )
        self.all_clients = {c.client_id: c for c in clients}
        self.dp = resolve_dp(config.privacy)
        self.trainer = LocalTrainer(
            loss_fn=loss_fn,
            optimizer=optimizer,
            batch_size=config.batch_size,
            local_epochs=config.local_epochs,
            dp=self.dp,
        )
        self.cohort_trainer = CohortTrainer(
            loss_fn=loss_fn,
            optimizer=optimizer,
            batch_size=config.batch_size,
            local_epochs=config.local_epochs,
            cohort_chunk=config.cohort_chunk,
            mesh=config.mesh,
            donate=config.donate_buffers,
            staging=config.staging,
            prefetch=config.prefetch,
            resident_budget_bytes=config.resident_budget_bytes,
            dp=self.dp,
            tracer=self.tracer,
        )

    @property
    def effective_engine(self) -> str:
        """The engine rounds actually run on.

        Stacked-mode aggregators need every client's params, which only the
        per-client trainer materializes — they run sequentially whatever
        ``config.engine`` says, and reports should say so.
        """
        return "sequential" if self.aggregator.mode == "stacked" else self.config.engine

    # -- stage 1: build_federation ------------------------------------------

    def build_federation(
        self, rng: np.random.Generator | None = None
    ) -> tuple[np.ndarray, RecruitmentResult | None]:
        """Recruitment happens here — before the federation exists.

        Stochastic recruitment draws from its own generator (derived from
        the seed, independent of the per-round stream), so the round-level
        sampling is identical across recruitment policies at a fixed seed.
        """
        if rng is None:
            rng = np.random.default_rng([self.config.seed, 1])
        all_ids = sorted(self.all_clients)
        stats = [self.all_clients[i].stats() for i in all_ids]
        decision = self.recruitment_policy.recruit(stats, rng)
        ids = np.sort(np.asarray(decision.federation_ids, dtype=np.int64))
        unknown = set(ids.tolist()) - set(all_ids)
        if unknown:
            raise ValueError(f"recruitment returned unknown client ids: {sorted(unknown)}")
        if ids.size == 0:
            raise ValueError("recruitment returned an empty federation")
        return ids, decision.detail

    # -- stages 3+4: train + aggregate --------------------------------------

    def _train_group(
        self, params: PyTree, group: np.ndarray, rng, jax_rng, spe: int
    ) -> tuple[PyTree, np.ndarray, int, tuple[int, int], jax.Array]:
        """One engine round over ``group``: FedAvg-reduced params.

        This is the pre-API hot path, untouched: the vectorized engine
        consumes one ``chain_split_keys`` chunk and streams the weighted
        sum inside its jitted round; the sequential engine splits one key
        per client and stacks once.  Returns the params, per-client
        losses, real local steps, ``(client-steps scanned, lanes)`` (padding
        included; the sequential engine pads nothing and trains each client
        on a lane of its own) and the advanced key.
        """
        cohort = [self.all_clients[int(cid)] for cid in group]
        if self.config.engine == "vectorized":
            jax_rng, key_data = chain_split_keys(jax_rng, len(cohort))
            params, per_losses, steps = self.cohort_trainer.train_cohort(
                params, cohort, rng, key_data, steps_per_epoch=spe
            )
            stats = self.cohort_trainer.last_round_stats
            return params, per_losses, steps, (stats["scanned_steps"], stats["lanes"]), jax_rng
        client_params, weights, losses, steps = [], [], [], 0
        for client in cohort:
            jax_rng, sub = jax.random.split(jax_rng)
            new_params, loss, n_c = self.trainer.train_client(params, client, rng, sub)
            client_params.append(new_params)
            weights.append(n_c)
            losses.append(loss)
            steps += self.trainer.steps_per_round(client)
        stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *client_params)
        params = aggregate_stacked(stacked, np.asarray(weights, dtype=np.float32))
        losses = np.asarray(losses, dtype=np.float32)
        return params, losses, steps, (steps, len(cohort)), jax_rng

    def _train_round(
        self, params: PyTree, participants: np.ndarray, rng, jax_rng, spe: int
    ) -> tuple[PyTree, np.ndarray, int, tuple[int, int], jax.Array]:
        """train -> aggregate for one round, dispatched on the aggregator mode.

        Returns what :meth:`_train_group` does, summed over the groups."""
        mode = self.aggregator.mode
        if mode == "reduced":
            return self._train_group(params, participants, rng, jax_rng, spe)

        if mode == "grouped":
            groups = self.aggregator.groups(participants)
            flat = np.concatenate([np.asarray(g) for g in groups]) if groups else np.array([])
            if sorted(flat.tolist()) != sorted(np.asarray(participants).tolist()):
                raise ValueError("aggregator groups must partition the participants")
            group_params, group_w, losses, steps, scanned, lanes = [], [], [], 0, 0, 0
            for group in groups:
                p_g, losses_g, steps_g, (scanned_g, lanes_g), jax_rng = self._train_group(
                    params, group, rng, jax_rng, spe
                )
                group_params.append(p_g)
                group_w.append(sum(self.all_clients[int(c)].n_train for c in group))
                losses.append(losses_g)
                steps += steps_g
                scanned += scanned_g
                lanes += lanes_g
            with self.tracer.span("aggregate", groups=len(groups)):
                stacked = jax.tree.map(
                    lambda *leaves: jnp.stack(leaves), *group_params
                )
                new_params = self.aggregator.aggregate(
                    stacked, np.asarray(group_w, dtype=np.float32)
                )
            return new_params, np.concatenate(losses), steps, (scanned, lanes), jax_rng

        # mode == "stacked": the aggregator needs every client's params, which
        # the vectorized engine's in-jit reduction never materializes — these
        # rounds run the per-client trainer whatever the engine setting.
        client_params, weights, losses, steps = [], [], [], 0
        for cid in participants:
            client = self.all_clients[int(cid)]
            jax_rng, sub = jax.random.split(jax_rng)
            new_params, loss, n_c = self.trainer.train_client(params, client, rng, sub)
            client_params.append(new_params)
            weights.append(n_c)
            losses.append(loss)
            steps += self.trainer.steps_per_round(client)
        with self.tracer.span("aggregate", clients=len(participants)):
            stacked = jax.tree.map(
                lambda *leaves: jnp.stack(leaves), *client_params
            )
            new_params = self.aggregator.aggregate(
                stacked, np.asarray(weights, dtype=np.float32)
            )
        losses = np.asarray(losses, dtype=np.float32)
        return new_params, losses, steps, (steps, len(participants)), jax_rng

    # -- observability --------------------------------------------------------

    def _absorb_round_metrics(
        self, record: RoundRecord, scanned_steps: int | None = None
    ) -> None:
        """Fold a finished round into the metrics registry.

        Absorbs the comms accounting and per-round loss from the record
        plus the cohort engine's ad-hoc ``last_round_stats`` dict (staged
        bytes, prefetch hits, pool uploads/evictions) into the typed
        counters/gauges/histograms the control plane streams as
        ``metrics.jsonl``.  ``scanned_steps`` (sync rounds) feeds
        ``train.scanned_steps``.
        """
        m = self.metrics
        m.counter("rounds.completed").inc()
        m.counter("comms.params_down").inc(record.params_down)
        m.counter("comms.params_up").inc(record.params_up)
        m.counter("comms.bytes_down").inc(record.bytes_transferred // 2)
        m.counter("comms.bytes_up").inc(
            record.bytes_transferred - record.bytes_transferred // 2
        )
        m.counter("train.local_steps").inc(record.local_steps)
        if scanned_steps is not None:
            m.counter("train.scanned_steps").inc(scanned_steps)
        m.histogram("round.time_s").observe(record.wall_time_s)
        if np.isfinite(record.mean_local_loss):
            m.histogram("round.loss").observe(record.mean_local_loss)
        if record.epsilon is not None:
            m.gauge("privacy.epsilon").set(record.epsilon)
        if record.staleness is not None:
            m.histogram("async.staleness").observe(record.staleness)
        if record.virtual_time is not None:
            m.gauge("async.virtual_time").set(record.virtual_time)
        stats = self.cohort_trainer.last_round_stats
        if stats:
            m.counter("staging.bytes_staged").inc(stats.get("bytes_staged", 0))
            m.counter("staging.plans_prefetched").inc(
                stats.get("plans_prefetched", 0)
            )
            m.counter("staging.chunks").inc(stats.get("chunks", 0))
            m.gauge("staging.bytes_resident").set(stats.get("bytes_resident", 0))
            m.gauge("staging.peak_live_bytes").set(stats.get("peak_live_bytes", 0))
            if stats.get("pool"):
                m.counter("pool.uploads").inc(stats.get("pool_uploads", 0))
                m.counter("pool.evictions").inc(stats.get("pool_evictions", 0))
                m.counter("pool.hits").inc(stats.get("pool_hits", 0))
                m.counter("pool.bytes_uploaded").inc(
                    stats.get("pool_bytes_uploaded", 0)
                )

    # -- the round program ---------------------------------------------------

    def run(
        self,
        init_params: PyTree,
        progress: Callable[[RoundRecord], None] | None = None,
        snapshot_hook: Callable[[FederationSnapshot], None] | None = None,
        resume: FederationSnapshot | None = None,
    ) -> FederatedRunResult:
        """Run the round program (optionally resuming a snapshotted run).

        ``progress`` receives each :class:`RoundRecord` as it lands — the
        record stream the control plane fans out to subscribers.
        ``snapshot_hook`` receives a :class:`FederationSnapshot` after
        every round; the hook decides whether/where to persist it (it may
        also raise to preempt the run — nothing after the snapshot is
        lost).  ``resume`` continues a run from such a snapshot: the
        restored streams make the continuation consume the same batches
        and keys the uninterrupted run would have, so the final params
        agree to float tolerance.  ``total_wall_time_s`` counts only the
        resumed segment; ``history`` and ``total_local_steps`` span the
        whole run.
        """
        cfg = self.config
        rng = np.random.default_rng(cfg.seed)
        jax_rng = jax.random.key(cfg.seed)

        federation_ids, recruitment = self.build_federation()
        uses_cohort_engine = (
            cfg.engine == "vectorized" and self.aggregator.mode != "stacked"
        )
        if uses_cohort_engine and cfg.staging == "resident":
            # One host->device upload for the whole federation (only the
            # recruited clients — unrecruited ones never ship anything);
            # every round after this stages just an int32 index plan.
            # Stacked-mode aggregators never touch the cohort engine (their
            # rounds run the per-client trainer), so don't park the
            # federation's arrays on device for them.
            self.cohort_trainer.attach_device_cohort(
                [self.all_clients[int(i)] for i in federation_ids]
            )
        # One Rényi accountant per run: stepped once per round at that
        # round's client sampling rate, read for every RoundRecord.
        accountant = (
            RdpAccountant(self.dp.noise_multiplier, delta=self.dp.delta)
            if self.dp is not None
            else None
        )
        params = init_params
        history: list[RoundRecord] = []
        start_round = 0
        if resume is not None:
            if not (0 <= int(resume.round_index) <= cfg.rounds):
                raise ValueError(
                    f"snapshot round_index {resume.round_index} outside the "
                    f"configured {cfg.rounds}-round budget"
                )
            params = resume.params
            start_round = int(resume.round_index)
            rng.bit_generator.state = resume.np_rng_state
            jax_rng = jax.random.wrap_key_data(jnp.asarray(resume.jax_key_data))
            history = list(resume.history)
            self.selection_policy.load_state_dict(resume.selection_state)
            if accountant is not None:
                # Privacy loss composes over the whole run: replay the
                # completed rounds' sampling rates so the resumed segment's
                # epsilons continue the original accounting.
                for past in history:
                    accountant.step(
                        len(past.participant_ids) / federation_ids.size
                    )
        # Pin the vectorized schedule's step axis to the federation-wide max
        # so every round shares one compiled shape whatever mix is sampled.
        federation_spe = cohort_steps_per_epoch(
            [self.all_clients[int(i)].n_train for i in federation_ids], cfg.batch_size
        )
        # Communication accounting: each participant receives the full param
        # pytree and returns one of the same shape.
        n_tensors = len(jax.tree.leaves(init_params))
        model_nbytes = params_nbytes(init_params)
        tracer = self.tracer
        t_start = time.perf_counter()

        with CompileWatcher(self.metrics, tracer) as watcher:
            for rnd in range(start_round, cfg.rounds):
                if self.profiler is not None:
                    self.profiler.round_start(rnd)
                t_round = time.perf_counter()
                with tracer.span("select", round=rnd):
                    participants = np.asarray(
                        self.selection_policy.select(rnd, federation_ids, rng)
                    )
                if not (
                    len(participants) > 0
                    and np.all(np.diff(participants) > 0)
                    and set(participants.tolist()) <= set(federation_ids.tolist())
                ):
                    raise ValueError(
                        "selection must return a non-empty, strictly sorted subset of the federation"
                    )
                with tracer.span(
                    "train", round=rnd, participants=len(participants)
                ):
                    params, losses, steps, (scanned, lanes), jax_rng = self._train_round(
                        params, participants, rng, jax_rng, federation_spe
                    )
                self.selection_policy.observe(participants, losses)
                epsilon = None
                if accountant is not None:
                    accountant.step(len(participants) / federation_ids.size)
                    epsilon = accountant.epsilon()
                wall = time.perf_counter() - t_round
                record = RoundRecord(
                    round_index=rnd,
                    participant_ids=[int(c) for c in participants],
                    mean_local_loss=float(np.nanmean(losses)) if len(losses) else float("nan"),
                    local_steps=steps,
                    params_down=len(participants) * n_tensors,
                    params_up=len(participants) * n_tensors,
                    bytes_transferred=2 * len(participants) * model_nbytes,
                    wall_time_s=wall,
                    epsilon=epsilon,
                )
                # The round span reuses the record's own start/duration so
                # the trace reconciles exactly with round_time_s.
                tracer.complete(
                    "round",
                    start=tracer.host_ts(t_round),
                    dur=wall,
                    round=rnd,
                    participants=len(participants),
                    local_steps=steps,
                    scanned_steps=scanned,
                    lanes=lanes,
                )
                history.append(record)
                with tracer.span("record", round=rnd):
                    watcher.poll()
                    self._absorb_round_metrics(record, scanned)
                    if progress is not None:
                        progress(record)
                if snapshot_hook is not None:
                    with tracer.span("checkpoint", round=rnd):
                        snapshot_hook(
                            FederationSnapshot(
                                round_index=rnd + 1,
                                params=params,
                                np_rng_state=rng.bit_generator.state,
                                jax_key_data=np.asarray(jax.random.key_data(jax_rng)),
                                history=list(history),
                                selection_state=self.selection_policy.state_dict(),
                            )
                        )
                if self.profiler is not None:
                    self.profiler.round_end(rnd)

        return FederatedRunResult(
            params=params,
            history=history,
            recruitment=recruitment,
            federation_ids=federation_ids,
            total_wall_time_s=time.perf_counter() - t_start,
            total_local_steps=sum(r.local_steps for r in history),
            metrics=self.metrics.snapshot(),
        )


# Registry side effects: importing the privacy tier's aggregator modules here
# makes "secagg-fedavg" and "krum" resolvable wherever the registry is.  The
# import sits at the bottom because those modules import back the registry
# helpers defined above — a deliberate, documented cycle-breaker.
from repro.privacy import adversary as _adversary  # noqa: E402,F401
from repro.privacy import secagg as _secagg  # noqa: E402,F401
