"""Device-resident client data + per-round index plans.

The vectorized engine's remaining per-round cost (after PR 2 moved the
round computation into one jitted vmap) is host-side: every round
re-materializes the full ``(clients, steps, batch, *features)`` schedule in
numpy and re-uploads O(dataset) bytes host->device, fully serialized with
the round computation.  This module removes that traffic for the lifetime
of a federation:

* ``build_device_cohort`` pads every client's train split to a common
  sample axis and uploads the stacked arrays **once** (sharded over the
  mesh's ``"data"`` axis when one is given): ``x`` as ``(rows, max_n + 1,
  padded)``, each sample's features flattened into one row padded to a
  multiple of 128 lanes (see ``DeviceCohort``).  Row ``max_n`` of every
  client is all-zero padding.
* ``build_lane_plan`` replaces ``build_cohort_schedule`` on the hot path:
  it draws the *same* permutations from the *same* numpy RNG stream in the
  same client-major order, but records only int32 sample indices, packed
  into a few vmap lanes (``LanePlan``): each lane holds several clients'
  real steps end to end, so the round scans ``W`` lanes of ``L`` slots
  instead of every client for the longest client's step count.  The actual
  batch gather happens on device, inside the jitted round.
* ``pack_federation`` packs the attached federation into lanes once, by
  first-fit decreasing; ``assign_lanes`` places a round's participants in
  those lanes, so the lane count depends on the participant count alone.

Parity is bitwise by construction: a real slot's index points at the same
shuffled sample the schedule would have copied; every padding slot points
at the all-zero pad row, so the gathered batch equals the schedule's
zero-padded batch exactly, and the example mask is recoverable on device
as ``sample_idx < pad_index``.
"""

from __future__ import annotations

import dataclasses
import heapq
from collections import OrderedDict
from typing import Any, Sequence

import numpy as np

from repro.data.pipeline import ClientDataset
from repro.obs.trace import resolve_tracer

PyTree = Any

# A TPU vector register is 128 lanes wide.  A float32 array whose minor axis
# is a multiple of that is laid out row-major, so one sample's features are
# one contiguous row; with a narrower or ragged minor axis (the GRU-eICU
# stay's 24 x 38 = 912 features) the TPU's default layout puts the sample
# axis minor-most, and a per-sample gather reads scattered elements.
LANES = 128

_SCATTER = None


def _scatter_rows(buf: Any, idx: Any, rows: Any) -> Any:
    """Jitted in-place row scatter (donated off-CPU, so no full-array copy)."""
    global _SCATTER
    if _SCATTER is None:
        import jax

        donate = (0,) if jax.default_backend() != "cpu" else ()
        _SCATTER = jax.jit(lambda b, i, r: b.at[i].set(r), donate_argnums=donate)
    return _SCATTER(buf, idx, rows)


@dataclasses.dataclass(frozen=True)
class LanePlan:
    """One round's real client-steps, packed end to end into vmap lanes.

    Within a round every client trains independently from the same global
    parameters, so clients can share a lane one after another.  A lane is a
    run of ``L = steps_per_epoch * local_epochs`` slots; a client takes
    ``ceil(n_c / B) * local_epochs`` consecutive slots of one lane, its
    epochs laid end to end with no per-epoch padding (a padded step
    advances nothing, so dropping it changes no number).  The jitted round
    scans the slots and vmaps the lanes: at a ``first`` slot a lane restarts
    from the round's global state with its client's key, at a ``last`` slot
    it adds the client's weighted parameters to the FedAvg sum.  Slots after
    a lane's last client are ``valid=False`` no-ops.

    ``sample_idx`` entries index a client's *local* sample axis in the
    device-resident cohort; batch tails and empty slots hold ``pad_index``,
    which every client maps to an all-zero row.
    """

    rows: np.ndarray        # (W, L) int32 resident row of the slot's client
    sample_idx: np.ndarray  # (W, L, B) int32 into that row's sample axis
    valid: np.ndarray       # (W, L) bool: a real client-step
    first: np.ndarray       # (W, L) bool: the client's first slot
    last: np.ndarray        # (W, L) bool: the client's last slot
    client: np.ndarray      # (W, L) int32 index of the slot's client in the round
    last_epoch: np.ndarray  # (W, L) bool: a real step of the client's last epoch
    weights: np.ndarray     # (C,) float32 local sample counts n_c
    pad_index: int          # the all-zero sample every padding slot points at

    @property
    def num_lanes(self) -> int:
        return self.valid.shape[0]

    @property
    def total_steps(self) -> int:
        return self.valid.shape[1]

    @property
    def slot_arrays(self) -> tuple[np.ndarray, ...]:
        """The per-slot arrays, in the round program's argument order."""
        return (
            self.rows,
            self.sample_idx,
            self.valid,
            self.first,
            self.last,
            self.client,
            self.last_epoch,
        )


@dataclasses.dataclass(frozen=True)
class FederationLanes:
    """An attached federation's clients packed into lanes, once.

    A round at the federation's ``steps_per_epoch`` places its participants
    with ``assign_lanes`` against this packing, so its lane count depends on
    the participant count alone and one compiled round serves every draw.
    """

    steps_per_epoch: int
    lane: dict[int, int]  # client_id -> lane within the client's shard
    width: int            # lanes the fullest shard needs

@dataclasses.dataclass
class DeviceCohort:
    """A federation's train arrays, resident on device for its lifetime.

    ``x``/``y`` are uploaded once by ``build_device_cohort``; afterwards a
    round stages only a ``LanePlan`` and the jitted round gathers its
    batches on device.  Sample row ``pad_index`` (== ``x.shape[1] - 1``) is
    all-zero for every client, as are any dummy client rows added to make
    the row axis divide a mesh's data axis.

    ``x`` holds each sample's features flattened into one row, zero-padded
    to a multiple of ``LANES`` values, so that on a TPU the
    round's per-step gather reads whole contiguous rows (about 20x faster a
    step than the 4-D layout at the GRU-eICU shape on a TPU v5e); the round
    slices the first ``prod(feature_shape)`` values and reshapes.
    """

    x: Any                   # jax.Array (rows, max_n + 1, padded feature row)
    y: Any                   # jax.Array (rows, max_n + 1)
    rows: dict[int, int]     # client_id -> row (current residency when pooled)
    nbytes: int              # resident device bytes (pool bytes when pooled)
    feature_shape: tuple[int, ...] = ()  # one sample's features, unflattened
    _sources: dict[int, Any] = dataclasses.field(default_factory=dict, repr=False)
    # -- memory-bounded (LRU pool) mode; None/unused when fully resident ----
    pool_rows: int | None = None
    uploads: int = 0
    evictions: int = 0
    hits: int = 0
    bytes_uploaded: int = 0
    _lru: OrderedDict = dataclasses.field(default_factory=OrderedDict, repr=False)
    _free: list = dataclasses.field(default_factory=list, repr=False)
    # Observability: pool uploads record a "pool_upload" span (None = no-op).
    tracer: Any = dataclasses.field(default=None, repr=False)

    def __post_init__(self) -> None:
        self.tracer = resolve_tracer(self.tracer)

    @property
    def pad_index(self) -> int:
        return self.x.shape[1] - 1

    @property
    def num_rows(self) -> int:
        return self.x.shape[0]

    @property
    def is_pooled(self) -> bool:
        return self.pool_rows is not None

    def row_of(self, client: ClientDataset) -> int:
        try:
            return self.rows[client.client_id]
        except KeyError:
            if self.is_pooled:
                raise KeyError(
                    f"client {client.client_id} is not resident in the pool; "
                    "call ensure_resident(round_clients) before staging"
                ) from None
            raise KeyError(
                f"client {client.client_id} is not part of this device cohort; "
                "attach the full federation before training"
            ) from None

    def owns(self, client: ClientDataset) -> bool:
        """True iff this resident copy was built from exactly this dataset."""
        return self._sources.get(client.client_id) is client.train

    def ensure_resident(self, clients: Sequence[ClientDataset]) -> int:
        """Make every client in ``clients`` resident; returns rows uploaded.

        Pool mode only (a fully resident cohort is a no-op).  Runs once per
        round on the consumer thread, *before* any plan is staged: rows are
        then stable for the whole round, so plan prefetch on the staging
        thread never races an eviction.  Eviction is LRU among clients not in
        the current round; the pool must hold the round's whole cohort, which
        is exactly the ``resident_budget_bytes`` contract.
        """
        if not self.is_pooled:
            return 0
        if len(clients) > self.pool_rows:
            raise ValueError(
                f"round cohort of {len(clients)} clients exceeds the resident "
                f"pool ({self.pool_rows} rows); raise resident_budget_bytes or "
                "sample fewer clients per round"
            )
        wanted = {c.client_id for c in clients}
        missing: list[ClientDataset] = []
        for c in clients:
            if not self.owns(c):
                raise KeyError(
                    f"client {c.client_id} was not part of the federation this "
                    "pool was built for"
                )
            if c.client_id in self._lru:
                self._lru.move_to_end(c.client_id)
                self.hits += 1
            else:
                missing.append(c)
        if not missing:
            return 0

        with self.tracer.span("pool_upload", track="pool", missing=len(missing)):
            target_rows: list[int] = []
            for _ in missing:
                if self._free:
                    target_rows.append(self._free.pop())
                    continue
                victim = next(cid for cid in self._lru if cid not in wanted)
                row = self._lru.pop(victim)
                del self.rows[victim]
                self.evictions += 1
                target_rows.append(row)

            max_n = self.pad_index
            hx = np.zeros((len(missing), *self.x.shape[1:]), dtype=self.x.dtype)
            hy = np.zeros((len(missing), max_n + 1), dtype=self.y.dtype)
            size = int(np.prod(self.feature_shape))
            for i, c in enumerate(missing):
                n = c.n_train
                hx[i, :n, :size] = c.train.x.reshape(n, size)
                hy[i, :n] = c.train.y
                self._lru[c.client_id] = target_rows[i]
                self.rows[c.client_id] = target_rows[i]
            idx = np.asarray(target_rows, dtype=np.int32)
            self.x = _scatter_rows(self.x, idx, hx)
            self.y = _scatter_rows(self.y, idx, hy)
            self.uploads += len(missing)
            self.bytes_uploaded += hx.nbytes + hy.nbytes
        return len(missing)


def resident_row_bytes(
    samples: int,
    feature_shape: Sequence[int],
    x_dtype: Any = np.float32,
    y_dtype: Any = np.float32,
) -> int:
    """Device bytes of one client row of ``samples`` samples (pad included)."""
    padded = -(-int(np.prod(feature_shape)) // LANES) * LANES
    return samples * (padded * np.dtype(x_dtype).itemsize + np.dtype(y_dtype).itemsize)


def build_device_cohort(
    clients: Sequence[ClientDataset],
    mesh: Any = None,
    resident_budget_bytes: int | None = None,
    tracer: Any = None,
) -> DeviceCohort:
    """Pad and upload every client's train arrays once.

    The sample axis is padded to ``max_n + 1`` so index ``max_n`` is an
    all-zero row shared by every client — the target of every padding slot
    in a ``LanePlan``; each sample's features become one lane-padded row
    (``DeviceCohort``).  With a ``mesh`` carrying a ``"data"`` axis the
    row axis is padded to the axis size with all-zero dummy rows and the
    arrays are sharded over it (one ``device_put`` for the whole pytree).

    ``resident_budget_bytes`` bounds device memory for population-scale
    federations: when the fully baked cohort would exceed the budget, only a
    pool of ``budget // row_bytes`` rows is allocated and rows are uploaded
    lazily per round (LRU eviction) via ``ensure_resident`` — a 10^5-client
    population trains out of a pool sized for its round cohorts instead of
    one giant array.  The pool is deliberately single-host: combining it
    with a sharded mesh would re-shard every upload, so that pairing raises.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if not clients:
        raise ValueError("empty cohort")
    feat = clients[0].train.x.shape[1:]
    x_dtype = clients[0].train.x.dtype
    y_dtype = clients[0].train.y.dtype
    max_n = max(c.n_train for c in clients)
    size = int(np.prod(feat))
    padded = -(-size // LANES) * LANES
    row_bytes = resident_row_bytes(max_n + 1, feat, x_dtype, y_dtype)
    shards = 1
    if mesh is not None and "data" in getattr(mesh, "axis_names", ()):
        shards = int(mesh.shape["data"])
    num_rows = len(clients) + (-len(clients) % shards)

    full_bytes = num_rows * row_bytes
    if resident_budget_bytes is not None and full_bytes > resident_budget_bytes:
        if shards > 1:
            raise ValueError(
                "resident_budget_bytes pooling is single-host; drop the mesh "
                "or raise the budget to fit the full cohort"
            )
        pool_rows = int(resident_budget_bytes // row_bytes)
        if pool_rows < 1:
            raise ValueError(
                f"resident_budget_bytes={resident_budget_bytes} cannot hold "
                f"even one client row ({row_bytes} bytes)"
            )
        sources: dict[int, Any] = {}
        for client in clients:
            if client.train.x.shape[1:] != feat:
                raise ValueError("all cohort clients must share a feature shape")
            sources[client.client_id] = client.train
        hx = np.zeros((pool_rows, max_n + 1, padded), dtype=x_dtype)
        hy = np.zeros((pool_rows, max_n + 1), dtype=y_dtype)
        dx, dy = jax.device_put((hx, hy))
        return DeviceCohort(
            x=dx,
            y=dy,
            rows={},
            nbytes=hx.nbytes + hy.nbytes,
            feature_shape=feat,
            _sources=sources,
            pool_rows=pool_rows,
            _free=list(range(pool_rows - 1, -1, -1)),
            tracer=tracer,
        )

    hx = np.zeros((num_rows, max_n + 1, padded), dtype=x_dtype)
    hy = np.zeros((num_rows, max_n + 1), dtype=y_dtype)
    rows: dict[int, int] = {}
    sources = {}
    for r, client in enumerate(clients):
        if client.train.x.shape[1:] != feat:
            raise ValueError("all cohort clients must share a feature shape")
        n = client.n_train
        hx[r, :n, :size] = client.train.x.reshape(n, size)
        hy[r, :n] = client.train.y
        rows[client.client_id] = r
        sources[client.client_id] = client.train

    if shards > 1:
        sharding = NamedSharding(mesh, P("data"))
        dx, dy = jax.device_put((hx, hy), sharding)
    else:
        dx, dy = jax.device_put((hx, hy))
    return DeviceCohort(
        x=dx, y=dy, rows=rows, nbytes=hx.nbytes + hy.nbytes, feature_shape=feat,
        _sources=sources, tracer=tracer,
    )


def first_fit_decreasing(sizes: Sequence[int], capacity: int) -> tuple[np.ndarray, int]:
    """Bin-pack ``sizes`` into lanes of ``capacity`` by first-fit decreasing.

    Returns each item's lane and the number of lanes.  Items go largest
    first (ties in input order) into the lowest-numbered lane with room.
    ``by_room[r]`` heaps the lanes with exactly ``r`` room left, so an item
    of size ``s`` finds its lane among the heads of ``by_room[s:]``.
    """
    sizes = np.asarray(sizes, dtype=np.int64)
    if sizes.size and sizes.max() > capacity:
        raise ValueError(f"an item of {sizes.max()} exceeds the lane capacity {capacity}")
    lane = np.zeros(sizes.size, dtype=np.int32)
    room: list[int] = []
    by_room: list[list[int]] = [[] for _ in range(capacity + 1)]
    for i in np.argsort(-sizes, kind="stable"):
        s = int(sizes[i])
        heads = [h[0] for h in by_room[s:] if h]
        if heads:
            w = min(heads)
            heapq.heappop(by_room[room[w]])
        else:
            w = len(room)
            room.append(capacity)
        room[w] -= s
        heapq.heappush(by_room[room[w]], w)
        lane[i] = w
    return lane, len(room)


def assign_lanes(
    steps: Sequence[int],
    shard: Sequence[int],
    num_shards: int,
    capacity: int,
    fed_lane: Sequence[int] | None = None,
    fed_width: int | None = None,
) -> tuple[np.ndarray, int]:
    """Each client's lane within its shard, and the lanes every shard gets.

    A client trains on a lane of the shard that holds its resident rows.
    Given the attached federation's packing (``fed_lane`` of these clients,
    ``fed_width``), the width is ``min(C, fed_width)``: it depends on the
    participant count alone, never on which clients were drawn.  A shard
    whose clients number no more than the width gives each its own lane;
    any other keeps the federation's lanes, which hold every subset of the
    federation.  Without a packing, each shard's clients are packed by
    first-fit decreasing and the width is the most any shard needs.
    """
    steps = np.asarray(steps, dtype=np.int64)
    shard = np.asarray(shard, dtype=np.int64)
    lane = np.zeros(steps.size, dtype=np.int32)
    members = [np.flatnonzero(shard == s) for s in range(num_shards)]
    if fed_lane is None:
        width = 1
        for m in members:
            if m.size:
                lane[m], used = first_fit_decreasing(steps[m], capacity)
                width = max(width, used)
        return lane, width
    fed_lane = np.asarray(fed_lane, dtype=np.int32)
    width = min(steps.size, int(fed_width))
    for m in members:
        lane[m] = np.arange(m.size) if m.size <= width else fed_lane[m]
    return lane, width


def pack_federation(
    clients: Sequence[ClientDataset],
    shard: Sequence[int],
    num_shards: int,
    batch_size: int,
    local_epochs: int,
) -> FederationLanes:
    """First-fit-decreasing lanes for a whole federation, shard by shard."""
    per_epoch = -(-np.asarray([c.n_train for c in clients], dtype=np.int64) // batch_size)
    spe = int(per_epoch.max())
    lane, width = assign_lanes(
        per_epoch * local_epochs, shard, num_shards, spe * local_epochs
    )
    return FederationLanes(
        steps_per_epoch=spe,
        lane={c.client_id: int(w) for c, w in zip(clients, lane)},
        width=width,
    )


def build_lane_plan(
    sizes: Sequence[int],
    batch_size: int,
    local_epochs: int,
    rng: np.random.Generator,
    lanes: Sequence[int],
    num_lanes: int,
    steps_per_epoch: int | None = None,
    client_rows: Sequence[int] | None = None,
    pad_index: int | None = None,
) -> LanePlan:
    """One round's index plan, with client ``c`` on lane ``lanes[c]``.

    Consumes ``rng`` in the schedule builder's order (client-major, one
    ``rng.permutation(n_c)`` per epoch), so every client is fed the batches
    ``build_cohort_schedule`` would give it and the generator ends in the
    same state.  Clients that share a lane run in round order.
    """
    sizes = np.asarray(sizes, dtype=np.int64)
    if not sizes.size:
        raise ValueError("empty cohort")
    per_epoch = -(-sizes // batch_size)
    spe = steps_per_epoch or int(per_epoch.max())
    total = spe * local_epochs
    n_clients = sizes.size
    if pad_index is None:
        pad_index = int(sizes.max())
    if pad_index < sizes.max():
        raise ValueError(
            f"pad_index={pad_index} must be >= the largest client size {sizes.max()}"
        )
    if (per_epoch > spe).any():
        c = int(np.argmax(per_epoch > spe))
        raise ValueError(f"client {c} needs more than steps_per_epoch={spe} batches")
    steps = per_epoch * local_epochs
    lanes = np.asarray(lanes, dtype=np.int64)
    if lanes.min() < 0 or lanes.max() >= num_lanes:
        raise ValueError(f"lanes must lie in [0, {num_lanes})")

    # Each client starts where the earlier clients of its lane end.
    order = np.argsort(lanes, kind="stable")
    ends = np.cumsum(steps[order])
    lane_start = np.searchsorted(lanes[order], lanes[order])
    offset = np.empty_like(steps)
    offset[order] = ends - steps[order] - (ends - steps[order])[lane_start]
    if (offset + steps > total).any():
        raise ValueError(f"a lane holds more than {total} client-steps")
    base = lanes * total + offset  # flat slot of each client's first step

    n_slots = num_lanes * total
    who = np.repeat(np.arange(n_clients), steps)
    pos = np.arange(who.size) - np.repeat(np.cumsum(steps) - steps, steps)
    slot = base[who] + pos
    rows_of = np.arange(n_clients) if client_rows is None else np.asarray(client_rows)
    rows = np.zeros(n_slots, dtype=np.int32)
    valid = np.zeros(n_slots, dtype=bool)
    client = np.zeros(n_slots, dtype=np.int32)
    last_epoch = np.zeros(n_slots, dtype=bool)
    first = np.zeros(n_slots, dtype=bool)
    last = np.zeros(n_slots, dtype=bool)
    rows[slot] = rows_of[who]
    valid[slot] = True
    client[slot] = who
    last_epoch[slot] = pos >= steps[who] - per_epoch[who]
    ran = steps > 0
    first[base[ran]] = True
    last[(base + steps - 1)[ran]] = True

    # Epoch e of client c fills per_epoch[c] slots from base[c] + e * per_epoch[c].
    drawn = np.concatenate(
        [rng.permutation(int(n)) for n in sizes for _ in range(local_epochs)]
    )
    n_ce = np.repeat(sizes, local_epochs)
    start_ce = np.repeat(base, local_epochs) + np.tile(
        np.arange(local_epochs), n_clients
    ) * np.repeat(per_epoch, local_epochs)
    p = np.arange(drawn.size) - np.repeat(np.cumsum(n_ce) - n_ce, n_ce)
    sample_idx = np.full((n_slots, batch_size), pad_index, dtype=np.int32)
    sample_idx[np.repeat(start_ce, n_ce) + p // batch_size, p % batch_size] = drawn

    shape = (num_lanes, total)
    return LanePlan(
        rows=rows.reshape(shape),
        sample_idx=sample_idx.reshape(*shape, batch_size),
        valid=valid.reshape(shape),
        first=first.reshape(shape),
        last=last.reshape(shape),
        client=client.reshape(shape),
        last_epoch=last_epoch.reshape(shape),
        weights=sizes.astype(np.float32),
        pad_index=pad_index,
    )
