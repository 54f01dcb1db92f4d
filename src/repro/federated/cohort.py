"""Vectorized cohort training: one jitted vmap trains every participant.

The sequential engine (``repro.federated.client.LocalTrainer``) dispatches
one jitted step per client per batch from Python, so a round's wall clock
scales linearly with federation size.  Here the global parameters are
broadcast across a leading client axis and a whole FedAvg round — every
participant's ``local_epochs`` of AdamW steps — runs inside a single
``jax.lax.scan`` over a ``jax.vmap``-ed per-client step.

This engine is orchestrated by the ``repro.federated.api.Federation``
round program: one ``train_cohort`` call is one FedAvg-reduced group
("reduced"-mode aggregation; "grouped" aggregators like hierarchical
FedAvg call it once per regional sub-federation), so new policies compose
around the hot path without forking it.

Parity with the sequential oracle is exact by construction:

* batch data consumes the shared numpy RNG in the same client-major order
  the sequential loop does, so each client sees identical shuffled batches;
* each client's jax PRNG chain is advanced only on its *real* steps (dummy
  padding steps are masked to exact no-ops on params, optimizer state, and
  the key), so per-step dropout keys match the sequential path;
* aggregation is the same FedAvg weighted mean: per-chunk unnormalized
  weighted sums accumulated into a running pytree, normalized once at the
  end of the round.

Staging (``staging=``) controls how a round's batches reach the device:

* ``"rebuild"`` — PR 2's path: every round re-materializes the full
  ``(clients, steps, batch, *features)`` schedule in numpy
  (``repro.data.pipeline.build_cohort_schedule``) and uploads O(dataset)
  bytes host->device.
* ``"resident"`` — client train arrays are uploaded **once** per
  federation (``repro.data.device_cohort``, sharded over the mesh when one
  is given) and a round stages only a compact int32 index plan drawn from
  the *same* RNG stream, its real client-steps packed into a few vmap lanes
  (``LanePlan``): clients train independently from the same global
  parameters, so several share a lane one after another, and the round
  scans ``W`` lanes of ``L`` slots instead of every client for the longest
  client's step count.  ``W = min(C, W_fed)``, where ``W_fed`` is the lane
  count first-fit decreasing needs for the attached federation, so one
  compiled round serves every draw of a participant count.  Each step
  gathers its batch as ``x[row, sample_idx]`` from the resident arrays on
  device, and the per-example mask is derived on device.  Per-round
  host->device traffic drops from O(C*T*B*features) floats to O(W*L*B)
  int32s.  With ``prefetch`` (the default) a ``StagingPipeline`` builds and
  uploads chunk k+1's plan on a background thread while chunk k's donated
  step runs, and all host syncs (per-chunk loss fetches) are deferred to
  the end of the round so XLA dispatch stays ahead of the device.

Memory (the 189-client paper federation): the round step is jitted with
``donate_argnums`` so the cross-chunk accumulator is updated *in place*
(XLA aliases the donated input to the output — no second params-sized
buffer per chunk), and the chunk's staged device buffers are released the
moment the step that consumed them returns.  On TPU/GPU the staged buffers
are additionally marked donated so XLA can reuse their memory for round
temporaries; XLA:CPU cannot consume a donation with no aliasable output,
so there the eager release is the mechanism.  The resident cohort arrays
themselves are never donated — they live for the federation.  Peak
live-buffer footprint is tracked per round in ``last_round_stats`` (see
``repro.launch.hlo_analysis.live_buffer_stats``).

Multi-device: pass ``mesh`` (or the string ``"auto"`` to build a 1-D
``("data",)`` mesh over every local device) to shard the client axis with
``shard_map``.  Rebuild staging pads cohorts that do not divide the axis
size with weight-0 dummy clients whose steps are all masked no-ops;
resident staging gives every shard the same number of lanes and trains
each client on a lane of the shard that holds its rows, so no batch
crosses shards.  Aggregation is a single cross-shard ``psum`` of the
per-shard weighted sums — the only collective in the round.
``cohort_chunk`` bounds peak memory by processing participants in chunks
through the same donated accumulator.
"""

from __future__ import annotations

import dataclasses
import functools
import sys
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.data.device_cohort import (
    DeviceCohort,
    FederationLanes,
    assign_lanes,
    build_device_cohort,
    build_lane_plan,
    pack_federation,
)
from repro.data.pipeline import (
    ClientDataset,
    build_cohort_schedule,
    cohort_steps_per_epoch,
    local_round_steps,
    pad_cohort_schedule,
)
from repro.federated.fedavg import weighted_sum_stacked
from repro.federated.staging import StagingPipeline
from repro.launch.hlo_analysis import live_buffer_stats
from repro.obs.trace import resolve_tracer
from repro.optim.adamw import AdamW, apply_updates
from repro.privacy.dp import DPConfig, dp_value_and_grad, resolve_dp

PyTree = Any
LossFn = Callable[..., Any]  # loss(params, batch, rng) -> scalar

STAGING_MODES = ("rebuild", "resident")


@functools.partial(jax.jit, static_argnums=1)
def _chain_split(key_data, n: int):
    def step(kd, _):
        ks = jax.random.split(jax.random.wrap_key_data(kd))
        return jax.random.key_data(ks[0]), jax.random.key_data(ks[1])

    return jax.lax.scan(step, key_data, None, length=n)


def chain_split_keys(key: jax.Array, n: int) -> tuple[jax.Array, jax.Array]:
    """``n`` sequential ``jax.random.split`` calls in one jitted scan.

    Bit-identical to the Python loop ``key, sub = jax.random.split(key)``
    repeated ``n`` times (the sequential server's per-client key chain), but
    one dispatch instead of ``n`` — at 189 clients the chained host loop
    costs ~0.2s per round, a measurable slice of a vectorized round.
    Returns the advanced key and the ``(n, ...)`` stacked sub-key data.
    The stacked data stays on device — the vectorized engine consumes it
    there, so round-tripping it through numpy would cost a device sync and
    a re-upload per round.
    """
    kd, subs = _chain_split(jax.random.key_data(key), n)
    return jax.random.wrap_key_data(kd), subs


@dataclasses.dataclass
class CohortTrainer:
    """Trains a whole cohort of clients per round in one jitted computation."""

    loss_fn: LossFn
    optimizer: AdamW
    batch_size: int
    local_epochs: int
    # Max clients per vmapped call; None = the whole cohort at once.
    cohort_chunk: int | None = None
    # Optional device mesh: shard the client axis over its "data" axis.
    # "auto" builds a ("data",) mesh over every local device (None if only
    # one device is visible — the degenerate mesh buys nothing).
    mesh: Any = None
    # Donate round buffers to the jitted step: the cross-chunk accumulator
    # is aliased in place and each chunk's staged buffers are released as
    # soon as the step consuming them returns.  Turn off only to diff
    # memory behavior.
    donate: bool = True
    # "rebuild" re-materializes and re-uploads the full batch schedule each
    # round (PR 2's path, kept as the staging reference); "resident" keeps
    # client data on device for the federation's lifetime and stages only
    # int32 index plans.  FederatedServer defaults to "resident".
    staging: str = "rebuild"
    # Resident staging: build/upload chunk k+1's plan on a background
    # thread while chunk k trains (double buffering).  Only engages when a
    # round has more than one chunk; numerically a no-op either way.
    prefetch: bool = True
    # Resident staging at population scale: bound the device cohort to this
    # many bytes.  When the full federation exceeds the budget, client rows
    # live in an LRU pool and only each round's cohort is uploaded
    # (repro.data.device_cohort.ensure_resident).  None = bake everything.
    resident_budget_bytes: int | None = None
    # Sample live-buffer peaks into last_round_stats (two process-wide
    # jax.live_arrays() walks per chunk).  Cheap, but disable on
    # latency-critical loops that never read the stats.
    track_stats: bool = True
    # In-jit DP-SGD: per-example clipping + Gaussian noise inside the
    # jitted step (repro.privacy.dp).  None (the default) builds the
    # original step closure untouched — the unprotected hot path stays
    # bitwise identical.  Accepts a DPConfig or a job-spec dict.
    dp: DPConfig | None = None
    # Observability: a repro.obs Tracer records per-chunk "stage" spans
    # (on the staging track, whichever thread stages) and flows down to
    # the device-cohort pool.  None resolves to the shared no-op tracer.
    tracer: Any = None
    # Peak live-buffer footprint + staging accounting of the most recent
    # train_cohort call, populated after every round.
    last_round_stats: dict[str, Any] | None = dataclasses.field(default=None, init=False)

    def __post_init__(self) -> None:
        self.tracer = resolve_tracer(self.tracer)
        if self.staging not in STAGING_MODES:
            raise ValueError(
                f"unknown staging {self.staging!r}; choose from {STAGING_MODES}"
            )
        if isinstance(self.mesh, str):
            if self.mesh != "auto":
                raise ValueError(f"mesh must be a Mesh, None, or 'auto'; got {self.mesh!r}")
            from repro.launch.mesh import make_data_mesh

            self.mesh = make_data_mesh() if jax.device_count() > 1 else None
        mesh = self.mesh if self.mesh is not None and "data" in self.mesh.axis_names else None
        self._data_mesh = mesh
        self._num_shards = int(mesh.shape["data"]) if mesh is not None else 1
        self._device_cohort: DeviceCohort | None = None
        self._fed_lanes: FederationLanes | None = None
        self.dp = resolve_dp(self.dp)

        if self.dp is None:

            def client_step(params, opt_state, key_data, batch, valid):
                """One masked local step; dummy steps are exact no-ops."""
                keys = jax.random.split(jax.random.wrap_key_data(key_data))
                loss, grads = jax.value_and_grad(self.loss_fn)(params, batch, keys[1])
                updates, opt_new = self.optimizer.update(grads, opt_state, params)
                params_new = apply_updates(params, updates)
                keep = lambda new, old: jnp.where(valid, new, old)
                params = jax.tree.map(keep, params_new, params)
                opt_state = jax.tree.map(keep, opt_new, opt_state)
                key_data = jnp.where(valid, jax.random.key_data(keys[0]), key_data)
                return params, opt_state, key_data, jnp.where(valid, loss, jnp.nan)

        else:
            dp_grad = dp_value_and_grad(self.loss_fn, self.dp)

            def client_step(params, opt_state, key_data, batch, valid):
                """One masked DP-SGD local step: clip per example, noise in-jit.

                The chain key splits 3 ways (next-chain, dropout, noise) so
                noise draws ride the same per-client key chain as dropout —
                seeded DP runs replay bit-identically.  Dummy steps stay
                exact no-ops: the key only advances on valid steps.
                """
                keys = jax.random.split(jax.random.wrap_key_data(key_data), 3)
                loss, grads = dp_grad(params, batch, keys[1], keys[2])
                updates, opt_new = self.optimizer.update(grads, opt_state, params)
                params_new = apply_updates(params, updates)
                keep = lambda new, old: jnp.where(valid, new, old)
                params = jax.tree.map(keep, params_new, params)
                opt_state = jax.tree.map(keep, opt_new, opt_state)
                key_data = jnp.where(valid, jax.random.key_data(keys[0]), key_data)
                return params, opt_state, key_data, jnp.where(valid, loss, jnp.nan)

        def train_one(params, x_c, y_c, m_c, v_c, key_data):
            """All local epochs for one client: a scan over the step axis."""
            opt_state = self.optimizer.init(params)

            def step(carry, inp):
                p, s, kd = carry
                xb, yb, mb, valid = inp
                p, s, kd, loss = client_step(p, s, kd, (xb, yb, mb), valid)
                return (p, s, kd), loss

            (params, _, _), losses = jax.lax.scan(
                step, (params, opt_state, key_data), (x_c, y_c, m_c, v_c)
            )
            return params, losses

        def train_block(params, x, y, mask, valid, key_data, weights, axis_name=None):
            """Train a block of clients and reduce to one weighted param sum.

            Inside shard_map each device holds one client shard and
            ``axis_name`` folds the cross-shard reduction into the same
            weighted sum — one psum of a params-sized tree, the round's
            only collective."""
            stacked, losses = jax.vmap(
                lambda xc, yc, mc, vc, kd: train_one(params, xc, yc, mc, vc, kd)
            )(x, y, mask, valid, key_data)
            return weighted_sum_stacked(stacked, weights, axis_name=axis_name), losses

        def train_lanes(
            params, x_all, y_all, rows, idx, valid, first, last, client, key_data,
            weights, feature_shape, axis_name=None,
        ):
            """Train a block of packed lanes and reduce to one weighted param sum.

            One scan over the plan's slots, vmapped over its lanes.  At a
            ``first`` slot a lane restarts from the round's global params,
            a fresh optimizer state and its client's key; at a ``last`` slot
            it adds ``n_c * params`` to the FedAvg sum.  Each step gathers
            its ``(B, ...)`` batch as ``x_all[row, idx]`` (each sample a
            flat, lane-padded row, ``DeviceCohort``) and derives the example
            mask as ``idx < pad`` (padding slots point at the all-zero pad
            row, so the gathered batch is bit-identical to the rebuilt
            schedule's).  Inside shard_map ``rows`` are local to the shard's
            block of the resident arrays."""
            opt0 = self.optimizer.init(params)
            pad = x_all.shape[1] - 1
            num_lanes = rows.shape[0]
            size = int(np.prod(feature_shape))
            bits = jnp.dtype(f"uint{8 * x_all.dtype.itemsize}")

            def gather_x(row, ib):
                # The features' only use is a default-precision matmul, so
                # on a TPU XLA's bfloat16 propagation would otherwise move
                # that matmul's input rounding back through the gather and
                # convert the whole resident cohort on every call.  A round
                # trip through the same bits behind a barrier stops the
                # propagation at the step's batch; every number is the same.
                xb = jax.lax.bitcast_convert_type(x_all[row, ib], bits)
                xb = jax.lax.bitcast_convert_type(jax.lax.optimization_barrier(xb), x_all.dtype)
                return xb[:, :size].reshape(*ib.shape, *feature_shape)

            def lane_step(p, s, kd, row, ib, ok, start, kd0):
                restart = lambda g, q: jnp.where(start, g, q)
                p = jax.tree.map(restart, params, p)
                s = jax.tree.map(restart, opt0, s)
                kd = jnp.where(start, kd0, kd)
                batch = (gather_x(row, ib), y_all[row, ib], (ib < pad).astype(jnp.float32))
                return client_step(p, s, kd, batch, ok)

            def slot(carry, inp):
                p, s, kd, wsum = carry
                row, ib, ok, start, end, c = inp
                p, s, kd, loss = jax.vmap(lane_step)(p, s, kd, row, ib, ok, start, key_data[c])
                done = weighted_sum_stacked(p, jnp.where(end, weights[c], 0.0))
                return (p, s, kd, jax.tree.map(jnp.add, wsum, done)), loss

            lanes_of = lambda tree: jax.tree.map(
                lambda a: jnp.broadcast_to(a, (num_lanes, *a.shape)), tree
            )
            carry = (
                lanes_of(params),
                lanes_of(opt0),
                jnp.zeros((num_lanes, *key_data.shape[1:]), key_data.dtype),
                jax.tree.map(
                    lambda a: jnp.zeros(a.shape, jnp.promote_types(a.dtype, jnp.float32)),
                    params,
                ),
            )
            xs = tuple(jnp.swapaxes(a, 0, 1) for a in (rows, idx, valid, first, last, client))
            (_, _, _, wsum), losses = jax.lax.scan(slot, carry, xs)
            if axis_name is not None:
                wsum = jax.tree.map(lambda a: jax.lax.psum(a, axis_name), wsum)
            return wsum, jnp.swapaxes(losses, 0, 1)

        data, rep = P("data"), P()

        def on_mesh(block, *in_specs):
            """``block`` per shard under shard_map, its sum psum'd; as is
            without a mesh."""
            if mesh is None:
                return block
            return jax.shard_map(
                functools.partial(block, axis_name="data"),
                mesh=mesh,
                in_specs=in_specs,
                out_specs=(rep, data),
                check_vma=False,
            )

        train_block = on_mesh(train_block, rep, data, data, data, data, data, data)

        def per_client_losses(losses, valid):
            # Per-client mean loss over the LAST epoch's real steps (matching
            # the sequential LocalTrainer's reported loss).
            spe = losses.shape[1] // self.local_epochs
            last, last_valid = losses[:, -spe:], valid[:, -spe:]
            count = jnp.maximum(last_valid.sum(axis=1), 1)
            return jnp.where(last_valid, last, 0.0).sum(axis=1) / count

        def cohort_round(params, acc, x, y, mask, valid, key_data, weights):
            wsum, losses = train_block(params, x, y, mask, valid, key_data, weights)
            acc = jax.tree.map(jnp.add, acc, wsum)
            return acc, per_client_losses(losses, valid)

        def lane_client_losses(losses, last_epoch, client, num_clients):
            # The same per-client mean, gathered from the lanes: each
            # client's last-epoch steps summed by its index in the round.
            total = jax.ops.segment_sum(
                jnp.where(last_epoch, losses, 0.0).ravel(), client.ravel(), num_clients
            )
            count = jax.ops.segment_sum(
                last_epoch.ravel().astype(jnp.float32), client.ravel(), num_clients
            )
            return total / jnp.maximum(count, 1.0)

        def packed_round(
            params, acc, x_all, y_all, rows, idx, valid, first, last, client,
            last_epoch, key_data, weights, feature_shape,
        ):
            block = on_mesh(
                functools.partial(train_lanes, feature_shape=feature_shape),
                rep, data, data, data, data, data, data, data, data, rep, rep,
            )
            wsum, losses = block(
                params, x_all, y_all, rows, idx, valid, first, last, client,
                key_data, weights,
            )
            acc = jax.tree.map(jnp.add, acc, wsum)
            return acc, lane_client_losses(losses, last_epoch, client, weights.shape[0])

        # Donation layout: the accumulator (argnum 1) aliases in place
        # everywhere; on TPU/GPU the per-round staged buffers are donated
        # too so XLA reuses their memory for round temporaries (XLA:CPU
        # warns on and ignores donations it cannot alias to an output).
        # The resident cohort arrays (argnums 2-3 of the resident round)
        # are never donated — they outlive every round.
        resident = self.staging == "resident"
        donate_argnums: tuple[int, ...] = ()
        if self.donate:
            donate_argnums = (1,)
            if jax.default_backend() != "cpu":
                donate_argnums += tuple(range(4, 13)) if resident else tuple(range(2, 8))
        if resident:
            self._round = jax.jit(
                packed_round, donate_argnums=donate_argnums, static_argnames="feature_shape"
            )
        else:
            self._round = jax.jit(cohort_round, donate_argnums=donate_argnums)

    # ------------------------------------------------------------------
    # staging helpers
    # ------------------------------------------------------------------

    def attach_device_cohort(self, clients: Sequence[ClientDataset]) -> DeviceCohort:
        """Upload a federation's train arrays once for resident staging.

        Rounds over any subset of ``clients`` then stage only index plans.
        ``FederatedServer`` calls this with the (possibly recruited)
        federation before round one; direct ``train_cohort`` callers may
        skip it, in which case the first resident round attaches its own
        cohort lazily.  With ``resident_budget_bytes`` set and a federation
        too large for it, the cohort is an LRU pool and rounds upload only
        their sampled clients.
        """
        dc = build_device_cohort(
            clients,
            mesh=self._data_mesh,
            resident_budget_bytes=self.resident_budget_bytes,
            tracer=self.tracer,
        )
        # Pack the federation into lanes once: every round at its
        # steps_per_epoch then places its participants in these lanes.
        shard = np.zeros(len(clients), dtype=np.int64)
        if not dc.is_pooled:
            per_shard = dc.num_rows // self._num_shards
            shard = np.asarray([dc.row_of(c) for c in clients]) // per_shard
        self._fed_lanes = pack_federation(
            clients, shard, self._num_shards, self.batch_size, self.local_epochs
        )
        self._device_cohort = dc
        return dc

    def _ensure_device_cohort(self, clients: Sequence[ClientDataset]) -> DeviceCohort:
        dc = self._device_cohort
        if dc is not None and all(dc.owns(c) for c in clients):
            return dc
        return self.attach_device_cohort(clients)

    def _device_put_chunk(self, arrays: tuple[tuple, tuple]) -> tuple:
        """Stage one chunk's host arrays in a single pytree ``device_put``.

        ``arrays`` is ``(sharded, replicated)``: under a mesh the first
        group is sharded over its data axis (each carries the client or
        lane axis first) and the second goes to every device.  Returns
        both groups as one flat tuple."""
        sharded, replicated = arrays
        if self._data_mesh is None:
            return jax.device_put((*sharded, *replicated))
        mesh = self._data_mesh
        return jax.device_put(sharded, NamedSharding(mesh, P("data"))) + jax.device_put(
            replicated, NamedSharding(mesh, P())
        )

    @staticmethod
    def _stack_key_data(client_keys) -> np.ndarray | jax.Array:
        """(C, ...) uint32 key data from typed keys, a key array, or raw data.

        Device inputs (the ``chain_split_keys`` output) stay on device —
        the round consumes them there."""
        if isinstance(client_keys, jax.Array) and jnp.issubdtype(
            client_keys.dtype, jax.dtypes.prng_key
        ):
            return jax.random.key_data(client_keys)
        if isinstance(client_keys, jax.Array):
            return client_keys
        if isinstance(client_keys, np.ndarray):
            return client_keys
        return np.stack([np.asarray(jax.random.key_data(k)) for k in client_keys])

    @staticmethod
    def _chunk_key_data(all_key_data, start: int, count: int, padded: int):
        """One chunk's key slice, zero-padded on the client axis to
        ``padded`` rows, staying on whichever side (host/device) the stacked
        keys already live.  The device path always materializes a fresh
        buffer: a full-range slice is an identity in jax, and the round
        step donates / eagerly deletes its staged inputs — handing it the
        caller's own array would destroy it as a side effect."""
        tail = all_key_data.shape[1:]
        if isinstance(all_key_data, jax.Array):
            sel = all_key_data[start : start + count]
            if padded == count:
                return jnp.copy(sel)
            return jnp.zeros((padded, *tail), all_key_data.dtype).at[:count].set(sel)
        out = np.zeros((padded, *tail), dtype=all_key_data.dtype)
        out[:count] = all_key_data[start : start + count]
        return out

    # ------------------------------------------------------------------
    # the round
    # ------------------------------------------------------------------

    def train_cohort(
        self,
        params: PyTree,
        clients: Sequence[ClientDataset],
        rng: np.random.Generator,
        client_keys: Sequence[jax.Array] | np.ndarray | jax.Array,
        steps_per_epoch: int | None = None,
    ) -> tuple[PyTree, np.ndarray, int]:
        """One FedAvg round over ``clients``.

        ``client_keys`` holds one jax PRNG key per client, in the same order
        the sequential engine would have split them — a list of typed keys,
        a typed key array, or the stacked ``(C, ...)`` key data straight
        from ``chain_split_keys`` (which stays on device).  Pass a
        federation-wide ``steps_per_epoch`` to pin the schedule's step axis
        across rounds — otherwise it tracks this cohort's largest client and
        a different participant mix can retrigger compilation.  Returns the
        round's aggregated params, per-client mean local losses, and the
        number of *real* (unpadded) local steps executed;
        ``last_round_stats["scanned_steps"]`` counts the client-steps the
        scan ran, padding included: ``lanes * L`` under resident staging,
        clients times their padded step axis under rebuild staging.
        """
        tracer = self.tracer
        with tracer.span("prepare", clients=len(clients)):
            all_key_data = self._stack_key_data(client_keys)
            if len(clients) != len(all_key_data):
                raise ValueError("need exactly one PRNG key per client")
            sizes = [c.n_train for c in clients]
            spe = steps_per_epoch or cohort_steps_per_epoch(sizes, self.batch_size)
            if self.cohort_chunk is not None and self.cohort_chunk <= 0:
                raise ValueError(f"cohort_chunk must be positive, got {self.cohort_chunk}")
            chunk = self.cohort_chunk or len(clients)
            resident = self.staging == "resident"
            dcohort = self._ensure_device_cohort(clients) if resident else None
            pool_before = (0, 0, 0, 0)
            if resident and dcohort.is_pooled:
                # One residency pass per round, before any plan is staged:
                # rows are then stable for the whole round, so the prefetch
                # thread's plan building never races an eviction.
                pool_before = (
                    dcohort.uploads,
                    dcohort.evictions,
                    dcohort.bytes_uploaded,
                    dcohort.hits,
                )
                dcohort.ensure_resident(clients)

            baseline = live_buffer_stats() if self.track_stats else {"count": 0, "bytes": 0}
            peak = {"count": 0, "bytes": 0}
            acc = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.promote_types(p.dtype, jnp.float32)), params
            )

        def sample() -> None:
            if not self.track_stats:
                return
            now = live_buffer_stats()
            peak["count"] = max(peak["count"], now["count"] - baseline["count"])
            peak["bytes"] = max(peak["bytes"], now["bytes"] - baseline["bytes"])

        def _build_chunk(start: int) -> tuple[int, float, int, int, int, tuple]:
            """Build + upload one chunk's batch data.

            Returns (host bytes staged, chunk weight, real client count,
            client-steps the round's scan runs, padding included, lanes,
            device args for the round step).  Consumes ``rng`` — must run
            strictly in chunk order (the StagingPipeline's single ordered
            producer preserves this).
            """
            part = clients[start : start + chunk]
            if resident:
                part_sizes = np.asarray([c.n_train for c in part], dtype=np.int64)
                rows = np.asarray([dcohort.row_of(c) for c in part], dtype=np.int64)
                per_shard = dcohort.num_rows // self._num_shards
                shard = rows // per_shard
                steps = -(-part_sizes // self.batch_size) * self.local_epochs
                # The attached federation's packing, where this round runs at
                # its step count; else the round packs its own participants.
                fed = self._fed_lanes
                packing = (None, None)
                if fed is not None and fed.steps_per_epoch == spe:
                    packing = ([fed.lane[c.client_id] for c in part], fed.width)
                lane, width = assign_lanes(
                    steps, shard, self._num_shards, spe * self.local_epochs, *packing
                )
                plan = build_lane_plan(
                    part_sizes,
                    self.batch_size,
                    self.local_epochs,
                    rng,
                    shard * width + lane,
                    width * self._num_shards,
                    steps_per_epoch=spe,
                    client_rows=rows % per_shard,
                    pad_index=dcohort.pad_index,
                )
                weight = float(plan.weights.sum())
                key_data = self._chunk_key_data(all_key_data, start, len(part), len(part))
                host: tuple = (*plan.slot_arrays, plan.weights)
                staged = self._device_put_chunk((plan.slot_arrays, (key_data, plan.weights)))
                lanes = plan.num_lanes
                scanned = int(plan.valid.size)  # (lanes, slots), empty slots included
            else:
                sched = build_cohort_schedule(
                    [c.train for c in part],
                    self.batch_size,
                    self.local_epochs,
                    rng,
                    steps_per_epoch=spe,
                )
                weight = float(sched.weights.sum())
                # Pad the client axis with weight-0 dummy clients so it
                # divides the mesh's data axis (all steps masked no-ops).
                sched = pad_cohort_schedule(sched, self._num_shards)
                key_data = self._chunk_key_data(
                    all_key_data, start, len(part), sched.num_clients
                )
                lanes = sched.num_clients
                scanned = int(sched.step_valid.size)
                host = (sched.x, sched.y, sched.mask, sched.step_valid, sched.weights)
                staged = self._device_put_chunk(
                    ((sched.x, sched.y, sched.mask, sched.step_valid, key_data, sched.weights), ())
                )
            nbytes = sum(a.nbytes for a in host)
            if isinstance(key_data, np.ndarray):
                nbytes += key_data.nbytes
            return nbytes, weight, len(part), scanned, lanes, staged

        def stage_chunk(start: int) -> tuple[int, float, int, int, int, tuple]:
            # The span lands on whichever thread stages — inline here, or
            # the StagingPipeline's producer during prefetch.
            with tracer.span("stage", track="staging", chunk=int(start)):
                return _build_chunk(start)

        total_weight = 0.0
        bytes_staged = 0
        scanned_steps = 0
        total_lanes = 0
        num_chunks = 0
        # Per-chunk device loss arrays; fetched once after the whole round
        # is dispatched so chunk k+1 never blocks on chunk k's readback.
        chunk_losses: list[tuple[int, int, jax.Array]] = []
        starts = range(0, len(clients), chunk)
        pipeline: StagingPipeline | None = None
        if resident and self.prefetch and len(starts) > 1:
            pipeline = StagingPipeline(stage_chunk, starts, tracer=tracer)
            staged_chunks = iter(pipeline)
        else:
            staged_chunks = (stage_chunk(s) for s in starts)

        # Keeps the previous chunk's staged buffers alive into the next
        # iteration's first sample() so the plain (non-donated) path's
        # documented two-chunk window is actually observed in the stats.
        held: list[tuple] = []
        resident_arrays = (dcohort.x, dcohort.y) if resident else ()
        features = {"feature_shape": dcohort.feature_shape} if resident else {}
        try:
            for start, (nbytes, weight, count, scanned, lanes, args) in zip(
                starts, staged_chunks
            ):
                total_weight += weight
                bytes_staged += nbytes
                scanned_steps += scanned
                total_lanes += lanes
                # Sampled before the previous chunk's buffers (still
                # referenced by ``held`` on the non-donated path) are
                # released: the plain rebuild path holds two chunks of
                # schedule here, the donated path one.
                sample()
                held.clear()
                with tracer.span("dispatch", chunk=int(start)):
                    acc, losses = self._round(params, acc, *resident_arrays, *args, **features)
                    if self.donate:
                        # Realize the donation of the staged chunk: the step
                        # consumed it, free the device copies now instead of
                        # at Python GC time.  The resident cohort arrays are
                        # not part of ``args`` and stay alive.
                        for a in args:
                            if not a.is_deleted():
                                a.delete()
                sample()
                chunk_losses.append((start, count, losses))
                held.append(args)
                num_chunks += 1
        finally:
            if pipeline is not None:
                # Re-raise an uncollected staging exception only when this
                # round is not already propagating one — close() must never
                # mask the error that aborted the loop above.
                pipeline.close(raise_pending=sys.exc_info()[0] is None)

        with tracer.span("readback", chunks=len(chunk_losses)):
            per_losses = np.full(len(clients), np.nan, dtype=np.float32)
            for start, count, losses in chunk_losses:
                per_losses[start : start + count] = np.asarray(losses)[:count]

        with tracer.span("finalize"):
            new_params = jax.tree.map(
                lambda t, ref: (t / total_weight).astype(ref.dtype), acc, params
            )
            pooled = resident and dcohort.is_pooled
            self.last_round_stats = {
                "chunks": num_chunks,
                "shards": self._num_shards,
                "donated": self.donate,
                "staging": self.staging,
                "prefetch": pipeline is not None,
                "bytes_staged": bytes_staged,
                "bytes_resident": dcohort.nbytes if resident else 0,
                "plans_prefetched": pipeline.prefetched if pipeline is not None else 0,
                "peak_live_buffers": peak["count"],
                "peak_live_bytes": peak["bytes"],
                "lanes": total_lanes,
                "scanned_steps": scanned_steps,
                "pool": pooled,
                "pool_rows": dcohort.pool_rows if pooled else 0,
                "pool_uploads": dcohort.uploads - pool_before[0] if pooled else 0,
                "pool_evictions": dcohort.evictions - pool_before[1] if pooled else 0,
                "pool_bytes_uploaded": dcohort.bytes_uploaded - pool_before[2] if pooled else 0,
                "pool_hits": dcohort.hits - pool_before[3] if pooled else 0,
            }
            real_steps = sum(
                local_round_steps(n, self.batch_size, self.local_epochs) for n in sizes
            )
        return new_params, per_losses, real_steps

    def steps_per_round(self, client: ClientDataset) -> int:
        return local_round_steps(client.n_train, self.batch_size, self.local_epochs)
