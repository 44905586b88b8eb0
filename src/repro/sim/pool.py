"""Device-resident client pool: pad once, gather cohorts on device.

The legacy host loop rebuilt every round's cohort batch with numpy fancy
indexing and re-uploaded it — O(cohort · batch bytes) of host work and
host→device traffic per round, fully serialized with the jitted round step.
The :class:`ClientPool` inverts that: the whole ``FederatedDataset`` is
padded/stacked ONCE into device-resident buffers, and a round cohort becomes
two tiny index arrays (client ids + per-client example rows) that a jitted
gather turns into the ``(n, R, b, ...)`` round batch entirely on device.

**Layout.** The gather reads whole examples, so every buffer keeps each
example contiguous and row-major: ``(rows, max_examples, F)`` with ``F`` the
product of the example shape (``(rows, max_examples)`` where ``F`` is 1).
A TPU picks a buffer's default layout by the least tile padding: left at
``F = 784`` it makes the client axis minor, and every gather then first
copied the whole pool into row-major order (a pool-sized temp in every
call).  So the device buffer pads its minor axis to whole 128-lane tiles and
``max_examples`` to whole sublane tiles (:func:`device_shape`), where
row-major needs no padding and is the default; padding is zeros and the
gather slices it off.  The layout stays the default one on purpose: a
layout requested through ``jax.experimental.layout`` does not survive JAX's
persistent compilation cache (an executable read back from it does not keep
the requested layout: a TPU refuses the buffer, a CPU misreads it), and the
program runs with that cache on.  Flattening also
keeps a small minor dimension (an image's 3 channels) from padding on its
own.  The upload copies each host buffer in flat row blocks, which the
runtime moves without tiling them on the host, and tiles them on the device
(:func:`_to_device`).

The driver (repro/sim/driver.py) runs that gather as a **double-buffered
host→device prefetch pipeline**: while round k's jitted step is still
executing, round k+1's plan is drawn on the host and its gather is already
dispatched — the host never sits between two device computations.  For fully
device-resident pools the driver can go further and `lax.scan` over whole
blocks of rounds (the plans for the block are stacked and the gather happens
inside the scan body), removing the per-round dispatch entirely.

Cohort *plans* (:func:`plan_cohort`) consume the host RNG in exactly the
order ``FederatedDataset.sample_round_batches`` does — one
``rng.permutation(n_i)`` per cohort client, in cohort order — so the batches
a pool gather produces are bitwise identical to the legacy host-built ones,
which is what keeps the driver's sampling masks bitwise identical to the
legacy trainer loop (gated by tests/test_sim.py).

**Sharded mode** (``ClientPool(dataset, mesh=...)``): the padded pool
buffers — the big object, ``pool × max_examples`` rows — are placed with a
``NamedSharding`` over the client mesh axis, so each device holds only its
``pool / axis_size`` row block.  The cohort gather then runs inside a
shard_map: the host splits the index plan per shard (owner shard + local row
for every cohort position), each shard performs ONE gather over its local
pool slice (non-owned positions masked to zero), and a single ``psum_scatter``
over the client axis hands every shard exactly its ``(n/axis_size, R, b, …)``
cohort slice — the layout the shard_map round's ``P(client_axis)`` in_spec
wants, with no resharding in between.  The replicated ``(pool, …)`` flatten
of the single-device pool never exists; the only cross-shard traffic is the
cohort-sized scatter-reduce.  Cohort order (and therefore the RNG stream and
the sampling masks) is untouched — sharding only changes WHERE rows live.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.ocs import AvailabilityTrace
from repro.obs.trace import span

# a TPU vector register's lanes: the minor tile of every layout
LANES = 128
# bytes of one host-to-device copy of the pool upload (``_to_device``)
UPLOAD_BYTES = 256 << 20

# fold constant deriving the client-state key from the round key.  The round
# engines consume the round key as ``k_sample, k_comp = split(key)``; folding
# a fixed constant instead gives the state layer a stream disjoint from both,
# so adding system realism never perturbs the sampling/compression draws
# (the bit-for-bit scalar-path regression gate relies on this).
STATE_FOLD = 7


class RoundPlan(NamedTuple):
    """One round's cohort, as host index arrays (the only per-round host work).

    ``clients``: (n,) pool rows; ``take``: (n, R, b) per-client example rows;
    ``step_mask``: (n, R) local-epoch step mask (see
    ``FederatedDataset.sample_round_batches``).
    """

    clients: np.ndarray
    take: np.ndarray
    step_mask: np.ndarray


def plan_cohort(rng, sizes, clients, max_steps, batch_size, local_epoch=True):
    """Draw one round's example indices, RNG-compatible with the host path.

    Consumes ``rng`` exactly like ``FederatedDataset.sample_round_batches``
    (one ``rng.permutation(n_i)`` per client, in cohort order) and computes
    the same cyclic ``np.resize`` fill and local-epoch step mask — so a pool
    gather of this plan is bitwise identical to the legacy host-built batch.
    """
    clients = np.asarray(clients)
    take = np.empty((len(clients), max_steps, batch_size), np.int32)
    step_mask = np.empty((len(clients), max_steps), np.float32)
    for i, ci in enumerate(clients):
        n = int(sizes[int(ci)])
        steps_i = (
            max(1, min(max_steps, -(-n // batch_size))) if local_epoch else max_steps
        )
        perm = rng.permutation(n)
        take[i] = np.resize(perm, (max_steps, batch_size))
        step_mask[i] = (np.arange(max_steps) < steps_i).astype(np.float32)
    return RoundPlan(clients.astype(np.int32), take, step_mask)


def gather_batch(buffers, shapes, clients, take, step_mask):
    """Pure (traceable) cohort gather: pool buffers -> ``(n, R, b, ...)`` batch.

    ``shapes`` holds ``(key, example shape)`` pairs (``ClientPool.
    example_shapes``): each gathered ``(n, R, b, F_pad)`` leaf drops its
    lane padding and is reshaped back to ``(n, R, b) + shape``.  Used both
    by the jitted :meth:`ClientPool.gather` and *inside* the driver's
    scan-over-rounds body, where ``clients``/``take``/``step_mask`` are one
    round's slice of the stacked block plans.
    """

    def one(buf, shape):
        # one fused gather: (n, R, b) example rows straight out of the
        # (pool, max_examples, F) buffer — no (n, max_examples, F)
        # per-cohort intermediate is ever materialised.
        rows = buf[clients[:, None, None], take]
        if buf.ndim == 3:
            rows = rows[..., : math.prod(shape)]
        return rows.reshape(take.shape + shape)

    batch = {k: one(buffers[k], shape) for k, shape in shapes}
    batch["_step_mask"] = step_mask
    return batch


def _padded(client_data, key, rows, max_examples):
    """One data key of every client, zero-padded into a host buffer of
    ``(rows, max_examples, F)``, ``F`` the product of the example shape
    (``(rows, max_examples)`` where ``F`` is 1)."""
    first = client_data[0][key]
    f = math.prod(first.shape[1:])
    tail = (f,) if f > 1 else ()
    buf = np.zeros((rows, max_examples) + tail, first.dtype)
    for i, d in enumerate(client_data):
        n = len(d[key])
        buf[i, :n] = d[key].reshape((n,) + tail)
    return buf


def device_shape(shape, dtype):
    """A pool buffer's device shape: the minor axis padded to a multiple of
    128 lanes and, for ``(rows, max_examples, F)``, ``max_examples`` to a
    whole sublane tile (8 rows of 32-bit values), so that the TPU's default
    layout of the buffer is row-major (module docstring, Layout)."""
    *major, minor = shape
    if len(major) == 2:
        sub = 8 * max(1, 4 // np.dtype(dtype).itemsize)
        major[1] = -(-major[1] // sub) * sub
    return (*major, -(-minor // LANES) * LANES)


@functools.partial(jax.jit, static_argnums=3, donate_argnums=0)
def _write(buf, flat, r0, tail):
    """The rows ``flat`` holds, each of shape ``tail``, written into the
    donated ``buf`` from row ``r0``."""
    piece = flat.reshape((-1,) + tail)
    return jax.lax.dynamic_update_slice(buf, piece, (r0,) + (0,) * len(tail))


def _to_device(host, dev):
    """``host`` in a zero buffer of its :func:`device_shape` on ``dev``
    (``None``: the default device, uncommitted), copied in row blocks of
    about ``UPLOAD_BYTES``, each one flat.

    A flat copy has the trivial 1-D layout, so the runtime moves it without
    tiling it on the host; :func:`_write` tiles it on the device.  Each
    block's write completes before the next copy is issued, so at most one
    block is on the device beside the buffer."""
    rows = host.shape[0]
    block = min(rows, max(1, UPLOAD_BYTES // host[0].nbytes))
    buf = jnp.zeros(device_shape(host.shape, host.dtype), host.dtype, device=dev)
    for r0 in range(0, rows, block):
        r0 = min(r0, rows - block)   # the last block overlaps: one shape
        flat = jax.device_put(host[r0 : r0 + block].reshape(-1), dev)
        buf = _write(buf, flat, r0, host.shape[1:])
        buf.block_until_ready()
    return buf


def _upload(host, sharding):
    """``host`` on the device by :func:`_to_device`: uncommitted on the
    default device without ``sharding`` (as ``jnp.asarray`` places it, so
    that jitted outputs stay uncommitted and a step's second call reuses its
    first compile), else each device's row block, placed by ``sharding``."""
    if sharding is None:
        return _to_device(host, None)
    shape = device_shape(host.shape, host.dtype)
    shards = [_to_device(host[idx[0]], dev)
              for dev, idx in sharding.addressable_devices_indices_map(shape).items()]
    return jax.make_array_from_single_device_arrays(shape, sharding, shards)


@functools.partial(jax.jit, static_argnums=1)
def _gather_jit(buffers, shapes, clients, take, step_mask):
    return gather_batch(buffers, shapes, clients, take, step_mask)


def _sharded_gather(mesh, axis, shapes):
    """The jitted shard-local gather + psum_scatter pipeline (module doc)
    over ``mesh``'s ``axis``, for buffers of ``shapes``' examples."""
    from repro.kernels.ops import get_shard_map

    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]

    def body(buffers, owner, local_row, take, step_mask):
        n = owner.shape[0]
        k = n // axis_size
        idx = jax.lax.axis_index(axis)
        own = owner == idx

        def one(buf, shape):
            # ONE gather over the shard's local pool slice; positions a
            # different shard owns read row 0 and are masked to zero, so
            # the cross-shard psum_scatter reconstructs each position from
            # its unique owner while handing this shard only its
            # (k, R, b, ...) cohort slice.
            rows = buf[jnp.where(own, local_row, 0)[:, None, None], take]
            if buf.ndim == 3:
                rows = rows[..., : math.prod(shape)]
            rows = jnp.where(own.reshape((n,) + (1,) * (rows.ndim - 1)), rows, 0)
            rows = jax.lax.psum_scatter(rows, axis, scatter_dimension=0, tiled=True)
            return rows.reshape(rows.shape[:3] + shape)

        batch = {bk: one(buffers[bk], shape) for bk, shape in shapes}
        batch["_step_mask"] = jax.lax.dynamic_slice_in_dim(step_mask, idx * k, k)
        return batch

    smap, check = get_shard_map()
    fn = smap(body, mesh=mesh, in_specs=(P(axis), P(), P(), P(), P()),
              out_specs=P(axis), **check)
    return jax.jit(fn)


class ClientPool:
    """Device-resident padded copy of a ``FederatedDataset``.

    Every data key is stacked into one ``(pool, max_examples, F)`` buffer,
    ``F`` the product of the key's example shape (``(pool, max_examples)``
    where ``F`` is 1), stored row-major on the device so that each example
    is contiguous, with :func:`device_shape`'s tile padding (module
    docstring, Layout); ``example_shapes`` keeps each key's example shape
    for :func:`gather_batch`.  Clients are padded with zeros up to the
    largest client; real rows are always addressed through a
    :class:`RoundPlan`, so padding is never read.  Built once per
    simulation; all subsequent per-round work is index generation on the
    host and a jitted gather on device.

    With ``mesh`` given, the pool runs in **sharded mode**: the row count
    pads to a multiple of the ``client_axis`` size, every buffer is placed
    with ``NamedSharding(mesh, P(client_axis))`` (each device holds one row
    block), and :meth:`gather` becomes the shard-local gather +
    ``psum_scatter`` pipeline of the module docstring, emitting the cohort
    batch already sharded over the client axis.
    """

    def __init__(self, dataset, mesh=None, client_axis: str = "data"):
        self.n_clients = dataset.n_clients
        self.sizes = np.asarray(dataset.sizes())
        self.max_examples = int(self.sizes.max())
        self.mesh, self.client_axis = mesh, client_axis
        if mesh is not None:
            self.axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))[client_axis]
        else:
            self.axis_size = 1
        # sharded mode pads the POOL axis so every shard owns an equal row
        # block; padded rows hold zeros and are never referenced by a plan
        # (plan clients always index the real dataset).
        rows = self.n_clients + (-self.n_clients) % self.axis_size
        self.rows_per_shard = rows // self.axis_size
        sharding = None if mesh is None else NamedSharding(mesh, P(client_axis))
        first = dataset.client_data[0]
        self.example_shapes = tuple((k, v.shape[1:]) for k, v in first.items())
        with span("pool_build"):
            host = {k: _padded(dataset.client_data, k, rows, self.max_examples)
                    for k in first}
        with span("pool_upload"):
            self.buffers = {k: _upload(b, sharding) for k, b in host.items()}
        self._sharded_gather = (None if mesh is None
                                else _sharded_gather(mesh, client_axis, self.example_shapes))

    @property
    def nbytes(self) -> int:
        """Bytes of the padded pool buffers (global, all shards)."""
        return sum(int(b.size * b.dtype.itemsize) for b in self.buffers.values())

    def plan(self, rng, clients, max_steps, batch_size, local_epoch=True):
        """:func:`plan_cohort` bound to this pool's client sizes."""
        return plan_cohort(rng, self.sizes, clients, max_steps, batch_size, local_epoch)

    def gather(self, plan: RoundPlan):
        """Dispatch the (async, jitted) device gather of one round's batch.

        Sharded mode returns the batch with every leaf sharded
        ``P(client_axis)`` — ready for the shard_map round's in_specs.
        """
        with span("gather"):
            if self._sharded_gather is None:
                return _gather_jit(
                    self.buffers,
                    self.example_shapes,
                    jnp.asarray(plan.clients),
                    jnp.asarray(plan.take),
                    jnp.asarray(plan.step_mask),
                )
            # host side of the per-shard index plan: owner shard + local row
            # of every cohort position (cohort ORDER is untouched — parity).
            owner = plan.clients // self.rows_per_shard
            local_row = plan.clients % self.rows_per_shard
            return self._sharded_gather(
                self.buffers,
                jnp.asarray(owner.astype(np.int32)),
                jnp.asarray(local_row.astype(np.int32)),
                jnp.asarray(plan.take),
                jnp.asarray(plan.step_mask),
            )


@dataclass(frozen=True)
class SystemConfig:
    """System-realism knobs for the client-state layer (ISSUE 7 tentpole).

    ``p_up``/``p_down`` drive each client's two-state Markov availability
    chain (P(down->up) and P(up->down)); its stationary distribution is
    ``pi = p_up / (p_up + p_down)``, and the Appendix-E i.i.d. Bernoulli(q)
    model is the exact degenerate case ``p_up = q, p_down = 1 - q`` (the
    chain transition then ignores the current state bit-for-bit — see
    :func:`step_client_state`).  ``latency_mu``/``latency_sigma`` give every
    client a fixed lognormal latency scale; each round's report time is an
    Exponential draw at that scale, and a client selected by the plan misses
    the round iff its draw exceeds ``deadline`` (None = no deadline).
    ``drop_prob`` injects mid-round dropout faults, i.i.d. per client per
    round.  All fields are plain Python floats so a config can close over a
    jitted state step statically.
    """

    p_up: float = 1.0        # P(down -> up) per round
    p_down: float = 0.0      # P(up -> down) per round
    latency_mu: float = 0.0      # lognormal location of the per-client scale
    latency_sigma: float = 0.0   # lognormal spread (0 = homogeneous clients)
    deadline: float | None = None  # round deadline in latency units
    drop_prob: float = 0.0   # mid-round dropout probability

    def __post_init__(self):
        for name in ("p_up", "p_down", "drop_prob"):
            v = getattr(self, name)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"{name} must be in [0, 1], got {v}")
        if self.drop_prob >= 1.0:
            raise ValueError("drop_prob must be < 1 (some client must survive)")
        if self.deadline is not None and self.deadline <= 0.0:
            raise ValueError(f"deadline must be > 0, got {self.deadline}")
        if self.latency_sigma < 0.0:
            raise ValueError(f"latency_sigma must be >= 0, got {self.latency_sigma}")

    def stationary(self) -> float:
        """Stationary up-probability ``pi = p_up / (p_up + p_down)``.

        The chain's long-run availability marginal; 1.0 for the frozen
        all-up chain (``p_up = p_down = 0``, the no-dynamics default)."""
        s = self.p_up + self.p_down
        return self.p_up / s if s > 0.0 else 1.0


class ClientState(NamedTuple):
    """Device-resident per-client system state, scanned with the round loop.

    Lives alongside :class:`ClientPool` over the same ``(pool,)`` client
    axis: ``up`` is the Markov availability chain's current state
    (initialised at stationarity so every round's marginal up-probability is
    exactly ``SystemConfig.stationary()``), ``lat_scale`` the client's fixed
    lognormal latency scale.  A plain pytree of arrays, so it threads
    through ``lax.scan`` carries unchanged — the scan-over-rounds driver
    mode carries it next to ``(params, opt_state)``.
    """

    up: jax.Array         # (pool,) bool — chain state entering the next round
    lat_scale: jax.Array  # (pool,) f32 — per-client mean report latency


def init_client_state(n: int, cfg: SystemConfig, key: jax.Array) -> ClientState:
    """Initialise the chain at stationarity and draw latency scales.

    ``up ~ Bernoulli(pi)`` with ``pi = p_up/(p_up+p_down)`` and
    ``lat_scale = exp(latency_mu + latency_sigma * N(0,1))`` per client —
    both deterministic in ``key``."""
    k_up, k_lat = jax.random.split(key)
    up = jax.random.uniform(k_up, (n,)) < cfg.stationary()
    lat_scale = jnp.exp(
        cfg.latency_mu + cfg.latency_sigma * jax.random.normal(k_lat, (n,))
    ).astype(jnp.float32)
    return ClientState(up=up, lat_scale=lat_scale)


def step_client_state(
    state: ClientState, round_key: jax.Array, clients: jax.Array, cfg: SystemConfig
) -> tuple[ClientState, AvailabilityTrace]:
    """Advance every chain one round and emit the cohort's availability trace.

    Deterministic in ``round_key``: all randomness comes from
    ``fold_in(round_key, STATE_FOLD)`` — a stream disjoint from the round
    engines' ``split(key)`` sampling/compression keys, so the engines' own
    draws are untouched.  The chain transition is written as a single
    uniform threshold per client, ``up' = u >= p_down`` if up else
    ``u >= 1 - p_up``: when ``p_up + p_down = 1`` (the Appendix-E degenerate
    case ``p_up = q``) both thresholds coincide and the next state is the
    i.i.d. Bernoulli(q) draw ``u >= 1 - q`` regardless of the current state
    — the recovery is bitwise, not just in distribution.  Latency is an
    Exponential draw at each client's fixed scale compared against
    ``cfg.deadline``; dropout is an i.i.d. Bernoulli fault.  The returned
    trace is gathered down to the round's cohort ``clients`` and carries
    each client's analytic ``include_prob = pi * P(on_time) * (1 - drop_prob)``
    so :func:`repro.core.ocs.sampling_plan` keeps the Eq. 2 estimator
    unbiased over the whole system process.
    """
    n = state.up.shape[0]
    k = jax.random.fold_in(round_key, STATE_FOLD)
    k_up, k_lat, k_drop = jax.random.split(k, 3)
    u = jax.random.uniform(k_up, (n,))
    up = jnp.where(state.up, u >= cfg.p_down, u >= 1.0 - cfg.p_up)
    if cfg.deadline is None:
        on_time = jnp.ones((n,), bool)
        p_on = jnp.ones((n,), jnp.float32)
    else:
        lat = state.lat_scale * jax.random.exponential(k_lat, (n,))
        on_time = lat <= cfg.deadline
        p_on = 1.0 - jnp.exp(-cfg.deadline / jnp.maximum(state.lat_scale, 1e-12))
    if cfg.drop_prob > 0.0:
        kept = jax.random.uniform(k_drop, (n,)) >= cfg.drop_prob
    else:
        kept = jnp.ones((n,), bool)
    include = (cfg.stationary() * (1.0 - cfg.drop_prob)) * p_on
    c = jnp.asarray(clients)
    trace = AvailabilityTrace(
        up=up[c], on_time=on_time[c], kept=kept[c],
        include_prob=include[c].astype(jnp.float32),
    )
    return ClientState(up=up, lat_scale=state.lat_scale), trace


def expected_survivors(cfg: SystemConfig, m: int, over_select: float = 1.0) -> float:
    """Back-of-envelope E[#reporting clients] for an over-selected plan.

    ``round(m * over_select) * pi * P(on_time at the median latency scale)
    * (1 - drop_prob)`` — a planning aid for picking ``over_select`` in
    scenario cells, not part of the estimator math."""
    m_eff = max(1, int(round(m * over_select)))
    p_on = 1.0
    if cfg.deadline is not None:
        p_on = 1.0 - math.exp(-cfg.deadline / math.exp(cfg.latency_mu))
    return m_eff * cfg.stationary() * p_on * (1.0 - cfg.drop_prob)


def stack_plans(plans):
    """Stack per-round plans into block arrays for the scan-over-rounds path.

    Returns ``(clients (S,n), take (S,n,R,b), step_mask (S,n,R))`` — the xs a
    ``lax.scan`` over ``S`` rounds consumes, gathering each round's batch from
    the device-resident pool inside the scan body.
    """
    return (
        np.stack([p.clients for p in plans]),
        np.stack([p.take for p in plans]),
        np.stack([p.step_mask for p in plans]),
    )
