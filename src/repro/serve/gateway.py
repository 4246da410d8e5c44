"""Live serving gateway: OnAlgo as a persistent online service.

Every other engine in the repo replays a horizon it already knows.  The
gateway runs the paper's actual deployment loop: devices *report* their
current observation ``(o, h, w)`` as requests arrive, the cloudlet ticks
Algorithm 1 once per slot over whatever reports came in, and streams the
offload/admit decisions back — no future knowledge anywhere.

Two layers:

  :class:`GatewayCore` — the synchronous algorithm surface.  A wave of
  device reports is padded to a size bucket, scattered into fleet-shaped
  ``(N,)`` buffers, quantized with the same
  :func:`~repro.serve.admission.quantize_states_device` the batch
  lowering uses, and rolled through ONE jitted, shape-stable OnAlgo slot
  (:func:`repro.core.onalgo.step` + per-slot cloudlet admission, with
  the topology tier's per-cloudlet duals when a
  :class:`~repro.topology.Topology` is attached).  The dual/rho state
  buffers are donated back to the step, so the persistent state is
  updated in place; there is exactly one compile per ``(bucket, K)``
  shape.  Because non-reporting devices scatter to ``j = 0`` (null) and
  every consumer masks by ``task``, a tick is *bit-identical* to the
  corresponding slot of ``fleet.simulate(..., overlay=...,
  enforce_slot_capacity=True)`` on the same workload counters
  (tests/test_gateway.py holds this over full replays).

  :class:`LiveGateway` — the asynchronous host loop, a depth-bounded
  wave *pipeline*.  Reports are submitted as chunks into a bounded
  queue; the dispatcher drains every queued chunk into one wave (one
  OnAlgo slot), dispatches it via :meth:`GatewayCore.tick_async`
  WITHOUT waiting for its decisions, and moves straight on to forming
  the next wave while a resolver task materializes the in-flight
  decisions in dispatch order and completes the submitters' futures.
  ``max_in_flight`` bounds the pipe depth (default 2; ``1`` reproduces
  the strictly sequential dispatch-then-resolve loop bit for bit).
  Because the persistent state advances at *dispatch* and dispatches
  are strictly ordered, the decision stream is identical at every
  depth — overlap only hides the host gather/scatter latency behind
  device execution.  Graceful degradation is explicit: a full queue
  sheds the chunk immediately, and a wave whose estimated completion —
  dispatch cost, plus the resolve cost of every wave already in
  flight, plus its own resolve cost — would blow the p99 latency SLO
  is answered with *local-execution fallback* decisions (offload
  nobody — always feasible: it is the paper's baseline action and
  touches no algorithm state) instead of missing the deadline.

Wave contract: a wave IS one OnAlgo slot.  Each device may appear at
most once per wave; devices that do not report are treated as null-state
(no task) for that slot, exactly like a ``False`` arrival in the batch
workload.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import re
import threading
import time
from collections import deque
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines as bl
from repro.core import onalgo
from repro.core.onalgo import OnAlgoParams, StepRule
from repro.parallel.mesh import auto_axes
from repro.serve.admission import quantize_states_device
from repro.serve.engine import WaveBuckets
from repro.topology import Topology, validate_topology


def default_buckets(num_devices: int, base: int = 64) -> Tuple[int, ...]:
    """Geometric wave-size buckets: ``base`` doubling up to N.

    One jit compile per bucket; doubling keeps the program count at
    O(log(N / base)) while padding waste stays under 2x.
    """
    if num_devices <= base:
        return (num_devices,)
    out = []
    b = base
    while b < num_devices:
        out.append(b)
        b *= 2
    out.append(num_devices)
    return tuple(out)


def make_tick(N: int, space, *, topo_duals: bool, admit_topo: bool,
              enforce: bool):
    """The gateway's slot function ``tick(state, tables, params, rule,
    idx, o, h, w, assoc, H_k) -> (state, off, adm)`` for an N-device
    fleet (:class:`GatewayCore` jits it with the state donated)."""

    def tick(state, tables, params, rule, idx, o, h, w, assoc, H_k):
        # scatter the wave into fleet-shaped buffers; pad slots carry
        # idx = N and drop.  Non-reporting devices quantize to j = 0
        # (null state) — identical to a False arrival in the batch
        # workload, so the slot replays bit for bit.
        zeros = jnp.zeros((N,), jnp.float32)
        o_f = zeros.at[idx].set(o, mode="drop")
        h_f = zeros.at[idx].set(h, mode="drop")
        w_f = zeros.at[idx].set(w, mode="drop")
        task = jnp.zeros((N,), bool).at[idx].set(True, mode="drop")
        j = quantize_states_device(space, o_f, h_f, w_f, task)
        if topo_duals:
            state, off = onalgo.step(state, j, o_f, h_f, w_f, task,
                                     tables, params, rule, assoc=assoc,
                                     H_k=H_k)
        else:
            state, off = onalgo.step(state, j, o_f, h_f, w_f, task,
                                     tables, params, rule)
        if not enforce:
            adm = off
        elif admit_topo:
            adm = bl.admit_by_capacity_topo(off, h_f, assoc, H_k)
        else:
            adm = bl.admit_by_capacity(off, h_f, params.H)
        # gather the wave's decisions back (pads clip to device N-1
        # and are sliced off on the host)
        off_r = jnp.take(off, idx, mode="clip")
        adm_r = jnp.take(adm, idx, mode="clip")
        return state, off_r, adm_r

    return tick


@dataclasses.dataclass
class GatewayCoreStats:
    ticks: int = 0
    reports: int = 0
    compiled_buckets: set = dataclasses.field(default_factory=set)

    @property
    def compiles(self) -> int:
        return len(self.compiled_buckets)


@dataclasses.dataclass
class PendingTick:
    """A dispatched-but-unresolved gateway tick.

    Returned by :meth:`GatewayCore.tick_async`: the decision arrays stay
    device-resident (no host sync has happened) until :meth:`resolve`
    materializes them.  The core's persistent state has already advanced
    — resolving late (or never) cannot change any decision, so pending
    ticks can be held across subsequent dispatches to double-buffer the
    serve loop.
    """

    off_p: jax.Array  # padded (bucket,) offload decisions, on device
    adm_p: jax.Array  # padded (bucket,) admitted decisions, on device
    n_reports: int  # R — the unpadded wave size
    bucket: int  # padded wave bucket this tick compiled under
    first_compile: bool  # True when this dispatch compiled its bucket
    dispatched_at: float  # perf_counter at dispatch end (EMA bookkeeping)

    def resolve(self) -> Tuple[np.ndarray, np.ndarray]:
        """Block until the decisions are materialized; returns
        (offload, admitted) bool arrays aligned with the wave's idx."""
        off = np.asarray(self.off_p)[: self.n_reports]
        adm = np.asarray(self.adm_p)[: self.n_reports]
        return off, adm


class GatewayCore:
    """The gateway's synchronous algorithm surface (one tick = one slot).

    Args:
      space: the pool-calibrated :class:`~repro.core.state_space.StateSpace`
        behind the value tables — reports are quantized with the same
        fused kernel as the batch lowering.
      tables/params/rule: the fleet-engine contract pieces
        (``CompiledService`` / ``StreamingService`` carry them; see
        :meth:`for_service`).
      num_devices: fleet size N (decisions are fleet-shaped internally).
      topology: optional multi-cloudlet :class:`Topology` — K-vector
        duals (K > 1) and per-cloudlet admission, same semantics as
        ``fleet.simulate(topology=...)``.  A time-varying association is
        indexed by the gateway's own slot counter.
      buckets: wave-size buckets (default :func:`default_buckets`).
      mesh / device_axis: optional device mesh — the persistent state
        (lam, rho counts) is placed sharded over ``device_axis`` so the
        jitted tick runs SPMD; decisions are unchanged.
      enforce_slot_capacity: apply per-slot cloudlet admission to the
        offload decisions (the live cloudlet's semantics; default True).
      est_alpha: EMA factor for the per-bucket tick-latency estimate
        driving the SLO check in :class:`LiveGateway`.
    """

    def __init__(self, space, tables, params: OnAlgoParams, rule: StepRule,
                 num_devices: int, *, topology: Optional[Topology] = None,
                 buckets=None, mesh=None, device_axis: str = "data",
                 enforce_slot_capacity: bool = True,
                 est_alpha: float = 0.25):
        self.space = space
        self.tables = tables
        self.params = params
        self.rule = rule
        self.N = int(num_devices)
        self.M = int(tables[0].shape[-1])
        self.topology = topology
        self.enforce_slot_capacity = bool(enforce_slot_capacity)
        self.buckets = WaveBuckets(tuple(buckets) if buckets is not None
                                   else default_buckets(self.N))
        if self.buckets.buckets[-1] < self.N:
            raise ValueError("largest bucket must cover the fleet "
                             f"({self.buckets.buckets[-1]} < N={self.N})")
        self._topo_k = (topology if topology is not None and topology.K > 1
                        else None)
        if topology is not None:
            if topology.assoc.shape[-1] != self.N:
                raise ValueError(
                    f"topology association covers {topology.assoc.shape[-1]}"
                    f" devices, gateway serves N={self.N}")
            # full validation (H_k shape, id range) at construction — the
            # tick would otherwise silently drop out-of-range load
            validate_topology(topology, 0, self.N)
            if topology.streaming:
                # a streaming walk is never materialized: _slot_assoc
                # regenerates one ROW_BLOCK-aligned block at a time and
                # serves slots out of the cached block
                self._assoc_np = None
                self._assoc_blk = None
                self._assoc_b0 = -1
            else:
                self._assoc_np = np.asarray(topology.assoc, np.int32)
        self.slots = 0  # host-side slot counter (== state.rho.t)
        self.stats = GatewayCoreStats()
        # Two-component latency model, per bucket: dispatch (host pad +
        # enqueue, measured sync-free inside tick_async) and resolve
        # (device execution + transfer, measured as the *marginal* busy
        # time when pending ticks are resolved in dispatch order).  The
        # split is what lets the pipelined serve loop price device work
        # already in flight into an SLO decision.
        self._est_dispatch_ms: dict = {}
        self._est_resolve_ms: dict = {}
        self._est_alpha = float(est_alpha)
        self._last_resolved_at = float("-inf")
        self._mesh = None if mesh is None else auto_axes(mesh)
        self._device_axis = device_axis
        self._state = onalgo.init_state(
            self.N, self.M, K=None if self._topo_k is None else topology.K)
        if self._mesh is not None:
            self._state = _shard_state(self._state, self._mesh, device_axis)
        self._tick_fn = jax.jit(
            make_tick(self.N, space, topo_duals=self._topo_k is not None,
                      admit_topo=topology is not None,
                      enforce=self.enforce_slot_capacity),
            donate_argnums=(0,))

    @classmethod
    def for_service(cls, service, **kw) -> "GatewayCore":
        """Build a core from a ``CompiledService`` / ``StreamingService``
        (both carry space/tables/params/rule + the fleet size)."""
        return cls(service.space, service.tables, service.params,
                   service.rule, service.sim.num_devices, **kw)

    @classmethod
    def for_sim(cls, sim, pool, *, gain_source=None, **kw) -> "GatewayCore":
        """Build a core straight from (SimConfig, pool) under any
        :class:`~repro.gain.GainSource` — the gateway analogue of
        ``simulate_service(gain_source=...)``.  The source resolves at
        compile time into the space/tables the tick consumes; table and
        overlay sources keep the live decision stream bit-identical to
        the batch engines' replay."""
        from repro.serve.compile import compile_service_streaming
        service = compile_service_streaming(sim, pool,
                                            gain_source=gain_source)
        return cls.for_service(service, **kw)

    # ------------------------------------------------------------------
    def _slot_assoc(self):
        """(assoc, H_k) device args for the current slot (None without a
        topology; a time-varying map is indexed by the slot counter)."""
        if self.topology is None:
            return None, None
        if self.topology.time_varying:
            horizon = self.topology.assoc.shape[0]
            if self.slots >= horizon:
                raise ValueError(
                    f"time-varying association covers {horizon} slots, "
                    f"gateway is at slot {self.slots}")
            if self.topology.streaming:
                from repro.workload.streams import ROW_BLOCK
                b0 = self.slots // ROW_BLOCK
                if b0 != self._assoc_b0:
                    L = min(ROW_BLOCK, horizon - b0 * ROW_BLOCK)
                    self._assoc_blk = np.asarray(
                        self.topology.assoc.slab(b0 * ROW_BLOCK, L))
                    self._assoc_b0 = b0
                return (self._assoc_blk[self.slots - b0 * ROW_BLOCK],
                        self.topology.H_k)
            return self._assoc_np[self.slots], self.topology.H_k
        return self.topology.assoc, self.topology.H_k

    # ------------------------------------------------------------------
    def tick_async(self, idx, o, h, w) -> "PendingTick":
        """Dispatch one OnAlgo slot WITHOUT waiting for its decisions.

        Same wave contract as :meth:`tick`, but returns a
        :class:`PendingTick` immediately after enqueueing the jitted
        slot: the persistent state advances on device (its buffers are
        donated to the launch), the decision arrays stay device-resident
        until ``resolve()`` is called, and no host sync happens here.
        That makes the gateway pipelineable — dispatch slot t+1 while
        slot t's decisions are still in flight — reusing the streaming
        engines' donated-carry contract.

        The host-side dispatch cost (pad + enqueue, no sync forced)
        feeds the per-bucket *dispatch* EMA on warm ticks; the *resolve*
        EMA is fed only by :meth:`resolve_timed` / :meth:`tick`, never
        by a bare ``PendingTick.resolve()``.

        Backend note: on runtimes where a donated-buffer launch executes
        synchronously (the CPU client), this call carries the device
        wait itself — the dispatch EMA then absorbs the execution time
        and the resolve EMA measures only the materialize copy, so the
        two-component estimate still sums to the true wall time.
        Pipelining pays either way: the serve loop pre-stages wave
        t+1's host work (drain, SLO check, pad) while wave t's dispatch
        call blocks in the executor.
        """
        t_start = time.perf_counter()
        idx = np.asarray(idx, np.int32).reshape(-1)
        R = idx.shape[0]
        if R > self.N:
            raise ValueError(f"wave of {R} reports exceeds fleet N={self.N}")
        bucket = self.buckets.bucket_len(R)
        idx_p = np.full((bucket,), self.N, np.int32)
        idx_p[:R] = idx
        pad = np.zeros((bucket,), np.float32)

        def pad_vals(x):
            out = pad.copy()
            out[:R] = np.asarray(x, np.float32).reshape(-1)
            return out

        assoc, H_k = self._slot_assoc()
        self._state, off_p, adm_p = self._tick_fn(
            self._state, self.tables, self.params, self.rule, idx_p,
            pad_vals(o), pad_vals(h), pad_vals(w), assoc, H_k)
        first = bucket not in self.stats.compiled_buckets
        self.stats.compiled_buckets.add(bucket)
        self.slots += 1
        self.stats.ticks += 1
        self.stats.reports += R
        dispatched_at = time.perf_counter()
        if not first:
            self._ema(self._est_dispatch_ms, bucket,
                      (dispatched_at - t_start) * 1e3)
        return PendingTick(off_p=off_p, adm_p=adm_p, n_reports=R,
                           bucket=bucket, first_compile=first,
                           dispatched_at=dispatched_at)

    def resolve_timed(self, pending: PendingTick
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """Materialize a pending tick's decisions and feed the
        per-bucket *resolve* EMA (warm ticks only — compiles don't
        vote).

        The resolve component is measured as the tick's MARGINAL device
        busy time: from the later of its dispatch and the previous
        resolve's completion, to its own completion.  Under pipelined
        overlap the device serializes in-flight ticks, so this charges
        each wave only its own execution, not the queue wait behind
        earlier waves.  FIFO contract: pending ticks must be resolved
        in dispatch order for the marginal timing to hold (the serve
        loop and :meth:`tick` both do).
        """
        off, adm = pending.resolve()  # forces the device sync
        done = time.perf_counter()
        start = max(pending.dispatched_at, self._last_resolved_at)
        self._last_resolved_at = done
        if not pending.first_compile:
            self._ema(self._est_resolve_ms, pending.bucket,
                      (done - start) * 1e3)
        return off, adm

    def tick(self, idx, o, h, w) -> Tuple[np.ndarray, np.ndarray]:
        """One OnAlgo slot over a wave of device reports.

        idx: (R,) int32 device ids (each at most once); o/h/w: (R,)
        float32 raw observed values.  R = 0 is a valid (empty) slot —
        rho and the duals still advance, like a no-arrival slot in the
        batch replay.  Returns (offload, admitted) bool arrays aligned
        with ``idx``; blocks until the decisions are materialized, and
        feeds both per-bucket latency EMAs (warm ticks only).
        """
        return self.resolve_timed(self.tick_async(idx, o, h, w))

    # ------------------------------------------------------------------
    def _ema(self, table: dict, bucket: int, dt_ms: float) -> None:
        prev = table.get(bucket)
        table[bucket] = (dt_ms if prev is None else
                         prev + self._est_alpha * (dt_ms - prev))

    def _bucket_est(self, table: dict, bucket: int) -> float:
        """Bucket's EMA; conservative fallback to the worst known
        bucket; 0 when nothing is known yet."""
        est = table.get(bucket)
        if est is not None:
            return est
        return max(table.values(), default=0.0)

    def bucket_len(self, n_reports: int) -> int:
        return self.buckets.bucket_len(n_reports)

    def estimate_ms(self, n_reports: int,
                    in_flight_ms: float = 0.0) -> float:
        """Estimated arrival-to-decisions wall-time for a wave of
        ``n_reports`` dispatched now: its dispatch estimate + its
        resolve estimate + ``in_flight_ms`` of device work already
        dispatched ahead of it (the pipelined serve loop passes the
        summed resolve estimates of the waves in flight)."""
        bucket = self.buckets.bucket_len(n_reports)
        return (self._bucket_est(self._est_dispatch_ms, bucket)
                + self._bucket_est(self._est_resolve_ms, bucket)
                + float(in_flight_ms))

    def estimate_resolve_ms(self, n_reports: int) -> float:
        """The resolve (device) component alone — what a wave queued
        behind this one will wait on."""
        return self._bucket_est(self._est_resolve_ms,
                                self.buckets.bucket_len(n_reports))

    def seed_estimate(self, n_reports: int, ms: float,
                      dispatch_ms: float = 0.0) -> None:
        """Preset the latency estimate for a bucket (operational
        warm-start, or fault injection in the SLO tests).  ``ms`` seeds
        the resolve component; the dispatch component defaults to 0 so
        ``estimate_ms`` returns ``ms`` exactly."""
        bucket = self.buckets.bucket_len(n_reports)
        self._est_resolve_ms[bucket] = float(ms)
        self._est_dispatch_ms[bucket] = float(dispatch_ms)

    def seed_from_trajectory(self, path, config: Optional[str] = None
                             ) -> float:
        """Bulk :meth:`seed_estimate`: warm-start every bucket's resolve
        EMA from a committed ``BENCH_gateway.json`` row, so a cold
        gateway doesn't serve its first waves with ``estimate_ms == 0``
        (an estimate of 0 can never trip the SLO check, however slow
        the tick actually is).

        Picks the latest gateway row whose fleet size (parsed from its
        ``N<n>`` config) is nearest to this core's N — or exactly
        ``config`` when given — and seeds its ``p50_ms`` into every
        bucket that has no live estimate yet (measured EMAs are never
        clobbered).  Returns the seeded milliseconds.
        """
        with open(path) as f:
            rows = json.load(f)
        rows = [r for r in rows if r.get("bench") == "gateway"
                and r.get("p50_ms") is not None]
        if config is not None:
            rows = [r for r in rows if r.get("config") == config]
        else:
            sized = []
            for r in rows:
                m = re.match(r"N(\d+)", r.get("config", ""))
                if m:
                    sized.append((abs(np.log(int(m.group(1)) / self.N)), r))
            if sized:
                best = min(d for d, _ in sized)
                rows = [r for d, r in sized if d == best]
        if not rows:
            raise ValueError(f"no gateway row with a p50_ms in {path!r}"
                             + (f" for config {config!r}" if config
                                else ""))
        ms = float(rows[-1]["p50_ms"])  # the trajectory's newest point
        for bucket in self.buckets.buckets:
            self._est_resolve_ms.setdefault(bucket, ms)
        return ms

    def warmup(self, n_reports=None, buckets=None, *,
               background: bool = False):
        """Precompile the tick's bucket ladder off the serve path.

        Runs one tick per target bucket against a THROWAWAY state (same
        shapes, dtypes, and sharding as the persistent one, so the jit
        cache is hit by real ticks) — the core's state, slot counter,
        and latency EMAs are untouched, but the buckets are marked
        compiled, so the first real wave per bucket is a warm tick: it
        neither stalls behind XLA nor pollutes the EMAs, and compile
        stalls stop masquerading as SLO violations.

        ``n_reports`` (an int or iterable of expected wave sizes) or
        ``buckets`` (explicit sizes) narrow the target set; default is
        the whole ladder.  ``background=True`` runs the compiles in a
        daemon thread and returns it (join it, or just start serving —
        JAX serializes compiles safely); otherwise returns the list of
        bucket sizes compiled.
        """
        if n_reports is not None and buckets is not None:
            raise ValueError("pass n_reports or buckets, not both")
        if background:
            th = threading.Thread(
                target=self.warmup, daemon=True,
                kwargs=dict(n_reports=n_reports, buckets=buckets))
            th.start()
            return th
        sizes = (self.buckets.buckets if n_reports is None
                 and buckets is None else
                 np.atleast_1d(n_reports if buckets is None else buckets))
        targets = sorted({self.buckets.bucket_len(int(s)) for s in sizes})
        if not targets:
            return targets
        state = onalgo.init_state(
            self.N, self.M,
            K=None if self._topo_k is None else self.topology.K)
        if self._mesh is not None:
            state = _shard_state(state, self._mesh, self._device_axis)
        assoc, H_k = self._slot_assoc()
        for bucket in targets:
            idx_p = np.full((bucket,), self.N, np.int32)  # all-pad wave
            z = np.zeros((bucket,), np.float32)
            state, _, adm = self._tick_fn(state, self.tables, self.params,
                                          self.rule, idx_p, z, z, z,
                                          assoc, H_k)
            self.stats.compiled_buckets.add(bucket)
        jax.block_until_ready(adm)  # compiles done before we return
        return targets

    @property
    def mu(self) -> np.ndarray:
        """Current capacity dual(s) — () scalar or (K,). Syncs."""
        return np.asarray(self._state.mu)

    @property
    def state(self):
        """The persistent OnAlgoState (duals + rho). Treat as read-only:
        its buffers are donated to the next tick."""
        return self._state


def _shard_state(state, mesh, device_axis: str):
    """Place the persistent state on a mesh: per-device buffers sharded
    over ``device_axis``, the K-vector/scalar dual and the slot counter
    replicated — the tick then runs SPMD under jit."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    dev = NamedSharding(mesh, P(device_axis))
    dev2 = NamedSharding(mesh, P(device_axis, None))
    rep = NamedSharding(mesh, P())
    rho = state.rho
    return onalgo.OnAlgoState(
        lam=jax.device_put(state.lam, dev),
        mu=jax.device_put(state.mu, rep),
        rho=type(rho)(counts=jax.device_put(rho.counts, dev2),
                      t=jax.device_put(rho.t, rep)))


# ----------------------------------------------------------------------
#  Async host loop
# ----------------------------------------------------------------------

@dataclasses.dataclass
class WaveReply:
    """Per-chunk decision reply.

    ``fallback`` marks graceful degradation: the chunk was answered with
    local execution (offload nobody) because the queue was full or the
    wave would have missed its latency deadline; ``t`` is then -1 and no
    algorithm state was touched.
    """

    t: int  # gateway slot that decided this chunk (-1: fallback)
    offload: np.ndarray
    admitted: np.ndarray
    fallback: bool
    latency_ms: float


class LatencyReservoir:
    """Fixed-size uniform sample of a latency stream (Vitter's
    Algorithm R): O(capacity) memory however long the soak, every
    appended value equally likely to be retained, so ``percentile()``
    stays within sampling error of the exact stream percentile.
    Deterministically seeded — soak runs are reproducible.  ``len()``
    is the TOTAL number of latencies recorded, not the sample size.
    """

    __slots__ = ("capacity", "count", "_size", "_buf", "_rng")

    def __init__(self, capacity: int = 4096, seed: int = 0x5EED):
        if capacity < 2:
            raise ValueError(f"capacity must be >= 2, got {capacity}")
        self.capacity = int(capacity)
        self.count = 0  # total appended
        self._size = 0  # retained (== min(count, capacity))
        self._buf = np.empty((self.capacity,), np.float64)
        self._rng = np.random.RandomState(seed)

    def append(self, ms: float) -> None:
        if self._size < self.capacity:
            self._buf[self._size] = ms
            self._size += 1
        else:
            j = self._rng.randint(0, self.count + 1)
            if j < self.capacity:
                self._buf[j] = ms
        self.count += 1

    def __len__(self) -> int:
        return self.count

    def __bool__(self) -> bool:
        return self.count > 0

    def sample(self) -> np.ndarray:
        """The retained sample (a copy)."""
        return self._buf[: self._size].copy()

    def percentile(self, q: float) -> float:
        if not self._size:
            return float("nan")
        return float(np.percentile(self._buf[: self._size], q))


@dataclasses.dataclass
class GatewayStats:
    waves: int = 0
    chunks: int = 0
    reports: int = 0
    fallback_waves: int = 0
    shed_chunks: int = 0
    max_queue_seen: int = 0
    # pipeline occupancy, sampled at dispatch entry: the deepest
    # dispatch-to-resolve backlog seen, and how many waves entered
    # dispatch while an earlier wave was still unresolved
    max_in_flight_seen: int = 0
    overlapped_waves: int = 0
    latencies_ms: LatencyReservoir = dataclasses.field(
        default_factory=LatencyReservoir)

    def percentile(self, q: float) -> float:
        return self.latencies_ms.percentile(q)

    def summary(self) -> dict:
        return {
            "waves": self.waves,
            "chunks": self.chunks,
            "reports": self.reports,
            "fallback_waves": self.fallback_waves,
            "shed_chunks": self.shed_chunks,
            "max_queue_seen": self.max_queue_seen,
            "max_in_flight_seen": self.max_in_flight_seen,
            "overlapped_waves": self.overlapped_waves,
            "latency_count": len(self.latencies_ms),
            "p50_ms": self.percentile(50.0),
            "p99_ms": self.percentile(99.0),
        }


class _Chunk:
    __slots__ = ("idx", "o", "h", "w", "fut", "t_arrival")

    def __init__(self, idx, o, h, w, fut, t_arrival):
        self.idx, self.o, self.h, self.w = idx, o, h, w
        self.fut, self.t_arrival = fut, t_arrival


class _InFlight:
    """One dispatched wave riding the pipeline, awaiting resolution."""

    __slots__ = ("pending", "chunks", "n", "slot", "resolve_est_ms")

    def __init__(self, pending, chunks, n, slot, resolve_est_ms):
        self.pending, self.chunks, self.n = pending, chunks, n
        self.slot, self.resolve_est_ms = slot, resolve_est_ms


class LiveGateway:
    """Async serving loop around a :class:`GatewayCore` — a
    depth-bounded wave pipeline.

    Submitted chunks queue (bounded by ``max_queue``); the dispatcher
    drains queued chunks into one wave — one OnAlgo slot — dispatches
    it via :meth:`GatewayCore.tick_async`, and immediately goes back to
    forming the next wave while a resolver task materializes in-flight
    decisions in dispatch order and completes each chunk's future with
    its slice.  At most ``max_in_flight`` waves sit between dispatch
    and resolution (default 2: wave t+1's host work overlaps wave t's
    device work; ``1`` is the strictly sequential loop).  Dispatch
    order is the slot order, so the decision stream is identical at
    every depth.

    SLO semantics: if the latency estimate — dispatch + the resolve
    backlog already in flight + the wave's own resolve — says the wave
    would finish past ``earliest_arrival + slo_ms``, every chunk in it
    gets a local-execution fallback reply instead of being dispatched
    (bounded staleness beats a missed deadline; nothing reaches the
    algorithm state, so waves already in flight and waves dispatched
    after are untouched); a full queue sheds new chunks the same way at
    submit time.

    ``coalesce=False`` disables micro-batch merging — every chunk is
    its own wave/slot.  That is the closed-loop replay contract: a
    pipelined run over one-chunk-per-slot submissions stays
    bit-identical to the batch engines at any depth.

    Use as ``async with LiveGateway(core) as gw: ...`` or call
    :meth:`start` / :meth:`stop` explicitly.
    """

    def __init__(self, core: GatewayCore, *, slo_ms: float = 50.0,
                 max_queue: int = 64, max_wave: Optional[int] = None,
                 max_in_flight: int = 2, coalesce: bool = True,
                 clock=time.monotonic):
        if max_in_flight < 1:
            raise ValueError(f"max_in_flight must be >= 1, "
                             f"got {max_in_flight}")
        self.core = core
        self.slo_ms = float(slo_ms)
        self.max_queue = int(max_queue)
        self.max_wave = int(max_wave) if max_wave is not None else core.N
        self.max_in_flight = int(max_in_flight)
        self.coalesce = bool(coalesce)
        self.stats = GatewayStats()
        self._clock = clock
        self._chunks: deque = deque()
        self._in_flight: deque = deque()
        self._wakeup: Optional[asyncio.Event] = None
        self._pipe: Optional[asyncio.Queue] = None
        self._slots_free: Optional[asyncio.Semaphore] = None
        self._task = None
        self._resolver = None
        self._closing = False

    async def __aenter__(self) -> "LiveGateway":
        self.start()
        return self

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    def start(self) -> None:
        if self._task is not None:
            raise RuntimeError("gateway already started")
        self._closing = False
        self._wakeup = asyncio.Event()
        self._pipe = asyncio.Queue()
        self._slots_free = asyncio.Semaphore(self.max_in_flight)
        loop = asyncio.get_running_loop()
        self._resolver = loop.create_task(self._resolve_loop())
        self._task = loop.create_task(self._serve())

    async def stop(self) -> None:
        """Drain the queue and the in-flight pipe, then stop."""
        self._closing = True
        self._wakeup.set()
        await self._task
        self._pipe.put_nowait(None)  # after the last dispatched wave
        await self._resolver
        self._task = self._resolver = None

    def _fallback_reply(self, n: int, t_arrival: float) -> WaveReply:
        zeros = np.zeros((n,), bool)
        return WaveReply(t=-1, offload=zeros, admitted=zeros.copy(),
                         fallback=True,
                         latency_ms=(self._clock() - t_arrival) * 1e3)

    async def submit(self, idx, o, h, w) -> WaveReply:
        """Submit one chunk of device reports; resolves with its slice
        of the wave's decisions (or a fallback reply under overload).
        An empty chunk is valid and still drives a slot tick."""
        if self._task is None:
            raise RuntimeError("gateway not started")
        now = self._clock()
        if len(self._chunks) >= self.max_queue:
            self.stats.shed_chunks += 1
            return self._fallback_reply(len(np.atleast_1d(idx)), now)
        fut = asyncio.get_running_loop().create_future()
        self._chunks.append(_Chunk(np.asarray(idx, np.int32).reshape(-1),
                                   o, h, w, fut, now))
        self.stats.max_queue_seen = max(self.stats.max_queue_seen,
                                        len(self._chunks))
        self._wakeup.set()
        return await fut

    async def _serve(self) -> None:
        """Dispatcher half of the pipeline: drain -> SLO check ->
        dispatch.  Never waits on a wave's decisions — only on a free
        pipe slot."""
        loop = asyncio.get_running_loop()
        while True:
            if not self._chunks:
                if self._closing:
                    return
                self._wakeup.clear()
                if self._chunks or self._closing:
                    continue  # raced with submit()/stop()
                await self._wakeup.wait()
                continue
            # depth bound: wait until fewer than max_in_flight waves
            # sit between dispatch and resolution (chunks arriving
            # meanwhile coalesce into a bigger wave below)
            await self._slots_free.acquire()
            # micro-batch: every queued chunk joins this wave (slot),
            # capped at max_wave reports
            wave = [self._chunks.popleft()]
            n = wave[0].idx.shape[0]
            if self.coalesce:
                while (self._chunks and
                       n + self._chunks[0].idx.shape[0] <= self.max_wave):
                    c = self._chunks.popleft()
                    wave.append(c)
                    n += c.idx.shape[0]
            earliest = min(c.t_arrival for c in wave)
            backlog_ms = sum(r.resolve_est_ms for r in self._in_flight)
            est_s = self.core.estimate_ms(n, in_flight_ms=backlog_ms) / 1e3
            if self._clock() + est_s > earliest + self.slo_ms / 1e3:
                # fallback BEFORE dispatch: the algorithm state is
                # untouched even with waves queued behind this one
                for c in wave:
                    c.fut.set_result(
                        self._fallback_reply(c.idx.shape[0], c.t_arrival))
                self.stats.fallback_waves += 1
                self.stats.chunks += len(wave)
                self._slots_free.release()  # nothing entered the pipe
                continue
            idx = np.concatenate([c.idx for c in wave])
            o = np.concatenate([np.asarray(c.o, np.float32).reshape(-1)
                                for c in wave])
            h = np.concatenate([np.asarray(c.h, np.float32).reshape(-1)
                                for c in wave])
            w = np.concatenate([np.asarray(c.w, np.float32).reshape(-1)
                                for c in wave])
            slot = self.core.slots
            # occupancy is sampled at dispatch ENTRY: this wave starts
            # dispatching with len(_in_flight) predecessors unresolved.
            # (Sampling after the dispatch returns would undercount on
            # backends where the donated tick executes synchronously —
            # the predecessor resolves during the call.)
            depth = len(self._in_flight) + 1
            self.stats.max_in_flight_seen = max(
                self.stats.max_in_flight_seen, depth)
            if depth > 1:
                self.stats.overlapped_waves += 1
            # dispatch in the default executor so submitters keep
            # enqueueing (that's what forms the next micro-batch); the
            # await also serializes dispatches — the state-donation
            # contract of tick_async
            pending = await loop.run_in_executor(
                None, self.core.tick_async, idx, o, h, w)
            rec = _InFlight(pending, wave, n, slot,
                            self.core.estimate_resolve_ms(n))
            self._in_flight.append(rec)
            self._pipe.put_nowait(rec)

    async def _resolve_loop(self) -> None:
        """Resolver half: materialize in-flight waves in dispatch order
        and complete their chunk futures.  Runs concurrently with the
        dispatcher — wave t+1's host work overlaps wave t's resolve."""
        loop = asyncio.get_running_loop()
        while True:
            rec = await self._pipe.get()
            if rec is None:
                return
            off, adm = await loop.run_in_executor(
                None, self.core.resolve_timed, rec.pending)
            self._in_flight.popleft()  # rec — the pipe is FIFO
            self._slots_free.release()
            done = self._clock()
            self.stats.waves += 1
            self.stats.chunks += len(rec.chunks)
            self.stats.reports += int(rec.n)
            lo = 0
            for c in rec.chunks:
                hi = lo + c.idx.shape[0]
                lat = (done - c.t_arrival) * 1e3
                self.stats.latencies_ms.append(lat)
                c.fut.set_result(WaveReply(
                    t=rec.slot, offload=off[lo:hi], admitted=adm[lo:hi],
                    fallback=False, latency_ms=lat))
                lo = hi


async def drive_closed_loop(gateway: LiveGateway, loadgen, t0: int = 0,
                            slots: Optional[int] = None) -> list:
    """Closed-loop driver: submit one workload slot's wave, await its
    decisions, advance — each gateway wave is exactly one workload slot,
    so the decision stream replays ``fleet.simulate`` bit for bit."""
    replies = []
    for wv in loadgen.waves(t0, slots):
        replies.append(await gateway.submit(wv.idx, wv.o, wv.h, wv.w))
    return replies


def run_closed_loop(core: GatewayCore, loadgen, t0: int = 0,
                    slots: Optional[int] = None, warmup: bool = False,
                    **gateway_kw):
    """Convenience sync wrapper: serve a closed-loop replay of
    ``loadgen`` through a fresh :class:`LiveGateway`; returns
    (replies, stats).  ``warmup=True`` precompiles the core's bucket
    ladder (:meth:`GatewayCore.warmup`) before the loop starts, so no
    wave ever waits on XLA."""
    if warmup:
        core.warmup()

    async def _run():
        async with LiveGateway(core, **gateway_kw) as gw:
            replies = await drive_closed_loop(gw, loadgen, t0, slots)
            return replies, gw.stats

    return asyncio.run(_run())


async def drive_pipelined_loop(gateway: LiveGateway, loadgen,
                               t0: int = 0,
                               slots: Optional[int] = None,
                               window: Optional[int] = None) -> list:
    """Pipelined driver: keep up to ``window`` slot-waves outstanding
    (submitted, decisions not yet returned) instead of awaiting each
    reply — the submission pattern that actually fills the gateway's
    dispatch/resolve pipeline.  ``window`` defaults to the gateway's
    ``max_in_flight`` + 1 (one wave queued, ``max_in_flight`` in the
    pipe).  Submission order is the slot order; with a
    ``coalesce=False`` gateway each wave is exactly one workload slot,
    so the decision stream replays ``fleet.simulate`` bit for bit at
    any depth.  Returns replies in slot order.
    """
    loop = asyncio.get_running_loop()
    window = (gateway.max_in_flight + 1 if window is None
              else int(window))
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    gate = asyncio.Semaphore(window)
    replies: dict = {}
    tasks = []

    async def _one(i, wv):
        try:
            replies[i] = await gateway.submit(wv.idx, wv.o, wv.h, wv.w)
        finally:
            gate.release()

    for i, wv in enumerate(loadgen.waves(t0, slots)):
        await gate.acquire()
        tasks.append(loop.create_task(_one(i, wv)))
    await asyncio.gather(*tasks)
    return [replies[i] for i in range(len(tasks))]


def run_pipelined_loop(core: GatewayCore, loadgen, t0: int = 0,
                       slots: Optional[int] = None,
                       window: Optional[int] = None,
                       warmup: bool = False, **gateway_kw):
    """Convenience sync wrapper around :func:`drive_pipelined_loop`;
    returns (replies, stats).  The gateway defaults to
    ``coalesce=False`` so every wave stays one workload slot — the
    bit-identical-replay contract — and ``warmup=True`` precompiles
    the bucket ladder before serving starts."""
    gateway_kw.setdefault("coalesce", False)
    if warmup:
        core.warmup()

    async def _run():
        async with LiveGateway(core, **gateway_kw) as gw:
            replies = await drive_pipelined_loop(gw, loadgen, t0, slots,
                                                 window)
            return replies, gw.stats

    return asyncio.run(_run())


async def drive_open_loop(gateway: LiveGateway, loadgen, rate_hz: float,
                          t0: int = 0,
                          slots: Optional[int] = None) -> list:
    """Open-loop driver: submit one workload slot's wave every
    ``1 / rate_hz`` seconds WITHOUT awaiting the previous decision —
    devices report on their own clocks, oblivious to gateway backlog.

    Below saturation this behaves like the closed loop with idle gaps;
    past it the queue grows, slot-waves merge into bigger micro-batches,
    and the SLO machinery sheds load (fallback waves / shed chunks)
    instead of the wall clock stretching — sweep ``rate_hz`` to find the
    saturation knee.  Replies resolve concurrently; the returned list is
    in submission order.
    """
    loop = asyncio.get_running_loop()
    period = 1.0 / float(rate_hz)
    tasks = []
    next_t = loop.time()
    for wv in loadgen.waves(t0, slots):
        now = loop.time()
        if now < next_t:
            await asyncio.sleep(next_t - now)
        next_t += period
        tasks.append(asyncio.ensure_future(
            gateway.submit(wv.idx, wv.o, wv.h, wv.w)))
    return list(await asyncio.gather(*tasks))


def run_open_loop(core: GatewayCore, loadgen, rate_hz: float, t0: int = 0,
                  slots: Optional[int] = None, warmup: bool = False,
                  **gateway_kw):
    """Convenience sync wrapper around :func:`drive_open_loop`; returns
    (replies, stats).  ``warmup=True`` precompiles the bucket ladder
    before the loop starts."""
    if warmup:
        core.warmup()

    async def _run():
        async with LiveGateway(core, **gateway_kw) as gw:
            replies = await drive_open_loop(gw, loadgen, rate_hz, t0,
                                            slots)
            return replies, gw.stats

    return asyncio.run(_run())
