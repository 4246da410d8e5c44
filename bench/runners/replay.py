"""Traffic kind ``replay``: the streaming chunked engine over one horizon.

Set-up lowers the configuration's service once (``compile_service_
streaming``: workload boundary states, the pool's device tables), builds
the mobility walk where the configuration has cloudlets, and walks one
piece from slot 0, which compiles the fused slab step.  The window walks
the horizon in pieces of ``piece_slots`` slots through the engine's own
resume (``fleet.simulate_chunked_stream(t0=, state0=)``), one piece kept
in flight behind the one the host waits on; at the horizon's end it
starts again from slot 0 with fresh duals.  Every piece has the same
length and a block-aligned start, so every piece runs the one compiled
slab step.

The check compares the window's first ``check_slots`` slots, from slot
0, with the plain reference (``bench/reference``): the per-slot series
the engine produced and the visit counts at the end of that span.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import harness
from bench.reference.onalgo_ref import Reference


class Runner:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.traffic = ctx.config, ctx.traffic
        self.N = int(self.cfg["num_devices"])
        self.T = int(self.cfg["horizon"])
        self.K = int(self.cfg.get("cloudlets", 1))
        self.P = int(self.traffic["piece_slots"])
        self.check_slots = int(self.traffic["check_slots"])
        eng = self.cfg["engine"]
        if self.T % self.P or self.P % int(eng["slab"]) or (
                self.check_slots % self.P):
            raise ValueError("piece_slots must divide the horizon and "
                             "check_slots, and be whole slabs")
        self.seed = harness.workload_seed(ctx.seed)
        self.kept = {}  # the window's first check_slots slots

    # -- set-up -------------------------------------------------------
    def setup(self):
        import jax
        from repro.core import fleet, onalgo
        from repro.serve.compile import compile_service_streaming
        from repro.serve.simulator import SimConfig, synthetic_pool
        from repro.topology import Topology

        cfg, N = self.cfg, self.N
        H = N * float(cfg["H_per_device"])
        sim = SimConfig(num_devices=N, T=self.T, algo="onalgo",
                        B_n=float(cfg["B_n"]), H=H,
                        v_risk=float(cfg["v_risk"]),
                        burst_len=tuple(self.traffic["burst_len"]),
                        mean_gap=float(self.traffic["mean_gap"]),
                        seed=self.seed, step_a=float(cfg["step_a"]),
                        num_w_levels=int(cfg["num_w_levels"]))
        pool = synthetic_pool(int(cfg["pool_images"]), int(cfg["pool_seed"]))
        ss = compile_service_streaming(sim, pool)
        topo = None
        if self.K > 1:
            topo = Topology.mobility_walk(
                self.K, N, self.T, H=H, p_handover=float(cfg["p_handover"]),
                seed=self.seed, streaming=True)
        eng = cfg["engine"]
        M = self.M = int(ss.tables[0].shape[-1])
        self._fresh = lambda: onalgo.init_state(
            N, M, K=None if self.K == 1 else self.K)

        def piece(t0, state):
            return fleet.simulate_chunked_stream(
                ss.slab, t0 + self.P, N, ss.tables, ss.params, ss.rule,
                chunk=int(eng["chunk"]), slab=int(eng["slab"]),
                enforce_slot_capacity=bool(eng["enforce_slot_capacity"]),
                topology=topo, source_aligned=ss.slab_aligned, t0=t0,
                state0=state)

        self._piece = piece
        harness.log(f"service lowered: N={N} T={self.T} K={self.K} "
                    f"M={M} piece={self.P} slots")
        series, state = piece(0, self._fresh())  # compiles the slab step
        jax.block_until_ready((series, state))
        harness.log("warm piece done")

    # -- the window ---------------------------------------------------
    def _walk(self, seconds):
        """Walk pieces until ``seconds`` have passed; one piece in flight
        behind the one waited on.  Returns (slots decided, elapsed s)."""
        import jax
        t0, state = 0, self._fresh()
        inflight = []
        slots = 0
        start = time.perf_counter()
        while True:
            series, state = self._piece(t0, state)
            inflight.append((t0, series, state))
            t0 = t0 + self.P
            if t0 >= self.T:
                t0, state = 0, self._fresh()
            if len(inflight) < 2:
                continue
            p0, s_done, st_done = inflight.pop(0)
            jax.block_until_ready(st_done.lam)
            slots += self.P
            self._keep(p0, s_done, st_done)
            if time.perf_counter() - start >= seconds:
                break
        for p0, s_done, st_done in inflight:
            jax.block_until_ready(st_done.lam)
            slots += self.P
            self._keep(p0, s_done, st_done)
        return slots, time.perf_counter() - start

    def _keep(self, p0, series, state):
        """Keep the first pass's pieces up to check_slots."""
        if self.kept.get("done") or p0 != len(self.kept.get("series", [])
                                                ) * self.P:
            self.kept["done"] = True
            return
        self.kept.setdefault("series", []).append(series)
        self.kept["state"] = state
        if (p0 + self.P) >= self.check_slots:
            self.kept["done"] = True

    def measure(self, seconds):
        slots, elapsed = self._walk(seconds)
        rate = slots * self.N / elapsed
        harness.log(f"window: {slots} slots x {self.N} devices in "
                    f"{elapsed:.6f} s")
        return {"metrics": {"replay_devslots_per_s":
                            (rate, "devslots/s")},
                "attempted": slots, "failed": 0}

    def traced(self, seconds):
        """A short traced window: ``trace_pieces`` pieces from slot 0,
        all dispatched before the first is waited on."""
        slots, elapsed = self._walk_count(int(self.traffic["trace_pieces"]))
        return {"slots": slots, "states": self.M, "elapsed_s": elapsed,
                "attempted": slots, "failed": 0}

    def _walk_count(self, n):
        """``n`` pieces from slot 0, all dispatched before the first is
        waited on, under host spans the trace attributes idle gaps to."""
        import jax
        span = jax.profiler.TraceAnnotation
        t0, state = 0, self._fresh()
        pend = []
        start = time.perf_counter()
        for _ in range(n):
            with span("bench.dispatch_piece"):
                series, state = self._piece(t0, state)
            pend.append((t0, series, state))
            t0 += self.P
        for p0, s_done, st_done in pend:
            with span("bench.wait_piece"):
                jax.block_until_ready(st_done.lam)
            self._keep(p0, s_done, st_done)
        return n * self.P, time.perf_counter() - start

    def walk_check_span(self):
        """The timed path over the span the check covers, untimed (for
        the readings the limits are set from)."""
        self._walk_count(self.check_slots // self.P)

    # -- the check ------------------------------------------------------
    def release(self):
        """Copy what the check needs to the host and free the program's
        state, so the reference runs on an empty chip."""
        kept = self.kept
        series = {k: np.concatenate([np.asarray(s[k]) for s in
                                     kept["series"]])
                  for k in kept["series"][0]}
        st = kept["state"]
        self.got = {"series": series,
                    "counts": np.asarray(st.rho.counts),
                    "slots": len(kept["series"]) * self.P}
        self.kept = {}
        self._piece = self._fresh = None
        gc.collect()

    def check(self):
        ref = Reference(self.cfg, self.traffic)
        slots = self.got["slots"]
        want, carry = ref.run(self.seed, slots)
        return compare(self.got["series"], self.got["counts"], want,
                       np.asarray(carry[5]), slots)

    def control(self, dtype):
        """The check's numbers with the reference computed in ``dtype``
        put in the program's place."""
        import jax.numpy as jnp
        slots = self.check_slots
        want, carry = Reference(self.cfg, self.traffic,
                                dtype=jnp.float32).run(self.seed, slots)
        want_counts = np.asarray(carry[5])
        del carry
        got, low = Reference(self.cfg, self.traffic, dtype=dtype).run(
            self.seed, slots)
        return compare(got, np.asarray(low[5], np.float32), want,
                       want_counts, slots)


def compare(series, counts, want, want_counts, slots):
    """The numbers ``correct`` is decided by: the engine's per-slot
    series and final visit counts against the reference's."""
    g = {k: np.asarray(v, np.float64)[:slots] for k, v in series.items()}
    r = {k: np.asarray(v, np.float64)[:slots] for k, v in want.items()}
    tasks = np.maximum(r["tasks"], 1.0)

    def rel(k):
        return float(np.max(np.abs(g[k] - r[k])) /
                     max(float(np.max(np.abs(r[k]))), 1e-30))

    out = {
        "tasks_err": float(np.sum(np.abs(g["tasks"] - r["tasks"]))),
        "counts_err": float(np.count_nonzero(
            np.asarray(counts) != np.asarray(want_counts))),
        "decision_err": float(max(
            np.max(np.abs(g[k] - r[k]) / tasks)
            for k in ("offloads", "admits"))),
        "value_err": max(rel(k) for k in ("reward", "power", "load",
                                          "correct")),
        "mu_err": rel("mu"),
        "lam_norm_err": rel("lam_norm"),
    }
    if "mu_k" in r:
        out["mu_k_err"] = rel("mu_k")
    return out
