"""Traffic kind ``gateway_open``: the live gateway under an open loop.

Set-up makes every wave the run can send, from the seed, with the
benchmark's own generator (``bench/reference``: the counter-addressed
workload the system's RNG contract states), cut on the host into one
wave per slot: the ids of the devices whose arrival chain fired and
their raw (o, h, w).  It builds a ``GatewayCore`` from the
configuration's state space, value tables and constants, warms the wave
buckets those waves fall in, and serves the first ``warm_waves`` waves
at the cell's rate.

The window offers one wave per slot at ``rate_hz`` to a ``LiveGateway``
(``max_in_flight`` waves in its pipeline, ``coalesce=False``: a wave is
one slot), on a fixed schedule that does not wait for replies.  Each
wave's latency runs from when it was due to when its decisions are on
the host; a wave that falls back or is shed counts as failed and ranks
above every served wave.  Where failed waves hold the median's or the
95th percentile's rank, that latency is no number, and ``run.py``
prints no result.  The generator's lateness (submit time less due time)
and where the failures began are printed.

The check replays the same slots through the plain reference, skipping
the slots the gateway did not serve, and compares every decision the
gateway returned.
"""

from __future__ import annotations

import asyncio
import gc
import time

import numpy as np

from bench import harness
from bench.reference.onalgo_ref import BLOCK, Reference


class Runner:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.traffic = ctx.config, ctx.traffic
        self.N = int(self.cfg["num_devices"])
        self.T = int(self.cfg["horizon"])
        if int(self.cfg.get("cloudlets", 1)) != 1:
            raise ValueError("gateway_open drives one cloudlet")
        self.rate = float(self.traffic["rate_hz"])
        self.seed = harness.workload_seed(ctx.seed)
        self.records = []  # every wave offered, in set-up and the window

    # -- set-up -------------------------------------------------------
    def make_waves(self, slots: int):
        """Waves for slots [0, slots): (idx int32, o, h, w float32)."""
        import jax
        waves = []
        for out in Reference(self.cfg, self.traffic).waves(self.seed, slots):
            on, o, h, w = (np.asarray(x) for x in jax.device_get(out))
            for r in range(BLOCK):
                idx = np.flatnonzero(on[r]).astype(np.int32)
                waves.append((idx, o[r][idx], h[r][idx], w[r][idx]))
        return waves[:slots]

    def setup(self):
        import jax.numpy as jnp
        from repro.core.onalgo import OnAlgoParams, StepRule
        from repro.serve.gateway import GatewayCore
        from repro.serve.simulator import pool_space, synthetic_pool

        cfg, N = self.cfg, self.N
        slots = int(self.traffic["window_waves"])
        if slots > self.T:
            raise ValueError("the window's waves exceed the horizon")
        t = time.perf_counter()
        self.waves = self.make_waves(slots)
        harness.log(f"{slots} waves made in {time.perf_counter() - t:.3f} s;"
                    f" mean size {np.mean([w[0].size for w in self.waves]):.1f}")
        # the gateway serves reports; it needs the configuration's state
        # space, value tables and constants, not the workload's lowering
        pool = synthetic_pool(int(cfg["pool_images"]), int(cfg["pool_seed"]))
        space = pool_space(pool, num_w=int(cfg["num_w_levels"]),
                           v_risk=float(cfg["v_risk"]))
        params = OnAlgoParams(
            B=jnp.full((N,), float(cfg["B_n"]), jnp.float32),
            H=jnp.float32(N * float(cfg["H_per_device"])))
        self.core = GatewayCore(space, space.tables(), params,
                                StepRule.inv_sqrt(float(cfg["step_a"])), N)
        warmed = self.core.warmup(n_reports=[w[0].size for w in self.waves])
        # serve the first waves at the cell's rate, so the pipeline's
        # threads and the SLO's latency estimates are warm before the
        # window; their decisions are checked with the window's
        warm = int(self.traffic["warm_waves"])
        self.records = asyncio.run(self._open_loop(0, warm))
        harness.log(f"gateway buckets warmed: {warmed}; {warm} waves served "
                    f"in set-up")

    # -- the window ---------------------------------------------------
    async def _open_loop(self, first: int, n: int):
        """Offer waves [first, first + n) on the schedule; returns one
        record per wave: (slot, due, sent, done, fallback, size, reply)."""
        import jax
        from repro.serve.gateway import LiveGateway
        if first + n > len(self.waves):
            raise ValueError(f"{first + n} waves needed, {len(self.waves)} "
                             "made: raise window_waves")
        gw = LiveGateway(self.core, slo_ms=float(self.traffic["slo_ms"]),
                         max_in_flight=int(self.traffic["max_in_flight"]),
                         coalesce=False)
        loop = asyncio.get_running_loop()
        period = 1.0 / self.rate
        records = []
        span = jax.profiler.TraceAnnotation

        async def one(i, due, wave):
            sent = time.perf_counter()
            rep = await gw.submit(*wave)
            done = time.perf_counter()
            records.append((i, due, sent, done, rep.fallback, wave[0].size,
                            rep))

        gw.start()
        tasks = []
        start = time.perf_counter()
        for k in range(n):
            due = start + k * period
            now = time.perf_counter()
            if now < due:
                with span("bench.wait_due"):
                    await asyncio.sleep(due - now)
            tasks.append(loop.create_task(one(first + k, due,
                                              self.waves[first + k])))
        await asyncio.gather(*tasks)
        await gw.stop()
        self.gw_stats = gw.stats
        return sorted(records, key=lambda r: r[0])

    def _run(self, seconds):
        first = len(self.records)  # the slots served in set-up come first
        records = asyncio.run(self._open_loop(
            first, int(seconds * self.rate) + 1))
        self.records = self.records + records
        lat = np.array([(r[3] - r[1]) * 1e3 for r in records])
        late = np.array([(r[2] - r[1]) * 1e3 for r in records])
        failed = np.array([r[4] for r in records])
        served = ~failed
        lat_lim = np.where(failed, np.inf, lat)
        first_due = records[0][1]
        last_done = max(r[3] for r in records)
        reports = sum(r[5] for r in records if not r[4])
        st = self.gw_stats
        harness.log(
            f"window: {len(records)} waves at {self.rate} Hz, "
            f"{int(failed.sum())} failed (fallback {st.fallback_waves}, "
            f"shed {st.shed_chunks}); generator "
            f"lateness p50 {np.percentile(late, 50):.6f} ms, max "
            f"{late.max():.6f} ms; latency p50 {np.percentile(lat, 50):.6f}"
            f" ms, max {lat.max():.6f} ms")
        bad = np.flatnonzero(failed)
        if bad.size:
            # a run of failures to the window's close, with the estimate
            # still over the SLO, is the gateway no longer dispatching
            est = self.core.estimate_ms(int(np.mean([r[5] for r in records])))
            at = records[bad[0]][1] - first_due
            harness.log(
                f"failed waves: the first due {at:.3f} s into the window, "
                f"{int(served[bad[0]:].sum())} "
                f"served after it; latency estimate at the close {est:.3f} ms"
                f" (SLO {self.traffic['slo_ms']} ms)")
        med = np.median(lat)
        slow = np.flatnonzero(lat > 2 * med)
        harness.log(f"waves slower than twice the median: {slow.size}"
                    + (f", the first due {records[slow[0]][1] - first_due:.3f}"
                       f" s into the window" if slow.size else ""))
        return {
            # nearest rank at or above: a failed wave ranks above all
            "p50": float(np.percentile(lat_lim, 50, method="higher")),
            "p95": float(np.percentile(lat_lim, 95, method="higher")),
            "reports_per_s": reports / (last_done - first_due),
            "lat": lat, "failed": failed, "served": served,
            "attempted": len(records), "n_failed": int(failed.sum()),
        }

    def measure(self, seconds):
        r = self._run(seconds)
        return {"metrics": {"gw_wave_p50_ms": (r["p50"], "ms"),
                            "gw_wave_p95_ms": (r["p95"], "ms"),
                            "gw_reports_per_s": (r["reports_per_s"],
                                                 "reports/s")},
                "attempted": r["attempted"], "failed": r["n_failed"]}

    def traced(self, seconds):
        secs = float(self.traffic["trace_seconds"])
        r = self._run(secs)
        return {"waves": int(r["served"].sum()), "lat_ms": r["lat"],
                "served": r["served"], "attempted": r["attempted"],
                "failed": r["n_failed"]}

    # -- the check ------------------------------------------------------
    def release(self):
        """Keep every served wave's decisions; free the gateway."""
        served = np.zeros((1 + max(r[0] for r in self.records),), bool)
        got = {}
        for i, _, _, _, fb, _, rep in self.records:
            if not fb:
                served[i] = True
                got[i] = (np.asarray(rep.offload), np.asarray(rep.admitted))
        self.got, self.served = got, served
        self.records, self.core = [], None
        gc.collect()

    def check(self):
        return self._compare(Reference(self.cfg, self.traffic,
                                       decisions=True))

    def control(self, dtype):
        """The bfloat16 reference's decisions in the gateway's place."""
        n = int(self.traffic["window_waves"])
        self.waves = self.make_waves(n)
        low = Reference(self.cfg, self.traffic, dtype=dtype, decisions=True)
        self.served = np.ones((n,), bool)
        self.got = self._decisions(low, self.served)
        return self._compare(Reference(self.cfg, self.traffic,
                                       decisions=True))

    def _decisions(self, ref, served):
        """Each served wave's (offload, admitted) at its reports, from
        ``ref`` replayed over the waves' slots."""
        out = {}
        carry = ref.init(self.seed)
        n = len(served)
        pad = np.zeros((-(-n // BLOCK) * BLOCK,), bool)
        pad[:n] = served
        for b in range(len(pad) // BLOCK):
            carry, o = ref.block(carry, self.seed, b,
                                 pad[b * BLOCK:(b + 1) * BLOCK])
            off = np.asarray(o["offload_mask"])
            adm = np.asarray(o["admit_mask"])
            for r in range(BLOCK):
                i = b * BLOCK + r
                if i < n and served[i]:
                    idx = self.waves[i][0]
                    out[i] = (off[r][idx], adm[r][idx])
        return out

    def _compare(self, ref):
        want = self._decisions(ref, self.served)
        reports = max(sum(want[i][0].size for i in want), 1)
        return {
            "offload_err": sum(int(np.sum(self.got[i][0] != want[i][0]))
                               for i in want) / reports,
            "admit_err": sum(int(np.sum(self.got[i][1] != want[i][1]))
                             for i in want) / reports,
        }
