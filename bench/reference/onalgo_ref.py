"""The plain reference: the service workload and OnAlgo, slot by slot.

A straightforward implementation of the semantics the benchmark checks
the system against, written from the paper (arXiv:2201.02840, Algorithm
1 and Sec. VI) and the RNG contract the system states for its workload.
It imports nothing of the system under test and takes nothing it made:
the image pool, the quantized state space, the counter-addressed random
streams and the mobility walk are all rebuilt here from the seed.

One jitted call advances ``BLOCK`` slots (a ``lax.scan`` over slots):
generate the block's uniforms, run the arrival chain, the held channel
rate and the held association on from the carried state, look up the
raw values, quantize, then for each slot the OnAlgo step (decision under
the duals entering the slot, visit counts, dual ascent) and the
cloudlet's greedy per-slot admission.  The seed is a traced argument, so
every seed runs the same compiled program.

``dtype`` sets the arithmetic of everything that is real-valued: the
uniforms, the raw values, the duals, the state distribution and the
series.  ``float32`` is the configuration's precision; ``bfloat16`` is
the control that the check must refuse.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

BLOCK = 64  # slots per block key of the workload's RNG contract
STREAM_SERVICE = 1  # arrival / image / channel uniforms, 4 channels
STREAM_ARRIVAL_INIT = 2  # initial ON/OFF uniforms
STREAM_TOPOLOGY = 4  # handover / candidate-cloudlet uniforms, 2 channels
RATES_MBPS = np.array([10.0, 25.0, 40.0])  # the testbed's WiFi rates
# Fixed by the system's contract, not by a configuration: its service
# workload keeps a device's channel rate with probability 0.9 a slot
# (``channel_stay``), and its service steps the duals by a / sqrt(t).
CHANNEL_STAY = 0.9
STEP_BETA = 0.5


# --- the image pool and the quantized state space --------------------------

def power_of_rate(r):
    """Transmit power (W) at rate r Mbps: the paper's Fig. 2b fit."""
    return -0.00037 * r**2 + 0.0214 * r + 0.1277


def synthetic_pool(S: int = 64, seed: int = 0) -> dict:
    """The deterministic image pool the configuration names: per image,
    local / cloudlet correctness, local confidence, predicted gain and
    its spread, and cloudlet cycles."""
    rng = np.random.default_rng(seed)
    return {
        "local_correct": (rng.random(S) < 0.6).astype(np.float64),
        "cloud_correct": (rng.random(S) < 0.85).astype(np.float64),
        "d_local": rng.uniform(0.3, 1.0, S),
        "phi_hat": rng.uniform(0.0, 0.3, S),
        "sigma": rng.uniform(0.0, 0.1, S),
        "cycles": np.clip(rng.normal(441e6, 90e6, S), 150e6, None),
    }


def state_levels(pool: dict, num_w: int, v_risk: float):
    """(o, h, w) level grids: power at the three rates, cycles at
    441 +/- 90 M, and gains on a grid up to the pool's 99.9th
    percentile risk-adjusted gain (at least 0.1)."""
    w_all = np.clip(pool["phi_hat"] - v_risk * pool["sigma"], 0.0, 1.0)
    w_hi = max(float(np.quantile(w_all, 0.999)), 0.1)
    return (power_of_rate(RATES_MBPS),
            np.array([441e6 - 90e6, 441e6, 441e6 + 90e6]),
            np.linspace(0.0, w_hi, num_w))


def state_tables(levels):
    """(M,) value tables over the joint states, state 0 the null state
    (no task), then (o, h, w) level triples in row-major order."""
    og, hg, wg = np.meshgrid(*levels, indexing="ij")
    return tuple(np.concatenate([[0.0], g.reshape(-1)]) for g in
                 (og, hg, wg))


# --- counter-addressed uniforms ---------------------------------------------

def _unit(bits, dtype):
    f = jax.lax.bitcast_convert_type(
        (bits >> 9) | jnp.uint32(0x3F800000), jnp.float32) - 1.0
    return jnp.maximum(f, 0.0).astype(dtype)


def _block_uniforms(seed, sid, b, channels, N, dtype):
    """(channels, BLOCK, N) uniforms of block ``b`` of stream ``sid``.

    The value at (slot-in-block r, channel c, device n) is the threefry
    hash, under key fold_in(fold_in(PRNGKey(seed), sid), b), of counter
    (r * channels + c) * N + n; counters of rows r and r + BLOCK/2 are
    hashed as one pair."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed),
                                                sid), b)
    half = BLOCK // 2
    r = jnp.arange(half, dtype=jnp.uint32)[:, None, None]
    c = jnp.arange(channels, dtype=jnp.uint32)[None, :, None]
    n = jnp.arange(N, dtype=jnp.uint32)[None, None, :]
    x0 = (r * channels + c) * jnp.uint32(N) + n
    x1 = x0 + jnp.uint32(half * channels * N)
    y0, y1 = threefry2x32_p.bind(key[0], key[1], x0, x1)
    u = jnp.concatenate([_unit(y0, dtype), _unit(y1, dtype)])
    return u.transpose(1, 0, 2)


def _initial_arrivals(seed, N, p_init, dtype):
    """(N,) ON/OFF state entering slot 0: uniform n of the initial
    stream below the stationary ON share."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), STREAM_ARRIVAL_INIT)
    half = (N + 1) // 2
    x0 = jnp.arange(half, dtype=jnp.uint32)
    x1 = jnp.where(x0 + half < N, x0 + half, 0).astype(jnp.uint32)
    y0, y1 = threefry2x32_p.bind(key[0], key[1], x0, x1)
    u = jnp.concatenate([_unit(y0, dtype), _unit(y1, dtype)])[:N]
    return u < jnp.asarray(p_init, dtype)


def _pick(idx, table):
    """table[idx] for a small table, as a sum over the table's entries of
    the one that matches: exact (one term, the rest zeros)."""
    hit = idx[..., None] == jnp.arange(table.shape[0])
    return jnp.sum(jnp.where(hit, table, jnp.zeros_like(table)), axis=-1)


def _levels(u, n):
    return jnp.minimum(jnp.floor(u.astype(jnp.float32) * n).astype(
        jnp.int32), n - 1)


# --- the reference ------------------------------------------------------------

class Reference:
    """The service of one configuration: ``init(seed)`` gives the carry
    entering slot 0 and ``block(carry, seed, b)`` advances it over block
    ``b``, returning the block's per-slot series (and, with ``decisions``,
    its (BLOCK, N) offload and admit masks).  ``waves(seed, slots)``
    yields the devices' reports alone."""

    def __init__(self, cfg: dict, traffic: dict, dtype=jnp.float32,
                 decisions: bool = False):
        self.N = int(cfg["num_devices"])
        self.K = int(cfg.get("cloudlets", 1))
        self.dtype = jnp.dtype(dtype)
        self.decisions = decisions
        pool = synthetic_pool(int(cfg["pool_images"]), int(cfg["pool_seed"]))
        v_risk = float(cfg["v_risk"])
        levels = state_levels(pool, int(cfg["num_w_levels"]), v_risk)
        self.levels = tuple(tuple(float(x) for x in lv) for lv in levels)
        self.M = 1 + int(np.prod([len(lv) for lv in levels]))
        self.tables = state_tables(levels)
        self.v_risk = v_risk
        self.pool = {"o_levels": power_of_rate(RATES_MBPS),
                     "cycles": pool["cycles"], "phi": pool["phi_hat"],
                     "sigma": pool["sigma"], "cl": pool["local_correct"],
                     "cc": pool["cloud_correct"]}
        # the chain's probabilities, rounded as float32 arithmetic does
        f = np.float32
        burst = traffic["burst_len"]
        mean_on = max((burst[0] + burst[1]) / 2.0, 1.0)
        mean_off = f(1.0) + f(traffic["mean_gap"])
        self.p_on = f(1.0) / mean_off
        self.p_stay = f(1.0 - 1.0 / mean_on)
        self.p_init = f(mean_on) / (f(mean_on) + mean_off)
        self.p_change = f(1.0) - f(CHANNEL_STAY)
        self.p_handover = float(cfg.get("p_handover", 0.0))
        self.B = float(cfg["B_n"])
        self.H = float(cfg["num_devices"]) * float(cfg["H_per_device"])
        self.step_a = float(cfg["step_a"])
        self.step_beta = STEP_BETA
        self._block = jax.jit(self._block_impl)
        self._init = jax.jit(self._init_impl)

    # carry: (on, rate, assoc, lam, mu, counts, t)
    def _init_impl(self, seed):
        N, dt = self.N, self.dtype
        on0 = _initial_arrivals(seed, N, self.p_init, dt)
        mu = jnp.zeros(() if self.K == 1 else (self.K,), dt)
        assoc = jnp.arange(N, dtype=jnp.int32) % self.K
        return (on0, jnp.zeros((N,), jnp.int32), assoc,
                jnp.zeros((N,), dt), mu, jnp.zeros((N, self.M), dt),
                jnp.int32(0))

    def init(self, seed):
        return self._init(jnp.int32(seed))

    def _reports(self, seed, b):
        """The block's per-slot report function: (on, rate, assoc) entering
        a slot, and the slot -> (on, rate, assoc, img, o, h, w) after it."""
        N, dt = self.N, self.dtype
        c = lambda x: jnp.asarray(x, dt)
        u = _block_uniforms(seed, STREAM_SERVICE, b, 4, N, dt)
        ut = (_block_uniforms(seed, STREAM_TOPOLOGY, b, 2, N, dt)
              if self.K > 1 else None)
        pool = {k: c(v) for k, v in self.pool.items()}
        w_img = jnp.clip(pool["phi"] - c(self.v_risk) * pool["sigma"],
                         0.0, 1.0)

        def reports(on, rate, assoc, r):
            g = b * BLOCK + r  # global slot
            u_r = u[:, r]
            on = jnp.where(on, u_r[0] < c(self.p_stay),
                           u_r[0] < c(self.p_on))
            img = _levels(u_r[1], w_img.shape[0])
            change = (u_r[2] < c(self.p_change)) | (g == 0)
            rate = jnp.where(change, _levels(u_r[3], 3), rate)
            if self.K > 1:
                assoc = jnp.where(ut[0, r] < c(self.p_handover),
                                  _levels(ut[1, r], self.K), assoc)
            o = _pick(rate, pool["o_levels"])
            h = _pick(img, pool["cycles"])
            w = _pick(img, w_img)
            return on, rate, assoc, img, o, h, w

        return reports, pool

    def _block_impl(self, carry, seed, b, served):
        N, dt = self.N, self.dtype
        c = lambda x: jnp.asarray(x, dt)
        reports, pool = self._reports(seed, b)
        o_tab, h_tab, w_tab = (c(x) for x in self.tables)
        # preconditioned constraint space: each row's right-hand side is 1
        o_s, h_s = o_tab / c(self.B), h_tab / c(self.H)
        a, beta, one = c(self.step_a), c(self.step_beta), c(1.0)
        H = c(self.H)
        cap_k = H / self.K  # each cloudlet's share of the capacity

        def slot(carry, xs):
            r, serve = xs
            on, rate, assoc, lam0, mu0, counts0, t0 = carry
            lam, mu, counts, t = lam0, mu0, counts0, t0
            on, rate, assoc, img, o, h, w = reports(on, rate, assoc, r)
            # nearest level in each coordinate, first level on ties
            near = lambda x, lv: jnp.argmin(
                jnp.abs(x[:, None] - c(np.array(lv))), axis=-1)
            io, ih, iw = (near(x, lv) for x, lv in zip((o, h, w),
                                                       self.levels))
            nh, nw = len(self.levels[1]), len(self.levels[2])
            j = jnp.where(on, 1 + (io * nh + ih) * nw + iw, 0)
            # OnAlgo: decide under the duals entering the slot
            mu_n = mu if self.K == 1 else mu[assoc]
            off = (lam * (o / c(self.B)) + mu_n * (h / H) < w) & (
                w > 0) & on & serve
            counts = counts.at[jnp.arange(N), j].add(one)
            t = t + 1
            rho = counts / c(t)
            lam_row = lam[:, None] * o_s
            if self.K == 1:
                y = ((lam_row + mu * h_s < w_tab) & (w_tab > 0)).astype(dt)
                load = jnp.sum(h_s * rho * y)
                g_cap = load - one
            else:
                y = ((lam_row + mu[assoc][:, None] * h_s < w_tab)
                     & (w_tab > 0)).astype(dt)
                rows = jnp.sum(h_s * rho * y, axis=-1)
                load = jax.ops.segment_sum(rows, assoc, num_segments=self.K)
                g_cap = load - cap_k / H
            g_pow = jnp.sum(o_s * rho * y, axis=-1) - one
            a_t = a / c(t) ** beta
            lam = jnp.maximum(lam + a_t * g_pow, 0.0)
            mu = jnp.maximum(mu + a_t * g_cap, 0.0)
            adm = self._admit(off, h, assoc, cap_k)
            # a slot the system did not serve leaves the duals and the
            # visit counts as they were
            keep = lambda new, old: jnp.where(serve, new, old)
            lam, mu, counts, t = (keep(lam, lam0), keep(mu, mu0),
                                  keep(counts, counts0), keep(t, t0))
            off_f, adm_f, task_f = (x.astype(dt) for x in (off, adm, on))
            out = {
                "tasks": jnp.sum(task_f.astype(jnp.float32)),
                "offloads": jnp.sum(off_f.astype(jnp.float32)),
                "admits": jnp.sum(adm_f.astype(jnp.float32)),
                "reward": jnp.sum(w * adm_f),
                "power": jnp.sum(o * off_f),
                "load": jnp.sum(h * adm_f),
                "correct": jnp.sum(jnp.where(adm, _pick(img, pool["cc"]),
                                             _pick(img, pool["cl"])) * task_f),
                "lam_norm": jnp.sqrt(jnp.sum(lam * lam) + jnp.sum(mu * mu)),
                "mu": mu if self.K == 1 else jnp.mean(mu),
            }
            if self.K > 1:
                out["mu_k"] = mu
            if self.decisions:
                out["offload_mask"], out["admit_mask"] = off, adm
            return (on, rate, assoc, lam, mu, counts, t), out

        return jax.lax.scan(slot, carry, (jnp.arange(BLOCK), served))

    def _admit(self, off, h, assoc, cap):
        """Greedy admission in device order: a cloudlet takes each of
        its offloaders while its running load stays within its share of
        the capacity, H / K."""
        h_eff = jnp.where(off, h, jnp.zeros_like(h))
        if self.K == 1:
            return off & (jnp.cumsum(h_eff) <= cap)
        order = jnp.argsort(assoc, stable=True)
        a_s, h_sorted = assoc[order], h_eff[order]
        start = jnp.concatenate([jnp.ones((1,), bool), a_s[1:] != a_s[:-1]])
        # running load within each cloudlet, summed in device order
        run = jax.lax.associative_scan(
            lambda x, y: (jnp.where(y[1], y[0], x[0] + y[0]), x[1] | y[1]),
            (h_sorted, start))[0]
        fits = jnp.zeros(off.shape, bool).at[order].set(run <= cap)
        return off & fits

    def _waves_impl(self, carry, seed, b):
        reports, _ = self._reports(seed, b)

        def slot(carry, r):
            on, rate, assoc, _, o, h, w = reports(*carry, r)
            return (on, rate, assoc), (on, o, h, w)

        return jax.lax.scan(slot, carry, jnp.arange(BLOCK))

    def waves(self, seed, slots: int):
        """The devices' reports over slots [0, slots), block by block on
        the device: yields (on, o, h, w), each (BLOCK, N), per block."""
        f = jax.jit(self._waves_impl)
        on, rate, assoc = self.init(seed)[:3]
        carry = (on, rate, assoc)
        for b in range(-(-slots // BLOCK)):
            carry, out = f(carry, jnp.int32(seed), jnp.uint32(b))
            yield out

    def block(self, carry, seed, b, served=None):
        """Block ``b``; ``served`` (BLOCK,) bool marks the slots the
        system decided (default all): an unserved slot's workload still
        advances, its decisions are none and the duals hold."""
        if served is None:
            served = np.ones((BLOCK,), bool)
        return self._block(carry, jnp.int32(seed), jnp.uint32(b),
                           jnp.asarray(served, bool))

    def run(self, seed, slots: int):
        """Slots [0, slots) from the start, every slot served: (series as
        host arrays, the final carry).  ``slots`` must be whole blocks."""
        if slots % BLOCK:
            raise ValueError(f"slots={slots} is not whole blocks of {BLOCK}")
        carry = self.init(seed)
        parts = []
        for b in range(slots // BLOCK):
            carry, out = self.block(carry, seed, b)
            parts.append(jax.tree.map(np.asarray, out))
        series = {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
        return series, carry
