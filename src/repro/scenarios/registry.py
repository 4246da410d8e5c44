"""Scenario generator registry.

Each generator is a function ``Scenario -> CompiledScenario`` registered under
its ``kind`` name.  All generators are host-side (numpy RNG, mirroring
``repro.data.traces``) and lower to the core ``(Trace, tables, params)``
contract; jit'd simulation consumes the result unchanged.

Kinds that act as pure transforms on an already-compiled scenario (churn
masks activity windows, outage mirrors the state space) are additionally
registered as *modifiers*, which ``spec.compose`` layers onto any base kind
— e.g. the registered ``churn_outage`` kind is churn composed with outage.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List

import jax.numpy as jnp
import numpy as np

from repro.core.fleet import Trace
from repro.data.traces import TraceSpec, bursty_trace, iid_trace
from repro.scenarios.spec import CompiledScenario, Scenario, scenario_space

SCENARIO_KINDS: Dict[str, Callable[[Scenario], CompiledScenario]] = {}
MODIFIERS: Dict[
    str, Callable[[Scenario, CompiledScenario], CompiledScenario]] = {}


def register(kind: str):
    def deco(fn):
        SCENARIO_KINDS[kind] = fn
        return fn
    return deco


def register_modifier(kind: str):
    def deco(fn):
        MODIFIERS[kind] = fn
        return fn
    return deco


def names() -> List[str]:
    return sorted(SCENARIO_KINDS)


def compile_scenario(sc: Scenario) -> CompiledScenario:
    if sc.kind not in SCENARIO_KINDS:
        raise KeyError(f"unknown scenario kind {sc.kind!r}; "
                       f"registered: {names()}")
    return SCENARIO_KINDS[sc.kind](sc)


def default_scenarios() -> List[Scenario]:
    """One representative spec per registered kind (tests / benches)."""
    base = dict(T=2000, N=8, seed=0)
    return [
        Scenario("stationary", **base),
        Scenario("bursty", **base),
        Scenario("bursty_counter", **base),
        Scenario("diurnal", **base).with_extra(period=500, amp=0.8),
        Scenario("churn", **base).with_extra(churn_frac=0.4),
        Scenario("flash_crowd", **base).with_extra(n_events=3,
                                                   event_len=60),
        Scenario("heterogeneous", **base).with_extra(o_spread=0.5),
        Scenario("outage", **base).with_extra(n_outages=2, outage_len=200),
        Scenario("churn_outage", **base).with_extra(
            churn_frac=0.3, n_outages=2, outage_len=150),
        Scenario("mobility", **base).with_extra(K=4, p_handover=0.05),
        Scenario("hotspot", **base).with_extra(K=4, hot_frac=0.6),
        Scenario("cloudlet_outage", **base).with_extra(
            K=4, n_outages=2, outage_len=150),
    ]


def _dloc(rng, w_vals, noise=0.08):
    d = 1.0 - w_vals + rng.normal(0, noise, size=w_vals.shape)
    return np.clip(d, 0.0, 1.0)


def _trace_spec(sc: Scenario) -> TraceSpec:
    return TraceSpec(T=sc.T, N=sc.N, task_prob=sc.task_prob, seed=sc.seed)


@register("stationary")
def _stationary(sc: Scenario) -> CompiledScenario:
    """IID traffic — the paper's baseline regime, exact true rho."""
    space = scenario_space(sc)
    trace, rho = iid_trace(space, _trace_spec(sc))
    return CompiledScenario(sc, trace, space.tables(), sc.params(),
                            true_rho=rho)


@register("bursty")
def _bursty(sc: Scenario) -> CompiledScenario:
    """Markov-modulated ON/OFF bursts (paper Sec. VI evaluation traffic)."""
    space = scenario_space(sc)
    trace, rho = bursty_trace(space, _trace_spec(sc))
    return CompiledScenario(sc, trace, space.tables(), sc.params(),
                            true_rho=rho, meta={"rho_is_approx": True})


@register("bursty_counter")
def _bursty_counter(sc: Scenario) -> CompiledScenario:
    """Bursty arrivals compiled through the workload layer (RNG v1).

    The ON/OFF process is the counter-based Markov chain the service
    tier's compiler uses (``repro.workload``: stationary-initialized,
    burst/gap means matched to the legacy renewal process), so fleet
    scenarios and compiled service runs share one arrival
    implementation.  States are iid categorical draws as in
    ``stationary``; the chain starts at its stationary law, so the
    per-slot marginal rho is exact (the *process* is non-iid —
    ``rho_is_approx`` flags the empirical-estimator caveat, as for
    ``bursty``).
    """
    from repro.workload import arrival_chain_probs, streams

    space = scenario_space(sc)
    burst_len = tuple(sc.opt("burst_len", (5, 10)))
    mean_gap = float(sc.opt("mean_gap", 8.0))
    T, N = sc.T, sc.N
    p_on, p_stay, p_init = arrival_chain_probs(burst_len, mean_gap)
    u = streams.uniform_block(sc.seed, streams.STREAM_SCENARIO, T, N, 1)
    u0 = streams.uniform_vector(sc.seed, streams.STREAM_ARRIVAL_INIT, N)
    on = np.asarray(streams.markov_chain(u[0], u0 < p_init,
                                         jnp.float32(p_on),
                                         jnp.float32(p_stay)))

    rng = np.random.default_rng(sc.seed)
    Lo, Lh, Lw = space.num_levels
    # same Dirichlet level priors as data.traces iid/bursty generators
    probs = [rng.dirichlet(np.full(L, 3.0)) for L in (Lo, Lh, Lw)]
    io = rng.choice(Lo, size=(T, N), p=probs[0])
    ih = rng.choice(Lh, size=(T, N), p=probs[1])
    iw = rng.choice(Lw, size=(T, N), p=probs[2])
    j = np.where(on, np.asarray(space.encode(io, ih, iw)), 0)

    w_tab = np.asarray(space.tables()[2])
    trace = Trace(j_idx=jnp.asarray(j, jnp.int32),
                  d_local=jnp.asarray(_dloc(rng, w_tab[j]), jnp.float32))
    joint = (probs[0][:, None, None] * probs[1][None, :, None]
             * probs[2][None, None, :])
    rho_row = np.concatenate([[1.0 - p_init], p_init * joint.reshape(-1)])
    rho = jnp.asarray(np.broadcast_to(rho_row, (N, space.M)).copy(),
                      jnp.float32)
    return CompiledScenario(sc, trace, space.tables(), sc.params(),
                            true_rho=rho,
                            meta={"rho_is_approx": True,
                                  "arrival_rng": "counter_v1"})


@register("diurnal")
def _diurnal(sc: Scenario) -> CompiledScenario:
    """Sinusoidal day cycle: task rate and gain distribution co-vary.

    At "night" traffic is sparse and gains are biased low; at "day" traffic
    is dense and high-gain (fresh content worth offloading).  This is the
    time-varying-rho regime OnAlgo's Azuma-style analysis targets.
    """
    period = int(sc.opt("period", max(sc.T // 4, 2)))
    amp = float(sc.opt("amp", 0.8))
    space = scenario_space(sc)
    rng = np.random.default_rng(sc.seed)
    Lo, Lh, Lw = space.num_levels
    T, N = sc.T, sc.N

    phase = 2 * np.pi * np.arange(T) / period
    day = 0.5 * (1.0 + np.sin(phase))  # (T,) in [0, 1]
    p_task_t = np.clip(sc.task_prob * (1.0 - amp + 2 * amp * day), 0.0, 0.98)

    # gain-level distributions: low-biased at night, high-biased at day
    bias = np.linspace(2.0, 0.5, Lw)
    p_night = bias / bias.sum()
    p_day = bias[::-1] / bias.sum()
    p_w_t = (1 - day)[:, None] * p_night + day[:, None] * p_day  # (T, Lw)

    io = rng.integers(0, Lo, size=(T, N))
    ih = rng.integers(0, Lh, size=(T, N))
    cdf = np.cumsum(p_w_t, axis=1)  # (T, Lw)
    u = rng.random((T, N))
    iw = np.clip((u[:, :, None] > cdf[:, None, :]).sum(-1), 0, Lw - 1)
    j = np.asarray(space.encode(io, ih, iw))
    task = rng.random((T, N)) < p_task_t[:, None]
    j = np.where(task, j, 0)

    w_tab = np.asarray(space.tables()[2])
    trace = Trace(j_idx=jnp.asarray(j, jnp.int32),
                  d_local=jnp.asarray(_dloc(rng, w_tab[j]), jnp.float32))
    return CompiledScenario(sc, trace, space.tables(), sc.params(),
                            meta={"period": period, "amp": amp})


@register_modifier("churn")
def _mod_churn(sc: Scenario, base: CompiledScenario) -> CompiledScenario:
    """Mask device activity windows onto an already-compiled scenario.

    Device n joins the fleet at ``arrive[n]`` and leaves at ``depart[n]``;
    outside its window it sits in the null state, so it generates no tasks
    and contributes nothing to the constraints — exactly how an absent
    device looks to the cloudlet.  Invalidates any analytic true_rho.
    """
    churn_frac = float(sc.opt("churn_frac", 0.4))
    rng = np.random.default_rng(sc.seed + 1)
    T, N = base.trace.j_idx.shape
    span = max(int(T * churn_frac), 1)
    arrive = rng.integers(0, span, N)
    depart = T - rng.integers(0, span, N)
    slots = np.arange(T)[:, None]
    active = (slots >= arrive[None, :]) & (slots < depart[None, :])
    j = np.where(active, np.asarray(base.trace.j_idx), 0)
    d = np.where(active, np.asarray(base.trace.d_local), 0.0)
    trace = Trace(j_idx=jnp.asarray(j, jnp.int32),
                  d_local=jnp.asarray(d, jnp.float32))
    meta = dict(base.meta, arrive=arrive, depart=depart)
    return CompiledScenario(base.scenario, trace, base.tables, base.params,
                            meta=meta, topology=base.topology)


@register("churn")
def _churn(sc: Scenario) -> CompiledScenario:
    """Device arrivals/departures over IID traffic (see ``_mod_churn``)."""
    space = scenario_space(sc)
    trace, _ = iid_trace(space, _trace_spec(sc))
    base = CompiledScenario(sc, trace, space.tables(), sc.params())
    return _mod_churn(sc, base)


@register("flash_crowd")
def _flash_crowd(sc: Scenario) -> CompiledScenario:
    """Flash-crowd bursts: short windows where nearly every device has a
    task and gains skew high (everyone films the same event)."""
    n_events = int(sc.opt("n_events", 3))
    event_len = int(sc.opt("event_len", 60))
    peak_prob = float(sc.opt("peak_prob", 0.97))
    space = scenario_space(sc)
    trace, _ = iid_trace(space, _trace_spec(sc))
    rng = np.random.default_rng(sc.seed + 2)
    Lo, Lh, Lw = space.num_levels
    T, N = sc.T, sc.N

    starts = np.sort(rng.integers(0, max(T - event_len, 1), n_events))
    in_event = np.zeros(T, bool)
    for s in starts:
        in_event[s:s + event_len] = True

    # resample event slots: dense traffic, high-gain-biased levels
    bias = np.linspace(0.5, 2.0, Lw)
    p_hi = bias / bias.sum()
    io = rng.integers(0, Lo, size=(T, N))
    ih = rng.integers(0, Lh, size=(T, N))
    iw = rng.choice(Lw, size=(T, N), p=p_hi)
    j_event = np.asarray(space.encode(io, ih, iw))
    task_event = rng.random((T, N)) < peak_prob
    j_event = np.where(task_event, j_event, 0)

    j = np.where(in_event[:, None], j_event, np.asarray(trace.j_idx))
    w_tab = np.asarray(space.tables()[2])
    trace = Trace(j_idx=jnp.asarray(j, jnp.int32),
                  d_local=jnp.asarray(_dloc(rng, w_tab[j]), jnp.float32))
    return CompiledScenario(sc, trace, space.tables(), sc.params(),
                            meta={"event_starts": starts,
                                  "event_len": event_len})


@register("heterogeneous")
def _heterogeneous(sc: Scenario) -> CompiledScenario:
    """Heterogeneous fleet: per-device (N, M) value tables.

    Each device pays a distance-dependent power multiplier (lognormal, the
    far-from-AP effect of paper Fig. 2b) and realizes a device-specific gain
    scale (camera/model quality).  ``fleet._lookup`` and the kernels handle
    the (N, M) layout natively; true_rho stays exact because the *state
    index* process is unchanged.
    """
    o_spread = float(sc.opt("o_spread", 0.5))
    w_spread = float(sc.opt("w_spread", 0.25))
    space = scenario_space(sc)
    trace, rho = iid_trace(space, _trace_spec(sc))
    rng = np.random.default_rng(sc.seed + 3)
    N = sc.N
    o_tab, h_tab, w_tab = space.tables()
    o_scale = rng.lognormal(0.0, o_spread, N).astype(np.float32)
    w_scale = np.clip(rng.normal(1.0, w_spread, N), 0.3, 1.7)
    o_nm = jnp.asarray(o_scale)[:, None] * o_tab[None, :]
    w_nm = jnp.asarray(w_scale, jnp.float32)[:, None] * w_tab[None, :]
    h_nm = jnp.broadcast_to(h_tab, (N, space.M))
    return CompiledScenario(sc, trace, (o_nm, h_nm, w_nm), sc.params(),
                            true_rho=rho,
                            meta={"o_scale": o_scale, "w_scale": w_scale})


@register_modifier("diurnal")
def _mod_diurnal(sc: Scenario, base: CompiledScenario) -> CompiledScenario:
    """Thin an already-compiled scenario's traffic on a sinusoidal day
    cycle: slot t keeps each task w.p. (1 - amp) + amp * day(t), so the
    peak keeps everything and the trough keeps (1 - amp).  Acting purely
    on the task mask (null-state thinning) keeps any table layout —
    doubled outage spaces, per-device (N, M) tables — untouched, so it
    composes with every other modifier.  Invalidates analytic true_rho.
    """
    period = int(sc.opt("period", max(sc.T // 4, 2)))
    amp = float(sc.opt("amp", 0.8))
    rng = np.random.default_rng(sc.seed + 5)
    T, N = base.trace.j_idx.shape
    day = 0.5 * (1.0 + np.sin(2 * np.pi * np.arange(T) / period))
    keep_p = (1.0 - amp) + amp * day  # (T,) in [1 - amp, 1]
    keep = rng.random((T, N)) < keep_p[:, None]
    j = np.where(keep, np.asarray(base.trace.j_idx), 0)
    d = np.where(keep, np.asarray(base.trace.d_local), 0.0)
    trace = Trace(j_idx=jnp.asarray(j, jnp.int32),
                  d_local=jnp.asarray(d, jnp.float32))
    meta = dict(base.meta, period=period, amp=amp)
    return CompiledScenario(base.scenario, trace, base.tables, base.params,
                            meta=meta, topology=base.topology)


@register_modifier("flash_crowd")
def _mod_flash_crowd(sc: Scenario, base: CompiledScenario
                     ) -> CompiledScenario:
    """Densify an already-compiled scenario during flash-crowd windows.

    Within each event window every idle device draws a task w.p.
    ``peak_prob`` by resampling a state from its OWN realized non-null
    states (a bootstrap of the base scenario's marginal), so the state
    distribution stays layout-compatible with whatever the base
    generator produced (outage mirrors, heterogeneous tables, ...).
    Devices with no task anywhere in the base trace stay silent.
    Composition order matters: churn applied after this re-silences
    absent devices.  Invalidates analytic true_rho.
    """
    n_events = int(sc.opt("n_events", 3))
    event_len = int(sc.opt("event_len", 60))
    peak_prob = float(sc.opt("peak_prob", 0.97))
    rng = np.random.default_rng(sc.seed + 6)
    T, N = base.trace.j_idx.shape

    starts = np.sort(rng.integers(0, max(T - event_len, 1), n_events))
    in_event = np.zeros(T, bool)
    for s in starts:
        in_event[s:s + event_len] = True

    j = np.asarray(base.trace.j_idx).copy()
    d = np.asarray(base.trace.d_local).copy()
    fill = in_event[:, None] & (j == 0) & (rng.random((T, N)) < peak_prob)
    for n in range(N):
        busy = np.flatnonzero(j[:, n] > 0)
        slots = np.flatnonzero(fill[:, n])
        if busy.size == 0 or slots.size == 0:
            continue
        donors = busy[rng.integers(0, busy.size, slots.size)]
        j[slots, n] = j[donors, n]
        d[slots, n] = d[donors, n]
    trace = Trace(j_idx=jnp.asarray(j, jnp.int32),
                  d_local=jnp.asarray(d, jnp.float32))
    meta = dict(base.meta, event_starts=starts, event_len=event_len)
    return CompiledScenario(base.scenario, trace, base.tables, base.params,
                            meta=meta, topology=base.topology)


@register_modifier("outage")
def _mod_outage(sc: Scenario, base: CompiledScenario) -> CompiledScenario:
    """Mirror w=0 down-states onto an already-compiled scenario.

    The state space is doubled: states [M, 2M) copy (o, h) but zero the
    gain w.  During an outage window every task state j is remapped to
    j + M, so the threshold rule (which requires w > 0) provably never
    offloads — the cloudlet being down costs zero accuracy gain — while
    rho keeps tracking the full process.  Concatenating along the state
    axis keeps both shared (M,) and per-device (N, M) table layouts on
    the contract untouched.
    """
    n_outages = int(sc.opt("n_outages", 2))
    outage_len = int(sc.opt("outage_len", 200))
    rng = np.random.default_rng(sc.seed + 4)
    T = base.trace.j_idx.shape[0]
    M = base.M

    starts = np.sort(rng.integers(0, max(T - outage_len, 1), n_outages))
    down = np.zeros(T, bool)
    for s in starts:
        down[s:s + outage_len] = True

    o_tab, h_tab, w_tab = base.tables
    o2 = jnp.concatenate([o_tab, o_tab], axis=-1)
    h2 = jnp.concatenate([h_tab, h_tab], axis=-1)
    w2 = jnp.concatenate([w_tab, jnp.zeros_like(w_tab)], axis=-1)

    j = np.asarray(base.trace.j_idx)
    j = np.where(down[:, None] & (j > 0), j + M, j)
    trace = Trace(j_idx=jnp.asarray(j, jnp.int32),
                  d_local=base.trace.d_local)
    meta = dict(base.meta, outage_starts=starts, outage_len=outage_len,
                down=down)
    return CompiledScenario(base.scenario, trace, (o2, h2, w2), base.params,
                            meta=meta, topology=base.topology)


@register("outage")
def _outage(sc: Scenario) -> CompiledScenario:
    """Cloudlet capacity outages over IID traffic (see ``_mod_outage``)."""
    space = scenario_space(sc)
    trace, _ = iid_trace(space, _trace_spec(sc))
    base = CompiledScenario(sc, trace, space.tables(), sc.params())
    return _mod_outage(sc, base)


def _default_topology(base: CompiledScenario, K: int):
    """The base scenario's topology, or a nearest-zone K-cloudlet default
    splitting the scenario's total capacity H evenly."""
    from repro.topology import Topology
    if base.topology is not None:
        return base.topology
    return Topology.nearest_zone(K, base.trace.N, base.params.H)


def _require_no_topology(kind: str, base: CompiledScenario):
    """Topology-BUILDING modifiers must not silently replace an
    inherited association map (cloudlet_outage, which transforms the
    existing one, is the composable exception)."""
    if base.topology is not None:
        raise ValueError(
            f"the {kind!r} modifier builds a topology, but the base "
            "scenario already carries one — apply the topology-defining "
            "modifier first and layer only topology-transforming "
            "modifiers (e.g. cloudlet_outage) on top")


@register_modifier("mobility")
def _mod_mobility(sc: Scenario, base: CompiledScenario) -> CompiledScenario:
    """Attach a mobility-walk topology to an already-compiled scenario.

    K cloudlets split the scenario's capacity evenly; each slot a device
    hands over to a random cloudlet w.p. ``p_handover`` (the workload
    layer's counter-addressed held-value process, so the walk composes
    with any traffic base).  Per-cloudlet duals and admission replace
    the scalar mu on every engine via ``run_scenario``.
    """
    from repro.topology import Topology
    _require_no_topology("mobility", base)
    K = int(sc.opt("K", 4))
    p_handover = float(sc.opt("p_handover", 0.05))
    T, N = base.trace.j_idx.shape
    topo = Topology.mobility_walk(K, N, T, H=base.params.H,
                                  p_handover=p_handover, seed=sc.seed)
    meta = dict(base.meta, K=K, p_handover=p_handover)
    return dataclasses.replace(base, topology=topo, meta=meta)


@register("mobility")
def _mobility(sc: Scenario) -> CompiledScenario:
    """Mobile fleet over IID traffic: devices random-walk between K
    cloudlets (see ``_mod_mobility``)."""
    space = scenario_space(sc)
    trace, rho = iid_trace(space, _trace_spec(sc))
    base = CompiledScenario(sc, trace, space.tables(), sc.params(),
                            true_rho=rho)
    return _mod_mobility(sc, base)


@register_modifier("hotspot")
def _mod_hotspot(sc: Scenario, base: CompiledScenario) -> CompiledScenario:
    """Attach a hotspot topology: ``hot_frac`` of the fleet crowds one
    cloudlet (stadium / transit-hub cell) while capacity stays split
    evenly — the congested cloudlet's dual must rise above the others',
    which only the per-cloudlet mu vector can express."""
    from repro.topology import Topology
    _require_no_topology("hotspot", base)
    K = int(sc.opt("K", 4))
    hot_frac = float(sc.opt("hot_frac", 0.6))
    topo = Topology.hotspot(K, base.trace.N, base.params.H,
                            hot_frac=hot_frac)
    meta = dict(base.meta, K=K, hot_frac=hot_frac)
    return dataclasses.replace(base, topology=topo, meta=meta)


@register("hotspot")
def _hotspot(sc: Scenario) -> CompiledScenario:
    """Hotspot association skew over IID traffic (see ``_mod_hotspot``)."""
    space = scenario_space(sc)
    trace, rho = iid_trace(space, _trace_spec(sc))
    base = CompiledScenario(sc, trace, space.tables(), sc.params(),
                            true_rho=rho)
    return _mod_hotspot(sc, base)


@register_modifier("cloudlet_outage")
def _mod_cloudlet_outage(sc: Scenario,
                         base: CompiledScenario) -> CompiledScenario:
    """One cloudlet goes down for outage windows; its devices fail over.

    Unlike the fleet-wide ``outage`` modifier (which zeroes every gain),
    this is a TOPOLOGY event: during each window, cloudlet ``down_k``'s
    devices deterministically re-associate to the survivors — whose duals
    must then absorb the migrated load — and return when it recovers.
    Requires (or builds) a K >= 2 topology; composes with mobility /
    hotspot since it acts on the association map.
    """
    n_outages = int(sc.opt("n_outages", 2))
    outage_len = int(sc.opt("outage_len", 200))
    down_k = int(sc.opt("down_k", 0))
    K = int(sc.opt("K", 4))
    topo = _default_topology(base, K)
    if not 0 <= down_k < topo.K:
        # topo.K may come from an inherited base topology, not the K knob
        raise ValueError(
            f"down_k={down_k} is not a cloudlet of the K={topo.K} "
            "topology this scenario runs on — the outage would silently "
            "be a no-op")
    rng = np.random.default_rng(sc.seed + 7)
    T = base.trace.j_idx.shape[0]
    starts = np.sort(rng.integers(0, max(T - outage_len, 1), n_outages))
    down = np.zeros(T, bool)
    for s in starts:
        down[s:s + outage_len] = True
    topo = topo.failover(jnp.asarray(down), down_k)
    meta = dict(base.meta, cloudlet_outage_starts=starts,
                outage_len=outage_len, down_k=down_k, down=down)
    return dataclasses.replace(base, topology=topo, meta=meta)


@register("cloudlet_outage")
def _cloudlet_outage(sc: Scenario) -> CompiledScenario:
    """Cloudlet failover windows over IID traffic on a nearest-zone
    topology (see ``_mod_cloudlet_outage``)."""
    space = scenario_space(sc)
    trace, rho = iid_trace(space, _trace_spec(sc))
    base = CompiledScenario(sc, trace, space.tables(), sc.params(),
                            true_rho=rho)
    return _mod_cloudlet_outage(sc, base)


@register("churn_outage")
def _churn_outage(sc: Scenario) -> CompiledScenario:
    """Composed scenario: device churn layered with cloudlet outages.

    Built with ``spec.compose`` — churn's activity mask and outage's
    mirrored down-states stack because both act purely through the
    ``(Trace, tables, params)`` contract.
    """
    from repro.scenarios.spec import compose
    c = compose(dataclasses.replace(sc, kind="churn"),
                dataclasses.replace(sc, kind="outage"))
    return dataclasses.replace(c, scenario=sc)
