"""The benchmark's own tests, on the CPU at tiny sizes.

    PYTHONPATH=src python -m pytest -q bench/tests

They check that every cell resolves to its files by name, that a new
configuration is found by its name alone, the trace reduction on a
synthetic trace, that the roofline counts do not depend on the kernel's
tiling, that the command refuses to run without a TPU, and they drive
each cell's set-up, window and check end to end at a tiny size: sound,
under the control (the reference in bfloat16 in the program's place),
and with the timed path broken underneath.  No number here is a speed.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))  # the system under test

from bench import harness, tracing  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
# cells held out of BENCHMARK.json (bench/held/<cell>.json): their runner,
# traffic and limits stay, and the tests drive them as any other cell
HELD = {p.stem: json.loads(p.read_text())
        for p in sorted((harness.BENCH / "held").glob("*.json"))}
ALL = CELLS + list(HELD)


@pytest.fixture(scope="session")
def held_root(tmp_path_factory):
    """A checkout whose BENCHMARK.json lists the held cells again, with
    no file but BENCHMARK.json changed: how a cell comes back."""
    root = tmp_path_factory.mktemp("held")
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCHMARK))
    for entry in HELD.values():
        for key in ("workloads", "end_to_end", "per_layer"):
            bench[key] += entry[key]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def load(name, held_root):
    return harness.load_cell(name, root=held_root if name in HELD else ROOT)


# -- cells resolve by name ---------------------------------------------------

@pytest.mark.parametrize("name", ALL)
def test_cell_resolves_to_its_files(name, held_root):
    cell = load(name, held_root)
    assert cell.runner_path.is_file()
    assert cell.traffic["kind"] == cell.runner_path.stem
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
    assert cell.limits and all(v >= 0 for v in cell.limits.values())


def test_configs_and_paths():
    configs = {c["name"]: c for c in BENCHMARK["configs"]}
    used = {w["config"] for w in BENCHMARK["workloads"]}
    assert used == set(configs)
    for name, c in configs.items():
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == name and c["file"].startswith("bench/")
        assert cfg["reduced"] == c["reduced"]
        assert all(k in cfg and k in cfg["assumed"] for k in c["reduced"])


def test_new_config_found_by_name(tmp_path):
    """A cell added as data: a config file, a limits file and an entry
    in BENCHMARK.json, with no code edited."""
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCHMARK))
    cfg = json.loads((harness.BENCH / "configs" / "city_k1.json").read_text())
    cfg.update(name="town_k1", num_devices=4096)
    (tmp_path / "bench" / "configs" / "town_k1.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench" / "limits" / "replay.town_k1.json").write_text(
        (harness.BENCH / "limits" / "replay.city_k1.json").read_text())
    bench["workloads"].append({"name": "replay.town_k1", "config": "town_k1",
                               "traffic": "replay_horizon", "chips": 1,
                               "why": "a test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("replay.town_k1", root=tmp_path)
    assert cell.config["num_devices"] == 4096
    assert cell.runner_path == tmp_path / "bench" / "runners" / "replay.py"
    with pytest.raises(harness.CellError):
        harness.load_cell("replay.nowhere", root=tmp_path)


# -- the trace reduction ------------------------------------------------------

def _trace(kernel_events):
    """A window of 10 ms: a slab step program 0-8 ms holding the kernel's
    events and one fusion, idle 8-10 ms."""
    ms = 1_000_000
    ops = [("fusion.7", 0, 1 * ms)] + kernel_events
    modules = [("jit__pipelined_slab_step", 0, 8 * ms)]
    spans = [("bench.window", 0, 10 * ms), ("bench.wait", 7 * ms, 10 * ms)]
    return tracing.Trace([ops], [modules], spans, (0, 10 * ms))


def test_trace_reduction_busy_idle_gaps():
    ms = 1_000_000
    tr = _trace([("%onalgo_tiled.1 = (f32[64,1,1048576])", 1 * ms, 8 * ms)])
    assert tr.window_s == pytest.approx(0.010)
    assert tr.busy_s == pytest.approx(0.008)
    assert tr.module_seconds("_pipelined_slab_step") == pytest.approx(0.008)
    assert tr.op_seconds("onalgo") == pytest.approx(0.007)
    b = tr.breakdown()
    assert b["device_ops"][0] == ["%onalgo_tiled.1 = (f32[64,1,1048576])", pytest.approx(0.007)]
    assert b["idle_gaps"] == [["bench.wait", pytest.approx(0.002)]]
    run = {"slots": 64, "states": 73}
    ctx = {"trace": tr, "run": run, "cell": harness.load_cell(CELLS[0]),
           "peaks": harness.peaks("TPU v5 lite")}
    idle = harness.load_module(harness.BENCH / "metrics"
                               / "device_idle_pct.replay.py")
    assert idle.read(ctx) == pytest.approx(20.0)
    nonk = harness.load_module(harness.BENCH / "metrics" /
                               "slab_nonkernel_us_per_slot.replay.py")
    assert nonk.read(ctx) == pytest.approx(1e6 * 0.001 / 64)


def test_trace_reduction_from_xspace(tmp_path):
    """The reduction reads a serialized XSpace as the profiler writes it."""
    from jax.profiler import ProfileData
    text = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 4000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 7000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.3" } }
  event_metadata { key: 2 value { id: 2 name: "%onalgo_tiled.1 = (f32[64])" } }
  event_metadata { key: 3 value { id: 3 name: "jit__pipelined_slab_step" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}
"""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    tr = tracing.Trace.from_xspace(str(path), 1)
    assert tr.window_s == pytest.approx(10e-6)
    assert tr.busy_s == pytest.approx(6e-6)
    assert tr.op_seconds("onalgo") == pytest.approx(4e-6)
    assert tr.module_count("slab_step") == 1


# -- roofline counts -------------------------------------------------------------

@pytest.mark.parametrize("split", [1, 8, 64])
def test_kernel_roofline_independent_of_tiling(split):
    """The counts take no block_n or chunk; the share sums the kernel's
    events, so however a launch is split the share is the same."""
    roof = harness.load_module(harness.BENCH / "roofline"
                               / "onalgo_rollout.py")
    c = roof.counts(2**20, 73, 2048, 64, 64, 2**27)
    # the packed state (12-bit counts, f32 lam) fits in VMEM: one read
    # and one write a launch; a 7-bit index in, a 1-bit decision out
    state = 2**20 * (73 * 12 / 8 + 4)
    assert c["bytes"] == pytest.approx(2 * state + 2**20 * 64)
    assert c["flops"] == 4 * 2**20 * 73 * 64
    # where the packed state does not fit, it streams every slot
    big = roof.counts(2**20, 73, 2048, 64, 64, 2**26)
    assert big["bytes"] == pytest.approx(
        64 * (state + 2**20 * (4 + 1.5) + 2**20))
    ms = 1_000_000
    step = 7 * ms // split
    tr = _trace([("%onalgo_tiled.1 = (f32[64,1,1048576])", 1 * ms + i * step, 1 * ms + (i + 1) * step)
                 for i in range(split)])
    ctx = {"trace": tr, "run": {"slots": 64, "states": 73},
           "cell": harness.load_cell(CELLS[0]),
           "peaks": harness.peaks("TPU v5 lite")}
    reader = harness.load_module(harness.BENCH / "metrics"
                                 / "onalgo_tiled_roofline.py")
    want = 100 * c["bytes"] / 819e9 / (split * step * 1e-9)
    assert reader.read(ctx) == pytest.approx(want)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.CellError):
        harness.peaks("TPU v99")


# -- the command refuses to run without a TPU ----------------------------------

def test_no_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# -- the cells end to end at a tiny size -----------------------------------------

def tiny_cell(name, held_root=None):
    """The cell at N = 2048 (K <= 8) over a 256-slot horizon: replay in
    64-slot pieces, the gateway's open loop at 40 waves a second."""
    cell = load(name, held_root)
    k = min(int(cell.config.get("cloudlets", 1)), 8)
    cell.config = dict(cell.config, num_devices=2048, cloudlets=k,
                       horizon=256)
    cell.traffic = dict(cell.traffic, piece_slots=64, check_slots=128,
                        trace_pieces=2, rate_hz=40.0, window_waves=64,
                        warm_waves=4, trace_seconds=0.5)
    return cell


def drive(cell, seed=2**31 + 977, traced=False):
    """Set-up, a short window, release and check: the numbers and
    whether the cell's limits pass them."""
    mod = harness.load_module(cell.runner_path)
    drv = mod.Runner(harness.RunContext(cell=cell, seed=seed))
    drv.setup()
    if traced:
        drv.traced(0.5)
    else:
        drv.measure(0.5)
    drv.release()
    numbers = drv.check()
    return numbers, all(v <= cell.limits[k] for k, v in numbers.items())


REPLAY = [c for c in ALL if c.startswith("replay.")]
GATEWAY = [c for c in ALL if c.startswith("gateway.")]


@pytest.mark.parametrize("name", ALL)
def test_cell_sound_at_tiny_size(name, held_root):
    numbers, ok = drive(tiny_cell(name, held_root))
    assert ok, numbers
    assert all(numbers.get(k, 0) == 0 for k in ("tasks_err", "counts_err"))


def test_replay_over_cloudlets_at_tiny_size():
    """The replay runner over 8 cloudlets on a handover walk, as a later
    multi-cloudlet configuration would drive it: sound, with per-cloudlet
    duals compared."""
    cell = tiny_cell(REPLAY[0])
    cell.config = dict(cell.config, cloudlets=8, p_handover=0.03)
    cell.limits = dict(cell.limits, mu_k_err=0.1)
    numbers, ok = drive(cell)
    assert "mu_k_err" in numbers
    assert ok, numbers


@pytest.mark.parametrize("name", REPLAY[:1] + GATEWAY[:1])
def test_traced_window_checks_the_same(name, held_root):
    numbers, ok = drive(tiny_cell(name, held_root), traced=True)
    assert ok, numbers


@pytest.mark.parametrize("name", ALL)
def test_control_fails_the_check(name, held_root):
    import jax.numpy as jnp
    cell = tiny_cell(name, held_root)
    mod = harness.load_module(cell.runner_path)
    drv = mod.Runner(harness.RunContext(cell=cell, seed=4242))
    numbers = drv.control(jnp.bfloat16)
    failed = [k for k, v in numbers.items() if v > cell.limits[k]]
    assert failed, numbers


def _fault_state_unchanged(monkeypatch):
    from repro.core import fleet
    real = fleet.simulate_chunked_stream

    def broken(*a, state0=None, **kw):
        series, _ = real(*a, state0=state0, **kw)
        return series, state0

    monkeypatch.setattr(fleet, "simulate_chunked_stream", broken)


def _patch_slabs(monkeypatch, alter):
    from repro.serve import compile as sc
    for name in ("slab", "slab_aligned"):
        real = getattr(sc.StreamingService, name)

        def broken(self, t0, length, _real=real):
            j, ov = _real(self, t0, length)
            return alter(j, ov)

        monkeypatch.setattr(sc.StreamingService, name, broken)


def _fault_half_batch(monkeypatch):
    """Half the fleet left out, the sums doubled over the rest."""
    from repro.core import fleet
    real = fleet.simulate_chunked_stream

    def alter(j, ov):
        half = j.shape[1] // 2
        return j.at[:, half:].set(0), ov

    _patch_slabs(monkeypatch, alter)

    def doubled(*a, **kw):
        series, st = real(*a, **kw)
        return {k: (v if k in ("mu", "mu_k", "lam_norm") else 2 * v)
                for k, v in series.items()}, st

    monkeypatch.setattr(fleet, "simulate_chunked_stream", doubled)


def _fault_token_altered(monkeypatch):
    """One device's state index altered where the workload is made."""
    _patch_slabs(monkeypatch,
                 lambda j, ov: (j.at[0, 0].set(j[0, 0] % 72 + 1), ov))


@pytest.mark.parametrize("fault", [_fault_state_unchanged, _fault_half_batch,
                                   _fault_token_altered])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    numbers, ok = drive(tiny_cell(REPLAY[0]))
    assert not ok, numbers


def _fault_decision_altered(monkeypatch):
    """One wave's reply altered where the tick produces it: its offload
    decisions inverted."""
    from repro.serve import gateway
    real = gateway.PendingTick.resolve
    seen = []

    def broken(self):
        off, adm = real(self)
        if not seen and off.size:
            seen.append(True)
            off = ~off
        return off, adm

    monkeypatch.setattr(gateway.PendingTick, "resolve", broken)


def _fault_gateway_state_unchanged(monkeypatch):
    """Every tick runs from the state the first one started from."""
    from repro.serve import gateway
    real = gateway.GatewayCore.tick_async

    def broken(self, *a, **kw):
        if not hasattr(self, "_first_state"):
            self._first_state = jax_copy(self._state)
        self._state = jax_copy(self._first_state)
        return real(self, *a, **kw)

    monkeypatch.setattr(gateway.GatewayCore, "tick_async", broken)


def jax_copy(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(jnp.array, tree)


def _fault_gateway_half_batch(monkeypatch):
    """Only the first half of each wave reaches the tick; the rest are
    answered as local execution."""
    from repro.serve import gateway
    real = gateway.GatewayCore.tick_async

    def broken(self, idx, o, h, w):
        n = len(idx)
        pend = real(self, idx[: n // 2], o[: n // 2], h[: n // 2],
                    w[: n // 2])
        pad = n - n // 2
        off = jnp_pad(pend.off_p[: n // 2], pad)
        adm = jnp_pad(pend.adm_p[: n // 2], pad)
        return gateway.PendingTick(off_p=off, adm_p=adm, n_reports=n,
                                   bucket=pend.bucket,
                                   first_compile=pend.first_compile,
                                   dispatched_at=pend.dispatched_at)

    monkeypatch.setattr(gateway.GatewayCore, "tick_async", broken)


def jnp_pad(x, n):
    import jax.numpy as jnp
    return jnp.concatenate([x, jnp.zeros((n,), x.dtype)])


@pytest.mark.parametrize("fault", [_fault_decision_altered,
                                   _fault_gateway_state_unchanged,
                                   _fault_gateway_half_batch])
def test_broken_gateway_is_not_correct(fault, monkeypatch, held_root):
    fault(monkeypatch)
    numbers, ok = drive(tiny_cell(GATEWAY[0], held_root))
    assert not ok, numbers
