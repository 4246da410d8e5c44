"""What every cell shares: finding a cell's files by name, the device,
the compile cache and compile count, the trace, and the result line.

A cell names a configuration and a traffic mix.  Each is found by name:
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json`` (whose
``kind`` names the runner ``bench/runners/<kind>.py``), and the limits
its check holds the run to, ``bench/limits/<cell>.json``.  A per-layer
metric is ``bench/metrics/<metric>.py``; a kernel's operation and byte
counts are ``bench/roofline/<kernel>.py``; the chip's peaks are
``bench/peaks.json``, keyed by ``device_kind``.  Adding a cell or a
metric adds files; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CellError(Exception):
    """A cell, or one of its files, is missing or malformed."""


def load_module(path: Path):
    """Import a file under ``bench/`` by its path (names may hold dots)."""
    if not path.is_file():
        raise CellError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    if not path.is_file():
        raise CellError(f"no file {path}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    runner_path: Path
    end_to_end: list  # the BENCHMARK.json entries this cell reports
    per_layer: list


@dataclasses.dataclass
class RunContext:
    cell: Cell
    seed: int

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic


def workload_seed(seed: int) -> int:
    """The run's ``--seed`` as the system's workload seed, which is a
    non-negative int32."""
    return int(seed) % (2**31 - 1)


def reports(metric: dict, cell: str, e2e_names) -> bool:
    """Whether ``cell`` reports ``metric``: the cells it lists, else
    every cell that reports the end-to-end metric it moves (or, for an
    end-to-end metric without a list, every cell)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    here = root / "bench"
    config = read_json(here / "configs" / f"{w['config']}.json")
    traffic = read_json(here / "traffic" / f"{w['traffic']}.json")
    limits = read_json(here / "limits" / f"{name}.json")["limits"]
    e2e = [m for m in bench["end_to_end"] if reports(m, name, ())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                runner_path=here / "runners" / f"{traffic['kind']}.py",
                end_to_end=e2e, per_layer=per_layer)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set (JAX reads it itself), else ``<checkout>/.jax_cache`` —
    a fixed path, since the path is part of the cache's key."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCount:
    """JAX's compile events since the last ``reset``: backend compiles
    (a cache hit loads an executable instead), their seconds, and the
    persistent cache's hits and misses."""

    def __init__(self):
        import jax
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def reset(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0

    def _on_time(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __str__(self):
        return (f"{self.compiles} backend compiles ({self.compile_s:.3f} s), "
                f"cache hits {self.hits}, misses {self.misses}")


def tpu_devices(chips: int):
    """The chips a cell runs on, or None where JAX finds no TPU or fewer
    chips than the cell asks for (the benchmark never falls back)."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        log(f"no accelerator: {e}")
        return None
    if devs[0].platform != "tpu":
        log(f"no TPU: JAX's platform is {devs[0].platform!r}")
        return None
    if len(devs) < chips:
        log(f"the cell needs {chips} chips, JAX finds {len(devs)}")
        return None
    return devs[:chips]


def peak_bytes(devs) -> int:
    """The peak bytes in use on the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def peaks(device_kind: str) -> dict:
    table = read_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise CellError(f"no peaks for device kind {device_kind!r} in "
                        "bench/peaks.json")
    return table["devices"][device_kind]
