"""Scenario engine tour: declarative workloads, batched sweeps, and the
time-chunked kernel engine.

Run:  PYTHONPATH=src python examples/scenario_sweep.py
"""

import numpy as np

from repro.core import StepRule
from repro.scenarios import (Scenario, compile_scenario, default_scenarios,
                             product_grid, run_scenario, sweep_simulate,
                             unstack_series)


def tour_scenarios():
    print("== every registered scenario kind ==")
    for sc in default_scenarios():
        series, final, c = run_scenario(sc, engine="scan", use_kernel=False)
        tasks = float(np.sum(np.asarray(series["tasks"])))
        offl = float(np.sum(np.asarray(series["offloads"])))
        print(f"  {sc.kind:14s} M={c.M:3d} offload_frac={offl / tasks:5.2f} "
              f"mu_final={float(final.mu):.4f}")


def batched_sweep():
    print("== one vmapped scan over a 3x2 (step, budget) grid ==")
    c = compile_scenario(Scenario("bursty", T=4000, N=8, seed=1))
    grid = product_grid(8, a_values=(0.2, 0.5, 1.0), beta_values=(0.5,),
                        B_values=(0.04, 0.08), H_values=(c.scenario.H,))
    series, _ = sweep_simulate(c.trace, c.tables, grid)
    for label, cell in unstack_series(series, grid):
        pw = float(np.mean(cell["power"])) / 8
        print(f"  {label:34s} avg_power={pw:.4f}")


def chunked_engine():
    print("== chunked Pallas engine vs per-slot scan ==")
    sc = Scenario("diurnal", T=512, N=32, seed=2)
    s_scan, f_scan, _ = run_scenario(sc, engine="scan", use_kernel=False)
    s_chunk, f_chunk, _ = run_scenario(sc, engine="chunked", chunk=16)
    drift = float(np.max(np.abs(np.asarray(f_scan.lam)
                                - np.asarray(f_chunk.lam))))
    print(f"  reward(scan)={float(np.sum(np.asarray(s_scan['reward']))):.2f} "
          f"reward(chunked)={float(np.sum(np.asarray(s_chunk['reward']))):.2f} "
          f"max|dlam|={drift:.2e}")


def composed_on_tiled_engine():
    print("== compose(churn, outage) on the device-tiled chunked engine ==")
    sc = Scenario("churn_outage", T=256, N=48, seed=3).with_extra(
        churn_frac=0.3, n_outages=2, outage_len=40)
    s_scan, f_scan, c = run_scenario(sc, engine="scan", use_kernel=False)
    s_tile, f_tile, _ = run_scenario(sc, engine="chunked", chunk=16,
                                     block_n=16)
    down = c.meta["down"]
    off = np.asarray(s_tile["offloads"])
    drift = float(np.max(np.abs(np.asarray(f_scan.lam)
                                - np.asarray(f_tile.lam))))
    print(f"  M={c.M} (outage-mirrored) | offloads during outages: "
          f"{off[down].sum():.0f} | outside: {off[~down].sum():.0f} | "
          f"max|dlam| scan vs tiled={drift:.2e}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    tour_scenarios()
    batched_sweep()
    chunked_engine()
    composed_on_tiled_engine()
    rule = StepRule.inv_sqrt(0.5)
    print("done", rule.a, rule.beta)
