"""Quickstart: OnAlgo on a synthetic fleet, vs baselines and the oracle.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp
import numpy as np

from repro.core import (OnAlgoParams, StepRule, default_paper_space, oracle,
                        simulate, theory)
from repro.data.traces import TraceSpec, iid_trace


def main():
    space = default_paper_space(num_w=4)
    N, T = 8, 8000
    trace, true_rho = iid_trace(space, TraceSpec(T=T, N=N, task_prob=0.6,
                                                 seed=1))
    tables = space.tables()
    B = np.full(N, 0.08)  # 80 mW average power budget per device
    H = N * 0.25 * 441e6  # cloudlet capacity: 25% of always-offload load
    params = OnAlgoParams(B=jnp.asarray(B, jnp.float32), H=jnp.float32(H))

    print("== OnAlgo (the paper's algorithm) ==")
    series, final = simulate(trace, tables, params, StepRule.inv_sqrt(0.5),
                             true_rho=true_rho, with_true_rho=True)
    _, r_star = oracle.solve_lp(np.asarray(true_rho), tables, B, H)
    print(f"  oracle reward*      : {r_star:.4f}")
    print(f"  OnAlgo avg reward   : {np.mean(series['f_true']):.4f}")
    print(f"  optimality gap      : {theory.empirical_gap(series, r_star):.4f}")
    print(f"  constraint violation: {theory.positive_violation(series):.4f}")
    print(f"  avg power/device    : {np.mean(series['power'])/N*1e3:.1f} mW"
          f"  (budget {B[0]*1e3:.0f} mW)")
    print(f"  avg cloudlet load   : {np.mean(series['load']):.3e}"
          f"  (H = {H:.3e})")

    print("== Baselines ==")
    for algo in ("ato", "rco", "ocos"):
        s, _ = simulate(trace, tables, params, StepRule.inv_sqrt(0.5),
                        algo=algo, enforce_slot_capacity=True, ato_theta=0.8)
        print(f"  {algo.upper():5s} reward {np.mean(s['reward']):8.4f}"
              f"  power/dev {np.mean(s['power'])/N*1e3:6.1f} mW")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
