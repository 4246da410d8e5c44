"""Train-then-serve: a learned gain predictor in the serving loop.

Fits the paper's class-specific ridge predictor (Fig. 4) on synthetic
calibration pairs, drops it into the service tier as a
:class:`~repro.gain.ModelGain`, and scores the decisions it drives
against the oracle gain tables — then freezes the model back into a
``PrecomputedPool`` and shows the frozen tables replay the live model
bit for bit.

    PYTHONPATH=src python examples/gain_predictor.py
"""

import numpy as np

from repro.gain import (ModelGain, OverlayGain, TableGain, fit_ridge_gain,
                        oracle_pool, synthetic_gain_problem)
from repro.serve.gateway import GatewayCore
from repro.serve.simulator import SimConfig, simulate_service


def main():
    S, C = 512, 10
    probs, gains = synthetic_gain_problem(S=S, C=C, seed=0)
    pool = oracle_pool(probs, gains, seed=0)
    sim = SimConfig(num_devices=16, T=400, algo="onalgo", seed=4)

    print("== Train (class-specific ridge, closed form) ==")
    model = fit_ridge_gain(probs, gains)
    phi = np.asarray(model.apply(np.asarray(probs, np.float32))[0])
    print(f"  calibration samples : {S}")
    print(f"  gain MAE            : {np.abs(phi - gains).mean():.4f}"
          "  (paper Fig. 4: ~0.12)")

    print("== Serve under each gain source ==")
    sources = {"table (oracle)": TableGain(), "overlay": OverlayGain(),
               "model (ridge)": ModelGain(model, probs)}
    acc = {}
    for name, src in sources.items():
        out = simulate_service(sim, pool, gain_source=src)
        acc[name] = out["accuracy"]
        print(f"  {name:15s} accuracy {out['accuracy']:.4f}"
              f"  offload {out['offload_frac']:.3f}")
    regret = (acc["table (oracle)"] - acc["model (ridge)"]) \
        / max(acc["table (oracle)"], 1e-9)
    print(f"  model regret vs oracle: {regret:+.4f}")

    print("== Freeze the model into pool tables ==")
    mg = ModelGain(model, probs)
    frozen = mg.to_pool_tables(pool, sim)
    live = simulate_service(sim, pool, gain_source=mg)
    replay = simulate_service(sim, frozen, gain_source=TableGain())
    match = all(replay[k] == live[k] for k in live)
    print(f"  frozen-table replay bit-identical: {match}")
    assert match, "frozen tables diverged from the live model"

    print("== Live gateway with the model in the loop ==")
    core = GatewayCore.for_sim(sim, pool, gain_source=mg)
    print(f"  GatewayCore.for_sim ready: N={core.N}, M={core.M}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
