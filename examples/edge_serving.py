"""End-to-end edge analytics: trained classifiers + OnAlgo vs baselines.

Reproduces the paper's Sec. VI service on synthetic data: a fleet of camera
devices with weak local classifiers, a cloudlet with a strong one, a ridge
gain-predictor, bursty traffic, and the measured power/cycle constants.

Each policy's whole horizon runs as ONE vectorized fleet rollout: the run
is compiled to the core (Trace, tables, params, overlay) contract
(serve/compile.py) and scanned by fleet.simulate — not stepped slot by
slot in Python.

    PYTHONPATH=src python examples/edge_serving.py
"""

from repro.serve.simulator import SimConfig, make_scenario, simulate_service


def main():
    print("training classifier pair + predictor (hard/CIFAR-like)...")
    data, pair, predictor, pool = make_scenario("hard", seed=0)
    print(f"  local acc {pair.local_acc:.3f} | cloudlet acc "
          f"{pair.cloud_acc:.3f} | gap +{pair.cloud_acc-pair.local_acc:.3f}")

    print(f"{'policy':8s} {'accuracy':>9s} {'offload%':>9s} "
          f"{'power(mW)':>10s} {'delay(ms)':>10s}")
    for algo in ("local", "onalgo", "ato", "rco", "ocos", "cloud"):
        out = simulate_service(
            SimConfig(num_devices=4, T=2000, algo=algo, B_n=0.06,
                      H=2 * 441e6, seed=1), pool)
        print(f"{algo:8s} {out['accuracy']:9.3f} "
              f"{out['offload_frac']*100:8.1f}% "
              f"{out['avg_power_per_dev']*1e3:10.1f} "
              f"{out['avg_delay_ms']:10.2f}")
    print("\nOnAlgo holds near-OCOS accuracy at a fraction of the power and"
          "\nrespects the per-device budget — the paper's headline result.")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
