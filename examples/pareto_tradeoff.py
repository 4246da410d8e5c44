"""P3 (joint accuracy + delay) Pareto front over zeta — paper Fig. 8b.

    PYTHONPATH=src python examples/pareto_tradeoff.py
"""

from repro.serve.simulator import SimConfig, make_scenario, simulate_service


def main():
    _, pair, _, pool = make_scenario("hard", seed=0)
    print(f"{'zeta':>8s} {'accuracy':>9s} {'delay(ms)':>10s} "
          f"{'1/delay':>9s} {'offload%':>9s}")
    for zeta in (0.0, 50.0, 150.0, 400.0, 1000.0):
        out = simulate_service(SimConfig(num_devices=4, T=1500,
                                         algo="onalgo", B_n=0.08,
                                         H=2 * 441e6, zeta=zeta, seed=5),
                               pool)
        print(f"{zeta:8.0f} {out['accuracy']:9.3f} "
              f"{out['avg_delay_ms']:10.3f} "
              f"{1.0/out['avg_delay_ms']:9.3f} "
              f"{out['offload_frac']*100:8.1f}%")
    print("\nRaising zeta trades accuracy for delay-efficiency by "
          "offloading less (eq. 15).")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
