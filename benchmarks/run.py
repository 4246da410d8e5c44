# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
import argparse
import sys

from benchmarks.common import header


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: figs,convergence,controller,kernels,"
                         "compile_service,fleet_scale,topology,gateway,gain")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    header()
    if only is None or "figs" in only:
        from benchmarks import bench_paper_figs
        bench_paper_figs.run_all()
    elif "compile_service" in only:
        # figs runs it too; standalone target for the fast CI artifact
        # (synthetic pool — no classifier training)
        from benchmarks import bench_paper_figs
        bench_paper_figs.bench_compile_service()
    if only is None or "convergence" in only:
        from benchmarks import bench_convergence
        bench_convergence.run_all()
    if only is None or "controller" in only:
        from benchmarks import bench_controller
        bench_controller.run_all()
    if only is None or "kernels" in only:
        from benchmarks import bench_kernels
        bench_kernels.run_all()
    if only is None or "fleet_scale" in only:
        from benchmarks import bench_fleet_scale
        bench_fleet_scale.run_all()
    if only is None or "topology" in only:
        from benchmarks import bench_topology
        bench_topology.run_all()
    if only is None or "gateway" in only:
        from benchmarks import bench_gateway
        bench_gateway.run_all()
    if only is None or "gain" in only:
        from benchmarks import bench_gain
        bench_gain.run_all()
    print("benchmarks: done", file=sys.stderr)


if __name__ == '__main__':
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
