"""``python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

Runs one cell once in this process, which holds the chip, and prints as the
last line of its standard output the one JSON object the benchmark's contract
fixes.  Refuses to run without a TPU, with fewer chips than the cell asks
for, or on a ``device_kind`` that ``benchmark/peaks.json`` does not list: it
then exits non-zero and prints no result.
"""

import time

T_START = time.time()   # set-up is counted from here, before any heavy import

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from . import harness

    manifest = harness.load_manifest()
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        print(f"benchmark: no cell {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2
    cell = harness.load_json("workloads", args.workload + ".json")
    config = harness.load_json("configs", cell["config"] + ".json")

    # The compile cache goes where the program puts it, before any compile.
    from pytorch_distributed_training_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    devices = jax.devices()
    chips = int(cells[args.workload]["chips"])
    if devices[0].platform != "tpu":
        print(f"benchmark: needs a TPU, JAX found {devices[0].platform!r}; "
              "a CPU number is never written under a device metric's name", file=sys.stderr)
        return 3
    if len(devices) < chips:
        print(f"benchmark: cell asks for {chips} chip(s), JAX found {len(devices)}", file=sys.stderr)
        return 3
    devices = devices[:chips]
    peaks = harness.load_peaks(devices[0].device_kind)

    ctx = harness.Context(
        cell_name=args.workload, cell=cell, config=config, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), devices=devices, peaks=peaks,
        t_start=T_START,
    )
    line = harness.run_cell(ctx, manifest)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
