"""Test-only entry: runs one kind end to end at a toy size on the CPU.

``python -m benchmark.rehearse --workload <file under benchmark/rehearsal>``
takes its cell and configuration from ``benchmark/rehearsal/`` (toy sizes that
are never cells), needs no accelerator, and prints counts only — ``correct``,
``attempted``, ``failed`` and the names of the facts the kind produced — never
a time, a rate or any other device metric.  ``python -m benchmark.run`` is the
only entry that measures, and it refuses to run without a chip.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.rehearse")
    ap.add_argument("--workload", required=True, help="name of a file in benchmark/rehearsal/, without .json")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--cpu-devices", type=int, default=1)
    args = ap.parse_args(argv)

    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", args.cpu_devices)

    import importlib

    from . import harness

    cell = harness.load_json("rehearsal", args.workload + ".json")
    config = harness.load_json("rehearsal", cell["config"] + ".json")
    devices = jax.devices()[: int(cell["chips"])]
    if len(devices) < int(cell["chips"]):
        print(f"rehearse: cell asks for {cell['chips']} devices, have {len(devices)} "
              "(pass --cpu-devices)", file=sys.stderr)
        return 3
    ctx = harness.Context(
        cell_name=args.workload, cell=cell, config=config, seed=args.seed,
        seconds=args.seconds, trace=False, devices=devices, peaks=None, t_start=T_START,
    )
    outcome = importlib.import_module(f"benchmark.kinds.{cell['kind']}").run(ctx)
    print(json.dumps({
        "rehearsal": True, "correct": bool(outcome["correct"]),
        "attempted": int(outcome["attempted"]), "failed": int(outcome["failed"]),
        "devices": len(devices), "facts": sorted(outcome["facts"]),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
