"""The readings the limits of ``correct`` are set from, on the chip at a
cell's own size, several seeds in one process:

    python3 benchmarks/readings.py --workload <name> --seeds 1,2,3 \\
        [--control] [--faults half_batch,...] [--seconds S]

For each seed: the program through its set-up (and, for a serving cell, a
short window), its state freed, then every number compared with the plain
reference; with ``--control`` also the control's reading (the reference
in the program's place, one precision lower) and with ``--faults`` each
planted fault's. One JSON line per seed on standard output. Not part of a
benchmark run.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import run as harness  # noqa: E402
from benchmarks.lib import cells  # noqa: E402


def rows_dict(rows) -> dict:
    return {n: [v, note] for n, v, _, note in rows}


def read_seed(cell, seed: int, devices, seconds: float, control: bool,
              faults) -> dict:
    run = cells.builder(cell.config["builder"])(cell, seed,
                                                devices[:cell.chips])
    run.setup()
    if seconds > 0:
        run.window(seconds, None)
    run.free()
    out = {"seed": seed, "program": rows_dict(run.compare())}
    if control:
        out["control"] = rows_dict(run.control())
    for kind in faults:
        out[f"fault:{kind}"] = rows_dict(run.fault(kind))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    cell = cells.load(ROOT, args.workload)

    from paddle_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = harness.find_devices(cell)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(read_seed(
            cell, seed, devices, args.seconds, args.control,
            [f for f in args.faults.split(",") if f])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
