"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: holds the cell's chips, builds the weights on the device
from the seed, warms this cell's shapes (that is ``setup_s``), measures
for ``--seconds``, then frees the program's state and compares what the
timed path produced with the plain reference. The last line of standard
output is the result. Fails, printing no result, where JAX finds no TPU,
fewer chips than the cell asks for, or a ``device_kind`` that the table
of peaks does not hold.

Everything that belongs to one cell is data: ``BENCHMARK.json`` names the
cell's configuration and traffic files and its metrics, and nothing here
branches on a name.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse
import json
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.lib import cells, compare, xplane  # noqa: E402
from benchmarks.lib.meter import CompileMeter  # noqa: E402
from benchmarks.lib.peaks import peaks  # noqa: E402


class Tracer:
    """The profiler over the first ``trace_seconds`` of the window (a
    whole window of a serving cell is millions of device events). The
    builder calls :meth:`tick` as the window goes; the per-layer numbers
    are of the traced stretch ``[t0, t1]``."""

    def __init__(self, on: bool, trace_seconds: float):
        self.on, self.trace_seconds = on, trace_seconds
        self.dir = None
        self.t0 = self.t1 = None

    def start(self) -> None:
        if not self.on:
            return
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()

    def tick(self) -> None:
        if (self.on and self.t1 is None
                and time.perf_counter() - self.t0 >= self.trace_seconds):
            self.stop()

    def stop(self) -> None:
        if not self.on or self.t1 is not None:
            return
        import jax

        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def reduced(self) -> dict:
        try:
            found = sorted(pathlib.Path(self.dir).rglob("*.xplane.pb"))
            return xplane.reduce(xplane.load(found[-1]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def find_devices(cell):
    """The cell's chips, or an error: a benchmark measures nothing on a
    CPU, on too few chips, or on a chip whose peaks are not on record."""
    import jax

    devices = jax.devices()
    if jax.default_backend() != "tpu" or len(devices) < cell.chips:
        raise SystemExit(
            f"benchmarks/run.py: {cell.name} needs {cell.chips} TPU chip(s); "
            f"JAX found backend {jax.default_backend()!r} with "
            f"{len(devices)} device(s). Run it on the chip.")
    peaks(devices[0].device_kind)
    return devices


def drive(cell, seed: int, seconds: float, trace: bool, devices,
          meter=None, out=sys.stdout, err=sys.stderr,
          chip_peaks=None) -> dict:
    """Everything of a run after the look for a chip (``chip_peaks``
    stands in for the table where a test drives this on a CPU)."""
    run = cells.builder(cell.config["builder"])(cell, seed,
                                                devices[:cell.chips])
    run.setup()
    setup_s = time.monotonic() - T_START
    compiled_before = meter.programs if meter else 0
    tracer = Tracer(trace, float(cell.traffic.get("trace_seconds", 8)))
    tracer.start()
    run.window(seconds, tracer)
    tracer.stop()
    in_window = (meter.programs - compiled_before) if meter else None
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell.chips])
    attempted, failed = run.attempted()
    values = dict(run.end_to_end(), setup_s=setup_s)
    ctx = None
    if trace:
        ctx = dict(run.trace_context((tracer.t0, tracer.t1)),
                   trace=tracer.reduced(),
                   traced_s=tracer.t1 - tracer.t0, chips=cell.chips,
                   peaks=chip_peaks or peaks(devices[0].device_kind),
                   end_to_end=values)
        ctx["counters"]["compiles_in_window"] = in_window
    run.free()
    rows = run.compare()

    dev = devices[0]
    result = {"correct": compare.verdict(rows), "attempted": attempted,
              "failed": failed, "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": int(peak)}}
    if trace:
        for m in cell.per_layer():
            value = cells.reader(m["reader"])(ctx, **m["args"])
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        red = ctx["trace"]
        result["device"].update(busy_s=red.get("busy_s", 0.0),
                                window_s=ctx["traced_s"])
        result["breakdown"] = {"device_ops": xplane.top_ops(red),
                               "idle_gaps": [list(g) for g in red["gaps"]]}
    else:
        for m in cell.end_to_end():
            if m["name"] in values:
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}
    if meter:
        result["setup"] = meter.report()
    result["compared"] = {n: {"value": v, "limit": lim, "at": note}
                          for n, v, lim, note in rows}
    for n, v, lim, note in rows:
        print(f"compared {n}: {v:.6g} (limit {lim:.6g}) {note}", file=err)
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load(ROOT, args.workload)

    from paddle_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()      # <checkout>/.jax_cache unless placed
    devices = find_devices(cell)
    drive(cell, args.seed, args.seconds, bool(args.trace), devices,
          CompileMeter())
    return 0


if __name__ == "__main__":
    sys.exit(main())
