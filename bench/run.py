"""Run one benchmark cell once on the chip and print the result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, compile-cache load, warm-up of the cell's
own shapes, the traffic's ramp to steady state) counts in ``setup_s``;
then the window is measured for ``--seconds``; then the served output is
checked against the plain reference. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiled
window. Anything but a TPU, or fewer chips than the cell asks for, exits
non-zero with no result line.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import spec  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def setup_jax(chips: int):
    """The compile cache at its fixed path (the environment's, if set) and
    the device check. Returns the devices, or exits."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: no TPU (found {devs[0].platform}); refusing to "
                 f"measure anything else")
    if len(devs) < chips:
        sys.exit(f"bench: cell needs {chips} chips, found {len(devs)}")
    return devs


def metrics_of(cell, out, tracing: bool, device_kind: str) -> dict:
    """The cell's end-to-end metrics, or with tracing its per-layer ones.
    A reader that finds nothing to read is left out of the line."""
    res = {}
    if not tracing:
        for m in cell.end_to_end:
            v = out.setup_s if m.name == "setup_s" else out.end_to_end.get(
                m.name)
            if v is not None:
                res[m.name] = {"value": v, "unit": m.unit}
        return res
    ctx = dict(out.data, trace=out.trace, peaks=spec.peaks(device_kind))
    for m in cell.per_layer:
        v = spec.metric_reader(m.name).read(ctx)
        if v is not None:
            res[m.name] = {"value": v, "unit": m.unit}
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devs = setup_jax(cell.chips)
    spec.peaks(devs[0].device_kind)        # an unknown chip is an error
    from bench import compiles
    from bench.result import device_info, emit
    monitor = compiles.Monitor()
    out = spec.runner(cell.kind)(cell, args.seed, args.seconds,
                                 bool(args.trace), t_start=T_START,
                                 monitor=monitor)
    metrics = metrics_of(cell, out, bool(args.trace), devs[0].device_kind)
    emit(out, metrics, device_info(cell.chips, out.memory_peak_bytes,
                                   out.trace))
    return 0


if __name__ == "__main__":
    sys.exit(main())
