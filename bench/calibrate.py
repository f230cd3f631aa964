"""Several runs of the benchmark's cells in one process, for what the
benchmark's own runs do not do: a sweep of the offered rate to find a
cell's knee, and the readings of the output check on many seeds, with the
lower-precision control beside them.

    python3 bench/calibrate.py OUT.jsonl '[{"w": "slay124m-chat", "seed": 7,
        "s": 20, "rate": 1.6, "control": "float8_e4m3fn"}, ...]'

Each job is one set-up, ramp, window and check of the cell ``w`` on the
chip, as ``bench/run.py`` makes it, with optional overrides: ``rate``
(requests per second of an open-loop cell), ``trace`` and ``control``
(the dtype the reference's control rounds to). One JSON line a
job goes to OUT.jsonl. The compiled programs are shared between jobs, so
the set-up times it prints are not those of a fresh process.
"""
import time

T_START = time.perf_counter()

import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run, spec  # noqa: E402


def main(argv):
    out_path, jobs = argv[0], json.loads(argv[1])
    devs = run.setup_jax(1)
    from bench import compiles
    monitor = compiles.Monitor()
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    for job in jobs:
        cell = spec.load_cell(job["w"])
        if "rate" in job:
            if "rate_per_s" not in cell.traffic:
                raise ValueError(f"{job['w']} is not an open-loop cell")
            cell = dataclasses.replace(
                cell, traffic=dict(cell.traffic, rate_per_s=job["rate"]))
        t0 = time.perf_counter()
        out = spec.runner(cell.kind)(
            cell, job["seed"], job["s"], bool(job.get("trace")), t_start=t0,
            monitor=monitor, control=job.get("control"))
        st = out.data["steps"]
        dec = [s for s in st if s.decode_ticks]
        res = {"job": job, "e2e": out.end_to_end, "setup_s": out.setup_s,
               "split": out.setup_split, "attempted": out.attempted,
               "failed": out.failed, "peak": out.memory_peak_bytes,
               "checks": {k: [c.value, c.limit, c.why]
                          for k, c in out.checks.items()},
               "decode_steps": len(dec),
               "prefill_steps": sum(1 for s in st if s.prefill_ticks),
               "decode_step_ms": (1e3 * sum(s.t1 - s.t0 for s in dec)
                                  / max(len(dec), 1)),
               "wall_s": time.perf_counter() - t0}
        if job.get("trace"):
            res["per_layer"] = run.metrics_of(cell, out, True,
                                              devs[0].device_kind)
            tr = out.trace
            res["trace"] = {k: tr[k] for k in ("busy_s", "window_s", "ops",
                                               "gaps")}
            res["per_op"] = sorted(([k, v[0], v[1], v[2]] for k, v in
                                    tr["per_op"].items()),
                                   key=lambda x: -x[1])[:40]
        line = json.dumps(res)
        print(line[:1500], flush=True)
        with open(out_path, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
