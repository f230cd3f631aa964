"""Record the small traced engine run that ``test_engine_trace.py`` reads.

    python3 bench/tests/record_engine_trace_fixture.py \
        bench/tests/fixtures/engine_trace

Run on the chip. The program's own serving engine (SLAY attention at the
published widths, two layers, a four-slot pool) serves two one-chunk
requests from submission to idle under the benchmark's profiler settings,
each ``step()`` inside a ``bench.step`` span and one 30 ms ``bench.sleep``
between two steps. The same schedule runs once before tracing, so nothing
compiles in the trace. The ``/host:metadata`` plane (the compiled
programs' HLO, ~1 MB, which no reader looks at) is left out of the file.
What the tests expect of it is written beside it in ``expect.json``.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import serving, spec, trace, weights  # noqa: E402

LAYERS, SEED = 2, 1
POOL = {"num_slots": 4, "max_len": 256, "prefill_chunk": 64,
        "macro_ticks": 4}
PROMPTS = (40, 40)                # one chunk each, one chunk program
ANSWER = POOL["macro_ticks"] + 1  # the first token and one macro-step
SLEEP_AFTER, SLEEP_S = 3, 0.03
METADATA_PLANE = "/host:metadata"


def schedule(eng, cap) -> int:
    """Submit the requests, then step the engine until it idles; returns
    the number of steps."""
    from repro.serving.engine import Request
    for n in PROMPTS:
        eng.submit(Request(np.full(n, 5, np.int32), max_new_tokens=ANSWER,
                           eos_id=-1, arrival_time=float(eng.tick)))
    steps, more = 0, True
    while more:
        if steps == SLEEP_AFTER:
            with cap.annotate("bench.sleep"):
                time.sleep(SLEEP_S)
        with cap.annotate("bench.step"):
            more = eng.step()
        steps += 1
    jax.block_until_ready(eng.pool)
    return steps


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        value |= (b & 0x7F) << shift
        i += 1
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes):
    """(field number, wire type, raw bytes of the whole field, payload of
    a length-delimited field) of each top-level field of a message."""
    i = 0
    while i < len(buf):
        start = i
        tag, i = _varint(buf, i)
        kind, payload = tag & 7, None
        if kind == 0:
            _, i = _varint(buf, i)
        elif kind == 1:
            i += 8
        elif kind == 5:
            i += 4
        elif kind == 2:
            n, i = _varint(buf, i)
            payload, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"wire type {kind} in an XSpace")
        yield tag >> 3, kind, buf[start:i], payload


def drop_plane(xspace: bytes, name: str) -> bytes:
    """The serialized XSpace without its plane called ``name`` (XSpace
    field 1 holds the planes; XPlane field 2 is the name)."""
    out = []
    for field, kind, raw, payload in _fields(xspace):
        if field == 1 and kind == 2 and any(
                f == 2 and p == name.encode()
                for f, _, _, p in _fields(payload)):
            continue
        out.append(raw)
    return b"".join(out)


def record(out_dir: str):
    cfg = json.load(open(spec.config_path("slayformer-124m",
                                          spec.load_benchmark())))
    cfg = dict(cfg, arch=dict(cfg["arch"], num_layers=LAYERS))
    params = weights.make(cfg["arch"], SEED, spec.model(cfg))
    eng = serving.build_engine(cfg, params, POOL)
    schedule(eng, trace.Capture(False))         # compile everything
    m = eng.metrics
    before = (m.decode_dispatches, m.prefill_ticks, m.decode_ticks)
    cap = trace.Capture(True)
    cap.start()
    steps = schedule(eng, cap)
    cap.stop()
    src = os.path.join(cap.dir, "plugins", "profile")
    run, = os.listdir(src)
    pb, = [f for f in os.listdir(os.path.join(src, run))
           if f.endswith(".xplane.pb")]
    dst = os.path.join(out_dir, "plugins", "profile", "run")
    os.makedirs(dst, exist_ok=True)
    with open(os.path.join(src, run, pb), "rb") as f:
        xspace = drop_plane(f.read(), METADATA_PLANE)
    with open(os.path.join(dst, "trace.xplane.pb"), "wb") as f:
        f.write(xspace)
    cap.close()
    with open(os.path.join(out_dir, "expect.json"), "w") as f:
        json.dump({"window_s": cap.window_s, "steps": steps,
                   "decode_steps": m.decode_dispatches - before[0],
                   "prefill_steps": m.prefill_ticks - before[1],
                   "decode_ticks": m.decode_ticks - before[2],
                   "installs": len(PROMPTS),
                   "chunks": sum(-(-n // POOL["prefill_chunk"])
                                 for n in PROMPTS),
                   "sleep_s": SLEEP_S}, f, indent=1)


def main(out_dir: str):
    if jax.devices()[0].platform != "tpu":
        sys.exit("record the fixture on the chip")
    record(out_dir)


if __name__ == "__main__":
    main(sys.argv[1])
