"""What the serving drivers share: the engine under test, its warm-up,
the recorder of every delivered token, and the output check.

The engine is the program's ``ContinuousServingEngine`` driven through
``submit()`` and ``step()`` only: greedy sampling, no journal, no prefix
cache, every request ``eos_id=-1`` so it runs to its output length.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import numpy as np

from bench import generate


def arch_config(cfg: dict):
    """The program's ArchConfig for a configuration file."""
    from repro.configs.base import ArchConfig
    return ArchConfig(name=cfg["name"], **cfg["arch"])


def build_engine(cfg: dict, params, pool: dict):
    from repro.configs.base import ServingConfig
    from repro.launch.mesh import make_host_mesh
    from repro.serving.engine import ContinuousServingEngine
    serving = ServingConfig(num_slots=pool["num_slots"],
                            max_len=pool["max_len"],
                            prefill_chunk=pool["prefill_chunk"],
                            macro_ticks=pool["macro_ticks"],
                            temperature=0.0)
    return ContinuousServingEngine(arch_config(cfg), params,
                                   make_host_mesh(), serving=serving,
                                   clock=time.perf_counter)


def warm_up(eng, traffic: dict):
    """Compile every shape the cell's traffic uses, and no other: one
    request of each prompt length the mix sends, so each chunk it splits
    into runs as it will in the window (a prompt's first chunk starts from
    a fresh cache, which is a program of its own), with the first-token
    sampler, slot install and reset, and the decode macro-step. Only the
    first request decodes; the others end at their first token, so many
    lengths cost no macro-steps."""
    from repro.serving.engine import Request
    prompts, _ = generate.lengths(traffic)
    K = traffic["pool"]["macro_ticks"]
    for i, n in enumerate(prompts):
        eng.submit(Request(np.full(int(n), 3, np.int32),
                           max_new_tokens=2 * K + 1 if i == 0 else 1,
                           eos_id=-1, arrival_time=float(eng.tick)))
    while eng.step():
        pass
    jax.block_until_ready(eng.pool)


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    decode_ticks: int
    prefill_ticks: int
    tokens: int
    busy_slots: int      # slots holding a request after the step
    waiting: int         # requests sent and not yet admitted after it


class Recorder:
    """Every token the engine delivers, stamped on the host clock."""

    def __init__(self, eng, capture):
        self.eng = eng
        self.capture = capture
        self.clock = time.perf_counter
        self.due: dict[int, float] = {}
        self.req: dict[int, generate.Req] = {}
        self.admit: dict[int, float] = {}
        self.token_times: dict[int, list] = {}
        self.tokens: dict[int, list] = {}
        self.finish: dict[int, tuple[float, str]] = {}
        self.steps: list[Step] = []
        self._unadmitted: set[int] = set()
        self.finished_rids: list[int] = []
        self._n_tok = 0

    def on_token(self, rid: int, tok: int):
        self.token_times[rid].append(self.clock())
        self.tokens[rid].append(tok)
        self._n_tok += 1

    def on_finish(self, rid: int, reason: str):
        self.finish[rid] = (self.clock(), reason)
        self.finished_rids.append(rid)

    def submit(self, r: generate.Req, due_abs: float) -> int:
        from repro.serving.engine import Request
        with self.capture.annotate("bench.submit"):
            rid = self.eng.submit(Request(
                r.prompt, max_new_tokens=r.max_new, eos_id=-1,
                arrival_time=float(self.eng.tick), on_token=self.on_token,
                on_finish=self.on_finish))
        self.due[rid], self.req[rid] = due_abs, r
        self.token_times[rid], self.tokens[rid] = [], []
        self._unadmitted.add(rid)
        return rid

    def step(self) -> bool:
        m = self.eng.metrics
        d0, p0, n0 = m.decode_ticks, m.prefill_ticks, self._n_tok
        with self.capture.annotate("bench.step"):
            t0 = self.clock()
            more = self.eng.step()
            t1 = self.clock()
        if self._unadmitted:
            per = m.per_request
            for rid in [r for r in self._unadmitted
                        if per[r].admitted is not None]:
                self.admit[rid] = t0
                self._unadmitted.discard(rid)
        self.steps.append(Step(t0, t1, m.decode_ticks - d0,
                               m.prefill_ticks - p0, self._n_tok - n0,
                               self.eng.sched.occupancy,
                               len(self._unadmitted)))
        return more

    def idle(self) -> bool:
        s = self.eng.sched
        return not (s.active or s.ready or s.waiting
                    or self.eng._prefill is not None)

    def step_label(self, name: str, i: int) -> str:
        """Trace label of the i-th ``bench.step`` span of the window."""
        if name != "bench.step" or i >= len(self._window_steps):
            return name
        s = self._window_steps[i]
        return ("bench.step.decode" if s.decode_ticks else
                "bench.step.prefill" if s.prefill_ticks else
                "bench.step.idle")

    def mark_window(self, i0: int, i1: int):
        self._window_steps = self.steps[i0:i1]


def load_of(steps: list[Step], num_slots: int) -> dict:
    """How full the pool and the queue were over a window's steps, each
    step weighted by its wall time: the mean share of slots holding a
    request, and the mean number waiting in each quarter of the window
    (a queue that grows from quarter to quarter is above the knee)."""
    if not steps:
        return {}
    w = np.array([s.t1 - s.t0 for s in steps])
    busy = np.array([s.busy_slots for s in steps], float)
    quarters = np.array_split(np.array([s.waiting for s in steps], float),
                             4)
    wq = np.array_split(w, 4)
    return {"slots_busy_share": round(float((busy * w).sum() / w.sum()
                                            / num_slots), 4),
            "waiting_by_quarter": [round(float((q * x).sum() / x.sum()), 2)
                                   if x.sum() > 0 else None
                                   for q, x in zip(quarters, wq)]}


def pick_sample(rec: Recorder, seed: int, n: int) -> list[int]:
    """Requests to check: the one served most tokens, and the rest drawn
    from the seed among those served at least two. A request still in a
    slot when the run ends is checked over the tokens it was served, so
    the long answers of a cell whose requests outlast the window count."""
    served = [rid for rid, toks in rec.tokens.items() if len(toks) >= 2]
    if not served:
        return []
    longest = max(served, key=lambda r: (len(rec.tokens[r]),
                                         len(rec.req[r].prompt), -r))
    rest = sorted(r for r in served if r != longest)
    rng = generate.rng_for(seed, 3)
    k = min(n - 1, len(rest))
    pick = list(rng.choice(rest, size=k, replace=False)) if k else []
    return [longest] + [int(r) for r in pick]


def served_gaps(model, params, cfg: dict, seqs, pad_to: int, n_out: int,
                control: str | None = None) -> dict:
    """Widest gap by which a served token's logit lies below the
    reference's best, over every served token of the sampled requests.

    ``model``: the configuration's model module, whose ``logits`` is the
    reference. ``seqs``: [(prompt, served tokens)]. The reference runs
    once over each prompt followed by its served tokens (teacher-forced),
    padded to one length so it compiles once. With ``control`` (a lower
    precision) it also gives the gap of the token that precision ranks
    first at each position: the reading a program in that precision would
    give."""
    fns = {prec: jax.jit(functools.partial(model.logits, cfg=cfg,
                                           prec=prec))
           for prec in ("float32", control) if prec}
    worst, worst_c, n_tok = 0.0, 0.0, 0
    for prompt, served in seqs:
        toks = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        L, n = len(toks), len(served)
        pad = np.zeros(pad_to, np.int32)
        pad[:L] = toks
        idx = np.zeros(n_out, np.int32)
        idx[:n] = len(prompt) - 1 + np.arange(n)
        lg = fns["float32"](params, tokens=pad, idx=idx)
        lg = np.asarray(lg)[:n]
        best = lg.max(-1)
        gap = best - lg[np.arange(n), np.asarray(served)]
        worst = max(worst, float(gap.max()))
        n_tok += n
        if control:
            lc = np.asarray(fns[control](params, tokens=pad,
                                           idx=idx))[:n]
            top = lc.argmax(-1)
            worst_c = max(worst_c, float((best - lg[np.arange(n),
                                                     top]).max()))
    # Nothing to compare is no pass: a run that served no request two
    # tokens reads an infinite gap.
    out = {"gap": worst if n_tok else float("inf"), "tokens": n_tok}
    if control:
        out["control_gap"] = worst_c
    return out


def memory_peak() -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


TAIL_S = 60.0   # how long past the close a window's requests are awaited
# A traced run traces the window's last TRACE_S seconds and its tail: the
# profiler takes minutes to write out a whole window of a prefill-heavy
# cell, and a run has to end within six.
TRACE_S = 20.0


def run(driver, cell, seed: int, seconds: float, tracing: bool, *,
        t_start: float, monitor, control: str | None = None):
    """One serving cell run by its traffic kind's ``driver``, which gives
    the arrivals (``Source``) and the end-to-end metrics
    (``end_to_end``)."""
    src = driver.Source(cell.traffic, seed, cell.config["arch"]["vocab_size"])
    out = run_cell(cell, seed, seconds, tracing, t_start=t_start,
                   monitor=monitor, source=src, control=control)
    out.end_to_end = driver.end_to_end(out.data["rec"], *out.data["window"])
    return out


def run_cell(cell, seed: int, seconds: float, tracing: bool, *,
             t_start: float, monitor, source, control: str | None = None):
    """Set up, ramp, measure one window, then check the served tokens.

    ``source`` decides when requests are sent (open or closed loop):
    ``start(rec, T0)``, ``pump(now)`` submits what is due,
    ``next_due()`` is the next send time or None."""
    import gc

    from bench import spec, weights
    from bench import trace as trace_lib
    from bench.result import Check, Outcome

    cfg, traffic = cell.config, cell.traffic
    arch, pool = cfg["arch"], traffic["pool"]
    clock = time.perf_counter
    split = {"process_start_s": round(clock() - t_start, 3)}
    a = clock()
    model = spec.model(cfg)
    params = weights.make(arch, seed, model)
    jax.block_until_ready(params)
    split["weights_s"] = round(clock() - a, 3)
    a = clock()
    eng = build_engine(cfg, params, pool)
    split["engine_s"] = round(clock() - a, 3)
    a = clock()
    warm_up(eng, traffic)
    split["warm_up_s"] = round(clock() - a, 3)
    split.update(monitor.snapshot())

    cap = trace_lib.Capture(tracing)
    rec = Recorder(eng, cap)
    T0 = clock()
    W0, W1 = T0 + traffic["ramp_s"], None
    source.start(rec, T0)
    opened = closed = traced = False
    i0 = i1 = it = c0 = c1 = q0 = q1 = 0
    while True:
        now = clock()
        source.pump(now)
        if not opened and now >= W0:
            opened, W0 = True, now
            W1 = W0 + seconds
            i0, c0 = len(rec.steps), monitor.compiles
            q0 = len(rec._unadmitted)
            tr0, gc0 = monitor.trace_s, monitor.gc_s
            monitor.gc_max_s = 0.0
        if opened and not traced and now >= W1 - TRACE_S:
            traced, it = True, len(rec.steps)
            cap.start()
        if opened and not closed and now >= W1:
            closed, W1 = True, now
            i1, c1 = len(rec.steps), monitor.compiles
            q1 = len(rec._unadmitted)
            split["traced_in_window_s"] = round(monitor.trace_s - tr0, 3)
            split["gc_in_window_s"] = round(monitor.gc_s - gc0, 3)
            split["gc_longest_s"] = round(monitor.gc_max_s, 3)
        if closed:
            due = [r for r, d in rec.due.items() if W0 <= d < W1]
            if (all(rec.token_times[r] for r in due)
                    or now >= W1 + TAIL_S):
                # The trace runs on to here, so that writing it out does
                # not stall the requests that were due in the window.
                cap.stop()
                break
        if rec.idle():
            marks = [source.next_due(), None if opened else W0,
                     None if closed else W1]
            wake = min((m for m in marks if m is not None), default=now)
            with cap.annotate("bench.sleep"):
                time.sleep(max(0.0, min(wake, now + 0.05) - clock()))
            continue
        rec.step()
    split["ramp_s"] = round(W0 - T0, 3)
    split["compiles_in_window"] = c1 - c0
    split["compiles_after_warm_up"] = c1 - split["compiles"]
    split["queue_open_close"] = [q0, q1]
    split.update(load_of(rec.steps[i0:i1], pool["num_slots"]))
    setup_s = W0 - t_start
    window_steps = rec.steps[i0:i1]
    rec.mark_window(it, len(rec.steps))

    peak = memory_peak()
    rec.eng = None
    del eng
    gc.collect()

    due = {r: d for r, d in rec.due.items() if W0 <= d < W1}
    first = {r: ts[0] for r, ts in rec.token_times.items() if ts}
    unanswered = sum(1 for r in due if r not in first)
    short = sum(1 for r in rec.finished_rids
                if len(rec.tokens[r]) != rec.req[r].max_new)
    n_check = int(traffic["check"]["requests"])
    sample = pick_sample(rec, seed, n_check)
    prompts, outs = generate.lengths(traffic)
    n_out = int(outs.max())
    pad_to = -(-(int(prompts.max()) + n_out) // 128) * 128
    seqs = [(rec.req[r].prompt, np.asarray(rec.tokens[r], np.int32))
            for r in sample]
    a = clock()
    gaps = served_gaps(model, params, cfg, seqs, pad_to, n_out, control)
    split["reference_s"] = round(clock() - a, 3)
    checks = {
        "gap": Check(gaps["gap"], cell.limits["gap"],
                     f"widest logit gap of {gaps['tokens']} served tokens "
                     f"of {len(seqs)} requests below the fp32 reference's "
                     f"best"),
        "unanswered": Check(unanswered, 0,
                            "requests due in the window with no token "
                            f"{TAIL_S:.0f} s past its close"),
        "wrong_length": Check(short, 0, "finished requests served another "
                              "number of tokens than they asked for"),
    }
    if control:
        split["control_gap"] = gaps["control_gap"]
    tr = None
    if tracing:
        a = clock()
        tr = trace_lib.reduce(cap.dir, cap.window_s, rec.step_label)
        cap.close()
        split["trace_write_s"] = round(cap.stop_s, 3)
        split["trace_read_s"] = round(clock() - a, 3)
    data = {"rec": rec, "window": (W0, W1), "steps": window_steps,
            "traced_steps": rec.steps[it:], "arch": arch, "pool": pool}
    return Outcome(setup_s=setup_s, end_to_end={}, attempted=len(due),
                   failed=unanswered, checks=checks,
                   memory_peak_bytes=peak, setup_split=split, data=data,
                   trace=tr)
