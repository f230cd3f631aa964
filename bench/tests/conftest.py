"""Shared helpers of the benchmark's CPU tests: a cell of the real
benchmark cut to a size the CPU runs in seconds (the harness's look for a
chip is skipped by calling the driver directly)."""
import dataclasses
import json
import time

import pytest

from bench import spec


def tiny_cell(workload: str, **arch_over) -> spec.Cell:
    cell = spec.load_cell(workload)
    arch = dict(cell.config["arch"], num_layers=2, d_model=64, num_heads=4,
                num_kv_heads=4, head_dim=16, d_ff=128, vocab_size=256,
                chunk_size=32)
    arch.update(arch_over)
    traffic = json.loads(json.dumps(cell.traffic))
    # Prompts of 16-64 on chunks of 32: some fit one chunk, some need two.
    traffic["pool"].update(num_slots=4, max_len=256, prefill_chunk=32)
    traffic["prompt"].update(mean=40, lo=16, hi=64)
    traffic["output"].update(mean=16, lo=8, hi=24)
    traffic["block"] = 8
    if "rate_per_s" in traffic:
        traffic["rate_per_s"] = 20.0
    if "clients" in traffic:
        traffic["clients"] = 6
    traffic["ramp_s"] = 0.5
    return dataclasses.replace(cell, config=dict(cell.config, arch=arch),
                               traffic=traffic)


def full_width_cell(workload: str, layers: int = 2) -> spec.Cell:
    """The cell at its published widths and vocabulary, with two layers
    and a pool, prompts and answers the CPU serves in seconds: the size
    at which the output check's control is tested."""
    cell = spec.load_cell(workload)
    traffic = json.loads(json.dumps(cell.traffic))
    traffic["pool"].update(num_slots=3, max_len=512, prefill_chunk=128)
    traffic["prompt"].update(lo=200, hi=200)     # two chunks, one a tail
    traffic["output"].update(mean=36, lo=24, hi=48)
    traffic["block"] = 8
    if "rate_per_s" in traffic:
        traffic["rate_per_s"] = 5.0
    if "clients" in traffic:
        traffic["clients"] = 4
    traffic["ramp_s"] = 0.5
    traffic["check"]["requests"] = 4
    arch = dict(cell.config["arch"], num_layers=layers, chunk_size=128)
    return dataclasses.replace(cell, config=dict(cell.config, arch=arch),
                               traffic=traffic)


def run_tiny(cell: spec.Cell, seed: int = 5, seconds: float = 1.0,
             tracing: bool = False, control: str | None = None):
    return spec.runner(cell.kind)(
        cell, seed, seconds, tracing, t_start=time.perf_counter(),
        monitor=_monitor(), control=control)


_MON = []


def _monitor():
    # JAX keeps monitoring listeners for the life of the process.
    from bench import compiles
    if not _MON:
        _MON.append(compiles.Monitor())
    return _MON[0]


@pytest.fixture
def tiny():
    return tiny_cell
