"""A whole serving run at a CPU size, the faults its check must see, and
the lower-precision control it must refuse.

The driver is called directly (the harness's look for a chip is skipped);
everything else is the run the chip does: weights from the seed, warm-up,
ramp, window, then the served tokens against the plain reference. Each
fault is planted in the program under the timed path and must turn
``correct`` false.
"""
import jax.numpy as jnp
import pytest

from bench import spec
from bench.tests.conftest import full_width_cell, run_tiny, tiny_cell

WORKLOADS = [w["name"] for w in spec.load_benchmark()["workloads"]]


def _correct(out) -> bool:
    return all(c.ok for c in out.checks.values())


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload):
    out = run_tiny(tiny_cell(workload))
    assert _correct(out), {k: (c.value, c.limit) for k, c in
                           out.checks.items()}
    assert out.attempted > 0 and out.failed == 0
    assert out.setup_split["compiles_in_window"] == 0
    # Warm-up compiled every program the traffic runs: nothing compiles
    # in the ramp either, where a missed shape would hide.
    assert out.setup_split["compiles_after_warm_up"] == 0
    assert out.end_to_end["output_tok_s"] > 0
    assert 0 < out.setup_s


@pytest.mark.parametrize("workload", WORKLOADS)
def test_altered_token_is_caught(monkeypatch, workload):
    """A token altered where it is produced: the sampler's pick moved to
    the next vocabulary id."""
    from repro.serving import sampling
    real = sampling.sample_tokens

    def altered(logits, *a, **k):
        return (real(logits, *a, **k) + 1) % logits.shape[-1]

    monkeypatch.setattr(sampling, "sample_tokens", altered)
    out = run_tiny(tiny_cell(workload))
    assert not out.checks["gap"].ok
    assert not _correct(out)


def test_answer_cut_short_is_caught(monkeypatch):
    """The server stops each answer at half the tokens it was asked for."""
    from repro.serving import engine

    class Halved(engine.Request):
        def __post_init__(self):
            self.max_new_tokens = max(2, self.max_new_tokens // 2)
            super().__post_init__()

    monkeypatch.setattr(engine, "Request", Halved)
    out = run_tiny(tiny_cell("slay124m-chat"), seconds=1.5)
    assert out.checks["wrong_length"].value > 0
    assert not _correct(out)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_step_returning_its_state_unchanged_is_caught(monkeypatch,
                                                      workload):
    """A decode step that leaves the slot state as it was (at the
    published widths: at toy widths the logits are too flat to show it
    against the chat cell's limit)."""
    from repro.models import api
    real = api.decode_step

    def frozen(params, cfg, cache, tokens, active=None):
        logits, _ = real(params, cfg, cache, tokens, active)
        return logits, cache._replace(pos=cache.pos + jnp.where(
            active, 1, 0).astype(cache.pos.dtype))

    monkeypatch.setattr(api, "decode_step", frozen)
    out = run_tiny(full_width_cell(workload), seed=3, seconds=2.0)
    assert not _correct(out), out.checks["gap"].value


@pytest.mark.parametrize("workload", WORKLOADS)
def test_float8_control_fails_the_limit(workload):
    """At the published widths (two layers, a small pool), the program's
    served tokens pass the limit and the reference computed in float8,
    the precision below the configuration's bfloat16, fails it."""
    out = run_tiny(full_width_cell(workload), seed=3, seconds=2.0,
                   control="float8_e4m3fn")
    gap = out.checks["gap"]
    assert gap.ok, (gap.value, gap.limit)
    assert out.setup_split["control_gap"] > gap.limit
