"""The mixed stack's work counts (``bench/work_hybrid.py``) against hand
arithmetic at granite-4.0-h-micro's published sizes, and its two readers
when the window has nothing to read."""
import json
import types

import pytest

from bench import spec, work_hybrid

ARCH = json.load(open(spec.config_path("granite-4.0-h-micro",
                                       spec.load_benchmark())))["arch"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_layer_counts():
    assert work_hybrid.layer_counts(ARCH) == (36, 4)


def test_matmul_params_by_hand():
    # Mamba: in 2048 x (2*4096 + 2*128 + 64 = 8512), out 4096 x 2048;
    # attention: 2048 x 64 x (2*32 + 2*8); MLP 3 x 2048 x 8192 in all 40;
    # tied unembedding 100352 x 2048.
    mamba = 36 * (2048 * 8512 + 4096 * 2048)         # 929,562,624
    attn = 4 * 2048 * 64 * 80                        # 41,943,040
    mlp = 40 * 3 * 2048 * 8192                       # 2,013,265,920
    unembed = 100352 * 2048                          # 205,520,896
    assert work_hybrid.matmul_params(ARCH) == mamba + attn + mlp + unembed
    assert work_hybrid.matmul_params(ARCH) == 3_190_292_480


def test_weight_bytes_by_hand():
    # Besides the matmul weights, bf16: per Mamba layer conv weights and
    # bias 5 x 4352, A, dt bias and D 3 x 64, gated-norm scale 4096; two
    # norm scales a layer and the final norm.
    small = 36 * (5 * 4352 + 3 * 64 + 4096) + 2 * 40 * 2048 + 2048
    assert work_hybrid.weight_bytes(ARCH) == 2 * (3_190_292_480 + small)


def test_state_bytes_per_slot_by_hand():
    assert work_hybrid.ssm_state_bytes(ARCH) == 36 * 64 * 64 * 128 * 4
    assert work_hybrid.ssm_state_bytes(ARCH) == 75_497_472      # 75.5 MB
    assert work_hybrid.conv_tail_bytes(ARCH) == 36 * 3 * 4352 * 4
    assert work_hybrid.kv_row_bytes(ARCH) == 4 * 2 * 8 * 64 * 2


def test_token_flops_and_bytes_by_hand():
    c = 300
    flops = (2 * 3_190_292_480 + 36 * 64 * 5 * 64 * 128
             + 4 * 4 * c * 64 * 32)
    assert work_hybrid.decode_token_flops(ARCH, c) == flops
    nbytes = 2 * (75_497_472 + 36 * 3 * 4352 * 4) + (c + 1) * 8192
    assert work_hybrid.decode_token_bytes(ARCH, c) == nbytes


def _ctx(steps, token_times, prompts):
    rec = types.SimpleNamespace(
        token_times=token_times,
        req={r: types.SimpleNamespace(prompt=[0] * n)
             for r, n in prompts.items()})
    return {"steps": steps, "rec": rec, "arch": ARCH, "peaks": PEAKS}


def _step(t0, t1, ticks, tokens):
    return types.SimpleNamespace(t0=t0, t1=t1, decode_ticks=ticks,
                                 prefill_ticks=0, tokens=tokens)


@pytest.mark.parametrize("metric", ["mfu.granite", "mbu.granite"])
def test_readers_read_none_with_nothing_to_read(metric):
    read = spec.metric_reader(metric).read
    assert read(_ctx([], {}, {})) is None
    # A prefill step only, and a decode step that delivered no token.
    pre = types.SimpleNamespace(t0=0.0, t1=1.0, decode_ticks=0,
                                prefill_ticks=1, tokens=1)
    assert read(_ctx([pre], {0: [0.5]}, {0: 4})) is None
    assert read(_ctx([_step(2.0, 3.0, 8, 0)], {0: [0.5]}, {0: 4})) is None


def test_readers_by_hand():
    """One request of a 10-token prompt: its first token from prefill, then
    two tokens in a 0.5 s decode step of 2 ticks, at contexts 11 and 12."""
    ctx = _ctx([_step(1.0, 1.5, 2, 2)], {0: [0.9, 1.2, 1.4]}, {0: 10})
    flops = (work_hybrid.decode_token_flops(ARCH, 11)
             + work_hybrid.decode_token_flops(ARCH, 12))
    assert spec.metric_reader("mfu.granite").read(ctx) == pytest.approx(
        100 * flops / (0.5 * 197e12))
    nbytes = (2 * work_hybrid.weight_bytes(ARCH)
              + work_hybrid.decode_token_bytes(ARCH, 11)
              + work_hybrid.decode_token_bytes(ARCH, 12))
    assert spec.metric_reader("mbu.granite").read(ctx) == pytest.approx(
        100 * nbytes / (0.5 * 819e9))
