"""The training probe at a CPU size: the program's step leaves its bf16
matrix leaves unmoved in the first steps of the default schedule, while
the float32 witness moves every element of every matrix leaf."""
import json

from bench import spec
from bench.train_probe import moved_shares


def _arch():
    cfg = json.load(open(spec.config_path("slayformer-124m",
                                          spec.load_benchmark())))
    return dict(cfg["arch"], num_layers=2, d_model=64, num_heads=4,
                num_kv_heads=4, head_dim=16, d_ff=128, vocab_size=256,
                chunk_size=16)


def test_bf16_step_leaves_matrices_unmoved_and_fp32_moves_them():
    bf = moved_shares(_arch(), 11, 2, 32, 2, "bfloat16")
    fp = moved_shares(_arch(), 11, 2, 32, 2, "float32")
    assert [x["step"] for x in bf] == [1, 2]
    assert bf[-1]["lr"] == fp[-1]["lr"] > 0
    b, f = bf[-1]["changed_share_since_start"], fp[-1][
        "changed_share_since_start"]
    weights = [k for k in b if k.startswith(("embed", "layers/attn",
                                             "layers/mlp"))]
    assert len(weights) == 7
    # bf16: the update of ~lr = 4e-7 is under half a bf16 spacing of
    # all but the tiniest weights.
    assert all(b[k] < 0.05 for k in weights), b
    assert all(f[k] > 0.5 for k in weights), f
    # Norm scales start at zero and move in both; the feature bank in
    # neither.
    assert b["final_norm"] == f["final_norm"] == 1.0
    assert b["slay/anchors"] == f["slay/anchors"] == 0.0
