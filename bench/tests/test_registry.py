"""Discovery by name, the traffic generator, the weights tree and the
harness's refusal off the chip."""
import json
import math
import os
import statistics
import subprocess
import sys
import types

import numpy as np
import pytest

from bench import generate, spec, weights

BENCH = spec.load_benchmark()
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
TRAFFIC = sorted({w["traffic"] for w in BENCH["workloads"]})


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_cell_loads_by_name(workload):
    cell = spec.load_cell(workload)
    assert cell.name == workload
    assert cell.config["name"] == next(
        w["config"] for w in BENCH["workloads"] if w["name"] == workload)
    assert callable(spec.runner(cell.kind))
    assert "gap" in cell.limits
    names = {m.name for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:            # what a layer metric moves is there
        assert m.moves in names, (m.name, m.moves)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(spec.metric_reader(metric).read)


def test_each_config_file_is_its_own():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/")
        cfg = json.load(open(os.path.join(spec.ROOT, c["file"])))
        assert cfg["name"] == c["name"]


def test_unknown_device_has_no_peaks():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("cpu")


@pytest.mark.parametrize("traffic", TRAFFIC)
def test_same_seed_same_requests(traffic):
    t = json.load(open(os.path.join(spec.BENCH_DIR, "traffic",
                                    traffic + ".json")))
    a, b = generate.Stream(t, 2**31 + 7, 1000), generate.Stream(t, 2**31 + 7,
                                                                 1000)
    c = generate.Stream(t, 2**31 + 8, 1000)
    n = 2 * t["block"]
    for i in range(n):
        assert a[i].due == b[i].due and a[i].max_new == b[i].max_new
        np.testing.assert_array_equal(a[i].prompt, b[i].prompt)
    # Another seed: the same sizes at the same times, other tokens.
    for i in range(n):
        assert a[i].due == c[i].due and a[i].max_new == c[i].max_new
        assert len(a[i].prompt) == len(c[i].prompt)
    assert any(not np.array_equal(a[i].prompt, c[i].prompt)
               for i in range(n))
    # Each block holds the same sizes, in another order.
    b0 = sorted(a[i].max_new for i in range(t["block"]))
    b1 = [a[i].max_new for i in range(t["block"], n)]
    assert sorted(b1) == b0 and b1 != [a[i].max_new
                                       for i in range(t["block"])]


@pytest.mark.parametrize("traffic", TRAFFIC)
def test_length_distributions(traffic):
    t = json.load(open(os.path.join(spec.BENCH_DIR, "traffic",
                                    traffic + ".json")))
    s = generate.Stream(t, 3, 1000)
    reqs = [s[i] for i in range(t["block"])]
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new for r in reqs])
    pd, od = t["prompt"], t["output"]
    assert p.min() >= pd["lo"] and p.max() <= pd["hi"]
    assert o.min() >= od["lo"] and o.max() <= od["hi"]
    # Every prompt and its answer fit one slot.
    assert (p + o).max() <= t["pool"]["max_len"]
    # The block's median is the truncated log-normal's median.
    for x, d in ((p, pd), (o, od)):
        sig = d["sigma"]
        nd = statistics.NormalDist(math.log(d["mean"]) - sig * sig / 2, sig)
        a, b = nd.cdf(math.log(d["lo"])), nd.cdf(math.log(d["hi"]))
        med = math.exp(nd.inv_cdf((a + b) / 2))
        assert 0.95 * med <= np.median(x) <= 1.05 * med
    assert all(r.prompt.min() >= 3 and r.prompt.max() < 1000 for r in reqs)
    if t["kind"] == "open_loop":
        gaps = np.diff([0.0] + [r.due for r in reqs])
        assert abs(gaps.mean() * t["rate_per_s"] - 1) < 0.05
    else:
        assert all(r.due == 0 for r in reqs)


@pytest.mark.parametrize("traffic", TRAFFIC)
def test_source_means_are_kept(traffic):
    """Each mix's block of lengths has the means its source reports."""
    t = json.load(open(os.path.join(spec.BENCH_DIR, "traffic",
                                    traffic + ".json")))
    for d in (t["prompt"], t["output"]):
        q = generate.quantiles(d, t["block"])
        assert abs(q.mean() - d["source_mean"]) < 0.01 * d["source_mean"]


def test_quantiles_by_hand():
    d = {"dist": "lognormal", "mean": 100 * math.exp(0.5), "sigma": 1.0,
         "lo": 1, "hi": 1e9}
    # mu = ln 100; u = 0.25, 0.75 -> 100 * exp(-+0.6745) = 50.9, 196.4.
    assert generate.quantiles(d, 2).tolist() == [51, 196]
    e = generate.quantiles({"dist": "exponential", "mean": 2.0}, 2)
    np.testing.assert_allclose(e, [-2 * np.log(0.75), -2 * np.log(0.25)])


def test_truncation_spans_lo_to_hi():
    """Truncation keeps the whole of [lo, hi], as a length filter does:
    the outer quantiles lie next to its ends, and nothing beyond."""
    d = {"dist": "lognormal", "mean": 300.0, "sigma": 1.0, "lo": 50,
         "hi": 400}
    q = generate.quantiles(d, 1000)
    assert q.min() == 50 and q.max() == 400
    assert (np.diff(q) >= 0).all()
    same = generate.quantiles(dict(d, lo=200, hi=200), 8)
    assert same.tolist() == [200] * 8


def test_a_driver_with_its_own_run_keeps_it(monkeypatch):
    own = types.SimpleNamespace(run=lambda *a, **k: "own")
    monkeypatch.setattr(spec, "driver", lambda kind: own)
    assert spec.runner("any")() == "own"
    serving_drv = types.SimpleNamespace(Source=None, end_to_end=None)
    monkeypatch.setattr(spec, "driver", lambda kind: serving_drv)
    r = spec.runner("any")
    assert r.args == (serving_drv,) and r.func.__name__ == "run"


@pytest.mark.parametrize("config", ["slayformer-124m",
                                    "slayformer-124m-softmax"])
def test_weights_tree_is_the_programs(config):
    """The benchmark's weights have the tree, shapes and dtypes the
    program's own init gives, so the engine takes them as they are."""
    import jax

    from bench import serving
    from repro.models import api
    cfg = json.load(open(spec.config_path(config, BENCH)))
    arch = dict(cfg["arch"], num_layers=2, d_model=64, num_heads=4,
                num_kv_heads=4, head_dim=16, d_ff=128, vocab_size=256)
    ours = jax.eval_shape(lambda: weights.make(arch, 9, spec.model(cfg)))
    prog = api.abstract_params(serving.arch_config(dict(cfg, arch=arch)))
    assert jax.tree.structure(ours) == jax.tree.structure(prog)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(prog)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_weights_are_a_function_of_the_seed():
    import jax
    cfg = json.load(open(spec.config_path("slayformer-124m", BENCH)))
    arch = dict(cfg["arch"], num_layers=1, d_model=32, num_heads=2,
                num_kv_heads=2, head_dim=16, d_ff=64, vocab_size=64)
    model = spec.model(cfg)
    a = weights.make(arch, 2**33 + 1, model)
    b = weights.make(arch, 2**33 + 1, model)
    c = weights.make(arch, 2**33 + 2, model)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a["embed"]), np.asarray(c["embed"]))
    om = np.asarray(a["slay"]["omegas"])
    np.testing.assert_array_equal(om[:8], -om[8:])       # antithetic pairs
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(a["slay"]["anchors"]), axis=-1), 1.0,
        rtol=1e-6)


def test_refuses_anything_but_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        WORKLOADS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=spec.ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
