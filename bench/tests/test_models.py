"""The model a configuration names: every configuration's model module,
slayformer's weights pinned to their bytes and its reference logits to
saved arrays, and a third configuration with a tree of its own added by
files alone."""
import hashlib
import json
import os
import shutil

import jax
import numpy as np
import pytest

from bench import serving, spec, weights

BENCH = spec.load_benchmark()
CONFIGS = [c["name"] for c in BENCH["configs"]]
HERE = os.path.dirname(__file__)
# test_weights_are_a_function_of_the_seed's widths.
SMALL = dict(num_layers=1, d_model=32, num_heads=2, num_kv_heads=2,
             head_dim=16, d_ff=64, vocab_size=64)
SEED = 2**33 + 1


def _config(name: str) -> dict:
    with open(spec.config_path(name, BENCH)) as f:
        return json.load(f)


def _digest(tree) -> str:
    """SHA-256 of a tree's leaves: path, dtype, shape and bytes."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


# Digests computed at commit 8607e8a (weights.make(arch, seed)), where the
# tree was slayformer's alone; moving it behind the configuration's model
# module keeps every byte. The weights are PRNG draws and elementwise
# arithmetic, so their bytes do not depend on the CPU.
WEIGHTS_AT_8607E8A = {
    "slayformer-124m":
        "a6de9c670647a92836a41cbf6cc41770b5924cf14508dfcdc14c2f7b13680aa9",
    "slayformer-124m-softmax":
        "baa6e4521bf8a1229d01cd1bca409818b350bed5add1b403c2601537cfd26841",
}
# bench/reference/model.py's logits at commit 8607e8a, saved from jax 0.9.0
# on an x86-64 CPU, keyed "<config>/<prec>". They pass through matmuls, whose
# last bits follow the CPU's summation order, so they are compared within a
# few hundred fp32 ulps of the logits' scale (~0.35), not by their bytes.
LOGITS_AT_8607E8A = os.path.join(HERE, "fixtures",
                                 "reference_logits_8607e8a.npz")
LOGITS_KEYS = [(c, p) for c in sorted(WEIGHTS_AT_8607E8A)
               for p in ("float32", "float8_e4m3fn")]


@pytest.mark.parametrize("config", sorted(WEIGHTS_AT_8607E8A))
def test_weights_keep_their_bytes(config):
    cfg = _config(config)
    arch = dict(cfg["arch"], **SMALL)
    params = weights.make(arch, SEED, spec.model(cfg))
    assert _digest(params) == WEIGHTS_AT_8607E8A[config]


@pytest.mark.parametrize("config,prec", LOGITS_KEYS)
def test_reference_logits_match_the_saved_arrays(config, prec):
    """Two layers, the reference and its float8 control, at positions
    across a 24-token sequence."""
    cfg = _config(config)
    cfg = dict(cfg, arch=dict(cfg["arch"], **dict(SMALL, num_layers=2)))
    model = spec.model(cfg)
    params = weights.make(cfg["arch"], SEED, model)
    toks = (np.arange(24, dtype=np.int32) * 7 + 3) % 64
    idx = np.array([0, 5, 11, 23], np.int32)
    lg = jax.jit(lambda p, t, i: model.logits(p, cfg, t, i, prec))(
        params, toks, idx)
    with np.load(LOGITS_AT_8607E8A) as saved:
        want = saved[f"{config}/{prec}"]
    assert lg.dtype == want.dtype and lg.shape == want.shape
    np.testing.assert_allclose(np.asarray(lg), want, rtol=0, atol=1e-6)


@pytest.mark.parametrize("config", CONFIGS)
def test_every_config_names_its_model(config):
    cfg = _config(config)
    assert os.path.isfile(os.path.join(spec.ROOT, cfg["model"]))
    model = spec.model(cfg)
    for fn in ("logits", "shapes", "finish"):
        assert callable(getattr(model, fn)), fn


@pytest.mark.parametrize("model,error", [(None, KeyError),
                                         ("bench/reference/none.py",
                                          FileNotFoundError)])
def test_config_without_a_model_file_is_refused(model, error):
    cfg = {k: v for k, v in _config(CONFIGS[0]).items() if k != "model"}
    if model is not None:
        cfg["model"] = model
    with pytest.raises(error):
        spec.model(cfg)


def _tree_bytes(root: str) -> dict:
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_configuration_is_added_by_files_alone(tmp_path):
    """A copy of the benchmark gains a configuration whose model has a
    tree and a reference of its own: the configuration file, its model
    module, the limits of its cell, and entries in BENCHMARK.json. The
    harness loads the cell, makes the weights and runs the reference over
    served tokens, and no file that was there changes."""
    root = str(tmp_path)
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(spec.BENCH_DIR, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = _tree_bytes(root)

    added = {
        "bench/configs/toy-lm.json": json.dumps({
            "name": "toy-lm", "source": "a toy family of the tests",
            "model": "bench/reference/toy_model.py",
            "arch": {"num_layers": 2, "d_model": 32, "vocab_size": 64}}),
        "bench/limits/toy-chat.json": json.dumps({"gap": 0.1}),
    }
    for rel, text in added.items():
        with open(os.path.join(root, rel), "w") as f:
            f.write(text)
    shutil.copy(os.path.join(HERE, "fixtures", "toy_model.py"),
                os.path.join(root, "bench", "reference", "toy_model.py"))
    bench = dict(BENCH)
    bench["configs"] = BENCH["configs"] + [{
        "name": "toy-lm", "source": "a toy family of the tests",
        "file": "bench/configs/toy-lm.json", "reduced": [],
        "why": "a family with a tree of its own"}]
    bench["workloads"] = BENCH["workloads"] + [{
        "name": "toy-chat", "config": "toy-lm", "traffic": "chat",
        "chips": 1, "why": "the chat mix on the toy family"}]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    after = _tree_bytes(root)
    changed = {p for p in before if p != "BENCHMARK.json"
               and after[p] != before[p]}
    assert not changed
    assert set(after) - set(before) == set(added) | {
        "bench/reference/toy_model.py"}

    cell = spec.load_cell("toy-chat", root=root)
    assert cell.config["name"] == "toy-lm" and cell.limits == {"gap": 0.1}
    assert cell.traffic == spec.load_cell("slay124m-chat").traffic
    model = spec.model(cell.config, root=root)
    assert model.__file__.startswith(root)

    arch = cell.config["arch"]
    params = weights.make(arch, SEED, model)
    assert set(params) == {"embed", "mix", "out_bias"}
    assert params["mix"].shape == (2, 32, 32)
    assert params["out_bias"].dtype == np.float32
    # finish ran: the bias is a tenth of an N(0, 1) draw.
    assert 0.03 < float(np.std(np.asarray(params["out_bias"]))) < 0.3
    assert not np.array_equal(np.asarray(params["embed"]),
                              np.asarray(weights.make(arch, SEED + 1,
                                                      model)["embed"]))

    # Served tokens: the toy reference's own greedy continuation reads no
    # gap; the same tokens each moved to the next id read one.
    prompt = np.array([5, 17, 3, 40, 9], np.int32)
    seq = list(prompt)
    for _ in range(6):
        seq.append(int(np.argmax(model.logits(params, cell.config,
                                              np.array(seq))[-1])))
    served = np.array(seq[len(prompt):], np.int32)
    good = serving.served_gaps(model, params, cell.config,
                               [(prompt, served)], 32, 8)
    assert good["tokens"] == 6 and good["gap"] < 1e-5
    bad = serving.served_gaps(model, params, cell.config,
                              [(prompt, (served + 1) % 64)], 32, 8)
    assert bad["gap"] > cell.limits["gap"]
