"""The family contract: what knows an architecture is found by the
configuration's ``family``, and the llama family reads what it read before
it was moved under ``families/`` (digests taken on the parent tree, PR 27).
"""

import glob
import hashlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import cells
from harness import reference as R
from harness import weights as W
from harness.runner import overlay

SEED = 2 ** 31 + 77
DOOR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "door")
PACKAGE = "analytics_zoo_" + "tpu"

# taken on the parent tree (commit 2c81174) from harness/weights.py and
# harness/reference.py as they were, with the code of ``_digests`` below
PARENT = {
    "qwen2.5-1.5b": {
        "published_shapes": "1d9e9288011e50d3052dfd907e8b5d527c2d9fb84a08739971925f5bd8640d7f",
        "tiny_values": "bd53f25d963fbe0f09835ddd8b1c6bf5c05ff196bf64664693a12738f23442f4",
        "argmax": [315, 245, 167, 245],
        "probe": [0.8503496050834656, 0.38345301151275635,
                  0.2513377070426941, 0.21178866922855377,
                  1.8319939374923706, -1.2441524267196655,
                  0.12499351799488068, 1.6078479290008545],
        "fp8_probe": [0.8117217421531677, 0.4494660496711731,
                      0.24490691721439362, 0.08102238178253174,
                      1.7262126207351685, -1.4383922815322876,
                      0.05395236238837242, 1.4760725498199463]},
    "mistral-7b-v0.3-l16": {
        "published_shapes": "58b45f5b80286d806db9dc72746a35f0f12c95de52604471738a69945a584bd3",
        "tiny_values": "27c148fca6c0034a939bdc4b510f766fb76fa0d39cd2618f596d79aa5dfa981c",
        "argmax": [307, 28, 409, 68],
        "probe": [-0.1324375867843628, -0.7606333494186401,
                  0.07292824238538742, 0.36942005157470703,
                  0.7631623148918152, -1.6864410638809204,
                  0.05566790699958801, 0.20651981234550476],
        "fp8_probe": [-0.24847984313964844, -0.7264720797538757,
                      0.18540409207344055, 0.14047124981880188,
                      0.7533161044120789, -1.785913109779358,
                      0.16492824256420135, 0.39563626050949097]},
}


def _config(name: str) -> dict:
    return cells.load_json(os.path.join(cells.BENCH_DIR, "configs",
                                        f"{name}.json"))


def _sha_of_leaves(h, tag: str, tree: dict, values: bool) -> None:
    for k in sorted(tree):
        if values:
            h.update(f"{tag}{k}".encode())
            h.update(np.asarray(tree[k].astype(jnp.float32)).tobytes())
        else:
            h.update(f"{k}:{tree[k].shape}:{tree[k].dtype};".encode())


@pytest.mark.parametrize("name", sorted(PARENT))
def test_llama_leaves_are_the_parents_to_the_last_bit(name):
    cfg = _config(name)
    fam = cells.family(cfg)
    assert fam.name == "llama"
    # published shapes: nothing is made, only described
    h = hashlib.sha256()
    frozen = W.freeze(cfg)
    for fn in (W._top_fn(frozen),
               W._layer_fn(frozen, fam.leaves.kind(cfg, 0))):
        _sha_of_leaves(h, "", jax.eval_shape(fn, W.seed_key(SEED)), False)
    assert h.hexdigest() == PARENT[name]["published_shapes"]
    # tiny sizes: every value of every leaf
    tiny = overlay(cfg, cfg["tiny"])
    h = hashlib.sha256()
    _sha_of_leaves(h, "", W.top(tiny, SEED), True)
    for i in range(fam.leaves.n_layers(tiny)):
        _sha_of_leaves(h, f"{i}.", W.layer(tiny, SEED, i), True)
    assert h.hexdigest() == PARENT[name]["tiny_values"]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_llama_reference_logits_are_the_parents(name):
    cfg = _config(name)
    fam, tiny = cells.family(cfg), overlay(cfg, cfg["tiny"])
    T = 24
    toks = (np.arange(T) * 37 + 11) % fam.leaves.vocab(tiny)
    top = W.top(tiny, SEED)
    x = xq = fam.reference.embed(tiny, top, jnp.asarray(toks))
    frozen = W.freeze(tiny)
    for i in range(fam.leaves.n_layers(tiny)):
        w, kind = W.layer(tiny, SEED, i), fam.leaves.kind(tiny, i)
        x = R._layer_fn(frozen, kind, None)(w, x)
        xq = R._layer_fn(frozen, kind, "fp8")(w, xq)
    rows = jnp.arange(T - 4, T)
    lg = np.asarray(fam.reference.logits(tiny, None, top, x, rows))
    lq = np.asarray(fam.reference.logits(tiny, "fp8", top, xq, rows))
    cols = [0, 7, 100, 511]
    want = PARENT[name]
    assert lg.argmax(-1).tolist() == want["argmax"]
    # float32 on the CPU: the same to rounding, whatever the thread count
    np.testing.assert_allclose(lg[:2, cols].ravel(), want["probe"],
                               atol=2e-5)
    np.testing.assert_allclose(lq[:2, cols].ravel(), want["fp8_probe"],
                               atol=2e-5)


def test_a_configuration_names_its_family_or_fails_with_those_present():
    cfg = _config("qwen2.5-1.5b")
    assert "llama" in cells.families()
    for bad in ({k: v for k, v in cfg.items() if k != "family"},
                dict(cfg, family="mamba")):
        with pytest.raises(SystemExit) as e:
            cells.family(bad)
        assert str(cells.families()) in str(e.value)
        assert "llama" in str(e.value)
    with pytest.raises(SystemExit):
        W.layer(dict(cfg, family=None), 0, 0)


def test_every_configuration_names_a_family_that_is_there():
    for c in cells.benchmark()["configs"]:
        cfg = cells.load_json(os.path.join(cells.ROOT, c["file"]))
        fam = cells.family(cfg)
        for part in ("leaves", "reference", "counts"):
            assert getattr(fam, part).__name__ == \
                f"families.{fam.name}.{part}"


def test_only_the_model_builder_imports_the_package():
    files = glob.glob(os.path.join(cells.BENCH_DIR, "families", "*",
                                   "*.py"))
    assert len(files) >= 5
    for path in files:
        if os.path.basename(path) == "model.py":
            continue
        assert PACKAGE not in open(path).read(), path
    for path in glob.glob(os.path.join(cells.BENCH_DIR, "harness",
                                       "*.py")):
        if os.path.basename(path) in ("server.py", "runner.py"):
            continue        # the stack, and the window's trace guard
        assert f"import {PACKAGE}" not in open(path).read() and \
            f"from {PACKAGE}" not in open(path).read(), path


def test_the_harness_never_branches_on_a_familys_name():
    names = cells.families()
    for sub in ("harness", "layer_metrics"):
        for path in glob.glob(os.path.join(cells.BENCH_DIR, sub, "*.py")):
            for line in open(path).read().splitlines():
                if re.search(r"\b(if|elif)\b", line) and any(
                        re.search(rf"""["']{n}["']""", line)
                        for n in names):
                    raise AssertionError(f"{path}: branches on a family: "
                                         f"{line.strip()}")


def test_the_harness_names_no_layer_leaf_or_count():
    moved = ("build_model", "def dims", "def layer_leaves",
             "def top_leaves", "def _layer(", "def _logits(", "layer_matmul_params",
             "def token_flops", "def span_flops", "def head_flops",
             "kv_bytes_per_token", "def paged_attn_bytes",
             "def weight_bytes", "def n_params", "num_hidden_layers",
             "vocab_size\"]", "[\"embed\"]")
    for sub in ("harness", "layer_metrics"):
        for path in glob.glob(os.path.join(cells.BENCH_DIR, sub, "*.py")):
            text = open(path).read()
            for needle in moved:
                assert needle not in text, (path, needle)


@pytest.fixture
def toy(monkeypatch):
    """The door test's toy family, findable in this process."""
    import families

    monkeypatch.setattr(families, "__path__", list(families.__path__)
                        + [os.path.join(DOOR, "families")])
    monkeypatch.setattr(cells, "BENCH_DIR", DOOR)
    return cells.load_json(os.path.join(DOOR, "configs", "toygpt.json"))


def test_a_list_valued_key_reaches_leaves_reference_and_counts(toy):
    cfg = toy
    assert cells.families() == ["toygpt"]
    fam = cells.family(cfg)
    # the cache key keeps lists and nested keys (harness/weights.py once
    # kept scalars only, and rebuilt the configuration without them)
    assert W.thaw(W.freeze(cfg)) == cfg
    assert W.thaw(W.freeze(cfg))["ffn_live"] == [160, 96, 160]
    # leaves, by layer index
    before = W._layer_fn.cache_info().currsize
    ws = [W.layer(cfg, SEED, i) for i in range(3)]
    assert [w["w_up"].shape for w in ws] == [(64, 160), (64, 96), (64, 160)]
    assert W._layer_fn.cache_info().currsize == before + 2, \
        "the maker is jitted per KIND of layer, not per layer"
    assert not np.array_equal(np.asarray(ws[0]["wq"], np.float32),
                              np.asarray(ws[2]["wq"], np.float32))
    # reference: one program a kind, and each kind takes its own leaves
    frozen = W.freeze(cfg)
    x = fam.reference.embed(cfg, W.top(cfg, SEED), jnp.arange(12))
    before = R._layer_fn.cache_info().currsize
    for i, w in enumerate(ws):
        x = R._layer_fn(frozen, fam.leaves.kind(cfg, i), None)(w, x)
    assert x.shape == (12, 64) and bool(jnp.isfinite(x).all())
    assert R._layer_fn.cache_info().currsize == before + 2
    with pytest.raises(AssertionError):
        R._layer_fn(frozen, 96, None)(ws[0], x)
    # counts, summed over the layers by kind
    flat = dict(cfg, ffn_live=[160, 160, 160])
    c = fam.counts
    assert c.token_flops(flat, 10) - c.token_flops(cfg, 10) == \
        2.0 * 2 * 64 * (160 - 96)
    assert c.n_params(flat) - c.n_params(cfg) == (2 * 64 + 1) * (160 - 96)
    swapped = dict(cfg, ffn_live=[96, 160, 160])
    assert W.layer(swapped, SEED, 0)["w_up"].shape == (64, 96)
