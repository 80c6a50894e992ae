"""Moving the architecture into its module moved nothing the benchmark
reads: for a fixed seed the dense weights are the same, leaf by leaf, and
``dims`` and ``shapes.token_flops`` of both real configurations are the
same, as recorded from the harness that built them itself
(``data/parent_fingerprints.json``)."""
import json
import math
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness, shapes, weights
from bench.tests import tiny

REPO = Path(__file__).resolve().parents[2]
RECORDED = json.loads((Path(__file__).resolve().parent / "data"
                       / "parent_fingerprints.json").read_text())
DENSE = harness.load_module(REPO / "bench/references/dense_gqa.py")
# the tiny configuration, and one without biases and with its own head
VARIANTS = {"tied_bias": {},
            "untied": {"attention_bias": False, "tie_word_embeddings": False}}


def _dims(config, traffic):
    vp = harness.program_config(DENSE, config).padded_vocab
    return harness.dims_of(DENSE, config, traffic, vp)


def _without_count(dims):
    # the recorded dims hold no token_params: it is compared on its own
    return {k: v for k, v in dims.items() if k != "token_params"}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_dense_weights_unchanged(variant):
    cfg = json.loads((REPO / "bench/configs/qwen2-0.5b.json").read_text())
    cfg.update(tiny.CONFIG, **VARIANTS[variant])
    dims = _dims(cfg, tiny.TRAFFIC["closed"])
    want = RECORDED["tiny"][variant]
    assert _without_count(dims) == want["dims"]
    tree = weights.build(DENSE, dims, RECORDED["seed"])
    got = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = np.asarray(leaf, np.float64).ravel().tolist()
        # exactly rounded sums: the same bits give the same numbers anywhere
        got[jax.tree_util.keystr(path)] = [math.fsum(x),
                                           math.fsum(v * v for v in x)]
    assert got == want["weights"]


@pytest.mark.parametrize("name", ["qwen2-0.5b", "mistral-7b"])
def test_dims_and_token_flops_unchanged(name):
    cfg = json.loads((REPO / f"bench/configs/{name}.json").read_text())
    traffic = json.loads((REPO / "bench/traffic/decode-1.json").read_text())
    dims = _dims(cfg, traffic)
    want = RECORDED["configs"][name]
    assert dims["token_params"] == want["token_params"]
    assert _without_count(dims) == want["dims"]
    assert [shapes.token_flops(dims, c) for c in RECORDED["contexts"]] == \
        want["token_flops"]
