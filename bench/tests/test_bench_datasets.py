"""A configuration's data is a generator found by name: the image data is
the same as before it was moved there, a configuration that names no
generator is refused, the test's token generator makes packed multi-domain
text, and a token configuration runs through the harness by new files
alone."""
import copy
import hashlib
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import generate, harness
from bench.faults import FAULTS
from bench.tests import tiny

DATASETS = tiny.ROOT / "bench" / "datasets"
LM_FILES = tiny.ROOT / "bench" / "tests"


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


# digests of the train split, the test split, the six clients' shards and
# their (3, 8) batch schedules of rounds 0 and 1, recorded from the code
# before the image construction moved to bench/datasets/images.py
@pytest.mark.parametrize("spec, seed, digests", [
    ({"shape": [16, 16, 3], "n_classes": 10, "n_train": 1200, "n_test": 512, "snr": 0.8}, 2 ** 33 + 17,
     ("45d79f10391f3e52", "ef518d7566935ff0", "7b54f4447a67bf3d", "b2ca5247b144c791")),
    ({"shape": [12, 12, 1], "n_classes": 10, "n_train": 900, "n_test": 300, "snr": 2.2}, 2147483710,
     ("827ba69f43273490", "8e7f1a03529f6441", "70f90db65a3cf214", "552e42f5590ec296")),
])
def test_image_data_is_unchanged(spec, seed, digests):
    seeds = generate.derive_seeds(seed)
    data = harness.load_module(DATASETS / "images.py").make(spec, seeds["data"])
    parts = generate.dirichlet_partition(data["train"]["label"], 6, 0.5, seeds["partition"])
    schedule = [generate.local_batches(p, seeds["clients"] + c, 8, 3, r)
                for r in (0, 1) for c, p in enumerate(parts)]
    assert (_digest(data["train"]["image"], data["train"]["label"]),
            _digest(data["test"]["image"], data["test"]["label"]),
            _digest(*parts), _digest(*schedule)) == digests


def _copy_tree(dst):
    shutil.copy(tiny.ROOT / "BENCHMARK.json", dst)
    shutil.copytree(tiny.ROOT / "bench", dst / "bench", ignore=shutil.ignore_patterns("__pycache__"))


@pytest.mark.parametrize("key", harness.DATASET_KEYS)
def test_a_dataset_without_its_key_is_refused(tmp_path, key):
    _copy_tree(tmp_path)
    path = tmp_path / "bench" / "configs" / "resnet_tiny-cifar10.json"
    config = json.loads(path.read_text())
    del config["dataset"][key]
    path.write_text(json.dumps(config))
    with pytest.raises(ValueError, match=f"lacks {key}, which have no default"):
        harness.load_cell(tmp_path, "cifar10-secagg-l8")


# ---------------------------------------------------------------------------
# token data
# ---------------------------------------------------------------------------

TOKENS = {"vocab": 512, "seq_len": 64, "n_train": 2000, "n_test": 300, "n_domains": 4,
          "zipf": 1.1, "doc_len_mu": 3.0, "doc_len_sigma": 1.0}


@pytest.fixture(scope="module")
def tokens():
    return harness.load_module(LM_FILES / "lm_tokens.py")


@pytest.fixture(scope="module")
def drawn(tokens):
    return tokens.make(TOKENS, 7)


def test_token_shapes_and_ids(tokens, drawn):
    for split, n in (("train", TOKENS["n_train"]), ("test", TOKENS["n_test"])):
        t, d = drawn[split]["tokens"], drawn[split]["domain"]
        assert set(drawn[split]) == {"tokens", "domain"}
        assert t.shape == (n, TOKENS["seq_len"] + 1) and t.dtype == np.int32
        assert d.shape == (n,) and d.dtype == np.int32
        assert 0 <= t.min() and t.max() < TOKENS["vocab"]
        assert 0 <= d.min() and d.max() < TOKENS["n_domains"]
    # documents of a median e**3 = 20 tokens: nearly every sequence of 65
    # holds a boundary, and about one id in 21 is one
    eod = drawn["train"]["tokens"] == tokens.EOD
    assert eod.any(axis=1).mean() > 0.95
    assert 0.02 < eod.mean() < 0.1


def test_each_domain_has_its_own_frequent_ids(tokens, drawn):
    t, d = drawn["train"]["tokens"], drawn["train"]["domain"]
    top = []
    for dom in range(TOKENS["n_domains"]):
        ids = t[d == dom]
        counts = np.bincount(ids[ids != tokens.EOD], minlength=TOKENS["vocab"])
        # Zipf(1.1) over 511 ids puts about a fifth of the mass on rank 1
        assert 0.1 < counts.max() / counts.sum() < 0.3
        top.append(int(counts.argmax()))
    assert len(set(top)) == TOKENS["n_domains"], top


def test_same_seed_same_tokens_other_seed_same_sizes(tokens, drawn):
    again, other = tokens.make(TOKENS, 7), tokens.make(TOKENS, 2 ** 31 + 11)
    for split in ("train", "test"):
        for key in ("tokens", "domain"):
            np.testing.assert_array_equal(again[split][key], drawn[split][key])
            assert other[split][key].shape == drawn[split][key].shape
            assert other[split][key].dtype == drawn[split][key].dtype
    assert not np.array_equal(other["train"]["tokens"], drawn["train"]["tokens"])


def test_partition_over_domains_is_skewed(drawn):
    domain = drawn["train"]["domain"]
    parts = generate.dirichlet_partition(domain, 50, 0.5, 3)
    top_share = [np.bincount(domain[p], minlength=TOKENS["n_domains"]).max() / len(p) for p in parts]
    # an even split would give each client's top domain about a quarter
    assert np.mean(np.array(top_share) > 0.5) > 0.5, np.round(sorted(top_share), 2)


# ---------------------------------------------------------------------------
# a token configuration through the unchanged harness, by new files alone
# ---------------------------------------------------------------------------

LM_CONFIG = {
    "name": "tiny_lm-tokens",
    "source": "a tiny causal language model for a CPU test",
    "reference": "tiny_lm",
    "program": {"module": "bench.tests.lm_program", "config": "LMConfig", "loss": "lm_loss"},
    "model": {"vocab": 128, "d_model": 16, "d_ff": 32},
    "param_count": 128 * 16 * 2 + 4 * 16 * 16 + 2 * 16 * 32,
    "dataset": {"generator": "tokens", "partition_by": "domain", "vocab": 128, "seq_len": 16,
                "n_train": 1200, "n_test": 512, "n_domains": 4, "zipf": 1.1,
                "doc_len_mu": 2.0, "doc_len_sigma": 0.8},
    "protocol": {"algorithm": "fedavg", "n_clients": 6, "clients_per_round": 3, "batch_size": 8,
                 "client_lr": 0.05, "client_momentum": 0.9, "server_lr": 1.0,
                 "dirichlet_alpha": 0.5, "eval_every": 5, "max_eval_batches": 2, "eval_batch": 256},
}
LM_TRAFFIC = {"local_steps": 2, "warmup_rounds": 3, "check": "fedavg_sync",
              "experiment": {"topology": {"mode": "sync"}, "orchestrator": {"selection": "rl_green"},
                             "privacy": {"secure_agg": True}}}
LM_CELL = "tiny_lm-secagg-l2"


def _files(root):
    return {p.relative_to(root): p.read_bytes() for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def lm_tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench-copy")
    _copy_tree(root)
    had = _files(root)
    spec = json.loads(had.pop(Path("BENCHMARK.json")))
    bench = root / "bench"
    (bench / "configs" / "tiny_lm-tokens.json").write_text(json.dumps(LM_CONFIG))
    shutil.copy(LM_FILES / "lm_reference.py", bench / "configs" / "tiny_lm.py")
    shutil.copy(LM_FILES / "lm_tokens.py", bench / "datasets" / "tokens.py")
    (bench / "traffic" / "lm-secagg-l2.json").write_text(json.dumps(LM_TRAFFIC))
    limits = json.loads((bench / "cells" / "cifar10-secagg-l8.json").read_text())
    (bench / "cells" / f"{LM_CELL}.json").write_text(json.dumps(limits))
    grown = copy.deepcopy(spec)
    grown["configs"].append({"name": "tiny_lm-tokens", "source": LM_CONFIG["source"],
                             "file": "bench/configs/tiny_lm-tokens.json", "reduced": [],
                             "why": "token data through the harness"})
    grown["workloads"].append({"name": LM_CELL, "config": "tiny_lm-tokens",
                               "traffic": "lm-secagg-l2", "chips": 1, "why": "a CPU test"})
    (root / "BENCHMARK.json").write_text(json.dumps(grown))
    return root, had, spec


def test_token_configuration_runs_by_new_files_alone(lm_tree):
    root, had, spec = lm_tree
    result, run = harness.run_cell(root, LM_CELL, 2 ** 32 + 41, 0.2, False, require_tpu=False)
    assert result["correct"] is True, result["checks"]
    assert set(run["data"]["train"]) == {"tokens", "domain"}
    assert result["attempted"] >= 1 and set(result["metrics"]) == {"samples_per_s", "round_s_p90",
                                                                    "setup_s"}
    # the new cell gets every per-layer metric that names no cells of its own
    everywhere = [m["name"] for m in spec["per_layer"] if "workloads" not in m]
    assert everywhere and [m["name"] for m in run["cell"]["per_layer"]] == everywhere
    # no file the copy had was changed; BENCHMARK.json only grew
    assert {p: b for p, b in _files(root).items() if p in had} == had
    grown = json.loads((root / "BENCHMARK.json").read_text())
    assert {k: v for k, v in grown.items() if k not in ("configs", "workloads")} == \
        {k: v for k, v in spec.items() if k not in ("configs", "workloads")}
    for key in ("configs", "workloads"):
        assert grown[key][:len(spec[key])] == spec[key]


def test_token_configuration_fault_fails(lm_tree):
    """The check reads the token cell's updates: a server step that returns
    its state unchanged is caught."""
    root, _, _ = lm_tree
    result, run = harness.run_cell(root, LM_CELL, 2 ** 32 + 41, 0.1, False, require_tpu=False,
                                   fault=FAULTS["state_unchanged"], measure=False)
    assert result["correct"] is False, run["readings"]
