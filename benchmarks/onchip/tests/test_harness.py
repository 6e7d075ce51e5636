"""The harness without a chip: it refuses to run, finds every file a cell
names, makes the same traffic from the same seed, and judges numbers
against their limits."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import tiny
import compare
import generator

RUN = tiny.ONCHIP / "run.py"
BENCH = json.loads((tiny.harness.ROOT / "BENCHMARK.json").read_text())


def test_no_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(RUN), "--workload", BENCH["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tiny.harness.ROOT,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(cell):
    c = tiny.harness.Cell(BENCH, cell)
    assert c.job["kind"] and (tiny.ONCHIP / "drivers"
                              / f"{c.job['kind']}.py").exists()
    assert c.limits and set(c.limits) <= {"loss_gap", "grad_gap",
                                          "change_gap"}
    assert "setup_s" in {m["name"] for m in c.end_to_end}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(tiny.harness.reader(m["name"]).read)
    # what runs is the file's published keys, cut where ``reduced`` says
    cfg, sizes = c.config, c.sizes
    assert sizes["d_model"] == cfg["hidden_size"]
    assert sizes["d_ff"] == cfg["intermediate_size"]
    assert sizes["n_heads"] == cfg["num_attention_heads"]
    assert sizes["kv_heads"] == cfg["num_key_value_heads"]
    assert sizes["n_layers"] == cfg["num_hidden_layers"]
    assert sizes["vocab"] == cfg["vocab_size"]
    assert sizes["head_dim"] * sizes["n_heads"] == cfg["hidden_size"]
    entry = {x["name"]: x for x in BENCH["configs"]}[c.entry["config"]]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    for key, cut in cfg["reduced"].items():
        assert cfg[key] == cut["run"] != cut["published"]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_program_config_matches_the_file(cell):
    sys.path.insert(0, str(tiny.ONCHIP / "drivers"))
    import train
    c = tiny.harness.Cell(BENCH, cell)
    arch = train.program_config(c)
    assert arch.n_layers == c.sizes["n_layers"]
    c.sizes = dict(c.sizes, d_ff=1)
    with pytest.raises(ValueError):
        train.program_config(c)


def test_generator_is_seeded():
    spec = {"dist": "zipf", "exponent": 1.0}
    big = 2 ** 40 + 3
    a = generator.make_batch_fn(big, 1000, 2, 64, spec)
    b = generator.make_batch_fn(big, 1000, 2, 64, spec)
    c = generator.make_batch_fn(big + 1, 1000, 2, 64, spec)
    x, y, z = a(3), b(3), c(3)
    np.testing.assert_array_equal(x["tokens"], y["tokens"])
    assert not np.array_equal(x["tokens"], z["tokens"])
    assert not np.array_equal(a(3)["tokens"], a(4)["tokens"])
    assert x["tokens"].shape == (2, 64) and x["tokens"].dtype == jnp.int32
    np.testing.assert_array_equal(x["tokens"][:, 1:], x["labels"][:, :-1])
    assert 0 <= int(x["tokens"].min()) and int(x["tokens"].max()) < 1000
    # the rows of a batch differ, and the ids follow a heavy head
    assert not np.array_equal(x["tokens"][0], x["tokens"][1])
    ids = np.asarray(a(0)["tokens"]).ravel()
    assert np.bincount(ids).max() > 8 * len(ids) / 1000


def test_seed_range():
    generator.seed_key(2 ** 63)
    with pytest.raises(ValueError):
        generator.seed_key(-1)


def test_compare_worst_leaf_and_negligible_leaves():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-6}
    assert compare.worst_leaf_gap({"a": 1.1, "b": 2.0, "c": 0.0}, ref) == (
        pytest.approx(0.1), "a")
    # a tiny leaf is measured against the median leaf, not against itself
    assert compare.worst_leaf_gap({"a": 1.0, "b": 2.0, "c": 1e-3},
                                  ref)[0] == pytest.approx(1e-3 - 1e-6)
    nan = compare.worst_leaf_gap({"a": float("nan"), "b": 2.0, "c": 1e-6},
                                 ref)
    assert nan[1] == "a" and nan[0] != nan[0]
    prog = {"losses": [2.0, 1.0], "grad_norms": {"a": 1.0, "b": 2.0,
                                                  "c": 1e-9},
            "change_norms": {"a": 0.0, "b": 2.0, "c": 5.0}}
    refd = {"losses": [2.02, 1.0], "grad_norms": {"a": 1.0, "b": 2.0,
                                                   "c": 1e-9},
            "change_norms": {"a": 1.0, "b": 2.0, "c": 0.0}}
    got = compare.readings(prog, refd)
    # the first step's loss, before any update
    assert got["loss_gap"] == (pytest.approx(0.02 / 2.02), "step 0")
    # leaf c has a negligible reference gradient and is left out
    assert got["change_gap"] == (pytest.approx(1.0), "a")
    ok, rows = compare.judge(got, {"loss_gap": 0.1, "grad_gap": 0.1,
                                   "change_gap": 0.5})
    assert not ok and [r[0] for r in rows] == ["loss_gap", "grad_gap",
                                               "change_gap"]
    # a number without a limit is read but not judged
    ok, rows = compare.judge(got, {"grad_gap": 0.1})
    assert ok and [r[0] for r in rows] == ["grad_gap"]
