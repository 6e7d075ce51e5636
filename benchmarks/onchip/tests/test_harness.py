"""The harness without a chip: it refuses to run, finds every file a cell
names, makes the same traffic from the same seed, and judges numbers
against their limits."""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import types

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
    cfg = c.config
    c.reference.check_config(cfg)
    entry = {x["name"]: x for x in BENCH["configs"]}[c.entry["config"]]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    for key, cut in cfg["reduced"].items():
        assert cfg[key] == cut["run"] != cut["published"]


def test_check_config_takes_the_files_head_size():
    ref = tiny.harness.load_module(tiny.ONCHIP / "configs"
                                   / "dense_decoder.py")
    cfg = json.loads((tiny.ONCHIP / "configs" / "minicpm-2b.json")
                     .read_text())
    # a head size that the file gives, wider than hidden / heads
    ref.check_config(dict(cfg, head_dim=128))
    assert ref.sizes(dict(cfg, head_dim=128))["head_dim"] == 128
    # no head size given, and hidden / heads is no whole number
    with pytest.raises(ValueError):
        ref.check_config(dict(cfg, num_attention_heads=35))


def _driver():
    sys.path.insert(0, str(tiny.ONCHIP / "drivers"))
    import train
    return train


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_program_config_matches_the_file(cell):
    train = _driver()
    c = tiny.harness.Cell(BENCH, cell)
    arch = train.program_config(c)
    assert arch.n_layers == c.sizes["n_layers"]
    c.sizes = dict(c.sizes, d_ff=1)
    with pytest.raises(ValueError):
        train.program_config(c)


def _moe_stub_cell(monkeypatch):
    """A cell of ``stub_moe.py``'s configuration, whose program is the
    registered reduced DeepSeekMoE."""
    from repro.configs import base, deepseek_moe_16b
    stub = tiny.harness.load_module(tiny.ONCHIP / "tests" / "stub_moe.py")
    want = deepseek_moe_16b.reduced()
    monkeypatch.setattr(base, "get_config", lambda name: want)
    return want, types.SimpleNamespace(
        config=stub.CONFIG, reference=stub, sizes=stub.sizes(stub.CONFIG),
        cut=[])


@pytest.mark.parametrize("size", ["moe.top_k", "moe.dense_d_ff",
                                  "moe.expert_d_ff", "d_model"])
def test_program_config_of_another_block_kind(monkeypatch, size):
    """A reference of another block kind (``stub_moe.py``) maps onto the
    program's registered reduced DeepSeekMoE with no edit to the harness,
    and a size the program does not have is refused."""
    train = _driver()
    want, c = _moe_stub_cell(monkeypatch)
    assert train.program_config(c) == want
    c.sizes = dict(c.sizes, **{size: c.sizes[size] + 1})
    with pytest.raises(ValueError):
        train.program_config(c)


def test_program_config_cuts_a_nested_size(monkeypatch):
    train = _driver()
    want, c = _moe_stub_cell(monkeypatch)
    c.sizes = dict(c.sizes, **{"moe.n_experts": 4})
    c.cut = ["moe.n_experts"]
    arch = train.program_config(c)
    assert arch.moe.n_experts == 4
    assert arch == want.replace(moe=dataclasses.replace(want.moe,
                                                        n_experts=4))


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
