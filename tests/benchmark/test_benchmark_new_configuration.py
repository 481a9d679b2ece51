"""A configuration made of new files only: a copy of the benchmark gains one
``configs`` entry, one ``workloads`` entry and the files they name (its
configuration with a ``modules`` block and a ``widths`` map, a traffic mix,
its limits, a reference and an operations module), and a cell of it runs end
to end on the CPU at toy widths, served by those files, while every file the
benchmark had is byte for byte the repository's."""

import copy
import json
import pickle
import shutil
import time

import pytest
from benchmark_toy import TOY_LIMITS, TOY_MODEL

from benchmark import flops
from benchmark.harness import cell as cells
from benchmark.harness import check as checks
from benchmark.harness import drive
from benchmark.harness.cell import ROOT

BENCH = cells.load_benchmark()
CONFIG, CELL, TRAFFIC, STEM = "toy-arch", "toy-arch-pretrain-64", "pretrain-64", "toy_arch"
SOURCE = "https://example.org/toy-arch/config.json"
#: the new count: the accepted one, for a stack applied twice
PASSES = 2

REFERENCE_PY = '''"""Thin: the accepted equations, and a record of who was asked (kept in
a dataclass, which looks its module up by name when it is defined)."""
from __future__ import annotations

import dataclasses

from benchmark import reference as accepted

leaf_names = accepted.leaf_names


@dataclasses.dataclass
class Asked:
    what: str
    seed: int | None = None


CALLS: list[Asked] = []


def init_params(model, key):
    CALLS.append(Asked("init_params"))
    return accepted.init_params(model, key)


def run(model, optim, clip, tokens_per_step, seed, shard=None):
    CALLS.append(Asked("run", seed))
    return accepted.run(model, optim, clip, tokens_per_step, seed, shard=shard)
'''

OPERATIONS_PY = f'''"""Thin: the accepted count, for a stack applied {PASSES} times."""
from benchmark import flops as accepted

CALLS = []


def train_flops_per_token(model, seq_len):
    CALLS.append("train_flops_per_token")
    once = accepted.train_flops_per_token(model, seq_len)
    return {{"total": {PASSES} * once["total"], "passes": {PASSES}}}


def kernel_calls(model, traffic, data_parallel):
    CALLS.append("kernel_calls")
    return {{kind: {{**need, "calls": {PASSES} * need["calls"]}} for kind, need in
            accepted.kernel_calls(model, traffic, data_parallel).items()}}
'''


def files_of(root):
    return {p.relative_to(root): p.read_bytes()
            for sub in ("benchmark", "BENCHMARK.json")
            for p in ([root / sub] if (root / sub).is_file() else (root / sub).rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def toy_config():
    """The file of the new configuration: toy widths, published as they are
    run, in the source's own key names."""
    accepted = cells.load_config_file(BENCH, BENCH["configs"][0]["name"])
    trainer = copy.deepcopy(accepted["trainer_config"])
    trainer["model"].update(TOY_MODEL)
    trainer["name"] = trainer["exp_manager"]["name"] = CONFIG
    m = trainer["model"]
    return {
        "source": SOURCE,
        "published": {"d_model": m["hidden_size"], "d_ff": m["intermediate_size"],
                      "n_heads": m["num_attention_heads"], "d_head": m["head_dim"],
                      "n_kv_heads": m["num_key_value_heads"],
                      "vocab": m["vocab_size"], "rope_base": m["rope_theta"],
                      "n_layers": 8},
        "reduced": {"num_layers": "8 -> 1"},
        "assumed": {"tokens": "uniform from --seed"},
        "deployment": "one chip holds the whole of it",
        "modules": {"reference": STEM, "operations": STEM},
        "widths": {"hidden_size": "d_model", "intermediate_size": "d_ff",
                   "num_attention_heads": "n_heads", "head_dim": "d_head",
                   "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab",
                   "rope_theta": "rope_base"},
        "trainer_config": trainer,
    }


@pytest.fixture()
def grown(tmp_path):
    """The repository's benchmark, copied, with the new files beside it."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = files_of(tmp_path)
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({
        "name": CONFIG, "source": SOURCE, "file": f"benchmark/configs/{CONFIG}.json",
        "reduced": ["num_layers"], "why": "a stack applied twice: equations of its own"})
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
        "why": "seq 64, gbs 4: the seam, on the CPU"})
    accepted_traffic = cells.load_cell(BENCH["workloads"][0]["name"]).traffic
    new = {
        "BENCHMARK.json": json.dumps(bench, indent=2),
        f"benchmark/configs/{CONFIG}.json": json.dumps(toy_config(), indent=2),
        f"benchmark/traffic/{TRAFFIC}.json": json.dumps(
            dict(accepted_traffic, seq_length=64, trace_steps=3,
                 why="seq 64, global batch 4: toy")),
        f"benchmark/limits/{CONFIG}.json": json.dumps(TOY_LIMITS),
        f"benchmark/references/{STEM}.py": REFERENCE_PY,
        f"benchmark/operations/{STEM}.py": OPERATIONS_PY,
    }
    for rel, text in new.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(text)
    return tmp_path, before, set(new) - {"BENCHMARK.json"}


def test_a_cell_served_by_new_files_alone(grown, capsys):
    root, before, added = grown
    cell = cells.load_cell(CELL, root=root)
    for module, sub in ((cell.reference, "references"), (cell.operations, "operations")):
        assert module.__file__ == str(root / "benchmark" / sub / f"{STEM}.py")
    assert cell.root == root and checks.limits_for(CONFIG, root) == TOY_LIMITS
    with pytest.raises(KeyError):
        checks.limits_for(CONFIG)        # the repository itself has no such limits

    result = drive.run_cell(cell, seed=2**31 + 26, seconds=1.0, trace=False,
                            t_process=time.perf_counter(), require_tpu=False)
    lines = capsys.readouterr().out.splitlines()
    assert result["correct"] is True, "\n".join(l for l in lines if l.startswith("check"))
    assert result["failed"] == 0 and result["attempted"] >= 2
    # the new reference made the seeded weights and ran the checked steps ...
    asked = cell.reference.CALLS
    assert [a.seed for a in asked if a.what == "run"] == [2**31 + 26]
    assert any(a.what == "init_params" for a in asked)
    # ... as a module like any other: found by its name, executed once a process
    assert pickle.loads(pickle.dumps(asked[0])) == asked[0]
    assert cells.load_cell(CELL, root=root).reference is cell.reference
    # ... the run's log names both modules, and the new limits are the ones printed
    served = next(l for l in lines if l.startswith("  modules:"))
    assert served.count(f"{STEM}.py") == 2 and str(root) in served
    limit = f" limit {TOY_LIMITS['grad1_worst_leaf']:.4e} "
    assert any(l.startswith("check: grad1_worst_leaf") and limit in l for l in lines)
    # ... and mfu's required operations came from the new count
    assert cell.operations.CALLS == ["train_flops_per_token"]
    once = flops.train_flops_per_token(cell.model, 64)["total"]
    need = next(l for l in lines if l.startswith("required operations per token"))
    assert f"{PASSES * once / 1e9:.4f} G" in need and f"passes {PASSES}" in need
    kernels = cell.operations.kernel_calls(cell.model, cell.traffic, 1)
    assert {k["calls"] for k in kernels.values()} == {PASSES * 4}  # 4 micro-batches, 1 layer

    # nothing the benchmark had was touched, and BENCHMARK.json only gained entries
    after = files_of(root)
    assert {str(p) for p in set(after) - set(before)} == added
    repo = files_of(ROOT)
    for rel, held in before.items():
        if str(rel) != "BENCHMARK.json":
            assert after[rel] == held == repo[rel], rel
    bench = cells.load_benchmark(root)
    for key, value in BENCH.items():
        assert bench[key][:len(value)] == value if isinstance(value, list) else bench[key] == value
    # an accepted cell of the copy still gets the defaults: the copy's own
    old = cells.load_cell(BENCH["workloads"][0]["name"], root=root)
    assert [old.reference.__file__, old.operations.__file__] == [
        str(root.joinpath(*name.split(".")).with_suffix(".py"))
        for name in cells.DEFAULT_MODULES.values()]


def test_a_module_that_breaks_the_contract_is_refused(grown):
    root, _, _ = grown
    (root / "benchmark" / "operations" / f"{STEM}.py").write_text(
        "def train_flops_per_token(model, seq_len):\n    return {'total': 1.0}\n")
    with pytest.raises(AttributeError, match="lacks .'kernel_calls'."):
        cells.load_cell(CELL, root=root)
    (root / "benchmark" / "operations" / f"{STEM}.py").unlink()
    with pytest.raises(FileNotFoundError):
        cells.load_cell(CELL, root=root)


#: a published block in the source's own key names, with no
#: ``num_key_value_heads``; 64 routed experts published, 8 held here
EXPERTS = {
    "published": {"d_model": 64, "moe_d_ff": 128, "n_heads": 4, "d_latent": 16,
                  "n_routed_experts": 64, "n_experts_per_tok": 2, "vocab": 256,
                  "rope_base": 10000.0},
    "deployment": "each layer over 8 chips; this chip holds 8 of the 64 experts",
    "widths": {"hidden_size": "d_model", "intermediate_size": "moe_d_ff",
               "num_attention_heads": "n_heads", "head_dim": "d_latent",
               "vocab_size": "vocab", "rope_theta": "rope_base",
               "moe.num_experts": "n_routed_experts",
               "moe.top_k": "n_experts_per_tok"},
    "trainer_config": {"model": {
        "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "head_dim": 16, "vocab_size": 256, "rope_theta": 10000.0, "num_layers": 1,
        "moe": {"num_experts": 8, "top_k": 2}}},
}
REDUCED = ["num_layers", "moe.num_experts"]


def variant(edit=None, reduced=REDUCED):
    cfg = copy.deepcopy(EXPERTS)
    if edit:
        edit(cfg)
    return cfg, reduced


def narrow(c):
    c["trainer_config"]["model"].update(hidden_size=32)


@pytest.mark.parametrize("cfg, reduced, fault", [
    (*variant(), None),
    (*variant(reduced=["num_layers"]),
     "moe.num_experts is 8, published n_routed_experts is 64"),
    # a cut is named by the model's own key: the source's spelling excuses nothing
    (*variant(reduced=["num_layers", "n_routed_experts"]),
     "moe.num_experts is 8, published n_routed_experts is 64"),
    (*variant(narrow, reduced=REDUCED + ["d_model"]),
     "hidden_size is 32, published d_model is 64"),
    (*variant(narrow, reduced=REDUCED + ["hidden_size"]),
     "reduced names a width: hidden_size"),
    (*variant(narrow), "hidden_size is 32, published d_model is 64"),
    (*variant(reduced=REDUCED + ["moe.top_k"]), "reduced names a width: moe.top_k"),
    (*variant(reduced=REDUCED + ["rope_theta"]),
     "reduced cuts rope_theta, which is no count"),
    (*variant(lambda c: c["published"].pop("n_routed_experts")),
     "published lacks n_routed_experts"),
    (*variant(lambda c: c.update(deployment="")),
     "moe.num_experts is cut and no deployment is stated"),
    (*variant(lambda c: c["widths"].pop("head_dim")),
     "widths leaves out the model's head_dim"),
    (*variant(lambda c: c["widths"].pop("moe.top_k")),
     "widths leaves out the model's moe.top_k"),
    # what the accepted configurations are held to, a declared map covers too
    (*variant(lambda c: c["widths"].pop("num_attention_heads")),
     "widths leaves out the model's num_attention_heads"),
    (*variant(lambda c: c["widths"].pop("vocab_size")),
     "widths leaves out the model's vocab_size"),
    (*variant(lambda c: c["widths"].pop("rope_theta")),
     "widths leaves out the model's rope_theta"),
    (*variant(lambda c: c["widths"].pop("moe.num_experts")),
     "widths leaves out the model's moe.num_experts"),
], ids=["sound", "count-not-listed", "cut-by-published-key", "width-cut-by-published-key",
        "width-cut-by-model-key", "width-differs", "nested-width-listed", "no-count-cut",
        "published-count-missing", "no-deployment", "width-undeclared",
        "nested-width-undeclared", "heads-undeclared", "vocabulary-undeclared",
        "rope-undeclared", "experts-undeclared"])
def test_the_header_check_reads_the_files_own_widths(cfg, reduced, fault):
    faults = cells.header_faults(cfg, reduced)
    assert faults == [] if fault is None else any(fault in f for f in faults), faults


def test_a_file_with_no_published_block_is_refused(grown):
    root, _, _ = grown
    path = root / "benchmark" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg.update(cfg.pop("published"))      # the source's keys at the top level
    path.write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="header lacks .'published'."):
        cells.load_cell(CELL, root=root)
