"""Graph contracts: the compile-artifact regression ratchet.

Per-rule fault injections prove the differ fires on every seeded contract
break (added collective, GSPMD reshard, lost donation, dtype upcast, memory
+20%); snapshots are byte-stable across identical runs; the update flow
refuses growth without a justification; and every shipped example config
checks clean against its committed contract with every collective
attributed (the acceptance criterion)."""

import copy
import glob
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from neuronx_distributed_training_tpu.analysis import graph_contract as gc
from neuronx_distributed_training_tpu.analysis.graph_contract import (
    ContractError,
    DeclaredComms,
    attribution_report,
    check_contract,
    diff_fingerprint,
    fingerprint_artifacts,
    fingerprint_config,
    unattributed_entries,
    update_contract,
)
from neuronx_distributed_training_tpu.telemetry.census import (
    _parse_iota_groups,
    collective_ops_from_texts,
)
from tests.test_graph_audit import compile_step, make_ctx, mesh_of

CONF = os.path.join(os.path.dirname(__file__), "..", "examples", "conf")
TINY = os.path.join(CONF, "tiny_smoke_config.yaml")


# --------------------------------------------------------------------------
# HLO collective-line parsing (telemetry.census structured census)
# --------------------------------------------------------------------------


class TestCollectiveParse:
    def test_explicit_groups_and_metadata(self):
        text = (
            "ENTRY %main {\n"
            "  %ar = f32[4]{0} all-reduce(f32[4]{0} %dot), channel_id=1, "
            "replica_groups={{0,1},{2,3}}, use_global_device_ids=true, "
            "to_apply=%add, metadata={op_name=\"jit(f)/dot_general\" "
            "source_file=\"x.py\"}\n"
            "}\n"
        )
        ops = collective_ops_from_texts([text])
        assert len(ops) == 1
        assert ops[0]["kind"] == "all-reduce"
        assert ops[0]["groups"] == [[0, 1], [2, 3]]
        assert ops[0]["source_op"] == "jit(f)/dot_general"

    def test_iota_groups_with_transpose(self):
        # [4,2]<=[2,4]T(1,0): arange(8).reshape(2,4).T.reshape(4,2)
        assert _parse_iota_groups("4,2", "2,4", "1,0") == [
            [0, 4], [1, 5], [2, 6], [3, 7]]

    def test_iota_groups_without_transpose(self):
        assert _parse_iota_groups("2,4", "2,4", None) == [
            [0, 1, 2, 3], [4, 5, 6, 7]]

    def test_iota_line_form(self):
        text = ("  %ag = f32[8]{0} all-gather(f32[4]{0} %p), channel_id=2, "
                "replica_groups=[4,2]<=[2,4]T(1,0), dimensions={0}\n")
        ops = collective_ops_from_texts([text])
        assert ops[0]["groups"] == [[0, 4], [1, 5], [2, 6], [3, 7]]

    def test_start_counts_done_does_not(self):
        text = (
            "  %s = (f32[4], f32[4]) all-gather-start(f32[4] %p), "
            "replica_groups={{0,1}}\n"
            "  %d = f32[4] all-gather-done((f32[4], f32[4]) %s)\n"
        )
        ops = collective_ops_from_texts([text])
        assert len(ops) == 1 and ops[0]["op"] == "s"

    def test_source_target_pairs(self):
        text = ("  %cp = f32[4] collective-permute(f32[4] %x), "
                "source_target_pairs={{0,1},{1,0}}\n")
        ops = collective_ops_from_texts([text])
        assert ops[0]["pairs"] == [(0, 1), (1, 0)]


class TestAxisResolution:
    def test_groups_resolve_to_axes(self, devices8):
        mesh = mesh_of(devices8, (2, 2, 2), ("data", "context", "model"))
        parts = gc._mesh_partitions(mesh)
        coords = gc._device_coords(mesh)
        # consecutive pairs = innermost (model) axis
        axes = gc._axes_of_op(
            {"groups": [[0, 1], [2, 3], [4, 5], [6, 7]], "pairs": None},
            mesh, parts, coords)
        assert axes == ("model",)
        # stride-4 pairs = outermost (data) axis
        axes = gc._axes_of_op(
            {"groups": [[0, 4], [1, 5], [2, 6], [3, 7]], "pairs": None},
            mesh, parts, coords)
        assert axes == ("data",)
        # groups of 4 spanning the two inner axes
        axes = gc._axes_of_op(
            {"groups": [[0, 1, 2, 3], [4, 5, 6, 7]], "pairs": None},
            mesh, parts, coords)
        assert axes == ("context", "model")

    def test_pairs_resolve_and_self_pairs_degenerate(self, devices8):
        mesh = mesh_of(devices8, (2, 2, 2), ("data", "context", "model"))
        parts = gc._mesh_partitions(mesh)
        coords = gc._device_coords(mesh)
        axes = gc._axes_of_op(
            {"groups": None, "pairs": [(0, 4), (4, 0), (1, 5), (5, 1)]},
            mesh, parts, coords)
        assert axes == ("data",)
        # identity pairs only: a no-op edge, not communication
        axes = gc._axes_of_op(
            {"groups": None, "pairs": [(0, 0), (1, 1)]}, mesh, parts, coords)
        assert axes == ()

    def test_irregular_partition_resolves_to_minimal_cover(self, devices8):
        """GSPMD sub-axis groups (no exact axis-subset partition) attribute
        to the MINIMAL axis set whose blocks contain every group — traffic
        confined within an axis's blocks is that axis's communication."""
        mesh = mesh_of(devices8, (2, 2, 2), ("data", "context", "model"))
        parts = gc._mesh_partitions(mesh)
        coords = gc._device_coords(mesh)
        # irregular pairing inside each (context, model) block of 4
        axes = gc._axes_of_op(
            {"groups": [[0, 3], [1, 2], [4, 7], [5, 6]], "pairs": None},
            mesh, parts, coords)
        assert axes == ("context", "model")
        # half-axis groups on a flat data mesh still read as data traffic
        flat = mesh_of(devices8, (8,), ("data",))
        fparts = gc._mesh_partitions(flat)
        fcoords = gc._device_coords(flat)
        axes = gc._axes_of_op(
            {"groups": [[0, 1, 2, 3], [4, 5, 6, 7]], "pairs": None},
            flat, fparts, fcoords)
        assert axes == ("data",)


# --------------------------------------------------------------------------
# provenance: a seeded GSPMD reshard is flagged with the nearest named op
# --------------------------------------------------------------------------


class TestProvenance:
    def test_declared_zero1_attributes(self, devices8):
        mesh = mesh_of(devices8, (8,), ("data",))

        def step(p, o, b, k):
            return ({"w": p["w"] + 1}, {"m": o["m"] * 2}, {"loss": b.sum()})

        args = ({"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                {"m": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                jax.ShapeDtypeStruct((8, 8), jnp.float32),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
        shlo, comp = compile_step(
            mesh, step,
            ({"w": P()}, {"m": P("data")}, P("data"), P()),
            ({"w": P()}, {"m": P("data")}, {"loss": P()}),
            args, donate=(0, 1),
        )
        fp = fingerprint_artifacts(make_ctx(mesh), comp, shlo)
        rep = attribution_report(fp)
        assert rep.stats["collectives_unattributed"] == 0, rep.format()
        assert not rep.findings

    def test_seeded_reshard_fires_gc201(self, devices8):
        """A dp-only config with zero1 off has no declared source for an
        all-gather: a batch-sharded value regathered to replicated is a
        GSPMD-inserted reshard — GC201, naming the op."""
        mesh = mesh_of(devices8, (8,), ("data",))

        def step(p, o, b, k):
            big = jnp.broadcast_to(b[:, None], (8, 64)) * p["w"].sum()
            return ({"w": p["w"] + 1}, {"m": o["m"] * 2},
                    {"gathered": big})

        args = ({"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                {"m": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                jax.ShapeDtypeStruct((8,), jnp.float32),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
        shlo, comp = compile_step(
            mesh, step,
            ({"w": P()}, {"m": P()}, P("data"), P()),
            ({"w": P()}, {"m": P()}, {"gathered": P()}),
            args, donate=(0, 1),
        )
        ctx = make_ctx(mesh, zero1=False)
        fp = fingerprint_artifacts(ctx, comp, shlo)
        unattr = unattributed_entries(fp)
        assert unattr, fp["collectives"]
        rep = attribution_report(fp)
        assert rep.failed("error")
        f = [x for x in rep.findings if x.rule == "GC201"][0]
        assert "no declared source" in f.message
        assert "nearest named op" in f.message
        assert f.location  # the offending HLO op is named

    def test_waiver_silences_gc201(self, devices8):
        mesh = mesh_of(devices8, (4, 2), ("data", "model"))
        fp = {"config": "x", "collectives": {
            "all-to-all|model": {"count": 2, "source": None, "hint": "",
                                 "sample_ops": ["all-to-all.1"],
                                 "sample_source_ops": ["jit(f)/transpose"]}}}
        assert attribution_report(fp).failed("error")
        rep = attribution_report(fp, waivers={"all-to-all|model": "known"})
        assert not rep.findings

    def test_source_classes_respect_declarations(self):
        d = DeclaredComms(tp=2, pp=1, cp=1, ep=1, dp=4, zero1=True,
                          seq_par=False, moe=False, ulysses=False, ring=False)
        rules = gc.declared_source_classes(d)
        assert gc.attribute("all-reduce", ("model",), [], rules)[0] \
            == "tp/SP layer collective"
        # no seq_par: an all-to-all over model has no declared source
        assert gc.attribute("all-to-all", ("model",), [], rules) is None
        # zero1 explains data-axis gathers
        assert "ZeRO-1" in gc.attribute(
            "all-gather", ("data",), [], rules)[0]
        d2 = DeclaredComms(tp=2, pp=1, cp=1, ep=1, dp=4, zero1=False,
                           seq_par=True, moe=False, ulysses=False, ring=False)
        rules2 = gc.declared_source_classes(d2)
        assert gc.attribute("all-to-all", ("model",), [], rules2)[0] \
            == "SP seq<->hidden reshard"
        assert gc.attribute("all-gather", ("data",), [], rules2) is None

    @pytest.mark.parametrize("dropless", [True, False],
                             ids=["dropless", "dropped"])
    def test_moe_source_classes(self, dropless):
        """Dropless MoE under EP declares the expert exchange over 'expert':
        the rows' transfers (all-gathers and all-to-alls, by their source ops
        under moe/dispatch and moe/combine) and, for the branch past the row
        bound, the expert weights' gather and their gradients' reduce-scatter,
        ahead of the embedding and ZeRO-1 classes; the capacity-factor path
        keeps its global routing gather."""
        d = DeclaredComms(tp=2, pp=1, cp=1, ep=2, dp=2, zero1=True,
                          seq_par=True, moe=True, ulysses=False, ring=False,
                          moe_dropless=dropless)
        rules = gc.declared_source_classes(d)
        scope = "jit(train_step)/jvp()/while/body/closed_call/moe/shard_map"
        back = ("jit(train_step)/transpose(jvp())/while/body/closed_call/"
                "checkpoint/moe/shard_map")
        gather = gc.attribute(
            "all-gather", ("expert",),
            [f"{scope}/dispatch/all_gather", f"{back}/combine/all_gather"],
            rules)[0]
        weights = gc.attribute(
            "all-gather", ("expert",),
            [f"{scope}/cond/branch_1_fun/experts/shard_map/all_gather"], rules)[0]
        returned = gc.attribute(
            "all-to-all", ("expert",),
            [f"{scope}/combine/all_to_all", f"{back}/dispatch/all_to_all"],
            rules)[0]
        scatter = gc.attribute(
            "reduce-scatter", ("expert",),
            [f"{back}/cond/branch_1_fun/experts/shard_map/reduce_scatter"],
            rules)[0]
        sort = gc.attribute(
            "all-gather", ("data", "expert", "model"),
            ["jit(train_step)/jvp()/moe/dispatch/jit(argsort)/sort"], rules)
        top_k = gc.attribute(
            "all-gather", ("data", "expert", "model"),
            ["jit(train_step)/jvp()/moe/router/top_k"], rules)[0]
        if dropless:
            assert top_k == "MoE router top-k gather"
            assert gather == weights == "ep exchange all-gather"
            assert returned == "ep exchange all-to-all"
            assert scatter == "ep expert gradient reduce-scatter"
            # a gather of the global token list is no declared cost
            assert sort is None
        else:
            assert gather != "ep exchange all-gather"
            assert returned == "ep token all-to-all"  # the dropped path's own
            assert "ZeRO-1" in scatter
            assert sort[0] == top_k == "MoE dropped routing gather"
        # without the block's own source op the classes do not over-claim
        assert "ZeRO-1" in gc.attribute(
            "reduce-scatter", ("expert",), [], rules)[0]


# --------------------------------------------------------------------------
# the semantic differ: per-rule fault injections
# --------------------------------------------------------------------------


def base_fp():
    return {
        "version": gc.FINGERPRINT_VERSION,
        "config": "fault.yaml",
        "mesh": {"pipe": 1, "data": 2, "expert": 1, "context": 1, "model": 2},
        "collectives": {
            "all-gather|data": {
                "count": 2, "source": "ZeRO-1 parameter all-gather",
                "hint": "ZeRO-1 resharding duplicated; likely spec change "
                        "in optim/zero1",
                "sample_ops": ["all-gather.1"], "sample_source_ops": ["w"]},
            "all-reduce|model": {
                "count": 4, "source": "tp/SP layer collective", "hint": "",
                "sample_ops": ["all-reduce.2"], "sample_source_ops": ["d"]},
        },
        "donation": {"expected": 4, "aliased": 4, "coverage": 1.0,
                     "missing": []},
        "matmul_dtypes": {"counts": {"bf16xbf16": 10},
                          "samples": {"bf16xbf16": "dot_general (...)"}},
        "memory": {"argument_size_in_bytes": 800, "temp_size_in_bytes": 200,
                   "resident_bytes": 1000},
    }


class TestDiffer:
    def test_identical_is_clean(self):
        rep = diff_fingerprint(base_fp(), base_fp())
        assert not rep.findings

    def test_added_collective_explained_in_config_terms(self):
        new = base_fp()
        new["collectives"]["all-gather|data"]["count"] = 4
        rep = diff_fingerprint(base_fp(), new)
        assert rep.failed("error")
        f = [x for x in rep.findings if x.rule == "GC101"][0]
        assert "[data]-axis all-gather count 2 -> 4" in f.message
        assert "ZeRO-1 parameter all-gather" in f.message
        assert "optim/zero1" in f.hint
        assert "all-gather.1" in f.message  # names the offending HLO op

    def test_new_unattributed_key_is_gc201(self):
        new = base_fp()
        new["collectives"]["all-to-all|model"] = {
            "count": 3, "source": None, "hint": "",
            "sample_ops": ["all-to-all.7"],
            "sample_source_ops": ["jit(step)/transpose"]}
        rep = diff_fingerprint(base_fp(), new)
        f = [x for x in rep.findings if x.rule == "GC201"][0]
        assert "GSPMD-inserted reshard" in f.message
        assert "jit(step)/transpose" in f.message
        assert rep.failed("error")

    def test_lost_donation_names_leaf(self):
        new = base_fp()
        new["donation"] = {"expected": 4, "aliased": 3, "coverage": 0.75,
                           "missing": ["params/w"]}
        rep = diff_fingerprint(base_fp(), new)
        f = [x for x in rep.findings if x.rule == "GC301"][0]
        assert "params/w" in f.message and "alias" in f.message
        assert rep.failed("error")

    def test_dtype_upcast_fires(self):
        new = base_fp()
        new["matmul_dtypes"]["counts"]["f32xf32"] = 2
        new["matmul_dtypes"]["samples"]["f32xf32"] = \
            "dot_general (tensor<8x8xf32> x tensor<8x8xf32>)"
        rep = diff_fingerprint(base_fp(), new)
        f = [x for x in rep.findings if x.rule == "GC401"][0]
        assert f.severity == "error" and "upcast" in f.message
        assert "f32" in f.location  # names the offending dot
        assert rep.failed("error")

    def test_memory_growth_20pct_fires_10pct_tolerated(self):
        new = base_fp()
        new["memory"]["resident_bytes"] = 1200
        rep = diff_fingerprint(base_fp(), new)
        assert any(f.rule == "GC501" and f.severity == "error"
                   for f in rep.findings)
        ok = base_fp()
        ok["memory"]["resident_bytes"] = 1050
        assert not diff_fingerprint(base_fp(), ok).failed("error")

    def test_shrink_is_info_only(self):
        new = base_fp()
        new["collectives"]["all-reduce|model"]["count"] = 2
        new["memory"]["resident_bytes"] = 500
        rep = diff_fingerprint(base_fp(), new)
        assert rep.findings  # the improvement is reported...
        assert not rep.failed("error")  # ...but the ratchet passes
        assert all(f.severity == "info" for f in rep.findings)

    def test_mesh_change_invalidates_contract(self):
        new = base_fp()
        new["mesh"]["model"] = 4
        rep = diff_fingerprint(base_fp(), new)
        assert any(f.rule == "GC002" for f in rep.findings)
        assert rep.failed("error")

    def test_waived_key_growth_still_fails(self):
        old = base_fp()
        old["collectives"]["all-to-all|model"] = {
            "count": 1, "source": None, "hint": "", "sample_ops": ["a.1"],
            "sample_source_ops": []}
        new = copy.deepcopy(old)
        new["collectives"]["all-to-all|model"]["count"] = 3
        rep = diff_fingerprint(old, new, waivers={"all-to-all|model": "ok"})
        assert any(f.rule == "GC101" for f in rep.findings)
        assert rep.failed("error")


# --------------------------------------------------------------------------
# snapshots: byte stability + the justification ratchet
# --------------------------------------------------------------------------


class TestSnapshotRatchet:
    def test_update_then_check_clean(self, tmp_path):
        path, rep = update_contract("fault.yaml", base_fp(),
                                    contracts_dir=tmp_path)
        assert path.exists()
        crep = check_contract("fault.yaml", base_fp(), contracts_dir=tmp_path)
        assert not crep.findings

    def test_missing_contract_is_gc000(self, tmp_path):
        rep = check_contract("fault.yaml", base_fp(), contracts_dir=tmp_path)
        assert any(f.rule == "GC000" for f in rep.findings)
        assert rep.failed("error")

    def test_rewrite_is_byte_stable(self, tmp_path):
        path, _ = update_contract("fault.yaml", base_fp(),
                                  contracts_dir=tmp_path)
        first = path.read_bytes()
        update_contract("fault.yaml", base_fp(), contracts_dir=tmp_path)
        assert path.read_bytes() == first

    def test_growth_refuses_without_justify(self, tmp_path):
        update_contract("fault.yaml", base_fp(), contracts_dir=tmp_path)
        grown = base_fp()
        grown["collectives"]["all-gather|data"]["count"] = 4
        with pytest.raises(ContractError, match="justify"):
            update_contract("fault.yaml", grown, contracts_dir=tmp_path)
        # the committed file is untouched by the refused update
        crep = check_contract("fault.yaml", base_fp(), contracts_dir=tmp_path)
        assert not crep.findings

    def test_growth_with_justify_records_in_file(self, tmp_path):
        update_contract("fault.yaml", base_fp(), contracts_dir=tmp_path)
        grown = base_fp()
        grown["collectives"]["all-gather|data"]["count"] = 4
        path, _ = update_contract(
            "fault.yaml", grown, justify="fused CE adds one regather pair",
            contracts_dir=tmp_path)
        snap = json.loads(path.read_text())
        assert "fused CE adds one regather pair" in snap["justifications"]
        crep = check_contract("fault.yaml", grown, contracts_dir=tmp_path)
        assert not crep.failed("error")

    def test_shrink_updates_silently(self, tmp_path):
        update_contract("fault.yaml", base_fp(), contracts_dir=tmp_path)
        better = base_fp()
        better["collectives"]["all-reduce|model"]["count"] = 2
        path, rep = update_contract("fault.yaml", better,
                                    contracts_dir=tmp_path)  # no justify
        assert not rep.failed("error")
        snap = json.loads(path.read_text())
        assert snap["fingerprint"]["collectives"]["all-reduce|model"][
            "count"] == 2

    def test_unattributed_needs_justify_and_becomes_waiver(self, tmp_path):
        fp = base_fp()
        fp["collectives"]["all-to-all|model"] = {
            "count": 1, "source": None, "hint": "", "sample_ops": ["a.9"],
            "sample_source_ops": []}
        with pytest.raises(ContractError):
            update_contract("fault.yaml", fp, contracts_dir=tmp_path)
        path, _ = update_contract("fault.yaml", fp,
                                  justify="known ulysses boundary reshard",
                                  contracts_dir=tmp_path)
        snap = json.loads(path.read_text())
        assert snap["waivers"] == {
            "all-to-all|model": "known ulysses boundary reshard"}
        # and the waived reshard no longer fails the check
        crep = check_contract("fault.yaml", fp, contracts_dir=tmp_path)
        assert not crep.failed("error")


# --------------------------------------------------------------------------
# end to end: fingerprint a real config, break it, watch the ratchet fire
# --------------------------------------------------------------------------


class TestEndToEnd:
    @pytest.fixture(scope="class")
    def tiny_fp(self):
        return fingerprint_config(TINY)

    def test_fingerprint_byte_stable_across_runs(self, tiny_fp):
        fp2 = fingerprint_config(TINY)
        assert json.dumps(tiny_fp, sort_keys=True) \
            == json.dumps(fp2, sort_keys=True)

    def test_tiny_attributes_fully(self, tiny_fp):
        rep = attribution_report(tiny_fp)
        assert rep.stats["collectives_unattributed"] == 0, rep.format()
        assert rep.stats["collectives_total"] > 0

    def test_tiny_checks_clean_against_committed(self, tiny_fp):
        rep = check_contract(TINY, tiny_fp)
        assert not rep.failed("error"), rep.format()

    def test_seeded_breaks_fail_check(self, tiny_fp, tmp_path):
        update_contract(TINY, tiny_fp, contracts_dir=tmp_path)
        broken = copy.deepcopy(tiny_fp)
        key = next(iter(broken["collectives"]))
        broken["collectives"][key]["count"] += 2
        broken["donation"]["missing"] = ["params/embed"]
        broken["donation"]["coverage"] = 0.97
        broken["matmul_dtypes"]["counts"]["f32xf32"] = \
            broken["matmul_dtypes"]["counts"].get("f32xf32", 0) + 5
        broken["memory"]["resident_bytes"] = int(
            broken["memory"]["resident_bytes"] * 1.2)
        rep = check_contract(TINY, broken, contracts_dir=tmp_path)
        rules = {f.rule for f in rep.findings if f.severity == "error"}
        assert {"GC101", "GC301", "GC401", "GC501"} <= rules, rep.format()


#: every shipped example config must check clean against its committed
#: contract with every collective attributed (acceptance criterion); the
#: shrunk lowering is ~1-2 s per config, so the sweep stays tier-1
@pytest.mark.parametrize(
    "config_path",
    sorted(glob.glob(os.path.join(CONF, "*.yaml"))),
    ids=lambda p: os.path.basename(p).replace("_config.yaml", ""),
)
def test_example_config_contract_clean(config_path):
    fp = fingerprint_config(config_path)
    assert not unattributed_entries(fp), json.dumps(
        unattributed_entries(fp), indent=1)
    rep = check_contract(config_path, fp)
    assert not rep.failed("error"), rep.format()


# --------------------------------------------------------------------------
# in-loop wiring: the telemetry.graph_audit verdict carries provenance
# --------------------------------------------------------------------------


def test_trainer_graph_audit_contract_in_run_summary(tmp_path):
    from neuronx_distributed_training_tpu.config.loader import load_config
    from neuronx_distributed_training_tpu.trainer.loop import Trainer

    cfg = load_config(TINY, {
        "exp_manager.exp_dir": str(tmp_path),
        "exp_manager.telemetry.graph_audit": True,
        "data.global_batch_size": 16,
        "data.micro_batch_size": 1,
        "trainer.max_steps": 2,
    })
    trainer = Trainer.from_config(cfg, enable_checkpointing=False)
    trainer.fit()
    with open(os.path.join(trainer.exp.log_dir, "run_summary.json")) as f:
        summary = json.load(f)
    audit = summary["graph_audit"]
    assert audit["verdict"] == "clean"
    contract = audit["contract"]
    assert contract["collectives_unattributed"] == 0
    assert contract["collectives_total"] > 0
    assert all(v["source"] for v in contract["collectives"].values())
    assert contract["matmul_dtypes"]


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------


def test_graph_contract_cli_check(monkeypatch, capsys):
    import sys

    tools = os.path.join(os.path.dirname(__file__), "..", "tools")
    sys.path.insert(0, tools)
    try:
        import graph_contract as cli

        monkeypatch.setattr(sys, "argv", [
            "graph_contract.py", "--check", "--config", TINY, "--json", "-"])
        with pytest.raises(SystemExit) as exc:
            cli.main()
        assert exc.value.code == 0
        out = capsys.readouterr().out
        assert "clean" in out
        payload = json.loads(out.strip().splitlines()[-1])
        assert payload["reports"][0]["verdict"] == "clean"
        assert payload["reports"][0]["fingerprint"]["collectives"]
    finally:
        sys.path.remove(tools)


def test_graph_contract_cli_update_to_tmpdir(monkeypatch, capsys, tmp_path):
    import sys

    tools = os.path.join(os.path.dirname(__file__), "..", "tools")
    sys.path.insert(0, tools)
    try:
        import graph_contract as cli

        monkeypatch.setattr(sys, "argv", [
            "graph_contract.py", "--update-contracts", "--config", TINY,
            "--contracts-dir", str(tmp_path)])
        with pytest.raises(SystemExit) as exc:
            cli.main()
        assert exc.value.code == 0
        assert (tmp_path / "tiny_smoke_config.json").exists()
        monkeypatch.setattr(sys, "argv", [
            "graph_contract.py", "--check", "--config", TINY,
            "--contracts-dir", str(tmp_path)])
        with pytest.raises(SystemExit) as exc:
            cli.main()
        assert exc.value.code == 0
    finally:
        sys.path.remove(tools)


def test_preflight_contracts_flag(monkeypatch, capsys):
    import sys

    tools = os.path.join(os.path.dirname(__file__), "..", "tools")
    sys.path.insert(0, tools)
    try:
        import preflight_audit

        monkeypatch.setattr(sys, "argv", [
            "preflight_audit.py", "--config", TINY, "--contracts"])
        with pytest.raises(SystemExit) as exc:
            preflight_audit.main()
        assert exc.value.code == 0
        out = capsys.readouterr().out
        assert "contract [tiny_smoke_config.yaml]: clean" in out
    finally:
        sys.path.remove(tools)
