"""Shared by the benchmark's tests: a cell shrunk to toy widths (CPU), and
the limits that hold at those widths.

The cells' own limits (``benchmark/limits.json``) were read on the chip at
7B widths, where a norm averages rounding over 10^7..10^8 terms.  At hidden
size 64 the same comparison reads wider on both sides, so the tests carry
limits set by the same rule from toy readings (PR 23, CPU, seeds 1-3 and the
rehearsal seeds): sound program runs at most 1.2e-3 on the gradient and
parameter-change norms and 2.2e-4 on the loss; the fp8 control at least
4.0e-3 on the gradient norms.  A configuration whose toy readings are wider
brings ``toy_limits_<stem>.json`` (``toy_limits`` below).
"""

import copy
import dataclasses
import json
from pathlib import Path

TOY_MODEL = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, vocab_size=256,
                 sliding_window=32, max_position_embeddings=1024)
TOY_LIMITS = {"loss_gap": 1e-3, "grad1_worst_leaf": 2.2e-3,
              "dparam_worst_leaf": 3e-3}
TOY_LIMITS_ROUTED = {**TOY_LIMITS, "routed_leaves": "mlp/(router|experts)",
                     "grad1_routed_worst_leaf": 2.2e-3,
                     "dparam_routed_worst_leaf": 3e-3}


def toy(cell, seq=64):
    cfg = copy.deepcopy(cell.config)
    cfg["trainer_config"]["model"].update(TOY_MODEL)
    return dataclasses.replace(
        cell, config=cfg, traffic=dict(cell.traffic, seq_length=seq, trace_steps=3))


def toy_limits(cell):
    """The limits a cell is held to at toy widths: its configuration's own,
    ``toy_limits_<stem>.json`` beside this file (``why`` and ``limits``: the
    readings and what was set from them), where it has one.  The stem is that
    of the configuration's own reference module, as for its other files
    (``tests/test_ouro.py`` reads ``toy_limits_ouro.json`` by that name)."""
    stem = (cell.config.get("modules") or {}).get("reference")
    own = Path(__file__).parent / f"toy_limits_{stem}.json"
    if stem and own.exists():
        return json.loads(own.read_text())["limits"]
    return TOY_LIMITS_ROUTED if "moe" in cell.model else TOY_LIMITS
