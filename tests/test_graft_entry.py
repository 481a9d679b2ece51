"""Driver-contract smoke tests: entry() compiles, dryrun_multichip(8) executes
a real sharded train step on the virtual 8-device CPU mesh."""

import sys
from pathlib import Path

import jax

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import __graft_entry__ as graft  # noqa: E402

import pytest as _pytest_mark

pytestmark = _pytest_mark.mark.slow  # multi-minute parity tests; CI fast tier deselects


def test_entry_compiles(devices8):
    fn, args = graft.entry()
    lowered = jax.jit(fn).lower(*args)
    compiled = lowered.compile()
    loss = compiled(*args)
    assert float(loss) > 0


def test_dryrun_multichip_8(devices8):
    graft.dryrun_multichip(8)
