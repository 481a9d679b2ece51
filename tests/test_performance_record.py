"""One performance record (``ROADMAP.md`` C1, PR 49): what judges a time is the
driver's ledger over ``benchmark/``, and nothing else in the repository
compares a clock with a stored clock.  Until PR 49 a second benchmark
(``bench`` + ``.py`` at the root), a ratchet under ``analysis/`` and four files
of CPU clocks under device names stood beside it, and the documents cited them
as the proof of speed.  This file keeps them from coming back."""

import json
import re
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
PACKAGE = REPO / "neuronx_distributed_training_tpu"

#: the deleted unit's names; ``tools/comms_bench.py`` is another file and stays
GONE = re.compile(r"(?<![A-Za-z0-9_])bench\.py|perf_contract|perf_baselines")

#: what a reader is told how the system works and is run (the dated records
#: ``CHANGES.md``, ``PERF.md``, ``docs/perf_history.md``, ``VERDICT.md``,
#: ``ROADMAP.md`` and ``ISSUE.md`` tell what was, and may name what is gone)
READ = ("neuronx_distributed_training_tpu", "tools", "tests", "docs",
        "examples", ".claude/skills", "README.md", "chip_smoke.py",
        "__graft_entry__.py", "pyproject.toml", ".gitignore")
RECORDS = {REPO / "docs" / "perf_history.md", Path(__file__).resolve()}

#: a key that holds a clock, a rate or a utilization
CLOCK = re.compile(r"second|(^|_)ms(_|$)|time|per_s|mfu|flops|gbps|latency",
                   re.I)


def _files():
    for name in READ:
        root = REPO / name
        paths = [root] if root.is_file() else sorted(root.rglob("*"))
        for path in paths:
            if (path.is_file() and "__pycache__" not in path.parts
                    and path.suffix != ".pyc" and path not in RECORDS):
                yield path


def test_nothing_names_the_second_benchmark_or_its_ratchet():
    named = []
    for path in _files():
        try:
            text = path.read_text()
        except UnicodeDecodeError:
            continue
        named += [f"{path.relative_to(REPO)}:{n}: {line.strip()[:100]}"
                  for n, line in enumerate(text.splitlines(), 1)
                  if GONE.search(line)]
    assert not named, "\n".join(named)
    assert not (REPO / "bench.py").exists()


def test_the_package_ships_no_stored_clock():
    """The JSON files the package ships are the graph contracts and the lint
    baseline (counts, bytes, findings): none holds a time, a rate or a
    utilization to compare a run against."""
    stored = sorted(PACKAGE.rglob("*.json"))
    assert stored and all(
        p.parent.name == "contracts" or p.name == "jaxlint_baseline.json"
        for p in stored), [str(p.relative_to(REPO)) for p in stored]

    def keys(doc):
        if isinstance(doc, dict):
            for k, v in doc.items():
                yield k
                yield from keys(v)
        elif isinstance(doc, list):
            for v in doc:
                yield from keys(v)

    clocks = {f"{p.name}: {k}" for p in stored
              for k in keys(json.loads(p.read_text())) if CLOCK.search(k)}
    assert not clocks, sorted(clocks)
