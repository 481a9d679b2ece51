#!/usr/bin/env python
"""Render a run's ``metrics.jsonl`` + ``run_summary.json`` as a terminal table.

The quick ocular check before reaching for TensorBoard: last/mean/peak per
logged metric, the goodput breakdown, and the compile census — everything the
unified telemetry layer wrote, in one screen.

    python tools/metrics_report.py nxdt_experiments/hf_llama3_8B/version_0
    python tools/metrics_report.py path/to/metrics.jsonl --last 50
    python tools/metrics_report.py run_dir --follow --interval 5

``--follow`` live-tails a RUNNING fleet from one terminal: the report
re-renders every ``--interval`` seconds, picking up new metrics.jsonl
lines, the latest ``fleet_summary.json`` (straggler / quiet-host findings
from the beacon plane, docs/observability.md "Fleet observability"), and a
per-host beacon freshness line tailed straight from ``fleet/host_*.jsonl``.
Stop with Ctrl-C.

Pure stdlib on purpose: it must run on a login node with nothing installed.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

# sibling helpers (tools/_ctltrail.py): running as a script puts this dir
# on sys.path already; a by-file-path spec load (the tests) does not
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def load_metrics(path: str) -> list[dict]:
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except ValueError:
                continue  # torn tail line from a live run
    return records


def _fmt(v: float) -> str:
    if v != v:  # NaN
        return "nan"
    a = abs(v)
    if a != 0 and (a >= 1e6 or a < 1e-3):
        return f"{v:.3e}"
    if a >= 100 or float(v).is_integer():
        return f"{v:,.1f}" if not float(v).is_integer() else f"{v:,.0f}"
    return f"{v:.4f}"


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{n:.0f} B"
        n /= 1024
    return f"{n:.1f} TiB"


def _table(rows: list[tuple[str, ...]], header: tuple[str, ...]) -> str:
    widths = [max(len(str(r[i])) for r in [header, *rows])
              for i in range(len(header))]
    def fmt_row(r):
        return "  ".join(str(c).ljust(w) if i == 0 else str(c).rjust(w)
                         for i, (c, w) in enumerate(zip(r, widths)))
    sep = "  ".join("-" * w for w in widths)
    return "\n".join([fmt_row(header), sep, *(fmt_row(r) for r in rows)])


def metrics_table(records: list[dict], last_n: int = 0) -> str:
    if last_n > 0:
        records = records[-last_n:]
    by_key: dict[str, list[float]] = {}
    for rec in records:
        for k, v in rec.items():
            if k == "step" or not isinstance(v, (int, float)):
                continue
            if isinstance(v, float) and math.isnan(v):
                continue
            by_key.setdefault(k, []).append(float(v))
    rows = []
    for k in sorted(by_key):
        vals = by_key[k]
        rows.append((k, _fmt(vals[-1]), _fmt(sum(vals) / len(vals)),
                     _fmt(max(vals)), str(len(vals))))
    steps = [r.get("step") for r in records if isinstance(r.get("step"), int)]
    span = f"steps {steps[0]}..{steps[-1]}" if steps else "no steps"
    return (f"metrics ({span}, {len(records)} boundary records)\n"
            + _table(rows, ("metric", "last", "mean", "peak", "n")))


def goodput_section(summary: dict) -> str:
    gp = summary.get("goodput")
    if not gp:
        return ""
    lines = ["", "goodput"]
    frac = gp.get("goodput_fraction")
    if frac is not None:
        lines.append(f"  goodput_fraction      {frac:.4f}")
    for key in ("wall_seconds", "productive_seconds", "nonproductive_seconds"):
        if key in gp:
            lines.append(f"  {key:<21} {_fmt(gp[key])}")
    for name, secs in sorted((gp.get("breakdown_seconds") or {}).items()):
        lines.append(f"    {name:<19} {_fmt(secs)} s")
    return "\n".join(lines)


def startup_section(summary: dict) -> str:
    """The start-up timeline of the process's first fit()
    (docs/observability.md "Start-up timeline")."""
    st = summary.get("startup")
    if not st:
        return ""
    cache = st.get("compile_cache") or {}
    lines = ["", f"start-up ({st.get('origin')} to the end of the first "
                 f"boundary's fetch: {_fmt(st.get('to_first_step_s'))} s; "
                 f"unattributed {_fmt(st.get('unattributed_pct'))} %; compile "
                 f"cache {cache.get('cache_hits')} hits, "
                 f"{cache.get('cache_misses')} misses)"]
    for ph in st.get("phases") or []:
        lines.append(f"  {_fmt(ph['begin_s']):>10}  {ph['name']:<19} "
                     f"{_fmt(ph['seconds'])} s")
    for name, secs in sorted((st.get("imports_s") or {}).items()):
        lines.append(f"    import {name:<25} {_fmt(secs)} s")
    return "\n".join(lines)


def _plan_str(plan: dict) -> str:
    # deliberate copy of trainer/elastic.py::_plan_str — importing it would
    # pull the package __init__ (and jax) into this stdlib-only tool; keep
    # the two in sync when the plan record grows a rendered key
    keys = ("dp", "tp", "pp", "cp", "ep", "vp")
    parts = [f"{k}={plan[k]}" for k in keys if plan.get(k) is not None]
    if plan.get("micro_batch_size") is not None:
        parts.append(f"mbs={plan['micro_batch_size']}")
    if plan.get("schedule") not in (None, "none"):
        parts.append(f"sched={plan['schedule']}")
    return " ".join(parts) or "?"


def elastic_section(summary: dict) -> str:
    """Restart/replan trail (trainer.elastic -> run_summary.json "elastic"):
    whether this incarnation resumed, what the restart cost in span time,
    and — when the world size changed — the old plan -> new plan record the
    restart-time autotune replanner imposed (docs/elasticity.md)."""
    el = summary.get("elastic")
    if not isinstance(el, dict) or not el:
        return ""
    lines = ["", "elastic (restart/replan trail — docs/elasticity.md)"]
    lines.append(f"  resumed               {bool(el.get('resumed'))}")
    for key in ("restart_seconds", "replan_seconds"):
        if el.get(key) is not None:
            lines.append(f"  {key:<21} {_fmt(el[key])}")
    if el.get("stop_reason"):
        lines.append(f"  stop_reason           {el['stop_reason']}")
    rec = el.get("replan")
    if isinstance(rec, dict) and rec:
        lines.append(
            f"  replanned             world "
            f"{rec.get('old_world', '?')} -> {rec.get('new_world', '?')} "
            f"chips (resuming step {rec.get('checkpoint_step', '?')})")
        lines.append(f"    old plan            "
                     f"{_plan_str(rec.get('old_plan') or {})}")
        lines.append(f"    new plan            "
                     f"{_plan_str(rec.get('new_plan') or {})}")
        if rec.get("predicted_step_seconds") is not None:
            lines.append(f"    predicted_step      "
                         f"{_fmt(rec['predicted_step_seconds'])} s")
        if rec.get("skipped_incompatible"):
            lines.append(f"    skipped             "
                         f"{rec['skipped_incompatible']} layout-incompatible "
                         f"candidate(s)")
    return "\n".join(lines)


def integrity_section(summary: dict) -> str:
    """Checkpoint-integrity trail (checkpoint/integrity.py ->
    run_summary.json "integrity"): which step actually verified at restore,
    how many corrupt steps the walk-back skipped, what was quarantined, and
    the post-commit audit's cost (docs/elasticity.md "Integrity &
    walk-back")."""
    it = summary.get("integrity")
    if not isinstance(it, dict) or not it:
        return ""
    lines = ["", "integrity (verified restore — docs/elasticity.md)"]
    if it.get("verified_step") is not None:
        lines.append(f"  verified_step         {it['verified_step']}")
    if it.get("walk_back_count") is not None:
        lines.append(f"  walk_back_count       {it['walk_back_count']}")
    q = it.get("quarantined_steps") or []
    if q:
        lines.append(f"  quarantined_steps     "
                     f"{', '.join(str(s) for s in q)}")
    if it.get("legacy_restore"):
        lines.append("  legacy_restore        True (pre-integrity "
                     "checkpoint, restored UNVERIFIED)")
    if it.get("verify_seconds") is not None:
        lines.append(f"  verify_seconds        {_fmt(it['verify_seconds'])}")
    audit = it.get("audit")
    if isinstance(audit, dict) and audit:
        line = (f"  audit                 {audit.get('audited', 0)} step(s), "
                f"{audit.get('failed', 0)} failed, "
                f"{_fmt(audit.get('seconds', 0.0))} s")
        if audit.get("incomplete"):
            line += f", {audit['incomplete']} incomplete at teardown"
        lines.append(line)
        aq = it.get("audit_quarantined") or []
        if aq:
            lines.append(f"    audit_quarantined   "
                         f"{', '.join(str(s) for s in aq)}")
    return "\n".join(lines)


def anomalies_section(summary: dict) -> str:
    """Flight-recorder trail: one line per forensic bundle the run dumped
    (render a bundle itself with ``tools/anomaly_report.py``)."""
    anomalies = summary.get("anomalies") or []
    if not anomalies:
        return ""
    lines = ["", f"anomalies ({len(anomalies)} forensic bundle"
                 f"{'s' if len(anomalies) != 1 else ''} — "
                 f"tools/anomaly_report.py renders one)"]
    for a in anomalies:
        # tolerate partial/malformed entries (older schema / hand edits) —
        # a bad trail line must not abort the whole report
        if not isinstance(a, dict):
            lines.append(f"  (unreadable entry: {a!r})")
            continue
        step = str(a.get("step", "?"))
        policy = str(a.get("policy", "?"))
        lines.append(f"  step {step:<8} policy={policy:<18} "
                     f"{a.get('bundle', '?')}")
    return "\n".join(lines)


def trace_section(trace: dict) -> str:
    """Device-time trace summary (telemetry.trace -> trace_summary.json):
    achieved overlap, exposed collective time, and the top-5 op table —
    render the full breakdown with ``tools/trace_report.py``."""
    if not trace:
        return ""
    lines = ["", "device-time trace (tools/trace_report.py renders the "
                 "full breakdown)"]
    ov = trace.get("achieved_overlap")
    if ov is not None:
        lines.append(f"  achieved_overlap      {100 * float(ov):.1f}% of "
                     f"collective wire time hidden under compute")
    for key in ("collective_seconds", "exposed_collective_seconds",
                "total_device_seconds"):
        if trace.get(key) is not None:
            lines.append(f"  {key:<21} {_fmt(trace[key])}")
    top = (trace.get("top_ops") or [])[:5]
    if top:
        lines.append("  top ops by device time:")
        for o in top:
            lines.append(
                f"    {o.get('op', '?'):<20} {_fmt(o.get('total_seconds', 0))} s"
                f"  ({100 * o.get('share', 0.0):.1f}%, {o.get('class', '?')})")
    return "\n".join(lines)


def comms_section(summary: dict) -> str:
    """In-loop achieved interconnect bandwidth (telemetry.comms — the
    trainer's join of traced per-class wire seconds with the cost model's
    byte volumes; tools/comms_report.py renders the standalone sweep)."""
    comms = summary.get("comms")
    if not isinstance(comms, dict) or not comms.get("classes"):
        return ""
    peak = comms.get("peak_bandwidth_gbps")
    lines = ["", f"interconnect (measured achieved bandwidth vs "
                 f"{_fmt(peak) if peak is not None else '?'} GB/s topology "
                 f"peak — docs/observability.md 'Interconnect observatory')"]
    for kind in sorted(comms["classes"]):
        e = comms["classes"][kind]
        if not isinstance(e, dict):
            continue
        eff = e.get("efficiency")
        lines.append(
            f"  {kind:<20} achieved={_fmt(e.get('achieved_gbps'))} GB/s"
            + (f"  efficiency={100 * eff:.1f}%" if eff is not None else "")
            + (f"  wire_s/step={_fmt(e.get('wire_seconds_per_step'), 6)}"
               if e.get("wire_seconds_per_step") is not None else ""))
    return "\n".join(lines)


def alerts_section(summary: dict) -> str:
    """Alert-engine trail (telemetry.alerts -> run_summary.json "alerts"):
    one line per firing, with the action the loop took."""
    alerts = summary.get("alerts") or []
    if not alerts:
        return ""
    lines = ["", f"alerts ({len(alerts)} firing"
                 f"{'s' if len(alerts) != 1 else ''} — "
                 f"docs/observability.md 'Alert rules')"]
    for a in alerts:
        if not isinstance(a, dict):
            lines.append(f"  (unreadable entry: {a!r})")
            continue
        lines.append(f"  step {str(a.get('step', '?')):<8} "
                     f"action={str(a.get('action', '?')):<5} "
                     f"[{a.get('rule', '?')}] {a.get('message', '')}")
    return "\n".join(lines)


def control_section(summary: dict) -> str:
    """Fleet-control trail (trainer.control -> run_summary.json "control"):
    operator commands received (with ack status), and every consensus
    decision — the step it landed, the deciding condition, and the reason
    (docs/observability.md "Fleet control").  The line formatter is shared
    with ``tools/fleet_monitor.py`` (``tools/_ctltrail.py``)."""
    ctl = summary.get("control")
    if not isinstance(ctl, dict) or not ctl:
        return ""
    from _ctltrail import control_trail_lines

    return "\n".join(["", "fleet control (consensus decisions — "
                          "docs/observability.md 'Fleet control')",
                      *control_trail_lines(ctl)])


def memory_section(summary: dict, run_dir: str | None) -> str:
    """Memory observability (telemetry.memory -> run_summary.json "memory"
    + memory_summary.json): live-buffer attribution per subsystem, peak
    HBM, headroom, and the OOM trail when one fired — render the full
    breakdown (per-device spread, predicted-vs-measured) with
    ``tools/memory_report.py``."""
    mem = summary.get("memory")
    oom = summary.get("oom")
    doc: dict = {}
    if run_dir:
        try:
            with open(os.path.join(run_dir, "memory_summary.json")) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            pass
    if not isinstance(mem, dict):
        mem = {}
    if not mem and not doc and not oom:
        return ""
    lines = ["", "memory (telemetry.memory — docs/observability.md "
                 "'Memory observability'; tools/memory_report.py renders "
                 "the full breakdown)"]
    prof = doc.get("profile") or {}
    in_use = mem.get("in_use_bytes") or prof.get("total_bytes")
    if in_use is not None:
        lines.append(f"  in_use_bytes          {_fmt_bytes(in_use)} "
                     f"(profiled step "
                     f"{mem.get('profiled_step', doc.get('profiled_step', '?'))})")
    peak = mem.get("peak_hbm_bytes") or (doc.get("sampled")
                                         or {}).get("peak_hbm_bytes")
    if peak is not None:
        lines.append(f"  peak_hbm_bytes        {_fmt_bytes(peak)} "
                     f"(worst device watermark)")
    pred = mem.get("predicted_hbm_bytes") or (doc.get("predicted")
                                              or {}).get("total")
    if pred and in_use:
        n_dev = max(int(prof.get("num_devices", 1) or 1), 1)
        lines.append(f"  predicted_hbm_bytes   {_fmt_bytes(pred)} per device "
                     f"(measured/predicted "
                     f"{float(peak or in_use / n_dev) / float(pred):.2f})")
    att = doc.get("attribution") or {}
    if not att and mem.get("attribution"):
        att = {k: {"bytes": v} for k, v in mem["attribution"].items()
               if v is not None}
    if att:
        total = prof.get("total_bytes") or sum(
            (r.get("bytes") if isinstance(r, dict) else r) or 0
            for r in att.values())
        lines.append("  attribution (live bytes per subsystem):")
        order = ("params", "opt_state", "master", "ema", "activations",
                 "chunk_store", "moe_workspace", "batch", "executable",
                 "unattributed")
        # known order first, then any class this tool's list predates —
        # the plane's "never silently dropped" contract holds here too
        for cls in (*order, *(c for c in att if c not in order)):
            rec = att.get(cls)
            if rec is None:
                continue
            b = rec.get("bytes") if isinstance(rec, dict) else rec
            share = (f"  ({100 * float(b) / float(total):.1f}%)"
                     if total and b is not None else "")
            lines.append(f"    {cls:<14} {_fmt_bytes(b or 0):>12}{share}")
    if isinstance(oom, dict) and oom:
        lines.append(f"  OOM at step {oom.get('step', '?')}: bundle "
                     f"{oom.get('bundle', '?')} — {oom.get('error', '')}")
    return "\n".join(lines)


def fleet_section(run_dir: str | None) -> str:
    """Fleet plane summary (telemetry.fleet -> fleet_summary.json): host
    count, the modal straggler with its cause, quiet hosts, and the fleet
    goodput decomposition — render the full per-window breakdown with
    ``tools/fleet_monitor.py``."""
    if not run_dir:
        return ""
    path = os.path.join(run_dir, "fleet_summary.json")
    if not os.path.exists(path):
        return ""
    try:
        with open(path) as f:
            fs = json.load(f)
    except ValueError:
        return f"\nunreadable {path}"
    lines = ["", f"fleet ({fs.get('n_hosts', 0)} hosts — "
                 f"tools/fleet_monitor.py renders the full breakdown)"]
    st = fs.get("straggler")
    if st:
        lines.append(f"  straggler             host {st.get('host')} "
                     f"({st.get('cause')}; led {st.get('windows_led')}/"
                     f"{st.get('windows_attributed')} windows)")
    gp = fs.get("goodput") or {}
    if gp.get("fleet_goodput_fraction") is not None:
        lines.append(f"  fleet_goodput         "
                     f"{_fmt(gp['fleet_goodput_fraction'])} "
                     f"(straggler loss {_fmt(gp.get('straggler_loss_fraction', 0))}, "
                     f"common {_fmt(gp.get('common_overhead_fraction', 0))})")
    for q in fs.get("quiet_hosts") or []:
        lines.append(f"  QUIET host {q.get('host')}    last step "
                     f"{q.get('last_step')}, silent "
                     f"{_fmt(q.get('silent_seconds'))} s")
    for f in fs.get("findings") or []:
        if f.get("kind") != "fleet_stall":  # quiet hosts rendered above
            lines.append(f"  [{f.get('kind')}] {f.get('message')}")
    return "\n".join(lines)


def beacon_tail_section(run_dir: str | None) -> str:
    """Per-host beacon freshness tailed straight from ``fleet/host_*.jsonl``
    (no aggregation — just "who reported what, when", cheap enough for the
    --follow refresh loop).  Torn tail lines (a live writer mid-flush, a
    died host) are skipped."""
    if not run_dir:
        return ""
    fleet_dir = os.path.join(run_dir, "fleet")
    if not os.path.isdir(fleet_dir):
        return ""
    import glob
    import time as _time

    rows = []
    now = _time.time()
    for path in sorted(glob.glob(os.path.join(fleet_dir, "host_*.jsonl"))):
        last = None
        try:
            # only the last record matters: seek to the final few KB
            # instead of re-parsing a multi-day stream on every refresh
            with open(path, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(0, size - 8192))
                tail = f.read().decode("utf-8", errors="replace")
            for line in tail.splitlines():
                line = line.strip()
                if not line:
                    continue
                try:
                    last = json.loads(line)
                except ValueError:
                    continue  # the cut-off first line / a torn tail
        except OSError:
            continue
        if not isinstance(last, dict):
            continue
        age = (now - float(last["t_wall"])
               if last.get("t_wall") is not None else None)
        status = ("closed" if last.get("closing")
                  else "DIED" if last.get("last_exception") else "live")
        m = last.get("metrics") or {}
        rows.append((os.path.basename(path).split(".")[0],
                     str(last.get("step", "?")), status,
                     f"{age:.0f}s" if age is not None else "-",
                     _fmt(m["loss"]) if m.get("loss") is not None else "-"))
    if not rows:
        return ""
    return "\n".join(["", "beacons (age = seconds since last heartbeat)",
                      _table(rows, ("host", "step", "status", "age",
                                    "loss"))])


def census_section(summary: dict) -> str:
    lines: list[str] = []
    if "compile_seconds" in summary:
        lines.append(f"  compile_seconds       {_fmt(summary['compile_seconds'])}")
    mem = summary.get("memory_analysis") or {}
    for key in ("peak_bytes", "temp_size_in_bytes", "argument_size_in_bytes",
                "output_size_in_bytes"):
        if key in mem:
            lines.append(f"  {key:<21} {_fmt_bytes(mem[key])}")
    coll = summary.get("collectives") or {}
    nonzero = {k: v for k, v in coll.items() if v}
    if coll:
        lines.append("  collectives           "
                     + (", ".join(f"{k}={v}" for k, v in sorted(nonzero.items()))
                        or "none"))
    for key in ("model_family", "n_chips", "seq_len", "global_batch_size",
                "pipeline_schedule", "bubble_fraction_predicted",
                "bubble_fraction_measured",
                "fwd_flops_per_token",
                "train_step_flops_per_token", "peak_tflops_per_chip"):
        if summary.get(key) is not None:
            v = summary[key]
            lines.append(f"  {key:<21} {_fmt(v) if isinstance(v, (int, float)) else v}")
    ticks = summary.get("pipeline_ticks_per_step")
    if isinstance(ticks, dict) and ticks:
        # the work-compacted executor's per-step trip counts (span +
        # per-kind active ticks vs the old lockstep count)
        lines.append("  ticks_per_step        "
                     + ", ".join(f"{k}={ticks[k]}" for k in sorted(ticks)))
    if summary.get("retrace_events"):
        lines.append(f"  retrace_events        {len(summary['retrace_events'])} "
                     f"(see run_summary.json — each cost a recompile)")
    if not lines:
        return ""
    return "\n".join(["", "compile census / run facts", *lines])


def render(metrics_path: str | None, summary_path: str | None,
           last_n: int = 0, trace_path: str | None = None,
           run_dir: str | None = None) -> str:
    parts: list[str] = []
    if metrics_path and os.path.exists(metrics_path):
        records = load_metrics(metrics_path)
        if records:
            parts.append(metrics_table(records, last_n))
        else:
            parts.append(f"no records in {metrics_path}")
    summary = {}
    if summary_path and os.path.exists(summary_path):
        try:
            with open(summary_path) as f:
                summary = json.load(f)
        except ValueError as e:
            parts.append(f"unreadable {summary_path}: {e}")
    if summary:
        parts.append(goodput_section(summary))
        parts.append(startup_section(summary))
        parts.append(elastic_section(summary))
        parts.append(integrity_section(summary))
        parts.append(anomalies_section(summary))
        parts.append(alerts_section(summary))
        parts.append(control_section(summary))
        parts.append(census_section(summary))
        parts.append(comms_section(summary))
    parts.append(memory_section(summary, run_dir))
    parts.append(fleet_section(run_dir))
    parts.append(beacon_tail_section(run_dir))
    if trace_path and os.path.exists(trace_path):
        try:
            with open(trace_path) as f:
                parts.append(trace_section(json.load(f)))
        except ValueError as e:
            parts.append(f"unreadable {trace_path}: {e}")
    return "\n".join(p for p in parts if p)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="run dir (containing metrics.jsonl / "
                                 "run_summary.json) or a metrics.jsonl file")
    ap.add_argument("--last", type=int, default=0,
                    help="only the last N boundary records (default: all)")
    ap.add_argument("--follow", action="store_true",
                    help="live-tail: re-render every --interval seconds "
                         "(metrics.jsonl + fleet beacons; Ctrl-C stops)")
    ap.add_argument("--interval", type=float, default=5.0,
                    help="refresh interval seconds for --follow (default 5)")
    ap.add_argument("--refreshes", type=int, default=0,
                    help="stop --follow after N refreshes (0 = forever; "
                         "mainly for smoke tests)")
    args = ap.parse_args(argv)

    path = args.path
    if os.path.isdir(path):
        metrics_path = os.path.join(path, "metrics.jsonl")
        summary_path = os.path.join(path, "run_summary.json")
        run_dir = path
    elif path.endswith(".jsonl"):
        metrics_path = path
        summary_path = os.path.join(os.path.dirname(path), "run_summary.json")
        run_dir = os.path.dirname(path) or "."
    else:
        metrics_path, summary_path = None, path
        run_dir = os.path.dirname(path) or "."
    trace_path = (os.path.join(os.path.dirname(summary_path),
                               "trace_summary.json")
                  if summary_path else None)
    if not any(p and os.path.exists(p) for p in (metrics_path, summary_path)):
        print(f"metrics_report: nothing to read at {path}", file=sys.stderr)
        return 2
    if not args.follow:
        print(render(metrics_path, summary_path, args.last, trace_path,
                     run_dir))
        return 0

    # --follow: the one-terminal fleet watch.  Re-render from scratch each
    # refresh (the files are small; incremental tailing lives in the
    # aggregator, not the report) with a timestamped banner per frame so
    # scrollback stays legible without cursor tricks.
    import time as _time

    n = 0
    try:
        while True:
            n += 1
            stamp = _time.strftime("%H:%M:%S")
            print(f"\n===== metrics_report --follow  refresh {n} "
                  f"({stamp}; every {args.interval:g}s, Ctrl-C stops) =====")
            print(render(metrics_path, summary_path,
                         args.last or 20, trace_path, run_dir))
            sys.stdout.flush()
            if args.refreshes and n >= args.refreshes:
                return 0
            _time.sleep(max(args.interval, 0.0))
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    raise SystemExit(main())
