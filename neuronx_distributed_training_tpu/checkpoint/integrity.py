"""Checkpoint integrity: end-to-end digests, verified restore, quarantine.

PR 9 closed the *availability* half of the NxDT resilience story (a failed
save never shadows the last good one, elastic resume reshards onto the live
fleet).  This module closes the *correctness* half: a save that committed
successfully yet is **corrupt** — bitrot on the store, a truncated object
after a partial upload, a torn multi-host write, version-skewed
serialization — must be detected and walked past, not crash-looped into.

Mechanics (docs/elasticity.md "Integrity & walk-back"):

- every save carries an ``integrity`` sidecar item (:func:`build_sidecar`):
  per-leaf-group content digests (``params``, ``opt_state/mu``,
  ``opt_state/master``, EMA, health, …) over the serialized bytes of every
  leaf, digests of the ``meta``/``manifest`` JSON items, and a
  tree-structure/shape/dtype summary — all computed host-side from the very
  trees handed to orbax (after the ``save_bf16`` cast, so the digests match
  the on-disk bytes);
- restore verifies the sidecar **before** imposing a mesh
  (:func:`verify_step` is template-free: items are read back with no target
  tree and re-hashed), and on mismatch the step is **quarantined** (the step
  dir is renamed ``quarantined.<step>.<reason>`` — invisible to orbax step
  discovery and to ``latest_version`` parsing — plus a ledger entry) and the
  walk-back continues to the newest step that verifies;
- a checkpoint that predates this subsystem (no sidecar) restores with a
  warning, never a crash;
- an optional post-commit **save audit** (:class:`SaveAuditor`, behind
  ``exp_manager.checkpoint.integrity.audit``) re-reads committed steps on a
  background thread so corruption is caught at save time, not days later.

The knob block (validated at config load with did-you-mean hints):

.. code-block:: yaml

    exp_manager:
      checkpoint:
        integrity:
          enabled: true                 # digest sidecar in every save
          verify_restore: true          # verify + walk back before restore
          quarantine: true              # rename + ledger corrupt steps
          audit: false                  # post-commit read-back audit
          audit_deadline_seconds: 120.0 # teardown drain bound
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import queue
import re
import shutil
import threading
import time
from pathlib import Path
from typing import Any, Callable, Mapping, Optional

import numpy as np

logger = logging.getLogger(__name__)

#: sidecar schema version (bump on breaking layout changes)
INTEGRITY_FORMAT = 1

#: orbax item name of the sidecar inside every save
INTEGRITY_ITEM = "integrity"

#: digest algorithm recorded in the sidecar (verification refuses a sidecar
#: hashed with an algorithm this build does not know)
DIGEST_ALGO = "blake2b-128"

#: quarantined step dirs are renamed ``quarantined.<step>.<reason>`` — the
#: leading prefix is non-numeric, so orbax step discovery and the
#: exp-manager ``version_N`` parse both skip them by construction
QUARANTINE_PREFIX = "quarantined."

#: quarantine ledger filename (checkpoint-root sibling of the step dirs)
LEDGER_NAME = "quarantine_ledger.json"

#: corruption kinds the drill harness can inject (tools/elastic_drill.py)
CORRUPTION_KINDS = ("byte_flip", "truncate", "delete_item", "stale_sidecar")

#: knob name -> default — the single source of truth the validator,
#: ``from_config``, and docs/elasticity.md share
INTEGRITY_KNOBS: dict[str, Any] = {
    "enabled": True,
    "verify_restore": True,
    "quarantine": True,
    "audit": False,
    "audit_deadline_seconds": 120.0,
}

#: keys the ``exp_manager.checkpoint`` block accepts
CHECKPOINT_BLOCK_KEYS = frozenset({"integrity"})


class CheckpointIntegrityError(RuntimeError):
    """No retained checkpoint verifies: every step in the retention chain is
    corrupt (or quarantined).  Carries the per-step verdicts so the operator
    sees *what* failed where instead of an opaque restore crash."""

    def __init__(self, message: str, verdicts: Optional[list] = None):
        super().__init__(message)
        self.verdicts = list(verdicts or [])


@dataclasses.dataclass(frozen=True)
class IntegrityConfig:
    """``exp_manager.checkpoint.integrity`` — checkpoint-integrity policy."""

    enabled: bool = True
    verify_restore: bool = True
    quarantine: bool = True
    audit: bool = False
    audit_deadline_seconds: float = 120.0

    @classmethod
    def from_config(cls, block: Any) -> "IntegrityConfig":
        """Parse (and validate) an ``exp_manager.checkpoint.integrity``
        block.  Accepts ``None``/``{}`` (defaults) or a mapping; a bare bool
        toggles ``enabled``.  Unknown keys and ill-typed values raise
        ``ValueError`` with a did-you-mean hint — a typo'd knob must not
        silently run with defaults."""
        if block is None:
            return cls()
        if isinstance(block, bool):
            return cls(enabled=block)
        if not isinstance(block, Mapping):
            raise ValueError(
                f"exp_manager.checkpoint.integrity must be a mapping of "
                f"{sorted(INTEGRITY_KNOBS)} (or a single bool), got "
                f"{type(block).__name__}"
            )
        unknown = set(block) - set(INTEGRITY_KNOBS)
        if unknown:
            from neuronx_distributed_training_tpu.config.loader import (
                did_you_mean,
            )

            raise ValueError(
                f"unknown exp_manager.checkpoint.integrity keys "
                f"{sorted(unknown)}; supported: {sorted(INTEGRITY_KNOBS)}"
                + did_you_mean(unknown, INTEGRITY_KNOBS)
            )
        values: dict[str, Any] = {}
        for k, v in block.items():
            default = INTEGRITY_KNOBS[k]
            if isinstance(default, bool):
                if not isinstance(v, bool):
                    raise ValueError(
                        f"exp_manager.checkpoint.integrity.{k} must be a "
                        f"boolean, got {v!r}"
                    )
                values[k] = v
            else:
                if isinstance(v, bool) or not isinstance(v, (int, float)):
                    raise ValueError(
                        f"exp_manager.checkpoint.integrity.{k} must be a "
                        f"number, got {v!r}"
                    )
                values[k] = float(v)
                if values[k] < 0.0:
                    raise ValueError(
                        f"exp_manager.checkpoint.integrity.{k} must be >= 0, "
                        f"got {v!r}"
                    )
        return cls(**values)


def parse_checkpoint_block(block: Any) -> IntegrityConfig:
    """Validate an ``exp_manager.checkpoint`` block and return its parsed
    :class:`IntegrityConfig`.  ``None`` → defaults.  Unknown sub-blocks are
    rejected with a did-you-mean hint (``checkpoint_callback_params`` keeps
    its separate reference-schema home — this block is for the NEW validated
    knobs only)."""
    if block is None:
        return IntegrityConfig()
    if not isinstance(block, Mapping):
        raise ValueError(
            f"exp_manager.checkpoint must be a mapping of "
            f"{sorted(CHECKPOINT_BLOCK_KEYS)}, got {type(block).__name__}"
        )
    unknown = set(block) - CHECKPOINT_BLOCK_KEYS
    if unknown:
        from neuronx_distributed_training_tpu.config.loader import (
            did_you_mean,
        )

        raise ValueError(
            f"unknown exp_manager.checkpoint keys {sorted(unknown)}; "
            f"supported: {sorted(CHECKPOINT_BLOCK_KEYS)}"
            + did_you_mean(unknown, CHECKPOINT_BLOCK_KEYS)
        )
    return IntegrityConfig.from_config(block.get("integrity"))


# ---------------------------------------------------------------------------
# digests
# ---------------------------------------------------------------------------


def _hasher():
    return hashlib.blake2b(digest_size=16)


def json_digest(obj: Any) -> str:
    """Digest of a JSON-serializable object over its *normalized* form (one
    ``dumps``/``loads`` round-trip first, so the digest of the in-memory dict
    matches the digest of what ``JsonRestore`` hands back)."""
    normalized = json.loads(json.dumps(obj, default=str))
    h = _hasher()
    h.update(json.dumps(normalized, sort_keys=True,
                        separators=(",", ":")).encode())
    return h.hexdigest()


def _leaf_entries(tree: Any) -> list[tuple[str, Any]]:
    """``(path, leaf)`` pairs sorted by path — the canonical leaf order both
    the save-side and verify-side hashing walk."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    entries = [(jax.tree_util.keystr(kp), leaf) for kp, leaf in flat]
    entries.sort(key=lambda e: e[0])
    return entries


def _group_of(item: str, path: str, split_top_level: bool) -> str:
    """Leaf-group name: ``params`` stays one group; ``opt_state`` splits on
    its top-level key (``opt_state/mu``, ``opt_state/master``, …) so a
    mismatch names the damaged subtree."""
    if not split_top_level:
        return item
    m = re.match(r"\['([^']+)'\]", path)
    return f"{item}/{m.group(1)}" if m else item


def tree_digest_groups(
    item: str, tree: Any, *, split_top_level: bool = False
) -> tuple[dict[str, dict[str, Any]], dict[str, dict[str, Any]], bool]:
    """Per-leaf-group content digests + structure summary for one item tree.

    Returns ``(groups, structure, content)``: ``groups`` maps group name →
    ``{digest, leaves, bytes}``; ``structure`` maps leaf path →
    ``{dtype, shape}``; ``content`` is False when the leaf bytes could not be
    fetched (non-fully-addressable arrays on a multi-host run — integrity
    then degrades to the structure summary, with a warning)."""
    hashers: dict[str, Any] = {}
    counts: dict[str, int] = {}
    sizes: dict[str, int] = {}
    structure: dict[str, dict[str, Any]] = {}
    content = True
    for path, leaf in _leaf_entries(tree):
        arr_meta_shape = tuple(getattr(leaf, "shape", ()) or ())
        arr_meta_dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
        structure[path] = {"dtype": arr_meta_dtype,
                           "shape": list(arr_meta_shape)}
        group = _group_of(item, path, split_top_level)
        h = hashers.setdefault(group, _hasher())
        counts[group] = counts.get(group, 0) + 1
        header = f"{path}|{arr_meta_dtype}|{arr_meta_shape}".encode()
        h.update(header)
        if not content:
            continue
        try:
            arr = np.ascontiguousarray(np.asarray(leaf))
        except Exception as e:  # noqa: BLE001 — non-addressable (multi-host)
            logger.warning(
                "integrity: cannot fetch %s/%s for hashing (%s: %s) — "
                "digests degrade to structure-only for this save",
                item, path, type(e).__name__, e,
            )
            content = False
            continue
        data = arr.tobytes()
        h.update(data)
        sizes[group] = sizes.get(group, 0) + len(data)
    groups = {
        g: {
            "digest": h.hexdigest(),
            "leaves": counts[g],
            "bytes": sizes.get(g, 0),
        }
        for g, h in hashers.items()
    }
    return groups, structure, content


def build_sidecar(
    *,
    step: int,
    params: Any,
    opt_state: Any,
    meta: Mapping[str, Any],
    manifest: Optional[Mapping[str, Any]] = None,
) -> dict[str, Any]:
    """The ``integrity`` sidecar item saved with every checkpoint: content
    digests per leaf-group over the exact trees handed to orbax (call AFTER
    the ``save_bf16`` cast / master drop), JSON digests for meta + manifest,
    and the tree-structure summary."""
    p_groups, p_struct, p_content = tree_digest_groups("params", params)
    o_groups, o_struct, o_content = tree_digest_groups(
        "opt_state", opt_state, split_top_level=True)
    return {
        "format": INTEGRITY_FORMAT,
        "algo": DIGEST_ALGO,
        "step": int(step),
        "content": bool(p_content and o_content),
        "groups": {**p_groups, **o_groups},
        "tree": {"params": p_struct, "opt_state": o_struct},
        "meta_digest": json_digest(dict(meta)),
        "manifest_digest": (json_digest(dict(manifest))
                            if manifest is not None else None),
    }


# ---------------------------------------------------------------------------
# verification
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StepVerification:
    """One step's integrity verdict.  ``status``:

    - ``ok``      sidecar present, every digest matches;
    - ``legacy``  no sidecar (pre-integrity checkpoint) — restorable, warned;
    - ``corrupt`` sidecar/digest mismatch or an unreadable item;
    - ``gone``    the step dir vanished mid-verify (retention race — the
      audit thread treats this as "nothing to verify", not corruption).
    """

    step: int
    status: str
    failures: list[str] = dataclasses.field(default_factory=list)
    groups_checked: int = 0
    seconds: float = 0.0

    @property
    def passed(self) -> bool:
        """Restorable?  ``ok`` and ``legacy`` both restore (legacy with a
        warning); ``gone`` is vacuously passed — there is nothing to
        quarantine."""
        return self.status != "corrupt"

    def to_dict(self) -> dict[str, Any]:
        return {
            "step": self.step, "status": self.status,
            "failures": list(self.failures),
            "groups_checked": self.groups_checked,
            "seconds": round(self.seconds, 3),
        }


def open_readonly_manager(directory) -> Any:
    """A fresh synchronous orbax manager over an EXISTING checkpoint dir for
    template-free verification reads — the offline CLI and the audit thread
    each open their own (orbax managers are not thread-shareable)."""
    import orbax.checkpoint as ocp

    return ocp.CheckpointManager(
        directory,
        options=ocp.CheckpointManagerOptions(
            enable_async_checkpointing=False, save_interval_steps=1),
    )


def _step_dir(directory, step: int):
    return directory / str(int(step))


def verify_step(directory, step: int, *, mgr: Any = None) -> StepVerification:
    """Template-free integrity verification of one retained step.

    Reads the sidecar, re-reads every digested item with NO target tree
    (params/opt_state restore as plain host arrays, meta/manifest as JSON),
    re-hashes, and compares.  Any read failure on a digested item IS a
    verification failure — a truncated or missing file surfaces here as a
    curated verdict instead of a restore-time crash.

    Runs before any mesh exists: safe at discovery time, in the offline CLI,
    and on the audit thread.  NOTE the read materializes each item unsharded
    on the host — the cost of end-to-end verification.
    """
    import orbax.checkpoint as ocp

    t0 = time.perf_counter()
    sdir = _step_dir(directory, step)
    if not sdir.exists():
        return StepVerification(step=int(step), status="gone",
                                seconds=time.perf_counter() - t0)
    own_mgr = mgr is None
    if own_mgr:
        mgr = open_readonly_manager(directory)
    failures: list[str] = []
    groups_checked = 0
    try:
        if not (sdir / INTEGRITY_ITEM).exists():
            return StepVerification(
                step=int(step), status="legacy",
                seconds=time.perf_counter() - t0)
        try:
            sidecar = dict(mgr.restore(
                int(step),
                args=ocp.args.Composite(
                    **{INTEGRITY_ITEM: ocp.args.JsonRestore()}),
            )[INTEGRITY_ITEM])
        except Exception as e:  # noqa: BLE001 — an unreadable sidecar is
            # itself corruption (the item exists but cannot be parsed) —
            # unless the whole step dir vanished under the read (see the
            # 'gone' recheck below)
            return StepVerification(
                step=int(step),
                status="corrupt" if sdir.exists() else "gone",
                failures=([f"integrity sidecar unreadable: "
                           f"{type(e).__name__}: {e}"]
                          if sdir.exists() else []),
                seconds=time.perf_counter() - t0)
        if sidecar.get("algo") != DIGEST_ALGO:
            return StepVerification(
                step=int(step), status="corrupt",
                failures=[f"unknown digest algo {sidecar.get('algo')!r} "
                          f"(this build computes {DIGEST_ALGO})"],
                seconds=time.perf_counter() - t0)
        if int(sidecar.get("step", -1)) != int(step):
            failures.append(
                f"stale sidecar: records step {sidecar.get('step')} but "
                f"lives in step {step}")

        def read_json(item):
            return mgr.restore(
                int(step),
                args=ocp.args.Composite(**{item: ocp.args.JsonRestore()}),
            )[item]

        def read_tree(item):
            # DEVICE-INDEPENDENT read: restore every leaf as plain numpy via
            # explicit RestoreArgs.  The template-free StandardRestore would
            # pin to the sharding metadata saved with the arrays — and fail
            # outright on a host whose device count differs from the saving
            # fleet (exactly where offline verification runs)
            import jax as _jax

            ckpt = ocp.PyTreeCheckpointer()
            try:
                md = ckpt.metadata(sdir / item).item_metadata.tree
                is_arr = lambda x: hasattr(x, "shape")  # noqa: E731
                ra = _jax.tree_util.tree_map(
                    lambda x: ocp.RestoreArgs(restore_type=np.ndarray),
                    md, is_leaf=is_arr)
                return ckpt.restore(sdir / item, restore_args=ra)
            finally:
                try:
                    ckpt.close()
                except Exception:  # noqa: BLE001 — read-only teardown
                    pass

        # meta / manifest JSON digests
        for item, want in (("meta", sidecar.get("meta_digest")),
                           ("manifest", sidecar.get("manifest_digest"))):
            if want is None:
                continue
            groups_checked += 1
            try:
                have = json_digest(dict(read_json(item)))
            except Exception as e:  # noqa: BLE001 — read failure = corrupt
                failures.append(
                    f"{item}: unreadable ({type(e).__name__}: {e})")
                continue
            if have != want:
                failures.append(f"{item}: digest mismatch "
                                f"(saved {want}, read back {have})")

        # array items: re-read template-free, re-hash with the same walk
        expected = dict(sidecar.get("groups") or {})
        tree_summary = dict(sidecar.get("tree") or {})
        has_content = bool(sidecar.get("content", True))
        for item in ("params", "opt_state"):
            item_groups = {g: v for g, v in expected.items()
                           if g == item or g.startswith(item + "/")}
            if not item_groups:
                continue
            try:
                tree = read_tree(item)
            except Exception as e:  # noqa: BLE001 — read failure = corrupt
                failures.append(
                    f"{item}: unreadable ({type(e).__name__}: {e})")
                continue
            got_groups, got_struct, got_content = tree_digest_groups(
                item, tree, split_top_level=(item == "opt_state"))
            want_struct = dict(tree_summary.get(item) or {})
            for path in sorted(set(want_struct) | set(got_struct))[:2048]:
                w, g = want_struct.get(path), got_struct.get(path)
                if w != g:
                    failures.append(
                        f"{item}{path}: structure drift "
                        f"(saved {w}, read back {g})")
            if not (has_content and got_content):
                # save-side (multi-host) or read-side degraded to
                # structure-only: digests are not comparable
                groups_checked += len(item_groups)
                continue
            for g in sorted(item_groups):
                groups_checked += 1
                want_d = item_groups[g].get("digest")
                have_d = (got_groups.get(g) or {}).get("digest")
                if have_d != want_d:
                    failures.append(
                        f"{g}: content digest mismatch "
                        f"(saved {want_d}, read back {have_d})")
        status = "corrupt" if failures else "ok"
        if status == "corrupt" and not sdir.exists():
            # the step dir was deleted UNDER the read (top-k retention or a
            # concurrent quarantine on another actor): the read failures are
            # an artifact of the race, not corruption — the 'gone' status
            # exists precisely for this
            return StepVerification(
                step=int(step), status="gone",
                seconds=time.perf_counter() - t0)
        return StepVerification(
            step=int(step), status=status, failures=failures,
            groups_checked=groups_checked,
            seconds=time.perf_counter() - t0)
    finally:
        if own_mgr:
            try:
                mgr.close()
            except Exception:  # noqa: BLE001 — read-only teardown
                pass


# ---------------------------------------------------------------------------
# quarantine
# ---------------------------------------------------------------------------


def _reason_slug(reason: str) -> str:
    slug = re.sub(r"[^A-Za-z0-9]+", "-", reason).strip("-").lower()
    return (slug or "corrupt")[:48]


def quarantine_name(step: int, reason: str) -> str:
    return f"{QUARANTINE_PREFIX}{int(step)}.{_reason_slug(reason)}"


def parse_quarantine_name(name: str) -> Optional[int]:
    """Step number of a quarantined dir name, or ``None`` for anything else
    (the round-trip the discovery tests pin: a quarantined name must never
    parse as a live step, and this parse must recover the original step)."""
    if not name.startswith(QUARANTINE_PREFIX):
        return None
    rest = name[len(QUARANTINE_PREFIX):]
    head = rest.split(".", 1)[0]
    return int(head) if head.isdigit() else None


def read_ledger(directory) -> list[dict[str, Any]]:
    """Entries of the quarantine ledger (empty when none)."""
    path = directory / LEDGER_NAME
    try:
        if not path.exists():
            return []
        data = json.loads(path.read_text())
        return list(data.get("entries") or [])
    except Exception as e:  # noqa: BLE001 — a torn ledger must not block
        logger.warning("quarantine ledger %s unreadable: %s", path, e)
        return []


def _append_ledger(directory, entry: dict[str, Any]) -> None:
    path = directory / LEDGER_NAME
    entries = read_ledger(directory)
    entries.append(entry)
    payload = json.dumps({"entries": entries}, indent=1, sort_keys=True) + "\n"
    if isinstance(path, Path):
        tmp = path.with_suffix(".json.tmp")
        tmp.write_text(payload)
        tmp.replace(path)
    else:  # remote store: whole-object writes commit atomically
        path.write_text(payload)


def apply_quarantine(directory, step: int, *, reason: str,
                     failures: Optional[list[str]] = None) -> bool:
    """Rename ``<dir>/<step>`` out of the discovery namespace and record the
    ledger entry.  Returns True when the step dir was actually moved (False:
    already gone, or the rename failed — the ledger entry is written either
    way so the event is never silent)."""
    src = _step_dir(directory, step)
    dst = directory / quarantine_name(step, reason)
    moved = False
    try:
        if src.exists():
            src.rename(dst)
            moved = True
    except Exception as e:  # noqa: BLE001 — a failed rename (exotic remote
        # store) must not turn detection into a crash; the ledger + logs
        # still carry the verdict
        logger.error(
            "quarantine of step %d failed to rename %s -> %s: %s "
            "(the corrupt step remains discoverable — remove it by hand)",
            step, src, dst, e)
    entry = {
        "step": int(step),
        "reason": reason,
        "failures": list(failures or [])[:16],
        "quarantined_to": dst.name if moved else None,
        "time": time.strftime("%Y-%m-%d %H:%M:%S"),
    }
    try:
        _append_ledger(directory, entry)
    except Exception as e:  # noqa: BLE001 — best-effort record
        logger.warning("quarantine ledger write failed for step %d: %s",
                       step, e)
    logger.error(
        "checkpoint step %d QUARANTINED (%s): %s", step, reason,
        "; ".join((failures or ["no detail"])[:4]))
    return moved


# ---------------------------------------------------------------------------
# corruption injection (the drill harness's bitrot switch)
# ---------------------------------------------------------------------------


def inject_corruption(directory, step: int, kind: str, *,
                      item: str = "params") -> str:
    """Deliberately damage a COMMITTED checkpoint step — the drill harness's
    stand-in for bitrot/truncated-upload/torn-write/stale-metadata.  Returns
    a description of what was done (drill reports carry it).

    - ``byte_flip``      flip one byte in the middle of the largest data
      file of ``item``;
    - ``truncate``       cut the largest data file of ``item`` in half;
    - ``delete_item``    remove the whole ``item`` directory;
    - ``stale_sidecar``  replace the step's ``integrity`` sidecar with the
      next-older step's (falls back to tampering a digest when no older
      sidecar exists).
    """
    if kind not in CORRUPTION_KINDS:
        raise ValueError(
            f"unknown corruption kind {kind!r}; supported: "
            f"{'/'.join(CORRUPTION_KINDS)}")
    sdir = _step_dir(directory, step)
    if not sdir.exists():
        raise FileNotFoundError(f"no committed step {step} under {directory}")

    def data_files(root):
        # prefer the OCDBT data payloads the manifest actually READS.  Newer
        # orbax/tensorstore merges per-process writes into a top-level
        # "<item>/d/<hash>" kvstore and restores through that; the
        # "ocdbt.process_N/d/" copies become write-side staging, so damaging
        # one is invisible to both restore and verification.  Older layouts
        # keep the payloads only under the process dirs — fall back there,
        # then to any file (largest first)
        top = root / "d"
        files = ([p for p in top.glob("*") if p.is_file()]
                 if top.is_dir() else [])
        if not files:
            files = [p for p in root.rglob("*")
                     if p.is_file() and p.parent.name == "d"]
        if not files:
            files = [p for p in root.rglob("*") if p.is_file()]
        files.sort(key=lambda p: p.stat().st_size, reverse=True)
        return files

    if kind in ("byte_flip", "truncate"):
        root = sdir / item
        files = data_files(root) if root.exists() else []
        if not files:
            raise FileNotFoundError(f"no files under {root} to corrupt")
        target = files[0]
        size = target.stat().st_size
        if kind == "byte_flip":
            pos = max(size // 2 - 1, 0)
            with open(target, "r+b") as f:
                f.seek(pos)
                b = f.read(1)
                f.seek(pos)
                f.write(bytes([(b[0] ^ 0xFF) if b else 0xFF]))
            return (f"byte_flip: flipped byte {pos} of "
                    f"{target.relative_to(sdir)} ({size} bytes)")
        with open(target, "r+b") as f:
            f.truncate(max(size // 2, 1))
        return (f"truncate: {target.relative_to(sdir)} "
                f"{size} -> {max(size // 2, 1)} bytes")
    if kind == "delete_item":
        root = sdir / item
        if not root.exists():
            raise FileNotFoundError(f"no item {item} under {sdir}")
        shutil.rmtree(root)
        return f"delete_item: removed {item}/"
    # stale_sidecar
    dst = sdir / INTEGRITY_ITEM / "metadata"
    if not dst.exists():
        raise FileNotFoundError(
            f"step {step} has no integrity sidecar to go stale")
    older = sorted(
        (int(p.name) for p in directory.iterdir()
         if p.name.isdigit() and int(p.name) < int(step)
         and (p / INTEGRITY_ITEM / "metadata").exists()),
        reverse=True)
    if older:
        src = directory / str(older[0]) / INTEGRITY_ITEM / "metadata"
        dst.write_text(src.read_text())
        return f"stale_sidecar: copied step {older[0]}'s sidecar over {step}'s"
    side = json.loads(dst.read_text())
    for g in side.get("groups", {}).values():
        g["digest"] = "0" * 32
    dst.write_text(json.dumps(side))
    return "stale_sidecar: zeroed every group digest (no older sidecar)"


# ---------------------------------------------------------------------------
# post-commit save audit
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class AuditStats:
    audited: int = 0
    failed: int = 0
    seconds: float = 0.0
    incomplete: int = 0

    def to_dict(self) -> dict[str, Any]:
        return {"audited": self.audited, "failed": self.failed,
                "seconds": round(self.seconds, 3),
                "incomplete": self.incomplete}


class SaveAuditor:
    """Background post-commit read-back verification of committed steps.

    The trainer's hot path never blocks on it: :meth:`schedule` enqueues a
    COMMITTED step; a daemon thread re-reads and re-hashes it
    (:func:`verify_step` with its own read-only manager); :meth:`poll`
    returns completed verdicts without waiting — the SNAPSHOT the emergency
    save path takes at the stop boundary (an in-flight audit keeps running;
    a finished failure still gets its quarantine even while the run is
    stopping).  :meth:`drain` bounds the teardown wait by the configured
    deadline; jobs still unfinished then are counted ``incomplete``, never
    joined unboundedly — the grace window cannot deadlock on an audit.
    """

    def __init__(self, directory, *,
                 verify_fn: Optional[Callable[[Any, int],
                                              StepVerification]] = None):
        self.directory = directory
        self._verify = verify_fn or (lambda d, s: verify_step(d, s))
        self._q: "queue.Queue[Optional[int]]" = queue.Queue()
        self._cond = threading.Condition()
        self._pending = 0  # queued + in-flight (under _cond)
        self._done: list[StepVerification] = []
        self.stats = AuditStats()
        self._thread: Optional[threading.Thread] = None
        self._closed = False

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="nxdt-ckpt-audit")
            self._thread.start()

    def _run(self) -> None:
        while True:
            step = self._q.get()
            if step is None:
                return
            t0 = time.perf_counter()
            try:
                v = self._verify(self.directory, int(step))
            except Exception as e:  # noqa: BLE001 — the audit itself failing
                # is a verdict, not a crash (e.g. store unreachable)
                v = StepVerification(
                    step=int(step), status="corrupt",
                    failures=[f"audit error: {type(e).__name__}: {e}"])
            v.seconds = time.perf_counter() - t0
            with self._cond:
                self._done.append(v)
                self.stats.audited += 1
                self.stats.seconds += v.seconds
                if v.status == "corrupt":
                    self.stats.failed += 1
                self._pending -= 1
                self._cond.notify_all()

    def schedule(self, step: int) -> None:
        """Enqueue a committed step for background verification."""
        if self._closed:
            return
        self._ensure_thread()
        with self._cond:
            self._pending += 1
        self._q.put(int(step))

    def poll(self) -> list[StepVerification]:
        """Completed verdicts so far — non-blocking (the boundary/emergency
        snapshot).  Clears the internal list."""
        with self._cond:
            out, self._done = self._done, []
            return out

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Wait (bounded) for in-flight audits; True when everything
        finished.  Unfinished jobs are recorded ``incomplete``."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while self._pending > 0:
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    self.stats.incomplete += self._pending
                    logger.warning(
                        "save audit: %d verification(s) still running at the "
                        "drain deadline — verdicts will be lost with this "
                        "process (raise audit_deadline_seconds to wait "
                        "longer)", self._pending)
                    return False
                self._cond.wait(timeout=remaining)
        return True

    def close(self, timeout: Optional[float] = None) -> list[StepVerification]:
        """Drain (bounded), stop the worker, and return the final verdicts."""
        self._closed = True
        self.drain(timeout)
        if self._thread is not None and self._thread.is_alive():
            self._q.put(None)
        return self.poll()
