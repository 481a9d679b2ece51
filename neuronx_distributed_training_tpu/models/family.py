"""One record per model family: what the rest of the package asks of a model.

``models/llama.py``, ``mixtral.py``, ``gpt.py``, ``ouro.py``, ``laguna.py``, ``kanana.py``,
``lfm2.py``, ``nemotron_h.py`` and ``keye.py`` each end in one
:class:`Family` (``FAMILY``) and their config class answers ``.family`` with
it.  The trainer, the pipeline gate, the launch planner, the FLOPs count, the
cached decode and the config validator ask the record; none of them names a
family.  A new family is its file and its line in :data:`FAMILIES`.

What a family cannot do is a field too: :class:`Refused` stands where the
capability would and, called like it, raises its sentence, so the reason is
written once, beside the family it describes.  A capability may also refuse
one config of a family it otherwise serves (zig-zag attention under pipeline
parallelism), by raising ``NotImplementedError`` when it is built.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Mapping, Optional

from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy


@dataclasses.dataclass(frozen=True)
class Refused:
    """A capability a family does not have, and the sentence that says so."""
    sentence: str

    def __call__(self, *args: Any, **kwargs: Any):
        raise NotImplementedError(self.sentence)


@dataclasses.dataclass(frozen=True)
class AfterUpdate:
    """Leaves that move by a rule that is not the optimizer's (a router's
    selection bias, by the experts' load).  The train step keeps the loss's
    aux entries ``reads`` whole (summed over micro-batches; non-scalars
    otherwise stay inside the loss) and, after the optimizer, inside the
    compiled step, takes ``apply(params, {name: entry}) -> params``.  The
    leaves stay in ``params`` with a gradient of zero, so the optimizer's
    state keeps the parameters' structure and AdamW moves them by nothing."""
    reads: tuple[str, ...]
    apply: Callable[[Any, Mapping[str, Any]], Any]


@dataclasses.dataclass(frozen=True)
class Family:
    """Everything looked up per family.  ``cfg`` is the family's config
    dataclass throughout; ``logits`` to ``decode`` are capabilities, each the
    callable described or a :class:`Refused`."""
    name: str
    #: ``(model_block, ds_block) -> cfg``; refuses, by key, what it is not wired for
    config_from: Callable[[dict, dict], Any]
    #: ``(cfg, policy, *, shift_labels) -> (params, batch, key) -> (loss, aux)``
    loss: Callable[..., Callable]
    #: ``(key, cfg, policy) -> params``
    init_params: Callable[..., Any]
    #: ``(cfg, *, pipeline=False) -> PartitionSpec tree``
    param_specs: Callable[..., Any]
    #: ``(cfg, seq_len) -> {attention, mlp, router, head}`` fwd FLOPs/token
    flops_breakdown: Callable[[Any, int], dict[str, float]]
    #: ``(cfg) -> dict``: ``autotune.space.ModelFacts``'s shape fields
    plan_shape: Callable[[Any], dict[str, Any]]
    #: ``(cfg, policy) -> (params, batch, rng=None) -> (logits, reg_loss)``:
    #: the preference losses' forward (``reg_loss``: the router's, 0.0 if dense)
    logits: Callable[..., Callable]
    #: ``(cfg, policy, *, norm=True) -> (params, hidden) -> logits``; ``norm``
    #: applies the final norm first (``False``: ``hidden`` already has it)
    head: Callable[..., Callable]
    #: ``(cfg, policy, *, shift_labels) -> ((embed, stage, loss), opts)`` for
    #: ``parallel.pipeline.pipeline_loss``; ``opts``: ``stage_aux`` (stage
    #: returns ``(x, aux)``), ``aux_inv_layers`` (1 / layers with a router),
    #: ``needs_rng`` (per-microbatch dropout keys)
    pipeline: Callable[..., tuple]
    #: ``(cfg, policy) -> (head_hidden_fn, head_params_of, head_weight_of,
    #: fold_grads)`` for the manual-vjp schedules (``pipeline_loss_and_grad``)
    onef1b_head: Callable[..., tuple]
    #: ``() -> (prefill, decode_step)`` of ``models/decode.py``
    decode: Callable[[], tuple]
    #: ``(cfg) -> groups``: the (MoE + dense) layer groups the pipeline must
    #: slice whole, or None where any layer boundary will do
    moe_groups: Callable[[Any], Optional[int]] = lambda cfg: None
    #: ``(cfg, sched) -> dict``: the family's own lines of ``run_summary.json``
    run_facts: Callable[[Any, Mapping], dict] = lambda cfg, sched: {}
    #: ``(cfg) -> AfterUpdate | None``: what moves after the optimizer's step
    after_update: Callable[[Any], Optional[AfterUpdate]] = lambda cfg: None

    def manual_vjp_refusal(self, cfg: Any) -> Optional[str]:
        """The family's half of ``parallel.pipeline.supports_1f1b``: why the
        manual-vjp schedules cannot run ``cfg``, or None (asked by building
        the head hooks, which are closures)."""
        try:
            self.onef1b_head(cfg, DtypePolicy())
        except NotImplementedError as refusal:
            return str(refusal)
        return None


#: ``model.architecture`` / ``model_type`` string -> the module under
#: ``models/`` whose ``FAMILY`` answers to it (or the record itself); imported
#: when resolved, so that a llama run imports neither ``gpt`` nor ``mixtral``
FAMILIES: dict[str, Any] = {
    "llama": "llama", "mistral": "llama", "mixtral": "mixtral",
    "ouro": "ouro", "laguna": "laguna", "gpt": "gpt",
    "kanana": "kanana", "deepseek_v3": "kanana",
    "lfm2": "lfm2", "lfm2_moe": "lfm2",
    "nemotron_h": "nemotron_h",
    "keye": "keye", "keyevl2": "keye",
}


def resolve(cfg: Mapping) -> tuple[Family, Any]:
    """``(family, model_cfg)`` of a whole config mapping, by ``model_source``
    and ``model.architecture`` (reference ``training.py:71-91`` selects
    Megatron vs HF modules the same way)."""
    source = str(cfg.get("model_source", "hf")).lower()
    if source not in ("hf", "megatron"):
        raise ValueError(f"unsupported model_source {source!r} (want 'hf' or 'megatron')")
    model = dict(cfg.get("model", {}) or {})
    arch = str(model.get("architecture", model.get("model_type", "llama"))).lower()
    family = FAMILIES.get(arch, "gpt" if source == "megatron" else None)
    if family is None:
        raise ValueError(f"unsupported model_source/architecture: {source}/{arch}")
    if isinstance(family, str):
        family = importlib.import_module(f"{__package__}.{family}").FAMILY
    return family, family.config_from(model, dict(cfg.get("distributed_strategy", {}) or {}))


def flops_breakdown_for_model(model_cfg: Any, seq_len: int) -> dict[str, float]:
    """Per-component fwd FLOPs/token of any family, ``{attention, mlp, router,
    head}`` (``utils.perf.FLOPS_COMPONENTS``): what the autotune cost model
    prices (each scales differently under tp/cp/remat).  MFU's conventions:
    only ACTIVATED experts and the router matmul count; causal masking halves
    the score/context term."""
    return model_cfg.family.flops_breakdown(model_cfg, seq_len)


def flops_for_model(model_cfg: Any, seq_len: int) -> float:
    """fwd FLOPs/token of any family (MFU's count): the breakdown's sum, so
    that the scalar and the components cannot drift apart."""
    return float(sum(flops_breakdown_for_model(model_cfg, seq_len).values()))
