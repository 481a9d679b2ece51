"""Laguna-family decoder (``model_type: laguna``): a stack whose layers differ.

Every layer is a pre-norm attention block and a pre-norm MLP, as in
``models.llama``, but what fills the two slots is read per layer from the
source's lists:

- ``layer_types``: ``full_attention`` (causal) or ``sliding_attention``
  (causal inside ``sliding_window``), each kind with its own number of query
  heads (``num_attention_heads_per_layer``; the key/value heads are shared) and
  its own rotary embedding (``rope_parameters``: plain, or YaRN frequencies on
  a leading part of each head);
- a per-head sigmoid gate, read from the normed layer input, on the attention
  output before ``o`` (``gating: per-head``);
- ``mlp_layer_types``: ``dense`` (SwiGLU of ``intermediate_size``) or
  ``sparse``: ``num_experts`` SwiGLU experts of ``moe_intermediate_size`` of
  which a token takes ``num_experts_per_tok`` (softmax scores, renormalised over
  the chosen, times ``moe_routed_scaling_factor``) beside a shared expert of
  ``shared_expert_intermediate_size`` that every token passes, added ungated.

Layers of different kinds cannot share a stacked leaf (their ``qkv`` and ``o``
differ in shape), so the parameters hold one stack per kind
(``layers/<attention>_<mlp>``, in layer order) and the forward pass follows
``stack_plan``: the longest periodic run of the layer list is one ``lax.scan``
over periods, each period an inner scan per run of equal layers; what
precedes and follows it is a scan per run.  No Python loop over the depth.

``num_experts_held: [lo, hi]`` makes the program one chip of an
expert-parallel deployment, alone: it routes over all the experts, holds and
multiplies ``lo .. hi - 1`` only (``ops.moe._held_experts``) and leaves the
other chips' rows out.

Not wired (each refused by name): pipeline parallelism (a stage would have to
slice every kind's stack), ``models/decode.py`` (two kinds of cache: a ring of
the window and the whole context), tensor parallelism over heads (72 and 48
heads over 8 key/value heads), expert parallelism over chips together with a
held range, and context parallelism (YaRN positions past the original context
per shard).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from neuronx_distributed_training_tpu.models import llama
from neuronx_distributed_training_tpu.models.family import Family, Refused
from neuronx_distributed_training_tpu.ops import linear as linear_ops
from neuronx_distributed_training_tpu.ops import moe as moe_ops
from neuronx_distributed_training_tpu.ops import norm as norm_ops
from neuronx_distributed_training_tpu.ops import rope as rope_ops
from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

ATTENTION_TYPES = ("full_attention", "sliding_attention")
MLP_TYPES = ("dense", "sparse")
#: inner scope of ``attention`` by the layer's kind (telemetry.spans.DEVICE_SCOPES)
ATTENTION_SCOPES = {"full_attention": "attn_full", "sliding_attention": "attn_window"}


def kind_name(*kind: str) -> str:
    """The stack a layer of this kind lies in, ``layers/<name>``: ``(attention,
    mlp)`` here and in models/lfm2.py, one part where a layer is one mixer
    (models/nemotron_h.py)."""
    return "_".join((kind[0].split("_")[0],) + kind[1:])


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """Llama knobs (``llama``: the widths every layer shares, the dense MLP's
    ``intermediate_size``, the full layers' head count) + the routed block
    (``moe``) + what differs by layer."""

    llama: llama.LlamaConfig = dataclasses.field(default_factory=llama.LlamaConfig)
    moe: moe_ops.MoEConfig = dataclasses.field(default_factory=moe_ops.MoEConfig)
    layer_types: tuple[str, ...] = ()
    mlp_layer_types: tuple[str, ...] = ()
    #: query heads by attention type, ``((type, heads), ...)``
    heads_by_type: tuple[tuple[str, int], ...] = ()
    #: ``rope_parameters`` by attention type, each a sorted tuple of items
    rope_by_type: tuple[tuple[str, tuple[tuple[str, Any], ...]], ...] = ()
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 0

    # architecture passthroughs (perf estimation, data-module sizing)
    @property
    def vocab_size(self) -> int:
        return self.llama.vocab_size

    @property
    def hidden_size(self) -> int:
        return self.llama.hidden_size

    @property
    def intermediate_size(self) -> int:
        return self.llama.intermediate_size

    @property
    def num_layers(self) -> int:
        return self.llama.num_layers

    @property
    def num_attention_heads(self) -> int:
        return self.llama.num_attention_heads

    @property
    def num_kv_heads(self):
        return self.llama.num_kv_heads

    @property
    def head_dim(self):
        return self.llama.head_dim

    @property
    def family(self) -> Family:
        return FAMILY

    @property
    def kinds(self) -> tuple[tuple[str, str], ...]:
        """``(attention type, mlp type)`` of every layer."""
        return tuple(zip(self.layer_types, self.mlp_layer_types))

    def heads(self, attention: str) -> int:
        return dict(self.heads_by_type)[attention]

    def rope(self, attention: str) -> dict:
        return dict(dict(self.rope_by_type)[attention])

    def window(self, attention: str) -> Optional[int]:
        return self.llama.sliding_window if attention == "sliding_attention" else None

    def block_kv(self, attention: str) -> Optional[int]:
        """Key tile of a layer's flash kernels: the config's, but in window
        layers no wider than the window (in lanes of 128): a wider tile shows
        a query block mostly masked keys.  At the published window of 512 that
        is the default query tile, and a call whose window is no wider than
        its square tile takes the flash kernels' diagonal walk
        (``ops/flash_attention.py``).  One v5e, window 512 x 72 heads x seq
        8192, forward + forward and backward of one layer: 31.4 ms at the
        default key tile of 2048 and 21.3 at 512 the band's way (PERF.md
        section 4, PR 36), 9.0 the diagonal way (PR 38).  A window under 512
        gets a key tile under the query tile here and so keeps the band walk
        (26.5 ms at 256 where 512 x 512 tiles would take 7.4: PERF.md
        section 7)."""
        from neuronx_distributed_training_tpu.ops.flash_attention import DEFAULT_BLOCK_KV

        window = self.window(attention)
        if window is None:
            return self.llama.flash_block_kv
        return min(self.llama.flash_block_kv or DEFAULT_BLOCK_KV, -(-window // 128) * 128)

    @classmethod
    def from_config(cls, model_cfg: dict[str, Any], ds_cfg: dict[str, Any] | None = None):
        # the one place that refuses what the mixed stack is not wired for,
        # each by its key's name
        m, ds = dict(model_cfg or {}), dict(ds_cfg or {})
        base = llama.LlamaConfig.from_config(m, ds)
        if m.get("head_dim") is not None:  # no function of hidden_size here
            base = dataclasses.replace(base, head_dim=int(m["head_dim"]))
        # a depth under the source's (``num_layers`` / ``num_hidden_layers``
        # cut by a benchmark or an audit) runs the leading layers: the
        # per-layer lists are read up to it
        n = base.num_layers
        layer_types = tuple(m.get("layer_types") or ("full_attention",) * n)[:n]
        experts = int(m.get("num_experts", 0) or 0)
        mlp_types = tuple(m.get("mlp_layer_types") or (
            "dense" if not experts or i in (m.get("mlp_only_layers") or ()) else "sparse"
            for i in range(n)))[:n]
        for key, got, known in (("layer_types", layer_types, ATTENTION_TYPES),
                                ("mlp_layer_types", mlp_types, MLP_TYPES)):
            if len(got) != n:
                raise ValueError(f"model.{key} lists {len(got)} layers, "
                                 f"the model has {n}")
            if set(got) - set(known):
                raise ValueError(f"model.{key}: unknown {sorted(set(got) - set(known))} "
                                 f"(known: {known})")
        per_layer = m.get("num_attention_heads_per_layer")
        if per_layer is None:
            heads = {t: base.num_attention_heads for t in ATTENTION_TYPES}
        elif isinstance(per_layer, dict):
            heads = {t: int(per_layer.get(t, base.num_attention_heads))
                     for t in ATTENTION_TYPES}
        else:  # the source's list, one entry a layer
            if len(per_layer) < n:
                raise ValueError(f"model.num_attention_heads_per_layer lists "
                                 f"{len(per_layer)} layers, the model has {n}")
            by_type = {t: {int(c) for c, lt in zip(per_layer, layer_types) if lt == t}
                       for t in ATTENTION_TYPES}
            if any(len(counts) > 1 for counts in by_type.values()):
                raise ValueError(
                    "model.num_attention_heads_per_layer: layers of one attention "
                    f"type differ in their head count ({by_type}); a kind's stack "
                    "has one shape")
            heads = {t: (counts.pop() if counts else base.num_attention_heads)
                     for t, counts in by_type.items()}
        if any(c % base.kv_heads for c in heads.values()):
            raise ValueError(f"model.num_attention_heads_per_layer {heads}: every count "
                             f"must be a multiple of num_key_value_heads {base.kv_heads}")
        if "sliding_attention" in layer_types and not base.sliding_window:
            raise ValueError("model.layer_types names sliding_attention and "
                             "model.sliding_window is not set")
        rope = {t: dict((m.get("rope_parameters") or {}).get(t) or {}) for t in ATTENTION_TYPES}
        for t, r in rope.items():
            if str(r.get("rope_type", "default")) not in ("default", "yarn"):
                raise ValueError(f"model.rope_parameters.{t}.rope_type "
                                 f"{r.get('rope_type')!r}: wired are default and yarn")
        if base.fuse_qkv is False:
            raise ValueError("model.fuse_qkv: false is not wired for "
                             "model.architecture: laguna")
        if str(m.get("gating", "per-head")) != "per-head":
            raise ValueError(f"model.gating {m.get('gating')!r}: wired is per-head")
        for key, why in (
                ("pipeline_model_parallel_size",
                 "a stage would have to slice every kind's stack "
                 "(parallel/pipeline.py slices one)"),
                ("tensor_model_parallel_size",
                 "the kinds' 72 and 48 query heads over 8 key/value heads are "
                 "not laid out over the model axis"),
                ("context_parallel_size",
                 "YaRN positions past the original context are not split by shard")):
            if int(ds.get(key, 1) or 1) > 1:
                raise ValueError(f"distributed_strategy.{key} > 1 is not wired for "
                                 f"model.architecture: laguna: {why}")
        held = m.get("num_experts_held")
        if held is not None and int(ds.get("expert_model_parallel_size", 1) or 1) > 1:
            raise ValueError(
                "model.num_experts_held with distributed_strategy."
                "expert_model_parallel_size > 1: a held range is one chip's share "
                "of the experts, with no peer to exchange with (ops/moe.py)")
        if "sparse" in mlp_types and not experts:
            raise ValueError("model.mlp_layer_types names sparse layers and "
                             "model.num_experts is not set")
        if held is not None and not 0 <= int(held[0]) < int(held[1]) <= experts:
            raise ValueError(f"model.num_experts_held {held}: want 0 <= lo < hi <= "
                             f"num_experts {experts}")
        moe = moe_ops.MoEConfig(
            num_experts=experts or 1, top_k=int(m.get("num_experts_per_tok", 1)),
            dropless=True, capacity_factor=None,
            router_aux_loss_coef=float(m.get("router_aux_loss_coef", 0.001)),
            normalize_top_k_affinities=bool(m.get("norm_topk_prob", True)),
            routed_scaling_factor=float(m.get("moe_routed_scaling_factor", 1.0)),
            experts_held=None if held is None else (int(held[0]), int(held[1])))
        return cls(
            llama=base, moe=moe, layer_types=layer_types, mlp_layer_types=mlp_types,
            heads_by_type=tuple(sorted(heads.items())),
            rope_by_type=tuple((t, tuple(sorted(r.items()))) for t, r in sorted(rope.items())),
            moe_intermediate_size=int(m.get("moe_intermediate_size", 1024)),
            shared_expert_intermediate_size=int(m.get("shared_expert_intermediate_size", 0) or 0))


# ---------------------------------------------------------------------------
# the stack's plan
# ---------------------------------------------------------------------------


def _runs(kinds) -> list[tuple[Any, int]]:
    runs: list[list] = []
    for kind in kinds:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return [(kind, n) for kind, n in runs]


def stack_plan(kinds) -> list[tuple]:
    """How a layer list is run, as segments in order: ``("run", kind, n)``,
    one scan over ``n`` equal layers, or ``("periods", m, ((kind, n), ...))``,
    one scan over ``m`` periods of the given runs.  The periodic segment is
    the stretch of repeated runs that covers most layers (at least two
    periods, of at least two runs: a single run is already one scan); every
    kind's layers are consumed from its stack in order."""
    runs = _runs(kinds)
    best = (0, 0, 0, 0)  # layers covered, first run, runs a period, periods
    for width in range(2, len(runs) // 2 + 1):
        for first in range(len(runs) - 2 * width + 1):
            period = runs[first:first + width]
            m = 1
            while runs[first + m * width:first + (m + 1) * width] == period:
                m += 1
            covered = m * sum(n for _, n in period)
            if m >= 2 and covered > best[0]:
                best = (covered, first, width, m)
    _, first, width, m = best
    plan: list[tuple] = [("run", kind, n) for kind, n in runs[:first]]
    if m:
        plan.append(("periods", m, tuple(runs[first:first + width])))
    plan += [("run", kind, n) for kind, n in runs[first + m * width:]]
    return plan


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_layer(key: jax.Array, cfg: LagunaConfig, kind: tuple[str, str], dtype):
    """One layer of ``kind`` (unstacked)."""
    lc = cfg.llama
    attention, mlp = kind
    ks = jax.random.split(key, 8)
    h, d, nkv, std = lc.hidden_size, lc.head_size, lc.kv_heads, lc.initializer_range
    nh = cfg.heads(attention)

    def linear(k, n_in, n_out, shard):
        return linear_ops.init_linear(k, n_in, n_out, shard=shard, dtype=dtype, stddev=std)[0]

    def swiglu(width):
        return {"gate_up": linear(ks[4], h, 2 * width, "column"),
                "down": linear(ks[5], width, h, "row")}

    params: dict[str, Any] = {
        "input_norm": norm_ops.init_rms_norm(h, dtype=dtype)[0],
        "post_attn_norm": norm_ops.init_rms_norm(h, dtype=dtype)[0],
        "attn": {"qkv": linear(ks[0], h, (nh + 2 * nkv) * d, "column"),
                 "o": linear(ks[3], nh * d, h, "row"),
                 "gate": linear(ks[6], h, nh, "replicated")},
    }
    if mlp == "dense":
        params["mlp"] = swiglu(lc.intermediate_size)
    else:
        params["mlp"] = moe_ops.init_moe_params(
            ks[7], h, cfg.moe_intermediate_size, cfg.moe, dtype=dtype, stddev=std)
        if cfg.shared_expert_intermediate_size:
            params["mlp"]["shared"] = swiglu(cfg.shared_expert_intermediate_size)
    return params


def _kind_layers(cfg: LagunaConfig) -> dict[tuple[str, str], list[int]]:
    """Kind -> its layers' indices, kinds in order of first appearance."""
    found: dict[tuple[str, str], list[int]] = {}
    for i, kind in enumerate(cfg.kinds):
        found.setdefault(kind, []).append(i)
    return found


def init_params(key: jax.Array, cfg: LagunaConfig, policy: DtypePolicy | None = None):
    """The parameter pytree: llama's top level, ``layers`` one stack per kind
    (``layers/full_dense``, ``layers/sliding_sparse``, ...), each in layer
    order, layer ``i`` drawn from the ``i``-th of the layers' keys."""
    policy = policy or DtypePolicy()
    dtype = policy.param_dtype
    lc = cfg.llama
    kemb, klayers, khead = jax.random.split(key, 3)
    layer_keys = jax.random.split(klayers, lc.num_layers)
    params: dict[str, Any] = {
        "embed": linear_ops.init_embedding(
            kemb, lc.vocab_size, lc.hidden_size, dtype=dtype, stddev=lc.initializer_range)[0],
        "layers": {
            kind_name(*kind): jax.vmap(lambda k, kind=kind: _init_layer(k, cfg, kind, dtype))(
                layer_keys[jnp.asarray(indices)])
            for kind, indices in _kind_layers(cfg).items()},
        "final_norm": norm_ops.init_rms_norm(lc.hidden_size, dtype=dtype)[0],
    }
    if not lc.tie_word_embeddings:
        params["lm_head"], _ = linear_ops.init_linear(
            khead, lc.hidden_size, lc.vocab_size, shard="column", dtype=dtype,
            stddev=lc.initializer_range)
    return params


def param_specs(cfg: LagunaConfig, *, pipeline: bool = False):
    """PartitionSpec tree of ``init_params``: the vocabulary over ``model``
    as llama's; the layers replicated but for the expert dim over ``expert``
    where the experts are all held (tp over heads is refused at the config)."""
    if pipeline:
        raise NotImplementedError(FAMILY.pipeline.sentence)
    w2 = {"w": P(None, None, None)}   # every leaf leads with its stack's layers
    w3 = P(None, None if cfg.moe.experts_held is not None else "expert", None, None)

    def layer(kind):
        _, mlp = kind
        swiglu = {"gate_up": w2, "down": w2}
        specs: dict[str, Any] = {
            "input_norm": {"scale": P(None, None)}, "post_attn_norm": {"scale": P(None, None)},
            "attn": {"qkv": w2, "o": w2, "gate": w2}}
        if mlp == "dense":
            specs["mlp"] = swiglu
        else:
            specs["mlp"] = {"router": w2, "experts": {"gate_up": w3, "down": w3}}
            if cfg.shared_expert_intermediate_size:
                specs["mlp"]["shared"] = swiglu
        return specs

    specs: dict[str, Any] = {
        "embed": {"embedding": P("model", None)},
        "layers": {kind_name(*kind): layer(kind) for kind in _kind_layers(cfg)},
        "final_norm": {"scale": P(None)},
    }
    if not cfg.llama.tie_word_embeddings:
        specs["lm_head"] = {"w": P(None, "model")}
    return specs


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def rope_tables(cfg: LagunaConfig, positions: jax.Array) -> dict[str, tuple]:
    """``(cos, sin)`` of ``positions`` by attention type."""
    tables = {}
    for attention in set(cfg.layer_types):
        r = cfg.rope(attention)
        yarn = r if str(r.get("rope_type", "default")) == "yarn" else None
        inv_freq = rope_ops.rope_frequencies(
            cfg.llama.head_size, theta=float(r.get("rope_theta", cfg.llama.rope_theta)),
            partial_rotary_factor=float(r.get("partial_rotary_factor", 1.0)), yarn=yarn)
        tables[attention] = rope_ops.rope_cos_sin(
            positions, inv_freq, dtype=jnp.float32,
            scale=rope_ops.yarn_attention_factor(yarn) if yarn else None)
    return tables


def _cast_layer(lp, policy: DtypePolicy):
    """The per-layer cast to the compute dtype of all but the router and the
    expert weights: the router decides in float32 (``ops.moe.route``), and
    the expert block casts its weights where it multiplies them and hands
    their gradients back in the dtype they arrive in (as models/mixtral.py)."""
    cast = policy.cast_to_compute(lp)
    if "experts" not in lp["mlp"]:
        return cast
    return {**cast, "mlp": {**cast["mlp"], "experts": lp["mlp"]["experts"],
                            "router": lp["mlp"]["router"]}}


def _decoder_layer(lp, x, tables, cfg: LagunaConfig, policy: DtypePolicy,
                   kind: tuple[str, str], attention_mask=None, segment_ids=None):
    """One layer of ``kind`` -> ``(x, aux_loss, stats)``; ``stats`` the routed
    block's per-step scalars (``ops.moe.moe_block``), none in a dense layer."""
    lc = cfg.llama
    attention, mlp = kind
    aspec = shd.act_spec(lc.sequence_parallel, lc.context_parallel)
    cos, sin = tables[attention]
    # scope names: telemetry.spans.DEVICE_SCOPES
    with jax.named_scope("attention"), jax.named_scope(ATTENTION_SCOPES[attention]):
        hidden = norm_ops.apply_rms_norm(lp["input_norm"], x, eps=lc.rms_norm_eps)
        hidden = llama._attention_block(
            lp["attn"], hidden, cos, sin, lc, policy, attention_mask=attention_mask,
            segment_ids=segment_ids, num_heads=cfg.heads(attention),
            sliding_window=cfg.window(attention), block_kv=cfg.block_kv(attention))
        x = shd.constrain(x + hidden, aspec)
    if mlp == "dense":
        with jax.named_scope("mlp"):
            hidden = norm_ops.apply_rms_norm(lp["post_attn_norm"], x, eps=lc.rms_norm_eps)
            x = shd.constrain(x + llama._mlp_block(lp["mlp"], hidden), aspec)
        return x, jnp.zeros((), jnp.float32), {}
    # moe_block opens the "moe" scope itself; the norm before it and the
    # router loss and residual after it belong with it (as models/mixtral.py)
    with jax.named_scope("moe"):
        hidden = norm_ops.apply_rms_norm(lp["post_attn_norm"], x, eps=lc.rms_norm_eps)
    hidden, aux = moe_ops.moe_block(
        lp["mlp"], hidden, cfg.moe, compute_dtype=policy.compute_dtype,
        reduce_dtype=policy.reduce_dtype, act_spec=aspec)
    with jax.named_scope("moe"):
        aux_loss = moe_ops.weighted_router_loss(
            aux["router_logits"], aux["expert_idx"], cfg.moe)
        x = shd.constrain(x + hidden, aspec)
    return x, aux_loss, aux["stats"]


def decoder_stack(layers, x, tables, cfg: LagunaConfig, policy: DtypePolicy, *,
                  attention_mask=None, segment_ids=None):
    """The whole stack by ``stack_plan`` -> ``(x, router loss summed over the
    sparse layers, [stats of each scan])``."""
    def run_of(kind):
        def body(carry, lp):
            x, aux_acc = carry
            x, aux, stats = _decoder_layer(
                _cast_layer(lp, policy), x, tables, cfg, policy, kind,
                attention_mask=attention_mask, segment_ids=segment_ids)
            return (x, aux_acc + aux), stats
        # a run of one layer stays merged with its rerun (prevent_cse off, as
        # every stack): the two full layers fit so, and un-merging them would
        # rerun layer 0's 12288-wide MLP (models/kanana.py takes the other side)
        body = llama.checkpoint_layer(body, cfg.llama, stack=kind_name(*kind))
        return lambda carry, stack: jax.lax.scan(body, carry, stack)

    (x, aux_sum), all_stats = run_stacks(
        layers, (x, jnp.zeros((), jnp.float32)), cfg.kinds, run_of)
    return x, aux_sum, all_stats


def run_stacks(layers, carry, kinds, run_of):
    """``carry`` through the stacks ``layers`` (``kind_name`` -> a kind's
    layers, in layer order) by ``stack_plan(kinds)`` -> ``(carry, [what each
    scan stacked, in the plan's order])``.  ``run_of(kind)`` gives ``(carry,
    a stack of that kind's layers) -> (carry, stacked)``, one scan (shared
    with models/lfm2.py, whose layers differ in other ways)."""
    taken = {name: 0 for name in layers}

    def take(kind, n, lead=()):
        """The next ``n`` layers of ``kind``'s stack, as ``lead + (n // prod(lead),)``."""
        name = kind_name(*kind)
        lo = taken[name]
        taken[name] += n
        per = n
        for size in lead:
            per //= size
        return jax.tree_util.tree_map(
            lambda a: a[lo:lo + n].reshape(lead + (per,) + a.shape[1:]), layers[name])

    all_stats = []
    for segment in stack_plan(kinds):
        if segment[0] == "run":
            _, kind, n = segment
            carry, stats = run_of(kind)(carry, take(kind, n))
            all_stats.append(stats)
            continue
        _, m, period = segment
        # a kind may come twice in a period: its layers then interleave
        xs = [take(kind, m * n, lead=(m,)) for kind, n in _period_takes(period)]

        def one_period(carry, stacks, period=period):
            stats = []
            for (kind, n), stack in zip(period, _period_split(period, stacks)):
                carry, s = run_of(kind)(carry, stack)
                stats.append(s)
            return carry, stats

        carry, stats = jax.lax.scan(one_period, carry, xs)
        all_stats += stats
    return carry, all_stats


def stats_by_kind(kinds, all_stats) -> dict[tuple, dict]:
    """``run_stacks``'s stacked stats regrouped: kind -> each entry ``[the
    kind's layers, ...]`` in the order of the kind's stack."""
    found: dict[tuple, list] = {}
    at = 0
    for segment in stack_plan(kinds):
        if segment[0] == "run":
            found.setdefault(segment[1], []).append(all_stats[at])
            at += 1
            continue
        _, _, period = segment
        runs: dict[tuple, list] = {}
        for kind, _ in period:
            runs.setdefault(kind, []).append(all_stats[at])
            at += 1
        for kind, parts in runs.items():
            # ``[periods, the run's layers, ...]`` each: a kind's runs side by
            # side inside a period, then the periods in a row
            joined = jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs, axis=1), *parts)
            found.setdefault(kind, []).append(jax.tree_util.tree_map(
                lambda a: a.reshape((-1,) + a.shape[2:]), joined))
    return {kind: jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)
            for kind, parts in found.items()}


def _period_takes(period):
    """The stacks a periodic segment takes, one per distinct kind: ``(kind,
    layers a period)``."""
    per: dict = {}
    for kind, n in period:
        per[kind] = per.get(kind, 0) + n
    return list(per.items())


def _period_split(period, stacks):
    """One period's stacks (``_period_takes``'s order) cut into its runs."""
    by_kind = dict(zip((kind for kind, _ in _period_takes(period)), stacks))
    at = {kind: 0 for kind in by_kind}
    for kind, n in period:
        lo = at[kind]
        at[kind] += n
        yield jax.tree_util.tree_map(lambda a: a[lo:lo + n], by_kind[kind])


def forward(params, batch: dict[str, jax.Array], cfg: LagunaConfig, policy: DtypePolicy, *,
            shift_labels: bool = True, return_logits: bool = False):
    """Causal-LM forward -> ``(loss, aux)``: llama's loss plus the router's
    load-balancing loss, coefficient applied, averaged over the sparse layers."""
    lc = cfg.llama
    input_ids = batch["input_ids"]
    attention_mask, segment_ids = batch.get("attention_mask"), batch.get("segment_ids")
    x = linear_ops.apply_embedding(
        params["embed"], input_ids, compute_dtype=policy.compute_dtype)
    x = shd.constrain(x, shd.act_spec(lc.sequence_parallel, lc.context_parallel))
    tables = rope_tables(cfg, llama.positions_for(input_ids, attention_mask, segment_ids))
    x, aux_sum, all_stats = decoder_stack(
        params["layers"], x, tables, cfg, policy,
        attention_mask=attention_mask, segment_ids=segment_ids)
    aux: dict[str, Any] = {}
    n_sparse = cfg.mlp_layer_types.count("sparse")
    if n_sparse:
        aux["router_aux_loss"] = aux_sum / n_sparse
        # the expert blocks' scalars (moe/...), the largest over the layers
        for name in sorted({name for stats in all_stats for name in stats}):
            aux[name] = jnp.max(jnp.stack(
                [jnp.max(stats[name]) for stats in all_stats if name in stats]))
    with jax.named_scope("ce_head"):
        hidden = norm_ops.apply_rms_norm(params["final_norm"], x, eps=lc.rms_norm_eps)
        out, head_aux = llama._head_loss(params, hidden, batch, lc, policy,
                                         shift_labels=shift_labels,
                                         return_logits=return_logits)
    aux.update(head_aux)
    if batch.get("labels") is None:
        return out, aux
    aux["lm_loss"] = out
    return out + aux.get("router_aux_loss", 0.0), aux


# ---------------------------------------------------------------------------
# the family's record (models/family.py)
# ---------------------------------------------------------------------------


def _mean_visible_keys(seq_len: int, window: Optional[int]) -> float:
    """Mean keys a causal query sees: query ``i`` sees ``min(i + 1, window)``."""
    w = seq_len if not window else min(int(window), seq_len)
    return (w * (w + 1) / 2 + (seq_len - w) * w) / seq_len


def flops_breakdown(cfg: LagunaConfig, seq_len: int) -> dict[str, float]:
    """``Family.flops_breakdown``: projections, gate and window-capped scores
    per kind; of the routed experts only the slots this program holds (the
    expected ``top_k * held / E`` a token)."""
    lc = cfg.llama
    h, d, nkv = lc.hidden_size, lc.head_size, lc.kv_heads
    attention = 0.0
    for t in cfg.layer_types:
        nh = cfg.heads(t)
        attention += (2 * h * (nh + 2 * nkv) * d + 2 * nh * d * h + 2 * h * nh
                      + 4 * nh * d * _mean_visible_keys(seq_len, cfg.window(t)))
    n_sparse = cfg.mlp_layer_types.count("sparse")
    slots = cfg.moe.top_k * cfg.moe.experts_resident / cfg.moe.num_experts
    sparse = 6 * h * (cfg.shared_expert_intermediate_size + slots * cfg.moe_intermediate_size)
    return {
        "attention": float(attention),
        "mlp": float((lc.num_layers - n_sparse) * 6 * h * lc.intermediate_size
                     + n_sparse * sparse),
        "router": float(n_sparse * 2 * h * cfg.moe.num_experts),
        "head": 2.0 * h * lc.vocab_size,
    }


def _run_facts(cfg: LagunaConfig, sched) -> dict:
    facts: dict[str, Any] = {"layer_kinds": {
        "attention": {t: cfg.layer_types.count(t) for t in ATTENTION_TYPES},
        "mlp": {t: cfg.mlp_layer_types.count(t) for t in MLP_TYPES}}}
    if cfg.moe.experts_held is not None:
        facts["moe_experts_held"] = [*cfg.moe.experts_held, cfg.moe.num_experts]
    return facts


def _logits(cfg: LagunaConfig, policy: DtypePolicy):
    def fwd(p, b, rng=None):
        logits, aux = forward(p, {"input_ids": b["input_ids"]}, cfg, policy)
        return logits, aux.get("router_aux_loss", 0.0)

    return fwd


FAMILY = Family(
    name="laguna",
    config_from=LagunaConfig.from_config,
    loss=lambda cfg, policy, *, shift_labels=True: (
        lambda p, batch, key: forward(p, batch, cfg, policy, shift_labels=shift_labels)),
    init_params=init_params,
    param_specs=param_specs,
    flops_breakdown=flops_breakdown,
    # llama's layout with the full layers' heads and the dense layer's width:
    # the planner prices neither the kinds nor the experts of this stack
    plan_shape=lambda cfg: llama.plan_shape(cfg.llama),
    logits=_logits,
    head=lambda cfg, policy, **kw: llama.head(cfg.llama, policy, **kw),
    pipeline=Refused(
        "pipeline parallelism not wired for LagunaConfig: a stage would have "
        "to slice every kind's stack (parallel/pipeline.py slices one)"),
    onef1b_head=Refused(
        "LagunaConfig: head not wired for the manual-vjp schedules (supported "
        "families: llama/mistral)"),
    decode=Refused(
        "model.architecture: laguna has no cached decode: window layers want "
        "a ring of sliding_window keys, full layers the whole context "
        "(models/decode.py holds one kind of cache)"),
    run_facts=_run_facts,
)
