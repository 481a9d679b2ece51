"""What ``activations_checkpoint_granularity: full`` keeps of a layer
(``models/llama.py::_remat_policy`` / ``checkpoint_layer``): its input and the
flash forward kernel's two outputs, ``o`` and ``lse [b, heads, s]``
(``ops/flash_attention.py::KEPT_NAMES``), so that the rematerialized layer
rebuilds q, k and v and does not call the kernel again.  Interpret mode, toy
depths at widths that tile the kernels.  Held here: the count of forward
kernels in the gradient's jaxpr, what crosses the checkpoint, loss and
gradients bit for bit against the program that keeps nothing, ``selective``
and no rematerialization lowered to the text they lowered to before, and the
``remat`` fact of ``run_summary.json``."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals  # print_saved_residuals' list

from neuronx_distributed_training_tpu.models import llama
from neuronx_distributed_training_tpu.models.family import resolve
from neuronx_distributed_training_tpu.ops import flash_attention as fa
from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

FP32 = DtypePolicy.from_precision_config({"type": "fp32"})
B, S, D = 2, 256, 128
FLASH = {"flash_attention": True}
LLAMA = dict(architecture="llama", vocab_size=96, hidden_size=2 * D, intermediate_size=64,
             num_layers=2, num_attention_heads=2, num_key_value_heads=1, fusions=FLASH)
MIXTRAL = {**LLAMA, "architecture": "mixtral",
           "moe": {"num_experts": 4, "top_k": 2, "dropless": True}}
#: 1 + 3 layers: full+dense, two window layers (3 heads) and a full one, sparse
LAGUNA = dict(
    architecture="laguna", vocab_size=96, hidden_size=64, intermediate_size=64,
    num_hidden_layers=4, num_attention_heads=2, num_key_value_heads=1, head_dim=D,
    sliding_window=128, fusions=FLASH,
    layer_types=["full_attention", "sliding_attention", "sliding_attention", "full_attention"],
    mlp_layer_types=["dense", "sparse", "sparse", "sparse"],
    num_attention_heads_per_layer={"full_attention": 2, "sliding_attention": 3},
    num_experts=4, num_experts_per_tok=2, moe_intermediate_size=16,
    shared_expert_intermediate_size=16)
#: the dense layer 0 and two sparse layers; 2 heads score over 128 + 64 dims
KANANA = dict(
    architecture="kanana", vocab_size=96, hidden_size=64, intermediate_size=64,
    num_hidden_layers=3, num_attention_heads=2, num_key_value_heads=2,
    qk_nope_head_dim=D, qk_rope_head_dim=64, v_head_dim=D, kv_lora_rank=32,
    first_k_dense_replace=1, n_routed_experts=4, num_experts_per_tok=2,
    moe_intermediate_size=16, n_shared_experts=1, scoring_func="sigmoid",
    router_bias_update_rate=0.001, fusions=FLASH)
#: family -> (model, layer applications a stack, heads of each stack's call)
FULL_CASES = {
    "llama": (LLAMA, {"layers": (2, 2)}),
    "kanana": (KANANA, {"dense": (1, 2), "sparse": (2, 2)}),
    "laguna": (LAGUNA, {"full_dense": (1, 2), "sliding_sparse": (2, 3), "full_sparse": (1, 2)}),
}


def program(model, granularity):
    """-> (the loss as a function of the parameters, parameters)."""
    family, cfg = resolve({"model": {
        **model, "activations_checkpoint_granularity": granularity}})
    params = family.init_params(jax.random.PRNGKey(0), cfg, FP32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, model["vocab_size"])
    loss = family.loss(cfg, FP32)
    return lambda p: loss(p, {"input_ids": toks, "labels": toks}, jax.random.PRNGKey(2))[0], params


@pytest.fixture
def keeps_nothing(monkeypatch):
    """``full`` as it was: ``nothing_saveable``, the kernel's outputs unnamed."""
    monkeypatch.setattr(llama, "_remat_policy", lambda granularity, kept=(): (
        jax.checkpoint_policies.nothing_saveable if granularity == "full" else None))
    monkeypatch.setattr(llama, "_keeps_flash_outputs", lambda cfg: False)


def forward_kernels(f, params) -> int:
    return len(re.findall(r"name=flash_fwd\b", str(jax.make_jaxpr(jax.grad(f))(params))))


def kept_by_the_scans(f, params):
    """Shapes of what the scans over layers hand the backward pass."""
    return sorted(tuple(aval.shape) for aval, why in saved_residuals(f, params)
                  if "output of scan" in why)


@pytest.mark.parametrize("name", list(FULL_CASES))
def test_full_keeps_the_input_and_the_kernels_outputs_and_runs_the_kernel_once(name):
    model, stacks = FULL_CASES[name]
    f, params = program(model, "full")
    # one forward kernel in the text of each stack's scan, none in its transpose
    assert forward_kernels(f, params) == len(stacks)
    hidden = model["hidden_size"]
    expected = []
    for layers, heads in stacks.values():
        expected += [(layers, B, S, hidden), (layers, B, heads, S, D), (layers, B, heads, S)]
    kept = kept_by_the_scans(f, params)
    # beside them: the last layer's output (the final norm's input) and, from
    # a sparse stack, scalars a layer (its router loss and stats)
    assert [shape for shape in kept if len(shape) > 3] == sorted(expected)


@pytest.mark.parametrize("name", list(FULL_CASES))
def test_full_equals_the_program_that_keeps_nothing_bit_for_bit(name, request):
    model, stacks = FULL_CASES[name]
    f, params = program(model, "full")
    loss, grads = jax.jit(jax.value_and_grad(f))(params)
    request.getfixturevalue("keeps_nothing")
    g, same_params = program(model, "full")
    # the rerun calls the kernel: a second one in each stack's text
    assert forward_kernels(g, same_params) == 2 * len(stacks)
    assert not [s for s in kept_by_the_scans(g, same_params) if len(s) > 4]
    loss0, grads0 = jax.jit(jax.value_and_grad(g))(same_params)
    assert np.array_equal(np.asarray(loss), np.asarray(loss0))
    flat, flat0 = jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(grads0)
    assert len(flat) == len(flat0) and all(
        np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(flat, flat0))
    attention = [a for path, a in jax.tree_util.tree_leaves_with_path(grads)
                 if "attn" in jax.tree_util.keystr(path)]
    assert attention and all(np.any(np.asarray(a) != 0) for a in attention)


def test_full_keeps_the_kernels_outputs_of_a_call_made_per_shard(devices8):
    """On a mesh the kernel is called inside a manual region (``ops/attention.py::
    _flash_on_mesh``): the names reach the layer's policy through it."""
    from neuronx_distributed_training_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(tensor_model_parallel_size=2), devices=devices8[:4])
    f, params = program(LLAMA, "full")
    with mesh, shd.use_mesh(mesh):
        assert forward_kernels(f, params) == 1
        kept = kept_by_the_scans(f, params)
    # dp 2 x tp 2: four shards of one sequence and one head each, stacked by layer
    assert (2, 4, 1, S, D) in kept and (2, 4, 1, S) in kept


def _parents_flash():
    """``_flash`` as it was before its forward rule could name anything."""
    @functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
    def flash(q, k, v, kvm, seg, *static):
        return fa._forward(q, k, v, kvm, seg, *static)[0]

    def fwd(q, k, v, kvm, seg, *static):
        o, lse = fa._forward(q, k, v, kvm, seg, *static)
        return o, (q, k, v, kvm, seg, o, lse)

    flash.defvjp(fwd, fa._backward)
    return lambda *args: flash(*args[:-1])  # the last argument: keep


@pytest.mark.parametrize("granularity", ["selective", None, "full"])
@pytest.mark.parametrize("model", [LLAMA, MIXTRAL, LAGUNA],
                         ids=lambda m: m["architecture"])
def test_selective_and_none_lower_to_the_text_they_lowered_to(model, granularity, monkeypatch):
    """Only ``full`` names and keeps anything: the steps of the cells that run
    ``selective`` are the programs they were (and ``full``'s is another, which
    shows that the comparison can tell)."""
    from neuronx_distributed_training_tpu.optim.adamw import AdamWConfig, init_opt_state
    from neuronx_distributed_training_tpu.trainer.step import make_train_step

    family, cfg = resolve({"model": {
        **model, "activations_checkpoint_granularity": granularity}})
    params = family.init_params(jax.random.PRNGKey(0), cfg, FP32)
    batch = {"input_ids": jnp.zeros((B, S), jnp.int32), "labels": jnp.zeros((B, S), jnp.int32)}

    def lowered():
        step = make_train_step(family.loss(cfg, FP32), AdamWConfig(), lambda s: 1e-3, FP32)
        return jax.jit(step).lower(params, init_opt_state(params, FP32), batch,
                                   jax.random.PRNGKey(0)).as_text()

    mine = lowered()
    monkeypatch.setattr(fa, "_flash", _parents_flash())
    assert (mine == lowered()) == (granularity != "full")


@pytest.mark.parametrize("granularity, impl, expected", [
    ("full", "flash", {"granularity": "full", "kept": ["flash_o", "flash_lse"],
                       "flash_fwd_per_layer_application": 1}),
    ("full", "ring", {"granularity": "full", "kept": ["flash_o", "flash_lse"],
                      "flash_fwd_per_layer_application": 2}),
    ("full", "core", {"granularity": "full", "kept": ["flash_o", "flash_lse"]}),
    ("selective", "flash", {"granularity": "selective", "kept": "all",
                            "recomputed": ["attn_scores", "attn_probs"],
                            "flash_fwd_per_layer_application": 1}),
    (None, "flash", {"granularity": None, "kept": "all",
                     "flash_fwd_per_layer_application": 1}),
])
def test_the_remat_fact_says_what_each_stack_keeps(granularity, impl, expected):
    cfg = llama.LlamaConfig(activations_checkpoint_granularity=granularity,
                            attention_impl=impl)
    body = lambda x, lp: (x, None)  # noqa: E731
    with shd.collect_trace_facts() as facts:
        wrapped = llama.checkpoint_layer(body, cfg, stack="layers")
    assert facts == {"remat": {"layers": expected}}
    assert (wrapped is body) == (granularity is None)
    assert llama.checkpoint_layer(body, cfg, stack="layers") is not None  # no trace: no fact


def test_a_cells_stacks_record_their_facts():
    """Every stack of a family records under its own name, from the trace."""
    for name, (model, stacks) in FULL_CASES.items():
        f, params = program(model, "full")
        with shd.collect_trace_facts() as facts:
            jax.make_jaxpr(f)(params)
        assert set(facts["remat"]) == set(stacks), name
        assert all(entry["flash_fwd_per_layer_application"] == 1
                   for entry in facts["remat"].values())
