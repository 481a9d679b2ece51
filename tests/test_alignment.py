"""LoRA (inject/freeze/merge), DPO (losses + reference pass), ORPO."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P


from neuronx_distributed_training_tpu.alignment import (
    compute_reference_logprobs,
    dpo_loss,
    orpo_loss,
    sequence_logprobs,
)
from neuronx_distributed_training_tpu.alignment.dpo import make_dpo_loss_fn
from neuronx_distributed_training_tpu.models import llama
from neuronx_distributed_training_tpu.optim.adamw import AdamWConfig, adamw_update, init_opt_state
from neuronx_distributed_training_tpu.peft import (
    LoraConfig,
    add_lora,
    lora_param_specs,
    merge_lora,
    trainable_mask,
)
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy

FP32 = DtypePolicy(param_dtype=jnp.float32, compute_dtype=jnp.float32,
                   softmax_dtype=jnp.float32)
TINY = llama.LlamaConfig(
    vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
    num_attention_heads=4, num_kv_heads=2, max_position_embeddings=32,
    activations_checkpoint_granularity=None,
)


class TestLora:
    def test_inject_preserves_forward(self):
        """Zero-init B => LoRA model == base model at t=0."""
        params = llama.init_params(jax.random.PRNGKey(0), TINY, FP32)
        batch = {"input_ids": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)}
        base_logits, _ = llama.forward(params, batch, TINY, FP32)
        lparams = add_lora(params, LoraConfig(rank=4), jax.random.PRNGKey(2))
        lora_logits, _ = llama.forward(lparams, batch, TINY, FP32)
        np.testing.assert_allclose(np.asarray(base_logits), np.asarray(lora_logits),
                                   atol=1e-6)
        # adapters exist on targeted modules, stacked over layers
        assert lparams["layers"]["attn"]["qkv"]["lora_a"].shape == (2, 32, 4)

    def test_trainable_mask_freezes_base(self):
        params = llama.init_params(jax.random.PRNGKey(0), TINY, FP32)
        lparams = add_lora(params, LoraConfig(rank=4), jax.random.PRNGKey(2))
        mask = trainable_mask(lparams)
        assert mask["layers"]["attn"]["qkv"]["lora_a"] == 1.0
        assert mask["layers"]["attn"]["qkv"]["w"] == 0.0
        assert mask["embed"]["embedding"] == 0.0

    @pytest.mark.slow
    def test_frozen_params_do_not_move(self):
        params = llama.init_params(jax.random.PRNGKey(0), TINY, FP32)
        lparams = add_lora(params, LoraConfig(rank=4), jax.random.PRNGKey(2))
        batch = {"input_ids": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)}
        batch["labels"] = batch["input_ids"]

        def loss_fn(p):
            return llama.forward(p, batch, TINY, FP32)[0]

        grads = jax.grad(loss_fn)(lparams)
        opt = init_opt_state(lparams, FP32)
        mask = trainable_mask(lparams)
        new_params, _, _ = adamw_update(
            lparams, grads, opt, 1e-2, AdamWConfig(), FP32, trainable_mask=mask
        )
        np.testing.assert_array_equal(
            np.asarray(new_params["layers"]["attn"]["qkv"]["w"]),
            np.asarray(lparams["layers"]["attn"]["qkv"]["w"]),
        )
        # adapters DO move
        assert not np.allclose(
            np.asarray(new_params["layers"]["attn"]["qkv"]["lora_b"]),
            np.asarray(lparams["layers"]["attn"]["qkv"]["lora_b"]),
        )

    def test_merge_matches_adapter_forward(self):
        params = llama.init_params(jax.random.PRNGKey(0), TINY, FP32)
        lparams = add_lora(params, LoraConfig(rank=4, alpha=8), jax.random.PRNGKey(2))
        # give B nonzero values so the adapter actually does something
        lparams["layers"]["attn"]["qkv"]["lora_b"] = (
            0.01 * jax.random.normal(jax.random.PRNGKey(3),
                                     lparams["layers"]["attn"]["qkv"]["lora_b"].shape)
        )
        batch = {"input_ids": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)}
        adapter_logits, _ = llama.forward(lparams, batch, TINY, FP32)
        merged = merge_lora(lparams)
        merged_logits, _ = llama.forward(merged, batch, TINY, FP32)
        np.testing.assert_allclose(np.asarray(adapter_logits),
                                   np.asarray(merged_logits), atol=1e-5)
        assert "lora_a" not in merged["layers"]["attn"]["qkv"]

    def test_lora_specs_follow_base_layout(self):
        specs = llama.param_specs(TINY)
        lspecs = lora_param_specs(specs, LoraConfig(rank=4))
        qkv = lspecs["layers"]["attn"]["qkv"]
        assert qkv["lora_a"] == P(None, None, None)
        assert qkv["lora_b"] == P(None, None, "model")  # column layout preserved
        o = lspecs["layers"]["attn"]["o"]
        assert o["lora_a"] == P(None, "model", None)  # row layout preserved
        assert o["lora_b"] == P(None, None, None)


class TestDPO:
    def test_sequence_logprobs_masking(self):
        logits = jnp.zeros((1, 4, 8))  # uniform -> log p = -log 8 per token
        labels = jnp.array([[1, 2, 3, 4]])
        mask = jnp.array([[0, 0, 1, 1]])
        lp = sequence_logprobs(logits, labels, mask)
        # shift drops position 0; mask keeps labels at shifted positions 1,2
        np.testing.assert_allclose(float(lp[0]), -2 * np.log(8), rtol=1e-5)

    def test_dpo_loss_prefers_chosen(self):
        b = jnp.array([0.0, 0.0])
        loss_good, m_good = dpo_loss(b + 2.0, b - 2.0, b, b, beta=0.5)
        loss_bad, m_bad = dpo_loss(b - 2.0, b + 2.0, b, b, beta=0.5)
        assert float(loss_good) < float(loss_bad)
        assert float(m_good["reward_accuracy"]) == 1.0
        assert float(m_bad["reward_accuracy"]) == 0.0

    def test_reference_pass_and_loss_fn(self):
        params = llama.init_params(jax.random.PRNGKey(0), TINY, FP32)

        def fwd(p, batch):
            logits, _ = llama.forward(p, batch, TINY, FP32)
            return logits

        key = jax.random.PRNGKey(1)
        mk = lambda k: jax.random.randint(k, (2, 16), 0, 64)
        batches = [
            {
                "chosen_input_ids": mk(jax.random.fold_in(key, i)),
                "rejected_input_ids": mk(jax.random.fold_in(key, 100 + i)),
            }
            for i in range(2)
        ]
        cols = compute_reference_logprobs(params, batches, fwd)
        assert cols["reference_chosen_logps"].shape == (4,)
        assert np.all(np.isfinite(cols["reference_chosen_logps"]))

        # policy == reference at t=0 -> logits term 0 -> loss = -logsigmoid(0)
        batch = dict(batches[0])
        batch["reference_chosen_logps"] = jnp.asarray(cols["reference_chosen_logps"][:2])
        batch["reference_rejected_logps"] = jnp.asarray(cols["reference_rejected_logps"][:2])
        loss_fn = make_dpo_loss_fn(fwd, beta=0.1)
        loss, metrics = loss_fn(params, batch, None)
        np.testing.assert_allclose(float(loss), -np.log(0.5), rtol=1e-4)
        assert float(metrics["reward_margin"]) == pytest.approx(0.0, abs=1e-5)


class TestORPO:
    def test_orpo_prefers_chosen(self):
        chosen = jnp.array([-0.5, -0.5])
        rejected = jnp.array([-3.0, -3.0])
        nll = jnp.asarray(0.5)
        loss_good, m = orpo_loss(chosen, rejected, nll, beta=0.5)
        loss_bad, _ = orpo_loss(rejected, chosen, nll, beta=0.5)
        assert float(loss_good) < float(loss_bad)
        assert float(m["orpo_log_odds"]) > 0


class TestKTO:
    """KTO (unpaired preference, arXiv:2402.01306) — an extension beyond the
    reference's DPO/ORPO pair-only surface."""

    def test_kto_prefers_desirable(self):
        from neuronx_distributed_training_tpu.alignment.losses import kto_loss

        ref = jnp.zeros((4,))
        labels = jnp.asarray([1.0, 1.0, 0.0, 0.0])
        # policy already agrees with the labels -> lower loss
        good = jnp.asarray([2.0, 2.0, -2.0, -2.0])
        bad = jnp.asarray([-2.0, -2.0, 2.0, 2.0])
        l_good, m = kto_loss(good, ref, labels, beta=0.5)
        l_bad, _ = kto_loss(bad, ref, labels, beta=0.5)
        assert float(l_good) < float(l_bad)
        assert float(m["rewards_desirable"]) > float(m["rewards_undesirable"])

    def test_kto_gradient_directions(self):
        from neuronx_distributed_training_tpu.alignment.losses import kto_loss

        ref = jnp.zeros((2,))
        labels = jnp.asarray([1.0, 0.0])

        def loss(p):
            return kto_loss(p, ref, labels, beta=0.5)[0]

        g = jax.grad(loss)(jnp.zeros((2,)))
        assert float(g[0]) < 0  # desirable logp pushed UP
        assert float(g[1]) > 0  # undesirable logp pushed DOWN

    def test_class_weights(self):
        from neuronx_distributed_training_tpu.alignment.losses import kto_loss

        ref = jnp.zeros((2,))
        labels = jnp.asarray([1.0, 0.0])
        p = jnp.asarray([-1.0, 1.0])  # both wrong
        l1, _ = kto_loss(p, ref, labels, beta=0.5, undesirable_weight=1.0)
        l2, _ = kto_loss(p, ref, labels, beta=0.5, undesirable_weight=2.0)
        assert float(l2) > float(l1)


class TestKTOMismatchedKL:
    """kl_estimator: mismatched — the paper's off-policy z0 baseline from
    (prompt_i, completion_{i+1}) pairs (arXiv:2402.01306 / TRL semantics)."""

    class CharTok:
        eos_token_id = 1
        def encode(self, s):
            return [3 + (ord(c) % 60) for c in s]

    def _records(self, n=8):
        return [{"prompt": f"pr{i}", "completion": f"answer {i}",
                 "label": i % 2 == 0} for i in range(n)]

    @staticmethod
    def _paired_indices(a):
        """Recover which record each kl row borrowed its completion from by
        matching completion tokens (pairing is a seeded derangement now, not
        a fixed shift)."""
        n = a["input_ids"].shape[0]
        comps = [tuple(a["input_ids"][j][a["loss_mask"][j] > 0])
                 for j in range(n)]
        pairs = []
        for i in range(n):
            kl_comp = tuple(a["kl_input_ids"][i][a["kl_loss_mask"][i] > 0])
            pairs.append(comps.index(kl_comp))
        return pairs

    def test_kl_columns_are_spliced_pairs(self):
        from neuronx_distributed_training_tpu.data.modules import KTODataModule

        dm = KTODataModule(self._records(), self.CharTok(), seq_length=32,
                           global_batch_size=4, kl_estimator="mismatched")
        a = dm.arrays
        assert "kl_input_ids" in a and "kl_loss_mask" in a
        n, s = a["input_ids"].shape
        pairs = self._paired_indices(a)
        for i, j in enumerate(pairs):
            # kl row i = prompt of i (masked) + completion of some j!=i
            assert j != i, "mismatched pairing must be a derangement"
            prompt_len_i = int(np.argmax(a["loss_mask"][i] > 0))
            comp_j = a["input_ids"][j][a["loss_mask"][j] > 0]
            kl_comp = a["kl_input_ids"][i][a["kl_loss_mask"][i] > 0]
            np.testing.assert_array_equal(kl_comp, comp_j)
            np.testing.assert_array_equal(
                a["kl_input_ids"][i][:prompt_len_i],
                a["input_ids"][i][:prompt_len_i],
            )
        # every completion is used exactly once (cyclic derangement)
        assert sorted(pairs) == list(range(n))

    def test_pairing_is_seeded_and_deterministic(self):
        from neuronx_distributed_training_tpu.data.modules import KTODataModule

        mk = lambda seed: KTODataModule(
            self._records(16), self.CharTok(), seq_length=32,
            global_batch_size=4, kl_estimator="mismatched", seed=seed)
        a1, a2 = mk(7).arrays, mk(7).arrays
        np.testing.assert_array_equal(a1["kl_input_ids"], a2["kl_input_ids"])
        a3 = mk(8).arrays
        assert not np.array_equal(a1["kl_input_ids"], a3["kl_input_ids"])

    def test_repeated_prompts_never_pair_matched(self):
        """Several completions per prompt listed consecutively (the common
        KTO file layout) must not yield an effectively matched KL pair."""
        from neuronx_distributed_training_tpu.data.modules import KTODataModule

        recs = []
        for p in range(4):
            for c in range(4):  # 4 consecutive completions per prompt
                recs.append({"prompt": f"prompt {p}",
                             "completion": f"ans {p}-{c}", "label": c % 2})
        dm = KTODataModule(recs, self.CharTok(), seq_length=48,
                           global_batch_size=4, kl_estimator="mismatched")
        a = dm.arrays
        enc = self.CharTok().encode
        prompt_of = [tuple(enc(r["prompt"])) for r in recs]
        pairs = self._paired_indices(a)
        for i, j in enumerate(pairs):
            assert prompt_of[j] != prompt_of[i], (
                f"kl row {i} paired with token-identical prompt {j}")
        # largest group (4) fits in half the dataset (16) -> a bijection:
        # every completion weighs into the z0 baseline exactly once
        assert sorted(pairs) == list(range(len(recs)))

    def test_majority_prompt_falls_back_non_injective(self):
        """One prompt owning > n/2 records: no bijection avoiding matched
        pairs exists (Hall) — the pairing warns and stays matched-pair-free."""
        from neuronx_distributed_training_tpu.data.modules import KTODataModule

        recs = [{"prompt": "big", "completion": f"b{i}", "label": True}
                for i in range(6)]
        recs += [{"prompt": "other", "completion": f"o{i}", "label": False}
                 for i in range(2)]
        with pytest.warns(UserWarning, match="no one-to-one"):
            dm = KTODataModule(recs, self.CharTok(), seq_length=32,
                               global_batch_size=4, kl_estimator="mismatched")
        enc = self.CharTok().encode
        prompt_of = [tuple(enc(r["prompt"])) for r in recs]
        for i, j in enumerate(self._paired_indices(dm.arrays)):
            assert prompt_of[j] != prompt_of[i]

    def test_all_identical_prompts_warns(self):
        from neuronx_distributed_training_tpu.data.modules import KTODataModule

        recs = [{"prompt": "same", "completion": f"c{i}", "label": True}
                for i in range(4)]
        with pytest.warns(UserWarning, match="shares one prompt"):
            KTODataModule(recs, self.CharTok(), seq_length=32,
                          global_batch_size=2, kl_estimator="mismatched")

    def test_grouping_keys_on_raw_prompt_not_truncated_prefix(self):
        """Overlong rows trim the prompt by their own completion's length, so
        two records sharing a prompt can carry different row prefixes — the
        pairing must still see ONE prompt group (here: the all-identical
        degenerate warning), not distinct groups it could pair together."""
        from neuronx_distributed_training_tpu.data.modules import KTODataModule

        recs = [
            {"prompt": "p" * 60, "completion": "c" * 4, "label": True},
            {"prompt": "p" * 60, "completion": "d" * 12, "label": False},
        ]
        with pytest.warns(UserWarning, match="shares one prompt"):
            KTODataModule(recs, self.CharTok(), seq_length=24,
                          global_batch_size=2, kl_estimator="mismatched")

    def test_kl_rewards_change_z0(self):
        from neuronx_distributed_training_tpu.alignment.losses import kto_loss

        ref = jnp.zeros((4,))
        labels = jnp.asarray([1.0, 1.0, 0.0, 0.0])
        policy = jnp.asarray([2.0, 2.0, -2.0, -2.0])
        _, m_batch = kto_loss(policy, ref, labels, beta=0.5)
        kl = jnp.asarray([0.3, 0.3, 0.3, 0.3])
        _, m_mis = kto_loss(policy, ref, labels, beta=0.5, kl_rewards=kl)
        assert abs(float(m_mis["kto_kl"]) - 0.3) < 1e-6
        assert float(m_batch["kto_kl"]) != float(m_mis["kto_kl"])

    def test_trainer_end_to_end_mismatched(self, tmp_path, devices8):
        from neuronx_distributed_training_tpu.config.loader import load_config
        from neuronx_distributed_training_tpu.data.modules import KTODataModule
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        cfg = load_config({
            "name": "ktomis", "model_source": "hf", "seed": 5,
            "trainer": {"max_steps": 2, "log_every_n_steps": 1},
            "exp_manager": {"exp_dir": str(tmp_path / "exp")},
            "model_alignment_strategy": {"kto": {"kl_beta": 0.2,
                                                 "kl_estimator": "mismatched"}},
            "distributed_strategy": {"tensor_model_parallel_size": 2},
            "data": {"global_batch_size": 8, "micro_batch_size": 1,
                     "seq_length": 32, "synthetic": True},
            "model": {
                "vocab_size": 128, "hidden_size": 64, "intermediate_size": 128,
                "num_layers": 2, "num_attention_heads": 4,
                "num_key_value_heads": 2, "max_position_embeddings": 32,
                "optim": {"lr": 1e-3,
                          "sched": {"name": "constant"}},
            },
            "precision": {"type": "mixed_precision"},
        })
        dm = KTODataModule(self._records(16), self.CharTok(), seq_length=32,
                           global_batch_size=8, kl_estimator="mismatched")
        t = Trainer.from_config(cfg, data_module=dm, enable_checkpointing=False)
        t.pre_fit(t)
        assert "reference_kl_logps" in dm.arrays  # pre-fit covered KL pairs
        m = t.fit()
        assert np.isfinite(m["loss"])
        assert "kto_kl" in m

    def test_mismatched_under_pp_rejected(self):
        from neuronx_distributed_training_tpu.config.loader import load_config

        with pytest.raises(ValueError, match="mismatched"):
            load_config({
                "model_alignment_strategy": {
                    "kto": {"kl_estimator": "mismatched"}},
                "distributed_strategy": {"pipeline_model_parallel_size": 2},
                "model": {"num_layers": 2},
                "data": {"global_batch_size": 4, "micro_batch_size": 1},
            })

    def test_single_record_mismatched_rejected(self):
        from neuronx_distributed_training_tpu.data.modules import KTODataModule

        with pytest.raises(ValueError, match="at least 2"):
            KTODataModule(self._records(1), self.CharTok(), seq_length=32,
                          global_batch_size=1, kl_estimator="mismatched")

    def test_overlong_splice_keeps_completion(self):
        from neuronx_distributed_training_tpu.data.modules import KTODataModule

        recs = [{"prompt": "p" * 60, "completion": f"c{i}" * 8,
                 "label": True} for i in range(4)]
        with pytest.warns(UserWarning, match="shares one prompt"):
            dm = KTODataModule(recs, self.CharTok(), seq_length=24,
                               global_batch_size=2, kl_estimator="mismatched")
        a = dm.arrays
        for i, j in enumerate(self._paired_indices(a)):
            comp_j = a["input_ids"][j][a["loss_mask"][j] > 0]
            kl_comp = a["kl_input_ids"][i][a["kl_loss_mask"][i] > 0]
            # the completion survives truncation intact (prompt is trimmed)
            np.testing.assert_array_equal(kl_comp, comp_j)
            assert kl_comp.size > 0

    def test_stale_sidecar_column_set_recomputes(self, tmp_path, devices8):
        """A batch_mean sidecar resumed under mismatched must recompute, not
        KeyError in the jitted step."""
        from neuronx_distributed_training_tpu.config.loader import load_config
        from neuronx_distributed_training_tpu.data.modules import KTODataModule
        from neuronx_distributed_training_tpu.trainer.loop import Trainer

        def cfg_for(est):
            return load_config({
                "name": "ktostale", "model_source": "hf", "seed": 5,
                "trainer": {"max_steps": 1, "log_every_n_steps": 1},
                "exp_manager": {"exp_dir": str(tmp_path / "exp")},
                "model_alignment_strategy": {"kto": {"kl_beta": 0.2,
                                                     "kl_estimator": est}},
                "distributed_strategy": {"tensor_model_parallel_size": 2},
                "data": {"global_batch_size": 8, "micro_batch_size": 1,
                         "seq_length": 32, "synthetic": True},
                "model": {
                    "vocab_size": 128, "hidden_size": 64,
                    "intermediate_size": 128, "num_layers": 2,
                    "num_attention_heads": 4, "num_key_value_heads": 2,
                    "max_position_embeddings": 32,
                    "optim": {"lr": 1e-3, "sched": {"name": "constant"}},
                },
                "precision": {"type": "mixed_precision"},
            })

        dm1 = KTODataModule(self._records(8), self.CharTok(), seq_length=32,
                            global_batch_size=8)
        t1 = Trainer.from_config(cfg_for("batch_mean"), data_module=dm1)
        t1.pre_fit(t1)  # writes the batch_mean sidecar (reference_logps only)

        dm2 = KTODataModule(self._records(8), self.CharTok(), seq_length=32,
                            global_batch_size=8, kl_estimator="mismatched")
        t2 = Trainer.from_config(cfg_for("mismatched"), data_module=dm2)
        t2.pre_fit(t2)
        assert "reference_kl_logps" in dm2.arrays
