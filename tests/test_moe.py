"""MoE: routing, dropped vs dropless numerics, aux loss, EP sharding."""

import hashlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from neuronx_distributed_training_tpu.ops import moe
from neuronx_distributed_training_tpu.parallel import sharding as shd
from neuronx_distributed_training_tpu.parallel.mesh import MeshConfig, build_mesh

CFG = moe.MoEConfig(num_experts=4, top_k=2, dropless=True)
FP32 = dict(compute_dtype=jnp.float32)


def params_and_x(key, t=32, h=16, ffn=32, cfg=CFG):
    kp, kx = jax.random.split(key)
    params = moe.init_moe_params(kp, h, ffn, cfg)
    x = jax.random.normal(kx, (t, h), jnp.float32)
    return params, x


def dense_reference(params, x, cfg):
    """Every token through its top-k experts, computed naively per expert."""
    probs, idx, _ = moe.route(params["router"], x, cfg)
    t, h = x.shape
    out = np.zeros((t, h), np.float32)
    gu = np.asarray(params["experts"]["gate_up"], np.float32)
    dn = np.asarray(params["experts"]["down"], np.float32)
    xn = np.asarray(x, np.float32)
    pn, en = np.asarray(probs), np.asarray(idx)
    for ti in range(t):
        for kk in range(en.shape[1]):
            e = int(en[ti, kk])
            g_u = xn[ti] @ gu[e]
            g, u = np.split(g_u, 2)
            act = (g / (1 + np.exp(-g))) * u
            out[ti] += pn[ti, kk] * (act @ dn[e])
    return out


class TestRouting:
    def test_topk_shapes_and_norm(self):
        params, x = params_and_x(jax.random.PRNGKey(0))
        probs, idx, logits = moe.route(params["router"], x, CFG)
        assert probs.shape == (32, 2) and idx.shape == (32, 2)
        assert logits.shape == (32, 4)
        np.testing.assert_allclose(np.asarray(probs.sum(-1)), 1.0, rtol=1e-5)

    def test_sinkhorn_balances(self):
        cfg = moe.MoEConfig(num_experts=4, top_k=1, router_type="sinkhorn")
        params, x = params_and_x(jax.random.PRNGKey(1), t=256, cfg=cfg)
        _, idx, _ = moe.route(params["router"], x, cfg)
        counts = np.bincount(np.asarray(idx).ravel(), minlength=4)
        # balanced routing: no expert should starve
        assert counts.min() > 0.1 * 256 / 4, counts

    def test_sigmoid_scores_choose_by_bias_and_weigh_by_score(self):
        """``score_func: sigmoid``: a bias that favours an expert brings it
        rows and leaves the weights the scores' own; the block's output is
        the dense sum over the experts so chosen."""
        cfg = moe.MoEConfig(num_experts=4, top_k=2, score_func="sigmoid",
                            routed_scaling_factor=1.5)
        params, x = params_and_x(jax.random.PRNGKey(2), cfg=cfg)
        assert params["router"]["bias"].shape == (4,)
        probs, idx, logits = moe.route(params["router"], x, cfg)
        np.testing.assert_allclose(np.asarray(probs.sum(-1)), 1.5, rtol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(jnp.sort(idx, -1)),
            np.asarray(jnp.sort(jax.lax.top_k(logits, 2)[1], -1)))   # bias 0: the scores'
        params["router"]["bias"] = jnp.array([0.0, 0.0, 0.0, 5.0])
        _, idx, _ = moe.route(params["router"], x, cfg)
        assert bool(jnp.all(jnp.any(idx == 3, axis=-1)))
        y, aux = moe.moe_block(params, x[None], cfg, compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(y[0]), dense_reference(params, x, cfg),
                                   rtol=2e-4, atol=2e-5)
        counts = np.asarray(aux["expert_counts"])
        assert counts.sum() == 64 and counts[3] == 32
        assert float(aux["stats"]["moe/load_max_share"]) == pytest.approx(32 / 16)
        # a softmax block returns neither
        _, plain = moe.moe_block(params_and_x(jax.random.PRNGKey(2))[0], x[None], CFG,
                                 compute_dtype=jnp.float32)
        assert "expert_counts" not in plain and plain["stats"] == {}

    def test_aux_loss_uniform_is_one(self):
        # perfectly uniform router -> loss == 1.0 (its minimum)
        logits = jnp.zeros((64, 4))
        idx = jnp.tile(jnp.arange(4), 32).reshape(64, 2)
        loss = moe.load_balancing_loss(logits, idx, CFG)
        np.testing.assert_allclose(float(loss), 1.0, rtol=1e-5)


class TestExpertCompute:
    def test_dropless_matches_dense_reference(self):
        params, x = params_and_x(jax.random.PRNGKey(2))
        y, _ = moe.moe_dropless(params, x, CFG, compute_dtype=jnp.float32)
        ref = dense_reference(params, x, CFG)
        np.testing.assert_allclose(np.asarray(y), ref, atol=1e-4)

    def test_dropped_high_capacity_matches_dense(self):
        cfg = moe.MoEConfig(num_experts=4, top_k=2, dropless=False, capacity_factor=4.0)
        params, x = params_and_x(jax.random.PRNGKey(3), cfg=cfg)
        y, _ = moe.moe_dropped(params, x, cfg, compute_dtype=jnp.float32)
        ref = dense_reference(params, x, cfg)
        np.testing.assert_allclose(np.asarray(y), ref, atol=1e-4)

    def test_dropped_capacity_drops_tokens(self):
        cfg = moe.MoEConfig(num_experts=4, top_k=1, dropless=False, capacity_factor=0.25)
        params, x = params_and_x(jax.random.PRNGKey(4), t=64, cfg=cfg)
        y, _ = moe.moe_dropped(params, x, cfg, compute_dtype=jnp.float32)
        dropped_rows = np.all(np.asarray(y) == 0.0, axis=-1)
        assert dropped_rows.sum() > 0  # over-capacity tokens zeroed

    def test_grads_flow(self):
        params, x = params_and_x(jax.random.PRNGKey(5))

        def loss(p):
            y, _ = moe.moe_dropless(p, x, CFG, compute_dtype=jnp.float32)
            return jnp.sum(jnp.square(y))

        g = jax.grad(loss)(params)
        assert float(jnp.abs(g["experts"]["gate_up"]).sum()) > 0
        assert float(jnp.abs(g["router"]["w"]).sum()) > 0

    def test_moe_block_3d(self):
        params, _ = params_and_x(jax.random.PRNGKey(6))
        x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, 16))
        y, aux = moe.moe_block(params, x, CFG, compute_dtype=jnp.float32)
        assert y.shape == (2, 8, 16)
        assert aux["router_logits"].shape == (16, 4)


def _unwritten_tail(ragged_dot):
    """``ragged_dot`` as XLA's TPU kernel leaves its results: the rows past
    ``sum(group_sizes)`` are skipped and never written (NaN here), in the
    result and in the operand's cotangent.  XLA:CPU writes zeros there."""
    def poison(a, group_sizes):
        return jnp.where((jnp.arange(a.shape[0]) < group_sizes.sum())[:, None], a, jnp.nan)

    @jax.custom_vjp
    def dot(lhs, rhs, group_sizes):
        return poison(ragged_dot(lhs, rhs, group_sizes), group_sizes)

    def fwd(lhs, rhs, group_sizes):
        return dot(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def bwd(res, ct):
        lhs, rhs, group_sizes = res
        d_lhs, d_rhs = jax.vjp(
            lambda a, b: ragged_dot(a, b, group_sizes), lhs, rhs)[1](ct)
        return poison(d_lhs, group_sizes), d_rhs, None

    dot.defvjp(fwd, bwd)
    return dot


class TestEP:
    def test_ep_sharded_dropped_matches(self, devices8):
        """Expert-parallel (expert axis 4) dropped-MoE matches unsharded."""
        cfg = moe.MoEConfig(num_experts=4, top_k=2, dropless=False, capacity_factor=4.0)
        params, x = params_and_x(jax.random.PRNGKey(8), cfg=cfg)
        ref, _ = moe.moe_dropped(params, x, cfg, compute_dtype=jnp.float32)

        mesh = build_mesh(MeshConfig(expert_model_parallel_size=4))
        specs = moe.moe_param_specs(cfg)
        sh_params = jax.device_put(
            params,
            jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), specs,
                is_leaf=lambda s: isinstance(s, P),
            ),
        )
        with mesh:
            y, _ = jax.jit(
                lambda p, xx: moe.moe_dropped(p, xx, cfg, compute_dtype=jnp.float32)
            )(sh_params, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)

    #: name -> (MeshConfig fields, devices, context-parallel activations)
    TOKEN_MESHES = {
        "ep4": (dict(expert_model_parallel_size=4), 4, False),
        "dp2_ep2": (dict(expert_model_parallel_size=2), 4, False),
        "ep2_tp2": (dict(expert_model_parallel_size=2,
                         tensor_model_parallel_size=2), 4, False),
        "ep2_cp2": (dict(expert_model_parallel_size=2,
                         context_parallel_size=2), 4, True),
        "ep4_tp2": (dict(expert_model_parallel_size=4,
                         tensor_model_parallel_size=2), 8, False),
        "no_mesh": None,
    }

    #: router -> MoEConfig fields; sinkhorn normalises over the whole token
    #: set, so it is the case that routing per shard would break
    ROUTERS = {
        "top_k": dict(top_k=2),
        "sinkhorn": dict(top_k=1, router_type="sinkhorn"),
    }

    #: forced routing of 8 experts, 2 a token -> the share of all tokens that
    #: choose the two first experts (both on chip 0 of every expert group);
    #: the rest go round the other chips (all of them when the share is 0).
    #: Sinkhorn would undo the forcing.
    SKEWS = {"even": 0.0, "half": 0.5, "3to1": 0.75, "one_chip": 1.0}

    @staticmethod
    def _forced(params, x, share, ep, cfg):
        """``params`` and ``x [b, s, h]`` with the routing forced: the router
        reads the first E features alone, which name each token's two
        experts, near enough in weight that both rows count."""
        e, (b, s, h) = cfg.num_experts, x.shape
        tok = np.arange(b * s)
        rest = tok % ep if share == 0 else 1 + tok % (ep - 1)
        chip = np.where(tok < share * b * s, 0, rest)
        first = chip * (e // ep)
        feat = np.zeros((b * s, e), np.float32)
        feat[tok, first], feat[tok, first + 1] = 3.0, 2.75
        x = jnp.concatenate([jnp.asarray(feat).reshape(b, s, e), x[..., e:]], -1)
        w = jnp.zeros((h, e)).at[:e].set(2.0 * jnp.eye(e))
        return {**params, "router": {"w": w}}, x

    @staticmethod
    def _received(idx, cfg, dp, ep, fair):
        """The largest count of expert rows a chip would receive, over the
        fair share, from the global ``expert_idx`` [b*s, k]: the batch is
        split data-major, and the ``ep`` chips of one data group exchange
        that group's rows."""
        chip_of = np.asarray(idx).reshape(dp, -1) // (cfg.num_experts // ep)
        return max(np.bincount(g, minlength=ep).max() for g in chip_of) / fair

    @pytest.mark.parametrize("name,router,skew", [
        *itertools.product(TOKEN_MESHES, ROUTERS, [None]),
        *itertools.product(("ep4", "dp2_ep2", "ep2_tp2", "ep4_tp2"), ["top_k"], SKEWS),
    ], ids=lambda v: str(v) if v else "")
    def test_token_sharded_dropless_matches(self, devices8, name, router, skew,
                                            monkeypatch):
        """The dropless block partitioned by tokens against the unsharded one:
        forward, router outputs and every gradient (router, both expert
        weights, input), at tight tolerance, on each mesh shape that shards
        tokens (and on none), under both routers; then with 8 experts and the
        routing forced to each of ``SKEWS``: balanced, a chip that receives
        exactly the bound, and past it, where at ep 4 the weights travel
        instead (at ep 2 the bound is the worst case: every token on one
        chip's experts fills it), with ``ragged_dot`` leaving the rows past
        its groups unwritten as on the TPU (``_unwritten_tail``).

        Where ``expert`` shards the tokens the rows travel to the resident
        experts (``moe_expert_exchange``), and the block says how many a chip
        would receive and which way it went.  Also the regression pin of the
        ragged_dot EP hazard: XLA's SPMD partitioner has no rule for
        ragged_dot's group dim; with the expert dim sharded on a strided axis
        (ep2_tp2) it silently computed local expert slices against global
        group offsets — full-signal corruption (forward off by the magnitude
        of y) with no error.  The kernel sees a chip's resident experts only,
        inside the manual region."""
        cfg = moe.MoEConfig(num_experts=8 if skew else 4, dropless=True,
                            **self.ROUTERS[router])
        params, x = params_and_x(jax.random.PRNGKey(9), cfg=cfg)
        x = x.reshape(4, 8, -1)
        fields, n, cp = self.TOKEN_MESHES[name] or ({}, 1, False)
        ep = fields.get("expert_model_parallel_size", 1)
        shards = n // fields.get("tensor_model_parallel_size", 1)
        if skew:
            params, x = self._forced(params, x, self.SKEWS[skew], ep, cfg)

        def run(p, xx, act_spec=None):
            def loss(p, xx):
                y, aux = moe.moe_block(p, xx, cfg, act_spec=act_spec, **FP32)
                total = (y ** 2).sum() + moe.weighted_router_loss(
                    aux["router_logits"], aux["expert_idx"], cfg)
                return total, (y, aux)

            return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(p, xx)

        (_, (y_ref, aux_ref)), g_ref = run(params, x)
        assert aux_ref["stats"] == {}
        if skew:
            monkeypatch.setattr(jax.lax, "ragged_dot",
                                _unwritten_tail(jax.lax.ragged_dot))
        if self.TOKEN_MESHES[name] is None:
            with shd.collect_trace_facts() as traced:
                (_, (y, aux)), g = jax.jit(run)(params, x)
        else:
            mesh = build_mesh(MeshConfig(**fields), devices=devices8[:n])
            act_spec = shd.act_spec(False, cp)
            ns = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
            sh_params = jax.device_put(params, jax.tree_util.tree_map(
                ns, moe.moe_param_specs(cfg), is_leaf=lambda s: isinstance(s, P)))
            sh_x = jax.device_put(x, ns(act_spec))
            with mesh, shd.use_mesh(mesh), shd.collect_trace_facts() as traced:
                (_, (y, aux)), g = jax.jit(
                    lambda p, xx: run(p, xx, act_spec))(sh_params, sh_x)
        # the mechanism engaged: manual over every axis that shards tokens,
        # and over ``expert`` the rows travel while no chip receives more
        # than the bound (where a chip could: else the weights travel)
        fair = x.shape[0] * x.shape[1] // shards * cfg.top_k
        worst = ep * fair // cfg.top_k * min(cfg.top_k, cfg.num_experts // ep)
        bound = min(2 * fair, worst)
        assert traced == {"moe_token_shards": shards, **(
            {"moe_expert_exchange": "tokens", "moe_row_bounds": [bound]}
            if ep > 1 else {})}
        assert set(aux["stats"]) == ({"moe/recv_rows_share_max"} | (
            {"moe/row_bound"} if bound < worst else set()) if ep > 1 else set())
        if skew:
            share = self._received(aux_ref["expert_idx"], cfg, shards // ep, ep, fair)
            assert float(aux["stats"]["moe/recv_rows_share_max"]) == share
            # the forcing reached what it was made for: a balanced exchange,
            # one that fills the bound, and past it (ep4) the weights' way
            assert share == {"even": 1.0, "half": 2.0 if ep == 4 else share,
                             "one_chip": ep}.get(skew, share)
            if bound < worst:
                assert int(aux["stats"]["moe/row_bound"]) == (share * fair > bound) \
                    == (skew in ("3to1", "one_chip"))
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(aux["expert_idx"]),
                                      np.asarray(aux_ref["expert_idx"]))
        np.testing.assert_allclose(np.asarray(aux["router_logits"]),
                                   np.asarray(aux_ref["router_logits"]),
                                   rtol=1e-5, atol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                        jax.tree_util.tree_leaves(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)

    @staticmethod
    def _block_on_ep(devices, ep, cfg, params, x):
        """``(mesh, jitted (params, x) -> ((y, stats), grads), sharded params,
        sharded x)`` of the dropless block in float32 on ``expert`` = ``ep``
        (4 devices) under the loss of the parity test above."""
        mesh = build_mesh(MeshConfig(expert_model_parallel_size=ep), devices=devices[:4])
        act_spec = shd.act_spec(False, False)

        def run(p, xx, act_spec=act_spec):
            def loss(p, xx):
                y, aux = moe.moe_block(p, xx, cfg, act_spec=act_spec, **FP32)
                return (y ** 2).sum() + moe.weighted_router_loss(
                    aux["router_logits"], aux["expert_idx"], cfg), (y, aux["stats"])

            (_, out), g = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(p, xx)
            return out, g

        ns = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
        sh_params = jax.device_put(params, jax.tree_util.tree_map(
            ns, moe.moe_param_specs(cfg), is_leaf=lambda s: isinstance(s, P)))
        return mesh, jax.jit(run), sh_params, jax.device_put(x, ns(act_spec))

    #: skew -> sha256 of the block's output and every gradient, float32, under
    #: XLA:CPU, from the tree of PR 41 (the exchange's residuals still of the
    #: gathered weights' shape): (the plain block's, what this PR leaves alone;
    #: the exchange's on ep 4)
    PARENT_BITS = {
        "even": ("617408d69ee87b8ace7c916372d34ad4a0ec611f522def80d22bae9ccb0a17d1",
                 "bd929de7828b2cfae135281f48b5e1111e95dc2e8058c4b173a2ace6efb6d8fb"),
        "half": ("77aa6369b5c926ff8715281896b5b3bfa399935324dff9a70a138f9fe68fe169",
                 "fdbdfbdeb86ae9645425eb64c5a619f26eaccf7515256e29ef45f1ad8c82273d"),
        "3to1": ("9e1c5c6b4eacfe600de5d669f62d71a0405dd02f9f99facfc30ca87607dc9be2",
                 "e7ab258a2fcb27a52a224c8718b75e9a127cebe099939394707f46eb2650ce6f"),
    }

    @pytest.mark.parametrize("skew", list(PARENT_BITS))
    def test_exchange_gives_the_bits_it_gave(self, devices8, skew):
        """The exchange does the arithmetic it did before its residuals shrank
        (PR 42): the rows' way, balanced and with a chip at the bound, and the
        weights' way past it, bit for bit against values pinned from the
        parent's tree.  Skipped on a machine whose float32 arithmetic is not
        the pinning machine's, which the plain block's own bits tell."""
        def bits(y, g):
            h = hashlib.sha256()
            for a in map(np.asarray, jax.tree_util.tree_leaves((y, g))):
                h.update(str((a.shape, a.dtype)).encode() + a.tobytes())
            return h.hexdigest()

        cfg = moe.MoEConfig(num_experts=8, top_k=2, dropless=True)
        params, x = params_and_x(jax.random.PRNGKey(9), cfg=cfg)
        params, x = self._forced(params, x.reshape(4, 8, -1), self.SKEWS[skew], 4, cfg)
        mesh, run, sh_params, sh_x = self._block_on_ep(devices8, 4, cfg, params, x)
        (y, _), g = jax.jit(lambda p, xx: run(p, xx, None))(params, x)
        plain, exchanged = self.PARENT_BITS[skew]
        if bits(y, g) != plain:
            pytest.skip("the plain block's bits are not the pinning machine's")
        with mesh, shd.use_mesh(mesh):
            (y, stats), g = run(sh_params, sh_x)
        assert int(stats["moe/row_bound"]) == (skew == "3to1")
        assert bits(y, g) == exchanged

    def test_bound_that_holds_every_case_lowers_as_it_did(self, devices8):
        """At ep 2 no routing passes the bound (``rows_travel`` is ``None``):
        one way, no ``cond``.  The step's lowered text is the parent's (PR 41:
        989 lines) less two ``_pad``s of the kept weights by no rows, 18
        lines which the compiler deleted, and the renumbering that follows:
        read in a diff of the two trees' texts by PR 42, which pinned this
        one's digest.  A PR that changes what the block lowers to re-pins it
        from such a diff."""
        cfg = moe.MoEConfig(num_experts=4, top_k=2, dropless=True)
        params, x = params_and_x(jax.random.PRNGKey(9), cfg=cfg)
        mesh, run, sh_params, sh_x = self._block_on_ep(
            devices8, 2, cfg, params, x.reshape(4, 8, -1))
        with mesh, shd.use_mesh(mesh):
            text = run.lower(sh_params, sh_x).as_text()
        assert "stablehlo.case" not in text and "stablehlo.pad" not in text
        assert (len(text.splitlines()), hashlib.sha256(text.encode()).hexdigest()) == (
            971, "e76222a3696d63ce45d7c623acc2f69f465a7102e12032948df4b4554e2a0da5")

    @pytest.mark.parametrize("side", ["rows", "weights", "cond"])
    def test_exchange_keeps_nothing_of_the_gathered_weights_shape(self, devices8, side):
        """What crosses from the forward to the backward pass of the exchange
        at ep 4, where both ways exist: ``(gu, ys)`` of ``bound`` rows from
        either way alone, and with them from ``_exchange_fwd`` (the ``cond``
        over both) this chip's own cast weights and the operands (``experts``
        a dict, so ``down`` first).  No array leads with all ``E`` experts,
        so no step pads to that shape."""
        cfg = moe.MoEConfig(num_experts=8, top_k=2, dropless=True)
        t, h, ffn, ep = 16, 16, 32, 4
        bound = 2 * t * cfg.top_k
        static = (cfg, bound, "expert", jnp.bfloat16, jnp.float32)
        mesh = build_mesh(MeshConfig(expert_model_parallel_size=ep), devices=devices8[:4])
        kept = []

        def body(experts, x, probs, chosen):
            if side == "cond":
                kept.append(moe._exchange_fwd(
                    experts, x, probs, chosen, jnp.max(chosen) < 0, *static)[1])
            else:
                forward = moe._exchange_sides(*static, t)[side == "weights"][0]
                kept.append(forward(moe._cast_experts(experts, jnp.bfloat16),
                                    experts, x, probs, chosen)[1])
            return x

        experts = moe.init_moe_params(jax.random.PRNGKey(0), h, ffn, cfg)["experts"]
        with mesh, shd.use_mesh(mesh):
            jax.eval_shape(shd.shard_map(
                body, mesh=mesh, in_specs=(P("expert"), P("expert"), P("expert"), P()),
                out_specs=P("expert"), axis_names=frozenset({"expert"}), check_vma=False,
            ), experts, jnp.zeros((ep * t, h)), jnp.zeros((ep * t, cfg.top_k)),
                jnp.zeros((ep * t * cfg.top_k,), jnp.int32))
        shapes = [a.shape for a in jax.tree_util.tree_leaves(kept)]
        rows = [(bound, 2 * ffn), (bound, h)]
        local = [(cfg.num_experts // ep, h, 2 * ffn), (cfg.num_experts // ep, ffn, h)]
        assert shapes == (rows if side != "cond" else rows + local + local[::-1] + [
            (t, h), (t, cfg.top_k), (ep * t * cfg.top_k,), ()])
        assert not any(s[:1] == (cfg.num_experts,) for s in shapes)

    def test_ep_tp_sharded_dropless_matches(self, devices8):
        """Regression: ``moe_dropless`` left to GSPMD on an EP x TP mesh
        (STRIDED expert axis) — the path a batch the token axes do not divide
        still takes.  The constraint gathers the expert weights over 'expert'
        for the compute (the ragged_dot group-dim hazard above); parity must
        be tight and the gradient path exact too."""
        cfg = moe.MoEConfig(num_experts=4, top_k=2, dropless=True)
        params, x = params_and_x(jax.random.PRNGKey(9), cfg=cfg)

        def fwd(p, xx):
            return moe.moe_dropless(p, xx, cfg, compute_dtype=jnp.float32)[0]

        ref = fwd(params, x)
        gref = jax.grad(lambda p, xx: (fwd(p, xx) ** 2).sum())(params, x)

        mesh = build_mesh(
            MeshConfig(tensor_model_parallel_size=2,
                       expert_model_parallel_size=2),
            devices=devices8[:4],
        )
        specs = moe.moe_param_specs(cfg)
        sh_params = jax.device_put(
            params,
            jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), specs,
                is_leaf=lambda s: isinstance(s, P),
            ),
        )
        with mesh:
            y = jax.jit(fwd)(sh_params, x)
            g = jax.jit(jax.grad(lambda p, xx: (fwd(p, xx) ** 2).sum()))(
                sh_params, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(gref),
                        jax.tree_util.tree_leaves(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)

    def test_batch_that_does_not_divide_takes_the_global_path(self, devices8):
        """A batch the token axes cannot split evenly (one decode row on an
        ep2 mesh) runs the block unpartitioned, and says so."""
        cfg = moe.MoEConfig(num_experts=4, top_k=2, dropless=True)
        params, x = params_and_x(jax.random.PRNGKey(3), t=8, cfg=cfg)
        ref, _ = moe.moe_block(params, x[None], cfg, **FP32)
        mesh = build_mesh(MeshConfig(expert_model_parallel_size=2),
                          devices=devices8[:2])
        with mesh, shd.use_mesh(mesh), shd.collect_trace_facts() as traced:
            y, _ = jax.jit(
                lambda p, xx: moe.moe_block(p, xx, cfg, **FP32))(params, x[None])
        assert traced == {"moe_token_shards": 1}
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


class TestTokenShuffle:
    """token_shuffle_group_size (reference transformer.py:410-411): de-bias
    capacity drops from sequence position in the dropped path."""


    def test_permutation_is_bijective(self):
        from neuronx_distributed_training_tpu.ops.moe import _shuffle_permutation

        for t, g in ((64, 8), (48, 7), (5, 16), (1, 4)):
            p = np.asarray(_shuffle_permutation(t, g))
            assert sorted(p.tolist()) == list(range(t)), (t, g)

    def test_dropless_output_unchanged(self):
        """Shuffle is a dropped-path concept; dropless output is identical."""
        import dataclasses

        cfg = moe.MoEConfig(num_experts=4, top_k=2, dropless=True)
        params = moe.init_moe_params(jax.random.PRNGKey(0), 16, 32, cfg,
                                 dtype=jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16), jnp.float32)
        y0, _ = moe.moe_block(params, x, cfg, compute_dtype=jnp.float32)
        cfg2 = dataclasses.replace(cfg, token_shuffle_group_size=4)
        y1, _ = moe.moe_block(params, x, cfg2, compute_dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))

    def test_dropped_shuffle_debiases_position(self):
        """With tight capacity, unshuffled drops pile onto LATE positions;
        the stride shuffle spreads them across the sequence."""
        import dataclasses

        cfg = moe.MoEConfig(num_experts=2, top_k=1, dropless=False,
                        capacity_factor=0.5)
        params = moe.init_moe_params(jax.random.PRNGKey(0), 16, 32, cfg,
                                 dtype=jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 16), jnp.float32)

        def dropped_positions(c):
            y, _aux = moe.moe_block(params, x, c, compute_dtype=jnp.float32)
            # a dropped token passes through as exactly zero output
            return np.nonzero(np.all(np.asarray(y[0]) == 0.0, axis=-1))[0]

        base = dropped_positions(cfg)
        shuf = dropped_positions(
            dataclasses.replace(cfg, token_shuffle_group_size=8))
        assert len(base) > 0  # capacity 0.5 guarantees drops
        # same total drop budget (capacity unchanged)
        assert abs(len(base) - len(shuf)) <= 2
        # unshuffled: drops concentrate in the back half; shuffled: spread out
        assert np.mean(base) > 32
        assert np.mean(shuf) < np.mean(base)

    def test_shuffled_outputs_keep_token_alignment(self):
        """Kept tokens produce the same expert output with and without
        shuffle when nothing is dropped (capacity ample)."""
        import dataclasses

        cfg = moe.MoEConfig(num_experts=2, top_k=1, dropless=False,
                        capacity_factor=4.0)
        params = moe.init_moe_params(jax.random.PRNGKey(0), 16, 32, cfg,
                                 dtype=jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16), jnp.float32)
        y0, a0 = moe.moe_block(params, x, cfg, compute_dtype=jnp.float32)
        y1, a1 = moe.moe_block(
            params, x, dataclasses.replace(cfg, token_shuffle_group_size=4),
            compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(a0["expert_idx"]),
                                      np.asarray(a1["expert_idx"]))


def test_the_sigmoid_routes_renormalising_epsilon_is_a_field_of_the_config():
    """``route`` adds ``cfg.renorm_eps`` to the chosen scores' sum: 1e-20 where
    nothing sets it (DeepSeek-V3's, the route ``models/kanana.py`` runs), what
    a family sets otherwise (``models/lfm2.py``: 1e-6).  No YAML key reads it,
    and the softmax routes never see it."""
    import dataclasses

    from neuronx_distributed_training_tpu.ops import moe as moe_ops

    cfg = moe_ops.MoEConfig(num_experts=8, top_k=2, score_func="sigmoid")
    assert cfg.renorm_eps == 1e-20
    assert moe_ops.MoEConfig.from_config({"renorm_eps": 1.0, "scoring_func": "sigmoid"}
                                         ).renorm_eps == 1e-20
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (32, 16))
    router = {"w": jax.random.normal(jax.random.fold_in(key, 1), (16, 8)),
              "bias": jnp.zeros((8,))}
    probs, idx, _ = moe_ops.route(router, x, cfg)
    big, big_idx, _ = moe_ops.route(router, x, dataclasses.replace(cfg, renorm_eps=0.5))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(big_idx))    # selection: untouched
    scores = jnp.take_along_axis(jax.nn.sigmoid(x @ router["w"]), idx, axis=-1)
    total = scores.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(probs), np.asarray(scores / total), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(big), np.asarray(scores / (total + 0.5)), rtol=1e-6)
    soft = moe_ops.MoEConfig(num_experts=8, top_k=2)
    a, _, _ = moe_ops.route({"w": router["w"]}, x, soft)
    b, _, _ = moe_ops.route({"w": router["w"]}, x, dataclasses.replace(soft, renorm_eps=0.5))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_experts_function_is_a_field_of_the_config(held=(2, 6)):
    """``expert_act``: ``swiglu`` where nothing sets it (what every accepted
    family runs); ``relu2`` (``models/nemotron_h.py``): the leaf ``gate_up`` is
    the up matrix alone and the block, forward and the hand-written backward of
    the held path, is ``down(relu(up x)^2)`` gate-weighted, the shared expert
    the same function (all experts held: tests/test_nemotron_h.py).  No YAML
    key reads it."""
    import dataclasses

    from neuronx_distributed_training_tpu.ops import moe as moe_ops

    base = moe_ops.MoEConfig(num_experts=8, top_k=2, score_func="sigmoid", experts_held=held)
    assert base.expert_act == "swiglu"
    assert moe_ops.MoEConfig.from_config({"expert_act": "relu2"}).expert_act == "swiglu"
    cfg = dataclasses.replace(base, expert_act="relu2")
    key = jax.random.PRNGKey(0)
    params = moe_ops.init_moe_params(key, 16, 12, cfg, stddev=0.5)
    n = cfg.experts_resident
    assert params["experts"]["gate_up"].shape == (n, 16, 12)
    assert moe_ops.init_moe_params(key, 16, 12, base)["experts"]["gate_up"].shape == (n, 16, 24)
    params["shared"] = {"gate_up": {"w": 0.5 * jax.random.normal(jax.random.fold_in(key, 1), (16, 20))},
                        "down": {"w": 0.5 * jax.random.normal(jax.random.fold_in(key, 2), (20, 16))}}
    x = jax.random.normal(jax.random.fold_in(key, 3), (2, 8, 16))

    def dense(params, x):
        flat = x.reshape(-1, 16)
        probs, idx, _ = moe_ops.route(params["router"], flat, cfg)
        gates = jnp.zeros((flat.shape[0], 8)).at[jnp.arange(flat.shape[0])[:, None], idx].set(probs)
        lo, hi = held or (0, 8)
        every = jnp.einsum("tef,efh->teh", jnp.square(jax.nn.relu(
            jnp.einsum("th,ehf->tef", flat, params["experts"]["gate_up"]))),
            params["experts"]["down"])
        shared = (jnp.square(jax.nn.relu(flat @ params["shared"]["gate_up"]["w"]))
                  @ params["shared"]["down"]["w"])
        return (jnp.einsum("te,teh->th", gates[:, lo:hi], every) + shared).reshape(x.shape)

    def block(params, x):
        return moe_ops.moe_block(params, x, cfg, compute_dtype=jnp.float32)[0]

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(block(params, x)), np.asarray(dense(params, x)),
                                   rtol=1e-5, atol=1e-5)
        loss = lambda f: (lambda p, x: jnp.sum(jnp.sin(f(p, x))))  # noqa: E731
        got = jax.grad(loss(block), argnums=(0, 1))(params, x)
        want = jax.grad(loss(dense), argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_widths_that_are_no_whole_ragged_columns_go_through_the_tiled_kernels(monkeypatch):
    """Where a width of the grouped dots is over ``_RAGGED_COLS`` and no whole
    multiple of it, and the rows are whole tiles, the held experts' rows go
    through megablox's ``gmm`` / ``tgmm`` (interpret mode here), nothing
    padded: outputs and every cotangent equal XLA's ragged dots', with rows
    past the groups' count in the operand; other shapes stay with XLA."""
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    t, h, f, e, top, rows = 512, 384, 320, 6, 2, 1024
    assert moe._tiles(moe._GMM_TILES, rows, h, f) == (256, 384, 384)
    assert moe._tiles(moe._TGMM_TILES, rows, f, h) == (512, 384, 384)
    assert moe._tiles(moe._GMM_TILES, rows, 2048, 1536) is None     # XLA's fast path
    assert moe._tiles(moe._GMM_TILES, rows, 64, 48) is None         # toy widths
    assert moe._tiles(moe._GMM_TILES, rows + 8, h, f) is None       # no whole tiles of rows
    x = jax.random.normal(k[0], (t, h), jnp.float32)
    probs = jax.nn.softmax(jax.random.normal(k[1], (t, top)), axis=-1)
    idx = jax.random.randint(k[2], (t, top), 0, e)
    gu_w = jax.random.normal(k[3], (e - 2, h, f), jnp.float32) * 0.1
    down_w = jax.random.normal(k[4], (e - 2, f, h), jnp.float32) * 0.1
    # experts 4 and 5 lie elsewhere: their rows sort last and are left out
    order, sizes = moe._sorted_rows(jnp.minimum(idx.reshape(-1), e - 2), e - 2, rows)

    @jax.jit
    def both_passes(x, probs, gu_w, down_w):
        with jax.default_matmul_precision("highest"):
            y, kept = moe._expert_rows(x, probs, order, sizes, gu_w, down_w, k=top,
                                       count=jnp.sum(sizes), act=moe._relu2)
            return y, moe._expert_rows_back(
                jnp.cos(y), kept, x, probs, order, sizes, gu_w, down_w, k=top,
                count=jnp.sum(sizes), grad_dtype=jnp.float32, act=moe._relu2)

    tiled = both_passes(x, probs, gu_w, down_w)
    monkeypatch.setattr(moe, "_RAGGED_COLS", 1 << 20)
    jax.clear_caches()
    for a, b in zip(jax.tree_util.tree_leaves(tiled),
                    jax.tree_util.tree_leaves(both_passes(x, probs, gu_w, down_w))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4 * float(jnp.max(jnp.abs(b))))
