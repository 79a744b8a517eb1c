"""auto_parallel: ProcessMesh, shard_tensor, Engine (reference analog:
python/paddle/fluid/tests/unittests/auto_parallel/). Runs on the 8-device
CPU mesh from conftest."""
import numpy as np
import pytest
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.distributed import (ProcessMesh, shard_tensor, reshard,
                                    unshard_dtensor, get_dist_attr)
from paddle_tpu.distributed.auto_parallel import Engine, Strategy


def make_mesh():
    n = len(jax.devices())
    return ProcessMesh(np.arange(n).reshape(2, n // 2),
                       dim_names=["x", "y"])


def test_process_mesh_basics():
    pm = make_mesh()
    assert pm.ndim == 2
    assert pm.dim_names == ["x", "y"]
    assert pm.get_dim_size("x") == 2
    jm = pm.jax_mesh()
    assert jm.axis_names == ("x", "y")
    assert pm == make_mesh()
    assert len({pm, make_mesh()}) == 1


def test_shard_tensor_places_data():
    pm = make_mesh()
    x = paddle.to_tensor(np.arange(32, dtype=np.float32).reshape(8, 4))
    sx = shard_tensor(x, pm, ["x", None])
    spec = sx._value.sharding.spec
    assert tuple(spec)[0] == "x"
    attr = get_dist_attr(sx)
    assert attr[0] == pm and attr[1] == ["x", None]
    # values unchanged
    np.testing.assert_allclose(np.asarray(sx._value), np.arange(32).reshape(8, 4))


def test_shard_tensor_context_mesh_and_reshard():
    pm = make_mesh()
    with pm:
        x = shard_tensor(paddle.ones([8, 8]), shard_spec=["x", "y"])
    assert get_dist_attr(x)[1] == ["x", "y"]
    y = reshard(x, pm, ["y", None])
    assert tuple(y._value.sharding.spec)[0] == "y"
    z = unshard_dtensor(y)
    assert z._value.sharding.is_fully_replicated
    np.testing.assert_allclose(z.numpy(), np.ones((8, 8)))


def test_shard_tensor_bad_axis():
    pm = make_mesh()
    with pytest.raises(ValueError):
        shard_tensor(paddle.ones([4]), pm, ["nope"])


def test_shard_tensor_under_jit_constraint():
    pm = make_mesh()

    def f(v):
        t = paddle.Tensor(v, stop_gradient=True)
        s = shard_tensor(t, pm, ["x", None])
        return (s * 2)._value

    out = jax.jit(f)(np.ones((8, 4), np.float32))
    np.testing.assert_allclose(np.asarray(out), 2 * np.ones((8, 4)))


def test_engine_fit_and_evaluate():
    paddle.seed(0)
    n = len(jax.devices())
    pm = ProcessMesh(np.arange(n), dim_names=["data"])

    class DS(paddle.io.Dataset):
        def __init__(self):
            rng = np.random.default_rng(0)
            self.x = rng.standard_normal((64, 8)).astype(np.float32)
            w = rng.standard_normal((8, 1)).astype(np.float32)
            self.y = self.x @ w

        def __getitem__(self, i):
            return self.x[i], self.y[i]

        def __len__(self):
            return 64

    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=model.parameters())
    engine = Engine(model, loss=nn.MSELoss(), optimizer=opt,
                    strategy=Strategy(), process_mesh=pm)
    hist = engine.fit(DS(), epochs=3, batch_size=16, verbose=0)
    assert hist["loss"][-1] < hist["loss"][0]
    res = engine.evaluate(DS(), batch_size=16)
    assert res["loss"] is not None and np.isfinite(res["loss"])


def test_engine_tp_annotation():
    """Megatron-style col/row sharding annotated via shard_tensor; GSPMD
    completes the rest (reference: dist_matmul rules)."""
    paddle.seed(0)
    n = len(jax.devices())
    pm = ProcessMesh(np.arange(n).reshape(1, n), dim_names=["data", "model"])

    model = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 1))
    # column-parallel first weight, row-parallel second
    shard_tensor(model[0].weight, pm, [None, "model"])
    shard_tensor(model[2].weight, pm, ["model", None])
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=model.parameters())

    class DS(paddle.io.Dataset):
        def __init__(self):
            rng = np.random.default_rng(1)
            self.x = rng.standard_normal((32, 8)).astype(np.float32)
            self.y = self.x.sum(-1, keepdims=True).astype(np.float32)

        def __getitem__(self, i):
            return self.x[i], self.y[i]

        def __len__(self):
            return 32

    st = Strategy({"dataset": {"batch_dim": "data"}})
    engine = Engine(model, loss=nn.MSELoss(), optimizer=opt, strategy=st,
                    process_mesh=pm)
    hist = engine.fit(DS(), epochs=4, batch_size=32, verbose=0)
    assert hist["loss"][-1] < hist["loss"][0]
    # the parameter kept its annotation through training
    assert tuple(model[0].weight._value.sharding.spec)[-1] == "model"


class TestMeshPlanner:
    """Cost-model mesh planner (reference analog: auto_parallel
    planner_v2.py + cost_model.py)."""

    def _stats(self):
        from paddle_tpu.distributed.auto_parallel import gpt_stats
        from paddle_tpu.incubate.models import gpt3_6p7b
        return gpt_stats(gpt3_6p7b())

    def test_small_model_prefers_pure_dp(self):
        from paddle_tpu.distributed.auto_parallel import (plan_mesh,
                                                          ModelStats)
        st = ModelStats(n_params=10_000_000, n_layers=12, hidden=768,
                        seq_len=512)
        best = plan_mesh(st, n_devices=8, batch=64, hbm_bytes=16e9)[0]
        assert best.feasible
        assert best.mp == 1 and best.pp == 1   # no model parallel needed

    def test_big_model_needs_model_parallelism(self):
        from paddle_tpu.distributed.auto_parallel import plan_mesh
        ranked = plan_mesh(self._stats(), n_devices=64, batch=64,
                           hbm_bytes=16e9)
        best = ranked[0]
        assert best.feasible, best.rationale
        # 6.7B bf16 + f32 AdamW state cannot fit replicated in 16 GB
        assert best.mp * best.pp * best.sharding > 1
        assert best.dp * best.mp * best.pp * best.sharding == 64

    def test_memory_infeasible_plans_ranked_out(self):
        from paddle_tpu.distributed.auto_parallel import plan_mesh
        ranked = plan_mesh(self._stats(), n_devices=8, batch=8,
                           hbm_bytes=16e9)
        for c in ranked:
            if c.feasible:
                # every feasible plan really fits
                assert c.mem_bytes <= 16e9
        # the fully replicated layout must be infeasible for 6.7B
        rep = [c for c in plan_mesh(self._stats(), 8, 8, hbm_bytes=16e9)
               if c.mp == c.pp == c.sharding == 1]
        assert not rep or not rep[0].feasible

    def test_pp_requires_divisible_layers(self):
        from paddle_tpu.distributed.auto_parallel import (plan_mesh,
                                                          ModelStats)
        st = ModelStats(n_params=1_000_000, n_layers=7, hidden=64)
        for c in plan_mesh(st, n_devices=8, batch=8):
            assert c.pp == 1 or 7 % c.pp == 0


class TestCompletion:
    """Parameter-graph sharding completion from PARTIAL annotations
    (reference analog: auto_parallel/completion.py propagating DistAttrs;
    here Megatron pairing over the parameter graph, GSPMD finishing the
    intermediates)."""

    def _mesh(self):
        n = len(jax.devices())
        return ProcessMesh(np.arange(n).reshape(1, n),
                           dim_names=["data", "model"])

    def test_column_mark_completes_row_partner(self):
        from paddle_tpu.distributed.auto_parallel import \
            complete_model_sharding
        paddle.seed(0)
        pm = self._mesh()
        model = nn.Sequential(nn.Linear(8, 32), nn.GELU(),
                              nn.Linear(32, 8), nn.LayerNorm(8))
        # the ONLY user annotation: column-parallel first weight
        shard_tensor(model[0].weight, pm, [None, "model"])
        decisions = complete_model_sharding(model, pm)
        # bias of the column linear follows the axis
        assert tuple(model[0].bias._value.sharding.spec) == ("model",)
        # the next linear completes ROW-parallel
        assert tuple(model[2].weight._value.sharding.spec)[0] == "model"
        # its bias and the LayerNorm complete replicated
        for p in [model[2].bias, model[3].weight, model[3].bias]:
            spec = p._value.sharding.spec
            assert all(s is None for s in spec), spec
        assert len(decisions) == len(list(model.parameters()))

    def test_completion_idempotent_on_annotated(self):
        from paddle_tpu.distributed.auto_parallel import \
            complete_model_sharding
        paddle.seed(0)
        pm = self._mesh()
        model = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 8))
        shard_tensor(model[0].weight, pm, [None, "model"])
        shard_tensor(model[2].weight, pm, ["model", None])
        complete_model_sharding(model, pm)
        assert tuple(model[0].weight._value.sharding.spec)[-1] == "model"
        assert tuple(model[2].weight._value.sharding.spec)[0] == "model"

    def test_engine_fit_with_partial_annotation_matches_full(self):
        """Engine.fit on a NON-GPT model where only the first weight is
        annotated: completion must produce the same training trajectory as
        the fully-annotated Megatron layout."""
        def run(annotate_all):
            paddle.seed(0)
            n = len(jax.devices())
            pm = ProcessMesh(np.arange(n).reshape(1, n),
                             dim_names=["data", "model"])
            model = nn.Sequential(nn.Linear(8, 32), nn.ReLU(),
                                  nn.Linear(32, 1))
            shard_tensor(model[0].weight, pm, [None, "model"])
            if annotate_all:
                shard_tensor(model[0].bias, pm, ["model"])
                shard_tensor(model[2].weight, pm, ["model", None])

            class DS(paddle.io.Dataset):
                def __init__(self):
                    rng = np.random.default_rng(1)
                    self.x = rng.standard_normal((32, 8)).astype(np.float32)
                    self.y = self.x.sum(-1, keepdims=True).astype(np.float32)

                def __getitem__(self, i):
                    return self.x[i], self.y[i]

                def __len__(self):
                    return 32

            opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                        parameters=model.parameters())
            st = Strategy({"dataset": {"batch_dim": "data"}})
            engine = Engine(model, loss=nn.MSELoss(), optimizer=opt,
                            strategy=st, process_mesh=pm)
            hist = engine.fit(DS(), epochs=3, batch_size=32, verbose=0)
            return hist["loss"], model

        partial_losses, pmodel = run(annotate_all=False)
        full_losses, _ = run(annotate_all=True)
        np.testing.assert_allclose(partial_losses, full_losses,
                                   rtol=1e-5, atol=1e-6)
        assert partial_losses[-1] < partial_losses[0]
        # completion actually placed the row partner
        assert tuple(pmodel[2].weight._value.sharding.spec)[0] == "model"


class TestPlannerValidation:
    """The planner's analytic ordering vs MEASURED step times on the
    virtual mesh (relative ordering, not absolute;
    the virtual CPU mesh timeshares cores, so only well-separated pairs are
    asserted)."""

    @pytest.mark.slow
    def test_planner_ordering_matches_measured(self):
        """2 configs x 1 round (~1-2 min on a loaded box): a wall-time
        measurement, so it lives in the opt-in slow tier — with the
        formerly shard_map-blocked SPMD suites now running, tier-1 has no
        budget for in-suite benchmarking. The full validation (3 configs x
        2 interleaved rounds, ~10 min) is the variant below."""
        self._planner_ordering(full=False)

    @pytest.mark.slow
    def test_planner_ordering_matches_measured_full(self):
        """Opt-in: `pytest -m slow` (deselected by default via addopts)."""
        self._planner_ordering(full=True)

    def _planner_ordering(self, full):
        import time
        import jax.numpy as jnp
        from paddle_tpu.distributed.auto_parallel import (plan_mesh,
                                                          gpt_stats)
        from paddle_tpu.distributed.mesh import build_mesh, set_global_mesh
        from paddle_tpu.distributed.fleet.meta_parallel import \
            PipelineTrainStep
        from paddle_tpu.incubate.models import (
            GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
            gpt_pipeline_layers, shard_gpt)
        from paddle_tpu.jit import TrainStep

        # compute-dominant workload: the virtual mesh cannot price real ICI
        # traffic, so the validation regime is one where both the analytic
        # model and the measurement agree compute/overheads dominate
        cfg = GPTConfig(vocab_size=256, hidden_size=64, num_hidden_layers=4,
                        num_attention_heads=4, intermediate_size=128,
                        max_position_embeddings=512, hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0,
                        use_flash_attention=False)
        batch, seq, steps = 32, 512, 2
        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(0, 256, (batch, seq)), jnp.int32)
        labels = jnp.asarray(rng.integers(0, 256, (batch, seq)), jnp.int32)

        def measure(dp, mp, pp):
            mesh = build_mesh(dp=dp, pp=pp, sharding=1, sep=1, mp=mp,
                              devices=jax.devices()[:8])
            set_global_mesh(mesh)
            paddle.seed(0)
            model = GPTForCausalLM(cfg)
            if mp > 1:
                shard_gpt(model, mesh)
            opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                         parameters=model.parameters())
            crit = GPTPretrainingCriterion()
            if pp > 1:
                step = PipelineTrainStep(gpt_pipeline_layers(model), crit,
                                         opt, mesh=mesh, num_microbatches=pp)
            else:
                step = TrainStep(model, lambda o, y: crit(o, y), opt)
            x = paddle.Tensor(ids, stop_gradient=True)
            y = paddle.Tensor(labels, stop_gradient=True)
            float(step(x, y))                 # compile
            t0 = time.perf_counter()
            for _ in range(steps):
                l = step(x, y)
            float(l)
            return (time.perf_counter() - t0) / steps

        configs = [(8, 1, 1), (2, 4, 1), (4, 1, 2)] if full else \
            [(8, 1, 1), (2, 4, 1)]
        # min over interleaved rounds: a CPU burst during one config's
        # window (CI contention) must not poison its estimate
        measured = {c: measure(*c) for c in configs}
        if full:
            for c in configs:
                measured[c] = min(measured[c], measure(*c))

        stats = gpt_stats(cfg, seq_len=seq)
        ranked = plan_mesh(stats, n_devices=8, batch=batch,
                           micro_batches=2)
        cost = {(c.dp, c.mp, c.pp): c.cost for c in ranked}
        planned = {c: cost[c] for c in configs}

        # argmin agreement: the planner picks the config that actually
        # measures fastest — asserted only when the measurement is
        # decisive (>1.3x over the runner-up) so scheduler noise on the
        # timeshared CPU mesh can't flip the test
        best_measured = min(measured, key=measured.get)
        best_planned = min(planned, key=planned.get)
        runner_up = sorted(measured.values())[1]
        if runner_up > 2.0 * measured[best_measured]:
            assert best_planned == best_measured, (measured, planned)
        # pairwise agreement wherever the measured separation is decisive
        # (2x: anything tighter is scheduler noise on a timeshared mesh)
        for a in configs:
            for b in configs:
                if measured[a] > 2.0 * measured[b]:
                    assert planned[a] > planned[b], \
                        (a, b, measured, planned)


class TestCompletionEdgeCases:
    """Regressions from review: short shard_specs, user-pinned replication
    closing the Megatron pair, and annotation-mesh preference."""

    def test_short_spec_annotation_pads(self):
        from paddle_tpu.distributed.auto_parallel import \
            complete_model_sharding
        paddle.seed(0)
        n = len(jax.devices())
        pm = ProcessMesh(np.arange(n).reshape(1, n),
                         dim_names=["data", "model"])
        model = nn.Sequential(nn.Linear(8, 16), nn.Linear(16, 8))
        # spec shorter than ndim — shard_tensor accepts it; completion
        # must pad, not crash
        shard_tensor(model[0].weight, pm, ["model"])
        complete_model_sharding(model, pm)
        assert tuple(model[0].weight._value.sharding.spec)[0] == "model"

    def test_pinned_replication_closes_pair(self):
        from paddle_tpu.distributed.auto_parallel import \
            complete_model_sharding
        paddle.seed(0)
        n = len(jax.devices())
        pm = ProcessMesh(np.arange(n).reshape(1, n),
                         dim_names=["data", "model"])
        model = nn.Sequential(nn.Linear(8, 16), nn.Linear(16, 16),
                              nn.Linear(16, 8))
        shard_tensor(model[0].weight, pm, [None, "model"])   # column mark
        shard_tensor(model[1].weight, pm, [None, None])      # user pin
        complete_model_sharding(model, pm)
        # the pinned layer closed the pair: layer 2 completes REPLICATED,
        # the carried axis must not leak onto it
        spec = tuple(model[2].weight._value.sharding.spec)
        assert all(s is None for s in spec), spec

    def test_engine_uses_annotation_mesh(self):
        """Engine built WITHOUT process_mesh while the marks reference a
        2-D mesh: completion must run on the annotations' mesh, not the
        Engine's 1-D fallback."""
        paddle.seed(0)
        n = len(jax.devices())
        pm = ProcessMesh(np.arange(n).reshape(1, n),
                         dim_names=["data", "model"])
        model = nn.Sequential(nn.Linear(8, 32), nn.ReLU(),
                              nn.Linear(32, 1))
        shard_tensor(model[0].weight, pm, [None, "model"])

        class DS(paddle.io.Dataset):
            def __init__(self):
                rng = np.random.default_rng(1)
                self.x = rng.standard_normal((16, 8)).astype(np.float32)
                self.y = self.x.sum(-1, keepdims=True).astype(np.float32)

            def __getitem__(self, i):
                return self.x[i], self.y[i]

            def __len__(self):
                return 16

        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=model.parameters())
        engine = Engine(model, loss=nn.MSELoss(), optimizer=opt)
        hist = engine.fit(DS(), epochs=2, batch_size=16, verbose=0)
        assert np.isfinite(hist["loss"][-1])
        assert tuple(model[2].weight._value.sharding.spec)[0] == "model"


class TestCompletionPatterns:
    """Completion beyond Linear/Embedding pairs: fused-qkv attention,
    conv channel pairing, MoE expert banks (round-4 verdict item 5)."""

    def _mesh(self):
        n = len(jax.devices())
        return ProcessMesh(np.arange(n).reshape(1, n),
                           dim_names=["data", "model"])

    def test_fused_qkv_attention_completes_head_parallel(self):
        from paddle_tpu.distributed.auto_parallel import \
            complete_model_sharding
        from paddle_tpu.incubate.nn import FusedMultiHeadAttention
        paddle.seed(0)
        pm = self._mesh()
        attn = FusedMultiHeadAttention(embed_dim=64, num_heads=8)
        # the ONLY mark: qkv_weight [3, H, D, h] on the heads dim
        shard_tensor(attn.qkv_weight, pm, [None, "model", None, None])
        complete_model_sharding(attn, pm)
        assert tuple(attn.qkv_bias._value.sharding.spec)[1] == "model"
        # out projection completes ROW-parallel
        assert tuple(attn.linear_weight._value.sharding.spec)[0] == "model"
        for p in [attn.linear_bias, attn.ln_scale, attn.ln_bias]:
            assert all(s is None for s in p._value.sharding.spec)

    def test_fused_ffn_completes_row_partner(self):
        from paddle_tpu.distributed.auto_parallel import \
            complete_model_sharding
        from paddle_tpu.incubate.nn import FusedFeedForward
        paddle.seed(0)
        pm = self._mesh()
        ffn = FusedFeedForward(d_model=16, dim_feedforward=64)
        shard_tensor(ffn.linear1_weight, pm, [None, "model"])
        complete_model_sharding(ffn, pm)
        assert tuple(ffn.linear1_bias._value.sharding.spec) == ("model",)
        assert tuple(ffn.linear2_weight._value.sharding.spec)[0] == "model"
        assert all(s is None
                   for s in ffn.linear2_bias._value.sharding.spec)

    def test_conv_tower_channel_pairing(self):
        from paddle_tpu.distributed.auto_parallel import \
            complete_model_sharding
        paddle.seed(0)
        pm = self._mesh()
        model = nn.Sequential(nn.Conv2D(3, 16, 3), nn.ReLU(),
                              nn.Conv2D(16, 8, 3))
        # mark the FIRST conv out-channel-parallel
        shard_tensor(model[0].weight, pm, ["model", None, None, None])
        complete_model_sharding(model, pm)
        assert tuple(model[0].bias._value.sharding.spec) == ("model",)
        # next conv completes IN-channel-sharded (dim 1), closing the pair
        spec2 = tuple(model[2].weight._value.sharding.spec)
        assert spec2[1] == "model" and spec2[0] is None
        assert all(s is None for s in model[2].bias._value.sharding.spec)

    def test_conv_tower_forward_matches_replicated(self):
        """The completed channel-pair placement must be numerically
        invisible: GSPMD inserts the psum."""
        from paddle_tpu.distributed.auto_parallel import \
            complete_model_sharding
        paddle.seed(0)
        model = nn.Sequential(nn.Conv2D(3, 16, 3), nn.ReLU(),
                              nn.Conv2D(16, 8, 3))
        x = paddle.to_tensor(
            np.random.default_rng(0).normal(size=(2, 3, 12, 12))
            .astype(np.float32))
        ref = model(x).numpy()
        pm = self._mesh()
        shard_tensor(model[0].weight, pm, ["model", None, None, None])
        complete_model_sharding(model, pm)
        np.testing.assert_allclose(model(x).numpy(), ref,
                                   rtol=2e-5, atol=2e-5)

    def test_moe_expert_bank_completes_on_expert_axis(self):
        from paddle_tpu.distributed.auto_parallel import \
            complete_model_sharding
        from paddle_tpu.incubate.distributed.models.moe import MoELayer
        paddle.seed(0)
        pm = self._mesh()
        moe = MoELayer(d_model=16, d_hidden=32, num_experts=8,
                       moe_axis="model")
        # one mark: w1 [E, d, ff] on the expert dim
        shard_tensor(moe.w1, pm, ["model", None, None])
        complete_model_sharding(moe, pm)
        for p in [moe.b1, moe.w2, moe.b2]:
            assert tuple(p._value.sharding.spec)[0] == "model", p.name
        # the gate stays replicated
        assert all(s is None
                   for s in moe.gate_weight._value.sharding.spec)


class TestMeasuringTuner:
    """Reference analog: auto_parallel/tuner/parallel_tuner.py — the tuner
    must pick the MEASURED best, not the analytic best. (Also hosts three
    completion-regression tests appended from review findings.)"""

    def _mesh(self):
        n = len(jax.devices())
        return ProcessMesh(np.arange(n).reshape(1, n),
                           dim_names=["data", "model"])

    def test_measurement_overrides_analytic_rank(self):
        """When the injected measurements say analytic rank-2 is faster,
        the tuner chooses it."""
        from paddle_tpu.distributed.auto_parallel import (gpt_stats,
                                                          tune_mesh)
        from paddle_tpu.incubate.models import GPTConfig
        cfg = GPTConfig(vocab_size=256, hidden_size=64,
                        num_hidden_layers=4, num_attention_heads=4,
                        intermediate_size=128, max_position_embeddings=128)
        stats = gpt_stats(cfg, seq_len=128)
        ranked_order = []

        def fake_measure(choice):
            ranked_order.append(choice)
            # rank-2 (the second candidate trialed) measures fastest
            return 0.5 if len(ranked_order) == 2 else 1.0

        report = tune_mesh(stats, n_devices=8, batch=32,
                           measure_fn=fake_measure, top_k=3)
        assert len(report.candidates) == 3
        second = report.candidates[1]
        assert (report.best.dp, report.best.mp, report.best.pp,
                report.best.sharding) == (second.dp, second.mp,
                                          second.pp, second.sharding)
        assert report.measurement_changed_plan

    def test_agreement_keeps_analytic_best(self):
        from paddle_tpu.distributed.auto_parallel import (gpt_stats,
                                                          tune_mesh)
        from paddle_tpu.incubate.models import GPTConfig
        cfg = GPTConfig(vocab_size=256, hidden_size=64,
                        num_hidden_layers=4, num_attention_heads=4,
                        intermediate_size=128, max_position_embeddings=128)
        stats = gpt_stats(cfg, seq_len=128)
        costs = iter([0.1, 0.5, 0.9])

        def fake_measure(choice):
            return next(costs)

        report = tune_mesh(stats, n_devices=8, batch=32,
                           measure_fn=fake_measure, top_k=3)
        assert not report.measurement_changed_plan

    def test_rounds_take_min(self):
        from paddle_tpu.distributed.auto_parallel import (gpt_stats,
                                                          tune_mesh)
        from paddle_tpu.incubate.models import GPTConfig
        cfg = GPTConfig(vocab_size=256, hidden_size=64,
                        num_hidden_layers=4, num_attention_heads=4,
                        intermediate_size=128, max_position_embeddings=128)
        stats = gpt_stats(cfg, seq_len=128)
        calls = {}

        def fake_measure(choice):
            k = (choice.dp, choice.mp, choice.pp, choice.sharding)
            calls[k] = calls.get(k, 0) + 1
            return 1.0 / calls[k]        # later rounds measure faster

        report = tune_mesh(stats, n_devices=8, batch=32,
                           measure_fn=fake_measure, top_k=2, rounds=2)
        assert all(v == 2 for v in calls.values())
        assert all(t == 0.5 for t in report.measured_s.values())

    def test_real_compile_and_time_top2(self):
        """End-to-end: the tuner compiles and times the top-2 plans of a
        tiny GPT on the live virtual mesh and returns a measured winner."""
        from paddle_tpu.distributed.auto_parallel import (gpt_stats,
                                                          tune_mesh,
                                                          gpt_measure_fn)
        from paddle_tpu.incubate.models import GPTConfig
        cfg = GPTConfig(vocab_size=128, hidden_size=32,
                        num_hidden_layers=2, num_attention_heads=4,
                        intermediate_size=64, max_position_embeddings=64,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0,
                        use_flash_attention=False)
        stats = gpt_stats(cfg, seq_len=64)
        report = tune_mesh(stats, n_devices=8, batch=16,
                           measure_fn=gpt_measure_fn(cfg, batch=16, seq=64,
                                                     steps=1),
                           top_k=2)
        assert len(report.measured_s) == 2
        assert all(t > 0 for t in report.measured_s.values())
        key = (report.best.dp, report.best.mp, report.best.pp,
               report.best.sharding)
        assert report.measured_s[key] == min(report.measured_s.values())

    def test_fused_ffn_square_dims_keep_norms_replicated(self):
        """d_model == dim_feedforward: ln params share linear1_bias's shape
        but must stay replicated (review regression)."""
        from paddle_tpu.distributed.auto_parallel import \
            complete_model_sharding
        from paddle_tpu.incubate.nn import FusedFeedForward
        paddle.seed(0)
        pm = self._mesh()
        ffn = FusedFeedForward(d_model=64, dim_feedforward=64)
        shard_tensor(ffn.linear1_weight, pm, [None, "model"])
        complete_model_sharding(ffn, pm)
        assert tuple(ffn.linear1_bias._value.sharding.spec) == ("model",)
        assert tuple(ffn.linear2_weight._value.sharding.spec)[0] == "model"
        for n, p in ffn.named_parameters():
            if "ln" in n or n.endswith("linear2_bias"):
                assert all(s is None for s in p._value.sharding.spec), n

    def test_moe_gate_replicated_when_dmodel_equals_experts(self):
        """d_model == num_experts: the gate's leading dim collides with E
        but must stay replicated (review regression)."""
        from paddle_tpu.distributed.auto_parallel import \
            complete_model_sharding
        from paddle_tpu.incubate.distributed.models.moe import MoELayer
        paddle.seed(0)
        pm = self._mesh()
        moe = MoELayer(d_model=8, d_hidden=32, num_experts=8,
                       moe_axis="model")
        shard_tensor(moe.w1, pm, ["model", None, None])
        complete_model_sharding(moe, pm)
        assert all(s is None
                   for s in moe.gate_weight._value.sharding.spec)
        assert tuple(moe.w2._value.sharding.spec)[0] == "model"

    def test_conv_transpose_channel_dims_swapped(self):
        """Conv2DTranspose stores [in_c, out_c, kh, kw]: an out-channel
        mark is dim 1 and the pairing must respect it."""
        from paddle_tpu.distributed.auto_parallel import \
            complete_model_sharding
        paddle.seed(0)
        pm = self._mesh()
        model = nn.Sequential(nn.Conv2DTranspose(3, 16, 3), nn.ReLU(),
                              nn.Conv2D(16, 8, 3))
        shard_tensor(model[0].weight, pm, [None, "model", None, None])
        complete_model_sharding(model, pm)
        assert tuple(model[0].bias._value.sharding.spec) == ("model",)
        spec2 = tuple(model[2].weight._value.sharding.spec)
        assert spec2[1] == "model" and spec2[0] is None

    def test_engine_tune_installs_winning_mesh(self):
        """Engine.tune trials plans and installs the measured winner's
        mesh for the next fit (reference Engine._tune analog)."""
        from paddle_tpu.distributed.auto_parallel import (Engine, Strategy,
                                                          gpt_stats)
        from paddle_tpu.incubate.models import GPTConfig
        cfg = GPTConfig(vocab_size=256, hidden_size=64,
                        num_hidden_layers=4, num_attention_heads=4,
                        intermediate_size=128, max_position_embeddings=128)
        stats = gpt_stats(cfg, seq_len=128)
        st = Strategy()
        st.tuning.enable = True
        engine = Engine(model=nn.Linear(4, 4), loss=nn.MSELoss(),
                        strategy=st)
        calls = []

        def fake_measure(choice):
            calls.append(choice)
            return 0.1 if len(calls) == 3 else 1.0   # 3rd candidate wins

        report = engine.tune(stats, batch=32, measure_fn=fake_measure,
                             n_devices=8)
        assert len(calls) == 3
        b = report.best
        third = report.candidates[2]
        assert (b.dp, b.mp, b.pp, b.sharding) == \
            (third.dp, third.mp, third.pp, third.sharding)
        pm = engine._process_mesh
        assert pm is not None
        assert int(np.prod(pm.shape)) == 8
        assert "model" in pm.dim_names
