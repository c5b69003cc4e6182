"""Distributed tests on the 8-device virtual CPU mesh (SURVEY §4: reference
uses multi-process localhost; our analogue is a real multi-device mesh in
one process — collectives actually execute)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


def _np(t):
    return np.asarray(t._value)


class TestMeshAndPlacement:
    def test_process_mesh_props(self):
        mesh = dist.ProcessMesh(shape=[2, 4], dim_names=["dp", "mp"])
        assert mesh.shape == [2, 4]
        assert mesh.get_dim_size("mp") == 4
        assert len(mesh.process_ids) == 8

    def test_shard_and_reshard_values(self):
        mesh = dist.ProcessMesh(shape=[8], dim_names=["x"])
        x = paddle.arange(0, 32, dtype="float32").reshape([8, 4])
        xs = dist.shard_tensor(x, mesh, [dist.Shard(0)])
        assert np.allclose(_np(xs), _np(x))
        xr = dist.reshard(xs, mesh, [dist.Replicate()])
        assert np.allclose(_np(xr), _np(x))
        # sharded compute produces correct global result
        y = paddle.sum(xs * 2)
        assert float(y) == float(paddle.sum(x * 2))

    def test_partial_placement_repr(self):
        p = dist.Partial()
        assert p.is_partial()
        s = dist.Shard(1)
        assert s.is_shard(1) and not s.is_shard(0)


class TestTopology:
    def test_communicate_topology(self):
        from paddle_tpu.distributed.fleet import CommunicateTopology
        topo = CommunicateTopology(["data", "pipe", "sharding", "sep", "model"],
                                   [2, 2, 1, 1, 2])
        assert topo.world_size() == 8
        coord = topo.get_coord(5)
        assert topo.get_rank(**coord) == 5
        groups = topo.get_comm_list("model")
        assert len(groups) == 4 and all(len(g) == 2 for g in groups)

    def test_hybrid_group(self):
        from paddle_tpu.distributed.fleet import (CommunicateTopology,
                                                  HybridCommunicateGroup)
        topo = CommunicateTopology(["data", "pipe", "sharding", "sep", "model"],
                                   [2, 1, 1, 1, 4])
        hcg = HybridCommunicateGroup(topo, rank=0)
        assert hcg.get_data_parallel_world_size() == 2
        assert hcg.get_model_parallel_world_size() == 4
        mesh = hcg.get_mesh()
        assert mesh.shape == [2, 1, 1, 1, 4]

    def test_fleet_init(self):
        from paddle_tpu.distributed import fleet
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4,
                                   "pp_degree": 1}
        fleet.init(is_collective=True, strategy=strategy)
        hcg = fleet.get_hybrid_communicate_group()
        assert hcg.get_model_parallel_world_size() == 4


class TestCollectivesCompiled:
    """Functional collectives inside shard_map over the 8-device mesh."""

    def test_psum_allgather(self):
        from jax.experimental.shard_map import shard_map
        mesh = dist.ProcessMesh(shape=[8], dim_names=["x"]).jax_mesh

        def f(x):
            s = jax.lax.psum(x, "x")
            g = jax.lax.all_gather(x, "x", tiled=True)
            return s, g

        xs = jnp.arange(8.0).reshape(8, 1)
        f_sharded = shard_map(f, mesh=mesh, in_specs=P("x", None),
                              out_specs=(P("x", None), P("x", None)))
        s, g = f_sharded(xs)
        assert np.allclose(np.asarray(s), 28.0)

    def test_fcollectives_through_tape(self):
        """fcollectives ops record on the tape; grad of psum is identity
        broadcast."""
        from jax.experimental.shard_map import shard_map
        from paddle_tpu.distributed import fcollectives as fc
        mesh = dist.ProcessMesh(shape=[8], dim_names=["x"]).jax_mesh

        def step(x):
            def inner(xv):
                return jax.lax.psum(xv * 2.0, "x")
            return shard_map(inner, mesh=mesh, in_specs=P("x"),
                             out_specs=P())(x)

        x = jnp.arange(8.0)
        out = step(x)
        assert float(np.asarray(out).reshape(())) == 2 * sum(range(8))
        g = jax.grad(lambda x: step(x).reshape(()))(x)
        assert np.allclose(np.asarray(g), 2.0)


class TestEagerCommAPI:
    def test_single_process_semantics(self):
        t = paddle.to_tensor([1.0, 2.0])
        dist.all_reduce(t)
        assert np.allclose(_np(t), [1, 2])
        out = []
        dist.all_gather(out, t)
        assert len(out) == 1
        g = dist.new_group([0])
        assert g.nranks == 1
        objs = []
        dist.all_gather_object(objs, {"a": 1})
        assert objs == [{"a": 1}]

    def test_reduce_scatter_local(self):
        t = paddle.zeros([2])
        dist.reduce_scatter(t, [paddle.ones([2]), paddle.ones([2])])
        assert np.allclose(_np(t), [2, 2])


class TestTPLayers:
    def _mesh(self):
        return dist.ProcessMesh(shape=[2, 4], dim_names=["dp", "mp"])

    def test_column_row_parallel_match_dense(self):
        paddle.seed(3)
        col = dist.fleet.ColumnParallelLinear(8, 16, has_bias=True,
                                              gather_output=False)
        row = dist.fleet.RowParallelLinear(16, 8, input_is_parallel=True)
        x = paddle.randn([4, 8])
        ref = F.linear(F.linear(x, col.weight, col.bias), row.weight, row.bias)
        # under mesh ctx with sharding hints
        from paddle_tpu.distributed.fleet.mp_layers import sharding_ctx
        with sharding_ctx(self._mesh().jax_mesh):
            out = row(col(x))
        assert np.allclose(_np(out), _np(ref), atol=1e-5)
        assert col.weight._dist_spec == (None, "mp")
        assert row.weight._dist_spec == ("mp", None)

    def test_vocab_parallel_embedding(self):
        emb = dist.fleet.VocabParallelEmbedding(100, 16)
        ids = paddle.to_tensor(np.array([[1, 5], [7, 99]]))
        out = emb(ids)
        assert out.shape == [2, 2, 16]
        assert emb.weight._dist_spec == ("mp", None)

    def test_parallel_cross_entropy(self):
        pce = dist.fleet.ParallelCrossEntropy()
        logits = paddle.randn([4, 10])
        labels = paddle.to_tensor(np.random.randint(0, 10, (4,)))
        loss = pce(logits, labels)
        ref = F.cross_entropy(logits, labels, reduction="none")
        assert np.allclose(_np(loss)[:, 0], _np(ref), atol=1e-5)

    def test_rng_tracker(self):
        tracker = dist.fleet.get_rng_state_tracker()
        tracker.reset()
        tracker.add("test_rng", 1234)
        with tracker.rng_state("test_rng"):
            a = paddle.randn([4])
        tracker.reset()
        tracker.add("test_rng", 1234)
        with tracker.rng_state("test_rng"):
            b = paddle.randn([4])
        assert np.allclose(_np(a), _np(b))


class TestSequenceParallelNumerics:
    """VERDICT weak #9: the Megatron-SP surface must be real — the
    Column/Row pair matches dense numerics under the seq-sharded layout,
    and the Row side's reduce-scatter is an ACTUAL reduce-scatter on the
    wire (GSPMD alone emitted all-reduce+slice, 2x the bytes)."""

    def _pair(self):
        from paddle_tpu.distributed.fleet.sequence_parallel import (
            ColumnSequenceParallelLinear, RowSequenceParallelLinear)
        paddle.seed(0)
        col = ColumnSequenceParallelLinear(16, 32, has_bias=True)
        row = RowSequenceParallelLinear(32, 16, has_bias=True)
        return col, row

    def test_sp_pair_matches_dense_and_uses_reduce_scatter(self):
        import re
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.distributed.fleet.mp_layers import sharding_ctx
        from paddle_tpu.distributed.fleet.sequence_parallel import scatter
        col, row = self._pair()
        mesh = dist.ProcessMesh(shape=[2, 4], dim_names=["dp", "mp"])
        x = paddle.randn([4, 8, 16])
        ref = F.linear(F.linear(x, col.weight, col.bias),
                       row.weight, row.bias)

        def f(xv):
            with sharding_ctx(mesh.jax_mesh):
                return row(col(scatter(Tensor(xv))))._value

        c = jax.jit(f).lower(x._value).compile()
        out = c(x._value)
        assert np.allclose(np.asarray(out), _np(ref), atol=1e-5)
        txt = c.as_text()
        assert re.search(r"reduce-scatter", txt)
        assert not re.search(r"all-reduce", txt)  # rs replaces ar+slice

    def test_sp_grads_flow(self):
        from paddle_tpu.distributed.fleet.mp_layers import sharding_ctx
        from paddle_tpu.distributed.fleet.sequence_parallel import scatter
        col, row = self._pair()
        mesh = dist.ProcessMesh(shape=[2, 4], dim_names=["dp", "mp"])
        x = paddle.randn([4, 8, 16])
        # dense reference grads
        ref_out = F.linear(F.linear(x, col.weight, col.bias),
                           row.weight, row.bias)
        (ref_out ** 2).mean().backward()
        g_ref = _np(row.weight.grad).copy()
        col.clear_gradients()
        row.clear_gradients()
        with sharding_ctx(mesh.jax_mesh):
            out = row(col(scatter(x)))
            (out ** 2).mean().backward()
        assert np.allclose(_np(row.weight.grad), g_ref, atol=1e-4)


class TestRecompute:
    def test_recompute_grads_match(self):
        from paddle_tpu.distributed.fleet import recompute
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 8))
        x = paddle.randn([4, 8])
        x.stop_gradient = False
        out1 = paddle.sum(net(x) ** 2)
        out1.backward()
        g_ref = [_np(p.grad) for p in net.parameters()]
        gx_ref = _np(x.grad)
        net.clear_gradients()
        x2 = paddle.to_tensor(_np(x), stop_gradient=False)
        out2 = paddle.sum(recompute(net, x2) ** 2)
        out2.backward()
        assert np.allclose(float(out1), float(out2), atol=1e-5)
        for p, g in zip(net.parameters(), g_ref):
            assert np.allclose(_np(p.grad), g, atol=1e-5)
        assert np.allclose(_np(x2.grad), gx_ref, atol=1e-5)


class TestShardingStages:
    def test_group_sharded_api(self):
        model = nn.Sequential(nn.Linear(64, 64), nn.ReLU(), nn.Linear(64, 8))
        opt = paddle.optimizer.AdamW(parameters=model.parameters())
        m2, o2, _ = dist.group_sharded_parallel(model, opt, "p_g_os")
        specs = [p._dist_spec for p in m2.parameters() if p.size >= 1024]
        assert any(s is not None and "sharding" in str(s) for s in specs)

    def test_stage1_partition_balanced(self):
        from paddle_tpu.distributed.fleet import DygraphShardingOptimizer
        model = nn.Sequential(*[nn.Linear(32, 32) for _ in range(4)])
        opt = paddle.optimizer.SGD(parameters=model.parameters())
        mapping = DygraphShardingOptimizer._partition_parameters(
            opt._parameter_list, 2)
        s0 = sum(p.size for p in mapping[0])
        s1 = sum(p.size for p in mapping[1])
        assert abs(s0 - s1) <= 32 * 32


class TestDistTrainStep:
    def test_dp_mp_train_step_matches_single(self):
        """The compiled hybrid step on a dp×mp mesh must match single-device
        SGD numerics."""
        paddle.seed(11)

        class TPNet(nn.Layer):
            def __init__(self):
                super().__init__()
                self.col = dist.fleet.ColumnParallelLinear(
                    16, 32, has_bias=True, gather_output=False)
                self.row = dist.fleet.RowParallelLinear(
                    32, 4, input_is_parallel=True)

            def forward(self, x):
                return self.row(F.relu(self.col(x)))

        def loss_fn(model, x, y):
            return F.cross_entropy(model(x), y)

        x = np.random.randn(8, 16).astype(np.float32)
        y = np.random.randint(0, 4, (8,))

        # single-device reference
        net1 = TPNet()
        opt1 = paddle.optimizer.SGD(learning_rate=0.1,
                                    parameters=net1.parameters())
        losses1 = []
        for _ in range(3):
            loss = loss_fn(net1, paddle.to_tensor(x), paddle.to_tensor(y))
            loss.backward()
            opt1.step()
            opt1.clear_grad()
            losses1.append(float(loss))

        # mesh step
        paddle.seed(11)
        net2 = TPNet()
        opt2 = paddle.optimizer.SGD(learning_rate=0.1,
                                    parameters=net2.parameters())
        mesh = dist.ProcessMesh(shape=[2, 4], dim_names=["dp", "mp"])
        dist.shard_model_state(net2, mesh)
        step = dist.DistTrainStep(net2, opt2, loss_fn, mesh, donate=False)
        losses2 = [float(step(paddle.to_tensor(x), paddle.to_tensor(y)))
                   for _ in range(3)]
        assert np.allclose(losses1, losses2, atol=1e-4), (losses1, losses2)
        for p1, p2 in zip(net1.parameters(), net2.parameters()):
            assert np.allclose(_np(p1), _np(p2), atol=1e-4)

    def test_fsdp_step_runs_sharded(self):
        model = nn.Sequential(nn.Linear(64, 128), nn.ReLU(),
                              nn.Linear(128, 8))
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        mesh = dist.ProcessMesh(shape=[8], dim_names=["sharding"])
        from paddle_tpu.distributed.fleet.sharding import apply_sharding_specs
        apply_sharding_specs(model, stage=3, min_size_to_shard=64)
        dist.shard_model_state(model, mesh)
        # params physically sharded
        w = model[0].weight
        assert "sharding" in str(w._value.sharding.spec)
        step = dist.DistTrainStep(
            model, opt,
            lambda m, a, b: F.cross_entropy(m(a), b), mesh, donate=False)
        x = paddle.randn([16, 64])
        y = paddle.to_tensor(np.random.randint(0, 8, (16,)))
        l0 = float(step(x, y))
        for _ in range(5):
            l = float(step(x, y))
        assert l < l0


class TestMoE:
    def test_moe_layer_forward_backward(self):
        d = 16
        experts = [nn.Sequential(nn.Linear(d, 32), nn.ReLU(),
                                 nn.Linear(32, d)) for _ in range(4)]
        moe = dist.fleet.MoELayer(d_model=d, experts=experts,
                                  gate={"type": "gshard", "top_k": 2})
        x = paddle.randn([2, 6, d])
        x.stop_gradient = False
        out = moe(x)
        assert out.shape == [2, 6, d]
        loss = paddle.sum(out ** 2) + moe.l_aux
        loss.backward()
        # gate + experts must receive gradient
        assert moe.gate.gate.weight.grad is not None
        assert experts[0][0].weight.grad is not None

    def test_moe_routes_tokens(self):
        """With an identity-ish single expert dominating, output is close to
        that expert's transform."""
        d = 8
        experts = [nn.Linear(d, d, bias_attr=False) for _ in range(2)]
        moe = dist.fleet.MoELayer(d_model=d, experts=experts, top_k=1,
                                  capacity_factor=4.0)
        # force router to expert 0
        gate_w = np.zeros((d, 2), np.float32)
        moe.gate.gate.weight.set_value(gate_w)
        moe.gate.gate.bias.set_value(np.array([100.0, -100.0], np.float32))
        x = paddle.randn([1, 4, d])
        out = moe(x)
        ref = F.linear(x, experts[0].weight)
        assert np.allclose(_np(out), _np(ref), atol=1e-4)

    def test_gshard_random_second_expert(self):
        """GShard gate: at train time the 2nd choice is kept with
        probability min(1, 2*g2) — a near-zero g2 must (almost) always be
        dropped, a dominant g2 kept."""
        from paddle_tpu.distributed.fleet.moe import GShardGate
        paddle.seed(0)
        gate = GShardGate(8, 4, topk=2)
        # logits with overwhelming expert 0, negligible everything else:
        # g2 ~ 0 -> drop mask ~ all True
        logits = np.full((64, 4), -20.0, np.float32)
        logits[:, 0] = 20.0
        drop = np.asarray(gate.second_expert_drop(logits, training=True))
        assert drop.mean() > 0.95
        # two equally strong experts: g2 = 0.5 -> 2*g2 = 1 -> never drop
        logits2 = np.full((64, 4), -20.0, np.float32)
        logits2[:, :2] = 20.0
        drop2 = np.asarray(gate.second_expert_drop(logits2, training=True))
        assert drop2.mean() < 0.05
        assert gate.second_expert_drop(logits, training=False) is None

    def test_switch_gate_train_jitter(self):
        from paddle_tpu.distributed.fleet.moe import SwitchGate
        paddle.seed(0)
        g = SwitchGate(8, 4, switch_eps=0.3)
        x = paddle.randn([16, 8])
        a = _np(g(x))
        b = _np(g(x))
        assert not np.allclose(a, b)  # jitter resampled per call
        g.eval()
        c = _np(g(x))
        d2 = _np(g(x))
        np.testing.assert_allclose(c, d2)


class TestSpmdPipeline:
    def test_pipeline_matches_sequential(self):
        """2-stage compiled pipeline over the pp axis == running both stages
        sequentially."""
        from jax.experimental.shard_map import shard_map
        from paddle_tpu.distributed.fleet.pipeline import spmd_pipeline
        n_stages, n_mb, mb, d = 2, 4, 3, 8
        mesh = dist.ProcessMesh(shape=[2], dim_names=["pp"]).jax_mesh
        rng = np.random.RandomState(0)
        w = rng.randn(n_stages, d, d).astype(np.float32) * 0.3
        x = rng.randn(n_mb, mb, d).astype(np.float32)

        def stage_fn(wi, xi):
            return jnp.tanh(xi @ wi[0])

        pipe = spmd_pipeline(stage_fn, n_stages, n_mb, axis_name="pp")
        f = shard_map(pipe, mesh=mesh, in_specs=(P("pp"), P()),
                      out_specs=P())
        out = np.asarray(f(jnp.asarray(w), jnp.asarray(x)))
        ref = np.tanh(np.tanh(x @ w[0]) @ w[1])
        assert np.allclose(out, ref, atol=1e-5)

    def test_pipeline_layer_segmentation(self):
        from paddle_tpu.distributed.fleet import LayerDesc, PipelineLayer
        descs = [LayerDesc(nn.Linear, 8, 8) for _ in range(6)]
        pp = PipelineLayer(descs, num_stages=3)
        assert pp.segment_parts == [0, 2, 4, 6]
        assert pp.get_stage_from_index(3) == 1
        out = pp(paddle.randn([2, 8]))
        assert out.shape == [2, 8]


class TestShardedCheckpoint:
    def test_save_load_reshard(self, tmp_path):
        mesh1 = dist.ProcessMesh(shape=[8], dim_names=["x"])
        model = nn.Linear(32, 16)
        model.weight._dist_spec = ("x", None)
        dist.shard_model_state(model, mesh1)
        ref = _np(model.weight)
        path = str(tmp_path / "ckpt")
        dist.save_state_dict(model.state_dict(), path)
        # perturb then reload with a DIFFERENT placement
        model.weight.set_value(np.zeros_like(ref))
        model.weight._dist_spec = (None, "x")
        dist.shard_model_state(model, mesh1)
        dist.load_state_dict(model.state_dict(), path)
        assert np.allclose(_np(model.weight), ref)

    def test_pdparams_suffix_forces_pickle_format(self, tmp_path):
        """The on-disk format is explicit by suffix (r5): .pdparams is
        always the host-pickle file, round-tripping even with orbax
        installed; a missing path raises FileNotFoundError, not a wrong
        'orbax artifact' diagnosis."""
        import os
        import pytest
        model = nn.Linear(4, 2)
        ref = _np(model.weight)
        path = str(tmp_path / "state.pdparams")
        dist.save_state_dict(model.state_dict(), path)
        assert os.path.isfile(path)          # a file, not an orbax dir
        model.weight.set_value(np.zeros_like(ref))
        dist.load_state_dict(model.state_dict(), path)
        assert np.allclose(_np(model.weight), ref)
        with pytest.raises(FileNotFoundError):
            dist.load_state_dict(model.state_dict(),
                                 str(tmp_path / "nope"))


class TestBaselineConfig4SFT:
    """BASELINE config 4 end to end: Qwen2 SFT under ZeRO-3 (GroupSharded
    Stage3 analogue) with cross-topology checkpoint reshard — train,
    snapshot, relaunch on a DIFFERENT mesh, resume, keep training."""

    def test_qwen2_zero3_sft_checkpoint_cross_topology(self, tmp_path):
        from paddle_tpu.distributed.fleet.sharding import (
            apply_sharding_specs)
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_loss_fn)
        ids = paddle.to_tensor(
            np.random.randint(0, 128, (4, 32), dtype=np.int32))

        # phase 1: mesh A (dp4 x mp2), ZeRO-3 over dp
        paddle.seed(8)
        m1 = LlamaForCausalLM("qwen2-debug")
        o1 = paddle.optimizer.AdamW(learning_rate=1e-3,
                                    parameters=m1.parameters())
        apply_sharding_specs(m1, stage=3, axis="dp", min_size_to_shard=64)
        meshA = dist.ProcessMesh(shape=[4, 1, 1, 1, 2],
                                 dim_names=["dp", "pp", "sep", "ep", "mp"])
        dist.shard_model_state(m1, meshA)
        step1 = dist.DistTrainStep(m1, o1, llama_loss_fn, meshA,
                                   donate=False)
        losses1 = [float(step1(ids, ids)) for _ in range(3)]
        assert losses1[-1] < losses1[0]
        path = str(tmp_path / "sft")
        state1 = {f"model.{k}": v for k, v in m1.state_dict().items()}
        for k, v in o1.state_dict().items():          # ZeRO-3's point:
            if hasattr(v, "_value"):                  # sharded moments
                state1[f"opt.{k}"] = v                # must survive too
        dist.save_state_dict(state1, path)
        w_ref = _np(m1._parameters["wq"])
        mom_ref = np.asarray(o1._accumulators["moment1"][0])

        # phase 2: fresh model on mesh B (dp2 x mp4) — reshard on load
        paddle.seed(99)  # different init proves the load works
        m2 = LlamaForCausalLM("qwen2-debug")
        o2 = paddle.optimizer.AdamW(learning_rate=1e-3,
                                    parameters=m2.parameters())
        apply_sharding_specs(m2, stage=3, axis="dp", min_size_to_shard=64)
        meshB = dist.ProcessMesh(shape=[2, 1, 1, 1, 4],
                                 dim_names=["dp", "pp", "sep", "ep", "mp"])
        dist.shard_model_state(m2, meshB)
        o2._ensure_state()
        state2 = {f"model.{k}": v for k, v in m2.state_dict().items()}
        opt_wrap = {}
        for k, v in o2.state_dict().items():
            if hasattr(v, "_value"):
                state2[f"opt.{k}"] = v
                opt_wrap[k] = v
        dist.load_state_dict(state2, path)
        o2.set_state_dict(opt_wrap)                   # wrappers -> slots
        np.testing.assert_allclose(_np(m2._parameters["wq"]), w_ref,
                                   atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(o2._accumulators["moment1"][0]), mom_ref,
            atol=1e-6)
        step2 = dist.DistTrainStep(m2, o2, llama_loss_fn, meshB,
                                   donate=False)
        l = float(step2(ids, ids))
        assert np.isfinite(l) and l < losses1[0]

    def test_ernie_moe_preset_trains(self):
        """BASELINE config 4's ERNIE-4.5 anchor: llama-family decoder
        with MoE FFN — debug-scale train step descends with the router
        aux loss in the objective."""
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_loss_fn)
        paddle.seed(0)
        m = LlamaForCausalLM("ernie-debug")
        o = paddle.optimizer.AdamW(learning_rate=3e-3,
                                   parameters=m.parameters())
        ids = paddle.to_tensor(
            np.random.randint(0, 128, (4, 32), dtype=np.int32))
        first = None
        for _ in range(3):
            loss = llama_loss_fn(m, ids, ids)
            if first is None:
                first = float(loss)
            loss.backward()
            o.step()
            o.clear_grad()
        assert float(loss) < first

    def test_shared_experts_active_and_trained(self):
        """VERDICT r3 #5: ERNIE-4.5/DeepSeekMoE shared experts — the
        always-on dense FFN beside the routed experts. The ernie preset
        now carries them; they must change the forward and receive
        gradients."""
        from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                             llama_loss_fn)
        paddle.seed(1)
        m = LlamaForCausalLM("ernie-debug")
        assert m.config.moe_num_shared_experts == 1
        assert any(n.endswith("ws_gate") for n, _ in m.named_parameters())
        ids = paddle.to_tensor(
            np.random.randint(0, 128, (2, 16), dtype=np.int32))
        loss = llama_loss_fn(m, ids, ids)
        loss.backward()
        grads = {n: p.grad for n, p in m.named_parameters()}
        for nm in ("ws_gate", "ws_up", "ws_down"):
            g = next(g for n, g in grads.items() if n.endswith(nm))
            assert g is not None and float(paddle.abs(g).sum()) > 0, nm
        # ablation: zeroing the shared experts changes the logits
        before = np.asarray(m(ids)._value)
        for n, p in m.named_parameters():
            if n.endswith(("ws_gate", "ws_up", "ws_down")):
                p._in_place_update(p._value * 0)
        after = np.asarray(m(ids)._value)
        assert not np.allclose(before, after)

    def test_dropless_matches_capacity_when_nothing_drops(self):
        """VERDICT r3 #5: dropless training (ragged grouped GEMMs via
        lax.ragged_dot). With capacity >= N*k the capacity path drops
        nothing, so both dispatches must agree; under a tight capacity
        they diverge (capacity really truncates) while dropless still
        serves every token."""
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        ids = np.random.randint(0, 128, (2, 16), dtype=np.int32)

        def build(dropless, cap=8.0):
            paddle.seed(3)
            cfg = dict(vocab_size=128, hidden_size=64,
                       intermediate_size=172, num_hidden_layers=2,
                       num_attention_heads=4, num_key_value_heads=2,
                       max_position_embeddings=256, num_experts=4,
                       num_experts_per_tok=2, moe_capacity_factor=cap,
                       moe_dropless=dropless)
            return LlamaForCausalLM(LlamaConfig(**cfg))

        out_cap = np.asarray(build(False)(paddle.to_tensor(ids))._value)
        out_drop = np.asarray(build(True)(paddle.to_tensor(ids))._value)
        np.testing.assert_allclose(out_drop, out_cap, atol=2e-4)
        out_tight = np.asarray(
            build(False, cap=0.3)(paddle.to_tensor(ids))._value)
        assert not np.allclose(out_tight, out_drop, atol=2e-4)


class TestBaselineConfig5MoE:
    def test_config5_presets_shapes(self):
        """BASELINE config-5 anchors exist as faithful presets: Mixtral
        8x7B (8 routed, top-2, wide experts) and DeepSeekMoE-16B (64
        routed + 2 shared, top-6, narrow experts)."""
        from paddle_tpu.models.llama import LLAMA_PRESETS, LlamaConfig
        mx = LlamaConfig(**LLAMA_PRESETS["mixtral-8x7b"])
        assert (mx.num_experts, mx.num_experts_per_tok,
                mx.moe_intermediate_size) == (8, 2, 14336)
        ds = LlamaConfig(**LLAMA_PRESETS["deepseek-moe-16b"])
        assert (ds.num_experts, ds.num_experts_per_tok,
                ds.moe_num_shared_experts,
                ds.moe_intermediate_size) == (64, 6, 2, 1408)
        # a scaled-down deepseek-shape model trains (same arch knobs)
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_loss_fn
        paddle.seed(0)
        # NB: adding this train loop originally tipped the suite into
        # an XLA-CPU-compiler segfault in LATER unrelated tests — the
        # cause turned out to be CUMULATIVE per-process compile pressure
        # (crash followed total compile count, not this test's shapes or
        # top_k), fixed structurally by pytest.ini's process sharding.
        # Lane-aligned dims kept anyway as good hygiene.
        tiny = LlamaConfig(**{**LLAMA_PRESETS["deepseek-moe-16b"],
                              "vocab_size": 128, "hidden_size": 64,
                              "intermediate_size": 176,
                              "num_hidden_layers": 2,
                              "num_attention_heads": 4,
                              "num_key_value_heads": 4,
                              "num_experts": 8, "num_experts_per_tok": 2,
                              "moe_intermediate_size": 48,
                              "max_position_embeddings": 256})
        m = LlamaForCausalLM(tiny)
        o = paddle.optimizer.AdamW(learning_rate=3e-3,
                                   parameters=m.parameters())
        ids = paddle.to_tensor(
            np.random.randint(0, 128, (4, 32), dtype=np.int32))
        first = None
        for _ in range(3):
            loss = llama_loss_fn(m, ids, ids)
            if first is None:
                first = float(loss)
            loss.backward()
            o.step()
            o.clear_grad()
        assert float(loss) < first

    def test_dropless_trains(self):
        """Dropless gradients flow through the ragged dispatch and the
        step descends."""
        from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                             llama_loss_fn)
        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=128, hidden_size=64,
                          intermediate_size=172, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=256, num_experts=4,
                          num_experts_per_tok=2, moe_dropless=True)
        m = LlamaForCausalLM(cfg)
        o = paddle.optimizer.AdamW(learning_rate=3e-3,
                                   parameters=m.parameters())
        ids = paddle.to_tensor(
            np.random.randint(0, 128, (4, 32), dtype=np.int32))
        first = None
        for _ in range(3):
            loss = llama_loss_fn(m, ids, ids)
            if first is None:
                first = float(loss)
            loss.backward()
            o.step()
            o.clear_grad()
        assert float(loss) < first


class TestZeroStage12:
    """ZeRO-1/2: optimizer state sharded over 'sharding' while params stay
    replicated (reference dygraph_sharding_optimizer.py:39,
    group_sharded_optimizer_stage2.py:53)."""

    def _run(self, stage):
        paddle.seed(33)
        model = nn.Sequential(nn.Linear(64, 64), nn.ReLU(),
                              nn.Linear(64, 8))
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=model.parameters())
        from paddle_tpu.distributed.fleet.sharding import apply_sharding_specs
        apply_sharding_specs(model, stage=stage)
        mesh = dist.ProcessMesh(shape=[2, 4], dim_names=["dp", "sharding"])
        dist.shard_model_state(model, mesh)
        step = dist.DistTrainStep(
            model, opt, lambda m, a, b: F.cross_entropy(m(a), b), mesh,
            donate=False)
        x = np.random.RandomState(5).randn(16, 64).astype(np.float32)
        y = np.random.RandomState(6).randint(0, 8, (16,))
        losses = [float(step(paddle.to_tensor(x), paddle.to_tensor(y)))
                  for _ in range(3)]
        return model, opt, losses

    def _reference(self):
        paddle.seed(33)
        model = nn.Sequential(nn.Linear(64, 64), nn.ReLU(),
                              nn.Linear(64, 8))
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=model.parameters())
        x = np.random.RandomState(5).randn(16, 64).astype(np.float32)
        y = np.random.RandomState(6).randint(0, 8, (16,))
        losses = []
        for _ in range(3):
            loss = F.cross_entropy(model(paddle.to_tensor(x)),
                                   paddle.to_tensor(y))
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        return model, losses

    @pytest.mark.parametrize("stage", [1, 2])
    def test_opt_state_sharded_param_replicated(self, stage):
        model, opt, _ = self._run(stage)
        w = model[0].weight  # 64x64 >= min_size_to_shard
        # param replicated
        assert "sharding" not in str(w._value.sharding.spec)
        # its moments sharded over the sharding axis
        idx = [id(p) for p in opt._parameter_list].index(id(w))
        m1 = opt._accumulators["moment1"][idx]
        assert "sharding" in str(m1.sharding.spec), m1.sharding
        m2 = opt._accumulators["moment2"][idx]
        assert "sharding" in str(m2.sharding.spec)

    @pytest.mark.parametrize("stage", [1, 2])
    def test_numeric_parity_vs_single_device(self, stage):
        ref_model, ref_losses = self._reference()
        model, _, losses = self._run(stage)
        assert np.allclose(ref_losses, losses, atol=1e-4), (ref_losses,
                                                            losses)
        for p1, p2 in zip(ref_model.parameters(), model.parameters()):
            assert np.allclose(_np(p1), _np(p2), atol=1e-4)

    def test_shard_optimizer_api(self):
        model = nn.Sequential(nn.Linear(64, 64))
        opt = paddle.optimizer.AdamW(parameters=model.parameters())
        opt = dist.shard_optimizer(opt)
        w = model[0].weight
        assert "sharding" in str(w._opt_shard_spec)


class TestSepAttention:
    """Ring / all-to-all attention over the sep axis (distributed/sep.py;
    SURVEY §5 long-context mandate — reference ships the sep axis with no
    library attention op, four_directions_p2p_communication.py)."""

    def _qkv(self, b=2, s=32, h=4, hkv=2, d=8):
        q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d),
                              jnp.float32)
        k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hkv, d),
                              jnp.float32)
        v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, d),
                              jnp.float32)
        return q, k, v

    @pytest.mark.parametrize("causal", [True, False])
    def test_ring_matches_gathered(self, causal):
        from paddle_tpu.distributed.sep import ring_attention
        from paddle_tpu.kernels.flash_attention import _sdpa_reference
        q, k, v = self._qkv()
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()).reshape(2, 4), ("dp", "sep"))
        ref = _sdpa_reference(q, k, v, causal)
        out = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, causal=causal, axis_name="sep", mesh=mesh))(q, k, v)
        assert np.allclose(out, ref, atol=1e-5)

    def test_ring_grads_match(self):
        from paddle_tpu.distributed.sep import ring_attention
        from paddle_tpu.kernels.flash_attention import _sdpa_reference
        q, k, v = self._qkv()
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()).reshape(2, 4), ("dp", "sep"))
        gr = jax.grad(lambda q, k, v: (_sdpa_reference(q, k, v, True) ** 2
                                       ).sum(), argnums=(0, 1, 2))(q, k, v)
        go = jax.jit(jax.grad(
            lambda q, k, v: (ring_attention(q, k, v, True, "sep", mesh) ** 2
                             ).sum(), argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(go, gr):
            assert np.allclose(a, b, atol=1e-4)

    def test_ulysses_matches_gathered(self):
        from paddle_tpu.distributed.sep import ulysses_attention
        from paddle_tpu.kernels.flash_attention import _sdpa_reference
        q, k, v = self._qkv()
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()).reshape(4, 2), ("dp", "sep"))
        ref = _sdpa_reference(q, k, v, True)
        out = jax.jit(lambda q, k, v: ulysses_attention(
            q, k, v, True, "sep", mesh))(q, k, v)
        assert np.allclose(out, ref, atol=1e-5)
        go = jax.jit(jax.grad(
            lambda q, k, v: (ulysses_attention(q, k, v, True, "sep",
                                               mesh) ** 2).sum(),
            argnums=(0, 1, 2)))(q, k, v)
        gr = jax.grad(lambda q, k, v: (_sdpa_reference(q, k, v, True) ** 2
                                       ).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(go, gr):
            assert np.allclose(a, b, atol=1e-4)

    def test_ulysses_rejects_indivisible_heads(self):
        from paddle_tpu.distributed.sep import ulysses_attention
        q, k, v = self._qkv(hkv=2)
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()).reshape(2, 4), ("dp", "sep"))
        with pytest.raises(ValueError, match="divisible"):
            ulysses_attention(q, k, v, True, "sep", mesh)

    def test_llama_forward_sep_sharded_matches_single(self):
        """Flagship integration: llama forward on a sep>1 mesh (ring
        attention path) matches the meshless forward."""
        from paddle_tpu.models.llama import LlamaForCausalLM
        paddle.seed(3)
        model = LlamaForCausalLM("debug")
        ids = paddle.to_tensor(
            np.random.randint(0, 128, (2, 32), dtype=np.int32))
        ref = _np(model(ids))
        mesh = dist.ProcessMesh(shape=[1, 1, 4, 1, 2],
                                dim_names=["dp", "pp", "sep", "ep", "mp"])
        dist.shard_model_state(model, mesh)
        from paddle_tpu.distributed.fleet.mp_layers import sharding_ctx
        with sharding_ctx(mesh.jax_mesh):
            out = _np(model(ids))
        assert np.allclose(out, ref, atol=1e-4), np.abs(out - ref).max()


class TestWrapperShardingVisibility:
    def test_zero_stage_seen_through_wrapper(self):
        """group_sharded_parallel returns a wrapper; DistTrainStep must
        still see the inner layer's stage (regression: stage-2 grad
        reduce-scatter was silently skipped for wrapped models)."""
        from paddle_tpu.distributed.fleet.sharding import (
            group_sharded_parallel)
        from paddle_tpu.distributed.parallelize import _resolve_zero_stage
        model = nn.Sequential(nn.Linear(64, 64))
        opt = paddle.optimizer.AdamW(parameters=model.parameters())
        wrapped, opt, _ = group_sharded_parallel(model, opt, "os_g")
        assert _resolve_zero_stage(wrapped) == 2


class TestPipelineParallelFlagship:
    """Real pipeline schedule wired into the flagship (VERDICT #3): when the
    mesh has pp>1, the decoder stack runs through spmd_pipeline inside
    shard_map (stage-local weights + microbatched ppermute), not
    scan-over-pp-sharded-weights."""

    def _mesh(self):
        return dist.ProcessMesh(shape=[2, 2, 1, 1, 2],
                                dim_names=["dp", "pp", "sep", "ep", "mp"])

    def test_forward_and_grads_match_single_device(self):
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_loss_fn
        from paddle_tpu.distributed.fleet.mp_layers import sharding_ctx
        paddle.seed(3)
        model = LlamaForCausalLM("debug")
        ids = paddle.to_tensor(
            np.random.randint(0, 128, (4, 32), dtype=np.int32))
        ref_out = _np(model(ids))
        mesh = self._mesh()
        dist.shard_model_state(model, mesh)
        with sharding_ctx(mesh.jax_mesh):
            out = _np(model(ids))
            loss = llama_loss_fn(model, ids, ids)
            loss.backward()
        assert np.allclose(out, ref_out, atol=1e-4)
        g_pp = {n: _np(p.grad) for n, p in model.named_parameters()
                if p.grad is not None}

        paddle.seed(3)
        ref = LlamaForCausalLM("debug")
        ref_loss = llama_loss_fn(ref, ids, ids)
        ref_loss.backward()
        assert abs(float(loss) - float(ref_loss)) < 1e-4
        for n, p in ref.named_parameters():
            if p.grad is None:
                continue
            assert np.allclose(g_pp[n], _np(p.grad), atol=1e-3), n

    def test_no_full_weight_allgather_in_hlo(self):
        """The pipelined program must not allgather the full stacked weight
        (that would be the FSDP-over-depth failure mode)."""
        import re
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.models.llama import LlamaForCausalLM
        from paddle_tpu.distributed.fleet.mp_layers import sharding_ctx
        paddle.seed(3)
        model = LlamaForCausalLM("debug")
        mesh = self._mesh()
        dist.shard_model_state(model, mesh)
        ids = np.random.randint(0, 128, (4, 32), dtype=np.int32)

        def f(ids_arr):
            with sharding_ctx(mesh.jax_mesh):
                return model(Tensor(ids_arr))._value

        txt = jax.jit(f).lower(jnp.asarray(ids)).compile().as_text()
        L = model.config.num_hidden_layers          # 2, pp-sharded to 1
        ff = model.config.intermediate_size
        # an all-gather producing a full [L, *, ff] stacked weight means
        # per-layer weight gathering; stage-local slices are [L/pp, ...]
        pat = re.compile(r"all-gather[^\n]*\[%d,\d+,%d\]" % (L, ff))
        assert not pat.search(txt), pat.search(txt).group(0)

    def test_dist_train_step_pp_matches_single(self):
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_loss_fn
        paddle.seed(5)
        ids = paddle.to_tensor(
            np.random.randint(0, 128, (4, 32), dtype=np.int32))

        ref = LlamaForCausalLM("debug")
        ropt = paddle.optimizer.SGD(learning_rate=0.1,
                                    parameters=ref.parameters())
        ref_losses = []
        for _ in range(3):
            loss = llama_loss_fn(ref, ids, ids)
            loss.backward()
            ropt.step()
            ropt.clear_grad()
            ref_losses.append(float(loss))

        paddle.seed(5)
        model = LlamaForCausalLM("debug")
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        mesh = self._mesh()
        dist.shard_model_state(model, mesh)
        step = dist.DistTrainStep(model, opt, llama_loss_fn, mesh,
                                  donate=False)
        losses = [float(step(ids, ids)) for _ in range(3)]
        assert np.allclose(ref_losses, losses, atol=1e-3), (ref_losses,
                                                            losses)


class TestPipelineScheduleV2:
    """Round-3 pipeline upgrades (VERDICT #1): interleaved virtual stages,
    remat-bounded activation memory, >pp default microbatches, and mp
    propagation inside the manual-pp region."""

    def test_interleave_parity_and_grads(self):
        """v=2 virtual stages on pp=2 must match the single-device model
        bit-for-bit at fp32 tolerances (forward, loss, and every grad)."""
        from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                             LLAMA_PRESETS, llama_loss_fn)
        from paddle_tpu.distributed.fleet.mp_layers import sharding_ctx
        paddle.seed(3)
        cfg = LlamaConfig(**LLAMA_PRESETS["tiny"])
        cfg.pp_interleave = 2
        model = LlamaForCausalLM(cfg)
        ids = paddle.to_tensor(
            np.random.randint(0, 1024, (4, 32), dtype=np.int32))
        ref_out = _np(model(ids))
        mesh = dist.ProcessMesh(shape=[2, 2, 1, 1, 2],
                                dim_names=["dp", "pp", "sep", "ep", "mp"])
        dist.shard_model_state(model, mesh)
        with sharding_ctx(mesh.jax_mesh):
            out = _np(model(ids))
            loss = llama_loss_fn(model, ids, ids)
            loss.backward()
        assert np.allclose(out, ref_out, atol=1e-4)
        g_pp = {n: _np(p.grad) for n, p in model.named_parameters()
                if p.grad is not None}
        paddle.seed(3)
        ref = LlamaForCausalLM(LlamaConfig(**LLAMA_PRESETS["tiny"]))
        ref_loss = llama_loss_fn(ref, ids, ids)
        ref_loss.backward()
        assert abs(float(loss) - float(ref_loss)) < 1e-4
        for n, p in ref.named_parameters():
            if p.grad is None:
                continue
            assert np.allclose(g_pp[n], _np(p.grad), atol=1e-3), n

    def test_remat_bounds_activation_memory(self):
        """jax.checkpoint around each chunk call must shrink the compiled
        temp footprint of the backward: without it every tick's stage
        internals stay live (unbounded in n_mb)."""
        from jax.sharding import Mesh
        from paddle_tpu.distributed.fleet.pipeline import spmd_pipeline
        pp, n_mb, mb, d = 2, 8, 4, 128
        devs = np.array(jax.devices()[:pp])
        mesh = Mesh(devs, ("pp",))
        params = jnp.ones((pp * 4, d, d), jnp.float32) * 0.01
        x = jnp.ones((n_mb, mb, d), jnp.float32)

        def stage_fn(sp, xm):
            def body(c, w):
                return jnp.tanh(c @ w), None
            out, _ = jax.lax.scan(body, xm, sp)
            return out

        def build(remat):
            apply = spmd_pipeline(stage_fn, pp, n_mb, axis_name="pp",
                                  remat=remat)
            sm = jax.shard_map(apply, mesh=mesh,
                               in_specs=(P("pp"), P()), out_specs=P(),
                               axis_names={"pp"})

            def loss(p, xx):
                return sm(p, xx).sum()

            return jax.jit(jax.grad(loss)).lower(params, x).compile()

        temp_remat = build(True).memory_analysis().temp_size_in_bytes
        temp_plain = build(False).memory_analysis().temp_size_in_bytes
        # the remat backward stores boundary activations only; the plain
        # backward stores every tick's scan internals as stacked residuals
        assert temp_remat < temp_plain * 0.7, (temp_remat, temp_plain)

    def test_grads_match_with_and_without_remat(self):
        from jax.sharding import Mesh
        from paddle_tpu.distributed.fleet.pipeline import spmd_pipeline
        pp, n_mb, mb, d = 2, 4, 2, 16
        mesh = Mesh(np.array(jax.devices()[:pp]), ("pp",))
        key = jax.random.PRNGKey(0)
        params = jax.random.normal(key, (pp * 2, d, d)) * 0.3
        x = jax.random.normal(jax.random.PRNGKey(1), (n_mb, mb, d))

        def stage_fn(sp, xm):
            def body(c, w):
                return jnp.tanh(c @ w), None
            out, _ = jax.lax.scan(body, xm, sp)
            return out

        grads = []
        for remat in (True, False):
            apply = spmd_pipeline(stage_fn, pp, n_mb, axis_name="pp",
                                  remat=remat)
            sm = jax.shard_map(apply, mesh=mesh,
                               in_specs=(P("pp"), P()), out_specs=P(),
                               axis_names={"pp"})
            grads.append(jax.jit(jax.grad(lambda p: sm(p, x).sum()))(params))
        np.testing.assert_allclose(np.asarray(grads[0]),
                                   np.asarray(grads[1]), atol=1e-5)

    def test_mp_is_manual_inside_pp_region(self):
        """VERDICT weak #6: GSPMD propagation does NOT shard mp activations
        inside the manual-pp region (measured: temps GROW with mp), so TP
        there is explicit Megatron SPMD — mp-local weight shards + psum
        over mp in _decoder_layer. Evidence: compiled temp bytes shrink
        ~proportionally when mp grows."""
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.distributed.fleet.mp_layers import sharding_ctx
        ids = np.random.randint(0, 1024, (8, 128), dtype=np.int32)

        def temp_bytes(mp):
            paddle.seed(3)
            cfg = LlamaConfig(vocab_size=1024, hidden_size=512,
                              intermediate_size=1376, num_hidden_layers=4,
                              num_attention_heads=8, num_key_value_heads=4)
            model = LlamaForCausalLM(cfg)
            mesh = dist.ProcessMesh(
                shape=[1, 2, 1, 1, mp],
                dim_names=["dp", "pp", "sep", "ep", "mp"])
            dist.shard_model_state(model, mesh)

            def f(ids_arr):
                with sharding_ctx(mesh.jax_mesh):
                    return model(Tensor(ids_arr))._value

            c = jax.jit(f).lower(jnp.asarray(ids)).compile()
            return c.memory_analysis().temp_size_in_bytes

        t1, t4 = temp_bytes(1), temp_bytes(4)
        assert t4 < t1 * 0.6, (t1, t4)

    def test_manual_mp_parity_inside_pp(self):
        """pp=2 x mp=2 manual TP must reproduce single-device numerics."""
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_loss_fn)
        from paddle_tpu.distributed.fleet.mp_layers import sharding_ctx
        paddle.seed(7)
        model = LlamaForCausalLM("tiny")
        ids = paddle.to_tensor(
            np.random.randint(0, 1024, (4, 32), dtype=np.int32))
        ref_out = _np(model(ids))
        mesh = dist.ProcessMesh(shape=[1, 2, 1, 1, 2],
                                dim_names=["dp", "pp", "sep", "ep", "mp"])
        dist.shard_model_state(model, mesh)
        with sharding_ctx(mesh.jax_mesh):
            out = _np(model(ids))
            loss = llama_loss_fn(model, ids, ids)
            loss.backward()
        assert np.allclose(out, ref_out, atol=1e-4)
        assert model._parameters["wq"].grad is not None

    def test_default_microbatches_above_pp(self):
        """VERDICT #1: default microbatch count must exceed pp when the
        batch allows (bubble (pp-1)/(n_mb+pp-1))."""
        from paddle_tpu.models.llama import LlamaConfig
        from paddle_tpu.distributed.fleet import pipeline as plmod
        cfg = LlamaConfig()
        assert cfg.pp_num_microbatches == 0  # auto
        # the auto rule: 2*pp when divisible (asserted indirectly through
        # interleave_permutation used by the schedule builder)
        perm = plmod.interleave_permutation(8, 2, 2)
        # rank 0 holds stages 0 and 2 (layers 0,1 + 4,5); rank 1 holds
        # stages 1 and 3 (layers 2,3 + 6,7)
        assert perm == [0, 1, 4, 5, 2, 3, 6, 7]

    def test_interleave_wrapper_sets_config(self):
        from paddle_tpu.models.llama import LlamaForCausalLM
        from paddle_tpu.distributed.fleet.meta_parallel import (
            PipelineParallelWithInterleave)
        model = LlamaForCausalLM("tiny")
        wrapped = PipelineParallelWithInterleave(
            model, num_virtual_pipeline_stages=2)
        assert model.config.pp_interleave == 2
        assert wrapped.virtual_pp_degree == 2

    def test_train_batch_returns_detached_loss(self):
        """VERDICT weak #8: the returned total must not pin the first
        microbatch's graph."""
        from paddle_tpu.distributed.fleet.meta_parallel import (
            PipelineParallel)
        model = nn.Sequential(nn.Linear(8, 8), nn.Linear(8, 1))
        model._loss_fn = lambda out, y: ((out - y) ** 2).mean()
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=model.parameters())

        class S:
            pipeline_configs = {"accumulate_steps": 2}
        pipe = PipelineParallel(model, strategy=S())
        x = paddle.to_tensor(np.random.randn(4, 8).astype("float32"))
        y = paddle.to_tensor(np.random.randn(4, 1).astype("float32"))
        total = pipe.train_batch((x, y), opt)
        assert total.stop_gradient  # detached
        # eval_batch honors compute_loss=False: concatenated outputs
        out = pipe.eval_batch((x, y), compute_loss=False)
        assert out.shape[0] == 4
        loss = pipe.eval_batch((x, y), compute_loss=True)
        assert loss.shape in ([], [1])


class TestStrategyDrivenCompilation:
    """VERDICT #8: DistributedStrategy knobs must ALTER the compiled
    DistTrainStep, not just be stored."""

    def _recipe(self):
        """A PaddleNLP-style llama recipe dict, used unmodified."""
        return {
            "dp_degree": 2, "mp_degree": 2, "pp_degree": 2,
            "amp": {"use_pure_fp16": False,
                    "custom_black_list": ["softmax"]},
            "recompute": {"granularity": "core_attn"},
            "gradient_merge": {"k_steps": 2, "avg": True},
            "pipeline": {"accumulate_steps": 4, "virtual_pp_degree": 2},
        }

    def _strategy(self, recipe):
        st = dist.fleet.DistributedStrategy()
        st.hybrid_configs = {**st.hybrid_configs,
                             "dp_degree": recipe["dp_degree"],
                             "mp_degree": recipe["mp_degree"],
                             "pp_degree": recipe["pp_degree"]}
        st.amp = True
        st.amp_configs.update(recipe["amp"])
        st.recompute = True
        st.recompute_configs.update(recipe["recompute"])
        st.gradient_merge = True
        st.gradient_merge_configs.update(recipe["gradient_merge"])
        st.pipeline = True
        st.pipeline_configs.update(recipe["pipeline"])
        return st

    def test_recipe_runs_and_steers_model_config(self):
        from paddle_tpu.models.llama import LlamaConfig, LLAMA_PRESETS, \
            LlamaForCausalLM, llama_loss_fn
        paddle.seed(2)
        cfg = LlamaConfig(**LLAMA_PRESETS["tiny"])
        model = LlamaForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        st = self._strategy(self._recipe())
        step = dist.DistTrainStep.from_strategy(
            model, opt, llama_loss_fn, st, donate=False)
        # knobs landed in the model config (observable compiled effects)
        assert cfg.recompute and cfg.recompute_granularity == "core_attn"
        assert cfg.pp_num_microbatches == 4
        assert cfg.pp_interleave == 2
        assert step.mesh.shape == [2, 2, 1, 1, 2]
        ids = paddle.to_tensor(
            np.random.randint(0, 1024, (8, 32), dtype=np.int32))
        l1 = float(step(ids, ids))
        l2 = float(step(ids, ids))
        assert np.isfinite(l1) and l2 < l1

    def test_gradient_merge_matches_manual_accumulation(self):
        """k_steps=2 inside the jitted step == two manual half-batch
        backwards with averaged grads + one update."""
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_loss_fn
        mesh = dist.ProcessMesh(shape=[1, 1, 1, 1, 1],
                                dim_names=["dp", "pp", "sep", "ep", "mp"])
        ids = paddle.to_tensor(
            np.random.randint(0, 128, (4, 16), dtype=np.int32))

        paddle.seed(5)
        ref = LlamaForCausalLM("debug")
        ropt = paddle.optimizer.SGD(learning_rate=0.1,
                                    parameters=ref.parameters())
        for sl in (slice(0, 2), slice(2, 4)):
            sub = paddle.to_tensor(np.asarray(ids._value)[sl])
            (llama_loss_fn(ref, sub, sub) / 2).backward()
        ropt.step()
        ropt.clear_grad()

        paddle.seed(5)
        model = LlamaForCausalLM("debug")
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        st = dist.fleet.DistributedStrategy()
        st.gradient_merge = True
        st.gradient_merge_configs.update({"k_steps": 2, "avg": True})
        step = dist.DistTrainStep(model, opt, llama_loss_fn, mesh,
                                  donate=False, strategy=st)
        step(ids, ids)
        for (n, p), (_, rp) in zip(model.named_parameters(),
                                   ref.named_parameters()):
            assert np.allclose(_np(p), _np(rp), atol=1e-5), n

    def test_amp_knob_changes_compiled_dtypes(self):
        """strategy.amp must put bf16 matmuls into the compiled program."""
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_loss_fn
        mesh = dist.ProcessMesh(shape=[1, 1, 1, 1, 1],
                                dim_names=["dp", "pp", "sep", "ep", "mp"])
        ids = paddle.to_tensor(
            np.random.randint(0, 128, (2, 16), dtype=np.int32))

        def lowered_text(amp_on):
            paddle.seed(5)
            model = LlamaForCausalLM("debug")
            opt = paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=model.parameters())
            st = dist.fleet.DistributedStrategy()
            st.amp = amp_on
            step = dist.DistTrainStep(model, opt, llama_loss_fn, mesh,
                                      donate=False, strategy=st)
            step(ids, ids)
            return step._jitted.lower(
                [p._value for p in step._params],
                [b._value for b in step._buffers],
                {k: list(v) for k, v in opt._accumulators.items()},
                jax.random.PRNGKey(0), jnp.asarray(0, jnp.int32),
                jnp.asarray(0.1, jnp.float32),
                (ids._value, ids._value)).as_text()

        assert "bf16" in lowered_text(True)
        assert "bf16" not in lowered_text(False)

    def test_inert_knob_warns_once(self):
        """VERDICT r3 weak #8: a stored-but-unconsumed knob set to a
        non-default value produces a one-time warning when the strategy
        is applied; consumed knobs never warn."""
        st = dist.fleet.DistributedStrategy()
        st.pipeline = True
        st.pipeline_configs = {"accumulate_steps": 2,
                               "schedule_mode": "FThenB"}
        st.sharding = True
        st.sharding_configs = {"stage": 2, "optimize_offload": True}
        with pytest.warns(RuntimeWarning, match="NOT consumed") as rec:
            st._warn_inert_knobs()
        msg = str(rec[0].message)
        assert "pipeline_configs.schedule_mode" in msg
        assert "sharding_configs.optimize_offload" in msg
        assert "accumulate_steps" not in msg
        import warnings as _w
        with _w.catch_warnings(record=True) as again:
            _w.simplefilter("always")
            st._warn_inert_knobs()
        assert not again

        clean = dist.fleet.DistributedStrategy()
        clean.gradient_merge = True
        clean.gradient_merge_configs = {"k_steps": 2}
        with _w.catch_warnings(record=True) as none:
            _w.simplefilter("always")
            clean._warn_inert_knobs()
        assert not none

    def test_proto_surface_accepts_reference_recipe_keys(self):
        st = dist.fleet.DistributedStrategy()
        # a sample of proto fields reference recipes set
        st.amp_configs["use_dynamic_loss_scaling"] = False
        st.sharding_configs["sharding_segment_strategy"] = "segment_anchors"
        st.pipeline_configs["enable_partial_send_recv"] = False
        st.hybrid_configs["pp_configs"]["dp_comm_overlap"] = True
        st.downpour_table_param["accessor"]["embedx_dim"] = 16
        st.trainer_desc_configs["dump_fields"] = ["loss"]
        assert st.hybrid_configs["pp_configs"]["dp_comm_overlap"]


class TestPipelineSepComposition:
    def test_pp_sep_mp_ring_inside_pipeline(self):
        """pp>1 + sep>1 + mp>1 (VERDICT weak #6 closed): the sequence
        stays SHARDED inside the manual-pp region and attention runs the
        ring body over the sep axis — forward, loss, and grads must match
        the single-device model."""
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_loss_fn)
        from paddle_tpu.distributed.fleet.mp_layers import sharding_ctx
        paddle.seed(4)
        model = LlamaForCausalLM("debug")
        ids = paddle.to_tensor(
            np.random.randint(0, 128, (4, 32), dtype=np.int32))
        ref_out = _np(model(ids))
        mesh = dist.ProcessMesh(shape=[1, 2, 2, 1, 2],
                                dim_names=["dp", "pp", "sep", "ep", "mp"])
        dist.shard_model_state(model, mesh)
        with sharding_ctx(mesh.jax_mesh):
            out = _np(model(ids))
            loss = llama_loss_fn(model, ids, ids)
            loss.backward()
        assert np.allclose(out, ref_out, atol=1e-4)
        g = {n: _np(p.grad) for n, p in model.named_parameters()
             if p.grad is not None}
        paddle.seed(4)
        ref = LlamaForCausalLM("debug")
        rl = llama_loss_fn(ref, ids, ids)
        rl.backward()
        assert abs(float(loss) - float(rl)) < 1e-4
        for n, p in ref.named_parameters():
            if p.grad is None:
                continue
            assert np.allclose(g[n], _np(p.grad), atol=1e-3), n

    def test_pp_sep_moe_runs(self):
        """pp x sep with MoE layers: local-per-shard routing + pp aux
        accumulation compiles and produces a finite loss."""
        from paddle_tpu.models.llama import (LlamaConfig, LLAMA_PRESETS,
                                             LlamaForCausalLM,
                                             llama_loss_fn)
        from paddle_tpu.distributed.fleet.mp_layers import sharding_ctx
        paddle.seed(6)
        model = LlamaForCausalLM(LlamaConfig(**LLAMA_PRESETS["tiny-moe"]))
        ids = paddle.to_tensor(
            np.random.randint(0, 1024, (4, 32), dtype=np.int32))
        mesh = dist.ProcessMesh(shape=[1, 2, 2, 1, 2],
                                dim_names=["dp", "pp", "sep", "ep", "mp"])
        dist.shard_model_state(model, mesh)
        with sharding_ctx(mesh.jax_mesh):
            loss = llama_loss_fn(model, ids, ids)
        assert np.isfinite(float(loss))


@pytest.mark.slow  # multi-process subprocess harnesses (tier-1 filters
class TestLaunchCLI:  # -m 'not slow'; run explicitly with -m slow)
    def test_two_process_rendezvous_and_comm(self, tmp_path):
        """VERDICT #7: python -m paddle_tpu.distributed.launch spawns per
        -host workers with PADDLE_TRAINER_* env; 2-process CPU rendezvous
        exercises every eager cross-host collective incl. send/recv and
        batch_isend_irecv (reference launch/main.py:18,
        test_parallel_dygraph_dataparallel.py:157 harness)."""
        import subprocess, sys, os
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "launch_worker.py")
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--log_dir", str(tmp_path), worker],
            cwd=root, capture_output=True, text=True, timeout=170)
        assert r.returncode == 0, r.stdout + r.stderr
        log1 = (tmp_path / "workerlog.1").read_text()
        assert "COMM_OK" in log1, log1

    def test_three_process_subgroup_collectives(self, tmp_path):
        """VERDICT #7: a 2-of-3 eager subgroup allreduce (+ broadcast /
        all_to_all / reduce_scatter) over the per-group KV namespace —
        the non-member rank is never blocked."""
        import subprocess, sys, os
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "launch_worker_subgroup.py")
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "3", "--log_dir", str(tmp_path), worker],
            cwd=root, capture_output=True, text=True, timeout=170)
        assert r.returncode == 0, r.stdout + r.stderr
        for i in range(3):
            log = (tmp_path / f"workerlog.{i}").read_text()
            assert "SUBGROUP_OK" in log, (i, log)

    def test_two_process_compiled_gspmd_parity(self, tmp_path):
        """VERDICT r3 #2: compiled GSPMD collectives ACROSS a process
        boundary. The same worker runs (a) single-process on 8 local CPU
        devices and (b) 2 processes × 4 CPU devices under the launch CLI
        sharing ONE 8-device mesh via jax.distributed — a DistTrainStep
        with dp×mp + ZeRO-2 must produce identical losses. This is the
        one-process-per-host shape of a real v5p pod (reference
        test_parallel_dygraph_dataparallel.py:157)."""
        import json, subprocess, sys, os
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "launch_worker_gspmd.py")

        def losses_from(text, tag="GSPMD_LOSSES "):
            for line in text.splitlines():
                if line.startswith(tag):
                    return json.loads(line[len(tag):])
            raise AssertionError(f"no {tag!r} in:\n{text}")

        env = dict(os.environ, GSPMD_LOCAL_DEVICES="8",
                   PYTHONPATH=root)
        single = subprocess.run([sys.executable, worker], cwd=root,
                                env=env, capture_output=True, text=True,
                                timeout=170)
        assert single.returncode == 0, single.stdout + single.stderr
        ref = losses_from(single.stdout)

        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--log_dir", str(tmp_path), worker],
            cwd=root, capture_output=True, text=True, timeout=170)
        assert r.returncode == 0, r.stdout + r.stderr
        ref_local = losses_from(single.stdout, "GSPMD_LOSSES_LOCAL ")
        np.testing.assert_allclose(ref_local, ref, rtol=1e-6)
        for i in range(2):
            text = (tmp_path / f"workerlog.{i}").read_text()
            np.testing.assert_allclose(losses_from(text), ref, rtol=1e-6)
            np.testing.assert_allclose(
                losses_from(text, "GSPMD_LOSSES_LOCAL "), ref, rtol=1e-6)

    def test_launch_propagates_failure(self, tmp_path):
        import subprocess, sys
        bad = tmp_path / "bad.py"
        bad.write_text("import sys; sys.exit(3)\n")
        import os
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--log_dir", str(tmp_path), str(bad)],
            cwd=root, capture_output=True, text=True, timeout=120)
        assert r.returncode == 3


class TestCheckNanInf:
    def test_eager_raises(self):
        paddle.set_flags({"check_nan_inf": True})
        try:
            with pytest.raises(FloatingPointError):
                paddle.log(paddle.to_tensor([-1.0]))
        finally:
            paddle.set_flags({"check_nan_inf": False})

    def test_jit_safe(self):
        """Under a trace the check must not crash tracing (VERDICT weak #8:
        bool() on a tracer raised TracerBoolConversionError); it reports
        at runtime via debug callback."""
        paddle.set_flags({"check_nan_inf": True})
        try:
            from paddle_tpu.core.tensor import Tensor

            def f(x):
                return paddle.exp(Tensor(x))._value

            out = jax.jit(f)(jnp.zeros((2,)))  # finite: no error
            assert np.allclose(np.asarray(out), 1.0)
            with pytest.raises(Exception):
                jax.block_until_ready(jax.jit(f)(jnp.full((2,), 1e30)))
        finally:
            paddle.set_flags({"check_nan_inf": False})


class TestAutoCheckpoint:
    """VERDICT #10: async orbax save + TTL auto-checkpoint keyed to the
    elastic store; relaunch resumes from the last COMPLETE snapshot."""

    @pytest.mark.slow  # two full subprocess train runs
    def test_kill_and_relaunch_resumes_step(self, tmp_path):
        import subprocess, sys, os
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        worker = os.path.join(root, "tests", "autockpt_worker.py")
        # first run crashes hard at step 6 (after the step-6 snapshot)
        r1 = subprocess.run([sys.executable, worker, str(tmp_path), "6"],
                            capture_output=True, text=True, timeout=170,
                            cwd=root)
        assert r1.returncode == 101, r1.stdout + r1.stderr
        assert "RESUMED_AT 0" in r1.stdout
        # relaunch: must resume from the recorded step (6) and finish
        r2 = subprocess.run([sys.executable, worker, str(tmp_path), "-1"],
                            capture_output=True, text=True, timeout=170,
                            cwd=root)
        assert r2.returncode == 0, r2.stdout + r2.stderr
        assert "RESUMED_AT 6" in r2.stdout, r2.stdout
        assert "DONE 10" in r2.stdout

    def test_auto_checkpoint_records_only_complete_snapshots(self, tmp_path):
        from paddle_tpu.distributed.checkpoint import AutoCheckpoint
        from paddle_tpu.distributed.fleet.elastic import FileKVStore
        paddle.seed(1)
        model = nn.Linear(4, 2)
        store = FileKVStore(str(tmp_path / "store"))
        auto = AutoCheckpoint("m", model, save_dir=str(tmp_path / "ck"),
                              store=store, every_n_steps=1)
        assert auto.resume() == 0          # fresh start
        auto.step(1)
        auto.wait()
        rec = store.get("ptpu_ckpt/m")
        assert rec and rec["step"] == 1
        # mutate weights, resume, weights restored
        w0 = _np(model.weight).copy()
        with paddle.no_grad():
            model.weight.fill_(123.0)
        assert auto.resume() == 1
        np.testing.assert_allclose(_np(model.weight), w0, atol=1e-6)

    def test_adam_moments_and_scheduler_survive_relaunch(self, tmp_path):
        """Optimizer slots restore through set_state_dict into the LIVE
        accumulators (fresh wrappers from state_dict() don't reach them),
        and the LR scheduler state rides the KV record."""
        from paddle_tpu.distributed.checkpoint import AutoCheckpoint
        from paddle_tpu.distributed.fleet.elastic import FileKVStore
        store = FileKVStore(str(tmp_path / "store"))

        def make():
            paddle.seed(3)
            m = nn.Linear(4, 2)
            sched = paddle.optimizer.lr.StepDecay(learning_rate=0.1,
                                                  step_size=2)
            o = paddle.optimizer.Adam(learning_rate=sched,
                                      parameters=m.parameters())
            return m, o

        m1, o1 = make()
        x = paddle.to_tensor(np.random.randn(4, 4).astype("float32"))
        for _ in range(3):
            (m1(x) ** 2).mean().backward()
            o1.step()
            o1.clear_grad()
            o1._lr_scheduler.step()
        auto1 = AutoCheckpoint("adam", m1, optimizer=o1,
                               save_dir=str(tmp_path / "ck"), store=store,
                               every_n_steps=1)
        auto1.step(3)
        auto1.wait()
        mom = np.asarray(o1._accumulators["moment1"][0])

        # fresh process analogue: new model + optimizer, resume
        m2, o2 = make()
        auto2 = AutoCheckpoint("adam", m2, optimizer=o2,
                               save_dir=str(tmp_path / "ck"), store=store,
                               every_n_steps=1)
        assert auto2.resume() == 3
        np.testing.assert_allclose(
            np.asarray(o2._accumulators["moment1"][0]), mom, atol=1e-7)
        assert o2._global_step == 3
        assert o2._lr_scheduler.last_epoch == o1._lr_scheduler.last_epoch

    def test_gc_keeps_last_snapshots(self, tmp_path):
        import os
        from paddle_tpu.distributed.checkpoint import AutoCheckpoint
        from paddle_tpu.distributed.fleet.elastic import FileKVStore
        model = nn.Linear(4, 2)
        store = FileKVStore(str(tmp_path / "store"))
        auto = AutoCheckpoint("m", model, save_dir=str(tmp_path / "ck"),
                              store=store, every_n_steps=1, keep_last=2)
        for s in (1, 2, 3, 4):
            auto.step(s)
            auto.wait()
        kept = sorted(d for d in os.listdir(str(tmp_path / "ck"))
                      if d.startswith("step_"))
        assert kept == ["step_3", "step_4"], kept

    def test_hapi_callback_resumes(self, tmp_path):
        from paddle_tpu.distributed.fleet.elastic import FileKVStore
        from paddle_tpu.hapi.callbacks import AutoCheckpointCallback
        import paddle_tpu.hapi as hapi

        class DS:
            def __len__(self):
                return 16

            def __getitem__(self, i):
                x = np.full((8,), float(i % 4), np.float32)
                return x, x[:1]

        store = FileKVStore(str(tmp_path / "store"))

        def run():
            paddle.seed(0)
            net = nn.Linear(8, 1)
            model = hapi.Model(net)
            model.prepare(paddle.optimizer.SGD(
                learning_rate=0.01, parameters=net.parameters()),
                nn.MSELoss())
            cb = AutoCheckpointCallback("h", every_n_steps=2,
                                        save_dir=str(tmp_path / "ck"),
                                        store=store)
            model.fit(DS(), batch_size=8, epochs=1, callbacks=[cb],
                      verbose=0)
            return cb

        cb1 = run()
        assert cb1.start_step == 0
        cb2 = run()                       # second fit resumes from store
        assert cb2.start_step > 0
        # resumed fit must SKIP completed steps, not double-train
        assert cb2._global_step == cb1._global_step


class TestReshardTaxonomy:
    """Reshard-function taxonomy (SURVEY item 16; reference
    phi/core/distributed/auto_parallel/*_reshard_function.cc: r_to_s,
    s_to_r, s_to_s, same_status, nd_mesh, cross-mesh): each conversion
    preserves the global value and lands the expected per-device shards."""

    def _x(self):
        return paddle.arange(0, 64, dtype="float32").reshape([8, 8])

    def test_r_to_s_and_back(self):
        m = dist.ProcessMesh(shape=[8], dim_names=["x"])
        x = self._x()
        xs = dist.shard_tensor(x, m, [dist.Shard(0)])       # r_to_s
        assert xs._value.addressable_shards[0].data.shape == (1, 8)
        xr = dist.reshard(xs, m, [dist.Replicate()])        # s_to_r
        assert xr._value.addressable_shards[0].data.shape == (8, 8)
        assert np.allclose(_np(xr), _np(x))

    def test_s_to_s_dim_change(self):
        m = dist.ProcessMesh(shape=[8], dim_names=["x"])
        xs = dist.shard_tensor(self._x(), m, [dist.Shard(0)])
        xt = dist.reshard(xs, m, [dist.Shard(1)])           # s0 -> s1
        assert xt._value.addressable_shards[0].data.shape == (8, 1)
        assert np.allclose(_np(xt), _np(self._x()))

    def test_nd_mesh_both_dims(self):
        m = dist.ProcessMesh(shape=[2, 4], dim_names=["a", "b"])
        xs = dist.shard_tensor(self._x(), m,
                               [dist.Shard(0), dist.Shard(1)])
        assert xs._value.addressable_shards[0].data.shape == (4, 2)
        flipped = dist.reshard(xs, m, [dist.Shard(1), dist.Shard(0)])
        assert flipped._value.addressable_shards[0].data.shape == (2, 4)
        assert np.allclose(_np(flipped), _np(self._x()))

    def test_cross_mesh(self):
        """reference nd_mesh/cross-mesh reshard: topology change 1D->2D."""
        mA = dist.ProcessMesh(shape=[8], dim_names=["x"])
        mB = dist.ProcessMesh(shape=[2, 4], dim_names=["a", "b"])
        xs = dist.shard_tensor(self._x(), mA, [dist.Shard(0)])
        xc = dist.reshard(xs, mB, [dist.Shard(1), dist.Shard(0)])
        assert xc._value.addressable_shards[0].data.shape == (2, 4)
        assert np.allclose(_np(xc), _np(self._x()))
        assert xc.dist_attr.process_mesh is mB

    def test_same_status_noop(self):
        m = dist.ProcessMesh(shape=[8], dim_names=["x"])
        xs = dist.shard_tensor(self._x(), m, [dist.Shard(0)])
        again = dist.reshard(xs, m, [dist.Shard(0)])
        assert np.allclose(_np(again), _np(self._x()))
        assert again._value.sharding == xs._value.sharding


class TestSpmdPropagationRules:
    """Per-op sharding propagation (SURVEY item 15; reference
    infermeta/spmd_rules/ matmul/elementwise/embedding/reduction/softmax/
    transpose): GSPMD must derive the canonical output shardings from the
    input shardings — the TPU substitute for hand-written InferSpmd."""

    def _mesh(self):
        return dist.ProcessMesh(shape=[2, 4], dim_names=["dp", "mp"])

    def _spec_of(self, arr):
        return arr.sharding.spec if hasattr(arr.sharding, "spec") else None

    def _run(self, fn, *arrs_specs):
        from jax.sharding import NamedSharding
        m = self._mesh().jax_mesh
        args = [jax.device_put(a, NamedSharding(m, s))
                for a, s in arrs_specs]
        return jax.jit(fn)(*args)

    def test_matmul_rule(self):
        # [b sharded dp, k] @ [k, n sharded mp] -> [dp, mp]
        a = jnp.ones((8, 16))
        b = jnp.ones((16, 32))
        out = self._run(lambda x, w: x @ w, (a, P("dp", None)),
                        (b, P(None, "mp")))
        assert self._spec_of(out) == P("dp", "mp")

    def test_matmul_contraction_partial_resolved(self):
        # contraction over an mp-sharded dim: output must be materialized
        # (GSPMD inserts the reduction; result spec has no mp on k)
        a = jnp.ones((8, 16))
        b = jnp.ones((16, 32))
        out = self._run(lambda x, w: x @ w, (a, P(None, "mp")),
                        (b, P("mp", None)))
        assert np.allclose(np.asarray(out), 16.0)

    def test_elementwise_and_softmax_keep_sharding(self):
        a = jnp.ones((8, 32))
        out = self._run(lambda x: jax.nn.softmax(x * 2.0, axis=-1),
                        (a, P("dp", "mp")))
        assert self._spec_of(out) == P("dp", "mp")

    def test_reduction_rule(self):
        a = jnp.ones((8, 32))
        out = self._run(lambda x: x.sum(axis=1), (a, P("dp", "mp")))
        # reduced dim's sharding disappears; batch dim's stays
        assert self._spec_of(out)[:1] == P("dp")

    def test_transpose_rule(self):
        a = jnp.ones((8, 32))
        out = self._run(lambda x: x.T, (a, P("dp", "mp")))
        assert self._spec_of(out) == P("mp", "dp")

    def test_embedding_rule(self):
        # vocab-sharded table gather -> replicated-row output, correct
        # values (reference embedding.h InferSpmd)
        table = jnp.arange(64.0).reshape(32, 2)
        ids = jnp.asarray(np.array([[1, 5], [7, 31]], np.int32))
        out = self._run(lambda t, i: jnp.take(t, i, axis=0),
                        (table, P("mp", None)), (ids, P(None, None)))
        assert np.allclose(np.asarray(out),
                           np.take(np.asarray(table), np.asarray(ids), 0))


@pytest.mark.slow  # 2-process launch-CLI harnesses, minutes each
class TestMultiControllerCheckpoint:
    """VERDICT r4 #4: checkpoint/resume in the 2-process GSPMD harness —
    the one topology the v5p north star actually uses."""

    def _run(self, worker, env=None, argv=(), nproc=2, log_dir=None,
             timeout=170):
        import os, subprocess, sys
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
               "--nproc_per_node", str(nproc)]
        if log_dir is not None:
            cmd += ["--log_dir", str(log_dir)]
        cmd += [worker, *argv]
        return subprocess.run(cmd, cwd=root, env=dict(os.environ,
                                                      **(env or {})),
                              capture_output=True, text=True,
                              timeout=timeout)

    @staticmethod
    def _tagged(text, tag):
        import json
        for line in text.splitlines():
            if line.startswith(tag + " "):
                return json.loads(line[len(tag) + 1:])
        raise AssertionError(f"no {tag!r} in:\n{text}")

    def test_two_process_orbax_save_load_and_crosstopo(self, tmp_path):
        """Save is a collective orbax write across 2 processes sharing a
        [dp=2, mp=4] mesh; reload + replay is bit-exact; the same
        checkpoint then restores into a single-process [dp=1, mp=8]
        mesh (cross-topology reshard-on-load) with loss parity."""
        import os, subprocess, sys
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        worker = os.path.join(root, "tests", "launch_worker_gspmd.py")
        ck = tmp_path / "ck"
        logs = tmp_path / "logs"
        r = self._run(worker, env={"GSPMD_CKPT_DIR": str(ck)},
                      log_dir=logs)
        assert r.returncode == 0, r.stdout + r.stderr
        posts = []
        for i in range(2):
            text = (logs / f"workerlog.{i}").read_text()
            post = self._tagged(text, "GSPMD_CKPT_POST")
            replay = self._tagged(text, "GSPMD_CKPT_REPLAY")
            assert post == replay, (post, replay)   # bit-exact replay
            posts.append(post)
        assert posts[0] == posts[1]                 # ranks agree

        # cross-topology: [2, 4] checkpoint -> [1, 8] mesh, 1 process
        r2 = subprocess.run(
            [sys.executable, worker], cwd=root,
            env=dict(os.environ, GSPMD_LOCAL_DEVICES="8",
                     GSPMD_LOAD_DIR=str(ck), PYTHONPATH=root),
            capture_output=True, text=True, timeout=170)
        assert r2.returncode == 0, r2.stdout + r2.stderr
        cross = self._tagged(r2.stdout, "GSPMD_CROSSTOPO_POST")
        np.testing.assert_allclose(cross, posts[0], rtol=1e-4)

    def test_kill_one_rank_relaunch_resumes_with_loss_parity(
            self, tmp_path):
        """Rank 1 dies hard (os._exit 101) at step 6; the launcher reaps
        the pod; a relaunch resumes BOTH ranks from the last advertised
        orbax snapshot and steps 7-10 match an uninterrupted run
        bit-exactly."""
        import os
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        worker = os.path.join(root, "tests", "autockpt_worker_gspmd.py")

        ref = self._run(worker, argv=(str(tmp_path / "ref"), "-1"),
                        log_dir=tmp_path / "l1")
        assert ref.returncode == 0, ref.stdout + ref.stderr
        ref_losses = dict(self._tagged(
            (tmp_path / "l1" / "workerlog.0").read_text(), "LOSSES"))

        crash = self._run(worker, argv=(str(tmp_path / "wd"), "6"),
                          log_dir=tmp_path / "l2")
        assert crash.returncode == 101, crash.stdout + crash.stderr

        resume = self._run(worker, argv=(str(tmp_path / "wd"), "-1"),
                           log_dir=tmp_path / "l3")
        assert resume.returncode == 0, resume.stdout + resume.stderr
        for i in range(2):
            text = (tmp_path / "l3" / f"workerlog.{i}").read_text()
            assert "RESUMED_AT 6" in text, text
        got = dict(self._tagged(
            (tmp_path / "l3" / "workerlog.0").read_text(), "LOSSES"))
        assert set(got) == {7, 8, 9, 10}
        for s, loss in got.items():
            assert loss == ref_losses[s], (s, loss, ref_losses[s])
