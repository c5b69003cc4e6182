"""Multi-controller compiled-collective proof worker (VERDICT r3 #2).

Run two ways with IDENTICAL seeds/data so losses must match:
- single process, 8 local CPU devices (GSPMD_LOCAL_DEVICES=8, no launch)
- 2 processes × 4 CPU devices under ``python -m
  paddle_tpu.distributed.launch --nproc_per_node 2`` — ONE shared
  8-device mesh, jax.distributed rendezvous, GSPMD collectives compiled
  ACROSS the process boundary (gloo CPU data plane).

This is the JAX analogue of the reference's multi-process-on-localhost
harness (test/legacy_test/test_parallel_dygraph_dataparallel.py:157) and
the shape that matches a v5p pod's one-process-per-host reality.
"""

import os

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices",
                  int(os.environ.get("GSPMD_LOCAL_DEVICES", "4")))
jax.config.update("jax_cpu_collectives_implementation", "gloo")

import json  # noqa: E402

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402  (import-time hook connects ranks)
import paddle_tpu.distributed as dist  # noqa: E402
import paddle_tpu.nn as nn  # noqa: E402
import paddle_tpu.nn.functional as F  # noqa: E402


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


def main():
    dist.init_parallel_env()
    assert len(jax.devices()) == 8, len(jax.devices())

    paddle.seed(11)
    net = TPNet()
    opt = paddle.optimizer.AdamW(learning_rate=0.05,
                                 parameters=net.parameters())
    # ZeRO-2 over dp composed with Megatron TP over mp — the compiled
    # program contains dp grad-reduce, mp allreduce and the ZeRO
    # reduce-scatter, all riding the cross-process mesh
    from paddle_tpu.distributed.fleet.sharding import apply_sharding_specs
    apply_sharding_specs(net, stage=2, axis="dp", min_size_to_shard=0)
    mesh = dist.ProcessMesh(shape=[2, 4], dim_names=["dp", "mp"])
    dist.shard_model_state(net, mesh)
    step = dist.DistTrainStep(net, opt, loss_fn, mesh, donate=False)

    rng = np.random.RandomState(0)
    x = rng.randn(8, 16).astype(np.float32)
    y = rng.randint(0, 4, (8,))
    losses = [float(step(paddle.to_tensor(x), paddle.to_tensor(y)))
              for _ in range(3)]
    assert losses[-1] < losses[0], losses
    print("GSPMD_LOSSES", json.dumps(losses), flush=True)

    # second run: per-process LOCAL batch shards (DistributedBatchSampler
    # semantics) assembled into the global batch via local_batch=True —
    # must reproduce the same losses as the replicated-loader run
    paddle.seed(11)
    net2 = TPNet()
    opt2 = paddle.optimizer.AdamW(learning_rate=0.05,
                                  parameters=net2.parameters())
    apply_sharding_specs(net2, stage=2, axis="dp", min_size_to_shard=0)
    dist.shard_model_state(net2, mesh)
    step2 = dist.DistTrainStep(net2, opt2, loss_fn, mesh, donate=False,
                               local_batch=True)
    nproc = jax.process_count()
    rows = x.shape[0] // nproc
    lo = jax.process_index() * rows
    xl, yl = x[lo:lo + rows], y[lo:lo + rows]
    losses_l = [float(step2(paddle.to_tensor(xl), paddle.to_tensor(yl)))
                for _ in range(3)]
    print("GSPMD_LOSSES_LOCAL", json.dumps(losses_l), flush=True)

    ck = os.environ.get("GSPMD_CKPT_DIR")
    if ck:
        _checkpoint_phase(net, opt, step, x, y, ck)


def _opt_state_tensors(opt):
    """Optimizer slots as checkpoint entries via the public
    state_dict(); returns (tensors, writeback) where writeback() hands
    the (restored-in-place) wrappers back through set_state_dict."""
    from paddle_tpu.core.tensor import Tensor
    sd = opt.state_dict()
    tensors = {f"__opt__/{k}": v for k, v in sd.items()
               if isinstance(v, Tensor)}

    def writeback(gstep):
        full = {k.split("/", 1)[1]: v for k, v in tensors.items()}
        full["global_step"] = gstep
        opt.set_state_dict(full)

    return tensors, writeback


def _checkpoint_phase(net, opt, step, x, y, ck):
    """VERDICT r4 #4: orbax save/load ACROSS the multi-controller
    process boundary. Save (collective), train 2 more steps, reload the
    snapshot, replay the same 2 steps — losses must match bit-exactly.
    The snapshot carries params AND optimizer moments + global step."""
    snap = os.path.join(ck, "snap")
    state = dict(net.state_dict())
    opt_ts, _ = _opt_state_tensors(opt)
    state.update(opt_ts)
    gstep = opt._global_step
    dist.save_state_dict(state, snap)
    post = [float(step(paddle.to_tensor(x), paddle.to_tensor(y)))
            for _ in range(2)]
    print("GSPMD_CKPT_POST", json.dumps(post), flush=True)

    targets = dict(net.state_dict())
    opt_ts2, writeback = _opt_state_tensors(opt)
    targets.update(opt_ts2)
    dist.load_state_dict(targets, snap)
    writeback(gstep)
    replay = [float(step(paddle.to_tensor(x), paddle.to_tensor(y)))
              for _ in range(2)]
    print("GSPMD_CKPT_REPLAY", json.dumps(replay), flush=True)


def crosstopo_load():
    """Cross-topology load (VERDICT r4 #4): a checkpoint written by the
    2-proc [dp=2, mp=4] run restores into a single-process model on a
    [dp=1, mp=8] mesh; two further train steps must track the 2-proc
    run's post-save losses (collective order may differ → fp tolerance
    checked host-side)."""
    dist.init_parallel_env()
    snap = os.path.join(os.environ["GSPMD_LOAD_DIR"], "snap")
    paddle.seed(11)
    net = TPNet()
    opt = paddle.optimizer.AdamW(learning_rate=0.05,
                                 parameters=net.parameters())
    from paddle_tpu.distributed.fleet.sharding import apply_sharding_specs
    apply_sharding_specs(net, stage=2, axis="dp", min_size_to_shard=0)
    mesh = dist.ProcessMesh(shape=[1, 8], dim_names=["dp", "mp"])
    dist.shard_model_state(net, mesh)
    step = dist.DistTrainStep(net, opt, loss_fn, mesh, donate=False)
    rng = np.random.RandomState(0)
    x = rng.randn(8, 16).astype(np.float32)
    y = rng.randint(0, 4, (8,))
    # build the jitted step + optimizer accumulators, then restore the
    # snapshot over them (3 throwaway steps mirror the saver's history)
    for _ in range(3):
        step(paddle.to_tensor(x), paddle.to_tensor(y))
    targets = dict(net.state_dict())
    opt_ts, writeback = _opt_state_tensors(opt)
    targets.update(opt_ts)
    dist.load_state_dict(targets, snap)
    writeback(3)
    post = [float(step(paddle.to_tensor(x), paddle.to_tensor(y)))
            for _ in range(2)]
    print("GSPMD_CROSSTOPO_POST", json.dumps(post), flush=True)


if __name__ == "__main__":
    if os.environ.get("GSPMD_LOAD_DIR"):
        crosstopo_load()
    else:
        main()
