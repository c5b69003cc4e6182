"""The train runner: ``dist.DistTrainStep`` on the mesh the mix's file
names, fed structured batches made on the host ahead of the step.

Set-up builds one object, the compiled step with its optimizer state,
drives it from the seed through its first steps by the window's own
call and feed, and hands that same object to the window. Those first
steps are what ``correct`` compares with the plain reference
(lib/reference_train.py) once the program's state is freed: each step's
loss, the norm of every leaf's first gradient as the optimizer got it
(AdamW's first moment after one step is 0.1 g), and the norm of every
leaf's change after the last checked step.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from ..lib import device as dev
from ..lib import program, reference_train, result, stats
from ..lib import weights as W


def batches_for(seed, n, batch, seq, support):
    """Structured batches (after chip_smoke.train_batches): a noisy affine
    next-token process over a small support, so that the loss falls
    within a few steps and a causality or optimizer fault shows as a
    flat one. Every row starts elsewhere and draws its own noise."""
    rng = np.random.default_rng([int(seed), 0x7472])
    out = []
    for _ in range(n):
        toks = np.empty((batch, seq), np.int32)
        toks[:, 0] = rng.permutation(support)[:batch]
        noise = rng.integers(-2, 3, size=(batch, seq - 1))
        for t in range(1, seq):
            toks[:, t] = (toks[:, t - 1] * 5 + 17 + noise[:, t - 1]) % support
        out.append(toks)
    return out


def optimizer_leaf_norms(opt, names, slot, scale=1.0):
    import jax.numpy as jnp
    return {n: scale * float(jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32))))) for n, a in zip(names, opt._accumulators[slot])}


def change_norms(opt, names, seed, cfg):
    """Norm of (master weight now - seeded weight), leaf by leaf; the
    seeded leaf is made again rather than kept, to spare the memory."""
    import jax
    import jax.numpy as jnp
    key, items = W.seed_key(seed), W.model_items(cfg)
    out = {}
    for n, now in zip(names, opt._accumulators["master_weight"]):
        w0 = W._make_all(key, items, jnp.bfloat16, only=(n,))[n]
        w0 = jax.device_put(w0.astype(jnp.float32), now.sharding)
        out[n] = float(jnp.sqrt(jnp.sum(jnp.square(now - w0))))
    return out


def run(ctx):
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models.llama import llama_loss_fn
    cfg, mix, seconds = ctx["cfg"], ctx["mix"], float(ctx["seconds"])
    devices = ctx["devices"]
    watch = dev.CompileWatch()
    batch, seq = int(mix["batch"]), int(mix["seq"])
    n_check = int(mix["check"]["steps"])
    hp = mix["optimizer"]

    model = program.build_model(cfg, ctx["seed"])
    opt = paddle.optimizer.AdamW(
        learning_rate=hp["learning_rate"], beta1=hp["beta1"], beta2=hp["beta2"],
        epsilon=hp["epsilon"], weight_decay=hp["weight_decay"],
        parameters=model.parameters(), multi_precision=True)
    mesh = dist.ProcessMesh(shape=list(mix["mesh"]["shape"]),
                            dim_names=list(mix["mesh"]["names"]))
    dist.shard_model_state(model, mesh)
    step = dist.DistTrainStep(model, opt, llama_loss_fn, mesh, donate=True)
    names = [n for n, p in model.named_parameters()]
    if len(names) != len(opt._parameter_list):
        raise AssertionError("optimizer and model disagree on the leaves")

    pool = batches_for(ctx["seed"], n_check + int(mix["data"]["pool"]), batch,
                       seq, int(mix["data"]["support"]))

    def feed(i):
        x = paddle.to_tensor(pool[i % len(pool)])
        return step(x, x)

    # the first steps, through the window's own call and feed
    # (programs lowered are counted round the step's own calls only: the
    # readings of the optimizer's state between them lower their own)
    losses, first_grad, relowered = [], None, 0
    for i in range(n_check):
        mark = watch.lowered
        losses.append(float(feed(i)))
        if i > 0:
            relowered += watch.lowered - mark
        if i == 0:
            first_grad = optimizer_leaf_norms(
                opt, names, "moment1", scale=1.0 / (1.0 - hp["beta1"]))
    delta = change_norms(opt, names, ctx["seed"], cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    gc.collect()
    gc.freeze()

    t0 = time.perf_counter()
    setup_s = t0 - ctx["t_process"]
    tracer = result.start_trace(ctx, t0, default_seconds=2.0)
    steps, walls, last = 0, [], None
    mark = watch.lowered
    every = int(mix.get("sync_every", 1))
    t_prev = t0
    while True:
        last = feed(n_check + steps)
        steps += 1
        if steps % every == 0:
            jax.block_until_ready(last._value)
            now = time.perf_counter()
            walls.append((now - t_prev) / every)
            t_prev = now
            if now - t0 >= seconds:
                break
    t_close = time.perf_counter()
    window_s = t_close - t0
    if tracer is not None:
        tracer.join()
    relowered += watch.lowered - mark
    loss_last = float(last)
    peak = dev.memory_peak_bytes(devices)
    tokens_per_step = batch * seq
    e2e = {"setup_s": (setup_s, "s"),
           "train_tok_s": (steps * tokens_per_step / window_s, "tokens/s")}
    print(f"samples: steps {steps} tokens_per_step {tokens_per_step} "
          f"window_s {window_s:.3f} setup_s {setup_s:.3f} "
          f"step_ms_median {1e3 * stats.median(walls):.3f} params {n_params}")
    print(f"losses: first_steps {losses} last {loss_last:.4f}")

    del step, opt, model, last
    gc.unfreeze()
    gc.collect()
    jax.clear_caches()

    ref = reference_train.follow(ctx["seed"], cfg, pool[:n_check], hp, devices)
    limits = mix["check"]["limits"]
    got = {"loss": losses, "grad_norm": first_grad, "delta_norm": delta}
    checks = compare(got, ref, limits)
    if ctx["control"]:
        low = reference_train.follow(ctx["seed"], cfg, pool[:n_check], hp,
                                     devices, precision="int8")
        checks += [("control." + n, v, lim) for n, v, lim
                   in compare(low, ref, limits)]
    checks += [("programs_lowered_after_first_step", relowered, 0),
               ("loss_not_fallen", float(not (np.isfinite(loss_last)
                                              and loss_last < losses[0])), 0)]
    correct = result.print_checks(checks)
    return result.assemble(
        ctx, correct, steps, 0, peak, e2e,
        {"window": (t0, t_close), "step_walls_train": walls,
         "n_params": n_params, "tokens_per_step": tokens_per_step},
        checks=checks)


def compare(got, ref, limits):
    """The numbers compared, each with its limit."""
    loss_gap = max(abs(a - b) for a, b in zip(got["loss"], ref["loss"]))
    return [
        ("loss_gap", loss_gap, limits["loss_gap"]),
        ("grad_norm_gap", reference_train.worst_leaf_gap(
            got["grad_norm"], ref["grad_norm"]), limits["grad_norm_gap"]),
        ("delta_norm_gap", reference_train.worst_leaf_gap(
            got["delta_norm"], ref["delta_norm"]), limits["delta_norm_gap"]),
    ]
