"""The plain reference for training: the decoder of lib/reference.py
under a mean next-token cross entropy, its gradient by ``jax.grad`` and
the AdamW rule (decoupled decay, bias correction) written out, float32
at matmul precision "highest". It follows the program's first steps on
the same batches from the same seeded weights and reports what the
comparison needs: each step's loss, the norm of every leaf's first
gradient, and the norm of every leaf's change after the last step.

Spread over the chips by plain ``jit`` shardings (every leaf split along
one divisible axis, the batch along its rows) so that float32 weights,
moments and gradients of a 1 B parameter cut fit beside each other; no
kernel, no recomputation policy beyond one checkpoint a layer.

``precision="int8"`` is the control: weight matrices rounded to int8 per
output channel (the gradient passes straight through the rounding),
arithmetic in bfloat16.

TODO, for the PR that proves the training cell (PERF.md, Open question
0): no limit of ``benchmark/traffic/dp2_mp2.json`` is set, and nothing
here has been read on the chip since these two repairs. (1) The one chip
run had the control round the weights inside the gradient, so its
gradients vanished; the straight-through form below has run on the CPU
only. (2) At learning rate 1e-4 the loss falls 2.7 a step and the
program's loss lay 0.24 from the reference's at step 3 with first
gradients 0.07% apart: hold the first loss to the reference and the
later ones to falling, or read the steps at 1e-5.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import reference as R
from . import weights as W


def _loss(params, tokens, cfg, precision):
    """Mean cross entropy of token t+1 given tokens up to t."""
    n_layers = cfg["num_hidden_layers"]
    cast = (lambda a: a) if precision == "float32" \
        else (lambda a: a.astype(jnp.bfloat16))
    x = cast(jnp.take(params["embed_tokens"], tokens, axis=0))     # [b, s, d]
    positions = jnp.arange(tokens.shape[1])
    names = W.layer_leaves(cfg)

    @jax.checkpoint
    def layer(x, lp):
        return jax.vmap(lambda row: R._layer(cfg, lp, row, positions))(x)

    for l in range(n_layers):
        x = layer(x, {n: cast(params[n][l]) for n in names})
    x = R._rms(x, cast(params["final_norm"]), cfg["rms_norm_eps"])
    lm = params["lm_head"] if "lm_head" in params else params["embed_tokens"].T
    logits = (x[:, :-1] @ cast(lm)).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -picked.mean()


def _adamw(p, m, v, g, step, hp):
    b1, b2 = hp["beta1"], hp["beta2"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    m_hat = m / (1 - b1 ** step)
    denom = jnp.sqrt(v / (1 - b2 ** step)) + hp["epsilon"]
    p = p * (1.0 - hp["learning_rate"] * hp["weight_decay"])
    return p - hp["learning_rate"] * m_hat / denom, m, v


def _leaf_sharding(mesh, shape):
    """Split the last axis the device count divides, else replicate."""
    n = mesh.devices.size
    for axis in reversed(range(len(shape))):
        if shape[axis] % n == 0 and shape[axis] >= n:
            spec = [None] * len(shape)
            spec[axis] = "x"
            return NamedSharding(mesh, P(*spec))
    return NamedSharding(mesh, P())


def build(cfg, hp, mesh, n_rows, precision="float32"):
    """The jitted pieces (init, step, change) and the shardings, for a
    mesh of real or described devices."""
    items = W.model_items(cfg)
    shapes = jax.eval_shape(lambda k: W._make_all(k, items, jnp.bfloat16),
                            W.seed_key(0))
    shard = {n: _leaf_sharding(mesh, s.shape) for n, s in shapes.items()}
    rows = NamedSharding(mesh, P("x" if n_rows % mesh.devices.size == 0
                                 else None))
    matmul = "highest" if precision == "float32" else "default"

    def quantized(w):
        if precision == "float32":
            return w


        def rounded(a):
            q = R._fake_int8(a.reshape(-1, a.shape[-1])).reshape(a.shape)
            return a + jax.lax.stop_gradient(q - a)

        return {n: rounded(a) if a.ndim >= 2 and n != "embed_tokens" else a
                for n, a in w.items()}

    @functools.partial(jax.jit, out_shardings=(shard, shard, shard))
    def init(key):
        w = W._make_all(key, items, jnp.bfloat16)
        w = {n: a.astype(jnp.float32) for n, a in w.items()}
        zeros = {n: jnp.zeros_like(a) for n, a in w.items()}
        return w, zeros, zeros

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                       out_shardings=(shard, shard, shard, None, None))
    def step(params, m, v, tokens, count):
        with jax.default_matmul_precision(matmul):
            loss, grads = jax.value_and_grad(
                lambda p: _loss(quantized(p), tokens, cfg, precision))(params)
        grads = {n: g.astype(jnp.float32) for n, g in grads.items()}
        out = {n: _adamw(params[n], m[n], v[n], grads[n], count, hp)
               for n in params}
        gnorm = {n: jnp.sqrt(jnp.sum(jnp.square(g))) for n, g in grads.items()}
        return ({n: o[0] for n, o in out.items()},
                {n: o[1] for n, o in out.items()},
                {n: o[2] for n, o in out.items()}, loss, gnorm)

    @jax.jit
    def change(params, key):
        w0 = W._make_all(key, items, jnp.bfloat16)
        return {n: jnp.sqrt(jnp.sum(jnp.square(
            params[n] - w0[n].astype(jnp.float32)))) for n in params}

    return init, step, change, shard, rows, shapes


def follow(seed, cfg, batches, hp, devices, precision="float32"):
    """Losses, first-gradient norms and change norms, by leaf."""
    mesh = Mesh(np.asarray(devices), ("x",))
    init, step, change, _, rows, _ = build(cfg, hp, mesh, batches[0].shape[0],
                                           precision)
    key = W.seed_key(seed)
    params, m, v = init(key)
    losses, first_grad = [], None
    for i, toks in enumerate(batches):
        tokens = jax.device_put(np.asarray(toks, np.int32), rows)
        params, m, v, loss, gnorm = step(params, m, v, tokens,
                                         jnp.float32(i + 1))
        losses.append(float(loss))
        if i == 0:
            first_grad = {n: float(g) for n, g in gnorm.items()}
    delta = {n: float(d) for n, d in change(params, key).items()}
    return {"loss": losses, "grad_norm": first_grad, "delta_norm": delta}


def worst_leaf_gap(got, want):
    """Largest |got - want| over the leaves, measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    floor = float(np.median(list(want.values())))
    return max(abs(got[n] - want[n]) / max(want[n], floor) for n in want)
