"""The decode-time state update of a Mamba-2 layer
(paddle_tpu/kernels/ssm_update.py, ``ssm_decode_update``): one token's
step of the recurrent state of every live row,
``S <- a S + dt x (x) B`` and ``y = S C``.

Needs, per (live row, layer, step), whatever implements it:
- bytes: the row's state ``heads * head_dim * state`` float32 once in
  and once out; beside it the step's inputs and outputs, all float32:
  the decay and ``dt x`` and the returned ``y`` (with the zeros it is
  laid over) at one value a (head, channel), ``B`` and ``C`` at one a
  state index;
- operations: five a state element (decay multiply, the outer
  product's multiply and add, the multiply by ``C`` and the add of the
  reduction).
Bound: bytes, at 0.6 operations a byte against the chip's 240.
"""


def needs(row_layer_steps, heads, head_dim, state):
    """(operations, bytes) of ``row_layer_steps`` updates."""
    elements = heads * head_dim * state
    nbytes = 4 * (2 * elements + 4 * heads * head_dim + 2 * state)
    return 5.0 * elements * row_layer_steps, float(nbytes) * row_layer_steps


def least_seconds(row_layer_steps, heads, head_dim, state, peaks):
    ops, nbytes = needs(row_layer_steps, heads, head_dim, state)
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
