"""What the attention tests share."""
import jax
import jax.numpy as jnp


def out_and_grads(fn, q, k, v, cot):
    """``fn(q, k, v)`` and the gradients of ``sum(fn(q, k, v) * cot)``
    with respect to ``q, k, v``: one program, compiled once (op by op, an
    attention's forward and backward are a hundred small compiles)."""
    def f(q, k, v):
        out = fn(q, k, v)
        return jnp.sum(out * cot), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        f, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return out, grads
