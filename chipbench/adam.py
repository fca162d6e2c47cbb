"""Adam's first two steps as the configurations state it, in plain
``jax.numpy`` float32: arithmetic on any tree of leaves, whatever the
model. Part of the plain reference; imports nothing of the program."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _adam_terms(t, lr, b1, b2):
    return lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("lr", "b1", "b2", "eps"))
def adam_first(w, g1, *, lr, b1, b2, eps):
    """Step 1 of Adam from zero state (``m1 = (1-b1) g1``, ``v1 = (1-b2)
    g1**2``); the update ``lr_t * m / (sqrt(v) + eps)`` with ``lr_t = lr *
    sqrt(1 - b2**t) / (1 - b1**t)``, as the program's optimizer has it."""
    lr1 = _adam_terms(1, lr, b1, b2)
    return jax.tree.map(
        lambda w, g: w - lr1 * (1 - b1) * g
        / (jnp.sqrt((1 - b2) * jnp.square(g)) + eps), w, g1)


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("leaf_norms", "lr", "b1", "b2", "eps"))
def adam_second(w1, g1, g2, *, leaf_norms, lr, b1, b2, eps):
    """Step 2 from the two gradients, and the norms (by the family's
    ``leaf_norms``, inside this program: the change's tree is never held
    beside the weights) of the change the two steps made together.
    Returns ``(w2, norms)``."""
    lr1, lr2 = _adam_terms(1, lr, b1, b2), _adam_terms(2, lr, b1, b2)

    def leaf(w, a, b):
        m1, v1 = (1 - b1) * a, (1 - b2) * jnp.square(a)
        m2 = b1 * m1 + (1 - b1) * b
        v2 = b2 * v1 + (1 - b2) * jnp.square(b)
        u1 = lr1 * m1 / (jnp.sqrt(v1) + eps)
        u2 = lr2 * m2 / (jnp.sqrt(v2) + eps)
        return w - u2, u1 + u2

    both = jax.tree.map(leaf, w1, g1, g2)
    w2 = {n: p[0] for n, p in both.items()}
    return w2, leaf_norms({n: p[1] for n, p in both.items()})
