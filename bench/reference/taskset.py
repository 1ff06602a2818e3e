"""The device task of the task-set cells, and its plain reference.

One task is ``rounds`` rounds of x <- ((x @ w) mod 7) - 3 on an [n, n]
bf16 matrix seeded by the task index; it returns sum(x). Every value is a
small integer and products accumulate in f32, so each step is exact and
any correct evaluation order gives the same checksum.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def task_program(n: int, rounds: int, accumulate=jnp.float32):
    """The task body; ``accumulate`` is the matmul's accumulation type."""
    def task(w, index):
        r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        x = ((r * 3 + c * 5 + index) % 7 - 3).astype(jnp.bfloat16)
        for _ in range(rounds):
            y = jnp.dot(x, w, preferred_element_type=accumulate)
            x = (y.astype(jnp.int32) % 7 - 3).astype(jnp.bfloat16)
        return jnp.sum(x.astype(jnp.int32))
    return task


def task_weights(seed: int, n: int):
    """``w``: [n, n] bf16 integers in [-3, 3], from the seed."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.random.randint(key, (n, n), -3, 4).astype(jnp.bfloat16)


def checksums(w, indices, n: int, rounds: int, *, chunk: int = 4,
              accumulate=jnp.float32) -> np.ndarray:
    """The task body run op by op (not jitted), ``chunk`` tasks at a time.

    Eager batched ops hold large temporaries: on a v5e a chunk of 64 tasks
    of n = 2048 peaked at 16.7 GB, a chunk of 4 at 0.42 GB.
    """
    batched = jax.vmap(task_program(n, rounds, accumulate),
                       in_axes=(None, 0))
    idx = np.asarray(indices, np.int32)
    return np.concatenate([
        np.asarray(batched(w, jnp.asarray(idx[s:s + chunk])))
        for s in range(0, len(idx), chunk)]) if len(idx) else \
        np.zeros((0,), np.int32)
