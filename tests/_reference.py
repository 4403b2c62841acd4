"""Teacher-forced greedy references for the serving tests.

Each reference jits its model step once per ``(cfg, mode)`` and shares
that compile with every test in the process that asks for the same
pair; a prompt length seen before is not compiled again.  Caches are
f32, as the server's, for ``MAX_LEN`` positions.
"""

import functools

import jax
import jax.numpy as jnp

from repro.models import decode_step, init_caches, prefill_step

MAX_LEN = 64


@functools.lru_cache(maxsize=None)
def _prefill(cfg, mode):
    return jax.jit(lambda p, t, c: prefill_step(p, t, c, cfg, mode=mode))


@functools.lru_cache(maxsize=None)
def _decode(cfg, mode):
    return jax.jit(lambda p, t, q, c: decode_step(p, t, q, c, cfg, mode=mode))


def teacher_forced(cfg, params, prompt, n, mode):
    """Greedy reference: prompt + ``n`` tokens, each the argmax of a
    prefill of the growing sequence from position 0 in ``mode`` (no
    cache reuse between tokens)."""
    pre = _prefill(cfg, mode)
    seq = list(prompt)
    for _ in range(n):
        caches = init_caches(cfg, 1, MAX_LEN, dtype=jnp.float32)
        logits, _ = pre(params, jnp.asarray([seq], jnp.int32), caches)
        seq.append(int(jnp.argmax(logits[0])))
    return seq


def stepwise(cfg, params, prompt, n, mode):
    """Greedy reference: prompt + ``n`` tokens, the first from a prefill
    of the prompt and each later one from a one-token decode step on
    the cache the earlier steps wrote, all in ``mode``."""
    pre, dec = _prefill(cfg, mode), _decode(cfg, mode)
    caches = init_caches(cfg, 1, MAX_LEN, dtype=jnp.float32)
    logits, caches = pre(params, jnp.asarray([list(prompt)], jnp.int32), caches)
    seq = list(prompt) + [int(jnp.argmax(logits[0]))]
    while len(seq) < len(prompt) + n:
        logits, caches = dec(
            params, jnp.asarray([seq[-1:]], jnp.int32),
            jnp.asarray([len(seq) - 1], jnp.int32), caches,
        )
        seq.append(int(jnp.argmax(logits[0])))
    return seq
