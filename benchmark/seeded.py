"""Everything random in a run comes from `--seed` through these functions:
weights and training batches on the device in one jitted call each, host
draws (traffic, samples) from `host_rng`."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def device_key(seed, stream):
    """A key for any whole-number seed (the driver's pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    key = jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(stream))


def host_rng(seed, stream):
    return np.random.default_rng([int(seed), int(stream)])


def make_weights(shapes, seed, dtype, init_std, shardings=None):
    """{name: array} for {name: shape}: matrices N(0, init_std), LayerNorm
    scales 1 + N(0, init_std), every other vector N(0, init_std), so that
    no term of the model is switched off by a zero. One jitted call; with
    `shardings` ({name: sharding}) each leaf is made where it will live."""
    names = list(shapes)

    def make(key):
        out = {}
        for i, name in enumerate(names):
            x = init_std * jax.random.normal(jax.random.fold_in(key, i),
                                             shapes[name], jnp.float32)
            if len(shapes[name]) == 1 and name.endswith("weight"):
                x = 1.0 + x
            out[name] = x.astype(dtype)
        return out

    out_shardings = None if shardings is None \
        else {n: shardings[n] for n in names}
    return jax.jit(make, out_shardings=out_shardings)(device_key(seed, 1))


def make_batches(count, rows, seq, vocab, seed, sharding=None):
    """`count` training batches, (ids, labels) int32 [rows, seq] each, every
    row different, made on the device."""
    def make(key):
        draw = jax.random.randint(key, (count, 2, rows, seq), 0, vocab,
                                  jnp.int32)
        return [(draw[i, 0], draw[i, 1]) for i in range(count)]
    out = None if sharding is None else [(sharding, sharding)] * count
    return jax.jit(make, out_shardings=out)(device_key(seed, 2))


def token_ids(rng, length, vocab):
    return rng.integers(0, vocab, int(length)).tolist()
