"""Random weights from the seed, made by the benchmark itself.

``canonical`` lays the weights out by the published architecture (the
layout of each ``model_type`` is in ``bench/arch``); the plain reference
reads that layout.  ``program_params`` puts the same numbers into the
program's parameter tree.  Both are one jitted call on the device, from
the seed; each leaf of the program's tree takes the type that the
program's ``init_params`` gives it, cast inside that call, and no leaf
may be narrower than the precision the configuration file states as
``served_dtype`` (float32 where it states none).  The program
gets its weights from here, never from its own ``init_params``, so the
reference takes nothing the program has made.
"""
from __future__ import annotations

import json
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch

NORM_SPREAD = 0.1       # norm gains are 1 + 0.1 N(0, 1): not all ones


def seed_key(seed: int):
    """A JAX key from any whole number, also past 32 bits."""
    s = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(s[0] >> 1)),
                              int(s[1] >> 1))


def draw(key, shapes: dict, fan_in) -> dict:
    """One leaf per name, in sorted order, each from its own fold of
    ``key``: a norm gain (``fan_in(name, shape)`` is None) is ``1 +
    NORM_SPREAD N(0, 1)``, any other weight a truncated normal (within
    two deviations) scaled by the inverse square root of its fan-in."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        f = fan_in(name, shape)
        if f is None:
            out[name] = 1.0 + NORM_SPREAD * jax.random.normal(k, shape)
        else:
            out[name] = f ** -0.5 * jax.random.truncated_normal(
                k, -2.0, 2.0, shape)
    return out


def conf_key(conf: dict) -> str:
    """A configuration as a hashable key for per-configuration caches."""
    return json.dumps(conf, sort_keys=True)


@lru_cache(maxsize=None)
def _jitted(conf_json: str, dtypes: tuple | None):
    """One jitted maker per configuration (and, for the program's tree,
    per leaf types), so a process that makes many seeds' weights traces
    and compiles it once."""
    conf = json.loads(conf_json)
    mod = arch.module(conf)
    if dtypes is None:
        return jax.jit(partial(mod.canonical, conf))

    def program(key):
        tree = mod.to_program(conf, mod.canonical(conf, key))
        leaves, treedef = jax.tree.flatten(tree)
        return jax.tree.unflatten(
            treedef, [a.astype(t) for a, t in zip(leaves, dtypes)])
    return jax.jit(program)


def canonical(conf: dict, seed: int) -> dict:
    """The weights in the published layout (float32, on the device)."""
    return _jitted(conf_key(conf), None)(seed_key(seed))


def program_maker(conf: dict, mcfg):
    """The jitted ``key -> parameter tree`` of the program, checked leaf
    by leaf against the shapes of ``init_params`` and giving each leaf
    its type, which may not be narrower than the stated precision."""
    from repro.models import model as M

    want = jax.eval_shape(partial(M.init_params, mcfg),
                          jax.random.PRNGKey(0))
    mod = arch.module(conf)
    got = jax.eval_shape(lambda k: mod.to_program(conf, mod.canonical(conf, k)),
                         seed_key(0))
    if (jax.tree.structure(got) != jax.tree.structure(want)
            or any(a.shape != b.shape for a, b in
                   zip(jax.tree.leaves(got), jax.tree.leaves(want)))):
        raise ValueError("the program's parameter tree no longer matches "
                         f"the benchmark's layout: {want} vs {got}")
    dtypes = tuple(str(a.dtype) for a in jax.tree.leaves(want))
    stated = jnp.dtype(conf.get("served_dtype", "float32"))
    narrow = sorted({t for t in dtypes
                     if jnp.dtype(t).itemsize < stated.itemsize})
    if narrow:
        raise ValueError(f"the program holds weights in {narrow}, narrower "
                         f"than the {stated} the configuration states "
                         "(served_dtype)")
    return _jitted(conf_key(conf), dtypes)


def program_params(conf: dict, mcfg, seed: int):
    """The program's parameter tree holding the canonical numbers."""
    return program_maker(conf, mcfg)(seed_key(seed))
