"""Weights from the seed, on the device, in one jitted call.

The program's parameter tree (names, shapes and dtypes) is read with
``jax.eval_shape``; the values come from the configuration's ``init``
rules, keyed by the leaf's name (``"mixer.w_r"`` before ``"w_r"``):

    ["const", v]              every element v
    ["uniform", lo, hi]       U[lo, hi)
    ["fan_in", axes, gain]    N(0, 1) * gain / sqrt(prod of those axes)

Axes count in one layer's shape: leaves under ``segments`` carry a leading
layer axis, which the rule skips. The benchmark, not the program, makes
the weights, so the plain reference can be given the same ones.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def leaf_name(path) -> tuple[str, ...]:
    return tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def rule_for(rules: dict, path: tuple[str, ...]):
    for name in (".".join(path[-2:]), path[-1]):
        if name in rules:
            return rules[name]
    raise KeyError(f"no init rule for leaf {'/'.join(path)}")


def _value(key, shape, dtype, rule, stacked: bool):
    kind = rule[0]
    if kind == "const":
        return jnp.full(shape, rule[1], dtype)
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, rule[1],
                                  rule[2]).astype(dtype)
    if kind == "fan_in":
        axes = [a + 1 for a in rule[1]] if stacked else list(rule[1])
        gain = rule[2] if len(rule) > 2 else 1.0
        fan = math.prod(shape[a] for a in axes)
        return (jax.random.normal(key, shape, jnp.float32)
                * (gain / math.sqrt(fan))).astype(dtype)
    raise ValueError(f"unknown init rule {rule!r}")


def maker(shapes, rules: dict):
    """A jitted ``seed key -> params`` for the tree of ``shapes``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    plan = [(leaf_name(p), s.shape, s.dtype) for p, s in flat]
    for path, _, _ in plan:
        rule_for(rules, path)           # fail before any compile

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(plan))
        leaves = [_value(k, shape, dtype, rule_for(rules, path),
                         path[0] == "segments")
                  for k, (path, shape, dtype) in zip(keys, plan)]
        return jax.tree.unflatten(treedef, leaves)

    return make
