"""Logical-axis sharding: rules, resolution, divisibility fallback, trees.

Models annotate every parameter/activation dimension with a *logical* axis
name ("batch", "heads", "ff", ...). A rule-set maps logical names to mesh
axes; resolution turns a tuple of logical names into a PartitionSpec for a
concrete mesh. The contract:

* a rule value may be a mesh-axis name (``"model"``), a tuple of mesh-axis
  names (``("pod", "data")`` — sharded over the product), or ``None``;
* tuple rules are filtered to the axes present in the target mesh,
  preserving order (so the same rule-set works on single- and multi-pod
  meshes);
* a mesh axis is used at most once per spec — later duplicates replicate;
* unknown logical names replicate;
* ``divisible_spec`` drops any mesh axis that does not divide the concrete
  dimension (for tuples: the longest divisible prefix survives), so reduced
  smoke shapes lower cleanly on production meshes.

``use_sharding(mesh, rules)`` installs an ambient context that the models'
``constrain(x, *names)`` calls read; outside the context ``constrain`` is an
identity, which keeps single-device tests/benchmarks free of mesh plumbing.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

Rule = Union[str, Tuple[str, ...], None]
Rules = Dict[str, Rule]

# ---------------------------------------------------------------------------
# rule-sets
# ---------------------------------------------------------------------------
# Training default: Megatron-style tensor parallelism over "model", data
# parallelism over ("pod", "data").
MEGATRON_RULES: Rules = {
    "batch": ("pod", "data"),
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "experts": "model",
    "vocab": "model",
    "ssm_inner": "model",
    "ssm_heads": "model",
    "conv_dim": "model",
}

# Decode: keep the TP layout but let the (small) decode batch also absorb the
# "model" axis when divisible — at decode shapes the batch is the only large
# dimension, and the divisibility fallback drops the extra axis otherwise.
DECODE_RULES: Rules = dict(MEGATRON_RULES, batch=("pod", "data", "model"))

# Expert parallelism: experts across "model", everything else data-parallel.
EP_RULES: Rules = {
    "batch": ("pod", "data"),
    "experts": "model",
    "vocab": "model",
}

# Pure data parallelism: flatten every mesh axis into the batch.
DP_RULES: Rules = {
    "batch": ("pod", "data", "model"),
}

# DP + EP hybrid (MoE without tensor parallelism).
DPEP_RULES: Rules = {
    "batch": ("pod", "data"),
    "experts": "model",
}

# FSDP-flavored: parameters sharded along their "embed" dim over the data
# axis (gathered on use); activations stay batch-sharded (the duplicate-axis
# rule replicates "embed" wherever "batch" already took "data").
FSDP_RULES: Rules = {
    "batch": ("pod", "data"),
    "embed": "data",
    "vocab": "model",
}

RULE_SETS: Dict[str, Rules] = {
    "megatron": MEGATRON_RULES, "decode": DECODE_RULES, "ep": EP_RULES,
    "dp": DP_RULES, "dpep": DPEP_RULES, "fsdp": FSDP_RULES,
}


# ---------------------------------------------------------------------------
# resolution
# ---------------------------------------------------------------------------
def logical_spec(names: Sequence[Optional[str]], rules: Rules,
                 mesh) -> P:
    """Resolve logical axis names to a PartitionSpec on `mesh`.

    Tuple rules keep tuple form after filtering to the mesh's axes; each
    mesh axis is consumed at most once (later claims replicate).
    """
    mesh_axes = set(mesh.axis_names)
    used: set = set()
    entries = []
    for name in names:
        rule = rules.get(name) if name is not None else None
        entry: Rule = None
        if isinstance(rule, str):
            if rule in mesh_axes and rule not in used:
                entry = rule
                used.add(rule)
        elif isinstance(rule, tuple):
            keep = tuple(a for a in rule if a in mesh_axes and a not in used)
            if keep:
                entry = keep
                used.update(keep)
        entries.append(entry)
    return P(*entries)


def divisible_spec(mesh, spec: P, shape: Sequence[int]) -> P:
    """Drop mesh axes that do not divide the concrete dims of `shape`.

    For tuple entries the longest divisible *prefix* survives (a tuple
    shards over the product of its axes, in order). Singleton tuples
    collapse to the bare axis name.
    """
    sizes = dict(mesh.shape)
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        keep = []
        prod = 1
        for a in axes:
            if dim % (prod * sizes[a]) == 0:
                keep.append(a)
                prod *= sizes[a]
            else:
                break
        out.append(None if not keep
                   else keep[0] if len(keep) == 1 else tuple(keep))
    return P(*out)


def spec(names: Sequence[Optional[str]], rules: Rules, mesh,
         shape: Optional[Sequence[int]] = None) -> P:
    """logical_spec + (optional) divisibility fallback in one call."""
    s = logical_spec(names, rules, mesh)
    return s if shape is None else divisible_spec(mesh, s, shape)


def named_sharding(mesh, names: Sequence[Optional[str]], rules: Rules,
                   shape: Sequence[int]) -> NamedSharding:
    return NamedSharding(mesh, spec(names, rules, mesh, shape))


def _is_axes_leaf(x: Any) -> bool:
    return isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x)


def tree_shardings(mesh, axes_tree, rules: Rules, specs_tree):
    """Map a pytree of logical-axes tuples + a matching pytree of
    ShapeDtypeStructs (or arrays) to a pytree of NamedShardings."""
    return jax.tree.map(
        lambda ax, sds: named_sharding(mesh, ax, rules, sds.shape),
        axes_tree, specs_tree, is_leaf=_is_axes_leaf)


# ---------------------------------------------------------------------------
# ambient context for model-internal constraints
# ---------------------------------------------------------------------------
_CTX = threading.local()


@contextlib.contextmanager
def use_sharding(mesh, rules: Rules):
    """Install (mesh, rules) as the ambient sharding context; model code's
    `constrain` calls resolve against it (trace-time, so wrap jit/lower)."""
    prev = getattr(_CTX, "active", None)
    _CTX.active = (mesh, rules)
    try:
        yield
    finally:
        _CTX.active = prev


def current_sharding() -> Optional[Tuple[Any, Rules]]:
    return getattr(_CTX, "active", None)


def constrain(x, *names: Optional[str]):
    """Apply a with_sharding_constraint derived from logical `names` when a
    sharding context is active; identity otherwise."""
    ctx = current_sharding()
    if ctx is None:
        return x
    mesh, rules = ctx
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec(names, rules, mesh, x.shape)))


def per_batch_shard(fn, split, whole):
    """`fn(*split, *whole)` on each shard of the mesh axes that "batch"
    resolves to, with no exchange between the shards: each array of
    `split` is cut along its leading axis, each of `whole` is handed to
    every shard as it is (its other mesh axes stay the compiler's), and
    every output is cut along its leading axis. Outside a sharding
    context, or where "batch" resolves to no mesh axis, it is `fn`."""
    ctx = current_sharding()
    axes = None
    if ctx is not None:
        mesh, rules = ctx
        axes = spec(("batch",), rules, mesh, split[0].shape[:1])[0]
    if axes is None:
        return fn(*split, *whole)
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(P(axes),) * len(split) + (P(),) * len(whole),
        out_specs=P(axes), axis_names=frozenset(axes),
        check_vma=False)(*split, *whole)
