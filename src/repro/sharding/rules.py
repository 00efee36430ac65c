"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Models annotate activations with *logical* axis names; a ``Rules`` object maps
them to mesh axes.  Outside a mesh context (CPU smoke tests) every helper is a
no-op, so the same model code runs unsharded.
"""
from __future__ import annotations

import contextlib
import math
import threading
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisVal = Union[None, str, Tuple[str, ...]]

# logical axis -> mesh axis (single-pod default). ``batch`` picks up the extra
# ``pod`` axis on the multi-pod mesh.
SINGLE_POD_MAPPING = {
    "batch": "data",
    "fed_group": "data",          # federated groups live on the data axis
    "seq": None,
    "d_model": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "ffn": "model",
    "vocab": "model",
    "experts": "model",
    "expert_cap": None,
    "conv": None,
    "ssm_heads": "model",
    "ssm_state": None,
    "classes": None,
    "stack": None,                # stacked-layer leading axis from scan
}

MULTI_POD_OVERRIDES = {
    "batch": ("pod", "data"),
    "fed_group": ("pod", "data"),
}


class Rules:
    def __init__(self, mesh: Mesh, mapping: Optional[dict] = None):
        self.mesh = mesh
        m = dict(SINGLE_POD_MAPPING)
        if "pod" in mesh.axis_names:
            m.update(MULTI_POD_OVERRIDES)
        if mapping:
            m.update(mapping)
        self.mapping = m

    def with_overrides(self, **overrides) -> "Rules":
        """New Rules with some logical axes remapped (e.g. inside the fed
        group-local region, ``batch``/``seq`` must NOT claim the fed axes)."""
        m = dict(self.mapping)
        m.update(overrides)
        r = Rules.__new__(Rules)
        r.mesh = self.mesh
        r.mapping = m
        return r

    # -- spec construction -------------------------------------------------
    def _mesh_size(self, axis: AxisVal) -> int:
        if axis is None:
            return 1
        if isinstance(axis, tuple):
            return math.prod(self.mesh.shape[a] for a in axis)
        return self.mesh.shape[axis]

    def spec(self, logical: Sequence[Optional[str]],
             shape: Optional[Sequence[int]] = None) -> P:
        """PartitionSpec for logical axes; drops mesh axes that don't divide."""
        parts = []
        for i, name in enumerate(logical):
            ax = self.mapping.get(name) if name else None
            if ax is not None and shape is not None:
                if shape[i] % self._mesh_size(ax) != 0:
                    ax = None  # non-divisible (e.g. smollm 9 heads on 16-way TP)
            parts.append(ax)
        return P(*parts)

    def sharding(self, logical: Sequence[Optional[str]],
                 shape: Optional[Sequence[int]] = None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical, shape))


_local = threading.local()


def active_rules() -> Optional[Rules]:
    return getattr(_local, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[Rules]):
    prev = getattr(_local, "rules", None)
    _local.rules = rules
    try:
        yield rules
    finally:
        _local.rules = prev


def shard(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Apply a sharding constraint if rules are active; identity otherwise."""
    rules = active_rules()
    if rules is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, rules.sharding(logical, getattr(x, "shape", None)))


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map``: the one spelling every call site goes through."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


# ----------------------------------------------------------------------
# name-based parameter sharding: leaf path keywords -> logical axes per ndim.
# Parameters created by repro.models use these canonical names.
_PARAM_LOGICAL = {
    "embed": ("vocab", "d_model"),
    "lm_head": ("d_model", "vocab"),
    "patch_proj": ("d_model", "d_model"),
    "wq": ("d_model", "heads"),
    "wk": ("d_model", "kv_heads"),
    "wv": ("d_model", "kv_heads"),
    "wo": ("heads", "d_model"),
    "w_gate": ("d_model", "ffn"),
    "w_up": ("d_model", "ffn"),
    "w_down": ("ffn", "d_model"),
    "router": ("d_model", None),
    # expert weights shard on the expert axis only (EP); ffn dim stays local
    "e_gate": ("experts", None, None),
    "e_up": ("experts", None, None),
    "e_down": ("experts", None, None),
    "in_proj": ("d_model", None),
    "out_proj": (None, "d_model"),
    "conv_w": ("conv", None),
    "a_log": (None,),
    "ssm_d": (None,),
    "dt_bias": (None,),
    # cnn / misc
    "conv1": (None, None, None, None),
    "conv2": (None, None, None, None),
    "fc1": (None, "ffn"),
    "fc2": ("ffn", None),
}


def logical_axes_for(path: str, ndim: int) -> Tuple[Optional[str], ...]:
    """Logical axes of a parameter given its (dot-joined) tree path."""
    leaf = path.split("/")[-1]
    base = _PARAM_LOGICAL.get(leaf)
    if base is None:
        return (None,) * ndim
    if len(base) == ndim:
        return base
    if len(base) < ndim:
        # stacked by scan over layers / hybrid groups / within-group index:
        # any number of leading 'stack' axes (jamba has two)
        return ("stack",) * (ndim - len(base)) + tuple(base)
    return (None,) * ndim


def param_shardings(rules: Rules, params):
    """NamedSharding pytree for a parameter pytree (by leaf path names)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        keys = []
        for p in path:
            if hasattr(p, "key"):
                keys.append(str(p.key))
            elif hasattr(p, "idx"):
                keys.append(str(p.idx))
        logical = logical_axes_for("/".join(keys), leaf.ndim)
        out.append(rules.sharding(logical, leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, out)
