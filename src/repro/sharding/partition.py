"""Logical-axis sharding: model code names axes, a rules table maps them to
mesh axes (the MaxText/flax-linen 'logical axes' pattern, dependency-free).

Model code calls `constrain(x, ("batch", "seq", "embed"))`. When a mesh and
rule-set are active (see `axis_rules`), this lowers to
jax.lax.with_sharding_constraint with the mapped PartitionSpec; with no mesh
active it is a no-op, so the same model runs single-device tests unchanged.

Logical axes used across the framework:
  batch       — global batch            -> ("pod", "data") | ("data",)
  seq         — sequence                -> None (or "model" for long-ctx SP)
  embed       — d_model features        -> None in activations
  heads       — attention heads         -> "model"
  kv_heads    — KV heads                -> "model" when divisible, else None
  mlp         — FFN hidden              -> "model"
  vocab       — vocabulary              -> "model"
  experts     — MoE experts             -> "model" (expert parallelism)
  fsdp        — param dim sharded FSDP  -> "data"
  kv_batch    — decode KV-cache batch   -> ("pod", "data") | ("data",)
  kv_seq      — decode KV-cache length  -> None | "model" (paged, MQA archs)
  stage       — reserved (pipeline)     -> None
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def make_auto_mesh(shape, axes) -> Mesh:
    """jax.make_mesh with every axis Auto (sharding propagated by XLA)."""
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


DEFAULT_RULES: dict[str, object] = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "fsdp": "data",
    "kv_batch": ("pod", "data"),
    "kv_seq": None,
    "kv_hd": None,
    "stage": None,
}


def _current():
    if not hasattr(_state, "stack"):
        _state.stack = []
    return _state.stack


@contextlib.contextmanager
def axis_rules(mesh: Mesh, rules: Optional[dict] = None):
    """Activate (mesh, logical->mesh rules) for constrain() calls within."""
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    # Drop mappings referring to axes the mesh does not have (e.g. "pod" on
    # the single-pod mesh).
    names = set(mesh.axis_names)

    def _filter(v):
        if v is None:
            return None
        if isinstance(v, str):
            return v if v in names else None
        t = tuple(a for a in v if a in names)
        return t if t else None

    merged = {k: _filter(v) for k, v in merged.items()}
    _current().append((mesh, merged))
    try:
        yield
    finally:
        _current().pop()


def active_mesh() -> Optional[Mesh]:
    st = _current()
    return st[-1][0] if st else None


def active_axis_size(logical_name: str) -> int:
    """Mesh-axis product a logical axis maps to under the active rules
    (1 when no mesh is active or the axis is unmapped). Model code uses
    this to pick between sharding layouts (e.g. head-TP vs context-parallel
    attention when head counts don't divide the tensor axis)."""
    st = _current()
    if not st:
        return 1
    mesh, rules = st[-1]
    return _axis_size(mesh, rules.get(logical_name))


def logical_to_spec(logical: Sequence[Optional[str]]) -> P:
    """Map logical axis names to a PartitionSpec under the active rules."""
    st = _current()
    if not st:
        return P(*([None] * len(logical)))
    _, rules = st[-1]
    return P(*[rules.get(a) if a is not None else None for a in logical])


def _dedup(parts):
    """Drop mesh axes already used earlier in the spec (GSPMD requires each
    mesh axis to appear at most once per PartitionSpec)."""
    used: set[str] = set()
    out = []
    for p in parts:
        if p is None:
            out.append(None)
            continue
        axes = (p,) if isinstance(p, str) else tuple(p)
        kept = tuple(a for a in axes if a not in used)
        used.update(kept)
        out.append(kept[0] if len(kept) == 1 else (kept or None))
    return out


def constrain(x: jax.Array, logical: Sequence[Optional[str]]) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op without active mesh.

    Uneven shardings are allowed here (GSPMD pads); duplicate mesh axes
    within one spec are resolved first-come-first-served.
    """
    st = _current()
    if not st:
        return x
    mesh, rules = st[-1]
    parts = [rules.get(a) if a is not None else None for a in logical]
    spec = P(*_dedup(parts))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _axis_size(mesh: Mesh, part) -> int:
    if part is None:
        return 1
    if isinstance(part, str):
        return mesh.shape[part]
    n = 1
    for a in part:
        n *= mesh.shape[a]
    return n


def checked_spec(mesh: Mesh, rules: dict, logical, shape) -> P:
    """Spec for a jit input: divisibility-enforced (pjit requires it) and
    mesh-axis-deduped. Non-dividing mappings are dropped (replicated)."""
    parts = []
    for dim, name in zip(shape, logical):
        p = rules.get(name) if name is not None else None
        if p is not None and dim % _axis_size(mesh, p) != 0:
            p = None
        parts.append(p)
    return P(*_dedup(parts))


def struct_shardings(struct_tree, axes_tree, mesh: Mesh, rules: Optional[dict] = None):
    """NamedShardings for a pytree of ShapeDtypeStructs/arrays given their
    logical-axes tree — divisibility- and duplicate-checked per leaf."""
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    names = set(mesh.axis_names)

    def _filter(v):
        if v is None:
            return None
        if isinstance(v, str):
            return v if v in names else None
        t = tuple(a for a in v if a in names)
        return t if t else None

    merged = {k: _filter(v) for k, v in merged.items()}

    def one(struct, logical):
        if logical is None or not hasattr(struct, "shape") or struct.ndim == 0:
            return NamedSharding(mesh, P())
        assert len(logical) == struct.ndim, f"axes {logical} vs shape {struct.shape}"
        return NamedSharding(mesh, checked_spec(mesh, merged, logical, struct.shape))

    return jax.tree.map(
        one,
        struct_tree,
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(e, (str, type(None))) for e in x),
    )


def named_sharding(logical: Sequence[Optional[str]]) -> Optional[NamedSharding]:
    st = _current()
    if not st:
        return None
    mesh, _ = st[-1]
    return NamedSharding(mesh, logical_to_spec(logical))


def tree_shardings(logical_tree, mesh: Mesh, rules: Optional[dict] = None):
    """Map a pytree of logical-axis tuples to NamedShardings (for jit)."""
    with axis_rules(mesh, rules):
        return jax.tree.map(
            lambda lg: named_sharding(lg),
            logical_tree,
            is_leaf=lambda x: isinstance(x, tuple)
            and all(isinstance(e, (str, type(None))) for e in x),
        )
