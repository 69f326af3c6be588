"""Logical-axis sharding: rules mapping model-logical axes to mesh axes.

Model code annotates activations with *logical* axes:

    'dp'  -- batch-parallel dimension (maps to ('pod', 'data'))
    'tp'  -- tensor-parallel dimension (maps to 'model')
    'ep'  -- expert-parallel dimension (maps to 'model' when the expert
             count divides the model-axis size, else dropped in favour
             of intra-expert TP)

``activate(mesh)`` installs the mapping; without an active mapping
every constraint is a no-op, so smoke tests and CPU examples run on a
single device unmodified.  Constraints whose dimension size does not
divide the mapped mesh-axis size are dropped per-dimension (e.g. a
25-head attention cannot head-shard over a 16-way model axis; XLA then
chooses the layout, typically gathering).

``param_specs`` derives the parameter PartitionSpec tree from array
paths + shapes -- the single source of truth for weight layouts used
by the dry-run, the trainer and the checkpointing code.
"""
from __future__ import annotations

import contextlib
import re
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

# logical -> physical mesh axis (or tuple of axes)
DEFAULT_RULES: Dict[str, object] = {
    "dp": ("pod", "data"),    # batch parallelism
    "tp": "model",            # tensor parallelism
    "ep": "model",            # expert parallelism (when E divides)
    "fsdp": "data",           # ZeRO-3-style weight sharding over data
    "sp": "model",            # sequence parallelism (residual stream)
}

# Serving rules: weights stay STATIONARY (tensor-parallel only).  FSDP
# is a training optimisation -- at decode batch sizes the per-layer
# weight all-gathers it implies dominate the step, while bf16 TP-only
# weights fit HBM comfortably (see EXPERIMENTS.md SPerf, decode cells).
SERVE_RULES: Dict[str, object] = {
    "dp": ("pod", "data"),
    "tp": "model",
    "ep": "model",
    "fsdp": None,
    "sp": "model",
}

_STATE = {"mesh": None, "rules": dict(DEFAULT_RULES)}


@contextlib.contextmanager
def activate(mesh, rules: Optional[Dict[str, object]] = None):
    """Install the logical->physical mapping for ``constrain``."""
    prev = dict(_STATE)
    _STATE["mesh"] = mesh
    _STATE["rules"] = dict(rules or DEFAULT_RULES)
    try:
        yield
    finally:
        _STATE.update(prev)


def _axis_size(mesh, phys) -> int:
    if isinstance(phys, (tuple, list)):
        n = 1
        for a in phys:
            n *= mesh.shape.get(a, 1)
        return n
    return mesh.shape.get(phys, 1)


def _phys_for(mesh, logical) -> Optional[object]:
    phys = _STATE["rules"].get(logical)
    if phys is None:
        return None
    if isinstance(phys, (tuple, list)):
        present = tuple(a for a in phys if a in mesh.shape)
        return present or None
    return phys if phys in mesh.shape else None


def logical_spec(shape: Tuple[int, ...], axes: Tuple[Optional[str], ...]
                 ) -> P:
    """Resolve logical axes against the active mesh into a
    PartitionSpec, dropping non-divisible dimensions and suppressing
    duplicate physical axes (e.g. MoE 'ep' and 'tp' both map to
    'model': whichever resolves first wins, the other is dropped)."""
    mesh = _STATE["mesh"]
    if mesh is None:
        return P()
    spec = []
    used = set()
    for dim, logical in zip(shape, axes):
        phys = _phys_for(mesh, logical) if logical else None
        if phys is not None:
            phys_set = set(phys) if isinstance(phys, tuple) else {phys}
            if phys_set & used:
                phys = None
        if phys is not None and dim % _axis_size(mesh, phys) == 0:
            spec.append(phys)
            used |= set(phys) if isinstance(phys, tuple) else {phys}
        else:
            spec.append(None)
    return P(*spec)


def axis_size(logical: str) -> int:
    """Mesh size behind a logical axis (1 when no mesh is active) --
    lets model code pick between alternative sharding layouts (e.g.
    head- vs sequence-sharded attention)."""
    mesh = _STATE["mesh"]
    if mesh is None:
        return 1
    phys = _phys_for(mesh, logical)
    return _axis_size(mesh, phys) if phys is not None else 1


def constrain(x, axes: Tuple[Optional[str], ...]):
    """with_sharding_constraint on logical axes; no-op w/o active mesh."""
    mesh = _STATE["mesh"]
    if mesh is None:
        return x
    from jax.sharding import NamedSharding
    spec = logical_spec(x.shape, axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Parameter layouts
# ---------------------------------------------------------------------------

# (path regex, logical axes per dim) -- first match wins.  Paths look
# like 'embed', 'layers/attn/wq', 'layers/moe/w_gate', ...  Stacked
# layer params carry a leading L dim (axis None).
# Weights shard 2-D: 'tp' (model axis) on the contraction-free dim and
# 'fsdp' (data axis, ZeRO-3 style) on the other large dim; XLA inserts
# the per-layer all-gathers / reduce-scatters this implies.
_PARAM_RULES = [
    (r"embed$",               ("tp", "fsdp")),               # (V, d)
    (r"lm_head$",             ("fsdp", "tp")),               # (d, V)
    (r"layers/.*attn/w[qkv]$", (None, "fsdp", "tp")),        # (L, d, H*hd)
    (r"layers/.*attn/wo$",    (None, "tp", "fsdp")),         # (L, H*hd, d)
    (r"layers/.*attn/b[qkv]$", (None, "tp")),                # (L, H*hd)
    (r"layers/.*attn/[qk]_norm$", (None, None)),             # (L, hd)
    (r"layers/.*mlp/w_(gate|up)$", (None, "fsdp", "tp")),    # (L, d, ff)
    (r"layers/.*mlp/w_down$", (None, "tp", "fsdp")),         # (L, ff, d)
    (r"layers/moe/router$",   (None, "fsdp", None)),         # (L, d, E)
    (r"layers/moe/w_(gate|up)$", (None, "ep", "fsdp", "tp")),  # (L,E,d,ff)
    (r"layers/moe/w_down$",   (None, "ep", "tp", "fsdp")),   # (L,E,ff,d)
    (r"layers/.*ssm/w_in$",   (None, "fsdp", "tp")),         # (L, d, 2d_in)
    (r"layers/.*ssm/conv_w$", (None, None, "tp")),           # (L, k, d_in)
    (r"layers/.*ssm/w_bcdt$", (None, "tp", None)),           # (L, d_in, *)
    (r"layers/.*ssm/a_log$",  (None, "tp", None)),           # (L, d_in, N)
    (r"layers/.*ssm/(d_skip|dt_bias)$", (None, "tp")),       # (L, d_in)
    (r"layers/.*ssm/w_out$",  (None, "tp", "fsdp")),         # (L, d_in, d)
    (r"layers/.*mlstm/w_up$", (None, "fsdp", "tp")),         # (L, d, 2d_in)
    (r"layers/.*mlstm/w[qkv]$", (None, "fsdp", "tp")),       # (L, d_in, d_in)
    (r"layers/.*mlstm/w_if$", (None, "fsdp", None)),         # (L, d_in, 2H)
    (r"layers/.*mlstm/ln$",   (None, None)),
    (r"layers/.*mlstm/w_down$", (None, "tp", "fsdp")),       # (L, d_in, d)
    (r"layers/.*slstm/w_gates$", (None, "fsdp", "tp")),      # (L, d, 4d)
    (r"layers/.*slstm/r_gates$", (None, None, None, None)),  # (L,H,4hd,hd)
    (r"layers/.*slstm/w_up$", (None, "fsdp", "tp")),
    (r"layers/.*slstm/w_down$", (None, "tp", "fsdp")),
    (r"layers/.*slstm/ln$",   (None, None)),
    (r".*norm.*$",            None),                         # replicated
    (r".*$",                  None),
]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def spec_for_path(path: str, shape: Tuple[int, ...]) -> P:
    """Match a parameter path against the rules.  Paths may carry any
    prefix (state trees embed the param tree under params/mu/nu) and
    Quant8-wrapped moments are matched via their parent path."""
    # Quant8 leaves: .../<param>/q (payload, param-shaped) and
    # .../<param>/scale (replicated per-block scales).
    if path.endswith("/scale") or path.endswith("/1"):
        return P()
    if path.endswith("/q") or path.endswith("/0"):
        head = path.rsplit("/", 1)[0]
        if re.search(r"(embed|lm_head|w[a-z_]*|r_gates|router|a_log|"
                     r"conv_w|d_skip|dt_bias|b[qkv])$", head):
            path = head
    for pattern, axes in _PARAM_RULES:
        if re.search(pattern, path):
            if axes is None:
                return P()
            axes = tuple(axes[: len(shape)]) + (None,) * (len(shape) - len(axes))
            return logical_spec(shape, axes)
    return P()


def tree_shardings(mesh, tree):
    """NamedSharding pytree for any state tree (params, optimizer
    moments, train state) via path-based rules."""
    from jax.sharding import NamedSharding

    def build(path, leaf):
        return NamedSharding(mesh, spec_for_path(_path_str(path),
                                                 leaf.shape))

    return jax.tree_util.tree_map_with_path(build, tree)


def param_specs(params) -> object:
    """PartitionSpec pytree matching ``params`` (requires an active
    mesh via ``activate``; otherwise everything is replicated)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    specs = {}

    def build(path, leaf):
        return spec_for_path(_path_str(path), leaf.shape)

    return jax.tree_util.tree_map_with_path(build, params)


def named_shardings(mesh, params):
    from jax.sharding import NamedSharding
    specs = param_specs(params)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P))

