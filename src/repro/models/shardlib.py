"""Logical-axis sharding annotations (MaxText-style, minimal).

Model code annotates activations with *logical* axis names; a caller that
installs a mesh and a logical->mesh rule table (``use_sharding``) turns
them into sharding constraints.  With no mesh installed, as on every served
path today, each annotation is a no-op.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def _current():
    if not hasattr(_state, "stack"):
        _state.stack = []
    return _state.stack


@contextlib.contextmanager
def use_sharding(mesh: Mesh, rules: dict[str, object]):
    """rules: logical axis name -> mesh axis (str), tuple of axes, or None."""
    _current().append((mesh, dict(rules)))
    try:
        yield
    finally:
        _current().pop()


def active_rules() -> Optional[tuple[Mesh, dict]]:
    stack = _current()
    return stack[-1] if stack else None


def logical_to_spec(names: Sequence[Optional[str]], rules: dict) -> P:
    axes = []
    for n in names:
        if n is None:
            axes.append(None)
        else:
            axes.append(rules.get(n))
    return P(*axes)


def shard(x, *names: Optional[str]):
    """Annotate ``x`` whose dims carry the given logical names."""
    ctx = active_rules()
    if ctx is None:
        return x
    mesh, rules = ctx
    if x.ndim != len(names):
        raise ValueError(
            f"shard(): rank {x.ndim} array got {len(names)} logical names"
        )
    spec = logical_to_spec(names, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
