"""Ambient sharding context.

Model code annotates activations with *logical* axes via `constrain`;
outside a mesh context this is the identity, inside it becomes
`with_sharding_constraint` using the rules engine. This keeps the model
definitions mesh-agnostic (smoke tests on 1 CPU device, dry-run on 512).
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding

from repro.sharding import logical_to_spec

_CTX: contextvars.ContextVar = contextvars.ContextVar("shard_ctx", default=None)


@contextlib.contextmanager
def sharding_context(mesh: Mesh, rules=None):
    tok = _CTX.set((mesh, rules))
    try:
        with jax.set_mesh(mesh):
            yield
    finally:
        _CTX.reset(tok)


def current_mesh() -> Optional[Mesh]:
    ctx = _CTX.get()
    return ctx[0] if ctx else None


def current_rules():
    ctx = _CTX.get()
    return ctx[1] if ctx else None


def constrain(x, logical: Sequence[Optional[str]]):
    ctx = _CTX.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = logical_to_spec(logical, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
