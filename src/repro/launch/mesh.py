"""Production mesh construction (function, not constant — importing this
module never touches jax device state)."""
from __future__ import annotations

import jax

from repro.dist.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, model_parallel: int | None = None):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    mp = model_parallel or (2 if n % 2 == 0 and n > 1 else 1)
    return make_mesh((n // mp, mp), ("data", "model"))
