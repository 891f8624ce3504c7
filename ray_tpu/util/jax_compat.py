"""``shard_map`` with this tree's default: replication checking off.

Every shard_map call site in the tree goes through :func:`shard_map`. The
collective programs differentiate inside the mapped body with hand-written
collective pairs (``models/llama.tp_psum_pair``), which ``check_vma=True``
(jax's default) rejects, so the default here is off and a caller that wants
the check asks for it.
"""

from __future__ import annotations

import jax


def shard_map(fn, *, mesh, in_specs, out_specs, check: bool = False):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
