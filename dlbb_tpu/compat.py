"""The JAX names every shard_map call site in the repo imports from one
place (jax 0.9: ``jax.shard_map`` with ``check_vma`` / ``axis_names``,
``jax.lax.pcast``, ``jax.lax.axis_size``)."""

from __future__ import annotations

import jax

shard_map = jax.shard_map
pcast = jax.lax.pcast
axis_size = jax.lax.axis_size
