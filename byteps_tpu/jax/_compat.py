"""One import site for the two jax APIs every layer of the tree uses.

``shard_map`` and ``axis_size`` are plain aliases of ``jax.shard_map`` and
``jax.lax.axis_size`` (jax 0.9): ~45 call sites in the library, tests and
examples import them from here, so the names stay.
"""

from jax import shard_map  # noqa: F401
from jax.lax import axis_size  # noqa: F401
