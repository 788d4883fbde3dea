"""The one spelling of shard_map this tree uses (jax 0.9).

``jax.shard_map`` with the ``check_vma`` keyword, and ``lax.axis_size``
for the static size of a named mesh axis inside mapped code.  Callers
import the three names from here so the spelling lives in one place.
"""

from jax import shard_map
from jax.lax import axis_size

#: kwargs disabling the output-replication check
NO_CHECK = {"check_vma": False}

__all__ = ["shard_map", "NO_CHECK", "axis_size"]
