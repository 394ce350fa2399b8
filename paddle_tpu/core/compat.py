"""The few jax entry points this package reaches through one spelling.

Written for the installed jax (0.9): there is one installation, so there
are no fallbacks to older locations or kwarg names here.
"""

from __future__ import annotations

import jax


def enable_x64(new_val: bool = True):
    """The jax.enable_x64 context manager."""
    return jax.enable_x64(new_val)


def jax_export():
    """The jax.export module (imported here so a bare `jax.export`
    attribute access never races the submodule import)."""
    import jax.export
    return jax.export


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """jax.shard_map with this package's default of check_vma=False."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
