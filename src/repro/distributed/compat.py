"""The one ``shard_map`` import site of the repo.

A thin keyword wrapper over ``jax.shard_map`` (``check_vma``,
``axis_names``), so call sites pass ``axis_names`` as any iterable and
leave unset options at JAX's defaults.
"""
from __future__ import annotations

import functools
from typing import Optional

from jax import shard_map as _shard_map


def shard_map(f=None, *, mesh, in_specs, out_specs,
              check_vma: Optional[bool] = None,
              axis_names=None):
    """``jax.shard_map`` with optional keywords left at JAX's defaults.

    ``axis_names``: the mesh axes that are manual inside ``f`` (all axes
    when None).  ``check_vma``: varying-manual-axes checking.
    """
    if f is None:
        return functools.partial(
            shard_map, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=check_vma, axis_names=axis_names)
    kwargs = {}
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      **kwargs)
