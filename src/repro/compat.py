"""Thin helpers over the installed jax's mesh and compile APIs.

``jax.make_mesh`` builds ``Explicit`` axes by default; the repo's
placement rules (``sharding.fleet``, ``sharding.rules``) rely on GSPMD
propagating shardings through jitted steps, so every mesh here is built
with all axes in ``Auto`` mode.
"""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis in Auto (GSPMD) mode."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names), **kwargs)


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict (empty when the backend
    reports none)."""
    return dict(compiled.cost_analysis() or {})
