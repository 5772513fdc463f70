"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches JAX device state — the dry-run sets XLA_FLAGS before any jax import
to fabricate 512 host devices; tests and benches must keep seeing 1 device.
"""

from __future__ import annotations


# Both meshes use Auto axes: ``jax.make_mesh`` defaults to Explicit axes,
# under which the model's unannotated gathers (the embedding lookup) raise
# ``ShardingTypeError`` instead of letting the partitioner place them.


def make_production_mesh(*, multi_pod: bool = False):
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_local_mesh(model: int = 1, data: int = 1):
    """Small mesh over however many devices exist (CPU tests)."""
    import jax
    n = len(jax.devices())
    assert model * data <= n, f"need {model * data} devices, have {n}"
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~per-direction)
HBM_BYTES = 16 * 2 ** 30        # 16 GiB per chip
