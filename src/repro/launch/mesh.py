"""Production mesh definitions.

Single pod: (data=16, model=16) = 256 chips.  Multi-pod: (pod=2, data=16,
model=16) = 512 chips — the ``pod`` axis carries the cross-region
"federated client group" semantics of the paper (aggregation over
(`pod`,`data`) is the server's Σ_i; XLA lowers it hierarchically:
in-pod reduce over ICI, cross-pod over DCN).

``make_production_mesh`` is a function (not a module constant) so importing
this module never touches jax device state.
"""
from __future__ import annotations

import jax

# TPU v5e hardware constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the engine places arrays with
    ``shard_map`` and ``PartitionSpec``, not with explicit-sharding types
    (which ``jax.make_mesh`` gives by default)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_client_mesh(num_shards: int = 0):
    """1-D mesh over the federated-client axis for the sharded engine.

    The engine shards each round's **participating cohort** (S clients)
    over this mesh — not the population: each of the ``num_shards``
    devices owns S / num_shards cohort slots of the round, uploads are
    computed shard-locally and the server aggregate is one psum over
    ``clients`` (the paper's Σ_i, lowered hierarchically by XLA exactly
    like the (`pod`,`data`) reduction of the production mesh).  The
    population size I never constrains the mesh — ``I=10_000, S=8`` runs
    on the same 2-device mesh as ``I=16`` — and cohorts are sentinel-
    padded up to a device multiple when num_shards ∤ S.
    ``num_shards=0`` uses every local device.
    """
    n = num_shards or jax.local_device_count()
    return make_mesh((n,), ("clients",))


def make_group_mesh(group_shards: int = 0, client_shards: int = 1):
    """2-D (groups, clients) mesh for the hierarchical two-level tree.

    The engine lays a round's (G groups × M members) grid directly onto
    this mesh: the ``groups`` axis shards the G edge aggregators
    (``group_shards`` must divide G), the ``clients`` axis shards the M
    members *within* each group (members are sentinel-padded up to a
    device multiple when client_shards ∤ M).  Level 1 of the tree is a
    psum over ``clients``, level 2 a psum over ``groups`` — the same
    in-pod-ICI / cross-pod-DCN lowering shape as the production
    (pod, data) reduction, which is exactly the physical topology an
    edge-aggregator deployment has.  ``group_shards=0`` spends every
    local device on the groups axis.
    """
    g = group_shards or max(1, jax.local_device_count() // client_shards)
    return make_mesh((g, client_shards), ("groups", "clients"))


def arena_axes(mesh) -> tuple:
    """The axes a **population-resident** (I, …) array's leading dim
    shards over under the engine's home-device arena: *every* axis of
    the federated mesh, in ``PartitionSpec`` order — ``("clients",)`` on
    the 1-D client mesh, ``("groups", "clients")`` flattened groups-
    major on the 2-D group mesh — so the arena composes with both mesh
    shapes and D is always the full device count.  (The *cohort*, by
    contrast, shards positionally: its layout is per-round, the arena's
    is per-client.)"""
    return tuple(mesh.axis_names)


def arena_spec(mesh):
    """PartitionSpec homing a leading client dim over the whole mesh
    (the spec behind :func:`repro.fed.arena.shard_spec` and the packed
    async ring's ``P(None, axes)`` column sharding)."""
    return jax.sharding.PartitionSpec(arena_axes(mesh))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """The axes the global batch (= federated clients) shards over."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def make_host_mesh():
    """1-device mesh for CPU smoke runs through the same code path."""
    return make_mesh((1, 1), ("data", "model"))
