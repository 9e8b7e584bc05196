"""Device-mesh construction.

This is the framework's replacement for the reference's process topology —
where the reference identifies a "shard" with a gRPC server process at an IP
(ref: generate.py:17, shard/openai_api.py:621-627), here a stage is a slice
of a ``jax.sharding.Mesh`` and topology is declared once, not dialed.

Axis conventions (the names the rest of the codebase shards against):
  dp — data / batch replication
  pp — pipeline stages (the reference's only axis, §2.3)
  sp — sequence/context parallelism (ring attention)
  tp — tensor parallelism within a stage
  ep — expert parallelism rides on tp for MoE layers

Multi-host: callers run ``jax.distributed.initialize()`` first (DCN), then
``make_mesh`` over ``jax.devices()`` spans hosts; mesh-axis order puts tp/sp
innermost so their collectives ride ICI, pp/dp outermost so stage hops and
gradient syncs cross DCN only when they must (scaling-book recipe).
"""

from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh

AXIS_DP = "dp"
AXIS_PP = "pp"
AXIS_SP = "sp"
AXIS_EP = "ep"
AXIS_TP = "tp"

# outermost → innermost; innermost axes get the fastest interconnect links
MESH_AXIS_ORDER = (AXIS_DP, AXIS_PP, AXIS_SP, AXIS_EP, AXIS_TP)


def make_mesh(
    dp: int = 1, pp: int = 1, sp: int = 1, tp: int = 1, ep: int = 1, devices=None
) -> Mesh:
    if devices is None:
        devices = jax.devices()
    n = dp * pp * sp * ep * tp
    if n > len(devices):
        raise ValueError(
            f"mesh dp={dp} pp={pp} sp={sp} ep={ep} tp={tp} needs {n} devices, "
            f"have {len(devices)}"
        )
    grid = np.asarray(devices[:n]).reshape(dp, pp, sp, ep, tp)
    return Mesh(grid, MESH_AXIS_ORDER)


def mesh_fingerprint(mesh: Mesh) -> str:
    """Stable identity of a mesh's placement: axis geometry plus the exact
    device grid, in order. This is the placement half of a
    ``weights.WeightKey`` — resident arrays are device-addressed, so WHERE
    a weight tree lives is part of WHAT it is, and two replicas may alias
    one tree only when their meshes print the same fingerprint."""
    axes = ",".join(f"{k}={v}" for k, v in mesh.shape.items())
    devs = ",".join(str(d.id) for d in mesh.devices.flat)
    return f"{axes}|{devs}"


def same_mesh_devices(a: Mesh, b: Mesh) -> bool:
    """True when two meshes span identical device grids — same axis sizes,
    same devices, same order. That is the condition for arrays placed
    against one mesh to feed programs shard_mapped over the other without
    a cross-device transfer (jit rejects a device-set mismatch outright),
    i.e. for a ``ResidentWeights`` built on ``a`` to be aliased by an
    engine running on ``b``."""
    return (
        dict(a.shape) == dict(b.shape)
        and [d.id for d in a.devices.flat] == [d.id for d in b.devices.flat]
    )


def pipeline_mesh(num_stages: int, devices=None) -> Mesh:
    """1-D pipeline mesh — the parity topology (reference §2.3: PP is the
    only strategy)."""
    return make_mesh(pp=num_stages, devices=devices)
