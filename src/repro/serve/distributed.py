"""Tensor-parallel sharded serving over a device mesh.

CPrune's premise is target-aware execution, and "the target" can be a
mesh, not a chip: this module takes a (possibly partition-stamped)
:class:`~repro.api.artifact.DeploymentArtifact` from single-device
serving to mesh-sharded serving.

:class:`ShardedServeEngine` is the :class:`~repro.serve.engine.ServeEngine`
with its arrays placed instead of its logic changed:

* params are ``jax.device_put`` with :class:`~jax.sharding.NamedSharding`
  resolved from :mod:`repro.sharding.rules` — the same trailing-dim rule
  table the training mesh uses, fitted to the serving mesh (axes a dim
  does not divide fall back to replicated);
* paged KV **pools** shard their ``n_kv_heads`` axis over ``model``; the
  paged decode step is traced under the mesh (``jax.set_mesh``) so the
  Pallas kernel, which GSPMD cannot partition, runs per KV-head shard
  through ``shard_map``;
  contiguous KV caches come out of the jitted prefill already placed by
  GSPMD propagation from the sharded params;
* paged **block tables** stay host-side numpy exactly as before and are
  consumed replicated, so admission/compaction remain pointer rewrites —
  sharding never touches the allocator;
* the decode/prefill step functions are the engine's own jits: tracing
  happens on first call with committed sharded inputs, so GSPMD
  partitions the very same jaxpr the single-device engine runs. Greedy
  decode therefore reproduces the tp=1 token stream (enforced
  bit-identical by tests/test_distributed_serve.py).

The mesh is ``(data, model)`` as built by
:func:`repro.launch.mesh.make_test_mesh` /
:func:`~repro.launch.mesh.make_production_mesh`; on CPU CI
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` makes tp=2 real.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional, Union

import jax
import numpy as np

from repro.launch.mesh import MeshError, make_test_mesh
from repro.serve.engine import ServeEngine
from repro.sharding import rules

__all__ = ["ShardedServeEngine", "MeshError", "mesh_for_artifact",
           "validate_mesh"]


def mesh_for_artifact(artifact) -> "jax.sharding.Mesh":
    """The default serving mesh for a partition-stamped artifact: all of
    the model axis (``tp`` shards), no data parallelism — ``(1, tp)``.
    Raises :class:`MeshError` naming the device shortfall when the host
    cannot express it."""
    tp = int(getattr(artifact, "tp", 1))
    return make_test_mesh(n_devices=tp, model=tp)


def validate_mesh(mesh, *, tp: Optional[int] = None,
                  what: str = "artifact") -> int:
    """Check a serving mesh carries a ``model`` axis and (when ``tp`` is
    given) that the axis matches the requested/partitioned degree.
    Returns the mesh's model degree. Errors name the mesh shape, never
    just "mismatch"."""
    shape = dict(mesh.shape)
    if "model" not in shape:
        raise MeshError(
            f"serving mesh must carry a 'model' axis for tensor "
            f"parallelism; got mesh axes {tuple(shape)} (shape {shape})")
    mtp = int(shape["model"])
    if tp is not None and tp > 1 and mtp != tp:
        raise MeshError(
            f"{what} is partitioned for tp={tp} model shards but the "
            f"mesh's model axis is {mtp} (mesh shape {shape}) — rebuild "
            f"the mesh with model={tp} (e.g. "
            f"make_test_mesh(n_devices={tp}, model={tp}))")
    return mtp


def _pool_pspecs(pools, mesh):
    """Paged pool specs: ``(n_blocks, block_size, n_kv, head_dim)`` (plus
    an optional leading stack axis) with the KV-head axis over ``model``
    — the same head sharding the contiguous cache rules use, expressed on
    the pool layout. Falls back to replicated when heads don't divide."""
    return jax.tree.map(
        lambda x: rules.fit_spec((None, None, "model", None),
                                 np.shape(x), mesh), pools)


class ShardedServeEngine(ServeEngine):
    """A :class:`ServeEngine` whose params and KV storage live sharded on
    a ``(data, model)`` mesh. Scheduling, admission, compaction, fault
    handling, and stats are inherited unchanged — only array placement
    differs, so every supervisor/router/autopilot layer stacks on top
    exactly as for the single-device engine."""

    def __init__(self, cfg, params, *, mesh, **kw):
        self.mesh = mesh
        self.tp = validate_mesh(mesh, what=cfg.name)
        super().__init__(cfg, params, **kw)
        # place params per the rule table; jits trace lazily, so their
        # first call sees committed sharded inputs and GSPMD partitions
        # the identical single-device jaxpr under the mesh
        self.param_pspecs = rules.param_pspecs(self.params, mesh)
        self.params = jax.device_put(
            self.params, rules.shardings_of(self.param_pspecs, mesh))
        if self.kv_layout == "paged":
            # shard the pools' KV-head axis; block tables remain host
            # numpy (PagedSlotGroup) and enter each step replicated
            self._pools = jax.device_put(
                self._pools,
                rules.shardings_of(_pool_pspecs(self._pools, mesh), mesh))
            # trace decode under the mesh: the Pallas paged kernel cannot
            # be auto-partitioned and runs per KV-head shard instead
            # (repro.models.attention._paged_kernel)
            decode = self._decode_paged

            def decode_in_mesh(*args):
                with jax.set_mesh(mesh):
                    return decode(*args)
            self._decode_paged = decode_in_mesh

    def stats(self) -> Dict[str, Any]:
        out = super().stats()
        out["mesh"] = {k: int(v) for k, v in dict(self.mesh.shape).items()}
        out["tp"] = self.tp
        return out

    @classmethod
    def for_artifact(cls, artifact: Union[str, "os.PathLike", Any], *,
                     mesh=None, **kw) -> "ShardedServeEngine":
        """Build a sharded engine for an artifact (path or instance).

        ``mesh=None`` on a partition-stamped artifact gets the default
        ``(1, tp)`` mesh; an explicit mesh is validated against the
        artifact's partition (errors name the mesh shape). Unpartitioned
        artifacts may also be served sharded — the partition stamp is a
        pricing/validation record, the layout itself always derives from
        the sharding rules."""
        if isinstance(artifact, (str, os.PathLike)):
            from repro.api.artifact import DeploymentArtifact
            artifact = DeploymentArtifact.load(os.fspath(artifact))
        if mesh is None:
            mesh = mesh_for_artifact(artifact)
        validate_mesh(mesh, tp=int(getattr(artifact, "tp", 1)),
                      what=f"artifact {artifact.measurement_tag!r}")
        return ServeEngine.from_artifact.__func__(
            cls, artifact, mesh=mesh, **kw)
