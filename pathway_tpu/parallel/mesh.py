"""Mesh construction + sharding helpers.

Replaces the reference's worker/cluster configuration
(src/engine/dataflow/config.rs:88-127: PATHWAY_THREADS × PATHWAY_PROCESSES →
timely thread/TCP topology). Here the topology is a `jax.sharding.Mesh`
over TPU chips: the ``data`` axis carries keyspace/batch shards (what the
reference calls workers) and the ``model`` axis carries tensor-parallel
weight shards. Env vars:

- ``PATHWAY_DATA_PARALLEL``  — size of the data axis (default: all devices)
- ``PATHWAY_MODEL_PARALLEL`` — size of the model axis (default 1)

There is deliberately no 8-worker cap (the reference's free-tier
MAX_WORKERS, config.rs:7, is a license artifact, not a design point).
"""

from __future__ import annotations

import contextlib
import os
from dataclasses import dataclass

DATA_AXIS = "data"
MODEL_AXIS = "model"


def validate_shard_specs(mesh, in_specs, out_specs) -> None:
    """Raise a clear ValueError when a PartitionSpec names an axis the mesh
    does not have — otherwise the typo surfaces as an opaque error deep in
    jax's shard_map lowering. (The static counterpart — rank consistency
    against plan-propagated operand shapes — is PWT103 in
    internals/static_check/shard_check.py.)"""
    from jax.sharding import PartitionSpec

    axes = set(getattr(mesh, "axis_names", ()))
    if not axes:
        return

    def walk(spec):
        if spec is None:
            return
        if isinstance(spec, PartitionSpec):
            for entry in spec:  # iterates the per-dim entries
                names = entry if isinstance(entry, tuple) else (entry,)
                for a in names:
                    if a is not None and a not in axes:
                        raise ValueError(
                            f"shard_map spec names axis {a!r} but the mesh "
                            f"only has axes {sorted(axes)} (PWT103)")
            return
        if isinstance(spec, (list, tuple)):
            for s in spec:
                walk(s)

    walk(in_specs)
    walk(out_specs)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` behind :func:`validate_shard_specs`."""
    import jax

    validate_shard_specs(mesh, in_specs, out_specs)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


@dataclass(frozen=True)
class MeshConfig:
    data: int
    model: int = 1

    def validate(self, n_devices: int) -> list[str]:
        """Reasons this topology cannot tile ``n_devices`` chips (empty =
        fine). Shared by :meth:`from_env` and the static shard checker
        (PWT101) so eager and pre-execution validation agree."""
        problems = []
        if self.data < 1 or self.model < 1:
            problems.append(
                f"axis sizes must be positive, got data={self.data}, "
                f"model={self.model}")
            return problems
        n = self.data * self.model
        if n > n_devices:
            problems.append(
                f"mesh {self.data}x{self.model} needs {n} devices but "
                f"only {n_devices} are available")
        elif n_devices % n != 0:
            problems.append(
                f"mesh {self.data}x{self.model} covers {n} of {n_devices} "
                f"devices and {n} does not divide {n_devices} — "
                f"{n_devices - n} chips would sit idle")
        return problems

    @staticmethod
    def from_env(n_devices: int | None = None) -> "MeshConfig":
        import jax

        if n_devices is None:
            n_devices = len(jax.devices())
        model_env = os.environ.get("PATHWAY_MODEL_PARALLEL")
        data_env = os.environ.get("PATHWAY_DATA_PARALLEL")
        try:
            model = int(model_env) if model_env is not None else 1
            data = (int(data_env) if data_env is not None
                    else max(1, n_devices // model))
        except ValueError:
            raise ValueError(
                f"PATHWAY_DATA_PARALLEL={data_env!r} / "
                f"PATHWAY_MODEL_PARALLEL={model_env!r} must be positive "
                f"integers") from None
        config = MeshConfig(data=data, model=model)
        # validate eagerly: letting jax discover the mismatch later fails
        # deep in mesh construction with an opaque reshape error that
        # never names the env vars that caused it
        problems = config.validate(n_devices)
        if problems:
            raise ValueError(
                f"invalid mesh topology from environment "
                f"(PATHWAY_DATA_PARALLEL={data_env!r}, "
                f"PATHWAY_MODEL_PARALLEL={model_env!r}, {n_devices} "
                f"devices visible): " + "; ".join(problems))
        return config


def make_mesh(config: MeshConfig | None = None, *, devices=None):
    """Build a 2-D (data, model) Mesh over the given (or all) devices."""
    import jax
    import numpy as np

    if devices is None:
        devices = jax.devices()
    if config is None:
        config = MeshConfig.from_env(len(devices))
    n = config.data * config.model
    if n > len(devices):
        raise ValueError(
            f"mesh {config} needs {n} devices, have {len(devices)}")
    arr = np.asarray(devices[:n]).reshape(config.data, config.model)
    return jax.sharding.Mesh(arr, (DATA_AXIS, MODEL_AXIS))


_ACTIVE_MESH = None


def get_mesh():
    """The process-wide active mesh, creating a default one on first use."""
    global _ACTIVE_MESH
    if _ACTIVE_MESH is None:
        _ACTIVE_MESH = make_mesh()
    return _ACTIVE_MESH


def current_mesh():
    """The active mesh or None (never creates one)."""
    return _ACTIVE_MESH


@contextlib.contextmanager
def use_mesh(mesh):
    """Set the process-wide mesh for the duration of the block."""
    global _ACTIVE_MESH
    prev = _ACTIVE_MESH
    _ACTIVE_MESH = mesh
    try:
        yield mesh
    finally:
        _ACTIVE_MESH = prev


def shard_batch(mesh=None, *extra_axes):
    """NamedSharding placing dim 0 on the data axis, rest replicated."""
    import jax

    if mesh is None:
        mesh = get_mesh()
    spec = jax.sharding.PartitionSpec(DATA_AXIS, *extra_axes)
    return jax.sharding.NamedSharding(mesh, spec)


def replicated(mesh=None):
    import jax

    if mesh is None:
        mesh = get_mesh()
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
