"""Mesh construction for the production pods and local runs.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state. Multi-host process bring-up
(jax.distributed.initialize) is a documented no-op in this single-process
container; on a real pod slice the coordinator address comes from the
launcher env and the same mesh code runs unchanged.
"""
from __future__ import annotations

import jax


def _auto(n: int):
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_local_mesh(data: int = 1, model: int = 1):
    """Mesh over locally available devices (CPU smoke / single host)."""
    n = data * model
    devs = jax.devices()[:n]
    assert len(devs) == n, f"need {n} devices, have {len(jax.devices())}"
    return jax.make_mesh((data, model), ("data", "model"), devices=devs,
                            axis_types=_auto(2))


def maybe_init_distributed() -> None:
    """Multi-host bring-up hook. Single-process here; on a real TPU pod:
    jax.distributed.initialize(coordinator_address, num_processes, process_id)
    driven by the cluster launcher's env (GCE metadata / SLURM / k8s)."""
    import os

    if os.environ.get("REPRO_COORDINATOR"):
        jax.distributed.initialize()  # pragma: no cover (multi-host only)
