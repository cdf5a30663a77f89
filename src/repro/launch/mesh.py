"""Mesh construction: model meshes + the campaign cells mesh.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state — required for the dry-run's
``xla_force_host_platform_device_count`` trick to work, and for smoke tests
to keep seeing a single device.

The campaign half (DESIGN.md §14) describes the Monte-Carlo engine's
topology: a flat 1-D ``cells`` axis over the local devices of every
process in the job.  ``build_campaign_mesh`` is jax.distributed-aware —
on a real multi-host fleet ``jax.distributed.initialize`` sets the
process topology and each process shards its launches over its own local
devices; in single-process CI the same code path runs with
``process_count == 1`` and ``xla_force_host_platform_device_count``
providing the multi-device axis (``host_device_flag``).  Cross-process
coordination never uses collectives: processes rendezvous only through
the content-addressed campaign store (``campaign.cache`` claims), so a
mesh of hosts needs nothing but a shared cache directory.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the model code shards
    through ``with_sharding_constraint`` and leaves propagation to XLA,
    which the default explicit axes would refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod mesh: (data=16, model=16) = 256 chips; multi-pod adds a
    leading pod axis (2 pods = 512 chips) for cross-pod data parallelism."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Debug mesh over whatever devices exist (tests use 1-8 host devices)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return _auto_mesh((n // model, model), ("data", "model"))


def data_axes(mesh) -> tuple:
    """The axes that act as data parallel (pod folded into data)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


# ------------------------------------------------------------- campaigns

def host_device_flag(n: int) -> str:
    """The XLA flag that splits one host CPU into ``n`` devices — the CI /
    smoke-test stand-in for a real accelerator mesh (must be in XLA_FLAGS
    before the first jax import of the target process)."""
    return f"--xla_force_host_platform_device_count={int(n)}"


@dataclasses.dataclass(frozen=True)
class CampaignMesh:
    """Topology of one multi-device / multi-process campaign run.

    ``n_devices`` local devices shard the cells plane inside each launch
    (``engine._integrate_sharded``); ``process_index``/``process_count``
    partition whole launches across processes, which dedupe and exchange
    results through the content-addressed store (claims + slice
    checkpoints — DESIGN.md §14).  ``claim_ttl_s`` bounds how long a
    process waits on a peer's claimed launch before presuming the peer
    dead and stealing the work; ``poll_s`` is the store poll interval.
    """

    n_devices: int
    process_index: int = 0
    process_count: int = 1
    claim_ttl_s: float = 60.0
    poll_s: float = 0.05

    def __post_init__(self):
        assert self.n_devices >= 1, self.n_devices
        assert self.process_count >= 1, self.process_count
        assert 0 <= self.process_index < self.process_count, (
            self.process_index, self.process_count)
        assert self.claim_ttl_s > 0 and self.poll_s > 0


def build_campaign_mesh(
    devices: Optional[int] = None,
    process_index: Optional[int] = None,
    process_count: Optional[int] = None,
    *,
    elastic_from: Optional[int] = None,
    claim_ttl_s: float = 60.0,
    poll_s: float = 0.05,
) -> CampaignMesh:
    """The campaign mesh of this process, jax.distributed-aware.

    Process topology defaults to ``jax.process_index()`` /
    ``jax.process_count()`` — populated by ``jax.distributed.initialize``
    on multi-host fleets, 1/1 otherwise — and the device axis to every
    local device.  ``elastic_from=N`` marks a resume of a campaign that
    was checkpointed on ``N`` local devices: the device count then routes
    through ``runtime.elastic.plan_campaign_devices`` so a degraded host
    lands on a plan-blessed count (slice checkpoints are device-count-
    independent, so the resume stays bit-identical either way — the plan
    only keeps the shard shapes on the compile-cache-friendly ladder).
    """
    pi = jax.process_index() if process_index is None else int(process_index)
    pc = jax.process_count() if process_count is None else int(process_count)
    n = jax.local_device_count() if devices is None else min(
        int(devices), jax.local_device_count())
    if elastic_from is not None:
        from repro.runtime.elastic import plan_campaign_devices

        n = plan_campaign_devices(n, old_devices=int(elastic_from)).mesh_shape[0]
    return CampaignMesh(n_devices=n, process_index=pi, process_count=pc,
                        claim_ttl_s=claim_ttl_s, poll_s=poll_s)
