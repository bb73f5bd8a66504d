"""Device-mesh and sharding setup (the reference's ``setup_sharding``, redone).

Reference behavior being rebuilt (``/root/reference/JAX-DevLab-Examples.py:
19-85``): read the ``parallelization`` config block; validate
``num_tiles = 6 * tiles_per_edge**2`` against the device count with
remediation-text errors; build a device mesh and a ``NamedSharding`` that
partitions the panel axis; support a virtual-CPU-device tier for testing.

TPU-native redesign:
  * 3-D mesh ``('panel', 'y', 'x')`` instead of the reference's 1-D
    ``('tiles',)`` — panels shard over 'panel', and each panel's interior
    block-decomposes over 'y' x 'x' (the reference's planned
    ``tiles_per_edge > 1``, which it explicitly left unimplemented at
    ``JAX-DevLab-Examples.py:31-37``).
  * The virtual-device flag ordering bug is avoided: we never set
    ``XLA_FLAGS`` after backend init; the testing tier *requests* CPU
    devices (``jax.devices('cpu')``) which works under any default
    platform, and documents that the host-device-count flag must be set
    before Python starts (tests/conftest.py does it).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import Config, ParallelConfig
from ..utils.logging import get_logger

__all__ = ["ShardingSetup", "available_devices", "setup_sharding",
           "shard_state", "setup_ensemble_sharding",
           "shard_ensemble_state", "member_spec", "member_shard_map"]

log = get_logger(__name__)


@dataclasses.dataclass(frozen=True)
class ShardingSetup:
    mesh: Optional[Mesh]
    num_devices: int
    panel: int
    sy: int
    sx: int
    use_shard_map: bool = False
    overlap_exchange: bool = False
    temporal_block: int = 1
    #: member-axis extent of the device mesh (ensemble runs on the 2-D
    #: ``('panel', 'member')`` mesh from :func:`setup_ensemble_sharding`;
    #: 1 everywhere else).
    member: int = 1

    @property
    def scalar_spec(self) -> P:
        return P("panel", "y", "x")

    def spec_for(self, ndim: int) -> P:
        """PartitionSpec for an array whose last 3 axes are (6, ny, nx)."""
        return P(*((None,) * (ndim - 3) + ("panel", "y", "x")))

    def sharding_for(self, ndim: int):
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec_for(ndim))

    def ensemble_spec_for(self, ndim: int) -> P:
        """PartitionSpec for a batched-ensemble array whose last 4 axes
        are ``(B, 6, ny, nx)`` (member axis immediately before the face
        axis, the :data:`...shallow_water_cov.ENSEMBLE_STATE_AXES`
        layout).  On the ``('panel', 'member')`` mesh the member axis
        shards over 'member' and faces over 'panel'; on the 1-D
        ``('member',)`` mesh (layout='member' — any device count, zero
        wire traffic) only the member axis shards; on the plain
        ``('panel', 'y', 'x')`` mesh members are replicated (stacked
        locally per face device)."""
        axes = self.mesh.axis_names if self.mesh is not None else ()
        if "member" in axes:
            tail = ("member", "panel" if "panel" in axes else None,
                    None, None)
        else:
            tail = (None, "panel", "y", "x")
        return P(*((None,) * (ndim - 4) + tail))

    def ensemble_sharding_for(self, ndim: int):
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.ensemble_spec_for(ndim))


def available_devices(kind: str = "cpu"):
    """Every device of ``kind`` — the pool a placement planner sizes
    against (``'default'`` = the default platform).  ``'tpu'`` means
    ``jax.devices('tpu')`` and nothing else: a host without a TPU
    raises, never silently lands elsewhere.  The ONE device-selection
    rule — :func:`_pick_devices` is this plus a count requirement."""
    kind = (kind or "cpu").lower()
    if kind == "default":
        return jax.devices()
    if kind in ("cpu", "tpu", "gpu"):
        try:
            return jax.devices(kind)
        except RuntimeError as e:
            raise RuntimeError(
                f"device_type {kind!r} requested but this host has no "
                f"{kind} devices ({e})") from e
    raise ValueError(
        f"unknown device_type {kind!r}; use 'cpu', 'tpu' or 'gpu'")


def _pick_devices(kind: str, count: int):
    devs = available_devices(kind)
    if len(devs) < count:
        raise ValueError(
            f"requested num_devices={count} but only {len(devs)} {kind} devices "
            f"exist. For CPU testing, start Python with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={count}."
        )
    return devs[:count]


def _factor_mesh(num_devices: int, tiles_per_edge: int):
    """(panel, sy, sx) device-mesh dims for D devices at tiling t."""
    p = math.gcd(num_devices, 6)
    rest = num_devices // p
    # Near-square split of the per-panel block grid.
    sy = int(math.sqrt(rest))
    while rest % sy:
        sy -= 1
    sx = rest // sy
    if sy > tiles_per_edge or sx > tiles_per_edge:
        raise ValueError(
            f"num_devices={num_devices} needs a {sy}x{sx} sub-panel split but "
            f"tiles_per_edge={tiles_per_edge} only allows up to "
            f"{tiles_per_edge}x{tiles_per_edge}; raise tiles_per_edge."
        )
    return p, sy, sx


def _coerce_parallel_config(config: Any) -> ParallelConfig:
    """Config / ParallelConfig / raw reference-style dict / None -> the
    ParallelConfig — ONE coercion for every mesh entry point, so a new
    field cannot be silently dropped in one of them."""
    if isinstance(config, Config):
        return config.parallelization
    if isinstance(config, ParallelConfig):
        return config
    if config is None:
        return ParallelConfig()
    # raw dict, reference-style: config['parallelization'].get(...)
    block = dict(config.get("parallelization", {}))
    known = {f.name for f in dataclasses.fields(ParallelConfig)}
    return ParallelConfig(**{k: v for k, v in block.items()
                             if k in known})


def setup_sharding(config: Any = None) -> ShardingSetup:
    """Build the device mesh + shardings from a Config (or its dict form)."""
    par = _coerce_parallel_config(config)

    t = par.tiles_per_edge
    if t < 1:
        raise ValueError(f"tiles_per_edge must be >= 1, got {t}")
    if par.temporal_block < 1:
        raise ValueError(
            f"temporal_block must be >= 1, got {par.temporal_block}")
    num_tiles = 6 * t * t
    d = par.num_devices
    if d > num_tiles:
        raise ValueError(
            f"num_devices={d} exceeds num_tiles={num_tiles} "
            f"(= 6 * tiles_per_edge^2). Reduce num_devices or raise "
            f"tiles_per_edge."
        )
    if num_tiles % d != 0:
        divisors = [k for k in range(1, num_tiles + 1) if num_tiles % k == 0]
        raise ValueError(
            f"num_tiles={num_tiles} is not divisible by num_devices={d}. "
            f"Valid device counts: {divisors}."
        )

    if d == 1:
        log.info("sharding: single device (no mesh)")
        return ShardingSetup(mesh=None, num_devices=1, panel=1, sy=1, sx=1,
                             temporal_block=par.temporal_block)

    p, sy, sx = _factor_mesh(d, t)
    devs = np.array(_pick_devices(par.device_type, d)).reshape(p, sy, sx)
    mesh = Mesh(devs, ("panel", "y", "x"))
    log.info(
        "sharding: %d %s devices as mesh panel=%d y=%d x=%d (tiles_per_edge=%d)",
        d, par.device_type, p, sy, sx, t,
    )
    return ShardingSetup(mesh=mesh, num_devices=d, panel=p, sy=sy, sx=sx,
                         use_shard_map=par.use_shard_map,
                         overlap_exchange=par.overlap_exchange,
                         temporal_block=par.temporal_block)


def shard_state(setup: ShardingSetup, state):
    """device_put every leaf with the rank-appropriate (panel,y,x) spec."""
    if setup.mesh is None:
        return state
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, setup.sharding_for(a.ndim)), state
    )


def setup_ensemble_sharding(config: Any = None,
                            members: int = 1,
                            layout: str = "auto") -> ShardingSetup:
    """2-D ``('panel', 'member')`` device mesh for batched ensemble runs.

    The ensemble workload has two data-parallel axes: the six cube faces
    (halo exchange along it) and the ``B`` perturbed-IC members (no
    communication at all).  This factors ``num_devices = 6 * m`` into a
    ``(panel=6, member=m)`` mesh — faces exchange over 'panel' exactly as
    on the face tier (a ``ppermute`` naming only 'panel' is per-member-
    column automatically), members scatter over 'member' with zero
    wire traffic.  Each device then holds ``B / m`` members of one face,
    and the batched exchange ships their strips in ONE ppermute per
    schedule stage.

    When to prefer member-sharding over face-sharding: with more than 6
    devices the face tier is out of axes (tiles_per_edge intra-face
    blocks add seam traffic), while extra member shards are free —
    docs/USAGE.md "Ensembles" quantifies the trade.  ``members`` must be
    divisible by ``m`` so every device carries the same member count.

    ``layout='member'`` (round 12) builds a 1-D ``('member',)`` mesh
    instead: ONLY the member axis shards, one member column per device
    — any device count that divides ``members`` works (no
    multiple-of-6 constraint) because members never communicate.  This
    is the GSPMD path's layout (the vmapped stepper partitions over
    the member axis with zero wire traffic); the explicit
    ``use_shard_map`` steppers need the panel axis and reject it.
    ``'auto'``/``'panel_member'`` are the 2-D mesh above.
    """
    par = _coerce_parallel_config(config)
    if members < 1:
        raise ValueError(f"members must be >= 1, got {members}")
    if layout not in ("auto", "panel_member", "member"):
        raise ValueError(
            f"ensemble layout {layout!r}; valid: 'auto' (the 2-D "
            f"('panel', 'member') mesh), 'panel_member' (same, "
            f"explicit), 'member' (1-D member-only mesh)")
    d = par.num_devices
    if d == 1:
        log.info("ensemble sharding: single device (no mesh), %d members "
                 "stacked locally", members)
        return ShardingSetup(mesh=None, num_devices=1, panel=1, sy=1, sx=1,
                             temporal_block=par.temporal_block)
    if layout == "member":
        if par.use_shard_map:
            raise ValueError(
                "ensemble.layout: member is the GSPMD layout (the "
                "member axis only); the explicit shard_map steppers "
                "exchange over the panel axis — set use_shard_map: "
                "false, or layout: panel_member with num_devices a "
                "multiple of 6")
        if members % d:
            raise ValueError(
                f"ensemble.layout: member shards {members} members over "
                f"{d} devices, which must divide evenly; use a device "
                f"count that divides members (or fewer devices)")
        devs = np.array(_pick_devices(par.device_type, d))
        mesh = Mesh(devs, ("member",))
        log.info("ensemble sharding: %d %s devices as 1-D member mesh "
                 "(%d members -> %d per device)", d, par.device_type,
                 members, members // d)
        return ShardingSetup(mesh=mesh, num_devices=d, panel=1, sy=1,
                             sx=1, overlap_exchange=par.overlap_exchange,
                             temporal_block=par.temporal_block, member=d)
    if d % 6:
        raise ValueError(
            f"ensemble sharding factors num_devices as 6 faces x m member "
            f"shards; num_devices={d} is not a multiple of 6. Valid "
            f"counts: 6, 12, 18, ... (or num_devices: 1 for the "
            f"single-device batched stepper).")
    m = d // 6
    if members % m:
        raise ValueError(
            f"ensemble.members={members} is not divisible by the member-"
            f"shard count {m} (= num_devices/6); every device must carry "
            f"the same number of members. Use members that {m} divides, "
            f"or fewer devices.")
    devs = np.array(_pick_devices(par.device_type, d)).reshape(6, m)
    mesh = Mesh(devs, ("panel", "member"))
    log.info("ensemble sharding: %d %s devices as mesh panel=6 member=%d "
             "(%d members -> %d per device)", d, par.device_type, m,
             members, members // m)
    return ShardingSetup(mesh=mesh, num_devices=d, panel=6, sy=1, sx=1,
                         use_shard_map=par.use_shard_map,
                         overlap_exchange=par.overlap_exchange,
                         temporal_block=par.temporal_block, member=m)


def shard_ensemble_state(setup: ShardingSetup, state):
    """device_put a batched ensemble state (leaves ``(.., B, 6, ny, nx)``
    in the member-before-face layout) with the ensemble specs."""
    if setup.mesh is None:
        return state
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, setup.ensemble_sharding_for(a.ndim)),
        state)


def member_spec(axis: int) -> P:
    """PartitionSpec splitting one array's member axis ``axis`` over
    the 1-D ``('member',)`` mesh; every other axis stays whole."""
    return P(*((None,) * axis + ("member",)))


def member_shard_map(fn, mesh: Mesh, in_axes, out_axes):
    """``fn`` run per chip on its own members: ``shard_map`` over the
    ``('member',)`` mesh, each argument and result split along the
    member axis that ``in_axes`` / ``out_axes`` (pytrees of ints
    matching them) name.  ``fn`` sees the local batch ``B // chips``
    and issues no collective, so members never leave their chip."""
    from jax import shard_map

    specs = lambda axes: jax.tree_util.tree_map(member_spec, axes)
    return shard_map(fn, mesh=mesh, in_specs=specs(in_axes),
                     out_specs=specs(out_axes), check_vma=False)
