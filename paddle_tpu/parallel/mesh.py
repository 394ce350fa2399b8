"""Device mesh construction.

TPU-native replacement for the reference's device topology machinery
(platform/nccl_helper.h NCCLContextMap, gen_nccl_id_op rendezvous): a
jax.sharding.Mesh over local or multi-host devices. Multi-host bootstrap
(the gen_nccl_id equivalent) is jax.distributed.initialize — see
parallel/distributed.py.

Axis convention (used across the framework):
  dp — data parallel (batch)        sp — sequence/context parallel
  tp — tensor/model parallel        ep — expert parallel
  pp — pipeline stages
Any subset may be present; size-1 axes are free.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_default_mesh: Optional[Mesh] = None

DP, TP, PP, SP, EP = "dp", "tp", "pp", "sp", "ep"


@dataclass(frozen=True)
class Topology:
    """Device-topology description for the placement planner
    (analysis/planner.py): what hardware a plan is searched FOR, not
    what this process can see — a laptop plans for a 2-host v5e pod.

    chip      PEAK_TABLE key (analysis/cost.py): per-chip peak flops,
              HBM bandwidth, ICI bandwidth, and HBM capacity.
    n_devices total chips across all hosts.
    hosts     host count; chips_per_host = n_devices // hosts. Mesh axes
              are laid out row-major (make_mesh), so the OUTERMOST axes
              are the ones that cross the host boundary.
    dci_gbps  per-chip inter-host (DCN) bandwidth — the tier a collective
              pays when any of its axes spans hosts; ICI otherwise.
    ici_gbps  intra-host tier override; None = the chip's PEAK_TABLE
              link bandwidth. Override it when planning for a fabric
              whose effective collective bandwidth differs from the
              chip's spec sheet — e.g. the 8-virtual-device CPU mesh the
              dryrun suite measures on, where a "collective" is memcpy +
              thread synchronization, nowhere near 10 GB/s effective.
    hbm_gb    per-chip HBM budget override; None = the chip's PEAK_TABLE
              capacity.
    """

    chip: str = "tpu v5e"
    n_devices: int = 8
    hosts: int = 1
    dci_gbps: float = 25.0
    ici_gbps: Optional[float] = None
    hbm_gb: Optional[float] = None

    def __post_init__(self):
        if self.n_devices < 1 or self.hosts < 1:
            raise ValueError(f"topology needs >=1 device and host, got "
                             f"{self.n_devices} devices / {self.hosts} hosts")
        if self.n_devices % self.hosts:
            raise ValueError(f"{self.n_devices} devices do not spread "
                             f"evenly over {self.hosts} hosts")

    @property
    def chips_per_host(self) -> int:
        return self.n_devices // self.hosts

    def chip_spec(self):
        # the topology's chip is an explicit user-declared TARGET (bare
        # generations like "v5e" allowed): a typo'd name must raise, not
        # silently price the pod with wrong peaks
        from ..analysis.cost import PEAK_TABLE
        kind = self.chip.lower()
        for cand in (kind, "tpu " + kind):  # bare generations: "v5e"
            for spec in PEAK_TABLE:
                if spec.name in cand:
                    return spec
        raise ValueError(
            f"topology chip {self.chip!r} does not name a PEAK_TABLE "
            f"chip ({sorted(s.name for s in PEAK_TABLE)})")

    def hbm_bytes(self) -> float:
        gb = self.hbm_gb if self.hbm_gb is not None \
            else self.chip_spec().hbm_gb
        return float(gb) * 1e9

    def ici_bandwidth_gbps(self) -> float:
        if self.ici_gbps is not None:
            return float(self.ici_gbps)
        return float(self.chip_spec().ici_gbps)

    def to_dict(self) -> dict:
        # hbm_gb recorded UNROUNDED: validate_plan re-derives the budget
        # from this field, and a rounded-down budget would reject plans
        # the search's own (exact) gate admitted
        return {"chip": self.chip, "n_devices": int(self.n_devices),
                "hosts": int(self.hosts), "dci_gbps": float(self.dci_gbps),
                "ici_gbps": self.ici_bandwidth_gbps(),
                "hbm_gb": self.hbm_bytes() / 1e9}

    @staticmethod
    def from_dict(d: dict) -> "Topology":
        """Rebuild from to_dict() output (plan artifacts record this)."""
        return Topology(chip=str(d.get("chip", "cpu")),
                        n_devices=int(d.get("n_devices", 8)),
                        hosts=int(d.get("hosts", 1)),
                        dci_gbps=float(d.get("dci_gbps", 25.0)),
                        ici_gbps=(None if d.get("ici_gbps") is None
                                  else float(d["ici_gbps"])),
                        hbm_gb=(None if d.get("hbm_gb") is None
                                else float(d["hbm_gb"])))

    @staticmethod
    def parse(spec: str) -> "Topology":
        """Parse 'chip:chips_per_host[xhosts][@dci=][@ici=][@hbm=]' —
        e.g. 'v5e:8' (one host), 'v5p:4x2@dci=50' (8 chips over 2
        hosts), 'cpu:8@ici=1@hbm=16' (the PT_PLAN_TOPOLOGY format;
        bandwidths in GB/s, hbm in GB)."""
        head, *opts = spec.strip().split("@")
        chip, _, devs = head.partition(":")
        if not devs:
            raise ValueError(f"topology {spec!r}: expected chip:devices")
        per_host, _, hosts = devs.partition("x")
        hosts = int(hosts) if hosts else 1
        kw: Dict[str, float] = {}
        names = {"dci": "dci_gbps", "ici": "ici_gbps", "hbm": "hbm_gb"}
        for opt in opts:
            k, _, v = opt.partition("=")
            if k not in names or not v:
                raise ValueError(f"topology {spec!r}: unknown option "
                                 f"{opt!r} (dci=GBPS / ici=GBPS / hbm=GB)")
            kw[names[k]] = float(v)
        return Topology(chip=chip.strip(),
                        n_devices=int(per_host) * hosts, hosts=hosts, **kw)


def make_mesh(axes: Optional[Dict[str, int]] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a Mesh. axes maps axis name -> size; one size may be -1 to
    absorb the remaining devices (like a reshape)."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if not axes:
        axes = {DP: n}
    names = list(axes)
    sizes = list(axes.values())
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = n // known
    total = int(np.prod(sizes))
    if total != n:
        raise ValueError(f"mesh axes {dict(zip(names, sizes))} need {total} "
                         f"devices, have {n}")
    arr = np.array(devices).reshape(sizes)
    return Mesh(arr, tuple(names))


def default_mesh() -> Mesh:
    global _default_mesh
    if _default_mesh is None:
        _default_mesh = make_mesh()
    return _default_mesh


def set_default_mesh(mesh: Mesh):
    global _default_mesh
    _default_mesh = mesh


def spec_for(var_sharding: Optional[Tuple], mesh: Mesh) -> PartitionSpec:
    """VarDesc.sharding tuple -> PartitionSpec, dropping axes the mesh lacks."""
    if not var_sharding:
        return PartitionSpec()
    dims = []
    for d in var_sharding:
        if d is None:
            dims.append(None)
        elif isinstance(d, (list, tuple)):
            kept = tuple(a for a in d if a in mesh.axis_names)
            dims.append(kept if kept else None)
        else:
            dims.append(d if d in mesh.axis_names else None)
    while dims and dims[-1] is None:
        dims.pop()
    return PartitionSpec(*dims)


def named(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))


def mesh_from_plan(plan, devices: Optional[Sequence] = None) -> Mesh:
    """Build the Mesh a PlacementPlan (analysis/planner.py) was scored
    for, preserving the plan's axis ORDER (outermost first — the order
    the planner's host-boundary pricing assumed). Uses the first
    n_devices local devices unless `devices` is given."""
    axes = {str(a): int(s) for a, s in dict(plan["mesh"]).items()}
    n = int(np.prod(list(axes.values())))
    if devices is None:
        devices = jax.devices()[:n]
    return make_mesh(axes, devices=devices)
