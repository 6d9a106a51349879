"""Named-mesh registry.

TPU-native replacement for the reference's NCCL communicator registry
(reference: paddle/fluid/platform/collective_helper.h:63 NCCLCommContext —
process-global map ring_id→device→NCCLComm, populated by c_gen_nccl_id +
c_comm_init startup ops). Design delta (SURVEY.md §2.3, §5.8): communicators
become mesh AXES declared once; collectives become XLA HLO emitted by the
partitioner over ICI/DCN; there are no comm streams or sync ops to manage.

Axis-name conventions used across the framework:
  dp — data parallel         tp — tensor (model) parallel
  pp — pipeline parallel     sp — sequence/context parallel
  ep — expert parallel (MoE)
"""
from __future__ import annotations

import threading
from typing import Dict, Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["init_mesh", "init_hybrid_mesh", "get_mesh", "set_mesh",
           "reset_mesh", "mesh_axis_size", "in_spmd_region",
           "named_sharding", "MeshGuard", "auto_mesh", "shard_map",
           "axis_sizes", "axis_tiers", "LINK_TIERS", "DEFAULT_TIER"]

# ---------------------------------------------------------------------------
# two-tier topology grammar. A mesh description's axis value is either a
# plain int size (legacy form, link tier defaults to ICI) or a dict
#   {"size": 2, "tier": "dcn"[, "gbps": 25.0]}
# declaring the link tier the axis crosses: "ici" for intra-pod chip
# links, "dcn" for the inter-pod data-center network, an order of
# magnitude slower (SURVEY §2.3 DCN row; MLPerf TPU-v3 pod scaling).
# Per-device link bandwidths default from FLAGS_topology_{ici,dcn}_gbps
# so the cost model is tunable without touching call sites.
# ---------------------------------------------------------------------------

LINK_TIERS = ("ici", "dcn")
DEFAULT_TIER = "ici"


def _tier_gbps(tier: str) -> float:
    from ..core.flags import flag as _flag
    if tier == "dcn":
        return float(_flag("FLAGS_topology_dcn_gbps"))
    return float(_flag("FLAGS_topology_ici_gbps"))


def _axis_entry(value):
    """(size, tier_meta | None) for one axis value of a mesh description."""
    if isinstance(value, dict):
        size = int(value.get("size", 1))
        tier = str(value.get("tier", DEFAULT_TIER))
        if tier not in LINK_TIERS:
            raise ValueError(
                f"unknown link tier {tier!r} (choose from {LINK_TIERS})")
        gbps = float(value.get("gbps", _tier_gbps(tier)))
        return size, {"tier": tier, "gbps": gbps}
    return int(value), None


def axis_sizes(shape: Dict[str, object]) -> Dict[str, int]:
    """{axis: int} from a mesh description dict, tier grammar accepted."""
    return {str(k): _axis_entry(v)[0] for k, v in shape.items()}


def axis_tiers(mesh_or_shape) -> Dict[str, dict]:
    """{axis: {"tier": str, "gbps": float}} for every axis of a mesh
    description dict or a Mesh. Axes without declared tier metadata get
    the ICI default; a Mesh carries its tiers in `_link_tiers` (attached
    by init_mesh tier grammar / init_hybrid_mesh DCN layering)."""
    out: Dict[str, dict] = {}
    if mesh_or_shape is None:
        return out
    if isinstance(mesh_or_shape, dict):
        for k, v in mesh_or_shape.items():
            _, meta = _axis_entry(v)
            out[str(k)] = meta or {"tier": DEFAULT_TIER,
                                   "gbps": _tier_gbps(DEFAULT_TIER)}
        return out
    declared = dict(getattr(mesh_or_shape, "_link_tiers", {}) or {})
    for name in getattr(mesh_or_shape, "axis_names", ()):
        meta = declared.get(name)
        if isinstance(meta, str):
            meta = {"tier": meta, "gbps": _tier_gbps(meta)}
        out[str(name)] = dict(meta) if meta else \
            {"tier": DEFAULT_TIER, "gbps": _tier_gbps(DEFAULT_TIER)}
    return out


def shard_map(f, mesh=None, in_specs=None, out_specs=None, check=False,
              axis_names=None):
    """`jax.shard_map` with the varying-manual-axes check defaulting OFF —
    the pipeline/MoE SPMD programs here intermix psum/ppermute/all_to_all
    in ways the checker rejects spuriously. `axis_names` makes only those
    axes manual and leaves the rest to GSPMD (default: all of them)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check,
                         axis_names=frozenset(axis_names or ()))


_lock = threading.Lock()
_meshes: Dict[str, Mesh] = {}
_default_name: Optional[str] = None


def init_mesh(shape: Dict[str, int] = None, name: str = "default",
              devices=None) -> Mesh:
    """Declare a named mesh once (the c_comm_init analog).

    shape: ordered {axis_name: size}; product must equal device count.
    Axis values may use the tier grammar ({"size": 2, "tier": "dcn"}) —
    sizes build the device array, tier metadata rides the Mesh as
    `_link_tiers` for the topology cost model (axis_tiers).
    Defaults to a pure data-parallel mesh over all devices.
    """
    global _default_name
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = {"dp": len(devices)}
    tiers = {k: m for k, m in
             ((k, _axis_entry(v)[1]) for k, v in shape.items()) if m}
    shape = axis_sizes(shape)
    sizes = list(shape.values())
    need = int(np.prod(sizes))
    if need > len(devices):
        raise ValueError(
            f"mesh shape {shape} needs {need} devices, have {len(devices)}")
    arr = np.array(devices[:need]).reshape(sizes)  # sub-mesh allowed
    mesh = Mesh(arr, tuple(shape.keys()))
    # always (re)assign: jax interns equivalent Mesh objects, so a stale
    # _link_tiers from an earlier same-shape mesh must not leak through
    # (object.__setattr__ — jax's Mesh forbids ordinary reassignment)
    object.__setattr__(mesh, "_link_tiers", tiers)
    with _lock:
        _meshes[name] = mesh
        if _default_name is None or name == "default":
            _default_name = name
    return mesh


def init_hybrid_mesh(ici_shape: Dict[str, int],
                     dcn_shape: Dict[str, int] = None,
                     name: str = "default") -> Mesh:
    """Declare a mesh with DCN axes layered over per-slice ICI axes.

    Devices are grouped by slice (TPU `slice_index`; process index under
    the CPU emulation, where each host process stands in for a slice) and
    laid out so DCN axes vary slowest. Collectives over the inner (ICI)
    axes then stay inside a slice and only the outer (DCN) axes cross the
    data-center network — the dp-across-slices x tp-within-slice recipe
    (SURVEY §2.3 DCN row; replaces the reference's per-ring NCCL comm
    bootstrap gen_nccl_id_op_helper.cc:277).

      init_hybrid_mesh({"tp": 4}, {"dp": 2})   # 2 slices x 4 chips
    """
    devices = list(jax.devices())

    # group by TPU slice when the platform reports distinct slices;
    # otherwise by host process (the CPU emulation, where each process
    # stands in for a slice — and single-slice multi-host jobs, where DCN
    # crosses hosts)
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    use_slice = len(slice_ids) > 1 and None not in slice_ids

    def slice_of(d):
        return d.slice_index if use_slice else d.process_index

    groups: Dict[int, list] = {}
    for d in devices:
        groups.setdefault(slice_of(d), []).append(d)
    slices = [groups[k] for k in sorted(groups)]
    n_slices = len(slices)
    per_slice = len(slices[0])
    if any(len(s) != per_slice for s in slices):
        raise ValueError(
            f"uneven slices: {[len(s) for s in slices]} devices per slice")
    if dcn_shape is None:
        dcn_shape = {"dp": n_slices}
    overlap = set(dcn_shape) & set(ici_shape)
    if overlap:
        raise ValueError(
            f"axis name(s) {sorted(overlap)} appear in both dcn_shape and "
            "ici_shape; hybrid axes must be distinct (e.g. dp over DCN, "
            "tp/sp over ICI)")
    need_dcn = int(np.prod(list(dcn_shape.values())))
    need_ici = int(np.prod(list(ici_shape.values())))
    if need_dcn != n_slices:
        raise ValueError(
            f"dcn_shape {dcn_shape} needs {need_dcn} slices, have "
            f"{n_slices}")
    if need_ici != per_slice:
        raise ValueError(
            f"ici_shape {ici_shape} needs {need_ici} devices per slice, "
            f"have {per_slice}")
    arr = np.array([sorted(s, key=lambda d: d.id) for s in slices])
    arr = arr.reshape(list(dcn_shape.values()) + list(ici_shape.values()))
    mesh = Mesh(arr, tuple(dcn_shape.keys()) + tuple(ici_shape.keys()))
    # the DCN axes cross the slow tier by construction — tag them so the
    # topology cost model (axis_tiers / spmd_analyzer) prices them as such
    object.__setattr__(mesh, "_link_tiers", {
        ax: {"tier": "dcn", "gbps": _tier_gbps("dcn")} for ax in dcn_shape})
    return set_mesh(mesh, name)


def set_mesh(mesh: Mesh, name: str = "default"):
    global _default_name
    with _lock:
        _meshes[name] = mesh
        _default_name = name
    return mesh


def reset_mesh(name: str = None):
    """Drop a registered mesh (all of them when name is None). Mainly for
    tests: a leaked dp mesh silently turns every later single-device train
    step into a GSPMD-partitioned one."""
    global _default_name
    with _lock:
        if name is None:
            _meshes.clear()
            _default_name = None
        else:
            _meshes.pop(name, None)
            if _default_name == name:
                _default_name = next(iter(_meshes), None)


def get_mesh(name: str = None) -> Optional[Mesh]:
    with _lock:
        if name is not None:
            return _meshes.get(name)
        if _default_name is not None:
            return _meshes.get(_default_name)
    return None


def auto_mesh() -> Mesh:
    """Get-or-create the default mesh (pure DP over all devices)."""
    m = get_mesh()
    if m is None:
        m = init_mesh()
    return m


def _axis_env():
    """The current trace context's bound mesh axes (jax keeps this
    accessor private; jax 0.9.0 has no public equivalent)."""
    from jax._src.core import get_axis_env
    return get_axis_env()


def mesh_axis_size(axis: str, name: str = None) -> int:
    """Size of a mesh axis. Inside an SPMD region (shard_map trace)
    the BOUND axis size is authoritative — the registry may hold a
    different default mesh (e.g. a test registered `{"dp": 8}` as
    "default" while the pipeline runs under a named `{"pp": 4}` mesh;
    reading the registry there silently degraded the pipeline to a
    single stage). Falls back to the registered mesh when the axis is
    not bound in the current trace."""
    env = _axis_env()
    if axis in env.axis_names():
        return int(env.axis_size(axis))
    m = get_mesh(name)
    if m is None or axis not in m.axis_names:
        return 1
    return m.shape[axis]


def in_spmd_region(axis: str = None) -> bool:
    """True when tracing inside shard_map where `axis` (or, with
    axis=None, any axis) is bound — i.e. lax.psum(axis) is legal here."""
    names = _axis_env().axis_names()
    return bool(names) if axis is None else axis in names


def named_sharding(spec: PartitionSpec, name: str = None) -> NamedSharding:
    return NamedSharding(auto_mesh() if name is None else get_mesh(name), spec)


class MeshGuard:
    """`with MeshGuard(mesh):` — scope the jax mesh context manager."""

    def __init__(self, mesh: Mesh = None, name: str = None):
        self.name = name
        self.mesh = mesh or get_mesh(name)

    def __enter__(self):
        if self.mesh is None:
            with _lock:
                have = sorted(_meshes)
            want = self.name if self.name is not None else \
                "<default>"
            raise RuntimeError(
                f"MeshGuard: no mesh named {want!r} in the mesh registry "
                f"(registered: {have or 'none'}). Declare one with "
                "init_mesh({'dp': n, ...}) / init_hybrid_mesh(...) or "
                "pass a Mesh explicitly: MeshGuard(mesh)")
        self._cm = self.mesh
        self._cm.__enter__()
        return self.mesh

    def __exit__(self, *exc):
        return self._cm.__exit__(*exc)
