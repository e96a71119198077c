"""Process-group topology (L3) — mesh-axis factorization.

TPU-native re-derivation of reference ``deepspeed/utils/groups.py:55-588`` +
``runtime/pipe/topology.py``: instead of materializing rank lists and creating
NCCL communicators per group, we build ONE global 5-axis
``jax.sharding.Mesh``

    (pp, dp, ep, sp, tp)   — pipeline / expert-data / expert / sequence /
                             tensor axes

where the FULL data-parallel degree is the product of ("dp", "ep") — see the
axis-name comment below.  ZeRO secondary-partition (hpZ) groups live on a
separate reshaped mesh.  Any communication "group" is then just a tuple of
axis names (see ``deepspeed_tpu.comm.backend.ProcessGroup``), and XLA lays the
collectives onto ICI along those axes.

Axis order: the *rightmost* mesh axes are most-minor (fastest-varying device
index) and therefore map to physically-closest chips; we order
(pp, dp, ep, sp, tp) so tensor-parallel collectives (latency-bound, per-layer)
ride the shortest ICI hops, matching how Megatron orders NCCL groups.
"""

import os
from dataclasses import dataclass, field

import numpy as np

import jax
from jax.sharding import Mesh

from .logging import logger

# Canonical axis names, most-major → most-minor.  The global mesh is ALWAYS
# 5-axis (pp, dp, ep, sp, tp): "dp" is the expert-data-parallel part and the
# full data-parallel degree is the product of ("dp", "ep") — when ep=1 they
# coincide.  Keeping expert parallelism as a first-class axis of the ONE
# global mesh (instead of the reference's separate expert process groups,
# utils/groups.py:117-310) lets a single jitted step shard experts over "ep"
# while ZeRO shards state over ("dp","ep").
PP_AXIS = "pp"
DP_AXIS = "dp"
SP_AXIS = "sp"
TP_AXIS = "tp"
EP_AXIS = "ep"
EDP_AXIS = DP_AXIS  # expert-data-parallel IS the dp axis
# hpZ (ZeRO++ secondary partition) axes: dp = zp_outer × zp
ZP_AXIS = "zp"
ZP_OUTER_AXIS = "zp_outer"

_mesh_state = None


@dataclass
class MeshState:
    mesh: Mesh
    pp: int
    dp: int  # TOTAL data-parallel degree (= mesh dp × ep)
    sp: int
    tp: int
    ep: int = 1
    # hpZ mesh reshapes dp → (zp_outer, zp); params secondarily replicated
    # within the (intra-host) zp axis
    hpz_mesh: Mesh = None
    zero_partition_size: int = None  # hpZ secondary partition (ranks per shard group)


def _check_sizes(total, pp, dp, sp, tp):
    if pp * dp * sp * tp != total:
        raise ValueError(
            f"pp({pp}) * dp({dp}) * sp({sp}) * tp({tp}) = {pp*dp*sp*tp} "
            f"!= device count {total}")


def _physical_device_grid(shape, devices):
    """Physically-aware device layout (plain reshape ignores ICI topology —
    hpZ's intra-host promise and multi-slice DCN both need real placement):

    * multi-slice pods: ``create_hybrid_device_mesh`` puts the slice (DCN)
      factor outermost on the dp axis, so ZeRO reduce-scatter segments ride
      ICI within a slice and only the final combine crosses DCN;
    * single slice: ``create_device_mesh`` orders devices so most-minor mesh
      axes (tp, sp) map to nearest ICI neighbors — and the hpZ ``zp`` inner
      factor of dp (derived by reshape of this grid) stays on adjacent
      chips.  (On a 2x2 v5e host the 5-axis dp=4 mesh comes out in ring
      order, devices 0,1,3,2.)

    CPU/virtual devices have no topology: plain reshape.  On TPU devices a
    construction failure raises — a linear order handed back behind a
    warning is a run without the locality the mesh axes promise.
    """
    if devices.flat[0].platform != "tpu" or devices.size == 1:
        return devices.reshape(shape)
    from jax.experimental import mesh_utils
    slices = {getattr(d, "slice_index", 0) for d in devices.flat}
    n_slices = len(slices)
    if n_slices > 1 and shape[1] % n_slices == 0:
        per_slice = list(shape)
        per_slice[1] //= n_slices
        dcn = [1] * len(shape)
        dcn[1] = n_slices  # DCN axis folded into dp, slice-major
        return mesh_utils.create_hybrid_device_mesh(
            per_slice, dcn, devices=list(devices.flat))
    return mesh_utils.create_device_mesh(
        shape, devices=list(devices.flat), allow_split_physical_axes=True)


def initialize_mesh(dp=None, pp=1, sp=1, tp=1, ep=1, devices=None,
                    zero_partition_size=None):
    """Build the global mesh. ``dp=None`` → use all remaining devices.

    Analog of reference ``deepspeed.initialize``'s mesh_device creation
    (``deepspeed/__init__.py:153-162``) plus ``PipelineParallelGrid``
    (``runtime/pipe/topology.py:251``) in one step.
    """
    global _mesh_state
    explicit_devices = devices is not None
    if devices is None:
        devices = np.array(jax.devices())
    else:
        devices = np.asarray(devices)
    total = devices.size
    if dp is None:
        rem = pp * sp * tp
        if total % rem != 0:
            raise ValueError(f"device count {total} not divisible by pp*sp*tp={rem}")
        dp = total // rem
    _check_sizes(total, pp, dp, sp, tp)
    if ep < 1:
        raise ValueError(f"expert parallel size ep={ep} must be >= 1")
    if dp % ep != 0:
        # loud, BEFORE the grid reshape: a bad factorization used to be
        # reachable as a cryptic numpy "cannot reshape array" error from
        # mesh construction paths that skipped this function
        raise ValueError(
            f"expert parallel size (ep_size) ep={ep} must divide the "
            f"data-parallel world size dp={dp} — the mesh factors dp into "
            f"(dp/ep, ep) = ({dp}/{ep}, {ep}) (reference moe/layer.py:89 "
            "semantics); pick ep from the divisors of dp")

    shape = (pp, dp // ep, ep, sp, tp)
    if explicit_devices:
        grid = devices.reshape(shape)
    else:
        grid = _physical_device_grid(shape, devices)
        devices = grid  # hpZ factoring below reuses the optimized order
    mesh = Mesh(grid, axis_names=(PP_AXIS, DP_AXIS, EP_AXIS, SP_AXIS, TP_AXIS))

    # hpZ secondary-partition mesh: dp factored into (outer, inner) where the
    # inner axis groups physically-adjacent chips (intra-host) — reference
    # groups.py:531 _create_zero_param_parallel_group.
    hpz_mesh = None
    if zero_partition_size and zero_partition_size > 1:
        if dp % zero_partition_size != 0:
            raise ValueError(
                f"zero_partition_size={zero_partition_size} must divide dp={dp}")
        zgrid = devices.reshape(pp, dp // zero_partition_size,
                                zero_partition_size, sp, tp)
        hpz_mesh = Mesh(zgrid, axis_names=(PP_AXIS, ZP_OUTER_AXIS, ZP_AXIS,
                                           SP_AXIS, TP_AXIS))

    _mesh_state = MeshState(mesh=mesh, pp=pp, dp=dp, sp=sp, tp=tp, ep=ep,
                            hpz_mesh=hpz_mesh,
                            zero_partition_size=zero_partition_size)
    logger.debug(f"initialized mesh pp={pp} dp={dp} sp={sp} tp={tp} ep={ep}")
    # Keep an already-created comm backend in sync so facade collectives and
    # groups-module accessors always agree on the topology.
    from ..comm import comm as _comm
    if _comm.cdb is not None:
        from ..comm.backend import ProcessGroup
        _comm.cdb.mesh = mesh
        _comm.cdb.world_group = ProcessGroup(mesh, mesh.axis_names)
    return _mesh_state


def mesh_is_initialized():
    return _mesh_state is not None


def get_mesh_state() -> MeshState:
    if _mesh_state is None:
        initialize_mesh()
    return _mesh_state


def reset_mesh():
    global _mesh_state
    _mesh_state = None


def get_global_mesh() -> Mesh:
    return get_mesh_state().mesh


def dp_axes():
    """Mesh axes whose product is the full data-parallel degree."""
    return (DP_AXIS, EP_AXIS)


# ----------------------------------------------------------------- group API
# Accessor names mirror reference utils/groups.py so engine code reads the same.

def _pg(axes, mesh=None):
    from ..comm.backend import ProcessGroup
    return ProcessGroup(mesh or get_global_mesh(), axes)


def _get_data_parallel_group():
    return _pg(dp_axes())


def _get_sequence_parallel_group():
    return _pg((SP_AXIS, ))


def _get_sequence_data_parallel_group():
    """ZeRO shards over the combined seq×dp group when SP is on (reference
    ``engine.py:1580,1651`` seq_data_parallel_group)."""
    return _pg(dp_axes() + (SP_AXIS, ))


def _get_model_parallel_group():
    return _pg((TP_AXIS, ))


def _get_pipe_parallel_group():
    return _pg((PP_AXIS, ))


def _get_expert_parallel_group():
    return _pg((EP_AXIS, ))


def _get_expert_data_parallel_group():
    """Grads of expert params reduce over this group only (reference
    engine.py:2510 _reduce_expert_gradients)."""
    return _pg((DP_AXIS, ))


def _get_zero_param_partition_group():
    """hpZ secondary partition group (reference ``groups.py:531``): params are
    secondarily replicated within this group so allgather rides intra-host ICI."""
    st = get_mesh_state()
    if st.hpz_mesh is None:
        return None
    return _pg((ZP_AXIS, ), mesh=st.hpz_mesh)


def _get_data_parallel_world_size():
    return get_mesh_state().dp


def _get_sequence_parallel_world_size():
    return get_mesh_state().sp


def _get_model_parallel_world_size():
    return get_mesh_state().tp


def _get_pipe_parallel_world_size():
    return get_mesh_state().pp


def _get_expert_parallel_world_size():
    return get_mesh_state().ep


def _get_data_parallel_rank():
    """Host-level dp rank for per-process data loading (reference
    ``groups.py`` dp rank feeding ``DistributedSampler``): the dp-axis
    coordinate block of this process's addressable devices.  Per-device ranks
    only exist inside shard_map; this is the IO-level notion — processes with
    the same value must feed identical data, processes with different values
    feed different dp shards (see ``engine.shard_batch``)."""
    if jax.process_count() == 1:
        return 0
    st = get_mesh_state()
    devs = st.mesh.devices
    names = st.mesh.axis_names
    pi = jax.process_index()
    dp_i = names.index(DP_AXIS)
    ep_i = names.index(EP_AXIS)
    ep = devs.shape[ep_i]
    for coords in np.ndindex(devs.shape):
        if devs[coords].process_index == pi:
            # full-dp coordinate = dp coord × ep + ep coord (dp_axes order)
            return int(coords[dp_i]) * ep + int(coords[ep_i])
    raise RuntimeError(
        f"process {pi} owns no device in the mesh — mesh built from a "
        "device subset?")




def zero_sharding_axes(sequence_parallel=False):
    """Mesh axes over which ZeRO partitions optimizer/grad/param state."""
    return dp_axes() + ((SP_AXIS, ) if sequence_parallel else ())
