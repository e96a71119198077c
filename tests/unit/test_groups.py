"""Mesh topology / group calculus tests (reference utils/groups.py +
runtime/pipe/topology.py analog)."""

import numpy as np
import pytest

from deepspeed_tpu.utils import groups


def test_default_mesh_all_dp():
    st = groups.initialize_mesh()
    assert st.dp == 8 and st.pp == 1 and st.sp == 1 and st.tp == 1
    assert st.mesh.shape["dp"] == 8


def test_mesh_factorization():
    st = groups.initialize_mesh(pp=2, dp=2, sp=1, tp=2)
    assert st.mesh.size == 8
    assert groups._get_pipe_parallel_world_size() == 2
    assert groups._get_data_parallel_world_size() == 2
    assert groups._get_model_parallel_world_size() == 2


def test_invalid_factorization_raises():
    with pytest.raises(ValueError):
        groups.initialize_mesh(pp=3, dp=3)


def test_expert_axes():
    st = groups.initialize_mesh(dp=8, ep=4)
    assert st.mesh.shape["ep"] == 4
    assert st.mesh.shape["dp"] == 2  # expert-dp part
    assert st.dp == 8  # total data-parallel degree
    g = groups._get_expert_parallel_group()
    assert g.size() == 4
    g2 = groups._get_expert_data_parallel_group()
    assert g2.size() == 2
    assert groups._get_data_parallel_group().size() == 8


def test_ep_must_divide_dp():
    with pytest.raises(ValueError):
        groups.initialize_mesh(dp=8, ep=3)


def test_seq_data_parallel_group():
    groups.initialize_mesh(dp=4, sp=2)
    g = groups._get_sequence_data_parallel_group()
    assert g.size() == 8
    assert groups._get_sequence_parallel_world_size() == 2


def test_zero_sharding_axes():
    groups.initialize_mesh(dp=4, sp=2)
    assert groups.zero_sharding_axes(sequence_parallel=True) == ("dp", "ep", "sp")
    assert groups.zero_sharding_axes() == ("dp", "ep")


def test_hpz_mesh():
    st = groups.initialize_mesh(dp=8, zero_partition_size=4)
    assert st.hpz_mesh is not None
    g = groups._get_zero_param_partition_group()
    assert g.size() == 4
    assert g.axis_names == ("zp", )


def test_hpz_must_divide_dp():
    with pytest.raises(ValueError):
        groups.initialize_mesh(dp=8, zero_partition_size=3)


def test_tpu_mesh_construction_failure_raises(monkeypatch):
    """On TPU devices a physical mesh that cannot be built is an error —
    never a linear device order behind a warning (the locality hpZ and the
    tp/sp axes promise would be gone).  CPU devices have no topology and
    take the plain reshape."""
    import numpy as np
    from jax.experimental import mesh_utils

    def boom(*a, **k):
        raise RuntimeError("topology query failed")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", boom)
    monkeypatch.setattr(mesh_utils, "create_hybrid_device_mesh", boom)

    class FakeTpu:
        platform = "tpu"

    chips = np.array([FakeTpu() for _ in range(4)], dtype=object)
    with pytest.raises(RuntimeError, match="topology query failed"):
        groups._physical_device_grid((1, 4, 1, 1, 1), chips)
    assert groups._physical_device_grid((1, 1, 1, 1, 1), chips[:1]).shape \
        == (1, 1, 1, 1, 1)
    assert groups.initialize_mesh(dp=8).mesh is not None    # CPU: reshape
