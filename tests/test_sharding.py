"""Tests for the logical-axis sharding rules."""

import jax
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P
import pytest

from repro.sharding import act_axes
from repro.sharding import constrain
from repro.sharding import logical_spec
from repro.sharding import use_mesh
from repro.sharding.api import ACT_SEQ


@pytest.fixture
def mesh():
    # AbstractMesh: real axis sizes without needing 256 devices
    return AbstractMesh((16, 16), ("data", "model"))


def test_no_mesh_is_noop():
    import jax.numpy as jnp
    x = jnp.ones((4, 4))
    assert constrain(x, ("dp", "tp")) is x


def test_logical_spec_basic(mesh):
    spec = logical_spec(("dp", None, "tp"), mesh)
    assert spec == P("data", None, "model")


def test_divisibility_filter(mesh):
    # dim size 3 cannot shard over data(16) → dropped; 64 can shard 16-way
    spec = logical_spec(("dp", "tp"), mesh, shape=(3, 64))
    assert spec == P(None, "model")


def test_axis_used_once(mesh):
    # "dp" consumes data; "sp" (data) must then resolve to nothing
    spec = logical_spec(("dp", "sp"), mesh)
    assert spec == P("data", None)


def test_kvseq_takes_leftover_axes(mesh):
    # batch=1: dp dropped by divisibility → kvseq gets data AND model
    spec = logical_spec(("dp", "kvseq"), mesh, shape=(1, 512))
    assert spec == P(None, ("data", "model"))
    # batch shardable: data consumed by dp → kvseq falls back to model
    spec = logical_spec(("dp", "kvseq"), mesh, shape=(32, 512))
    assert spec == P("data", "model")


def test_act_axes_flag():
    try:
        ACT_SEQ[0] = False
        assert act_axes() == ("dp", None, "tp_act")
        ACT_SEQ[0] = True
        assert act_axes() == ("dp", "act_seq", None)
    finally:
        ACT_SEQ[0] = False


def test_multipod_spec():
    mesh = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    spec = logical_spec(("dp", None, "tp"), mesh)
    assert spec == P(("pod", "data"), None, "model")


def test_use_mesh_binds_and_restores():
    from repro.sharding import current_mesh
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    assert current_mesh() is None
    with use_mesh(mesh):
        assert current_mesh() is mesh
    assert current_mesh() is None
