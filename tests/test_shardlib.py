"""Unit tests for the logical-axis sharding helpers (``models/shardlib.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.shardlib import (
    active_rules,
    logical_to_spec,
    shard,
    use_sharding,
)


def test_shard_is_noop_without_mesh():
    x = jnp.ones((4, 8))
    assert shard(x, "batch", "embed") is x
    assert active_rules() is None


def test_shard_rank_mismatch_raises():
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))
    with use_sharding(mesh, {"batch": "data"}):
        with pytest.raises(ValueError):
            shard(jnp.ones((4, 8)), "batch")


def test_logical_to_spec_mapping():
    from jax.sharding import PartitionSpec as P

    rules = {"batch": ("pod", "data"), "ffn": "model", "embed": None}
    spec = logical_to_spec(["batch", None, "ffn"], rules)
    assert spec == P(("pod", "data"), None, "model")


def test_use_sharding_nests_and_restores():
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))
    with use_sharding(mesh, {"batch": "data"}):
        assert active_rules()[1] == {"batch": "data"}
        with use_sharding(mesh, {"batch": None}):
            assert active_rules()[1] == {"batch": None}
        assert active_rules()[1] == {"batch": "data"}
    assert active_rules() is None
