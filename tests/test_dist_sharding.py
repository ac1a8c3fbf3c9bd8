"""dist/sharding: logical-axis rule resolution onto real CPU meshes."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import (SERVE_RULES, TRAIN_RULES, AxisRules,
                                 logical_spec, shard_constraint)
from repro.launch.mesh import auto_mesh

pytestmark = pytest.mark.skipif(
    jax.device_count() < 4, reason="needs 4 forced host devices")


@pytest.fixture(scope="module")
def data_mesh():
    return auto_mesh((4, 1), ("data", "model"))


@pytest.fixture(scope="module")
def model_mesh():
    return auto_mesh((1, 4), ("data", "model"))


class TestResolution:
    def test_batch_resolves_to_data(self, data_mesh):
        assert logical_spec(TRAIN_RULES, ("batch", "seq"), (8, 64),
                            data_mesh) == P("data", None)

    def test_none_names_replicate(self, data_mesh):
        assert logical_spec(TRAIN_RULES, (None, None), (8, 64),
                            data_mesh) == P(None, None)

    def test_mesh_none_replicates(self):
        assert logical_spec(TRAIN_RULES, ("batch", "seq"), (8, 64),
                            None) == P(None, None)

    def test_unknown_logical_axis_raises(self, data_mesh):
        with pytest.raises(KeyError):
            logical_spec(TRAIN_RULES, ("bogus",), (8,), data_mesh)

    def test_rank_mismatch_raises(self, data_mesh):
        with pytest.raises(ValueError):
            logical_spec(TRAIN_RULES, ("batch",), (8, 64), data_mesh)

    def test_size_one_axis_replicates(self, data_mesh):
        # 'model' has size 1 on this mesh: sharding over it is a no-op
        assert logical_spec(TRAIN_RULES, ("batch", "vocab"), (8, 128),
                            data_mesh) == P("data", None)


class TestDivisibilityFallback:
    def test_indivisible_dim_replicates(self, data_mesh):
        assert logical_spec(TRAIN_RULES, ("batch", "seq"), (6, 64),
                            data_mesh) == P(None, None)

    def test_multi_axis_prefix_fallback(self):
        if jax.device_count() < 4:
            pytest.skip("needs 4 devices")
        mesh = auto_mesh((2, 2, 1), ("pod", "data", "model"))
        # 8 % (2*2) == 0: both axes; 2 % 4 != 0 but 2 % 2 == 0: 'pod' only
        assert logical_spec(TRAIN_RULES, ("batch",), (8,),
                            mesh) == P(("pod", "data"))
        assert logical_spec(TRAIN_RULES, ("batch",), (2,), mesh) == P("pod")

    def test_duplicate_mesh_axis_not_reused(self, model_mesh):
        # heads and mlp both map to 'model'; one dimension wins, the other
        # replicates (a PartitionSpec may not repeat a mesh axis)
        spec = logical_spec(TRAIN_RULES, ("heads", "mlp"), (8, 16),
                            model_mesh)
        assert spec == P("model", None)


class TestTrainVsServe:
    def test_fsdp_only_in_train(self, data_mesh):
        wq_names = ("embed_fsdp", "heads", "head_dim")
        train = logical_spec(TRAIN_RULES, wq_names, (64, 8, 16), data_mesh)
        serve = logical_spec(SERVE_RULES, wq_names, (64, 8, 16), data_mesh)
        assert train == P("data", None, None)
        assert serve == P(None, None, None)

    def test_kv_cache_split_only_in_serve(self, model_mesh):
        names = (None, "batch", "kv_seq", "kv_heads", "head_dim")
        shape = (2, 8, 128, 2, 64)  # kv_heads=2 indivisible by model=4
        train = logical_spec(TRAIN_RULES, names, shape, model_mesh)
        serve = logical_spec(SERVE_RULES, names, shape, model_mesh)
        assert train == P(None, None, None, None, None)
        assert serve == P(None, None, "model", None, None)

    def test_tensor_parallel_in_both(self, model_mesh):
        for rules in (TRAIN_RULES, SERVE_RULES):
            assert logical_spec(rules, ("batch", "seq", "vocab"),
                                (8, 16, 128), model_mesh) == \
                P(None, None, "model")

    def test_extend_overrides_single_entry(self):
        base = AxisRules.of(a="data", b="model")
        ext = base.extend(b=None)
        assert ext.mesh_axes("a") == ("data",)
        assert ext.mesh_axes("b") == ()
        assert base.mesh_axes("b") == ("model",)  # original untouched


class TestShardConstraint:
    def test_identity_without_mesh(self):
        x = np.ones((8, 4), np.float32)
        y = shard_constraint(x, TRAIN_RULES, ("batch", None), None)
        assert y is x

    def test_constraint_places_output(self, data_mesh):
        x = np.arange(32, dtype=np.float32).reshape(8, 4)
        y = jax.jit(lambda v: shard_constraint(
            v, TRAIN_RULES, ("batch", None), data_mesh))(x)
        np.testing.assert_array_equal(np.asarray(y), x)
        # committed output sharding normalizes trailing Nones away
        assert y.sharding.spec in (P("data"), P("data", None))

    def test_indivisible_constraint_is_noop(self, data_mesh):
        x = np.ones((6, 4), np.float32)
        y = jax.jit(lambda v: shard_constraint(
            v, TRAIN_RULES, ("batch", None), data_mesh))(x)
        np.testing.assert_array_equal(np.asarray(y), x)
