"""Sharding rules: spec construction, axis filtering, divisibility fitting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.registry import get
from repro.distributed import sharding as shd
from repro.models import model as M


def test_fit_divisibility_drops_bad_axes():
    # vocab 51866 is not divisible by a 16-way axis → dropped
    spec = shd._fit_divisibility(P("model", "data"), (51866, 1280),
                                 {"model": 16, "data": 16})
    assert spec == P(None, "data")


def test_fit_divisibility_tuple_axes():
    # (pod, data) = 2·16 = 32 divides 64; keeps tuple
    spec = shd._fit_divisibility(P(("pod", "data")), (64,),
                                 {"pod": 2, "data": 16})
    assert spec == P(("pod", "data"))
    # 48 % 32 != 0 but 48 % 2 == 0 → keeps only 'pod'
    spec = shd._fit_divisibility(P(("pod", "data")), (48,),
                                 {"pod": 2, "data": 16})
    assert spec == P("pod")


def test_filter_axes_removes_missing():
    spec = shd._filter_axes(P("pod", "model"), ("data", "model"))
    assert spec == P(None, "model")


def test_param_specs_cover_tree():
    cfg = get("stablelm-3b-smoke")
    p = M.init_params(cfg, jax.random.PRNGKey(0))
    specs = shd.param_specs(p, ("data", "model"))
    flat_p = jax.tree.leaves(p)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
    assert len(flat_p) == len(flat_s)
    for x, s in zip(flat_p, flat_s):
        assert isinstance(s, P)
        # spec rank ≤ array rank
        assert len(s) <= x.ndim


def test_param_specs_embed_rule():
    cfg = get("stablelm-3b-smoke")
    p = M.init_params(cfg, jax.random.PRNGKey(0))
    specs = shd.param_specs(p, ("data", "model"),
                            {"data": 2, "model": 2})
    assert specs["embed"] == P("model", "data")


def test_lora_specs_follow_targets():
    spec = shd._leaf_spec("wq_lora_a", (512, 16), False)
    assert spec == P("data", None)
    spec = shd._leaf_spec("wq_lora_b", (16, 512), False)
    assert spec == P(None, "model")


def test_batch_specs():
    batch = {"tokens": jnp.zeros((8, 16), jnp.int32),
             "pos": jnp.zeros((), jnp.int32)}
    specs = shd.batch_specs(batch, ("pod", "data", "model"))
    assert specs["tokens"] == P(("pod", "data"), None)
    assert specs["pos"] == P()


def test_cache_specs_divisibility():
    cache = (jnp.zeros((4, 128, 32768, 8, 64)),   # (G,B,S,KH,D)
             jnp.zeros((4, 128, 1500, 8, 64)))    # cross-kv, S=1500
    specs = shd.cache_specs(cache, ("data", "model"), 128,
                            {"data": 16, "model": 16})
    assert specs[0] == P(None, "data", "model", None, None)
    # 1500 not divisible by 16 → seq axis unsharded
    assert specs[1] == P(None, "data", None, None, None)


def test_constrain_noop_outside_mesh():
    x = jnp.ones((4, 4))
    y = shd.constrain(x, P("data", None))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_constrain_and_axis_size_under_mesh():
    """Under a mesh the helpers act on it: axes it lacks are dropped,
    its sizes are read — and errors are not swallowed any more."""
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    assert shd.mesh_axis_size("data") == 1 == shd.mesh_axis_size("model")
    with jax.set_mesh(mesh):
        assert shd.mesh_axis_size("data") == 1
        y = jax.jit(lambda x: shd.constrain(x, P("data", "model")))(
            jnp.ones((4, 4)))
        np.testing.assert_array_equal(np.asarray(y), np.ones((4, 4)))
        with pytest.raises(ValueError):
            # a spec longer than the array's rank is a caller's bug
            jax.jit(lambda x: shd.constrain(x, P("data", None, None)))(
                jnp.ones((4, 4)))


def test_bench_mesh_width_is_never_cut():
    from benchmarks.hostdev import require_visible
    assert require_visible(len(jax.devices()), "t") == len(jax.devices())
    with pytest.raises(SystemExit, match="wanted"):
        require_visible(len(jax.devices()) + 1, "t")
