"""Ahead-of-time compiles of the main path for a described TPU v5e.

No chip is attached: the TPU compiler builds each program for a
``v5e:2x2`` topology described in a fixture, and refuses what the chip
would refuse (unsupported ops or dtypes, kernels that cannot tile, programs
that do not fit).  Nothing runs, so nothing here is a result or a time.

The Pallas SSD kernel (``repro.kernels.ssd``) is not compiled here: the
TPU lowering has no ``cumsum`` inside a kernel, so it is refused at any
block shape.

The topology is described only inside the module fixture — never at
import — so every test worker collects the same tests and only the worker
given this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.configs import get_config
from repro.core import build_state, tick_step
from repro.data.synthetic import scale_estimator
from repro.kernels.flash_attention import flash_attention
from repro.launch.steps import make_decode_step
from repro.models import AxisRules, build_model
from repro.online.fleet import fleet_pspecs, fleet_tick_step, stack_states

HBM_BYTES = 16e9     # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _abstract(tree, sharding):
    """Shape/dtype stand-ins of ``tree``'s arrays, placed by ``sharding``
    (one sharding for every leaf, or a matching tree of specs' shardings)."""
    if isinstance(sharding, SingleDeviceSharding):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)
    return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=s), tree, sharding)


def _hbm(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_tick_step_full_sweep_ceiling(one_chip):
    """The fused tick at (T, N) = (4096, 256), B = 64, float32."""
    assert not jax.config.jax_enable_x64
    est, _names, nodes = scale_estimator(4096, 256)
    state, _ = build_state(est, nodes)
    obs = jax.ShapeDtypeStruct((64, 8), jnp.float32, sharding=one_chip)
    size = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip,
                                weak_type=True)
    compiled = tick_step.lower(_abstract(state, one_chip), obs, size,
                               host_deadjust=True).compile()
    assert 0 < _hbm(compiled) < HBM_BYTES


def test_fleet_tick_step_four_chips(topo):
    """The W = 64, (128, 16) fleet tick sharded over a (4, 1) mesh: the
    estimate matrices come out split over all four chips."""
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("wf", "task"),
                axis_types=(AxisType.Auto,) * 2)
    est, _names, nodes = scale_estimator(128, 16)
    state, _ = build_state(est, nodes)
    fleet = stack_states([state] * 64)
    specs = jax.tree.map(lambda s: NamedSharding(mesh, s),
                         fleet_pspecs(fleet, mesh),
                         is_leaf=lambda s: isinstance(s, PartitionSpec))
    rep = NamedSharding(mesh, PartitionSpec())
    obs = jax.ShapeDtypeStruct((64, 64, 8), jnp.float32, sharding=rep)
    sizes = jax.ShapeDtypeStruct((64,), jnp.float32, sharding=rep)
    compiled = fleet_tick_step.lower(_abstract(fleet, specs), obs,
                                     sizes).compile()
    _fleet, mean, std = compiled.output_shardings
    for out in (mean, std):
        assert len(out.device_set) == 4
        assert not out.is_fully_replicated
    assert 0 < _hbm(compiled) < HBM_BYTES


def test_flash_attention_stablelm_widths(one_chip):
    """stablelm-1.6b attention: 32 heads x 4096 tokens x head_dim 64."""
    q = jax.ShapeDtypeStruct((1, 32, 4096, 64), jnp.bfloat16,
                             sharding=one_chip)
    compiled = flash_attention.lower(q, q, q, causal=True,
                                     interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_stablelm_decode_step_full_width(one_chip):
    """One stablelm-1.6b decode step as ``ServeLoop`` runs it: float32
    parameters, batch 4, a 28-position KV cache."""
    cfg = get_config("stablelm-1.6b")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: model.init_caches(4, max_len=28))
    tokens = jax.ShapeDtypeStruct((4, 1), jnp.int32, sharding=one_chip)
    index = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    step = jax.jit(make_decode_step(model, AxisRules(fsdp_axes=(),
                                                     dp_axes=())))
    compiled = step.lower(_abstract(params, one_chip), {"tokens": tokens},
                          _abstract(caches, one_chip), index).compile()
    assert cfg.param_count() * 4 < _hbm(compiled) < HBM_BYTES
