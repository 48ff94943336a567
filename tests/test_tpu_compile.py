"""Compile every Pallas kernel of the federated path for a TPU v5e chip
that is described, not attached, at the sizes ``chip_smoke.py`` runs.

The TPU compiler refuses here what interpret mode accepts: SMEM vector
loads, gathers inside a kernel, blocks past the VMEM limit. A pass is a
compile, not a chip run: nothing executes. The topology is described in
a module fixture, never at import, so only the worker that runs this file
loads the TPU library; where it cannot be described every test skips.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.kernels import ops
from repro.models import mlp


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back without the chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _train_args(dev, k, n, *, with_acc):
    params = jax.eval_shape(lambda: mlp.init(jax.random.key(0)))
    disp = jax.tree_util.tree_map(
        lambda l: _spec(dev, (k,) + l.shape, l.dtype), params)
    args = [disp, _spec(dev, (k, n, mlp.PAPER_LAYERS[0])),
            _spec(dev, (k, n), jnp.int32), _spec(dev, (k, n)),
            _spec(dev, (k,), jnp.int32), _spec(dev, (k,)), _spec(dev, ())]
    if with_acc:
        model = jax.tree_util.tree_map(
            lambda l: _spec(dev, l.shape, l.dtype), params)
        args += [model, model, _spec(dev, ()), _spec(dev, ())]
    return args


def _train_cycle(d, x, y, m, t, w, lr):
    return ops.train_agg_step(d, x, y, m, t, w, lr, loss_fn=mlp.loss,
                              use_pallas=True)


def _train_acc(d, x, y, m, t, w, lr, s, a, keep, flush):
    return ops.train_agg_step(d, x, y, m, t, w, lr, loss_fn=mlp.loss,
                              server=s, acc=a, keep=keep, flush=flush,
                              use_pallas=True)


def _case(name, dev):
    b, k = chip_smoke.WATERFILL_SHAPE
    row, col = _spec(dev, (b, k)), _spec(dev, (b,))
    if name == "fed_agg":
        return (lambda x, w: ops.fed_agg(x, w, use_pallas=True),
                [_spec(dev, chip_smoke.FED_AGG_SHAPE),
                 _spec(dev, chip_smoke.FED_AGG_SHAPE[:1])])
    if name == "waterfill_residual":
        return (lambda *a: ops.waterfill_residual(*a, use_pallas=True),
                [col, row, row, row, col, row, row, col])
    if name == "waterfill_energy_residual":
        return (lambda *a: ops.waterfill_energy_residual(*a, use_pallas=True),
                [col, row, row, row, col] + [row] * 6 + [col])
    k, n = chip_smoke.TRAIN_K, chip_smoke.TRAIN_D_CAP
    if name == "train_agg_step_cycle":
        return _train_cycle, _train_args(dev, k, n, with_acc=False)
    return _train_acc, _train_args(dev, k, n, with_acc=True)


def test_megakernel_compiles_inside_x64_scope(one_chip):
    """The reallocating scan and the fleet rounds trace the megakernel
    inside ``jax.enable_x64``; Mosaic takes no 64-bit block index."""
    args = _train_args(one_chip, chip_smoke.TRAIN_K, chip_smoke.TRAIN_D_CAP,
                       with_acc=False)
    with jax.enable_x64(True):
        exe = jax.jit(_train_cycle).lower(*args).compile()
    assert "tpu_custom_call" in exe.as_text()


@pytest.mark.parametrize("name", [
    "fed_agg", "waterfill_residual", "waterfill_energy_residual",
    "train_agg_step_cycle", "train_agg_step_acc",
])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _case(name, one_chip)
    exe = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in exe.as_text()
    mem = exe.memory_analysis()
    assert mem is not None and mem.argument_size_in_bytes > 0
