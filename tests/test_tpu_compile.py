"""The main path's Pallas kernels compiled for a TPU v5e that is described,
not attached (``interpret=False``): tile alignment, strided access and
VMEM fit are what interpret mode cannot check, and the TPU compiler
refuses them here without a chip.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and the test workers all import every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

import repro
from repro.core.quant import MX_43, W4A4
from repro.kernels import dispatch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topology = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed / loadable here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topology
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Compile ``fn`` for the described chip through the Pallas kernels
    (not the interpreter); -> the HLO text, which must hold a kernel."""
    with dispatch.use_backend("pallas"), dispatch.use_interpret(False):
        compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


def test_matmul_int_vgg16_fc1(one_chip):
    _compile(dispatch.matmul_int, _spec(one_chip, (8, 25088)),
             _spec(one_chip, (25088, 4096), jnp.int8))


@pytest.mark.parametrize("hw,c_in,c_out", [(224, 3, 64), (112, 64, 128)],
                         ids=["vgg16.conv1", "vgg16.conv3"])
def test_strip_conv_vgg16(one_chip, hw, c_in, c_out):
    strat = dispatch.select_conv_strategy(hw, hw, c_in, c_out, 3)
    assert strat.kind == "strip"
    _compile(lambda x, w: dispatch.conv_int(x, w, 1, ((1, 1), (1, 1)),
                                            strategy=strat),
             _spec(one_chip, (2, hw, hw, c_in)),
             _spec(one_chip, (3, 3, c_in, c_out), jnp.int8))


def test_strip_depthwise_256_rgb(one_chip):
    strat = dispatch.select_conv_strategy(256, 256, 3, 3, 5, groups=3)
    assert strat.kind == "strip"
    _compile(lambda x, w: dispatch.conv_int(x, w, 1, ((2, 2), (2, 2)),
                                            groups=3, strategy=strat),
             _spec(one_chip, (2, 256, 256, 3)),
             _spec(one_chip, (5, 5, 1, 3), jnp.int8))


def test_ca_acquire_vgg9(one_chip):
    _compile(lambda x: dispatch.ca_acquire(x, 2, True),
             _spec(one_chip, (8, 32, 32, 3)))


def _executor_args(one_chip, program, batch, scheme):
    exe = program.compile(repro.Options(scheme=scheme, backend="pallas",
                                        interpret=False))
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype), program.params)
    frames = _spec(one_chip, (batch, *program.input_hwc))
    return exe.plan, params, frames


def _pallas_executor(plan):
    """The plan's per-frame executor, looked up as it is traced, inside
    ``_compile``'s pallas context: the plan keys its executors by the
    backend active at lookup, and one looked up outside that context may
    carry a trace that an earlier CPU run of the same (cached) plan left,
    with no kernel in it."""
    return lambda *args: plan.executor(per_frame=True)(*args)


def test_lenet_per_frame_executor_fused_chain(one_chip):
    plan, params, frames = _executor_args(
        one_chip, repro.Program.from_model("lenet"), 8, W4A4)
    assert [s.names for s in plan.fused_segments] == [("conv1", "conv2")]
    _compile(_pallas_executor(plan), params, frames, plan.consts)


def test_vgg9_per_frame_executor_with_ca(one_chip):
    plan, params, frames = _executor_args(
        one_chip, repro.Program.from_model("vgg9"), 8, MX_43)
    _compile(_pallas_executor(plan), params, frames, plan.consts)
