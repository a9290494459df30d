"""The main path's device programs, compiled for a described TPU v5e.

No chip is attached: the TPU compiler installed with jax compiles for a
topology it is only told about, so what the chip's compiler would refuse
(scoped VMEM overflow, tiling, lowering) fails here at no chip time.
Nothing runs, so these tests say nothing about results or times.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and every pytest worker imports
this file.  Keep these tests in this one file for the same reason.
"""
import functools
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_cnn import FIG4_CNN
from repro.kernels.server_step.kernel import server_step_blocks
from repro.models import cnn
from repro.optim import adagrad
from repro.sharding.spec import values_tree
from repro.train_fabric import FusedServerStep


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _fig4_params(sharding):
    shapes = jax.eval_shape(
        lambda: values_tree(cnn.init_cnn(jax.random.PRNGKey(0), FIG4_CNN)))
    return _on(sharding, shapes)


@pytest.mark.parametrize("members", [16, 256])
def test_fused_server_step_compiles_at_fig4_widths(one_chip, members):
    params = _fig4_params(one_chip)
    step = FusedServerStep(adagrad(0.05), lr=0.05, mode="pallas")
    coeffs = jax.ShapeDtypeStruct((members,), jnp.float32, sharding=one_chip)
    compiled = step._jit.lower((params,) * members, coeffs, params,
                               params).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the names a trace reduction finds the program and its kernel by
    assert text.startswith("HloModule jit_fused_server_step,")
    assert "%server_step_update" in text


def test_server_step_kernel_compiles_at_256_members(one_chip):
    p2 = jax.ShapeDtypeStruct((608, 1024), jnp.float32, sharding=one_chip)
    g3 = jax.ShapeDtypeStruct((256, 608, 1024), jnp.float32,
                              sharding=one_chip)
    coeffs = jax.ShapeDtypeStruct((256,), jnp.float32, sharding=one_chip)
    kernel = jax.jit(functools.partial(server_step_blocks, lr=0.05,
                                       interpret=False))
    compiled = kernel.lower(p2, g3, p2, coeffs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_cnn_loss_and_grads_compiles_at_fig4_batch(one_chip):
    b, s, c = FIG4_CNN.batch_size, FIG4_CNN.image_size, FIG4_CNN.in_channels
    images = jax.ShapeDtypeStruct((b, s, s, c), jnp.float32,
                                  sharding=one_chip)
    labels = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    compiled = cnn.loss_and_grads(FIG4_CNN).lower(
        _fig4_params(one_chip), images, labels).compile()
    assert compiled.memory_analysis() is not None
    assert compiled.as_text().startswith("HloModule jit_cnn_loss_and_grads,")


def test_packed_cnn_loss_and_grads_compiles_at_fig4_batch(one_chip):
    b, s, c = FIG4_CNN.batch_size, FIG4_CNN.image_size, FIG4_CNN.in_channels
    leaves, treedef = jax.tree_util.tree_flatten(_fig4_params(one_chip))
    shapes = tuple(x.shape for x in leaves)
    words = sum(math.prod(x) for x in shapes) + b * s * s * c + b
    program = cnn.packed_loss_and_grads(FIG4_CNN, treedef, shapes,
                                        (b, s, s, c), (b,))
    compiled = program.lower(jax.ShapeDtypeStruct(
        (words,), jnp.uint32, sharding=one_chip)).compile()
    assert compiled.memory_analysis() is not None
    assert compiled.as_text().startswith("HloModule jit_cnn_loss_and_grads,")
