"""The search path's Pallas kernels compile for a TPU v5e chip.

Interpret mode on the CPU checks what a kernel computes but not whether
Mosaic accepts it.  These tests compile each kernel for a v5e chip that
is described, not attached, and check that the program holds the Mosaic
custom call.  The dominance kernel is compiled at the pool sizes a
1024-design query ranks: the 2*pop selection pool, and the archive
capacity plus one segment of evaluations for an archive insert.  The
metric model is compiled too, to check that it holds no f32 dot, which a
TPU would run in bf16.
"""

import inspect

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import repro.core as C
from repro.core.encoding import random_design
from repro.core.evaluate import evaluate_system
from repro.core.optimizer import metric_stack
from repro.explore.service import BudgetPolicy, ExplorationService
from repro.kernels.gp_cov.gp_cov import matern52_pallas
from repro.kernels.pareto_rank.pareto_rank import dominance_counts_pallas

POP = 1024
CAPACITY = inspect.signature(
    ExplorationService).parameters["capacity"].default
SEGMENT = BudgetPolicy().chunk_generations * POP
POOLS = {"selection": 2 * POP, "insert": CAPACITY + SEGMENT}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off:
    what is compiled for it cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("k", [2, 3, 4])
@pytest.mark.parametrize("pool", sorted(POOLS))
def test_dominance_kernel_compiles_for_v5e(one_chip, pool, k):
    n = POOLS[pool]
    objs = jax.ShapeDtypeStruct((n, k), jnp.float32, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(dominance_counts_pallas,
                                               objs, valid)


def test_matern52_kernel_compiles_for_v5e(one_chip):
    x = jax.ShapeDtypeStruct((256, 16), jnp.float32, sharding=one_chip)
    z = jax.ShapeDtypeStruct((384, 16), jnp.float32, sharding=one_chip)
    ls = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(matern52_pallas, x, z, ls)


def test_metric_model_has_no_default_precision_dot_on_v5e(one_chip):
    """A TPU runs an f32 dot at default precision in bf16, which rounds
    the model's integer tile sizes above 256: the compiled metric model
    must hold no dot (XLA writes a TPU dot as ``convolution``)."""
    spec = C.SystemSpec.build(C.presets.bert_mms()["att2"], ch_max=2)
    space = C.DesignSpace(spec)
    designs = jax.eval_shape(lambda: jax.vmap(
        lambda k: random_design(k, space))(
            jax.random.split(jax.random.PRNGKey(0), 8)))
    designs = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), designs)
    text = _compiled_text(jax.vmap(
        lambda d: metric_stack(evaluate_system(spec, d))), designs)
    assert "convolution(" not in text and " dot(" not in text
