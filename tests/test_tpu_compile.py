"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler refuses what interpret mode accepts: a kernel whose blocks
overflow VMEM, a step that does not fit HBM. These tests compile the three
Pallas kernels at the widths of the supported MoEs (granite-moe-3b-a800m
whole; qwen3-moe-235b-a22b and deepseek-v3 with 8 experts, one chip's
share of an expert-parallel deployment), granite's ragged dispatch around
the ragged kernel, and the full-width granite decode step, for one chip of
a ``v5e:2x2`` topology.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and under
pytest-xdist every worker imports every test file. Where it cannot be
described the tests skip.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get
from repro.core import default_slots_per_rank
from repro.kernels import ops
from repro.models import decode_fn, init_cache, init_params, make_moe_tables
from repro.models import moe
from repro.models.sharding import ShardingRules

#: (d_model, moe_d_ff, experts on one chip, top_k)
WIDTHS = {
    "granite": (1536, 512, 40, 8),
    "qwen3": (4096, 1536, 8, 8),
    "deepseek": (7168, 2048, 8, 8),
}
TOKENS, BM = 256, 128
HBM_BYTES = 16 * 2**30


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
    """One described chip, with the persistent compilation cache off (its
    entries cannot be read back without a chip) and the kernel wrappers
    steered to compile rather than interpret (the default backend here is
    the CPU)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_interpret", lambda: False)
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _kernel_case(kernel, D, F, E, K, sharding):
    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    A = TOKENS * K
    weights = (s((E, D, F)), s((E, D, F)), s((E, F, D)))
    if kernel == "fused":
        return ops.fused_moe_ffn, (*weights, s((E, A // E, D)))
    if kernel == "ragged":
        n_tiles = A // BM + E
        return ops.ragged_moe_ffn, (*weights, s((n_tiles * BM, D)),
                                    s((n_tiles,), jnp.int32))
    return (lambda logits: ops.router_topk(logits, K),
            (s((TOKENS, E), jnp.float32),))


@pytest.mark.parametrize("kernel", ["fused", "ragged", "router"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_kernel_compiles_for_v5e(one_chip, width, kernel):
    fn, args = _kernel_case(kernel, *WIDTHS[width], one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ragged_dispatch_compiles_for_v5e(one_chip):
    """granite's ragged dispatch around the Pallas FFN (sort plan, buffer,
    combine). Its combine is a reshape-and-sum: a scatter-add at the
    slot-sorted token ids, which an earlier version compiled to, lost most
    contributions on a v5e."""
    D, F, E, K = WIDTHS["granite"]
    rules = ShardingRules(mesh=None, moe_impl="ragged", use_kernel=True)
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), jax.eval_shape(
        lambda: moe.moe_init(jax.random.PRNGKey(0), d=D, f=F, n_experts=E,
                             n_slots=E)))
    x = jax.ShapeDtypeStruct((4, 64, D), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(lambda p, x: moe.moe_layer(
        p, x, top_k=K, n_experts=E, rules=rules)).lower(p, x).compile(
        ).as_text()
    assert "tpu_custom_call" in text
    assert "scatter-add" not in text


def test_granite_decode_step_fits_v5e(one_chip):
    """The served step at published width, with the expert slots the
    vibe_r slot budget grows to on one 16 GB chip (8 ranks x the policy
    default), 8 lanes x 2048 cached positions."""
    cfg = get("granite-moe-3b-a800m")
    B, S, G = 8, 2048, 8
    n_slots = G * default_slots_per_rank(cfg.n_experts, G)

    def place(path, a):
        shape = a.shape
        if path[-1].key in ("w1", "w2", "w3"):
            shape = shape[:1] + (n_slots,) + shape[2:]
        return jax.ShapeDtypeStruct(shape, a.dtype, sharding=one_chip)

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = jax.tree_util.tree_map_with_path(place, jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    perm = np.tile(np.arange(n_slots) % cfg.n_experts, (cfg.n_layers, 1))
    tables = shapes(jax.eval_shape(lambda: make_moe_tables(
        cfg, None, perm=perm, n_slots=n_slots, r_max=G)))
    cache = shapes(jax.eval_shape(lambda: init_cache(cfg, B, S)))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(decode_fn(cfg)).lower(params, tok, cache, pos,
                                             tables).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"decode step needs {total} B"
