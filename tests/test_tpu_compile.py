"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler refuses what interpret mode accepts: a kernel whose blocks
overflow VMEM, a step that does not fit HBM. These tests compile the three
Pallas kernels at the widths of the supported MoEs (granite-moe-3b-a800m
whole; qwen3-moe-235b-a22b and deepseek-v3 with 8 experts, one chip's
share of an expert-parallel deployment), granite's ragged dispatch around
the ragged kernel, and the full-width granite decode step, for one chip of
a ``v5e:2x2`` topology; and they check that granite's decode and chunk
steps, the cache donated, update it in place.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and under
pytest-xdist every worker imports every test file. Where it cannot be
described the tests skip.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get
from repro.core import default_slots_per_rank
from repro.kernels import ops
from repro.models import (decode_fn, init_cache, init_params,
                          make_moe_tables, prefill_chunk_fn)
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


def _granite_step_args(sharding, B, S):
    """Shapes of granite's parameters at published width, with the expert
    slots the vibe_r slot budget grows to on one 16 GB chip (8 ranks x the
    policy default: 48 a layer), its placement tables and a cache of ``B``
    lanes x ``S`` positions, all on ``sharding``."""
    cfg = get("granite-moe-3b-a800m")
    G = 8
    n_slots = G * default_slots_per_rank(cfg.n_experts, G)

    def place(path, a):
        shape = a.shape
        if path[-1].key in ("w1", "w2", "w3"):
            shape = shape[:1] + (n_slots,) + shape[2:]
        return jax.ShapeDtypeStruct(shape, a.dtype, sharding=sharding)

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    params = jax.tree_util.tree_map_with_path(place, jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    perm = np.tile(np.arange(n_slots) % cfg.n_experts, (cfg.n_layers, 1))
    tables = shapes(jax.eval_shape(lambda: make_moe_tables(
        cfg, None, perm=perm, n_slots=n_slots, r_max=G)))
    cache = shapes(jax.eval_shape(lambda: init_cache(cfg, B, S)))
    return cfg, params, tables, cache


def test_granite_decode_step_fits_v5e(one_chip):
    """The served step at published width, 8 lanes x 2048 cached
    positions."""
    B, S = 8, 2048
    cfg, params, tables, cache = _granite_step_args(one_chip, B, S)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(decode_fn(cfg)).lower(params, tok, cache, pos,
                                             tables).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"decode step needs {total} B"


#: ops that name or pass on buffers without moving their bytes
_NO_MOVE = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
            "conditional", "call", "constant", "opt-barrier"}
_COMP = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%(\S+) = (.+?) ([a-z][\w-]*)\((.*)$")
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
_NESTED = re.compile(r"(?:calls|to_apply)=%([\w.-]+)")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2}


def _top_level_arrays(hlo: str):
    """(instruction, op, dims, bytes) of every array an instruction outputs
    outside fusion bodies and reducers: the buffers the program writes."""
    comps, nested, cur = {}, set(), None
    for line in hlo.splitlines():
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            cur.append(m.groups()[:3])
            nested.update(_NESTED.findall(m.group(4)))
    for comp, instrs in comps.items():
        if comp in nested:
            continue
        for name, out, op in instrs:
            for dtype, dims in _ARRAY.findall(out):
                dims = tuple(int(d) for d in dims.split(",") if d)
                yield name, op, dims, np.prod(dims) * _BYTES.get(dtype, 4)


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_granite_step_updates_cache_in_place(one_chip, program):
    """At the served cell's shapes (32 lanes x 1024 positions, 48 expert
    slots a layer), the step program jitted with the cache donated, as the
    engine jits it, writes its output cache into the input's buffers and
    moves no layer's K or V: every op that writes a buffer as large as one
    layer's K is an update of the carried (blocks, lanes, kv heads,
    positions, head_dim) stack, and none is a copy."""
    B, S, C = 32, 1024, 256
    cfg, params, tables, cache = _granite_step_args(one_chip, B, S)

    def i32(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    if program == "decode_step":
        fn, args = decode_fn(cfg), (params, i32((B, 1)), cache, i32((B,)),
                                    tables)
    else:
        fn, args = prefill_chunk_fn(cfg), (params, i32((1, C)), cache,
                                           i32(()), i32(()), i32(()), tables)
    compiled = jax.jit(fn, donate_argnums=(2,)).lower(*args).compile()
    leaves = jax.tree.leaves(cache)
    assert compiled.memory_analysis().alias_size_in_bytes == sum(
        a.size * a.dtype.itemsize for a in leaves)
    stack = leaves[0].shape
    assert stack == (cfg.n_layers, B, cfg.n_kv_heads, S, cfg.hd)
    layer_bytes = np.prod(stack[1:]) * 2
    big = [(name, op, dims) for name, op, dims, nbytes
           in _top_level_arrays(compiled.as_text())
           if op not in _NO_MOVE and nbytes >= layer_bytes]
    assert big, "no update of the cache found"
    moved = [b for b in big if b[2] != stack or "copy" in b[1]]
    assert not moved, f"ops that move a layer's K or V: {moved}"
