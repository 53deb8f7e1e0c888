"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs here: each test lowers a kernel for a v5e device that JAX
describes but does not attach, and the TPU compiler (Mosaic included)
accepts it or raises what the chip's compiler would raise — a block that
does not tile, or more VMEM than the kernel asked for.  That covers ToyCar's
accelerator steps, as a real ``tpu_v5e`` compile binds them, the
scheduled GEMMs at widths where the scheduler's VMEM budget matters, the
device attention epilogue at MusicGen's width, Mixtral's grouped
expert GEMMs and device SiLU·mul, and both cells' attention context with
its requantize in the kernel's epilogue.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""

import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro
from repro.core import lowering
from repro.core.arch_spec import GemmWorkload
from repro.core.configurators import build_backend
from repro.core.descriptions.tpu_v5e import make_tpu_v5e_description
from repro.core.schedule import validate_schedule
from repro.kernels import ops as kops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip cannot be read back without the chip;
    keep these compiles out of JAX's persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_for_chip(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.fixture(scope="module")
def toycar_on_chip():
    """ToyCar compiled for ``tpu_v5e`` as it would be on the chip: the
    interpret-mode switch reads the backend, so it is steered here."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(lowering, "pallas_interpret_mode", lambda: False)
        return repro.compile(
            "toycar_mlp", repro.Target("tpu_v5e", batch_size=16, cache=False)
        )


@pytest.mark.parametrize("bucket", [1, 4, 16])
def test_toycar_step_kernels_compile_for_v5e(
    bucket, toycar_on_chip, one_chip, no_compile_cache
):
    module = toycar_on_chip.modules[bucket]
    assert module.ops
    compiled = set()
    for op in module.ops.values():
        cfg = op.executor.kernel_config
        assert cfg.interpret is False
        x, w, *bias = op.node.inputs
        shapes = [
            ((math.prod(x.shape[:-1]), x.shape[-1]), x.dtype),
            (w.shape, w.dtype),
            *((b.shape, b.dtype) for b in bias if b is not None),
        ]
        key = (cfg, tuple(shapes))
        if key in compiled:  # the three 128x128 layers share one kernel
            continue
        compiled.add(key)
        args = [
            jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)
            for shape, dtype in shapes
        ]
        if len(args) == 2:
            args.append(None)
        _compile_for_chip(op.executor.run_kernel, args)


# (m, k, n, input dtype, output dtype): one f32, two bf16 and one int8 GEMM
# whose CoSA tiles overflowed Mosaic's default VMEM share before the
# scheduler counted the kernel's real footprint.
WIDE_GEMMS = [
    (512, 4096, 1024, "float32", "float32"),
    (4096, 4096, 4096, "bfloat16", "float32"),
    (8192, 8192, 8192, "bfloat16", "bfloat16"),
    (4096, 8192, 4096, "int8", "int8"),
]


@pytest.mark.parametrize("m,k,n,in_dtype,out_dtype", WIDE_GEMMS)
def test_scheduled_gemm_at_width_compiles_for_v5e(
    m, k, n, in_dtype, out_dtype, one_chip, no_compile_cache
):
    desc = make_tpu_v5e_description()
    backend = build_backend(desc)
    quantized = in_dtype == "int8"
    in_bytes = jnp.dtype(in_dtype).itemsize
    wl = GemmWorkload(
        N=m, C=k, K=n, in_bytes=in_bytes, w_bytes=in_bytes,
        out_bytes=4 if quantized else jnp.dtype(out_dtype).itemsize,
    )
    sched = backend.scheduler.schedule(wl).best
    assert validate_schedule(sched, desc.arch) == []
    epilogue = (
        {"requant_scale": 2.0**-12, "clip_lo": -128.0, "clip_hi": 127.0}
        if quantized
        else None
    )
    cfg = backend.mapping_gen.to_kernel_config(
        sched,
        acc_dtype="int32" if quantized else "float32",
        out_dtype=out_dtype,
        epilogue=epilogue,
        interpret=False,
        has_bias=quantized,
    )
    assert cfg.vmem_limit_bytes == desc.kernel_vmem_limit_bytes
    x = jax.ShapeDtypeStruct((m, k), jnp.dtype(in_dtype), sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, n), jnp.dtype(in_dtype), sharding=one_chip)
    if quantized:
        bias = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
        _compile_for_chip(lambda x, w, b: kops.qmatmul(x, w, b, cfg), [x, w, bias])
    else:
        _compile_for_chip(lambda x, w: kops.matmul(x, w, cfg), [x, w])


def test_attention_epilogue_at_prefill512_compiles_for_v5e(one_chip, no_compile_cache):
    """The device attention epilogue of ``musicgen.prefill512``: 2 x 24 heads
    of 512 x 512 int32 scores and the causal mask."""
    shape = (48, 512, 512)
    s = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct(shape[1:], jnp.float32, sharding=one_chip)
    compiled = lowering._attn_epilogue.lower(
        s, mask, shape=shape, scale=2.0**-9, probs_scale=2.0**-7,
        capacity=48 * 512 // 16,
    ).compile()
    out, flags, rows = compiled.out_info
    assert out.shape == shape and out.dtype == jnp.int8
    assert flags.shape == shape[:-1] and rows.shape == (48 * 512 // 16, 512)


# (rows routed, K, N) of Mixtral-8x7B's two expert projections at 2 x 512
# tokens, top-2 of 8 experts: gate|up side by side, then down
MIXTRAL_EXPERT_GEMMS = [(2048, 4096, 2 * 14336), (2048, 14336, 4096)]


@pytest.mark.parametrize("rows,k,n", MIXTRAL_EXPERT_GEMMS, ids=["gate_up", "down"])
def test_grouped_expert_gemm_at_mixtral_width_compiles_for_v5e(
    rows, k, n, one_chip, no_compile_cache
):
    """The grouped int8 kernel as the plan binds it for ``tpu_v5e``: the
    schedule's column tile and the grouped row tile, whole-K weight panels
    that fit the kernel's VMEM, eight experts, one launch."""
    from repro.core import ir
    from repro.core.strategy import workload_from_node
    from repro.kernels import grouped

    desc = make_tpu_v5e_description()
    backend = build_backend(desc)
    x = ir.input_((rows, k), "int8", name="x")
    w = ir.input_((8, k, n), "int8", name="w")
    sizes = ir.input_((8,), "int32", name="sizes")
    node = ir.Node("generalized_grouped_dense", [x, w, sizes],
                   {"quantized": True, "requant_scale": 2.0**-8, "clip_lo": -128,
                    "clip_hi": 127}, shape=(rows, n), dtype="int8")
    sched = backend.scheduler.schedule(workload_from_node(node))
    strat = backend.strategy_gen.generate(node, sched)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(lowering, "pallas_interpret_mode", lambda: False)
        cfg = lowering.grouped_kernel_config(desc, backend.mapping_gen, node, strat)
    assert cfg.block_k == k and n % cfg.block_n == 0 and cfg.block_m % 32 == 0
    args = [
        jax.ShapeDtypeStruct((rows, k), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((8, k, n), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip),
    ]
    compiled = _compile_for_chip(
        lambda x, w, g: grouped.grouped_qmatmul(x, w, g, cfg=cfg), args
    )
    assert compiled.out_info.shape == (rows, n)


def test_swiglu_at_mixtral_width_compiles_for_v5e(one_chip, no_compile_cache):
    """The device SiLU·mul of ``mixtral.prefill512``: the gate|up rows of one
    layer's routed tokens in float32, and the patch of pairs the table
    decides."""
    rows = jax.ShapeDtypeStruct((2048, 2 * 14336), jnp.int8, sharding=one_chip)
    compiled = lowering._swiglu_device.lower(
        rows, in_scale=0.0625, out_scale=0.0625,
        patch=lowering.swiglu_patch(0.0625, 0.0625),
    ).compile()
    assert compiled.out_info.shape == (2048, 14336)
    assert compiled.out_info.dtype == jnp.int8


# (instances, rows, K, N) of the attention context at 2 x 512 tokens:
# Mixtral's 2 x 8 KV groups of 4 stacked query heads, MusicGen's 2 x 24 heads
CONTEXT_GEMMS = [(16, 2048, 512, 128), (48, 512, 512, 64)]


@pytest.mark.parametrize("b,m,k,n", CONTEXT_GEMMS, ids=["mixtral", "musicgen"])
def test_context_step_with_device_requantize_compiles_for_v5e(
    b, m, k, n, one_chip, no_compile_cache
):
    """The context step as a ``tpu_v5e`` compile binds it: one quantized
    generalized dense whose kernel requantizes in its epilogue (K = 512,
    scale 2^-7), compiled per instance."""
    from repro.core import ir

    probs = ir.input_((b, m, k), "int8", name="probs")
    v = ir.input_((b, k, n), "int8", name="v")
    graph = ir.Graph([ir.requantize(ir.dense(probs, v), scale=2.0**-7)])
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(lowering, "pallas_interpret_mode", lambda: False)
        module = repro.compile(graph, repro.Target("tpu_v5e", cache=False))
    (op,) = module.ops.values()
    cfg = op.executor.kernel_config
    assert op.node.op == "generalized_dense" and cfg.interpret is False
    assert cfg.requant_scale == 2.0**-7 and cfg.out_dtype == "int8"
    args = [
        jax.ShapeDtypeStruct((m, k), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((k, n), jnp.int8, sharding=one_chip),
        None,
    ]
    compiled = _compile_for_chip(op.executor.run_kernel, args)
    assert compiled.out_info.shape == (m, n) and compiled.out_info.dtype == jnp.int8
