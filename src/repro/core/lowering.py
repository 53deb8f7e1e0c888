"""Backend lowering: (node, strategy) -> executable callable per target.

Split out of the old ``pipeline.py`` monolith so executor construction is
testable without the scheduling machinery.  Two paths:

  * **Gemmini-style** (numpy): tensorized tiled loop nest over the
    registered compute intrinsic, with the fused epilogue (requantize/clip
    or activation), the optional pooling and residual epilogues the graph
    optimizer fuses in, and plan-time specialization over constant
    operands (pre-padded weight panels, bias preloaded as the initial
    accumulator tile).
  * **Pallas** (TPU targets always; any accelerator when the target sets
    ``use_pallas=True``): the schedule lowers to a ``pl.pallas_call``
    kernel config — interpret mode on CPU hosts, real Mosaic on TPU;
    quantized ops take the int8 kernel with fused requant+clip, convs run
    host-side im2col first, batched 3-D denses replay the per-sample
    kernel per instance.

Epilogue attribute contract on generalized ops (set by the passes):

  * ``quantized`` + ``requant_scale``/``clip_lo``/``clip_hi`` — fused
    quantized epilogue;
  * ``activation`` — "relu" | "gelu" | None (float path);
  * ``transpose_b`` — the 2-D weight operand arrives transposed (folded
    layout transpose); the executor reads it as a free view;
  * ``pool`` — ``{"size", "stride", "conv_shape"}``: max-pool the conv
    output (applied after the elementwise epilogue, exactly like the
    unfused graph);
  * ``residual`` — one extra trailing input added to the epilogued output
    (fused skip connection; applied last).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import threading
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trace
from repro.core.accel import AcceleratorDescription
from repro.core.intrinsics import HardwareIntrinsicGenerator
from repro.core.ir import (
    Node,
    execute_node,
    gelu_ref,
    max_pool2d_ref,
    requantize_ref,
    silu_mul_ref,
)
from repro.core.mapping import MappingGenerator
from repro.core.scheduler import grouped_block_m
from repro.core.strategy import Strategy


def make_accel_executor(
    desc: AcceleratorDescription,
    mapping_gen: MappingGenerator,
    intrinsic_gen: HardwareIntrinsicGenerator,
    node: Node,
    strategy: Strategy,
    *,
    use_pallas: bool = False,
) -> Callable:
    attrs = node.attrs
    fused_epilogue = resolved_fused_epilogue(node, strategy)
    if fused_epilogue:
        missing = [
            k
            for k in ("requant_scale", "clip_lo", "clip_hi")
            if attrs.get(k) is None
        ]
        if missing:
            source = (
                "node attrs"
                if attrs.get("quantized")
                else f"core compute {strategy.compute.name!r}"
            )
            raise ValueError(
                f"{node.name}: quantized {node.op} (flag from {source}) is "
                f"missing required epilogue attrs {missing}; legalization "
                f"sets them when fusing requantize/clip, hand-built "
                f"generalized ops must provide them"
            )

    if node.op.replace("generalized_", "") == "grouped_dense":
        return _make_grouped_executor(
            desc, mapping_gen, node, strategy, runs_pallas(desc, use_pallas)
        )
    if runs_pallas(desc, use_pallas):
        return _make_pallas_executor(
            desc, mapping_gen, node, strategy, fused_epilogue
        )
    return _make_gemmini_executor(
        desc, mapping_gen, intrinsic_gen, node, strategy, fused_epilogue
    )


def runs_pallas(desc: AcceleratorDescription, use_pallas: bool) -> bool:
    """Whether accelerator steps run the scheduled Pallas kernel: always on a
    TPU description, and on a described accelerator when the target asks
    (otherwise it is emulated in numpy)."""
    return use_pallas or desc.name.startswith("tpu")


def resolved_fused_epilogue(node: Node, strategy: Strategy) -> bool:
    """ONE resolved fused-epilogue flag: an explicit node attr wins
    (legalization sets quantized=False on float fused ops), otherwise the
    bound core compute decides.  The fused requantize/clip epilogue exists
    only on generalized (legalized) ops — a raw dense/conv in naive mode
    keeps its epilogue as separate graph nodes."""
    node_flag = node.attrs.get("quantized")
    quantized = bool(
        strategy.compute.quantized if node_flag is None else node_flag
    )
    return quantized and node.op.startswith("generalized")


def kernel_config_for(
    desc: AcceleratorDescription,
    mapping_gen: MappingGenerator,
    node: Node,
    strategy: Strategy,
):
    """Derive the schedule-determined Pallas kernel config for one
    accelerator step — the single derivation ``_make_pallas_executor``
    binds and the AOT artifact manifest records.  ``interpret`` reflects
    the *current* execution environment (it is a runtime property, not
    part of the compiled schedule)."""
    attrs = node.attrs
    fused_quant = resolved_fused_epilogue(node, strategy)
    int_acc = np.issubdtype(np.dtype(node.inputs[0].dtype), np.integer)
    if fused_quant:
        epilogue = {
            "requant_scale": attrs["requant_scale"],
            "clip_lo": attrs["clip_lo"],
            "clip_hi": attrs["clip_hi"],
        }
    else:
        epilogue = {"activation": attrs.get("activation")}
    out_dtype = node.dtype
    return mapping_gen.to_kernel_config(
        strategy.schedule,
        acc_dtype="int32" if (fused_quant or int_acc) else "float32",
        out_dtype=out_dtype if out_dtype != "float64" else "float32",
        epilogue=epilogue,
        interpret=pallas_interpret_mode(),
        has_bias=len(node.inputs) > 2 and node.inputs[2] is not None,
    )


def pallas_interpret_mode() -> bool:
    """Interpret-mode Pallas everywhere except a real TPU backend.

    Interpret mode executes the same kernel, BlockSpecs, and grid in pure
    XLA-on-host, so CPU CI covers the exact tiling the cycle model priced;
    on a TPU host the kernels compile through Mosaic.
    """
    return jax.default_backend() != "tpu"


def _im2col(x: np.ndarray, kh: int, kw: int, stride: int, padding: int) -> np.ndarray:
    # registered preprocessing: im2col on the host (non-constant
    # operand), then the conv is exactly the scheduled GEMM with
    # HWIO weights flattened to (kh*kw*ci, co) — §3.2.
    if padding:
        x = np.pad(x, ((0, 0), (padding, padding), (padding, padding), (0, 0)))
    n, h, wd, ci = x.shape
    oh = (h - kh) // stride + 1
    ow = (wd - kw) // stride + 1
    cols = np.empty((n * oh * ow, kh * kw * ci), dtype=x.dtype)
    idx = 0
    for b_ in range(n):
        for i in range(oh):
            for j in range(ow):
                patch = x[
                    b_,
                    i * stride : i * stride + kh,
                    j * stride : j * stride + kw,
                    :,
                ]
                cols[idx] = patch.reshape(-1)
                idx += 1
    return cols


def _to_device(a, traced: bool):
    """``a`` on the device: a host array is uploaded and counted (inside a
    ``repro.h2d`` span when ``traced``); a ``jax.Array`` moves nothing."""
    if isinstance(a, jax.Array):
        return a
    a = np.asarray(a)
    trace.count_h2d(a.nbytes)
    if traced:
        with trace.TraceAnnotation("repro.h2d", bytes=a.nbytes):
            return jnp.asarray(a)
    return jnp.asarray(a)


def _to_host(a, traced: bool) -> np.ndarray:
    """A kernel's result synced back to the host, counted (inside a
    ``repro.d2h`` span when ``traced``)."""
    if traced:
        with trace.TraceAnnotation("repro.d2h", bytes=a.nbytes):
            out = np.asarray(a)
    else:
        out = np.asarray(a)
    trace.count_d2h(out.nbytes)
    return out


def _make_gemmini_executor(
    desc: AcceleratorDescription,
    mapping_gen: MappingGenerator,
    intrinsic_gen: HardwareIntrinsicGenerator,
    node: Node,
    strategy: Strategy,
    fused_epilogue: bool,
) -> Callable:
    """Tensorized tiled numpy executor + fused epilogue chain."""
    attrs = node.attrs
    intr = desc.compute_intrinsic_for_tag(strategy.compute.tag)
    intrinsic_gen.tensorize_check(strategy.compute.tag, strategy.schedule)
    tiled = mapping_gen.to_tiled_executor(strategy.schedule, intr)
    is_conv = node.op.endswith("conv2d")
    # batched activation-activation matmul: both operands carry a leading
    # batch dim (attention scores/context).  The schedule covers the
    # per-sample GEMM; the executor replays it per batch instance.
    is_bmm = not is_conv and len(node.inputs[1].shape) == 3
    transpose_b = bool(attrs.get("transpose_b")) and not is_conv
    stride = attrs.get("stride", 1)
    padding = attrs.get("padding", 0)
    out_shape, out_dtype = node.shape, node.dtype
    activation = attrs.get("activation")
    pool = attrs.get("pool")
    # the elementwise epilogue runs over the conv's own output; pooling
    # then reduces it to the node shape.
    pre_shape = tuple(pool["conv_shape"]) if pool else out_shape

    if pool:
        pool_size, pool_stride = pool["size"], pool["stride"]

        def _finish(out):
            out = out.reshape(pre_shape).astype(out_dtype)
            return max_pool2d_ref(out, pool_size, pool_stride)

    else:

        def _finish(out):
            return out.reshape(out_shape).astype(out_dtype)

    if fused_epilogue:
        requant_scale = attrs["requant_scale"]
        clip_lo, clip_hi = attrs["clip_lo"], attrs["clip_hi"]

        def _epilogue(acc):
            # np.rint == np.round(decimals=0) (half-to-even), and
            # int64 * float scalar promotes to float64 elementwise —
            # bit-identical to astype(float64)-then-multiply for GEMM
            # accumulator magnitudes, minus one allocation.
            out = np.rint(acc * requant_scale)
            out = out.clip(clip_lo, clip_hi)
            return _finish(out)

    elif activation == "relu":

        def _epilogue(acc):
            return _finish(np.maximum(acc, 0))

    elif activation == "gelu":

        def _epilogue(acc):
            return _finish(gelu_ref(acc))

    else:

        def _epilogue(acc):
            return _finish(acc)

    # batched-matmul fast path: integer accumulation is exact, so one
    # vectorized int64 ``np.matmul`` over all instances is bit-identical to
    # replaying the tile loop per instance — verified once at plan-build
    # time by a random-operand probe against the tiled executor (a custom
    # intrinsic with non-multiply-add semantics, e.g. saturating, fails the
    # probe and keeps the faithful per-instance loop).  Decode serving runs
    # the attention GEMMs [B, 1, d] @ [B, d, L] every step: per-instance
    # tile-loop overhead, not arithmetic, dominated that path.
    bmm_fast = False
    if is_bmm and all(np.dtype(i.dtype).kind in "iu" for i in node.inputs[:2]):
        _b, _m, _c = node.inputs[0].shape
        _k = node.shape[-1]
        _rng = np.random.default_rng(0)
        _xs = _rng.integers(-128, 128, (_m, _c)).astype(node.inputs[0].dtype)
        _ws = _rng.integers(-128, 128, (_c, _k)).astype(node.inputs[1].dtype)
        try:
            bmm_fast = np.array_equal(
                tiled(_xs, _ws), _xs.astype(np.int64) @ _ws.astype(np.int64)
            )
        except Exception:
            bmm_fast = False

    def gemmini_exec(x, w, bias=None, residual=None):
        x = np.asarray(x)
        w = np.asarray(w)
        if is_conv:
            kh, kw, ci, co = w.shape
            x2 = _im2col(x, kh, kw, stride, padding)
            w2 = w.reshape(kh * kw * ci, co)
            acc = tiled(x2, w2)
        elif is_bmm:
            wb = w.swapaxes(-2, -1) if transpose_b else w
            if bmm_fast:
                acc = np.matmul(x.astype(np.int64), wb.astype(np.int64))
            else:
                acc = np.stack([tiled(xs, ws) for xs, ws in zip(x, wb)])
        else:
            x2 = x.reshape(-1, x.shape[-1])
            w2 = w.T if transpose_b else w
            acc = tiled(x2, w2)
        if bias is not None:
            acc = acc + np.asarray(bias).astype(np.int64)
        out = _epilogue(acc)
        if residual is not None:
            out = out + residual
        return out

    def specialize_consts(consts: dict[int, np.ndarray]):
        """Plan-time specialization over compile-time-constant inputs
        (weights, bias): conv weights are flattened, folded layout
        transposes are materialized once, and the weight panel padded to
        the schedule's (pk, pn) once, instead of on every call.  When the
        whole padded GEMM fits a single PE tile — the common case for
        serving-size layers — the intrinsic consumes the unpadded operands
        directly (tile limits are maxima), with the constant bias preloaded
        as the initial accumulator tile, exactly as a weight-stationary
        array preloads its accumulator.  Bit-identical to ``gemmini_exec``
        (zero-padding contributes exact zeros to integer accumulation); the
        per-node interpreter cannot do any of this because it re-reads the
        graph each run."""
        if is_bmm or 1 not in consts:
            # batched-matmul weights are activations; nothing to pre-pad
            return None
        w = np.asarray(consts[1])
        if is_conv:
            kh, kw, ci, co = w.shape
            w2 = w.reshape(kh * kw * ci, co)
            conv_dims = (kh, kw)
        else:
            w2 = np.ascontiguousarray(w.T) if transpose_b else w
            conv_dims = None
        n_out = w2.shape[1]
        wp = tiled.pad_w(w2)
        run_prepadded = tiled.prepadded
        has_const_bias = 2 in consts
        bias_c = (
            np.asarray(consts[2]).astype(np.int64) if has_const_bias else None
        )
        sched = strategy.schedule
        pe = sched.pe_tile()
        single_tile = all(sched.padded(j) == pe[j] for j in ("N", "C", "K"))
        intr_fn = intr.fn
        m_stat, k_stat = strategy.workload.N, strategy.workload.C
        x_dt = np.dtype(node.inputs[0].dtype)
        acc_shape = (m_stat, n_out)

        # single-call fast path, verified once by a zero-input probe:
        # the intrinsic must pass the initial accumulator through
        # unchanged (the same contract the generic k-loop accumulation
        # relies on) and must not mutate its operands.  Anything
        # surprising falls back to the padded tile loop.
        fast_init = None
        has_bias_operand = len(node.inputs) > 2 and node.inputs[2] is not None
        if single_tile and (has_const_bias or not has_bias_operand):
            if has_const_bias:
                init = np.broadcast_to(bias_c, acc_shape)  # read-only view
            else:
                init = np.zeros(acc_shape, dtype=np.int64)
                # an in-place-accumulating intrinsic would corrupt the
                # shared init across calls AND slip past a zero-input
                # probe; read-only makes it raise (and fall back) instead.
                init.setflags(write=False)
            try:
                probe = intr_fn(np.zeros((m_stat, k_stat), x_dt), w2, init)
                if (
                    getattr(probe, "shape", None) == acc_shape
                    and np.array_equal(probe, init)
                    and (not has_const_bias or np.array_equal(init[0], bias_c))
                ):
                    fast_init = init
            except Exception:
                fast_init = None

        if fused_epilogue:
            # preallocated requantize scratch (shapes are static per
            # node); the arena value is always the fresh array the final
            # astype produces, so scratch reuse can never alias results.
            # The scratch is THREAD-LOCAL: compiled modules are shared
            # across serving threads, and a process-wide buffer would let
            # two concurrent calls requantize into each other.
            scratch = threading.local()
            clip_lo_, clip_hi_ = attrs["clip_lo"], attrs["clip_hi"]
            scale_ = attrs["requant_scale"]

            def _epilogue_planned(acc):
                if acc.shape != acc_shape:
                    return _epilogue(acc)
                fbuf = getattr(scratch, "fbuf", None)
                if fbuf is None:
                    fbuf = scratch.fbuf = np.empty(acc_shape, dtype=np.float64)
                np.multiply(acc, scale_, out=fbuf)
                np.rint(fbuf, out=fbuf)
                fbuf.clip(clip_lo_, clip_hi_, out=fbuf)
                return _finish(fbuf)

        else:
            _epilogue_planned = _epilogue

        def gemmini_exec_planned(x, w=None, bias=None, residual=None):
            x = np.asarray(x)
            if conv_dims is not None:
                x2 = _im2col(x, *conv_dims, stride, padding)
            else:
                x2 = x.reshape(-1, x.shape[-1])
            if (
                fast_init is not None
                and x2.shape == (m_stat, k_stat)
                and x2.dtype == x_dt
            ):
                out = _epilogue_planned(intr_fn(x2, w2, fast_init))
            else:
                acc = run_prepadded(x2, wp, n_out)
                if has_const_bias:
                    acc = acc + bias_c
                elif bias is not None:
                    acc = acc + np.asarray(bias).astype(np.int64)
                out = _epilogue_planned(acc)
            if residual is not None:
                out = out + residual
            return out

        return gemmini_exec_planned

    gemmini_exec.specialize_consts = specialize_consts
    return gemmini_exec


def _make_pallas_executor(
    desc: AcceleratorDescription,
    mapping_gen: MappingGenerator,
    node: Node,
    strategy: Strategy,
    fused_quant: bool,
) -> Callable:
    """Lower one accelerator step to the scheduled Pallas GEMM/qGEMM.

    ``fused_quant`` is the resolved fused-epilogue flag from
    ``make_accel_executor``: the int8 kernel with fused requantize/clip.
    Every step shape the emulated path supports lowers here too:

      * conv2d runs host-side im2col, then the scheduled GEMM over the
        flattened HWIO weight panel (same §3.2 preprocessing the Gemmini
        path registers);
      * batched activation-activation matmuls (PR-5 3-D dense) replay the
        per-sample scheduled kernel per batch instance — one jit compile,
        since instances share shape and config.  A quantized one (the
        attention context) requantizes in the kernel's epilogue where
        ``_bmm_requant_exact`` holds, and syncs int8; elsewhere it runs
        the int32 kernel and requantizes on the host after the sync, in
        row blocks (``ir.requantize_ref``).  Counts
        ``bmm_requant_device_rows`` / ``bmm_requant_host_rows``;
      * the ``pool`` epilogue reduces the epilogued conv output on the
        host, and ``residual`` is added last, exactly like the emulated
        executor.

    Integer inputs always accumulate in int32 (not just the fused path):
    int32 accumulation wraps mod 2^32 identically to the emulated
    int64-accumulate-then-cast, so unfused naive-mode int GEMMs stay
    bit-exact.
    """
    from repro.kernels import ops as kops

    attrs = node.attrs
    is_conv = node.op.endswith("conv2d")
    is_bmm = not is_conv and len(node.inputs[1].shape) == 3
    transpose_b = bool(attrs.get("transpose_b")) and not is_conv
    stride = attrs.get("stride", 1)
    padding = attrs.get("padding", 0)
    pool = attrs.get("pool")
    out_shape, out_dtype = node.shape, node.dtype
    pre_shape = tuple(pool["conv_shape"]) if pool else out_shape
    # mirror the emulated ``_epilogue`` selection exactly: the fused
    # requantize/clip only fires on resolved-quantized generalized ops;
    # everything else gets at most an activation.
    cfg = kernel_config_for(desc, mapping_gen, node, strategy)
    # a quantized batched dense whose requantize the kernel cannot do with
    # the host's bits (``_bmm_requant_exact``) runs the int32 kernel, and
    # the host requantizes its result after the sync
    host_requant = is_bmm and fused_quant and not _bmm_requant_exact(node)
    if host_requant:
        cfg = dataclasses.replace(
            cfg, requant_scale=None, clip_lo=None, clip_hi=None, out_dtype="int32"
        )
        scale, clip_lo, clip_hi = (
            attrs["requant_scale"], attrs["clip_lo"], attrs["clip_hi"]
        )
    bmm_rows = math.prod(out_shape[:-1]) if is_bmm and fused_quant else 0

    def _run2d(x_j, w_j, b_j):
        if fused_quant and not host_requant:
            return kops.qmatmul(x_j, w_j, b_j, cfg)
        return kops.matmul(x_j, w_j, cfg, b_j)

    if pool:
        pool_size, pool_stride = pool["size"], pool["stride"]

        def _finish(out):
            out = out.reshape(pre_shape).astype(out_dtype)
            return max_pool2d_ref(out, pool_size, pool_stride)

    else:

        def _finish(out):
            return out.reshape(out_shape).astype(out_dtype)

    def _launch(on, x_j, w_j, b_j):
        trace.count_launch()
        if on:
            with trace.TraceAnnotation("repro.launch"):
                return _run2d(x_j, w_j, b_j)
        return _run2d(x_j, w_j, b_j)

    def _device_out(on, x, w, b_j):
        """The kernel's result, left on the device."""
        if is_conv:
            w = np.asarray(w)
            kh, kw, ci, co = w.shape
            x2 = _im2col(np.asarray(x), kh, kw, stride, padding)
            w2 = w.reshape(kh * kw * ci, co)
            return _launch(on, _to_device(x2, on), _to_device(w2, on), b_j)
        if is_bmm:
            x_j = _to_device(x, on)
            w_j = _to_device(w, on)
            if transpose_b:
                w_j = w_j.swapaxes(-2, -1)
            return jnp.stack(
                [_launch(on, x_j[i], w_j[i], b_j) for i in range(x_j.shape[0])]
            )
        w_j = _to_device(w, on)
        if transpose_b:
            w_j = w_j.T
        return _launch(on, _to_device(x, on), w_j, b_j)

    def pallas_exec(x, w, bias=None, residual=None):
        on = trace.enabled()
        b_j = _to_device(bias, on) if bias is not None else None
        out = _to_host(_device_out(on, x, w, b_j), on)
        if host_requant:
            out = requantize_ref(out, scale, clip_lo, clip_hi, out_dtype)
            trace.count_bmm_requant_rows(0, bmm_rows)
        elif bmm_rows:
            trace.count_bmm_requant_rows(bmm_rows, 0)
        out = _finish(out)
        if residual is not None:
            out = out + residual
        return out

    # what the step runs: the bound kernel config, and the 2-D kernel call
    # (x, w, bias) that ``jax.jit(...).lower`` can compile ahead of time
    pallas_exec.kernel_config = cfg
    pallas_exec.run_kernel = _run2d
    if _attn_scores_exact(node):
        pallas_exec.fuse_attn_epilogue = functools.partial(
            _attn_scores_step, node, lambda on, x, w: _device_out(on, x, w, None)
        )
    return pallas_exec


# ---------------------------------------------------------------------------
# the grouped (routed-expert) dense
# ---------------------------------------------------------------------------

#: rows of an int8 tile the chip packs into one vreg sublane group: the row
#: tile of an int8 grouped GEMM is a multiple of it on Mosaic
INT8_SUBLANE = 32


def grouped_kernel_config(desc, mapping_gen, node: Node, strategy: Strategy):
    """The grouped kernel's config: the schedule's epilogue and column tile,
    with three grouped choices (``kernels/grouped.py``).  ``block_k`` is the
    whole K, so an expert's weight panel stays put across its row tiles.
    ``block_m`` is ``scheduler.grouped_block_m`` for the rows routed over the
    experts, capped by the schedule's row tile.  ``block_n`` is the widest
    column tile that divides N (lane-aligned on the chip), at most the
    schedule's, whose double-buffered whole-K blocks fit the VMEM budget."""
    cfg = kernel_config_for(desc, mapping_gen, node, strategy)
    rows = node.inputs[0].shape[0]
    n_groups, k, n = node.inputs[1].shape
    align = 8 if cfg.interpret else INT8_SUBLANE
    bm = grouped_block_m(rows, n_groups, align, max(cfg.block_m, align))
    levels = desc.arch.buffered_levels()
    budget = desc.arch.levels[levels[0]].size_bytes if levels else None
    lane = 1 if cfg.interpret else 128

    def fits(bn):
        # x and w blocks and the int8 out block double-buffered, plus the
        # int32 product
        return budget is None or 2 * bm * k + 2 * k * bn + 6 * bm * bn <= budget

    widths = [d for d in range(1, n + 1) if n % d == 0 and (d % lane == 0 or d == n)]
    capped = [d for d in widths if d <= cfg.block_n] or widths[:1]
    bn = max((d for d in capped if fits(d)), default=capped[0])
    return dataclasses.replace(
        cfg, block_m=bm, block_k=k, block_n=bn, has_bias=False, out_dtype=node.dtype
    )


def _make_grouped_executor(
    desc, mapping_gen, node: Node, strategy: Strategy, pallas: bool
) -> Callable:
    """One accelerator step for a (generalized) grouped dense: every expert's
    rows in one launch of ``kernels.grouped.grouped_qmatmul``.  The plan
    specializes the step over its constant expert weights
    (``specialize_consts``): they are uploaded by the first call and stay
    on the device (``_Resident``), so a call moves only the routed rows, the
    group sizes and the result.  Rows already on the device (a
    ``jax.Array``) move nothing.  Counts ``expert_rows``,
    ``expert_pad_rows`` and ``expert_rows_peak`` (``repro.core.trace``).
    The step offers ``fuse_silu_mul`` (``_swiglu_step``).  Off the Pallas
    path, or unquantized, the step is the IR's reference."""
    from repro.kernels import grouped as kgrouped

    if not (pallas and resolved_fused_epilogue(node, strategy)):
        return lambda *ins: execute_node(node, list(ins))
    cfg = grouped_kernel_config(desc, mapping_gen, node, strategy)
    n_groups = node.inputs[1].shape[0]

    def run_kernel(x_j, w_j, gs_j):
        return kgrouped.grouped_qmatmul(x_j, w_j, gs_j, cfg=cfg)

    def device_rows(on, x, w_j, group_sizes):
        """The kernel's result, left on the device."""
        gs = np.asarray(group_sizes)
        _, pad, peak = kgrouped.tile_layout(gs, cfg.block_m)
        trace.count_expert_rows(int(gs.sum()), pad, n_groups * peak)
        x_j, gs_j = _to_device(x, on), _to_device(gs, on)
        trace.count_launch()
        if on:
            with trace.TraceAnnotation("repro.launch"):
                return run_kernel(x_j, w_j, gs_j)
        return run_kernel(x_j, w_j, gs_j)

    def weights(consts: dict[int, np.ndarray]) -> Callable:
        """``(on, w) -> w`` on the device: a constant panel's resident copy,
        else ``w`` uploaded."""
        if 1 not in consts:
            return lambda on, w: _to_device(w, on)
        resident = _Resident(np.asarray(consts[1]))
        return lambda on, w: resident.get(on)

    def grouped_exec(x, w, group_sizes):
        on = trace.enabled()
        return _to_host(device_rows(on, x, _to_device(w, on), group_sizes), on)

    def specialize_consts(consts: dict[int, np.ndarray]):
        if 1 not in consts:
            return None
        weights_on = weights(consts)

        def grouped_resident(x, w, group_sizes):
            on = trace.enabled()
            return _to_host(device_rows(on, x, weights_on(on, w), group_sizes), on)

        return grouped_resident

    grouped_exec.kernel_config = cfg
    grouped_exec.run_kernel = run_kernel
    grouped_exec.specialize_consts = specialize_consts
    grouped_exec.fuse_silu_mul = functools.partial(
        _swiglu_step, node, device_rows, weights
    )
    return grouped_exec


# ---------------------------------------------------------------------------
# SwiGLU (SiLU·mul) on the device after the gate|up grouped GEMM
# ---------------------------------------------------------------------------


#: The device's SiLU·mul is float32 (``_swiglu_q``), the host's float64
#: (``ir.silu_mul_ref``).  Each element is a function of its own int8
#: (gate, up) pair alone, so the host's bits are a 256 x 256 table
#: (``swiglu_table``), and the plan checks the device against all of it
#: once: it runs ``_swiglu_q`` over every pair on the device, and every
#: pair whose rounded float32 value is not the table's, or lies within
#: ``SWIGLU_TOL * max(|q|, 1)`` of a half (or is not finite), joins the
#: step's patch, which takes the table's value for that pair on every
#: call.  Every other pair's float32 value lies that far from a half and
#: rounds to the table's value, so a call's value of it rounds the same
#: unless it strays by 256 float32 ulp from the check's (the same
#: operations on the same pair; a TPU v5e's float32 value of a Mixtral
#: pair errs from the float64 one by at most 63 ulp, PERF.md).  Values
#: past the int8 range saturate alike and are never near.  Some
#: ``2 * SWIGLU_TOL * |q|`` of the pairs inside the range lie that near a
#: half, at most about 0.4%, so the patch stays short: about ten pairs at
#: Mixtral's scales.  A gather from the table on the device (XLA's, 237 ms
#: a layer on a TPU v5e, PERF.md) would cost more than the host.
SWIGLU_TOL = 2.0**-16


def _pair_rows() -> np.ndarray:
    """Every int8 (gate, up) pair as ``[gate | up]`` rows: int8 ``[256, 512]``,
    ``rows[i, :256] = i - 128``, ``rows[i, 256:] = arange(-128, 128)``; the
    pair of element ``(i, j)`` has the code ``i * 256 + j``."""
    codes = np.arange(-128, 128, dtype=np.int8)
    return np.concatenate(
        [np.repeat(codes[:, None], 256, axis=1), np.tile(codes, (256, 1))], axis=1
    )


def swiglu_table(in_scale: float, out_scale: float) -> np.ndarray:
    """``silu_mul_ref`` of every int8 (gate, up) pair, flat: the entry
    ``(gate + 128) * 256 + (up + 128)``."""
    return silu_mul_ref(_pair_rows(), in_scale, out_scale).reshape(-1)


def _swiglu_q(rows, in_scale: float, out_scale: float):
    """``silu(gate) * up / out_scale`` of the int8 ``[R, 2F]`` gate|up rows,
    in float32, in ``silu_mul_ref``'s order."""
    f = rows.shape[-1] // 2
    g = rows[:, :f].astype(jnp.float32) * jnp.float32(in_scale)
    u = rows[:, f:].astype(jnp.float32) * jnp.float32(in_scale)
    return g / (1.0 + jnp.exp(-g)) * u / jnp.float32(out_scale)


def _to_int8(q):
    return jnp.clip(jnp.round(q), -128, 127).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("in_scale", "out_scale"))
def _swiglu_check(rows, *, in_scale, out_scale):
    """The device's int8 SiLU·mul of ``rows`` and where its float32 value
    lies within ``SWIGLU_TOL`` of a half."""
    q = _swiglu_q(rows, in_scale, out_scale)
    a = jnp.abs(q)
    near = (jnp.abs(jnp.abs(q - jnp.floor(q)) - 0.5) < SWIGLU_TOL * jnp.maximum(a, 1.0)) & (
        a < 129.0
    )
    return _to_int8(q), near | ~jnp.isfinite(q)


@functools.partial(jax.jit, static_argnames=("in_scale", "out_scale", "patch"))
def _swiglu_device(rows, *, in_scale, out_scale, patch):
    """``silu_mul`` of the int8 ``[R, 2F]`` gate|up rows: int8 ``[R, F]``,
    ``patch`` the ``(code, value)`` of each pair the table decides."""
    out = _to_int8(_swiglu_q(rows, in_scale, out_scale))
    if patch:
        f = rows.shape[-1] // 2
        code = (rows[:, :f].astype(jnp.int32) + 128) * 256 + rows[:, f:].astype(
            jnp.int32
        ) + 128
        for c, value in patch:
            out = jnp.where(code == c, jnp.int8(value), out)
    return out


def swiglu_patch(in_scale: float, out_scale: float) -> tuple[tuple[int, int], ...]:
    """The ``(code, table value)`` of every pair whose device value does not
    give the table's bits with room to spare (``SWIGLU_TOL``): checked on
    the device over all 65,536 pairs."""
    table = swiglu_table(in_scale, out_scale)
    out, near = _swiglu_check(
        jnp.asarray(_pair_rows()), in_scale=in_scale, out_scale=out_scale
    )
    bad = np.asarray(near).reshape(-1) | (np.asarray(out).reshape(-1) != table)
    return tuple((int(c), int(table[c])) for c in np.flatnonzero(bad))


def _swiglu_step(
    node: Node,
    device_rows: Callable,
    weights: Callable,
    *,
    in_scale: float,
    out_scale: float,
    consts: dict[int, np.ndarray],
    keep_on_device: bool,
    patches: dict,
) -> Callable:
    """One accelerator step for ``silu_mul(grouped_dense(x, w, sizes))``
    over the gate|up grouped GEMM ``node``: the kernel as the plain step
    runs it, then ``_swiglu_device`` with the scales' patch
    (``SWIGLU_TOL``); the gate|up rows never leave the device.  With
    ``keep_on_device`` (the next step is a grouped GEMM that takes rows on
    the device) the ``[R, F]`` result stays there too, as a ``jax.Array``;
    otherwise it is synced.  ``patches`` holds one patch per pair of
    scales."""
    key = (in_scale, out_scale)
    if key not in patches:
        patches[key] = swiglu_patch(in_scale, out_scale)
    patch = patches[key]
    weights_on = weights(consts)
    n_rows = node.shape[0]

    def swiglu(x, w, group_sizes):
        on = trace.enabled()
        rows = device_rows(on, x, weights_on(on, w), group_sizes)
        out = _swiglu_device(rows, in_scale=in_scale, out_scale=out_scale, patch=patch)
        trace.count_swiglu_rows(n_rows, 0)
        return out if keep_on_device else _to_host(out, on)

    return swiglu


# ---------------------------------------------------------------------------
# the attention-score epilogue on the device
# ---------------------------------------------------------------------------

#: Rounding guard of the device attention epilogue.  The host chain defines
#: the result: ``x = fl32(s * scale + mask)``, ``p = fl32(softmax_f64(x))``,
#: ``out = clip(round_half_even(fl32(p / probs_scale)), -128, 127)``.  The
#: device computes the same float32 ``x``: ``s -> float32`` is exact for
#: ``|s| <= 2^24`` (int8 operands, K <= 1024), ``s * scale`` is exact for a
#: power-of-two scale, and the mask add is the same one IEEE float32 add.
#: From ``x`` on, with ``u = 2^-24``, the device's ``q = p / probs_scale``
#: differs from the exact one by a relative error of at most
#:
#: * ``88u`` — ``x - max(x)`` rounded to float32 (``|x - m| <= 88`` wherever
#:   ``exp(x - m)`` is a normal float32; below that both paths' ``e`` lie
#:   under 2^-126 and move ``q`` by less than 2^-100);
#: * ``256u`` — ``exp``: the allowance for the device's own, which is not
#:   correctly rounded (a TPU v5e's errs by at most 112u over every float32
#:   in ``[-88, -2^-12]``, PERF.md);
#: * ``(n - 1)u`` — the float32 sum of a row of ``n``, in any order;
#: * ``64u`` — the division by the sum, and by ``probs_scale`` (a TPU
#:   v5e's sum and division together read at most 9.3u on sampled rows,
#:   PERF.md);
#:
#: and the host's ``q`` from the exact one by ``u`` (float64 to float32,
#: then the division).  So ``|q_dev - q_host| <= (n + 408)u * q``.  A row in
#: which every ``q_dev`` lies at least ``ATTN_TOL * max(q_dev, 1)`` from
#: each ``k + 1/2`` rounds exactly as the host's does whenever
#: ``(n + 408)u * (1 + 2^-10) < ATTN_TOL``: with ``ATTN_TOL = 2^-13 = 2048u``
#: that holds for rows of up to ``ATTN_MAX_ROW = 1536`` elements.  Every
#: other row (and every row with a non-finite ``q``) is flagged and
#: recomputed by the host chain itself.  2^-14 was the first choice; the
#: largest error of ``q`` read on a TPU v5e, 4.5e-6 (75u), was not under
#: its sixteenth.
ATTN_TOL = 2.0**-13
ATTN_MAX_ROW = 1536
#: at most this share of a step's rows comes back through the compact
#: gather of flagged rows; a step that flags more syncs its whole scores
_ATTN_GATHER_SHARE = 16


def _attn_scores_exact(node: Node) -> bool:
    """Whether ``node`` is a scores GEMM whose float32 epilogue on the device
    starts from the host's exact ``x`` (see ``ATTN_TOL``): an int32 dense of
    two int8 operands, no bias or residual, K <= 1024, rows of at most
    ``ATTN_MAX_ROW``."""
    return (
        node.op in ("dense", "generalized_dense")
        and node.dtype == "int32"
        and all(i is None for i in node.inputs[2:])
        and all(i.dtype == "int8" for i in node.inputs[:2])
        and node.inputs[0].shape[-1] <= 1024
        and node.shape[-1] <= ATTN_MAX_ROW
    )


def _power_of_two(scale: float) -> bool:
    return scale > 0 and math.frexp(scale)[0] == 0.5


#: the longest contraction of int8 operands whose int32 accumulator is exact
#: in float32: ``|acc| <= K * 128 * 128 = K * 2^14 <= 2^24``
BMM_REQUANT_MAX_K = 1024


def _bmm_requant_exact(node: Node) -> bool:
    """Whether the quantized GEMM kernel's epilogue, ``clip(round(float32(acc)
    * scale))``, gives the host's float64 ``clip(rint(acc * scale))`` bit for
    bit on every instance of the batched dense ``node``: int8 operands with
    K <= ``BMM_REQUANT_MAX_K`` and no bias, so ``float32(acc)`` is exact; a
    power-of-two scale, so the product is exact too (or, far out of range,
    saturates alike); both then round the same value half to even.  An int8
    result, as the kernel writes.  Decided from the node, once."""
    return (
        all(i.dtype == "int8" for i in node.inputs[:2])
        and (len(node.inputs) < 3 or node.inputs[2] is None)
        and node.inputs[0].shape[-1] <= BMM_REQUANT_MAX_K
        and node.dtype == "int8"
        and _power_of_two(node.attrs["requant_scale"])
    )


def _attn_q(s, mask, scale: float, probs_scale: float):
    """``p / probs_scale`` of the scores ``s``, in float32, in the host
    chain's order."""
    x = s.astype(jnp.float32) * jnp.float32(scale)
    if mask is not None:
        x = x + mask
    e = jnp.exp(x - jnp.max(x, axis=-1, keepdims=True))
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    return p / jnp.float32(probs_scale)


def _attn_out(q, s, capacity: int):
    """The int8 result, the per-row flags of the rounding guard, and the
    scores of the first ``capacity`` flagged rows."""
    out = jnp.clip(jnp.round(q), -128, 127).astype(jnp.int8)
    near = jnp.abs(jnp.abs(q - jnp.floor(q)) - 0.5) < ATTN_TOL * jnp.maximum(q, 1.0)
    flags = jnp.any(near | ~jnp.isfinite(q), axis=-1)
    rows = s.reshape(-1, s.shape[-1])
    (idx,) = jnp.nonzero(flags.reshape(-1), size=capacity, fill_value=0)
    return out, flags, rows[idx]


@functools.partial(
    jax.jit, static_argnames=("shape", "scale", "probs_scale", "capacity")
)
def _attn_epilogue(s, mask, *, shape, scale, probs_scale, capacity):
    s = s.reshape(shape)
    return _attn_out(_attn_q(s, mask, scale, probs_scale), s, capacity)


class _Resident:
    """A constant's device copy, uploaded by the first call that needs it."""

    def __init__(self, value: np.ndarray):
        self.value = value
        self._dev = None
        self._lock = threading.Lock()

    def get(self, on: bool):
        if self._dev is None:
            with self._lock:
                if self._dev is None:
                    self._dev = _to_device(self.value, on)
        return self._dev


def _attn_scores_step(
    node: Node,
    device_scores: Callable,
    *,
    scale: float,
    probs_scale: float,
    mask: np.ndarray | None,
    mask_first: bool,
    host_ops: tuple,
    residents: dict,
) -> Callable | None:
    """One accelerator step for ``quantize(softmax(dequantize(s) [+ mask]))``
    over the scores GEMM ``node``: the kernels as the plain step runs them,
    then ``_attn_epilogue`` on the device; only the int8 result and the
    row flags are synced.  Flagged rows are recomputed by ``host_ops``
    (dequantize, add or None, softmax, quantize: the plan's own closures
    for those nodes) on those rows' scores.  None where the scale is not a
    power of two, so ``ATTN_TOL``'s argument does not hold."""
    if not _power_of_two(scale):
        return None
    dequantize, add, softmax, quantize = host_ops
    shape = tuple(node.shape)
    n = shape[-1]
    n_rows = math.prod(shape[:-1])
    capacity = -(-n_rows // _ATTN_GATHER_SHARE)
    resident = None
    if mask is not None:
        resident = residents.setdefault(id(mask), _Resident(mask))
    row_shape = (1,) * (len(shape) - 2) + (n,)

    def host_rows(s_rows, idx):
        """The host chain over the rows ``idx`` (flat row numbers)."""
        shaped = (len(idx),) + row_shape
        x = dequantize(s_rows.reshape(shaped))
        if add is not None:
            lead = np.unravel_index(idx, shape[:-1])
            m_rows = np.broadcast_to(mask, shape)[lead].reshape(shaped)
            x = add(m_rows, x) if mask_first else add(x, m_rows)
        return quantize(softmax(x))

    def attn_scores(x, w, bias=None, residual=None):
        on = trace.enabled()
        s = device_scores(on, x, w)
        m = resident.get(on) if resident is not None else None
        out_d, flags_d, picked_d = _attn_epilogue(
            s, m, shape=shape, scale=scale, probs_scale=probs_scale,
            capacity=capacity,
        )
        out = _to_host(out_d, on)
        idx = np.flatnonzero(_to_host(flags_d, on))
        trace.count_attn_rows(n_rows, len(idx))
        if not len(idx):
            return out
        if len(idx) <= capacity:
            s_rows = _to_host(picked_d, on)[: len(idx)]
        else:
            s_rows = _to_host(s, on).reshape(n_rows, n)[idx]
        with trace.span("repro.host.softmax_fallback"):
            out = out.reshape(n_rows, n).copy()
            out[idx] = host_rows(s_rows, idx).reshape(len(idx), n)
            return out.reshape(shape)

    return attn_scores
