"""The planned graph executor: host-op compilation, the slot-indexed
execution plan, and the compiled module (execution + cycle model).

Split out of the old ``pipeline.py`` monolith so plan building is testable
without a backend: ``build_plan(graph, {})`` lowers any host-only graph.
``repro.core.pipeline`` re-exports everything here for compatibility.
"""

from __future__ import annotations

import math
import queue
import threading
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

from repro.core import trace
from repro.core.accel import AcceleratorDescription
from repro.core.collective import collective_cycles, collective_fn
from repro.core.ir import (
    COLLECTIVE_OPS,
    MOE_HOST_OPS,
    Graph,
    Node,
    execute_node,
    gelu_ref,
    kv_append_ref,
    max_pool2d_ref,
    moe_combine_ref,
    moe_dispatch_ref,
    moe_group_sizes_ref,
    moe_route_ref,
    rms_norm_ref,
    rope_ref,
    silu_mul_ref,
)
from repro.core.simulator import simulate
from repro.core.strategy import Strategy, dtype_bytes, gemm_instances

# Zero-copy view ops: free in the cycle model (no data movement, the host
# just reinterprets the buffer).  One canonical set so the cycle model and
# the layout-op class below can never disagree about what a view is.
FREE_VIEW_OPS = {"reshape", "flatten"}

# host-op cost classes for the cycle model
_LAYOUT_OPS = {"transpose", "im2col", "quantize"} | FREE_VIEW_OPS
_EPILOGUE_OPS = {
    "requantize",
    "clip",
    "bias_add",
    "dequantize",
    "relu",
    "gelu",
    "add",
    "sub",
    "mul",
    "softmax",
    "max_pool2d",
} | MOE_HOST_OPS


@dataclass
class CompiledOp:
    node: Node
    strategy: Strategy
    executor: Callable[..., np.ndarray]


def compile_host_op(n: Node) -> Callable[..., np.ndarray]:
    """Specialize one host op into a direct closure: attrs/dtype lookups and
    the ``execute_node`` if-chain dispatch happen here, once, at plan-build
    time instead of on every call.  Semantics are bit-identical to
    ``execute_node`` (tests/test_host_ops.py holds both paths to that for
    every op in ``ir.HOST_OPS``)."""
    op, attrs, dtype = n.op, n.attrs, n.dtype
    if op == "relu":
        return lambda x: np.maximum(x, 0)
    if op == "gelu":
        return lambda x: gelu_ref(x).astype(dtype)
    if op == "add":
        return lambda a, b: a + b
    if op == "sub":
        return lambda a, b: a - b
    if op == "mul":
        return lambda a, b: a * b
    if op == "clip":
        lo, hi = attrs["lo"], attrs["hi"]
        return lambda x: np.clip(x, lo, hi).astype(dtype)
    if op == "requantize":
        scale = attrs["scale"]
        if dtype.startswith(("int", "uint")):
            info = np.iinfo(dtype)
            lo, hi = info.min, info.max
            return lambda x: np.clip(
                np.round(x.astype(np.float64) * scale), lo, hi
            ).astype(dtype)
        return lambda x: np.round(x.astype(np.float64) * scale).astype(dtype)
    if op == "quantize":
        scale = attrs["scale"]
        return lambda x: np.clip(np.round(x / scale), -128, 127).astype(dtype)
    if op == "dequantize":
        scale = attrs["scale"]
        return lambda x: x.astype(np.float32) * scale
    if op == "transpose":
        perm = attrs["perm"]
        return lambda x: np.transpose(x, perm)
    if op in FREE_VIEW_OPS:
        shape = attrs["shape"] if op == "reshape" else n.shape
        return lambda x: x.reshape(shape)
    if op == "max_pool2d":
        size, stride = attrs["size"], attrs["stride"]
        return lambda x: max_pool2d_ref(x, size, stride)
    if op == "bias_add":
        if dtype.startswith("int"):
            return lambda x, b: (
                x.astype(np.int64) + b.astype(np.int64)
            ).astype(dtype)
        return lambda x, b: x + b
    if op == "shard_slice":
        ax, rank, parts = attrs["axis"], attrs["rank"], attrs["parts"]

        def _shard_slice(x):
            size = x.shape[ax] // parts
            idx = [slice(None)] * x.ndim
            idx[ax] = slice(rank * size, (rank + 1) * size)
            return x[tuple(idx)]

        return _shard_slice
    if op in COLLECTIVE_OPS:
        # rendezvous through the thread-local CollectiveSession the
        # ShardedModule binds per call (identity when parts == 1)
        return collective_fn(
            op,
            attrs["group"],
            attrs["rank"],
            attrs["parts"],
            attrs["axis"],
            dtype,
        )
    if op == "softmax":
        ax = attrs.get("axis", -1)

        def _softmax(x):
            xf = x.astype(np.float64)
            e = np.exp(xf - np.max(xf, axis=ax, keepdims=True))
            return (e / np.sum(e, axis=ax, keepdims=True)).astype(dtype)

        return _softmax
    if op == "kv_cache_read":
        return lambda cache: np.asarray(cache)
    if op == "kv_cache_append":
        return kv_append_ref
    if op == "rms_norm":
        s_in, s_out, eps = attrs["in_scale"], attrs["out_scale"], attrs["eps"]
        return lambda x: rms_norm_ref(x, s_in, s_out, eps)
    if op == "rope":
        s_in, s_out = attrs["in_scale"], attrs["out_scale"]
        return lambda x, cos, sin: rope_ref(x, cos, sin, s_in, s_out)
    if op == "silu_mul":
        s_in, s_out = attrs["in_scale"], attrs["out_scale"]

        def _silu_mul(x):
            trace.count_swiglu_rows(0, math.prod(x.shape[:-1]))
            return silu_mul_ref(x, s_in, s_out)

        return _silu_mul
    if op == "moe_route":
        top_k = attrs["top_k"]
        return lambda logits: moe_route_ref(logits, top_k)
    if op == "moe_group_sizes":
        n_experts = attrs["n_experts"]
        return lambda idx: moe_group_sizes_ref(idx, n_experts)
    if op == "moe_dispatch":
        return moe_dispatch_ref
    if op == "moe_combine":
        l_scale, scale = attrs["logit_scale"], attrs["scale"]
        return lambda y, idx, logits: moe_combine_ref(y, idx, logits, l_scale, scale)
    # anything else (dense/conv left on the host, exotic ops): fall back to
    # the reference interpreter for this node only.
    return lambda *ins, _n=n: execute_node(_n, list(ins))


class FeedError(KeyError, ValueError):
    """A ``run``/``run_many`` feeds dict does not match the module's input
    signature; the message lists every unknown and missing name plus the
    expected signature.  Subclasses ``KeyError`` so pre-existing callers
    catching the old missing-feed error keep working."""

    def __init__(self, message: str):
        self.message = message
        super().__init__(message)

    def __str__(self):  # KeyError would repr() the message
        return self.message


# arena slot 0 permanently holds None so optional (absent) operands can be
# addressed like any other input slot.
_NONE_SLOT = 0


@dataclass
class PlanStep:
    """One computed node: write ``fn(*arena[arg_slots])`` into ``slot``.

    ``lane`` is the pipeline stage the step is assigned to at plan-build
    time: ``"accel"`` for accelerator-offloaded steps, ``"host"`` for
    everything else.  The pipelined executor runs the two lanes on two
    threads with watermark synchronization (see ``ExecutionPlan``)."""

    slot: int
    fn: Callable[..., np.ndarray]
    arg_slots: tuple[int, ...]
    op: str
    name: str
    lane: str = "host"


class _LaneFailure(Exception):
    """Internal: the other pipeline lane aborted; unwind quietly."""


class _PipelineRun:
    """Shared synchronization state of one pipelined execution stream: one
    condition variable + abort flag covering every in-flight call, so a
    failure in either lane (on any call) wakes every waiter."""

    __slots__ = ("cond", "aborted")

    def __init__(self):
        self.cond = threading.Condition()
        self.aborted = False

    def abort(self) -> None:
        with self.cond:
            self.aborted = True
            self.cond.notify_all()


class _CallState:
    """Per-call lane watermarks: ``done[lane]`` counts completed steps."""

    __slots__ = ("run", "done")

    def __init__(self, run: _PipelineRun):
        self.run = run
        self.done = {"host": 0, "accel": 0}


#: sentinel pushed into the arena-handoff queue to stop the host-lane worker
_STOP = object()

#: the span of one plan execution on one feed set
EXECUTE_SPAN = "repro.plan.execute"


def step_span(step: PlanStep) -> str:
    """The span of one plan step: ``repro.<lane>.<op>``."""
    lane = "accel" if step.lane == "accel" else "host"
    return f"repro.{lane}.{step.op}"


@dataclass
class ExecutionPlan:
    """Compile-time execution plan: topological op order, input/output slot
    indices, and pre-resolved per-step callables over a flat buffer arena.

    ``CompiledModule.run`` walks ``steps`` as a flat loop — no graph
    traversal, no dict-of-Node hashing, no per-call op dispatch.  Constants
    are materialized into the arena once, when it is created, and survive
    across calls (the arena is reused by ``run_many``).

    Steps additionally carry a dependency-aware *stage assignment* computed
    here at build time: each step belongs to a lane (``host`` / ``accel``)
    and records the cross-lane watermark it must wait for (how many steps
    of the *other* lane must have completed before its operands exist).
    The pipelined executor runs the host lane on a worker thread and the
    accelerator lane on the caller's thread; within a lane steps execute in
    topological order, so same-lane dependencies are free and cross-lane
    dependencies reduce to one monotone counter per lane — bit-exact with
    the sequential loop by construction (same fns, same operands)."""

    n_slots: int
    input_slots: tuple[tuple[str, int], ...]  # (feed name, arena slot)
    const_slots: tuple[tuple[int, np.ndarray], ...]
    steps: tuple[PlanStep, ...]
    output_slots: tuple[int, ...]

    def __post_init__(self):
        # flat (slot, fn, arg_slots) triples: the hot loop avoids dataclass
        # attribute lookups entirely.
        self._fast_steps = tuple((s.slot, s.fn, s.arg_slots) for s in self.steps)
        # the same steps, each inside its span (``repro.host.<op>`` or
        # ``repro.accel.<op>``), run instead while a profiler records: the
        # untraced loop pays one check per execution and nothing per step
        traced_fns = [trace.spanned(step_span(s), s.fn) for s in self.steps]
        self._traced_steps = tuple(
            (s.slot, fn, s.arg_slots) for s, fn in zip(self.steps, traced_fns)
        )
        # stage assignment: split steps into the two lanes, preserving topo
        # order within each, and compute per-step cross-lane watermarks.
        producer: dict[int, tuple[str, int]] = {}  # slot -> (lane, ordinal)
        lanes: dict[str, list] = {"host": [], "accel": []}
        traced_lanes: dict[str, list] = {"host": [], "accel": []}
        for s, traced_fn in zip(self.steps, traced_fns):
            lane = s.lane if s.lane in lanes else "host"
            other = "accel" if lane == "host" else "host"
            need = 0
            for a in s.arg_slots:
                p = producer.get(a)
                if p is not None and p[0] == other:
                    need = max(need, p[1] + 1)
            producer[s.slot] = (lane, len(lanes[lane]))
            lanes[lane].append((s.slot, s.fn, s.arg_slots, need))
            traced_lanes[lane].append((s.slot, traced_fn, s.arg_slots, need))
        self._lane_steps = {k: tuple(v) for k, v in lanes.items()}
        self._traced_lane_steps = {k: tuple(v) for k, v in traced_lanes.items()}

    def new_arena(self) -> list:
        arena: list = [None] * self.n_slots
        for slot, value in self.const_slots:
            arena[slot] = value
        return arena

    def execute(self, feeds: dict[str, np.ndarray], arena: list) -> list[np.ndarray]:
        if trace.enabled():
            with trace.span(EXECUTE_SPAN):
                return self._execute(feeds, arena, self._traced_steps)
        return self._execute(feeds, arena, self._fast_steps)

    def _execute(self, feeds, arena: list, steps: tuple) -> list[np.ndarray]:
        for name, slot in self.input_slots:
            try:
                arena[slot] = np.asarray(feeds[name])
            except KeyError:
                raise KeyError(f"missing feed for input {name!r}") from None
        for slot, fn, arg_slots in steps:
            arena[slot] = fn(*[arena[i] for i in arg_slots])
        return [arena[i] for i in self.output_slots]

    # -- pipelined (two-lane) execution -------------------------------------
    def stage_assignment(self) -> tuple[dict, ...]:
        """The build-time pipeline stage of every step: ``(name, op, lane,
        cross-lane watermark)`` — introspection for tests, docs, and the
        artifact manifest."""
        out = []
        counts = {"host": 0, "accel": 0}
        for s in self.steps:
            lane = s.lane if s.lane in counts else "host"
            other = "accel" if lane == "host" else "host"
            need = self._lane_steps[lane][counts[lane]][3]
            counts[lane] += 1
            out.append(
                {"name": s.name, "op": s.op, "lane": lane, f"waits_{other}": need}
            )
        return tuple(out)

    def lane_sizes(self) -> dict[str, int]:
        return {k: len(v) for k, v in self._lane_steps.items()}

    def recorded_lane_steps(self) -> dict[str, tuple]:
        """The precomputed per-lane ``(slot, fn, arg_slots, watermark)``
        tuples the pipelined executor actually runs — exposed so
        ``repro.core.verify`` can independently re-derive the watermarks
        and check dominance (the static race detector)."""
        return self._lane_steps

    def execute_lane(self, arena: list, state: _CallState, lane: str) -> None:
        """Run one lane of one call.  Steps run in topo order; before each
        step the other lane's watermark must reach the step's recorded
        dependency count.  Raises ``_LaneFailure`` if the run aborts."""
        other = "accel" if lane == "host" else "host"
        run = state.run
        cond, done = run.cond, state.done
        steps = self._traced_lane_steps if trace.enabled() else self._lane_steps
        for slot, fn, arg_slots, need in steps[lane]:
            if need and done[other] < need:
                with cond:
                    while done[other] < need and not run.aborted:
                        cond.wait()
                    if run.aborted:
                        raise _LaneFailure()
            arena[slot] = fn(*[arena[i] for i in arg_slots])
            with cond:
                done[lane] += 1
                cond.notify_all()

    def wait_lane(self, state: _CallState, lane: str) -> None:
        """Block until ``lane`` has completed every step of this call."""
        n = len(self._lane_steps[lane])
        run = state.run
        with run.cond:
            while state.done[lane] < n and not run.aborted:
                run.cond.wait()
            if run.aborted:
                raise _LaneFailure()


def _attn_epilogues(
    graph: Graph, order: list[Node], ops: dict[Node, CompiledOp]
) -> dict[Node, tuple[Callable, Node, tuple[Node, ...]]]:
    """The attention-score epilogues the plan runs on the device.

    A scores GEMM whose executor offers ``fuse_attn_epilogue`` (a Pallas
    step, see ``lowering._attn_scores_step``) followed by the sole-consumer
    chain ``dequantize -> [add(const mask)] -> softmax(last axis) ->
    quantize(int8)``, none of it a graph output, becomes one step.  Maps
    the GEMM to (its fused step fn, the chain's last node, the chain)."""
    consumers: dict[Node, list[Node]] = {}
    for n in order:
        for i in n.inputs:
            if i is not None:
                consumers.setdefault(i, []).append(n)
    outputs = set(graph.outputs)

    def sole(n: Node, op: str) -> Node | None:
        cs = consumers.get(n, ())
        if len(cs) == 1 and n not in outputs and cs[0].op == op:
            return cs[0]
        return None

    residents: dict = {}  # one device copy per mask constant
    fused = {}
    for n in order:
        fuse = getattr(ops[n].executor, "fuse_attn_epilogue", None) if n in ops else None
        deq = sole(n, "dequantize") if fuse is not None else None
        if deq is None:
            continue
        add = sole(deq, "add")
        mask = None
        if add is not None:
            mask = add.inputs[1] if add.inputs[0] is deq else add.inputs[0]
            if not (
                mask.is_const()
                and mask.dtype == add.dtype == "float32"
                and np.broadcast_shapes(mask.shape, n.shape) == tuple(n.shape)
            ):
                continue
        softmax = sole(add or deq, "softmax")
        if softmax is None or softmax.dtype != "float32" or softmax.attrs.get(
            "axis", -1
        ) not in (-1, len(n.shape) - 1):
            continue
        quant = sole(softmax, "quantize")
        if quant is None or quant.dtype != "int8":
            continue
        fn = fuse(
            scale=deq.attrs["scale"],
            probs_scale=quant.attrs["scale"],
            mask=None if mask is None else mask.value,
            mask_first=add is not None and add.inputs[0] is mask,
            host_ops=(
                compile_host_op(deq),
                None if add is None else compile_host_op(add),
                compile_host_op(softmax),
                compile_host_op(quant),
            ),
            residents=residents,
        )
        if fn is not None:
            chain = tuple(c for c in (deq, add, softmax, quant) if c is not None)
            fused[n] = (fn, quant, chain)
    return fused


def _swiglu_epilogues(
    graph: Graph, order: list[Node], ops: dict[Node, CompiledOp]
) -> dict[Node, tuple[Callable, Node]]:
    """The SiLU·mul steps the plan runs on the device.

    A grouped GEMM whose executor offers ``fuse_silu_mul`` (a Pallas step,
    see ``lowering._swiglu_step``) and whose sole consumer is an int8
    ``silu_mul``, neither a graph output, becomes one step.  When the
    ``silu_mul``'s sole consumer is such a grouped step too, reading it as
    its rows, the fused step leaves its result on the device.  Maps the
    GEMM to (its fused step fn, the ``silu_mul``)."""
    consumers: dict[Node, list[Node]] = {}
    for n in order:
        for i in n.inputs:
            if i is not None:
                consumers.setdefault(i, []).append(n)
    outputs = set(graph.outputs)

    def fuse_of(n: Node):
        return getattr(ops[n].executor, "fuse_silu_mul", None) if n in ops else None

    patches: dict = {}  # one per pair of scales
    fused = {}
    for n in order:
        fuse = fuse_of(n)
        users = consumers.get(n, ())
        if fuse is None or n.dtype != "int8" or n in outputs or len(users) != 1:
            continue
        silu = users[0]
        if silu.op != "silu_mul" or silu.dtype != "int8" or silu in outputs:
            continue
        # a consumer appears once per input it reads the silu_mul as
        nxt = consumers.get(silu, ())
        keep = len(nxt) == 1 and fuse_of(nxt[0]) is not None and nxt[0].inputs[0] is silu
        consts = {
            i: inp.value
            for i, inp in enumerate(n.inputs)
            if inp is not None and inp.is_const()
        }
        fn = fuse(
            in_scale=silu.attrs["in_scale"],
            out_scale=silu.attrs["out_scale"],
            consts=consts,
            keep_on_device=keep,
            patches=patches,
        )
        fused[n] = (fn, silu)
    return fused


def build_plan(graph: Graph, ops: dict[Node, CompiledOp]) -> ExecutionPlan:
    """Lower a compiled graph to its execution plan (one toposort, ever).

    An attention-score epilogue after a Pallas scores GEMM
    (``_attn_epilogues``) becomes one ``attn_scores`` step that writes the
    chain's int8 result; the chain's host steps are not planned.  So does a
    SiLU·mul after a Pallas grouped GEMM (``_swiglu_epilogues``): one
    ``swiglu`` step that writes the ``silu_mul``'s slot."""
    order = graph.toposort()
    slot_of: dict[Node, int] = {n: i + 1 for i, n in enumerate(order)}
    input_slots: list[tuple[str, int]] = []
    const_slots: list[tuple[int, np.ndarray]] = []
    steps: list[PlanStep] = []
    fused = _attn_epilogues(graph, order, ops)
    absorbed = {c for _, _, chain in fused.values() for c in chain}
    swiglu = _swiglu_epilogues(graph, order, ops)
    absorbed.update(silu for _, silu in swiglu.values())
    for n in order:
        slot = slot_of[n]
        if n.op == "input":
            input_slots.append((n.name, slot))
        elif n.op == "const":
            const_slots.append((slot, n.value))
        elif n in absorbed:
            continue
        else:
            arg_slots = tuple(
                _NONE_SLOT if i is None else slot_of[i] for i in n.inputs
            )
            if n in fused:
                fn, last, _ = fused[n]
                steps.append(
                    PlanStep(slot_of[last], fn, arg_slots, "attn_scores", n.name, "accel")
                )
                continue
            if n in swiglu:
                fn, silu = swiglu[n]
                steps.append(
                    PlanStep(slot_of[silu], fn, arg_slots, "swiglu", n.name, "accel")
                )
                continue
            if n in ops:
                fn = ops[n].executor
                # accelerator executors may offer plan-time specialization
                # over inputs that are compile-time constants (pre-padded
                # weight panels, pre-widened bias).
                specialize = getattr(fn, "specialize_consts", None)
                if specialize is not None:
                    consts = {
                        i: inp.value
                        for i, inp in enumerate(n.inputs)
                        if inp is not None and inp.is_const()
                    }
                    specialized = specialize(consts) if consts else None
                    if specialized is not None:
                        fn = specialized
            else:
                fn = compile_host_op(n)
            lane = "accel" if n in ops else "host"
            steps.append(PlanStep(slot, fn, arg_slots, n.op, n.name, lane))
    return ExecutionPlan(
        n_slots=len(order) + 1,
        input_slots=tuple(input_slots),
        const_slots=tuple(const_slots),
        steps=tuple(steps),
        output_slots=tuple(slot_of[o] for o in graph.outputs),
    )


@dataclass
class CompiledModule:
    graph: Graph
    desc: AcceleratorDescription
    mode: str
    ops: dict[Node, CompiledOp] = field(default_factory=dict)
    # built once by compile(); None only for hand-assembled modules.
    plan: ExecutionPlan | None = None
    #: PipelineReport from the PassManager run that lowered the graph
    #: (None for hand-assembled modules).
    pass_report: Any = None
    #: the CompilerBackend that produced this module (None for
    #: hand-assembled modules); exposes scheduler/cache introspection.
    backend: Any = field(default=None, repr=False)
    # arena pool: each in-flight call owns one arena, returned when done.
    # Steady-state single-threaded traffic reuses one arena (no per-call
    # allocation); N concurrent callers grow the pool to at most N, so the
    # module is thread- and reentrancy-safe to share across serving threads.
    _arena_pool: list = field(default_factory=list, repr=False)
    _arena_lock: threading.Lock = field(
        default_factory=threading.Lock, repr=False
    )
    _feed_names: frozenset | None = field(default=None, repr=False)

    # -- input signature / feed validation ----------------------------------
    def input_signature(self) -> tuple[tuple[str, tuple[int, ...], str], ...]:
        """(name, shape, dtype) for every graph input, in topological order."""
        return tuple((n.name, n.shape, n.dtype) for n in self.graph.inputs())

    def _check_feeds(self, feeds: dict[str, np.ndarray]) -> None:
        """Validate feeds up front against the input signature: ONE error
        listing every unknown name, missing name, and shape/dtype mismatch,
        instead of a bare KeyError (or silently wrong numerics) halfway
        through execution."""
        if self._feed_names is None:
            self._feed_names = frozenset(n.name for n in self.graph.inputs())
        problems = []
        if feeds.keys() != self._feed_names:
            for name in sorted(self._feed_names - feeds.keys()):
                problems.append(f"missing feed for input {name!r}")
            for name in sorted(feeds.keys() - self._feed_names):
                problems.append(f"unknown feed {name!r}")
        for name, shape, dtype in self.input_signature():
            if name not in feeds:
                continue
            value = np.asarray(feeds[name])
            if value.shape != shape or str(value.dtype) != dtype:
                problems.append(
                    f"feed {name!r} is {value.dtype}{list(value.shape)}, "
                    f"expected {dtype}{list(shape)}"
                )
        if not problems:
            return
        sig = ", ".join(
            f"{name}: {dtype}{list(shape)}"
            for name, shape, dtype in self.input_signature()
        )
        bullet = "\n  - ".join(problems)
        raise FeedError(
            f"feeds do not match the module's inputs:\n  - {bullet}\n"
            f"expected inputs: {sig or '<none>'}"
        )

    # -- execution ---------------------------------------------------------
    def finalize(self) -> "ExecutionPlan":
        """Build (or return) the execution plan.  Double-checked under the
        arena lock: compile() finalizes eagerly, but a hand-assembled
        module shared cold across threads must build exactly one plan."""
        if self.plan is None:
            with self._arena_lock:
                if self.plan is None:
                    self.plan = build_plan(self.graph, self.ops)
        return self.plan

    def _acquire_arena(self, plan: "ExecutionPlan") -> list:
        with self._arena_lock:
            if self._arena_pool:
                return self._arena_pool.pop()
        return plan.new_arena()

    def _release_arena(self, arena: list) -> None:
        with self._arena_lock:
            if len(self._arena_pool) < 16:
                self._arena_pool.append(arena)

    def run(
        self,
        feeds: dict[str, np.ndarray],
        *,
        use_plan: bool = True,
        pipelined: bool = False,
    ) -> list[np.ndarray]:
        """Execute the module.  Thread-safe: every call runs over its own
        buffer arena (pooled, so steady-state traffic allocates nothing).
        ``use_plan=False`` runs the legacy per-node interpreter (kept for
        planned-vs-interpreted equivalence testing and as the baseline of
        ``benchmarks/table2_bench.py``).  ``pipelined=True`` overlaps the
        host-op lane with accelerator-step dispatch on a worker thread —
        bit-exact with the sequential loop (same fns, same operand order)."""
        self._check_feeds(feeds)
        if pipelined:
            if not use_plan:
                raise ValueError("pipelined execution requires use_plan=True")
            return self._run_many_pipelined([feeds], self.finalize())[0]
        if not use_plan:
            return self._run_interpreted(feeds)
        plan = self.finalize()
        arena = self._acquire_arena(plan)
        try:
            return plan.execute(feeds, arena)
        finally:
            self._release_arena(arena)

    def run_many(
        self,
        feeds_list: list[dict[str, np.ndarray]],
        *,
        use_plan: bool = True,
        pipelined: bool = False,
    ) -> list[list[np.ndarray]]:
        """Repeated invocation over a list of feeds (serving-style traffic);
        the plan is built once and one pooled arena is held for the whole
        loop.  Thread-safe: concurrent callers each hold their own arena,
        so compiled modules can be shared across serving threads.

        ``pipelined=True`` runs the host lane on a worker thread and rotates
        two arenas through a free/ready queue pair (double buffering): while
        the caller dispatches call *i*'s accelerator steps, the worker is
        already loading feeds and running host stages of call *i+1*."""
        for feeds in feeds_list:
            self._check_feeds(feeds)
        if pipelined:
            if not use_plan:
                raise ValueError("pipelined execution requires use_plan=True")
            return self._run_many_pipelined(feeds_list, self.finalize())
        if not use_plan:
            return [self._run_interpreted(f) for f in feeds_list]
        plan = self.finalize()
        arena = self._acquire_arena(plan)
        try:
            execute = plan.execute
            return [execute(feeds, arena) for feeds in feeds_list]
        finally:
            self._release_arena(arena)

    def _run_many_pipelined(
        self, feeds_list: list[dict[str, np.ndarray]], plan: "ExecutionPlan"
    ) -> list[list[np.ndarray]]:
        """Two-lane, double-buffered execution.  A worker thread owns the
        host lane; the caller's thread owns the accelerator lane.  Two
        arenas rotate through ``free``/``ready`` queues so consecutive calls
        overlap (depth-2 pipeline); cross-lane dependencies inside one call
        are enforced by the plan's build-time watermarks.  Any exception on
        either side aborts the shared run, unblocks every waiter, and
        re-raises in the caller."""
        if not feeds_list:
            return []
        sizes = plan.lane_sizes()
        if not sizes["accel"] or not sizes["host"]:
            # one lane is empty: nothing to overlap, the sequential loop is
            # strictly better (and spawns no thread).
            arena = self._acquire_arena(plan)
            try:
                return [plan.execute(f, arena) for f in feeds_list]
            finally:
                self._release_arena(arena)
        run = _PipelineRun()
        free: queue.SimpleQueue = queue.SimpleQueue()
        ready: queue.SimpleQueue = queue.SimpleQueue()
        arenas = [self._acquire_arena(plan), self._acquire_arena(plan)]
        for a in arenas:
            free.put(a)
        worker_exc: list[BaseException] = []

        def host_worker() -> None:
            try:
                for feeds in feeds_list:
                    arena = free.get()
                    if arena is _STOP:
                        return
                    for name, slot in plan.input_slots:
                        arena[slot] = np.asarray(feeds[name])
                    state = _CallState(run)
                    # publish before executing: the accel lane starts as
                    # soon as the feeds are in place.
                    ready.put((arena, state))
                    plan.execute_lane(arena, state, "host")
            except _LaneFailure:
                pass  # the caller aborted; it owns the original exception
            except BaseException as e:  # noqa: BLE001 — re-raised in caller
                worker_exc.append(e)
                run.abort()
                ready.put(_STOP)

        t = threading.Thread(
            target=host_worker, name="repro-host-lane", daemon=True
        )
        t.start()
        results: list[list[np.ndarray]] = []
        try:
            try:
                for _ in feeds_list:
                    item = ready.get()
                    if item is _STOP:
                        break  # worker died; its exception re-raised below
                    arena, state = item
                    with trace.span(EXECUTE_SPAN):
                        plan.execute_lane(arena, state, "accel")
                        plan.wait_lane(state, "host")
                    results.append([arena[i] for i in plan.output_slots])
                    free.put(arena)
            except _LaneFailure:
                pass  # abort came from the worker; re-raised below
            except BaseException:
                run.abort()
                raise
            finally:
                free.put(_STOP)  # unblock a worker parked on free.get()
                t.join()
        finally:
            for a in arenas:
                self._release_arena(a)
        if worker_exc:
            raise worker_exc[0]
        return results

    def _run_interpreted(self, feeds: dict[str, np.ndarray]) -> list[np.ndarray]:
        """The pre-plan per-node interpreter: re-toposorts and re-dispatches
        on every call."""
        vals: dict[Node, np.ndarray] = {}
        for n in self.graph.toposort():
            if n.op == "input":
                vals[n] = np.asarray(feeds[n.name])
            else:
                ins = [vals[i] if i is not None else None for i in n.inputs]
                if n in self.ops:
                    vals[n] = self.ops[n].executor(*ins)
                else:
                    vals[n] = execute_node(n, ins)
        return [vals[o] for o in self.graph.outputs]

    # -- cycle model ---------------------------------------------------------
    def modeled_cycles(self) -> dict[str, float]:
        """Total modeled cycles: accelerator ops via the schedule simulator,
        residual host ops (unfolded preprocessing / unfused epilogues in
        naive mode) via per-byte host costs, and collectives (sharded
        plans) via the ring-interconnect model keyed on the arch's link
        parameters (``comm``; zero for unsharded plans)."""
        arch = self.desc.arch
        accel = 0.0
        host = 0.0
        comm = 0.0
        fused = self.mode != "naive"
        for n in self.graph.toposort():
            if n.op in COLLECTIVE_OPS:
                # the FULL payload: the gathered/reduced tensor — the
                # gather output, or the reduce input (== output for
                # all_reduce, parts x output for reduce_scatter)
                ref = n if n.op == "all_gather" else n.inputs[0]
                nbytes = math.prod(ref.shape) * dtype_bytes(ref.dtype)
                if n.op == "all_reduce":
                    nbytes = math.prod(n.shape) * dtype_bytes(n.dtype)
                comm += collective_cycles(n.op, nbytes, n.attrs["parts"], arch)
            elif n in self.ops:
                rep = simulate(
                    self.ops[n].strategy.schedule,
                    arch,
                    folded_preprocessing=True,  # graph structure carries it
                    fused_loop_instructions=fused,
                )
                # batched matmuls replay the scheduled per-sample GEMM once
                # per batch instance; everything else folds batch into M
                # and is already covered by the schedule itself.
                accel += rep.total_cycles * gemm_instances(n)
            elif n.op == "kv_cache_read":
                # streams the whole cache once into the attention GEMMs
                nbytes = math.prod(n.shape) * dtype_bytes(n.dtype)
                host += nbytes * arch.host_preproc_cycles_per_byte
            elif n.op == "kv_cache_append":
                # modeled as an in-place row write: only the update payload
                # moves (the functional numpy copy is an emulation artifact)
                upd = n.inputs[1]
                nbytes = math.prod(upd.shape) * dtype_bytes(upd.dtype)
                host += nbytes * arch.host_epilogue_cycles_per_byte
            elif n.op in _LAYOUT_OPS and n.op not in FREE_VIEW_OPS:
                nbytes = math.prod(n.shape) * dtype_bytes(n.dtype)
                host += nbytes * arch.host_preproc_cycles_per_byte
            elif n.op in _EPILOGUE_OPS:
                in_bytes = (
                    math.prod(n.inputs[0].shape) * dtype_bytes(n.inputs[0].dtype)
                    if n.inputs
                    else 0
                )
                host += in_bytes * arch.host_epilogue_cycles_per_byte
        return {
            "accel": accel,
            "host": host,
            "comm": comm,
            "total": accel + host + comm,
        }

    def schedules(self) -> dict[str, Any]:
        return {
            n.name: op.strategy.schedule.to_dict() for n, op in self.ops.items()
        }
