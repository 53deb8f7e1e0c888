"""The one front door: ``repro.compile(model, target=...)``.

The paper's pitch is that users target accelerators *without navigating
compiler internals*.  This module is that surface: a ``Target`` names the
accelerator and optimization mode (validated up front, every problem
listed), ``CompileOptions`` carries per-compile knobs, and ``compile()``
accepts whatever the user already has —

    import repro

    # an ir.Graph
    module = repro.compile(graph, target=repro.Target("gemmini"))

    # a model-zoo name (one string for CLIs / benchmarks)
    module = repro.compile("toycar_mlp", target="edge_npu:optimized")

    # a plain jax.numpy callable + example inputs (traced frontend)
    module = repro.compile(
        fn,
        target=repro.Target("gemmini", mode="optimized"),
        example_inputs={"x": x},
        params=params,
    )

    outputs = module.run({"x": x})
    cycles = module.modeled_cycles()

The legacy two-step flow (``repro.integrate()`` then ``backend.compile()``)
keeps working but is deprecated; it maps 1:1 onto this surface.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass, replace
from pathlib import Path

import numpy as np

from repro.core.accel import AcceleratorDescription
from repro.core.batching import BatchedModule, io_specs_from_graph
from repro.core.ir import Graph
from repro.core.pass_manager import PassContext
from repro.core.pipeline import PUBLIC_MODES, CompilerBackend, resolve_mode
from repro.core.registry import REGISTRY, build_integrated_backend

#: serving bucket ladder used when only ``Target.batch_size`` is given:
#: the buckets are the ladder entries below it, plus the batch itself.
DEFAULT_BATCH_BUCKETS = (1, 4, 16)


class TargetError(ValueError):
    """A target failed validation; ``.problems`` lists every issue."""

    def __init__(self, spec: str, problems: list[str]):
        self.problems = problems
        bullet = "\n  - ".join(problems)
        super().__init__(f"invalid target {spec!r}:\n  - {bullet}")


class CapabilityError(ValueError):
    """``allow_host_fallback=False`` and the target cannot run every core
    op; ``.problems`` lists each op left on the host."""

    def __init__(self, name: str, problems: list[str]):
        self.problems = problems
        bullet = "\n  - ".join(problems)
        super().__init__(
            f"accelerator {name!r} cannot offload the whole model "
            f"(allow_host_fallback=False):\n  - {bullet}"
        )


@dataclass(frozen=True)
class Target:
    """Where and how to compile: accelerator + mode + scheduler options.

    ``accelerator`` is a registered name or an ``AcceleratorDescription``;
    ``mode`` is one of ``naive`` / ``baseline`` / ``optimized`` (the paper's
    evaluation matrix; the internal mode names are accepted as aliases).
    Construction validates everything it can and raises ``TargetError``
    listing every problem at once.
    """

    accelerator: str | AcceleratorDescription
    mode: str = "optimized"
    use_mip: bool = True
    use_pallas: bool = False
    cache: bool = True
    cache_dir: str | Path | None = None
    parallel_dse: bool = False
    #: serving batch the deployment dispatches at.  ``batch_size > 1``
    #: makes ``compile()`` return a :class:`BatchedModule` bucketed at the
    #: DEFAULT_BATCH_BUCKETS entries up to (and including) this size;
    #: ``CompileOptions.batch_buckets`` overrides the bucket set exactly.
    batch_size: int = 1
    #: mesh size: ``devices > 1`` compiles ONE graph into one ExecutionPlan
    #: per shard of a ``(data, model)`` mesh and ``compile()`` returns a
    #: :class:`~repro.core.sharded.ShardedModule` (or a BatchedModule of
    #: them).  The factorization defaults to the elastic-mesh rule
    #: (``repro.launch.mesh.mesh_factorization``); ``mesh`` pins it.
    devices: int = 1
    #: explicit ``(data, model)`` factorization of ``devices``.  Giving
    #: only ``mesh`` derives ``devices`` from its product.
    mesh: tuple[int, int] | None = None

    def __post_init__(self):
        problems = []
        if not isinstance(self.batch_size, int) or self.batch_size < 1:
            problems.append(
                f"batch_size must be a positive int, got {self.batch_size!r}"
            )
        if not isinstance(self.devices, int) or self.devices < 1:
            problems.append(
                f"devices must be a positive int, got {self.devices!r}"
            )
        elif self.mesh is not None:
            mesh = tuple(self.mesh) if isinstance(self.mesh, list) else self.mesh
            if (
                not isinstance(mesh, tuple)
                or len(mesh) != 2
                or not all(isinstance(a, int) and a >= 1 for a in mesh)
            ):
                problems.append(
                    f"mesh must be a (data, model) pair of positive ints, "
                    f"got {self.mesh!r}"
                )
            else:
                object.__setattr__(self, "mesh", mesh)
                if self.devices == 1:
                    object.__setattr__(self, "devices", mesh[0] * mesh[1])
                elif mesh[0] * mesh[1] != self.devices:
                    problems.append(
                        f"mesh {mesh} factorizes {mesh[0] * mesh[1]} devices "
                        f"but devices={self.devices} was also passed"
                    )
        try:
            resolve_mode(self.mode)
        except ValueError:
            problems.append(
                f"unknown mode {self.mode!r}; expected one of "
                f"{', '.join(PUBLIC_MODES)}"
            )
        if isinstance(self.accelerator, str):
            if self.accelerator not in REGISTRY:
                known = ", ".join(REGISTRY.names()) or "<none>"
                problems.append(
                    f"unknown accelerator {self.accelerator!r}; "
                    f"registered: {known}"
                )
        elif not isinstance(self.accelerator, AcceleratorDescription):
            problems.append(
                f"accelerator must be a registered name or an "
                f"AcceleratorDescription, got {type(self.accelerator).__name__}"
            )
        if self.cache_dir is not None and not self.cache:
            problems.append("cache_dir given but cache=False")
        if problems:
            raise TargetError(self.describe(), problems)

    @classmethod
    def parse(cls, spec: str, **overrides) -> "Target":
        """Parse ``"accelerator[:mode]"`` — the one-string form CLIs and
        benchmarks pass around, e.g. ``Target.parse("gemmini:optimized")``."""
        parts = spec.split(":")
        if len(parts) > 2 or not parts[0]:
            raise TargetError(
                spec, ["expected 'accelerator' or 'accelerator:mode'"]
            )
        if len(parts) == 2:
            if "mode" in overrides and overrides["mode"] != parts[1]:
                raise TargetError(
                    spec,
                    [
                        f"spec names mode {parts[1]!r} but mode="
                        f"{overrides['mode']!r} was also passed"
                    ],
                )
            overrides["mode"] = parts[1]
        return cls(parts[0], **overrides)

    def describe(self) -> str:
        name = (
            self.accelerator
            if isinstance(self.accelerator, str)
            else getattr(self.accelerator, "name", "<description>")
        )
        base = f"{name}:{self.mode}"
        if isinstance(self.devices, int) and self.devices > 1:
            try:
                dp, mp = self.resolved_mesh
                base += f"@{self.devices}dev(data={dp},model={mp})"
            except Exception:  # an invalid mesh mid-TargetError formatting
                base += f"@{self.devices}dev"
        return base

    @property
    def resolved_mesh(self) -> tuple[int, int]:
        """The ``(data, model)`` mesh this target compiles for: the
        explicit ``mesh`` if given, else the elastic factorization of
        ``devices`` (largest power-of-two model axis, rest data)."""
        if self.mesh is not None:
            return self.mesh
        if self.devices == 1:
            return (1, 1)
        from repro.launch.mesh import mesh_factorization

        return mesh_factorization(self.devices)

    @property
    def internal_mode(self) -> str:
        return resolve_mode(self.mode)

    def with_mode(self, mode: str) -> "Target":
        return replace(self, mode=mode)


@dataclass(frozen=True)
class CompileOptions:
    """Per-compile knobs orthogonal to the target."""

    #: explicit pass list overriding the per-mode pipeline (experiments)
    passes: list | None = None
    #: trace/dump instrumentation context for the pass manager
    pass_context: PassContext | None = None
    #: False -> raise CapabilityError if any dense/conv stays on the host
    allow_host_fallback: bool = True
    #: True -> build a fresh backend instead of reusing the per-target one
    #: (benchmarking cold integration, isolating solver-call counters)
    fresh_backend: bool = False
    #: serving batch buckets: compile one ExecutionPlan per bucket and
    #: return a BatchedModule whose run_many packs/pads per-sample feeds
    #: into the smallest fitting bucket.  Only zoo names and traced
    #: callables can be rebuilt per bucket (a prebuilt ir.Graph is
    #: fixed-shape).  None (default) -> the classic single-shape module
    #: unless ``Target.batch_size > 1`` supplies the default ladder.
    batch_buckets: tuple[int, ...] | None = None
    #: measured DSE: time the K best modeled schedule candidates per node
    #: on the lowered executor (Pallas interpret / emulated tiled loop —
    #: whatever the target actually runs) and pick the wall-clock winner.
    #: Measurements persist in the schedule cache under a ``measured{K}``
    #: key, so warm recompiles do zero sweeps AND zero re-measurement.
    #: None (default) keeps the pure cycle-model argmin.
    measure_top_k: int | None = None
    #: transparent AOT write-through: probe a content-addressed
    #: ``ArtifactStore`` rooted here before compiling (keyed by source
    #: graph fingerprint, arch fingerprint, mode, pallas, bucket, schema
    #: version) and persist the compiled module after.  A hit restores the
    #: full module — plan, schedules, pass report, constants — with zero
    #: DSE sweeps, zero measurements, and zero rewrite fires.  Ignored when
    #: ``passes`` overrides the per-mode pipeline (custom pipelines are not
    #: part of the key).  See also ``repro.save`` / ``repro.load``.
    artifact_dir: str | Path | None = None
    #: static-verification gate (``repro.core.verify``): ``'each'`` runs
    #: the graph verifier before the first and after every compiler pass
    #: and the plan analysis on the finalized ExecutionPlan; ``'final'``
    #: verifies once after the pipeline; ``'off'`` disables the gate.
    #: None (default) defers to the ``REPRO_VERIFY`` env var.  Sharded
    #: compiles additionally check cross-shard collective-sequence
    #: consistency (the static deadlock detector).
    verify: str | None = None

    def __post_init__(self):
        k = self.measure_top_k
        if k is not None and (not isinstance(k, int) or k < 1):
            raise ValueError(
                f"measure_top_k must be a positive int or None, got {k!r}"
            )
        if self.verify not in (None, "each", "final", "off"):
            raise ValueError(
                f"verify must be 'each', 'final', 'off', or None, got "
                f"{self.verify!r}"
            )


# one backend per (accelerator fingerprint, backend options): repeated
# compiles share the scheduler's in-memory memo on top of the persistent
# schedule cache, so sweeping modes/models never repeats a DSE sweep.
# Bounded locked LRU (move-to-end on hit, evict the least recently used)
# so long-lived serving processes sweeping many descriptions or throwaway
# cache dirs cannot grow memory monotonically, and hot targets are never
# the ones evicted.  Concurrent compile() callers are safe: lookups,
# insertion, and eviction all happen under the lock, and two threads
# racing to build the same backend converge on whichever one published
# first (so they share its scheduler memo).
_BACKENDS: OrderedDict[tuple, CompilerBackend] = OrderedDict()
_BACKENDS_MAX = 16
_BACKENDS_LOCK = threading.Lock()


def clear_backend_cache() -> None:
    """Drop every memoized backend (fresh schedulers on the next compile)."""
    with _BACKENDS_LOCK:
        _BACKENDS.clear()


def backend_for(target: Target, *, fresh: bool = False) -> CompilerBackend:
    """Resolve (and memoize) the generated backend for a target.  The mode
    is a compile-time property, so all modes of one accelerator share a
    backend.  Raises ``IntegrationError`` for an invalid description."""
    desc = (
        REGISTRY.get(target.accelerator)
        if isinstance(target.accelerator, str)
        else target.accelerator
    )
    key = (
        desc.fingerprint(),
        target.use_mip,
        target.use_pallas,
        target.cache,
        str(target.cache_dir),
        target.parallel_dse,
    )
    if not fresh:
        with _BACKENDS_LOCK:
            cached = _BACKENDS.get(key)
            if cached is not None:
                _BACKENDS.move_to_end(key)
                return cached
    backend = build_integrated_backend(
        desc,
        use_mip=target.use_mip,
        use_pallas=target.use_pallas,
        cache=target.cache,
        cache_dir=target.cache_dir,
        parallel_dse=target.parallel_dse,
    )
    if not fresh:
        with _BACKENDS_LOCK:
            winner = _BACKENDS.get(key)
            if winner is not None:
                # lost a build race: share the published backend (and its
                # scheduler memo) instead of forking the cache
                _BACKENDS.move_to_end(key)
                return winner
            while len(_BACKENDS) >= _BACKENDS_MAX:
                _BACKENDS.popitem(last=False)
            _BACKENDS[key] = backend
    return backend


def _check_zoo_args(example_inputs, params) -> None:
    if example_inputs is not None or params is not None:
        raise ValueError(
            "zoo models carry their own inputs and parameters; "
            "drop example_inputs/params"
        )


def _check_callable_args(model, example_inputs) -> None:
    if not callable(model):
        raise TypeError(
            f"model must be an ir.Graph, a zoo model name, or a jax.numpy "
            f"callable; got {type(model).__name__}"
        )
    if not isinstance(example_inputs, dict) or not example_inputs:
        raise ValueError(
            "compiling a traced callable needs example_inputs: a dict "
            "mapping input names to example arrays, e.g. "
            "repro.compile(fn, target, example_inputs={'x': x})"
        )


def _graph_for(model, example_inputs, params) -> Graph:
    if isinstance(model, Graph):
        if example_inputs is not None or params is not None:
            raise ValueError(
                "example_inputs/params only apply to traced callables, "
                "not prebuilt ir.Graph models"
            )
        return model
    if isinstance(model, str):
        from repro.core.zoo import DECODE_ZOO, get_decode_model, get_model

        _check_zoo_args(example_inputs, params)
        if model in DECODE_ZOO:
            # the decode-step form; prefill compiles via
            # get_decode_model(name).trace(seq=P) passed as a Graph
            return get_decode_model(model).trace()
        return get_model(model).trace()
    _check_callable_args(model, example_inputs)
    from repro.frontend import trace_model

    return trace_model(model, example_inputs, params)


def _resolve_buckets(target: Target, options: CompileOptions) -> tuple[int, ...] | None:
    """The bucket set to compile, or None for the classic unbatched path."""
    buckets = options.batch_buckets
    if buckets is None:
        if target.batch_size <= 1:
            return None
        buckets = tuple(
            b for b in DEFAULT_BATCH_BUCKETS if b < target.batch_size
        ) + (target.batch_size,)
    buckets = tuple(buckets)
    problems = [
        f"bucket {b!r} must be a positive int"
        for b in buckets
        if not isinstance(b, int) or b < 1
    ]
    if not buckets:
        problems.append("batch_buckets must name at least one bucket")
    if problems:
        raise ValueError(
            "invalid batch buckets:\n  - " + "\n  - ".join(problems)
        )
    return tuple(sorted(set(buckets)))


def _batched_graph_builder(model, example_inputs, params):
    """A ``build(batch) -> Graph`` callback for models that can be rebuilt
    per bucket: zoo names re-trace their batched form, callables re-trace
    with batch-widened example inputs.  Prebuilt graphs are fixed-shape."""
    if isinstance(model, str):
        from repro.core.zoo import DECODE_ZOO, get_model

        if model in DECODE_ZOO:
            raise ValueError(
                "stateful decode models do not use batch buckets: the "
                "decode batch is the engine's static slot count — compile "
                "get_decode_model(name).trace(batch=B) directly, or serve "
                "via repro.serve.ContinuousBatchingEngine"
            )
        _check_zoo_args(example_inputs, params)
        zoo_model = get_model(model)
        # the hand-built twin is the cheap per-sample reference: it is
        # pinned bit-exact to trace() with identical input/output shapes
        # and names by tests/test_frontend.py, and only the IO specs are
        # read from it
        return zoo_model.build(), lambda b: zoo_model.trace(batch=b)
    if isinstance(model, Graph):
        raise ValueError(
            "batch buckets need a model that can be rebuilt per bucket "
            "(a zoo name or a traced callable); a prebuilt ir.Graph is "
            "fixed-shape — trace the model instead, or compile the graph "
            "without batch_buckets"
        )
    _check_callable_args(model, example_inputs)
    from repro.core.batching import batched_shape
    from repro.frontend import trace_model

    def widen(arr: np.ndarray, b: int) -> np.ndarray:
        return np.zeros(batched_shape(arr.shape, b), dtype=arr.dtype)

    sample = {k: np.asarray(v) for k, v in example_inputs.items()}
    reference = trace_model(model, sample, params)

    def build(b: int) -> Graph:
        return trace_model(
            model, {k: widen(v, b) for k, v in sample.items()}, params
        )

    return reference, build


def _check_offload(module) -> None:
    desc = module.desc
    left_on_host = [
        f"{n.name}: {n.op} {list(n.shape)} ({n.dtype})"
        for n in module.graph.toposort()
        if n.target != "accel"
        and n.op.replace("generalized_", "")
        in ("dense", "conv2d", "matmul", "grouped_dense")
    ]
    if left_on_host:
        left_on_host.append(
            f"(supported core ops: {', '.join(sorted(desc.supported_ops()))})"
        )
        raise CapabilityError(desc.name, left_on_host)


def compile(
    model,
    target: Target | str,
    *,
    example_inputs: dict | None = None,
    params=None,
    options: CompileOptions | None = None,
):
    """Compile a model for a target — the one entry point.

    Args:
      model: an ``ir.Graph``, a zoo model name (``repro.core.zoo``), or a
        plain ``jax.numpy`` callable (traced via ``repro.frontend``).
      target: a ``Target`` or an ``"accelerator[:mode]"`` string.
      example_inputs: for callables — dict of input name -> example array
        (shape/dtype only; values are not used).
      params: for callables — optional pytree of weight arrays, imported as
        graph constants (keeps weight preprocessing foldable).
      options: ``CompileOptions``.

    Returns a ``CompiledModule``: ``run(feeds)`` / ``run_many(feeds_list)``
    execute it, ``modeled_cycles()`` reads the cycle model.

    With ``Target(batch_size=...)`` > 1 or ``CompileOptions(batch_buckets=
    ...)``, returns a ``BatchedModule`` instead: one ExecutionPlan per batch
    bucket, ``run_many`` packing per-sample feeds into padded bucketed
    executions (see ``repro.core.batching``).
    """
    if isinstance(target, str):
        target = Target.parse(target)
    options = options or CompileOptions()
    # validate the model argument (and trace/resolve its graphs) BEFORE
    # touching the backend: a bad model must never trigger accelerator
    # integration or cache-dir side effects
    buckets = _resolve_buckets(target, options)
    if buckets is None:
        graph = _graph_for(model, example_inputs, params)
    else:
        reference, build = _batched_graph_builder(model, example_inputs, params)
    dp, mp = target.resolved_mesh
    if target.devices > 1 and options.passes is not None:
        raise ValueError(
            "devices > 1 inserts the shard-partitioning pass into the "
            "per-mode pipeline; a custom CompileOptions.passes list cannot "
            "be sharded"
        )
    backend = backend_for(target, fresh=options.fresh_backend)
    store = None
    if (
        options.artifact_dir is not None
        and options.passes is None
        and target.devices == 1  # the store key carries no mesh coordinate
    ):
        from repro.core.artifact import ArtifactStore

        store = ArtifactStore(Path(options.artifact_dir))

    def compile_graph(graph, bucket=None):
        key = src_fp = None
        if store is not None:
            # key by the PRE-pipeline graph (what the caller hands us);
            # the passes mutate it in place during compile
            from repro.core.artifact import graph_fingerprint

            src_fp = graph_fingerprint(graph)
            key = store.key_for(
                source_fingerprint=src_fp,
                arch_fingerprint=backend.desc.fingerprint(),
                mode=target.internal_mode,
                use_pallas=target.use_pallas,
                bucket=bucket,
                measure_top_k=options.measure_top_k,
            )
            cached = store.get(key, desc=backend.desc)
            if cached is not None:
                if not options.allow_host_fallback:
                    _check_offload(cached)
                return cached
        module = backend.compile_graph(
            graph,
            mode=target.internal_mode,
            passes=options.passes,
            pass_context=options.pass_context,
            measure_top_k=options.measure_top_k,
            verify=options.verify,
        )
        if not options.allow_host_fallback:
            _check_offload(module)
        if store is not None:
            store.put(key, module, source_fingerprint=src_fp)
        return module

    def compile_sharded(base_graph, dp_eff, signature):
        """Compile one graph into its per-shard ExecutionPlan set: every
        mesh coordinate gets its own CLONE of the source graph (the pass
        pipeline mutates in place, and each shard's shard pass rewrites
        different slices) compiled with that coordinate's ShardSpec."""
        from repro.core.collective import ShardSpec
        from repro.core.ir import clone_graph
        from repro.core.sharded import ShardedModule

        shards = {}
        for d in range(dp_eff):
            for m in range(mp):
                module = backend.compile_graph(
                    clone_graph(base_graph),
                    mode=target.internal_mode,
                    pass_context=options.pass_context,
                    measure_top_k=options.measure_top_k,
                    shard=ShardSpec(
                        data=dp_eff, model=mp, data_rank=d, model_rank=m
                    ),
                    verify=options.verify,
                )
                if not options.allow_host_fallback:
                    _check_offload(module)
                shards[(d, m)] = module
        from repro.core.verify import resolve_verify

        if resolve_verify(options.verify) != "off":
            # the per-shard gate proved each plan sound in isolation; the
            # cross-shard property — a consistent collective sequence on
            # every shard — is what rules out a rendezvous deadlock
            from repro.core.verify import VerifyError, verify_collectives

            diags = verify_collectives(shards)
            if diags:
                raise VerifyError(
                    f"sharded compile of {base_graph.name!r} "
                    f"(mesh data={dp_eff}, model={mp})",
                    diags,
                )
        return ShardedModule(
            shards=shards, mesh=(dp_eff, mp), signature=signature
        )

    if buckets is None:
        if target.devices == 1:
            return compile_graph(graph)
        if dp > 1:
            raise ValueError(
                f"target mesh (data={dp}, model={mp}) is data-parallel, "
                f"which splits along the batch dim and therefore needs "
                f"batch buckets (Target(batch_size=...) or CompileOptions("
                f"batch_buckets=...)); use mesh=(1, {target.devices}) for "
                f"pure tensor parallelism on an unbatched compile"
            )
        signature = tuple(
            (n.name, tuple(n.shape), n.dtype) for n in graph.inputs()
        )
        return compile_sharded(graph, 1, signature)

    inputs, outputs = io_specs_from_graph(reference)
    if target.devices == 1:
        # the per-sample reference compiles into the UNPADDED single-request
        # plan: run_many routes size-1 chunks through it instead of
        # pack/pad-to-bucket/unpack (the batched-serving latency fix)
        sample_module = compile_graph(reference)
        return BatchedModule(
            modules={b: compile_graph(build(b), bucket=b) for b in buckets},
            inputs=inputs,
            outputs=outputs,
            sample_module=sample_module,
        )
    modules = {}
    for b in buckets:
        # a bucket only splits data-parallel when the mesh divides it
        # evenly; otherwise that bucket runs tensor-parallel-only
        dp_eff = dp if dp > 1 and b % dp == 0 else 1
        signature = tuple(
            (s.name, s.batched_shape(b), s.dtype) for s in inputs
        )
        modules[b] = compile_sharded(build(b // dp_eff), dp_eff, signature)
    return BatchedModule(modules=modules, inputs=inputs, outputs=outputs)


def save(module, path):
    """Serialize a compiled module (or bucketed ``BatchedModule``) into an
    AOT artifact directory at ``path``.

    The artifact holds everything ``compile()`` produced — the optimized
    graph, per-node schedules (measured-DSE winners included), the
    pass-pipeline report, constant panels/weights, kernel configs, and the
    ExecutionPlan skeleton — versioned and content-verified, written
    atomically.  ``repro.load(path)`` restores it with zero DSE sweeps,
    zero measurements, and zero rewrite-rule fires.  See
    ``repro.core.artifact`` for the layout."""
    from repro.core.artifact import save_any

    return save_any(module, path)


def load(path):
    """Restore a compiled module from an AOT artifact written by
    ``repro.save`` (or by ``CompileOptions(artifact_dir=...)``
    write-through).

    Raises ``ArtifactError`` naming the mismatch if the artifact is torn
    or was built for a different schema version, architecture, or graph.
    The accelerator the artifact targets must be registered in this
    process (built-ins always are)."""
    from repro.core.artifact import load_any

    return load_any(path)
