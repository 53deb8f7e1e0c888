"""Traced-JAX frontend: import a plain ``jax.numpy`` callable into core IR.

``trace_model(fn, example_inputs, params)`` runs ``jax.make_jaxpr`` and walks
the jaxpr, translating each equation into ``repro.core.ir`` nodes.  Two kinds
of translation cooperate:

* **direct primitives** map 1:1 onto IR ops — ``dot_general`` -> ``dense``,
  ``ragged_dot_general`` -> ``grouped_dense``, ``conv_general_dilated`` ->
  ``conv2d``, ``transpose``/``reshape``, ``reduce_window_max`` ->
  ``max_pool2d``, elementwise ``add``/``sub``/``mul``, and the named
  ``jit`` calls of ``frontend.nn`` (the KV-cache ops and the routed-expert
  block's host ops, their float scalars read from the call's literals);

* **idiom patterns** recognize the multi-equation chains plain jnp produces
  for ops the IR models as one node: ``jnp.clip(jnp.round(x / s), -128, 127)
  .astype(int8)`` -> ``quantize``, the ``x * s`` saturating-round chain ->
  ``requantize``, ``x.astype(f32) * s`` -> ``dequantize``, ``jax.nn.relu`` /
  ``jnp.maximum(x, 0)`` -> ``relu``, the tanh-approximation chain of
  ``jax.nn.gelu`` -> ``gelu``, the exp/reduce/div chain of
  ``jax.nn.softmax`` -> ``softmax``, bias broadcasting -> ``bias_add``.

Low-level primitives (``div``, ``round``, ``exp``, reductions, ...) are held
as *pending* symbolic records rather than IR nodes; they are only legal as
interior steps of a recognized idiom.  Anything that cannot be translated is
collected and reported in ONE ``UnsupportedJaxprError`` listing every
problem, in the same all-problems-listed style as ``IntegrationError``.

The importer is target-independent: capability negotiation against the
``AcceleratorDescription`` (which ops offload, which fall back to the host)
happens in the partitioning pass, exactly as for hand-built graphs.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from repro.core import ir

try:  # Literal's import path moves across jax versions
    from jax.core import Literal
except ImportError:  # pragma: no cover
    from jax.extend.core import Literal


#: jax primitive -> IR construct it lowers to (drives the docs table and the
#: "supported ops" introspection; idiom chains are keyed by their sink).
SUPPORTED_PRIMITIVES: dict[str, str] = {
    "dot_general": "dense (leading dims fold into M; 1 batch dim -> batched matmul)",
    "conv_general_dilated": "conv2d",
    "transpose": "transpose",
    "reshape": "reshape / flatten",
    "squeeze": "reshape (unit dims drop as a free view)",
    "reduce_window_max": "max_pool2d",
    "add": "add / bias_add (broadcast bias idiom)",
    "sub": "sub",
    "mul": "mul / dequantize (astype-float * scale idiom)",
    "max": "relu (maximum(x, 0) idiom)",
    "custom_jvp_call": "(inlined: jax.nn.relu, ...)",
    "jit": (
        "(named: relu / clip / round / kv_cache_read / kv_cache_append / rms_norm / "
        "rope / silu_mul / moe_route / moe_group_sizes / moe_dispatch / moe_combine; "
        "others inlined)"
    ),
    "ragged_dot_general": "grouped_dense (jax.lax.ragged_dot: rows sorted by group)",
    "convert_element_type": "quantize / requantize chain sinks",
    "div": "quantize interior (round(x / scale) idiom)",
    "round": "quantize / requantize interior",
    "broadcast_in_dim": "bias_add / softmax interior",
    "reduce_max": "softmax interior",
    "reduce_sum": "softmax interior",
    "exp": "softmax interior",
    "stop_gradient": "softmax interior",
    "tanh": "gelu interior",
    "integer_pow": "gelu interior",
    "min": "clip interior",
}


class UnsupportedJaxprError(ValueError):
    """The traced function uses constructs the frontend cannot import;
    ``.problems`` lists every one of them."""

    def __init__(self, name: str, problems: list[str]):
        self.problems = problems
        bullet = "\n  - ".join(problems)
        super().__init__(
            f"cannot import traced function {name!r} into core IR:\n  - {bullet}\n"
            f"(supported jaxpr primitives: {', '.join(sorted(SUPPORTED_PRIMITIVES))})"
        )


@dataclass
class _Lit:
    """A scalar literal appearing inline in an equation."""

    val: Any
    dtype: str


@dataclass
class _Pending:
    """A low-level primitive held symbolically until an idiom consumes it."""

    prim: str
    args: list  # ir.Node | _Pending | _Lit
    params: dict
    shape: tuple
    dtype: str


def _is_lit(x) -> bool:
    return isinstance(x, _Lit)


def _scalar(x: _Lit) -> float:
    return float(np.asarray(x.val))


def _is_pend(x, prim: str | None = None) -> bool:
    return isinstance(x, _Pending) and (prim is None or x.prim == prim)


def _close(a: float, b: float, tol: float = 1e-3) -> bool:
    return math.isfinite(a) and abs(a - b) <= tol * max(1.0, abs(b))


@dataclass
class _Importer:
    name: str
    env: dict = field(default_factory=dict)
    problems: list[str] = field(default_factory=list)

    # -- plumbing -----------------------------------------------------------
    def fail(self, msg: str, shape, dtype) -> ir.Node:
        """Record a problem and return a placeholder so the walk continues
        and every remaining problem is still collected."""
        if msg not in self.problems:
            self.problems.append(msg)
        return ir.Node("unsupported", [], shape=tuple(shape), dtype=str(dtype))

    def read(self, atom):
        if isinstance(atom, Literal):
            return _Lit(np.asarray(atom.val), str(atom.aval.dtype))
        return self.env[atom]

    def realize(self, x) -> ir.Node:
        """Force a value into an IR node (raising idioms where possible)."""
        if isinstance(x, ir.Node):
            return x
        if _is_lit(x):
            return ir.const(np.asarray(x.val, dtype=x.dtype))
        assert isinstance(x, _Pending)
        if x.prim == "convert":
            src = self.realize(x.args[0])
            if x.dtype == src.dtype:
                return src
            if x.dtype == "float32" and src.dtype.startswith(("int", "uint")):
                # plain astype(float32): dequantize with unit scale is the
                # bit-exact IR spelling (astype then * 1.0)
                return ir.dequantize(src, scale=1.0)
            return self.fail(
                f"convert_element_type {src.dtype} -> {x.dtype} outside a "
                f"quantize/requantize chain",
                x.shape,
                x.dtype,
            )
        if x.prim == "broadcast":
            return self._realize_broadcast(x)
        if x.prim == "max":
            a, b = x.args
            lit, other = (a, b) if _is_lit(a) else (b, a) if _is_lit(b) else (None, None)
            if lit is not None and _scalar(lit) == 0.0:
                return ir.relu(self.realize(other))
        return self.fail(
            f"primitive {x.prim!r} is only supported inside a recognized "
            f"idiom (quantize / requantize / gelu / softmax / clip)",
            x.shape,
            x.dtype,
        )

    def _realize_broadcast(self, p: _Pending) -> ir.Node:
        """numpy-style (trailing-aligned) broadcasts are free: elementwise IR
        ops broadcast their operands exactly like numpy at execution time."""
        src = self.realize(p.args[0])
        dims = tuple(p.params["broadcast_dimensions"])
        out_rank = len(p.shape)
        if dims == tuple(range(out_rank - len(src.shape), out_rank)):
            return src
        return self.fail(
            f"broadcast_in_dim with non-trailing dimensions {dims} "
            f"({src.shape} -> {p.shape})",
            p.shape,
            p.dtype,
        )

    # -- idiom matchers -----------------------------------------------------
    def _match_quant_chain(self, pend, out_dtype: str, shape) -> ir.Node | None:
        """convert_element_type(int) over clip(round(...)): quantize (round of
        a division) or requantize (saturating round of a scaled value)."""
        if not _is_pend(pend, "clip"):
            return None
        inner, lo, hi = pend.args
        if not (_is_lit(lo) and _is_lit(hi) and _is_pend(inner, "round")):
            return None
        lo, hi = _scalar(lo), _scalar(hi)
        core = inner.args[0]
        if _is_pend(core, "div") and _is_lit(core.args[1]):
            if (lo, hi) != (-128.0, 127.0):
                return None
            x = self.realize(core.args[0])
            return ir.quantize(x, scale=_scalar(core.args[1]), dtype=out_dtype)
        # requantize: round((x -> float) * scale) saturating to the out range
        scale, base = self._match_scaled(core)
        if base is None:
            return None
        info = np.iinfo(out_dtype)
        if (lo, hi) != (float(info.min), float(info.max)):
            return None
        return ir.requantize(base, scale=scale, out_dtype=out_dtype)

    def _match_scaled(self, x):
        """x * scale where x entered float via astype: the shared interior of
        requantize.  The eager ``mul`` handler may already have emitted the
        astype-mul pair as a ``dequantize`` node — unwrap that too."""
        if isinstance(x, ir.Node) and x.op == "dequantize":
            return x.attrs["scale"], x.inputs[0]
        if _is_pend(x, "mul"):
            a, b = x.args
            lit, other = (a, b) if _is_lit(a) else (b, a) if _is_lit(b) else (None, None)
            if lit is None:
                return None, None
            if _is_pend(other, "convert"):
                other = other.args[0]
            if isinstance(other, ir.Node):
                return _scalar(lit), other
        return None, None

    def _match_dequantize(self, a, b) -> ir.Node | None:
        """mul(astype(x, float32), scale_literal) -> dequantize."""
        lit, other = (a, b) if _is_lit(a) else (b, a) if _is_lit(b) else (None, None)
        if lit is None or np.asarray(lit.val).ndim != 0:
            return None
        if not (_is_pend(other, "convert") and other.dtype == "float32"):
            return None
        src = other.args[0]
        if not (isinstance(src, ir.Node) and src.dtype.startswith(("int", "uint"))):
            return None
        return ir.dequantize(src, scale=_scalar(lit))

    def _match_gelu(self, a, b) -> ir.Node | None:
        """x * (0.5 * (1 + tanh(sqrt(2/pi) * (x + 0.044715 x^3)))) — the
        chain ``jax.nn.gelu(approximate=True)`` traces to."""

        def unwrap_scaled(p, expect, prim):
            # Pending(prim, [lit≈expect, inner]) in either operand order
            if not _is_pend(p, prim):
                return None
            u, v = p.args
            lit, inner = (u, v) if _is_lit(u) else (v, u) if _is_lit(v) else (None, None)
            if lit is None or not _close(_scalar(lit), expect):
                return None
            return inner

        for x, h in ((a, b), (b, a)):
            one_plus = unwrap_scaled(h, 0.5, "mul")
            tanh_p = unwrap_scaled(one_plus, 1.0, "add") if one_plus is not None else None
            if not _is_pend(tanh_p, "tanh"):
                continue
            poly = unwrap_scaled(tanh_p.args[0], math.sqrt(2.0 / math.pi), "mul")
            if not _is_pend(poly, "add"):
                continue
            u, v = poly.args
            base, cubic = (u, v) if u is x else (v, u) if v is x else (None, None)
            cube = unwrap_scaled(cubic, 0.044715, "mul") if cubic is not None else None
            if base is None or not _is_pend(cube, "integer_pow"):
                continue
            if cube.params.get("y") != 3 or cube.args[0] is not x:
                continue
            return ir.gelu(self.realize(x))
        return None

    def _match_softmax(self, num, den) -> ir.Node | None:
        """div(exp(x - max(x)), sum(exp(...))) — ``jax.nn.softmax``."""
        if not _is_pend(num, "exp"):
            return None
        d = den
        if _is_pend(d, "broadcast"):
            d = d.args[0]
        if not (_is_pend(d, "reduce_sum") and d.args[0] is num):
            return None
        axes = tuple(d.params.get("axes", ()))
        sub = num.args[0]
        if not _is_pend(sub, "sub"):
            return None
        x, shift = sub.args
        # unwrap stop_gradient(broadcast(max(-inf, reduce_max(x))))
        if _is_pend(shift, "stop_gradient"):
            shift = shift.args[0]
        if _is_pend(shift, "broadcast"):
            shift = shift.args[0]
        if _is_pend(shift, "max") and any(
            _is_lit(arg) and _scalar(arg) == -math.inf for arg in shift.args
        ):
            shift = next(arg for arg in shift.args if not _is_lit(arg))
        if not (_is_pend(shift, "reduce_max") and shift.args[0] is x):
            return None
        if tuple(shift.params.get("axes", ())) != axes or len(axes) != 1:
            return None
        node = self.realize(x)
        axis = axes[0] - len(node.shape) if axes[0] == len(node.shape) - 1 else axes[0]
        return ir.softmax(node, axis=axis)

    def _match_bias_add(self, a, b) -> ir.Node | None:
        """add(x, broadcast(b)) with a 1-D bias over the channel dim."""
        for x, p in ((a, b), (b, a)):
            if not (isinstance(x, ir.Node) and _is_pend(p, "broadcast")):
                continue
            bias = p.args[0]
            if not (isinstance(bias, ir.Node) and len(bias.shape) == 1):
                continue
            dims = tuple(p.params["broadcast_dimensions"])
            if dims != (len(p.shape) - 1,) or x.shape[-1] != bias.shape[0]:
                continue
            return ir.bias_add(x, bias)
        return None

    # -- per-equation translation -------------------------------------------
    def process(self, eqns) -> None:
        for eqn in eqns:
            try:
                results = self.eqn(eqn)
            except Exception as e:  # collect, placeholder, keep walking
                results = [
                    self.fail(
                        f"{eqn.primitive.name}: {e}",
                        v.aval.shape,
                        v.aval.dtype,
                    )
                    for v in eqn.outvars
                ]
            for var, val in zip(eqn.outvars, results):
                self.env[var] = val

    def eqn(self, eqn) -> list:
        prim = eqn.primitive.name
        args = [self.read(a) for a in eqn.invars]
        aval = eqn.outvars[0].aval
        shape, dtype = tuple(aval.shape), str(aval.dtype)
        pend = lambda p=prim: _Pending(p, args, dict(eqn.params), shape, dtype)

        if prim == "jit":
            return self.named_call(eqn, args)
        if prim == "custom_jvp_call":
            return self.inline(eqn.params["call_jaxpr"], args)
        if prim == "dot_general":
            return [self.dot_general(eqn, args)]
        if prim == "ragged_dot_general":
            return [self.ragged_dot(eqn, args)]
        if prim == "conv_general_dilated":
            return [self.conv(eqn, args)]
        if prim == "transpose":
            return [
                ir.transpose(self.realize(args[0]), tuple(eqn.params["permutation"]))
            ]
        if prim == "reshape":
            if eqn.params.get("dimensions") is not None:
                raise ValueError("reshape with explicit dimension order")
            return [ir.reshape(self.realize(args[0]), tuple(eqn.params["new_sizes"]))]
        if prim == "squeeze":
            # dropping unit dims is a zero-copy view: the IR spelling is a
            # free reshape to the squeezed shape
            return [ir.reshape(self.realize(args[0]), shape)]
        if prim == "reduce_window_max":
            return [self.max_pool(eqn, args)]
        if prim == "add":
            node = self._match_bias_add(*args)
            if node is not None:
                return [node]
            return [self.elementwise(ir.add, args) or pend()]
        if prim == "sub":
            return [self.elementwise(ir.sub, args) or pend()]
        if prim == "mul":
            node = self._match_gelu(*args) or self._match_dequantize(*args)
            if node is not None:
                return [node]
            return [self.elementwise(ir.mul, args) or pend()]
        if prim == "div":
            node = self._match_softmax(*args)
            if node is not None:
                return [node]
            return [pend()]
        if prim == "convert_element_type":
            if dtype.startswith(("int", "uint")):
                node = self._match_quant_chain(args[0], dtype, shape)
                if node is not None:
                    return [node]
            return [_Pending("convert", args, {}, shape, dtype)]
        if prim == "broadcast_in_dim":
            return [_Pending("broadcast", args, dict(eqn.params), shape, dtype)]
        if prim in (
            "max",
            "min",
            "round",
            "exp",
            "tanh",
            "integer_pow",
            "reduce_max",
            "reduce_sum",
            "stop_gradient",
        ):
            return [pend()]
        raise ValueError("unsupported primitive")

    def named_call(self, eqn, args) -> list:
        """jit: recognize the named jax.nn / jnp wrappers, inline the rest."""
        closed = eqn.params["jaxpr"]
        name = eqn.params.get("name", "")
        aval = eqn.outvars[0].aval
        shape, dtype = tuple(aval.shape), str(aval.dtype)
        if name == "relu":
            return [ir.relu(self.realize(args[0]))]
        if name == "kv_cache_read" and len(args) == 1:
            return [ir.kv_cache_read(self.realize(args[0]))]
        if name == "kv_cache_append" and len(args) == 3:
            cache, update, pos = (self.realize(a) for a in args)
            return [ir.kv_cache_append(cache, update, pos)]
        node = self.named_moe(name, args, shape)
        if node is not None:
            return [node]
        if name == "round":
            return [_Pending("round", args, {}, shape, dtype)]
        if name == "clip" and len(args) == 3 and _is_lit(args[1]) and _is_lit(args[2]):
            x, lo, hi = args
            if _is_pend(x, "round"):
                return [_Pending("clip", args, {}, shape, dtype)]
            node = self.realize(x)
            as_py = int if node.dtype.startswith(("int", "uint")) else float
            return [ir.clip(node, lo=as_py(_scalar(lo)), hi=as_py(_scalar(hi)))]
        return self.inline(closed, args)

    def named_moe(self, name: str, args: list, shape: tuple) -> ir.Node | None:
        """The named calls of ``frontend.nn``'s routed-expert block: tensor
        operands first, then scalar literals (the op's attrs); sizes that
        are static in the call (``top_k``, ``n_experts``) are read from its
        result's shape.  None for any other name or form."""
        arity = {"rms_norm": (1, 3), "rope": (3, 2), "silu_mul": (1, 2),
                 "moe_route": (1, 0), "moe_group_sizes": (1, 0),
                 "moe_dispatch": (2, 0), "moe_combine": (3, 2)}.get(name)
        if arity is None or len(args) != sum(arity):
            return None
        tensors, lits = args[: arity[0]], args[arity[0]:]
        if not all(_is_lit(v) for v in lits):
            raise ValueError(f"{name}: scalar arguments must be literals")
        x, *rest = (self.realize(t) for t in tensors)
        f = [_scalar(v) for v in lits]
        if name == "rms_norm":
            return ir.rms_norm(x, in_scale=f[0], out_scale=f[1], eps=f[2])
        if name == "rope":
            return ir.rope(x, *rest, in_scale=f[0], out_scale=f[1])
        if name == "silu_mul":
            return ir.silu_mul(x, in_scale=f[0], out_scale=f[1])
        if name == "moe_route":
            return ir.moe_route(x, top_k=shape[-1])
        if name == "moe_group_sizes":
            return ir.moe_group_sizes(x, n_experts=shape[0])
        if name == "moe_dispatch":
            return ir.moe_dispatch(x, *rest)
        return ir.moe_combine(x, *rest, logit_scale=f[0], scale=f[1])

    def ragged_dot(self, eqn, args) -> ir.Node:
        """``jax.lax.ragged_dot(x[R, C], w[G, C, K], group_sizes[G])``: rows
        sorted by group, the ragged dimension the rows of ``x``."""
        p = eqn.params
        dn = p["ragged_dot_dimension_numbers"]
        if (
            dn.dot_dimension_numbers != (((1,), (1,)), ((), ()))
            or tuple(dn.lhs_ragged_dimensions) != (0,)
            or tuple(dn.rhs_group_dimensions) != (0,)
            or p.get("group_offset") is not None
        ):
            raise ValueError(f"only ragged_dot over the rows of x: {dn}")
        x, w, gs = (self.realize(a) for a in args)
        return ir.grouped_dense(x, w, gs, out_dtype=str(eqn.outvars[0].aval.dtype))

    def inline(self, closed_jaxpr, args) -> list:
        jaxpr = closed_jaxpr.jaxpr
        inner = _Importer(self.name, env=dict(), problems=self.problems)
        for var, const in zip(jaxpr.constvars, closed_jaxpr.consts):
            inner.env[var] = ir.const(np.asarray(const))
        for var, val in zip(jaxpr.invars, args):
            inner.env[var] = val
        inner.process(jaxpr.eqns)
        return [inner.read(v) for v in jaxpr.outvars]

    def dot_general(self, eqn, args) -> ir.Node:
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        x, w = (self.realize(a) for a in args)
        out_dtype = str(eqn.outvars[0].aval.dtype)
        if (
            tuple(lb) == tuple(rb) == (0,)
            and len(x.shape) == len(w.shape) == 3
            and tuple(lc) == (2,)
            and tuple(rc) == (1,)
        ):
            # batched activation-activation matmul (one leading batch dim):
            # jnp.matmul((B, M, C), (B, C, K)) — attention scores/context
            return ir.dense(x, w, out_dtype=out_dtype)
        if lb or rb or len(w.shape) != 2:
            raise ValueError(
                "only 2-D weight matmuls and single-batch-dim batched "
                "matmuls are supported"
            )
        if tuple(lc) != (len(x.shape) - 1,) or tuple(rc) != (0,):
            raise ValueError(f"contraction {eqn.params['dimension_numbers']}")
        return ir.dense(x, w, out_dtype=out_dtype)

    def conv(self, eqn, args) -> ir.Node:
        p = eqn.params
        dn = p["dimension_numbers"]
        if (
            tuple(dn.lhs_spec) != (0, 3, 1, 2)
            or tuple(dn.rhs_spec) != (3, 2, 0, 1)
            or tuple(dn.out_spec) != (0, 3, 1, 2)
        ):
            raise ValueError("only NHWC / HWIO / NHWC convolutions")
        if p["feature_group_count"] != 1 or p["batch_group_count"] != 1:
            raise ValueError("grouped convolutions")
        if set(p["lhs_dilation"]) != {1} or set(p["rhs_dilation"]) != {1}:
            raise ValueError("dilated convolutions")
        (sh, sw) = p["window_strides"]
        pads = tuple(p["padding"])
        if sh != sw or len({pads[0][0], pads[0][1], pads[1][0], pads[1][1]}) != 1:
            raise ValueError("only square strides and symmetric padding")
        x, w = (self.realize(a) for a in args)
        return ir.conv2d(
            x,
            w,
            stride=int(sh),
            padding=int(pads[0][0]),
            out_dtype=str(eqn.outvars[0].aval.dtype),
        )

    def max_pool(self, eqn, args) -> ir.Node:
        p = eqn.params
        wd, ws = tuple(p["window_dimensions"]), tuple(p["window_strides"])
        if len(wd) != 4 or wd[0] != 1 or wd[3] != 1 or wd[1] != wd[2]:
            raise ValueError(f"window {wd} is not NHWC square pooling")
        if ws[0] != 1 or ws[3] != 1 or ws[1] != ws[2]:
            raise ValueError(f"strides {ws} are not NHWC square pooling")
        if any(pad != (0, 0) for pad in p["padding"]):
            raise ValueError("padded pooling")
        if set(p["base_dilation"]) != {1} or set(p["window_dilation"]) != {1}:
            raise ValueError("dilated pooling")
        return ir.max_pool2d(self.realize(args[0]), size=wd[1], stride=ws[1])

    def elementwise(self, build, args) -> ir.Node | None:
        """Two realized tensors (or tensor + scalar literal) -> direct IR op;
        anything pending stays symbolic for the idiom matchers downstream."""
        a, b = args
        if isinstance(a, ir.Node) and isinstance(b, ir.Node):
            return build(a, b)
        if isinstance(a, ir.Node) and _is_lit(b):
            return build(a, ir.const(np.asarray(b.val, dtype=b.dtype)))
        if _is_lit(a) and isinstance(b, ir.Node):
            return build(ir.const(np.asarray(a.val, dtype=a.dtype)), b)
        # broadcast-of-node operands realize to the source (numpy broadcast)
        for x, y in ((a, b), (b, a)):
            if isinstance(x, ir.Node) and _is_pend(y, "broadcast"):
                src = y.args[0]
                if isinstance(src, ir.Node):
                    if x is a:
                        return build(x, self._realize_broadcast(y))
                    return build(self._realize_broadcast(y), x)
        return None


def _import_closed(closed_jaxpr, invar_nodes: list[ir.Node], name: str) -> ir.Graph:
    """The one import driver: bind each invar to a prebuilt node (input or
    constant), walk the equations, realize the outputs, and either raise
    every collected problem at once or return the graph."""
    jaxpr = closed_jaxpr.jaxpr
    if len(invar_nodes) != len(jaxpr.invars):
        raise ValueError(
            f"{len(invar_nodes)} bindings for {len(jaxpr.invars)} jaxpr inputs"
        )
    imp = _Importer(name)
    for var, const in zip(jaxpr.constvars, closed_jaxpr.consts):
        imp.env[var] = ir.const(np.asarray(const))
    for var, node in zip(jaxpr.invars, invar_nodes):
        imp.env[var] = node
    imp.process(jaxpr.eqns)
    outputs = [imp.realize(imp.read(v)) for v in jaxpr.outvars]
    if imp.problems:
        raise UnsupportedJaxprError(name, imp.problems)
    return ir.Graph(outputs, name=name)


def import_jaxpr(
    closed_jaxpr,
    *,
    input_names: list[str],
    name: str = "traced",
) -> ir.Graph:
    """Import a ClosedJaxpr whose invars are all graph inputs, named by
    ``input_names`` (use ``trace_model`` to bind trailing invars to
    parameter constants)."""
    invar_nodes = [
        ir.input_(var.aval.shape, str(var.aval.dtype), name=input_name)
        for var, input_name in zip(
            closed_jaxpr.jaxpr.invars, input_names, strict=True
        )
    ]
    return _import_closed(closed_jaxpr, invar_nodes, name)


def trace_model(
    fn,
    example_inputs: dict[str, Any],
    params: Any = None,
    *,
    name: str | None = None,
) -> ir.Graph:
    """Trace ``fn(*inputs)`` (or ``fn(*inputs, params)``) with
    ``jax.make_jaxpr`` and import the jaxpr into an ``ir.Graph``.

    ``example_inputs`` maps graph-input names to example arrays (only shape
    and dtype matter).  ``params`` is an optional pytree of weight arrays;
    passing weights here (instead of closing over them) keeps their
    preprocessing (transposes, quantization) as graph ops, so compile-time
    constant folding — and the naive mode's run-time cost for skipping it —
    work exactly as for hand-built graphs.  Closed-over numpy constants are
    still captured, but jax evaluates their op chains eagerly during tracing.
    """
    import jax

    arrays = [np.asarray(v) for v in example_inputs.values()]
    if params is not None:
        closed = jax.make_jaxpr(fn)(*arrays, params)
    else:
        closed = jax.make_jaxpr(fn)(*arrays)

    jaxpr = closed.jaxpr
    input_names = list(example_inputs)
    n_inputs = len(input_names)
    param_leaves = jax.tree_util.tree_leaves(params) if params is not None else []
    if len(jaxpr.invars) != n_inputs + len(param_leaves):
        raise ValueError(
            f"traced {len(jaxpr.invars)} jaxpr inputs but got {n_inputs} "
            f"example inputs + {len(param_leaves)} param leaves"
        )
    param_names = [""] * len(param_leaves)
    if params is not None:
        flat, _ = jax.tree_util.tree_flatten_with_path(params)
        param_names = [
            "".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
            for path, _ in flat
        ]

    invar_nodes = [
        ir.input_(var.aval.shape, str(var.aval.dtype), name=input_names[i])
        if i < n_inputs
        else ir.const(
            np.asarray(param_leaves[i - n_inputs]),
            name=param_names[i - n_inputs] or "",
        )
        for i, var in enumerate(jaxpr.invars)
    ]
    return _import_closed(
        closed, invar_nodes, name or getattr(fn, "__name__", "traced")
    )
