"""The one front door: Target parsing/validation, CompileOptions,
repro.compile() dispatch (graph / zoo name / callable), capability
negotiation, feed validation, and warm-cache solver-call accounting."""

import numpy as np
import pytest

import repro
from repro.api import backend_for
from repro.core import ir
from repro.core.pipeline import resolve_mode
from repro.core.zoo import get_model


def _qdense_graph(seed=0):
    rng = np.random.default_rng(seed)
    x = ir.input_((4, 32), "int8", name="x")
    w = ir.quantize(
        ir.transpose(ir.const((rng.normal(size=(16, 32)) * 0.05).astype(np.float32))),
        scale=0.0625,
    )
    b = ir.const(rng.integers(-50, 50, size=(16,)).astype(np.int32))
    out = ir.clip(ir.requantize(ir.bias_add(ir.dense(x, w), b), scale=0.125))
    return ir.Graph([out], name="qdense")


# -- Target -------------------------------------------------------------------


def test_target_parse_one_string():
    t = repro.Target.parse("gemmini:optimized")
    assert t.accelerator == "gemmini"
    assert t.mode == "optimized"
    assert t.internal_mode == "proposed"
    assert repro.Target.parse("edge_npu").mode == "optimized"


def test_target_parse_rejects_bad_spec():
    with pytest.raises(repro.TargetError, match="accelerator:mode"):
        repro.Target.parse("a:b:c")
    with pytest.raises(repro.TargetError, match="accelerator:mode"):
        repro.Target.parse(":optimized")


def test_target_parse_rejects_conflicting_mode():
    with pytest.raises(repro.TargetError, match="also passed"):
        repro.Target.parse("gemmini:naive", mode="optimized")
    # agreeing spellings are fine
    assert repro.Target.parse("gemmini:naive", mode="naive").mode == "naive"


def test_target_unknown_accelerator_lists_registry():
    with pytest.raises(repro.TargetError, match="gemmini"):
        repro.Target("definitely_not_registered")


def test_target_unknown_mode_lists_modes():
    with pytest.raises(repro.TargetError, match="baseline"):
        repro.Target("gemmini", mode="fastest")


def test_target_lists_all_problems_at_once():
    with pytest.raises(repro.TargetError) as exc:
        repro.Target("nope", mode="bogus", cache=False, cache_dir="/tmp/x")
    assert len(exc.value.problems) == 3


def test_mode_aliases_resolve():
    assert resolve_mode("optimized") == "proposed"
    assert resolve_mode("baseline") == "c_toolchain"
    assert resolve_mode("naive") == "naive"
    assert resolve_mode("proposed") == "proposed"
    with pytest.raises(ValueError, match="unknown mode"):
        resolve_mode("warp_speed")


# -- compile() dispatch -------------------------------------------------------


def test_compile_graph_and_string_target():
    g = _qdense_graph()
    ref = ir.execute_graph(_qdense_graph(), {"x": _feed()})[0]
    mod = repro.compile(g, target="gemmini:optimized")
    assert np.array_equal(mod.run({"x": _feed()})[0], ref)


def _feed():
    return np.random.default_rng(1).integers(-128, 128, (4, 32)).astype(np.int8)


def test_compile_zoo_name_all_public_modes_agree_with_internal():
    feeds = get_model("mlp_tiny").feeds(seed=2)
    for public, internal in (
        ("optimized", "proposed"),
        ("baseline", "c_toolchain"),
        ("naive", "naive"),
    ):
        pub = repro.compile("mlp_tiny", repro.Target("edge_npu", mode=public))
        intl = repro.compile("mlp_tiny", repro.Target("edge_npu", mode=internal))
        assert pub.mode == intl.mode == internal
        assert np.array_equal(pub.run(feeds)[0], intl.run(feeds)[0])
        assert pub.modeled_cycles() == intl.modeled_cycles()


def test_compile_rejects_stray_kwargs_for_graph_and_zoo():
    with pytest.raises(ValueError, match="traced callables"):
        repro.compile(_qdense_graph(), "gemmini", example_inputs={"x": _feed()})
    with pytest.raises(ValueError, match="zoo models"):
        repro.compile("mlp_tiny", "gemmini", params={})


def test_compile_unknown_model_type():
    with pytest.raises(TypeError, match="ir.Graph"):
        repro.compile(12345, "gemmini")


def test_backend_memoized_per_target_family():
    """All modes of one accelerator share one backend (so mode sweeps reuse
    the scheduler's in-memory memo); fresh_backend opts out."""
    m1 = repro.compile("mlp_tiny", "gemmini:optimized")
    m2 = repro.compile("mlp_tiny", "gemmini:naive")
    assert m1.backend is m2.backend
    m3 = repro.compile(
        "mlp_tiny", "gemmini:optimized",
        options=repro.CompileOptions(fresh_backend=True),
    )
    assert m3.backend is not m1.backend
    assert backend_for(repro.Target.parse("gemmini")) is m1.backend


def test_warm_cache_compiles_with_zero_extra_solver_calls(tmp_path):
    """Acceptance: repro.compile on a warm persistent cache performs zero
    extended-CoSA DSE sweeps, even in a fresh backend (process stand-in)."""
    t = repro.Target("edge_npu", cache_dir=tmp_path)
    fresh = repro.CompileOptions(fresh_backend=True)
    cold = repro.compile("mlp_tiny", t, options=fresh)
    assert cold.backend.scheduler.n_solver_calls > 0
    warm = repro.compile("mlp_tiny", t, options=fresh)
    assert warm.backend.scheduler.n_solver_calls == 0
    feeds = get_model("mlp_tiny").feeds(seed=3)
    assert np.array_equal(warm.run(feeds)[0], cold.run(feeds)[0])


# -- capability negotiation ---------------------------------------------------


def _dense_only_desc():
    """A gemmini variant that cannot run convolutions at all."""
    from repro.core.descriptions import make_gemmini_description

    desc = make_gemmini_description()
    for tag, cc in list(desc.core_computes.items()):
        if cc.op == "conv2d":
            del desc.core_computes[tag]
    return desc


def test_host_fallback_is_clean_end_to_end():
    """Unsupported conv chains are NOT legalized into generalized ops (which
    the host cannot execute); they stay plain ops, fall to the host, and the
    whole model still runs bit-exactly — in every mode."""
    model = get_model("qcnn")
    feeds = model.feeds(seed=4)
    ref = ir.execute_graph(model.build(), feeds)[0]
    for mode in ("optimized", "baseline", "naive"):
        mod = repro.compile("qcnn", repro.Target(_dense_only_desc(), mode=mode))
        convs = [n for n in mod.graph.toposort() if "conv2d" in n.op]
        assert convs and all(n.target == "host" for n in convs)
        assert not any(n.op == "generalized_conv2d" for n in convs)
        assert np.array_equal(mod.run(feeds)[0], ref)


def test_allow_host_fallback_false_raises_capability_error():
    with pytest.raises(repro.CapabilityError) as exc:
        repro.compile(
            "qcnn",
            repro.Target(_dense_only_desc()),
            options=repro.CompileOptions(allow_host_fallback=False),
        )
    msg = str(exc.value)
    assert "conv2d" in msg and "supported core ops" in msg


# -- feed validation ----------------------------------------------------------


@pytest.fixture(scope="module")
def module():
    return repro.compile("mlp_tiny", "gemmini:optimized")


def test_input_signature_carried_on_module(module):
    assert module.input_signature() == (("x", (1, 16), "int8"),)


def test_feed_validation_lists_all_problems(module):
    with pytest.raises(repro.FeedError) as exc:
        module.run({"y": np.zeros((1, 16), np.int8), "z": 1})
    msg = str(exc.value)
    assert "missing feed for input 'x'" in msg
    assert "unknown feed 'y'" in msg
    assert "unknown feed 'z'" in msg
    assert "x: int8[1, 16]" in msg  # the expected signature


def test_feed_validation_applies_to_run_many(module):
    good = get_model("mlp_tiny").feeds(seed=0)
    with pytest.raises(repro.FeedError, match="unknown feed 'extra'"):
        module.run_many([good, {**good, "extra": 1}])
    with pytest.raises(repro.FeedError, match="missing feed"):
        module.run_many([{}])


def test_feed_error_is_a_key_error(module):
    """Back-compat: pre-existing callers catch KeyError on missing feeds."""
    with pytest.raises(KeyError, match="missing feed for input 'x'"):
        module.run({})


def test_feed_validation_checks_shape_and_dtype(module):
    with pytest.raises(repro.FeedError, match=r"float32\[1, 16\], expected"):
        module.run({"x": np.zeros((1, 16), np.float32)})
    with pytest.raises(repro.FeedError, match=r"int8\[2, 16\], expected"):
        module.run({"x": np.zeros((2, 16), np.int8)})
