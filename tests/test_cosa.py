"""Extended-CoSA scheduler: constraint invariants (hypothesis properties),
MIP-vs-heuristic cross-checks, description round-trips."""

import pytest

pytest.importorskip("hypothesis", reason="install the `test` extra for property tests")
from hypothesis import given, settings, strategies as st

from repro.core.arch_spec import GEMM_DIMS, ArchSpec, GemmWorkload
from repro.core.cosa.factors import pad_to_alignment, prime_factors
from repro.core.cosa.heuristic import solve_heuristic
from repro.core.cosa.mip import solve_mip
from repro.core.descriptions import (
    make_gemmini_description,
    make_tpu_v5e_description,
)
from repro.core.schedule import validate_schedule
from repro.core.scheduler import ExtendedCosaScheduler
from repro.core.simulator import simulate

GEMMINI = make_gemmini_description().arch
TPU = make_tpu_v5e_description().arch


def test_prime_factors():
    assert prime_factors(12) == (2, 2, 3)
    assert prime_factors(1) == ()
    assert prime_factors(97) == (97,)
    import math
    for n in (64, 27392, 102400, 524288):
        assert math.prod(prime_factors(n)) == n


def test_pad_to_alignment():
    assert pad_to_alignment(100, 16) % 16 == 0
    assert pad_to_alignment(100, 16) >= 100
    assert pad_to_alignment(128, 128) == 128


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(1, 2048),
    c=st.integers(1, 2048),
    k=st.integers(1, 2048),
)
def test_heuristic_schedule_always_valid(n, c, k):
    """Property: every heuristic schedule satisfies every hardware
    constraint (coverage, Eq. 1, spatial levels, memory shares)."""
    wl = GemmWorkload(N=n, C=c, K=k, in_bytes=1, w_bytes=1, out_bytes=4)
    for df in GEMMINI.dataflows:
        s = solve_heuristic(wl, GEMMINI, df, (1 / 3, 1 / 3, 1 / 3), True)
        if s is not None:
            assert validate_schedule(s, GEMMINI) == []


@pytest.mark.parametrize("dims", [(64, 64, 64), (256, 256, 256), (640, 128, 8)])
def test_mip_schedule_valid_and_competitive(dims):
    n, c, k = dims
    wl = GemmWorkload(N=n, C=c, K=k, in_bytes=1, w_bytes=1, out_bytes=4)
    df = GEMMINI.dataflow("WS")
    mip = solve_mip(wl, GEMMINI, df, (1 / 3, 1 / 3, 1 / 3), True)
    heur = solve_heuristic(wl, GEMMINI, df, (1 / 3, 1 / 3, 1 / 3), True)
    assert mip is not None and validate_schedule(mip, GEMMINI) == []
    assert heur is not None
    # the MIP should not be dramatically worse than the greedy heuristic
    t_mip = simulate(mip, GEMMINI).total_cycles
    t_heur = simulate(heur, GEMMINI).total_cycles
    assert t_mip <= 2.0 * t_heur


def test_eq1_instruction_limit_enforced():
    """Paper Eq. (1): PE-level factors never exceed DIM."""
    wl = GemmWorkload(N=512, C=512, K=512, in_bytes=1, w_bytes=1, out_bytes=4)
    sched = ExtendedCosaScheduler(GEMMINI).schedule(wl).best
    pe = sched.pe_tile()
    for j in GEMM_DIMS:
        assert pe[j] <= GEMMINI.pe_dim


def test_double_buffer_halves_memory():
    wl = GemmWorkload(N=1024, C=1024, K=1024, in_bytes=1, w_bytes=1, out_bytes=4)
    df = GEMMINI.dataflow("WS")
    s_db = solve_heuristic(wl, GEMMINI, df, (1 / 3, 1 / 3, 1 / 3), True)
    lvl = GEMMINI.buffered_levels()[0]
    cap = GEMMINI.levels[lvl].size_bytes
    # double-buffered footprint (2x tile) must fit within the shares
    assert s_db.level_footprint(lvl) <= cap


def test_scheduler_sweep_and_cache():
    sched = ExtendedCosaScheduler(TPU)
    wl = GemmWorkload(N=512, C=512, K=512, in_bytes=2, w_bytes=2, out_bytes=4)
    r1 = sched.schedule(wl)
    r2 = sched.schedule(wl)
    assert r1 is r2  # cached
    assert r1.n_candidates >= 4  # dataflows x shares x dbuf combos explored
    assert validate_schedule(r1.best, TPU) == []


def test_archspec_yaml_roundtrip():
    for arch in (GEMMINI, TPU):
        text = arch.to_yaml()
        back = ArchSpec.from_yaml(text)
        assert back.pe_dim == arch.pe_dim
        assert back.num_levels == arch.num_levels
        assert [d.name for d in back.dataflows] == [d.name for d in arch.dataflows]


def test_schedule_yaml_output():
    wl = GemmWorkload(N=128, C=128, K=128, in_bytes=1, w_bytes=1, out_bytes=4)
    s = ExtendedCosaScheduler(GEMMINI).schedule(wl).best
    d = s.to_dict()
    assert d["workload"]["N"] == 128
    assert len(d["levels"]) == GEMMINI.num_levels
    assert s.to_yaml()  # serializes


@pytest.mark.parametrize(
    "n,c,k,in_bytes,out_bytes",
    [
        (4096, 4096, 4096, 4, 4),
        (4096, 4096, 4096, 2, 4),
        (8192, 8192, 8192, 2, 2),
        (4096, 8192, 4096, 1, 4),
        (16, 640, 128, 1, 4),
    ],
)
def test_tpu_schedules_fit_the_kernel_vmem_limit(n, c, k, in_bytes, out_bytes):
    """What the Pallas GEMM holds in VMEM for a scheduled tile — every block
    double-buffered plus the 4-byte accumulator scratch — fits the
    scheduler's budget, which fits inside the limit the kernel asks for."""
    from repro.core.descriptions.tpu_v5e import MOSAIC_RESERVE_BYTES
    from repro.core.mapping import MappingGenerator

    desc = make_tpu_v5e_description()
    wl = GemmWorkload(
        N=n, C=c, K=k, in_bytes=in_bytes, w_bytes=in_bytes, out_bytes=out_bytes
    )
    sched = ExtendedCosaScheduler(desc.arch).schedule(wl).best
    cfg = MappingGenerator(desc).to_kernel_config(sched, interpret=False)
    bm, bk, bn = cfg.block_m, cfg.block_k, cfg.block_n
    held = 2 * (bm * bk + bk * bn) * in_bytes + 2 * bm * bn * out_bytes
    held += bm * bn * 4
    budget = desc.arch.levels[desc.arch.buffered_levels()[0]].size_bytes
    assert held <= budget
    assert budget + MOSAIC_RESERVE_BYTES == cfg.vmem_limit_bytes
