"""Phase attribution and the compile layer's readers.

On hand-made HLO and trace data, then on a trace of two tiny solves of
the scoped ``sinkhorn_log`` recorded on a v5e (``record_trace.py``), beside
the text of the same program compiled there (``record_phase_map.py``).
"""
import json
import time
from pathlib import Path

import pytest

import phases as P
import run as bench_run
import traces as T

DATA = Path(__file__).resolve().parent / "data"
OLD = str(DATA / "sinkhorn.xplane.pb")
SCOPED = str(DATA / "sinkhorn_scoped.xplane.pb")

HLO = """HloModule jit_solve, entry_computation_layout={(f32[8,8]{1,0})->f32[8,8]{1,0}}

%fused_computation (param_0: f32[8,8]) -> f32[8] {
  %param_0 = f32[8,8]{1,0} parameter(0)
  ROOT %reduce.1 = f32[8]{0} reduce(%param_0), metadata={op_name="jit(solve)/while/body/sinkhorn.f_update/reduce_max"}
}

%body (carry: (f32[8], f32[8,8])) -> (f32[8], f32[8,8]) {
  %carry = (f32[8]{0}, f32[8,8]{1,0}) parameter(0)
  %get-tuple-element.1 = f32[8,8]{1,0} get-tuple-element(%carry), index=1
  %copy-start = (f32[8,8]{1,0:S(1)}, f32[8,8]{1,0}, u32[]) copy-start(%get-tuple-element.1)
  %copy-done = f32[8,8]{1,0:S(1)} copy-done(%copy-start)
  %fusion.6 = f32[8]{0} fusion(%copy-done), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(solve)/while/body/sinkhorn.f_update/reduce_max"}
  %fusion.7 = f32[8]{0} fusion(%copy-done), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(solve)/while/body/sinkhorn.g_update/reduce_max"}
  %abs_reduce_fusion = f32[] fusion(%fusion.7), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(solve)/while/body/sinkhorn.marginal_err/reduce_sum"}
  ROOT %tuple = (f32[8]{0}, f32[8,8]{1,0}) tuple(%fusion.7, %get-tuple-element.1)
}

%cond (carry.1: (f32[8], f32[8,8])) -> pred[] {
  %carry.1 = (f32[8]{0}, f32[8,8]{1,0}) parameter(0)
  ROOT %compare = pred[] constant(true)
}

ENTRY %main (Arg_0.1: f32[8,8]) -> f32[8,8] {
  %Arg_0.1 = f32[8,8]{1,0} parameter(0), metadata={op_name="C"}
  %broadcast = f32[8]{0} broadcast(%constant), dimensions={}
  %while = (f32[8]{0}, f32[8,8]{1,0}) while(%tuple.0), condition=%cond, body=%body
  ROOT %divide_exponential_fusion = f32[8,8]{1,0} fusion(%while), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(solve)/sinkhorn.plan/exp"}
}
"""


def test_result_shape():
    assert P.result_shape("%copy-done.4 = f32[80]{0:T(128)S(1)} copy-done(%x)") == \
        "f32[80]{0:T(128)S(1)}"
    assert P.result_shape("%copy-start = (f32[8,8]{1,0:S(1)}, u32[]{:S(2)}) copy-start(%y)") \
        == "(f32[8,8]{1,0:S(1)}, u32[]{:S(2)})"


def test_phase_map_reads_scopes_and_places_unscoped_instructions():
    m = P.instruction_phases(HLO)
    assert {k: m[k][0] for k in ("fusion.6", "fusion.7", "abs_reduce_fusion",
                                 "divide_exponential_fusion")} == {
        "fusion.6": "f_update", "fusion.7": "g_update",
        "abs_reduce_fusion": "marginal_err", "divide_exponential_fusion": "plan"}
    # no scope: the copies inside the loop, everything else outside it
    assert m["copy-start"] == (P.LOOP, "(f32[8,8]{1,0:S(1)}, f32[8,8]{1,0}, u32[])")
    assert m["copy-done"][0] == P.LOOP and m["compare"][0] == P.LOOP
    assert m["broadcast"][0] == P.OUTSIDE and m["while"][0] == P.OUTSIDE


def test_phase_map_is_none_without_scopes():
    assert P.instruction_phases(HLO.replace("sinkhorn.", "other.")) is None


def test_seconds_by_phase_on_a_hand_made_trace():
    m = P.instruction_phases(HLO)
    ins = {"fusion.6": (3e-6, "f32[8]{0}"), "fusion.7": (2e-6, "f32[8]{0}"),
           "abs_reduce_fusion": (1e-6, "f32[]"), "copy-done": (5e-6, "f32[8,8]{1,0:S(1)}"),
           "broadcast": (1e-7, "f32[8]{0}")}
    assert P.seconds_by_phase(ins, m) == pytest.approx(
        {"f_update": 3e-6, "g_update": 2e-6, "marginal_err": 1e-6, P.LOOP: 5e-6,
         P.OUTSIDE: 1e-7})
    # an instruction of another program, by name or by shape, or no instruction
    assert P.seconds_by_phase({**ins, "fusion.99": (1e-6, "f32[8]{0}")}, m) is None
    assert P.seconds_by_phase({**ins, "fusion.6": (3e-6, "f32[16]{0}")}, m) is None
    assert P.seconds_by_phase({}, m) is None


@pytest.mark.parametrize("path", [OLD, SCOPED])
def test_instruction_seconds_sum_to_leaf_seconds(path):
    tr = T.load(path)
    ins = P.instruction_seconds(path)
    by_base = {}
    for name, (sec, _) in ins.items():
        by_base[T.base_name(name)] = by_base.get(T.base_name(name), 0.0) + sec
    # the same sums, added in another order
    assert by_base == pytest.approx(T.leaf_seconds(tr), rel=1e-12, abs=0)
    assert ins["copy-done"][1] == "f32[80,80]{1,0:T(8,128)S(1)}"


def test_existing_readers_read_the_recorded_trace_as_before():
    facts = json.loads((DATA / "sinkhorn.json").read_text())

    class Run:
        pass

    run = Run()
    run.facts = {"iters": facts["iters"], "solves": facts["solves"]}
    red = T.reduce(OLD)
    got = {n: bench_run.reader(n).read(run, red) for n in (
        "idle_share.entropic", "device_us_per_iter.entropic", "iters_per_solve.entropic")}
    assert got == {"idle_share.entropic": 82.01604965160472,
                   "device_us_per_iter.entropic": 5.32583,
                   "iters_per_solve.entropic": 100.0}


def test_recorded_scoped_trace_attributes_every_phase():
    facts = json.loads((DATA / "sinkhorn_scoped.json").read_text())
    assert facts["iters"] == 200 and facts["device_kind"] == "TPU v5 lite"
    m = P.instruction_phases((DATA / "sinkhorn_scoped.hlo.txt").read_text())
    ins = P.instruction_seconds(SCOPED)
    per = P.seconds_by_phase(ins, m)
    assert per is not None
    for phase in ("f_update", "g_update", "marginal_err", "plan", P.LOOP):
        assert per[phase] > 0, phase
    loop = {name for name in ins if m[name][0] == P.LOOP}
    assert {"copy-start", "copy-done"} <= loop
    assert all(T.base_name(n) in ("copy-start", "copy-done") for n in loop)
    # every leaf instruction has one phase; busy time adds the loop's own
    tr = T.load(SCOPED)
    assert sum(per.values()) == pytest.approx(sum(T.leaf_seconds(tr).values()), rel=1e-12)
    assert sum(per.values()) <= T.busy_s(tr)


class _Run:
    def __init__(self, t_start, setup_s):
        self.t_start, self.setup_s = t_start, setup_s


def test_compile_readers_count_set_up_builds_and_later_solver_builds():
    import jax
    import jax.numpy as jnp

    from repro.core import sinkhorn

    t_start = time.perf_counter()
    C = jnp.ones((8, 8), jnp.float32)
    a = jnp.full((8,), 1 / 8, jnp.float32)
    jax.clear_caches()
    jax.block_until_ready(sinkhorn.sinkhorn_log(C, a, a, max_iters=4).plan)
    run = _Run(t_start, time.perf_counter() - t_start)
    build_s = bench_run.reader("setup_build_s.entropic")
    after = bench_run.reader("solver_builds_after_setup.entropic")
    assert 0 < build_s.read(run, None) <= run.setup_s
    assert after.read(run, None) == 0
    jax.block_until_ready(sinkhorn.sinkhorn_log(C, a, a, max_iters=5).plan)   # a new program
    assert after.read(run, None) == 1
    t0 = time.perf_counter()
    jax.block_until_ready(sinkhorn.sinkhorn_log(C, a, a, max_iters=6).plan)
    P.own_work.append((t0, time.perf_counter()))       # the phase readers' own builds
    try:
        assert after.read(run, None) == 1
    finally:
        P.own_work.pop()
