"""Device time by tick phase (``phases.py``).

``testdata/f20k.xplane.pb`` holds two warm requests of the cfg III cell
cut to 20,000 files per site and 0.02 days, recorded on a TPU v5e with
the tick's named scopes and the repository tracer on (so its spans are
also profiler annotations); ``f20k.host.json`` holds the tracer's spans
and the ``perf_counter_ns`` reading taken inside the first request's
annotation; ``f20k.hlo.txt.gz`` is the compiled program's text, which
maps each traced operation to its phase. Recorded with::

    python3 benchmarks/chip/phases.py --workload cfgIII-1M.steady \\
        --seed 2718281828 --requests 2 --files 20000 --days 0.02 \\
        --record <dir> --name f20k

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""

from __future__ import annotations

import gzip
import json
import os
import re
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
DATA = os.path.join(BENCH, "testdata")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(BENCH)),
                                "src"))

import phases  # noqa: E402
import trace_reduce  # noqa: E402
from repro.sim.batched import TICK_SCOPES  # noqa: E402

MIGRATE = 'op_name="jit(lane_sim)/while/body/closed_call/tick.migrate/le"'

#: A loop body in the form of a compiled module's text: a scope-less
#: cumsum cluster (two ``reduce-window``s and the fusions XLA builds
#: around them) fed by a ``tick.transfer`` fusion and used by a
#: ``tick.migrate`` one; a copy of the scan carry used by a fusion whose
#: scope sits inside it; the loop counter, which no walk reaches; and
#: constants whose metadata names a phase they do not belong to.
SNIPPET = """HloModule jit_lane_sim, is_scheduled=true

%add_f32 (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %s = f32[] add(%x, %y)
}

%fused_in (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%param_0, %param_0)
}

%fused_slice (param_0.1: f32[4]) -> f32[1] {
  %param_0.1 = f32[4]{0} parameter(0)
  %constant.7 = f32[]{:T(128)} constant(-0), metadata={op_name="jit(lane_sim)/while/body/closed_call/tick.transfer/slice"}
  ROOT %slice.1 = f32[1]{0} slice(%param_0.1), slice={[3:4]}, metadata={op_name="reduce_window_sum"}
}

%fused_add (param_0.2: f32[4], param_1.2: f32[1]) -> f32[4] {
  %param_0.2 = f32[4]{0} parameter(0)
  %param_1.2 = f32[1]{0} parameter(1)
  %broadcast.2 = f32[4]{0} broadcast(%param_1.2), dimensions={0}
  ROOT %add.2 = f32[4]{0} add(%param_0.2, %broadcast.2), metadata={op_name="reduce_window_sum"}
}

%fused_gate (param_0.3: f32[4]) -> pred[4] {
  %param_0.3 = f32[4]{0} parameter(0)
  %constant.8 = f32[]{:T(128)} constant(1)
  %broadcast.3 = f32[4]{0} broadcast(%constant.8), dimensions={}
  ROOT %le.3 = pred[4]{0} compare(%param_0.3, %broadcast.3), direction=LE
}

%fused_apply (param_0.4: f32[4], param_1.4: pred[4]) -> f32[4] {
  %param_0.4 = f32[4]{0} parameter(0)
  %param_1.4 = pred[4]{0} parameter(1)
  %select.4 = f32[4]{0} select(%param_1.4, %param_0.4, %param_0.4), metadata={op_name="jit(lane_sim)/while/body/closed_call/tick.apply/jit(_where)/select_n"}
  ROOT %bitcast.4 = f32[4]{0} bitcast(%select.4)
}

%body (p: (s32[], f32[4], f32[4])) -> (s32[], f32[4], f32[4]) {
  %p = (s32[], f32[4]{0}, f32[4]{0}) parameter(0)
  %constant.0 = f32[] constant(0), metadata={op_name="jit(lane_sim)/while/body/closed_call/tick.waitq/x"}
  %constant.1 = s32[] constant(1), metadata={op_name="jit(lane_sim)/while/body/closed_call/tick.submit/y"}
  %gte.0 = s32[] get-tuple-element(%p), index=0
  %gte.1 = f32[4]{0} get-tuple-element(%p), index=1
  %gte.2 = f32[4]{0} get-tuple-element(%p), index=2
  %fusion.in = f32[4]{0} fusion(%gte.1), kind=kLoop, calls=%fused_in, metadata={op_name="jit(lane_sim)/while/body/closed_call/tick.transfer/mul"}
  %reduce-window.1 = f32[4]{0:T(128)} reduce-window(%fusion.in, %constant.0), window={size=4 pad=3_0}, to_apply=%add_f32
  %slice_reduce_fusion.1 = f32[1]{0} fusion(%reduce-window.1), kind=kLoop, calls=%fused_slice, metadata={op_name="reduce_window_sum"}
  %reduce-window.2 = f32[1]{0} reduce-window(%slice_reduce_fusion.1, %constant.0), window={size=1}, to_apply=%add_f32
  %add_bitcast_fusion.1 = f32[4]{0} fusion(%reduce-window.1, %reduce-window.2), kind=kLoop, calls=%fused_add
  %fusion.gate = pred[4]{0} fusion(%add_bitcast_fusion.1), kind=kLoop, calls=%fused_gate, MIGRATE
  %copy.1 = f32[4]{0} copy(%gte.2)
  %fusion.apply = f32[4]{0} fusion(%copy.1, %fusion.gate), kind=kLoop, calls=%fused_apply
  %add.9 = s32[] add(%gte.0, %constant.1), metadata={op_name="jit(lane_sim)/while/body/add"}
  ROOT %tuple.1 = (s32[], f32[4]{0}, f32[4]{0}) tuple(%add.9, %gte.1, %fusion.apply)
}

%cond (p.1: (s32[], f32[4], f32[4])) -> pred[] {
  %p.1 = (s32[], f32[4]{0}, f32[4]{0}) parameter(0)
  %gte.5 = s32[] get-tuple-element(%p.1), index=0
  %constant.5 = s32[] constant(361)
  ROOT %lt.5 = pred[] compare(%gte.5, %constant.5), direction=LT
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %c0 = s32[] constant(0)
  %init = (s32[], f32[4]{0}, f32[4]{0}) tuple(%c0, %a, %a)
  %while.1 = (s32[], f32[4]{0}, f32[4]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(lane_sim)/while"}
  ROOT %out = f32[4]{0} get-tuple-element(%while.1), index=1
}
""".replace("MIGRATE", "metadata={" + MIGRATE + "}")


@pytest.mark.parametrize("name, scope", [
    # the cumsum cluster takes the scope of what uses it (users first),
    # not that of what feeds it
    ("reduce-window.1", "tick.migrate"),
    ("slice_reduce_fusion.1", "tick.migrate"),
    ("reduce-window.2", "tick.migrate"),
    ("add_bitcast_fusion.1", "tick.migrate"),
    ("fusion.in", "tick.transfer"),
    # a fusion's own scope may sit inside it; the carry copy takes it
    ("fusion.apply", "tick.apply"),
    ("copy.1", "tick.apply"),
    # the loop counter and the loop reach no scope: a constant's
    # metadata counts for nothing
    ("add.9", "unscoped"),
    ("while.1", "unscoped"),
    # an instruction inside a fusion takes its caller's scope
    ("select.4", "tick.apply"),
    ("le.3", "tick.migrate"),
])
def test_walk_gives_expected_scope(name, scope):
    assert phases.hlo_scopes(SNIPPET)[name] == scope


def test_phase_times_groups_ops_by_module_run():
    # in a request from 0 to 300 ns: a loop (0-100) of the grid program
    # holding a sort (10-40) and a cumsum (50-60), then an op of another
    # module (200-210) that no map names
    reqs = [(0, 300)]
    devs = {"/device:TPU:0": [(0, 100, "while.1"), (10, 40, "sort.1"),
                              (50, 60, "reduce-window.1"),
                              (200, 210, "fusion.7")]}
    modules = {"/device:TPU:0": [(0, 150, "jit_lane_sim(1)"),
                                 (190, 220, "jit_other(2)")]}
    grid = {"while.1": "unscoped", "sort.1": "tick.waitq",
            "reduce-window.1": "tick.migrate"}
    other = {"sort.1": "tick.submit"}  # names fewer of the module's ops
    out = phases.phase_times(reqs, devs, modules, [other, grid])
    assert out == pytest.approx({"tick.waitq": 30e-9,
                                 "tick.migrate": 10e-9, "unscoped": 70e-9})
    assert sum(out.values()) == pytest.approx(
        trace_reduce.reduce(reqs, devs)["busy_s"])


def test_no_phase_names_gives_nothing():
    reqs = [(0, 300)]
    devs = {"/device:TPU:0": [(0, 100, "while.1")]}
    modules = {"/device:TPU:0": [(0, 150, "jit_lane_sim(1)")]}
    assert phases.phase_times(reqs, devs, modules,
                              [{"while.1": "unscoped"}]) is None
    assert phases.phase_times([], devs, modules,
                              [{"while.1": "tick.waitq"}]) is None


# --------------------------------------------------- the recorded trace
def _recorded():
    path = os.path.join(DATA, "f20k.xplane.pb")
    with open(os.path.join(DATA, "f20k.host.json")) as f:
        host = json.load(f)
    with gzip.open(os.path.join(DATA, "f20k.hlo.txt.gz"), "rt") as f:
        text = f.read()
    reqs, devs = trace_reduce.read_trace(path)
    reduced = trace_reduce.reduce(reqs, devs, host["spans"],
                                  host["perf_at_first_ns"])
    out = phases.phase_times(reqs, devs, phases.read_modules(path),
                             [phases.hlo_scopes(text)])
    return path, host, reqs, reduced, out


@pytest.fixture(scope="module")
def recorded():
    return _recorded()


def test_recorded_phases_sum_to_busy_time(recorded):
    _, _, _, reduced, out = recorded
    assert set(out) <= {*TICK_SCOPES.values(), "unscoped"}
    assert sum(out.values()) == pytest.approx(reduced["busy_s"], rel=0.005)


def test_recorded_trace_places_the_sort_and_leaves_little_unscoped(
        recorded):
    _, _, _, reduced, out = recorded
    ops = dict(reduced["device_ops"])
    sort = sum(s for op, s in ops.items() if op.startswith("sort"))
    assert sort > 0 and out["tick.waitq"] >= sort
    assert out.get("unscoped", 0.0) <= 0.05 * sum(out.values())


def test_in_trace_spans_agree_with_the_mapped_ones(recorded):
    path, host, reqs, _, _ = recorded
    names = ("pack_specs", "simulate_packed", "fold_results")
    offsets = phases.span_offsets(
        host["spans"], phases.read_annotations(path, names),
        reqs[0][0] - host["perf_at_first_ns"])
    for name in names:
        assert offsets[name] is not None, name  # one annotation per span
        assert offsets[name] <= 100_000, (name, offsets[name])


# ------------------------------------- the cell program, compiled for v5e
@pytest.fixture(scope="module")
def v5e_cell_text():
    """The cfg III cell's program at 20,000 files per site, compiled for
    one chip of a described v5e (no chip needed)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from run import grid_program, load_cell, request_seeds, request_specs

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from repro.core.scenarios import pack_specs

    cell = load_cell("cfgIII-1M.steady")
    cell["config"] = dict(cell["config"], n_files=20_000)
    grid = pack_specs(request_specs(cell, request_seeds(0, 0, 2)),
                      tick=float(cell["config"]["tick_s"]))
    program, arrays = grid_program(grid, cached=False)
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                 sharding=one_chip) for a in arrays]
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return program.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def test_v5e_cell_program_places_sort_cumsums_and_fusions(v5e_cell_text):
    text = v5e_cell_text
    scopes = phases.hlo_scopes(text)
    comps, _ = phases.parse_hlo(text)
    body = comps[re.search(r"\bbody=%?([\w.\-]+)", text).group(1)]
    sorts = [i.name for i in body if i.opcode == "sort"]
    assert sorts and all(scopes[n] == "tick.waitq" for n in sorts)
    # XLA's cumsum: reduce-windows with no op_name, one cluster per
    # cumsum; the first op of each covers a whole [lanes, sites x files]
    # plane: the GCS gate's three passes and the two migration ranks
    windows = [i for i in body if i.opcode == "reduce-window"
               and 'op_name="' not in _line(text, i.name)]
    assert all(scopes[i.name] == "tick.migrate" for i in windows)
    heads = [i for i in windows if _elements(i.shape) >= 2 * 2 * 20_000]
    assert len(heads) == 5
    fusions = [i.name for i in body if i.opcode == "fusion"]
    assert fusions and all(scopes[n] != "unscoped" for n in fusions)


def _line(text, name):
    return re.search(r"^\s+(?:ROOT )?%" + re.escape(name) + r" = .*$", text,
                     re.M).group(0)


def _elements(shape):
    n = 1
    for d in re.match(r"\w+\[([\d,]*)\]", shape).group(1).split(","):
        n *= int(d) if d else 1
    return n
