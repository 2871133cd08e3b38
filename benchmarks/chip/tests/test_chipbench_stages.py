"""Prune stages in a profiler trace (``stages.py``): the stage of a device
op from its metadata, program spans among the host events, device time
by stage and idle time by program span, on hand-made traces, on the
recorded chip trace and on a real CPU trace."""

import json
import os
import subprocess
import sys

import pytest

from chipbench_fixtures import CHIP

import stages  # noqa: E402
import tracing  # noqa: E402

DATA = CHIP / "tests" / "data"


@pytest.mark.parametrize("text,stage", [
    ('%fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name='
     '"jit(prune_capture)/prune_capture/dot_general"}', "capture"),
    ("jit(prune_solve)/prune_solve/while/body/cholesky", "solve"),
    ("jit(prune_capture)/prune_capture/jit(_prune_hessian_update)/"
     "prune_hessian/dot_general", "hessian"),
    ("jit(prune_propagate)/prune_propagate/add", "propagate"),
    ("jit(reshape)/reshape", None),
    ("jit(prune_capture)/mul", None),
    ("jit_prune_capture", None),
    ("jit(f)/prune_captures/mul", None),
])
def test_stage_of_long_name(text, stage):
    assert stages.stage_of("%fusion.3", [("long_name", text),
                                         ("flops", 12)]) == stage


def test_stage_of_reads_any_string_stat_and_the_name():
    assert stages.stage_of("copy.1", [("tf_op", "x"), (
        "op_name", "jit(prune_solve)/prune_solve/transpose")]) == "solve"
    assert stages.stage_of("jit(a)/prune_propagate/x", []) == "propagate"
    assert stages.stage_of("copy.1", []) is None


@pytest.mark.parametrize("module,stage", [
    ("jit_prune_capture(9906625609008023338)", "capture"),
    ("jit__prune_hessian_update(12)", "hessian"),
    ("jit__prune_hessian_update_weighted", "hessian"),
    ("jit__prune_hessian_merge(3)", "hessian"),
    ("jit_prune_solve(7)", "solve"),
    ("jit_prune_solve_rows", "solve"),
    ("jit_prune_propagate(1)", "propagate"),
    ("jit_prune_captures(1)", None),
    ("jit_reshape(15340820097382558083)", None),
    ("jit_solve_linear(2)", None),
])
def test_module_stage(module, stage):
    assert stages.module_stage(module) == stage


def test_attribute_fills_the_stage_from_the_module():
    ops = [("fusion.1", 10, 20, None, None),        # in jit_prune_solve
           ("copy.2", 20, 25, None, None),          # in jit_prune_solve
           ("fusion.3", 30, 40, None, "hessian"),   # its scope decides
           ("reshape.4", 50, 55, None, None),       # an eager op
           ("fusion.5", 70, 80, None, None)]        # in no module run
    runs = [("jit_reshape(4)", 50, 55), ("jit_prune_solve(1)", 10, 25),
            ("jit_prune_capture(2)", 30, 40)]
    assert [op[4] for op in stages.attribute(ops, runs)] == [
        "solve", "solve", "hessian", None, None]
    assert [op[:4] for op in stages.attribute(ops, runs)] == \
        [op[:4] for op in ops]


def test_program_span_labels():
    assert stages.program_span(
        "prune_capture", [("track", "prune"), ("segment", "period0")]) \
        == "prune_capture[segment=period0]"
    assert stages.program_span("prune_job", [("track", "prune")]) \
        == "prune_job"
    assert stages.program_span("PjitFunction(f)", []) is None
    assert stages.program_span("x", [("segment", "s")]) is None


def _hand_made():
    """One chip, window [0, 100): a solve while-loop (no stage of its
    own) holding two staged ops and an unattributed copy; a capture op;
    an eager op outside every program; idle gaps under program spans."""
    dev = {"/device:TPU:0": [
        ("capture.1", 0, 10, None, "capture"),
        ("while.2", 20, 50, None, None),
        ("fusion.3", 20, 30, None, "solve"),
        ("copy.4", 30, 35, None, None),
        ("custom-call.5", 35, 50, None, "solve"),
        ("reshape.6", 60, 70, None, None),
        ("fusion.7", 80, 90, None, "propagate"),
    ]}
    host = [
        (tracing.WINDOW_SPAN, 0, 100, "t", None),
        ("prune_job", 0, 100, "t", "prune_job"),
        ("prune_capture", 0, 18, "t", "prune_capture[segment=s0]"),
        ("_lower_sharding_computation", 11, 19, "t", None),
        ("prune_propagate", 72, 95, "t", "prune_propagate[segment=s0]"),
        ("prune_drain", 92, 100, "t", "prune_drain"),
    ]
    return dev, host


def test_stage_split_sums_to_busy():
    dev, host = _hand_made()
    r = stages.reduce_events(dev, host)
    st = r["stage_s"]
    assert st["capture"] == pytest.approx(10e-9)
    # the while loop's own time goes to its innermost staged op; the
    # copy inside it names no stage
    assert st["solve"] == pytest.approx(25e-9)
    assert st["unattributed"] == pytest.approx(15e-9)
    assert st["propagate"] == pytest.approx(10e-9)
    assert st["hessian"] == 0.0
    assert sum(st.values()) == pytest.approx(r["busy_s"], rel=1e-12)
    assert r["busy_s"] == pytest.approx(60e-9)
    assert r["stage_ops"]["solve"] == [["custom-call", pytest.approx(15e-9)],
                                       ["fusion", pytest.approx(10e-9)]]
    assert r["stage_ops"]["unattributed"] == [
        ["reshape", pytest.approx(10e-9)], ["copy", pytest.approx(5e-9)]]


def test_idle_by_span_takes_the_innermost_program_span():
    dev, host = _hand_made()
    r = stages.reduce_events(dev, host)
    # gaps: [10,20) mid 15 under capture (the lowering event is no
    # program span), [50,60) under the job alone, [70,80) under
    # propagate, [90,100) mid 95 under propagate and drain: the drain is
    # shorter
    assert r["idle_by_span"] == {
        "prune_job": pytest.approx(10e-9),
        "prune_capture[segment=s0]": pytest.approx(10e-9),
        "prune_propagate[segment=s0]": pytest.approx(10e-9),
        "prune_drain": pytest.approx(10e-9)}
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    # the old reduction still names the innermost host event of all
    assert r["breakdown"]["idle_gaps"][0] == [
        "t: _lower_sharding_computation", pytest.approx(10e-9)]


def test_gap_outside_every_program_span_is_none():
    dev = {"/device:TPU:0": [("a", 0, 10, None, None),
                             ("b", 20, 30, None, None)]}
    r = stages.reduce_events(dev, [("host", 0, 30, "t", None)])
    assert r["idle_by_span"] == {"none": pytest.approx(10e-9)}
    assert r["stage_s"]["unattributed"] == pytest.approx(r["busy_s"])


def test_unattributed_time_by_module():
    dev, host = _hand_made()
    mods = {"/device:TPU:0": [("jit_solve_linear", 20, 50),
                              ("jit_reshape", 60, 70)]}
    r = stages.reduce_events(dev, host, mods=mods)
    assert r["unattributed_modules"] == [
        ["jit_reshape", pytest.approx(10e-9)],
        ["jit_solve_linear", pytest.approx(5e-9)]]


def test_recorded_trace_keeps_the_old_keys():
    """On the recorded chip trace (ops without stage metadata) the old
    keys come out exactly as ``tracing.reduce_events`` gives them."""
    rec = json.loads((DATA / "trace_events.json").read_text())
    dev = {k: [(n, s, e, tracing.kernel_name(n, [])) for n, s, e in v]
           for k, v in rec["device"].items()}
    host = [tuple(e) for e in rec["host"]]
    old = tracing.reduce_events(dev, host)
    new = stages.reduce_events(dev, host)
    assert {k: new[k] for k in old} == old
    assert sum(new["stage_s"].values()) == pytest.approx(new["busy_s"],
                                                         rel=1e-9)
    assert set(new["idle_by_span"]) == {"none"}


def test_load_finds_program_spans_in_a_real_trace(tmp_path):
    import jax

    from repro.obs import Tracer
    tr = Tracer(enabled=False)
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("prune_capture", track="prune",
                     args={"segment": "period0"}):
            jax.block_until_ready(jax.numpy.ones(8) + 1)
    path, = tmp_path.glob("**/*.xplane.pb")
    _, host, _ = stages.load(str(path))
    labels = [h[4] for h in host if h[4] is not None]
    assert labels == ["prune_capture[segment=period0]"]


def test_stage_profile_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/stage_profile.py", "--seeds", "1"],
        cwd=CHIP.parents[1], env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 2 and "no TPU" in out.stderr
    assert out.stdout == ""
